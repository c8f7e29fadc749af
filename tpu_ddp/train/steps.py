"""Jitted SPMD train / eval steps.

The heart of the port (SURVEY.md §3.3): the reference's

    forward -> CE loss -> zero_grad -> backward[NCCL allreduce via DDP hooks]
    -> optimizer.step()                      (main.py:34-39)

becomes ONE compiled function per mesh:

    loss = lax.pmean(shard_loss, 'data')      # <- where NCCL sat: AD of this
    grads = value_and_grad(loss)(params, ...) #    pmean IS the grad allreduce
    params = optax.apply_updates(...)

run under ``jax.shard_map`` so per-device semantics match DDP exactly:
each device computes loss/grads on ITS shard with ITS batch-norm statistics
(the reference has no SyncBatchNorm — BN normalizes per replica), and only
gradients (and running stats, see note) cross the interconnect. The pmean
before AD is the all-reduce: it stands where DDP's C++ bucketing Reducer
did (SURVEY.md §2.6); what it costs on the chip is PERF.md's to say.

BN running stats: per-replica stats physically diverge across DDP ranks in
the reference and rank 0's are the ones checkpointed (``main.py:45``). With a
replicated TrainState we instead pmean the fresh stats each step — eval-time
only difference, strictly less arbitrary than "whatever rank 0 saw".
``sync_bn=True`` (build the model with ``bn_cross_replica_axis='data'``)
additionally normalizes over the global batch (the SyncBatchNorm upgrade the
reference lacks).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_ddp.health.stats import HealthConfig, guard_step, health_stats
from tpu_ddp.parallel.mesh import DATA_AXIS
from tpu_ddp.telemetry.phases import (
    FORWARD_BACKWARD_SCOPE,
    FORWARD_SCOPE,
    GRAD_ACCUM_SCOPE,
    GRAD_COMPRESS_SCOPE,
    HEALTH_SCOPE,
    INPUT_SCOPE,
    LOSS_SCOPE,
    METRICS_SCOPE,
    OPTIMIZER_SCOPE,
    STATS_SYNC_SCOPE,
)
from tpu_ddp.train.losses import (
    combine_aux_loss,
    cross_entropy_loss,
    masked_accuracy,
)
from tpu_ddp.train.optim import apply_optimizer
from tpu_ddp.train.state import TrainState
from tpu_ddp.train.tasks import IMAGE_CLASSIFICATION, Task

# Where the DDP gradient sync lives: make_train_step pmeans the per-shard
# loss BEFORE differentiation — AD's transpose of the replicated-params
# pbroadcast IS the cross-shard psum (shard_map's check_vma rewrite).


def resolve_remat(model, remat: bool):
    """(possibly-cloned model, need_whole_forward_checkpoint).

    Families with a ``remat`` field (ViT/MoEViT) rematerialize PER BLOCK —
    the granularity that actually reduces peak HBM (only block-boundary
    activations are stored; measured in tools/memplan.py). Families
    without it fall back to one whole-forward ``jax.checkpoint``, which
    keeps the semantics but barely moves peak (the recompute materializes
    everything at once) — callers apply that wrap themselves so the
    closure structure stays local."""
    if remat and hasattr(model, "remat"):
        return model.clone(remat=True), False
    return model, remat

Batch = dict

#: the collections a train step lets the model write: batch statistics,
#: auxiliary losses, and ``counters`` (what a layer counted this step: the
#: load of each held expert, ``models/moe.py``), which ride out of the step
#: with its metrics and are fetched where the losses are
COUNTERS = "counters"
MUTABLE = ("batch_stats", "aux_loss", COUNTERS)


def sum_counters(counters, data_axis: str):
    """What the model sowed into ``counters``, one array per name (a name
    sown by several layers is stacked in layer order), summed over the
    shards."""
    by_name = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(counters):
        name = next(k.key for k in reversed(path) if hasattr(k, "key"))
        by_name.setdefault(name, []).append(leaf)
    return {name: lax.psum(jnp.stack(leaves), data_axis)
            for name, leaves in by_name.items()}


def state_specs_for(zero1, compress, data_axis: str = DATA_AXIS):
    """shard_map in/out specs for the TrainState under the optional state
    layouts: ZeRO-1 scatters the optimizer state (``zero1.state_specs``),
    and --grad-compress error feedback adds the per-device residual
    (``TrainState.grad_residual``, leading axis over ``data``). Plain
    replicated state stays the bare ``P()`` prefix so those builds trace
    byte-identical to before either feature existed."""
    ef = compress is not None and compress.config.error_feedback
    if zero1 is None and not ef:
        return P()
    base = (zero1.state_specs() if zero1 is not None
            else TrainState(step=P(), params=P(), batch_stats=P(),
                            opt_state=P()))
    if ef:
        base = base.replace(grad_residual=P(data_axis))
    return base


def _bind_compressor(zero1, compress):
    """ZeRO-1 + compression compose by the partition delegating its
    reduce-scatter to the compressor's ring — make sure the two agree on
    ONE compressor object (idempotent; trainer/strategy normally attach
    it at construction, tests may pass both separately)."""
    if zero1 is not None and compress is not None:
        if zero1.compress is None:
            zero1.set_compression(compress)
        elif zero1.compress is not compress:
            raise ValueError(
                "zero1 partition already carries a different GradCompressor"
            )


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    accum_steps: int = 1,
    steps_per_call: int = 1,
    data_axis: str = DATA_AXIS,
    loss_fn: Callable = cross_entropy_loss,
    donate: bool = True,
    compute_accuracy: bool = True,
    remat: bool = False,
    augment: bool = False,
    augment_seed: int = 0,
    mixup_alpha: float = 0.0,
    aux_weight: float = 0.01,
    health: Optional[HealthConfig] = None,
    zero1=None,
    compress=None,
    task: Task = IMAGE_CLASSIFICATION,
) -> Callable[[TrainState, Batch], tuple]:
    """Build the compiled DDP train step for `mesh`: forward, the loss
    whose pmean is the gradient all-reduce, one optax update.

    Returns step(state, batch) -> (state, metrics) where batch is a global
    {image, label, mask} dict sharded on its leading axis over `data_axis`.
    ``compute_accuracy=False`` for losses whose labels aren't class indices
    (e.g. multi-hot BCE targets). ``remat=True`` rematerializes the forward
    during backward (jax.checkpoint) — trades FLOPs for HBM on deep models.
    ``augment=True`` applies on-device random crop+flip to the shard's images
    (keyed by step and shard index — reproducible across resume, distinct
    per device; the recipe extension the reference lacks, SURVEY.md §7.3).

    ``task`` (``train/tasks.py``) says which array of the batch the model
    reads and which loss it takes: an image classifier's ``loss_fn`` over
    ``label``, or a decoder's masked next-token loss over its own
    ``tokens``. A task with ``prepare`` has its batch made ready here, in
    the step, from a key folded from ``augment_seed``, ``state.step`` and
    the shard as ``augment``'s is (block diffusion's noise: new every step,
    the same for the same three). Everything else in the step is the same
    step.

    ``accum_steps`` > 1 makes the ONE optimizer step over a global batch
    too large to activate at once: each shard splits its rows into
    ``accum_steps`` microbatches and accumulates their gradients with
    ``lax.scan`` (activations for only one microbatch live at a time — the
    trade the reference cannot express; its global batch is rigidly
    per-process-batch × world size, ``main.py:61``), then everything after
    the gradients runs once, on their average: one statistics sync, one
    reduce-scatter or compressed ring, one update. With equal real counts
    per microbatch the accumulated gradient equals the full-batch gradient
    exactly (each microbatch's pmean-before-AD sync is preserved; the mean
    over microbatches commutes with AD). With masked/unequal microbatches
    the average weights microbatches equally — same approximation class as
    every accumulation implementation. BatchNorm stats chain through the
    scan (each microbatch normalizes by its own statistics, as the
    reference's per-replica BN does per step). Per-shard rows must divide
    by ``accum_steps``; what the model counted is summed over the
    microbatches.

    ``steps_per_call`` = K > 1 fuses K optimizer steps into ONE dispatch
    via ``lax.scan``. The reference pays Python-interpreter + launcher
    overhead every batch (the ``main.py:32-41`` hot loop crosses the host
    boundary per step). Here the host stacks K global batches on a new
    leading axis: every array in ``batch`` has shape (K, global_batch, ...)
    sharded over ``data_axis`` on axis 1, and every metric leaf gains a
    leading (K,) axis (per-step losses, in order — the trainer logs them
    exactly as if stepped one by one). Under ``zero1`` the scattered
    optimizer state rides the scan carry UNGATHERED: the K inner steps each
    reduce-scatter fresh grads, update their shard, and all-gather only the
    params — the shard state never re-replicates inside the fused dispatch.
    Under ``compress`` the error-feedback residual likewise rides the carry.

    ``compress`` (a ``tpu_ddp.parallel.compression.GradCompressor``)
    swaps the gradient sync's wire format: without zero1 the pmean
    becomes a block-scaled quantized ring all-reduce (f32 accumulation
    on-device, int8/bf16 payloads on the wire — ~4x/2x fewer gradient
    bytes per hop); with zero1 the partition's reduce-scatter runs the
    same quantized ring. Error feedback, when configured, carries each
    device's quantization error in ``state.grad_residual`` and adds it
    back next step.

    ``zero1`` (a ``tpu_ddp.parallel.zero.Zero1Partition``) swaps the
    replicated update for ZeRO-1 weight-update sharding: the grad pmean
    becomes a reduce-scatter, the optimizer touches only this shard's 1/N
    slice of params + optimizer state (opt state enters/leaves the step
    scattered over the data axis), and the updated params are all-gathered
    back to replicated — mathematically identical, 1/N the optimizer HBM
    and update FLOPs (parallel/zero.py).

    ``health`` compiles the numerics flight recorder into the step (see
    ``tpu_ddp.health.stats``): a ``metrics["health"]`` dict of global
    norms + finite-ness sentinels computed on the already-synchronized
    gradients/updates (under accumulation: the average the optimizer
    consumed), and (``skip_nonfinite``) the in-graph guard that keeps the
    old params/batch_stats/opt_state when the update is poisoned.
    ``health=None`` (default) leaves the traced step byte-identical to a
    build without the feature.

    Models that sow auxiliary losses into the ``aux_loss`` collection (the
    MoE router's load-balance term, ``models.moe.MoEMlp``) get them added to
    the differentiated loss with weight ``aux_weight`` — so a routed-MoE
    model picked from the zoo trains correctly through this generic step,
    not only through ``make_ep_train_step``. Reported ``loss`` stays the
    task loss; the aux term appears as its own metric when present."""
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if accum_steps > 1 and (augment or mixup_alpha > 0):
        raise ValueError(
            "--augment/--mixup-alpha are not yet supported with "
            "--grad-accum-steps"
        )
    model, remat = resolve_remat(model, remat)
    _bind_compressor(zero1, compress)
    want_accuracy = compute_accuracy and task.accuracy

    def apply_model(params, batch_stats, images):
        return model.apply(
            {"params": params, "batch_stats": batch_stats},
            images,
            train=True,
            mutable=list(MUTABLE),
        )

    if remat:
        apply_model = jax.checkpoint(apply_model)

    def compute_loss(params, batch_stats, batch):
        logits, mutated = apply_model(params, batch_stats,
                                      batch[task.input_key])
        with jax.named_scope(LOSS_SCOPE):
            task_loss, terms = task.loss(loss_fn, logits, batch)
            if mixup_alpha > 0:
                # hard-label mixup: blend the two CE terms by the same
                # lambda the images were blended with
                # (data/augment.py::mixup)
                task_loss = (batch["_mix_lam"] * task_loss
                             + (1.0 - batch["_mix_lam"])
                             * loss_fn(logits, batch["_mix_label"],
                                       batch.get("mask")))
            loss, aux = combine_aux_loss(task_loss, mutated, aux_weight)
        # Gradient sync lives HERE: pmean-ing the per-shard
        # loss before differentiation makes reverse-mode AD produce the
        # globally *averaged* gradient — the pmean's transpose scatters
        # cotangent 1/num_shards to every shard, and differentiating w.r.t.
        # replicated (unvarying) params inserts the cross-shard psum
        # automatically under shard_map. Net effect: grads == grad of the
        # global mean loss, the exact semantics of DDP's NCCL allreduce-mean
        # (main.py:63). (An explicit post-hoc pmean on grads would then
        # DOUBLE-count: AD has already summed.)
        # Under zero1 the sync is the reduce-scatter in sharded_update, so
        # the loss must stay LOCAL (AD differentiates w.r.t. pcast-varying
        # params instead — zero1.varying below).
        # Under --grad-compress the sync is the quantized ring, which AD
        # cannot own either — same local-loss convention. Both run AFTER
        # any accumulation: one collective per optimizer step.
        if zero1 is None and compress is None:
            loss = lax.pmean(loss, data_axis)
        return loss, (mutated.get("batch_stats", batch_stats), logits,
                      task_loss, aux, mutated.get(COUNTERS), terms)

    def accumulate(grad_fn, p_in, batch_stats, batch):
        """The gradient stage over ``accum_steps`` microbatches: what one
        ``grad_fn`` call gives, averaged (the counters and the accuracy's
        ``(correct, count)`` summed), with the statistics chained."""
        b = batch[task.input_key].shape[0]
        if b % accum_steps:
            raise ValueError(
                f"per-shard batch {b} not divisible by accum_steps "
                f"{accum_steps}"
            )
        micros = jax.tree.map(
            lambda x: x.reshape((accum_steps, b // accum_steps) + x.shape[1:]),
            batch,
        )

        def accum(carry, micro):
            grads_acc, stats = carry
            with jax.named_scope(FORWARD_BACKWARD_SCOPE):
                (_, (stats, logits, task_loss, aux, counted, terms)), grads = (
                    grad_fn(p_in, stats, micro))
            with jax.named_scope(GRAD_ACCUM_SCOPE):
                grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
            hits = None
            if want_accuracy:
                with jax.named_scope(METRICS_SCOPE):
                    hits = masked_accuracy(
                        logits, micro[task.target_key], micro.get("mask"))
            # one row a microbatch of what is not carried
            return (grads_acc, stats), (task_loss, aux, counted, hits, terms)

        # The accumulator takes the differentiation input's shapes (under
        # zero3 state.params are flat shards) AND its varying type: under
        # zero1/compress the grads are LOCAL, and zeros_like keeps p_in's
        # varying-over-data marking so the scan carry types match. The
        # fresh BN stats are computed from shard-local data, so the
        # replicated incoming stats are cast to match.
        zero_grads = jax.tree.map(jnp.zeros_like, p_in)
        stats0 = jax.tree.map(
            lambda s: lax.pcast(s, (data_axis,), to="varying"), batch_stats)
        (grads_acc, new_stats), rows = lax.scan(
            accum, (zero_grads, stats0), micros)
        with jax.named_scope(GRAD_ACCUM_SCOPE):
            grads = jax.tree.map(lambda g: g / accum_steps, grads_acc)
            loss_sum, aux_sum, counters, hits, term_sums = jax.tree.map(
                lambda x: x.sum(axis=0), rows)
            aux = None if aux_sum is None else aux_sum / accum_steps
            terms = {k: v / accum_steps for k, v in term_sums.items()}
        return (grads, new_stats, loss_sum / accum_steps, aux, counters, hits,
                terms)

    def shard_step(state: TrainState, batch: Batch):
        # Every part of the step sits in a scope of telemetry/phases.py:
        # jax writes the scope path into each operation's metadata, the
        # Trainer's program map reads it back from the compiled program,
        # and a device trace is split by phase and module from that map.
        with jax.named_scope(INPUT_SCOPE):
            if augment or mixup_alpha > 0 or task.prepare is not None:
                key = jax.random.fold_in(
                    jax.random.key(augment_seed), state.step)
                key = jax.random.fold_in(key, lax.axis_index(data_axis))
            if task.prepare is not None:
                # the task's own draw (block diffusion's noise), a stream
                # apart from crop/flip's and mixup's
                batch = task.prepare(jax.random.fold_in(key, 2), batch)
            if augment:
                from tpu_ddp.data.augment import random_crop_flip

                batch = dict(
                    batch, image=random_crop_flip(key, batch["image"]))
            if mixup_alpha > 0:
                from tpu_ddp.data.augment import mixup

                # distinct stream from crop/flip (same key would correlate
                # them)
                mixed, perm, lam = mixup(
                    jax.random.fold_in(key, 1), batch["image"],
                    alpha=mixup_alpha, valid=batch.get("mask"),
                )
                batch = dict(batch, image=mixed,
                             _mix_label=batch["label"][perm], _mix_lam=lam)
        grad_fn = jax.value_and_grad(compute_loss, has_aux=True)
        if zero1 is not None:
            if getattr(zero1, "scattered_params", False):
                # ZeRO-3: params enter the step as flat 1/N shards; the
                # differentiation input is re-assembled block by block on
                # the double-buffered prefetch schedule (block k+1's
                # all-gather rides under block k's compute —
                # parallel/zero.py::Zero3Partition.stream_params). The
                # gather sits OUTSIDE the grad closure (and outside the
                # accumulation scan: once a step), so the backward
                # is re-gather-free: grads come out full-shaped and LOCAL
                # (the gathered values are varying), exactly what the
                # reduce-scatter below consumes.
                p_in = zero1.stream_params(state.params)
            else:
                p_in = zero1.varying(state.params)
        elif compress is not None:
            p_in = compress.varying(state.params)
        else:
            p_in = state.params
        if accum_steps == 1:
            with jax.named_scope(FORWARD_BACKWARD_SCOPE):
                (_, (new_stats, logits, task_loss, aux, counters,
                     terms)), grads = grad_fn(p_in, state.batch_stats, batch)
            hits = None  # read from the logits where the metrics are made
        else:
            (grads, new_stats, task_loss, aux, counters, hits,
             terms) = accumulate(grad_fn, p_in, state.batch_stats, batch)
        with jax.named_scope(STATS_SYNC_SCOPE):
            new_stats = jax.tree.map(
                lambda s: lax.pmean(s, data_axis), new_stats)
        # error feedback reads/writes state.grad_residual; the error is
        # also computed (without being carried) whenever health wants the
        # compression-drift stat
        ef = compress is not None and compress.config.error_feedback
        want_err = compress is not None and (ef or health is not None)
        residual = state.grad_residual if ef else None
        err_state = None
        if zero1 is not None:
            # ZeRO-1: reduce-scatter IS the gradient sync; the optimizer
            # consumes only this shard's slice of grads/params/opt state
            # and the updated params come back via one all-gather.
            with jax.named_scope(OPTIMIZER_SCOPE):
                new_params, new_opt_state, gshards, ushards, err_state = (
                    zero1.sharded_update(
                        grads, state.params, state.opt_state,
                        residual=residual, with_error=want_err,
                    )
                )
        else:
            if compress is not None:
                # the quantized ring replaces the pmean (the loss stayed
                # local above)
                with jax.named_scope(GRAD_COMPRESS_SCOPE):
                    grads, err_state = compress.all_reduce_mean(
                        grads, residual, with_error=want_err)
            with jax.named_scope(OPTIMIZER_SCOPE):
                new_params, updates, new_opt_state = apply_optimizer(
                    tx, grads, state.opt_state, state.params)
        new_residual = err_state if ef else state.grad_residual
        if health is not None:
            with jax.named_scope(HEALTH_SCOPE):
                # grads/updates are the synchronized values in EVERY sync
                # mode (AD-of-pmean'd-loss, the dequantized ring output, or
                # the zero1 shards whose shard-local norms are psum'd over
                # data), so every shard computes identical global stats
                # in-graph.
                err_sq = (compress.error_sq(err_state)
                          if want_err else None)
                if zero1 is not None:
                    hstats = zero1.health_stats(
                        loss=lax.pmean(task_loss, data_axis),
                        grad_shards=gshards,
                        params=state.params, update_shards=ushards,
                        per_layer=health.per_layer, compress_error_sq=err_sq,
                    )
                else:
                    hstats = health_stats(
                        loss=lax.pmean(task_loss, data_axis), grads=grads,
                        params=state.params, updates=updates,
                        per_layer=health.per_layer, compress_error_sq=err_sq,
                    )
                (new_params, new_stats, new_opt_state,
                 new_residual) = guard_step(
                    health, hstats,
                    (new_params, new_stats, new_opt_state, new_residual),
                    (state.params, state.batch_stats, state.opt_state,
                     state.grad_residual),
                )
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
            grad_residual=new_residual,
        )
        with jax.named_scope(METRICS_SCOPE):
            metrics = {"loss": lax.pmean(task_loss, data_axis)}
            for name, term in terms.items():  # a loss of several terms
                metrics[name] = lax.pmean(term, data_axis)
            if health is not None:
                metrics["health"] = hstats
            if aux is not None:
                metrics["aux_loss"] = lax.pmean(aux, data_axis)
            if counters:
                metrics[COUNTERS] = sum_counters(counters, data_axis)
            if want_accuracy:
                if hits is None:
                    hits = masked_accuracy(
                        logits, batch[task.target_key], batch.get("mask"))
                correct, count = hits
                metrics["accuracy"] = (
                    lax.psum(correct, data_axis)
                    / jnp.maximum(lax.psum(count, data_axis), 1.0))
        return new_state, metrics

    def shard_multi(state: TrainState, batches: Batch):
        return lax.scan(shard_step, state, batches, length=steps_per_call)

    fused = steps_per_call > 1
    state_specs = state_specs_for(zero1, compress, data_axis)
    sharded = jax.shard_map(
        shard_multi if fused else shard_step,
        mesh=mesh,
        in_specs=(state_specs,
                  P(None, data_axis) if fused else P(data_axis)),
        out_specs=(state_specs, P()),
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_eval_step(
    model,
    mesh: Mesh,
    *,
    data_axis: str = DATA_AXIS,
    loss_fn: Callable = cross_entropy_loss,
    compute_accuracy: bool = True,
    task: Task = IMAGE_CLASSIFICATION,
) -> Callable[[TrainState, Batch], dict]:
    """Compiled eval step: running-stats BN, summed correct/count/loss over
    the mesh. The eval loop the reference's runnable path never had
    (SURVEY.md §6). ``count`` counts rows (examples), whatever the task's
    loss averages over."""

    def shard_eval(state: TrainState, batch: Batch):
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        if task.prepare is not None:
            # one fixed draw a shard, so that two evaluations compare
            with jax.named_scope(INPUT_SCOPE):
                batch = task.prepare(jax.random.fold_in(
                    jax.random.key(0), lax.axis_index(data_axis)), batch)
        with jax.named_scope(FORWARD_SCOPE):
            logits = model.apply(variables, batch[task.input_key],
                                 train=False)
            mask = batch.get("mask")
            with jax.named_scope(LOSS_SCOPE):
                loss, _ = task.loss(loss_fn, logits, batch)
        with jax.named_scope(METRICS_SCOPE):
            shard_count = (
                mask.astype(jnp.float32).sum()
                if mask is not None
                else jnp.asarray(float(logits.shape[0]))
            )
            if compute_accuracy and task.accuracy:
                correct, _ = masked_accuracy(
                    logits, batch[task.target_key], mask)
            else:
                correct = jnp.zeros(())
            return {
                "correct": lax.psum(correct, data_axis),
                "count": lax.psum(shard_count, data_axis),
                # EXACT sum of per-sample losses: the per-shard
                # (masked-mean) loss re-weighted by ITS OWN real count
                # before the psum — with drop_last=False padding, shards
                # hold different real counts, so a pmean over shard means
                # would mis-weight exactly the way the reference's val loop
                # mis-measured (ppe_main_ddp.py:160-166).
                "loss_sum": lax.psum(loss * shard_count, data_axis),
            }

    sharded = jax.shard_map(
        shard_eval,
        mesh=mesh,
        in_specs=(P(), P(data_axis)),
        out_specs=P(),
    )
    return jax.jit(sharded)


def make_predict_step(
    model,
    mesh: Mesh,
    *,
    data_axis: str = DATA_AXIS,
    task: Task = IMAGE_CLASSIFICATION,
):
    """Compiled batch-inference step: sharded forward, logits returned in the
    batch's global order. Covers the reference's batch-inference capability
    (``ppe_main_ddp.py:310-396`` runs a loaded model over a test loader and
    dumps predictions)."""

    def shard_predict(state: TrainState, batch: Batch):
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        with jax.named_scope(FORWARD_SCOPE):
            return model.apply(variables, batch[task.input_key],
                               train=False)

    sharded = jax.shard_map(
        shard_predict,
        mesh=mesh,
        in_specs=(P(), P(data_axis)),
        out_specs=P(data_axis),
    )
    return jax.jit(sharded)


def make_auto_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    data_axis: str = DATA_AXIS,
    loss_fn: Callable = cross_entropy_loss,
):
    """Alternative "auto-SPMD" step: plain jit + NamedSharding constraints,
    letting the XLA partitioner place the all-reduce (GSPMD). BatchNorm then
    normalizes over the GLOBAL batch (implicit SyncBN). Kept as the idiomatic
    single-annotation formulation; the shard_map step above is the faithful-
    DDP-semantics flagship."""
    from jax.sharding import NamedSharding

    batch_sharding = NamedSharding(mesh, P(data_axis))
    replicated = NamedSharding(mesh, P())

    def compute_loss(params, batch_stats, batch):
        variables = {"params": params, "batch_stats": batch_stats}
        logits, mutated = model.apply(
            variables, batch["image"], train=True, mutable=["batch_stats"]
        )
        with jax.named_scope(LOSS_SCOPE):
            loss = loss_fn(logits, batch["label"], batch.get("mask"))
        return loss, mutated["batch_stats"]

    @functools.partial(
        jax.jit,
        in_shardings=(replicated, batch_sharding),
        out_shardings=(replicated, replicated),
        donate_argnums=(0,),
    )
    def step(state: TrainState, batch: Batch):
        with jax.named_scope(FORWARD_BACKWARD_SCOPE):
            (loss, new_stats), grads = jax.value_and_grad(
                compute_loss, has_aux=True
            )(state.params, state.batch_stats, batch)
        with jax.named_scope(OPTIMIZER_SCOPE):
            new_params, updates, new_opt_state = apply_optimizer(
                tx, grads, state.opt_state, state.params)
        return (
            state.replace(
                step=state.step + 1,
                params=new_params,
                batch_stats=new_stats,
                opt_state=new_opt_state,
            ),
            {"loss": loss},
        )

    return step
