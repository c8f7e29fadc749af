"""Pipeline parallelism (GPipe-style) over the ``pipeline`` mesh axis.

Absent from the reference (SURVEY.md §2.3: "no stage splitting, no
microbatching"); built TPU-first: the transformer trunk is split into S
stages of ``depth/S`` blocks, each stage's block parameters live on one ring
position of the ``pipeline`` axis, and microbatch activations rotate through
the ring with ``lax.ppermute`` inside a ``lax.scan`` — the classic
S + M - 1-tick schedule, fully compiled (no Python per-tick control flow, no
per-stage processes; XLA overlaps the ppermute with the next tick's compute).

Composes with data parallelism on a 2-D ``data x pipeline`` mesh: the batch
is sharded over ``data``, stages over ``pipeline``, and gradient averaging
over ``data`` falls out of shard_map's unvarying-input transpose exactly as
in the DDP step (tpu_ddp.train.steps).

Design notes (how the grads stay correct without a hand-written backward):
  * stage-0 ingestion is ``where(stage == 0, fresh_embed, carried)`` — the
    embed params' cotangent is nonzero only on stage 0, and shard_map's
    psum-over-pipeline for unvarying params turns that into THE embed grad;
  * the head runs on every stage but the loss reads logits through
    ``psum(where(stage == S-1, logits, 0))`` — only the last stage's head
    application carries gradient, so the psum'd head grad is the single
    correct contribution (no double counting);
  * per-stage block params are *varying* over the pipeline axis, so their
    grads stay local to their stage — no collective at all.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_ddp.health.stats import (
    HealthConfig,
    assemble_stats,
    guard_step,
    per_layer_sq,
    tree_nonfinite,
    tree_sq,
)
from tpu_ddp.models.vit import TransformerBlock
from tpu_ddp.parallel.mesh import DATA_AXIS, PIPELINE_AXIS
from tpu_ddp.telemetry.phases import (
    FORWARD_BACKWARD_SCOPE,
    GRAD_SYNC_SCOPE,
    METRICS_SCOPE,
    OPTIMIZER_SCOPE,
)
from tpu_ddp.train.losses import cross_entropy_loss, masked_accuracy
from tpu_ddp.train.state import TrainState


def to_pipeline_params(params: dict, depth: int) -> dict:
    """Plain ViT params -> pipeline layout: the ``block_i`` subtrees (all
    structurally identical) stack into one ``blocks`` tree with a leading
    stage-major depth axis; everything else passes through. Inverse:
    ``from_pipeline_params`` — so plain checkpoints load into the pipeline
    layout and back."""
    blocks = [params[f"block_{i}"] for i in range(depth)]
    rest = {k: v for k, v in params.items() if not k.startswith("block_")}
    rest["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    return rest


def from_pipeline_params(pp_params: dict, depth: int) -> dict:
    out = {k: v for k, v in pp_params.items() if k != "blocks"}
    for i in range(depth):
        out[f"block_{i}"] = jax.tree.map(lambda x, i=i: x[i], pp_params["blocks"])
    return out


def _vit_pieces(model):
    """(embed, apply_stage, apply_head) closures over a ViT's hyperparams —
    the per-stage building blocks shared by the GPipe and 1F1B schedules
    (one implementation, so the two schedules can only differ in ORDER,
    never in math)."""
    cfg = dict(dtype=model.dtype)
    patch = nn.Conv(
        model.hidden_dim,
        kernel_size=(model.patch_size, model.patch_size),
        strides=(model.patch_size, model.patch_size),
        **cfg,
    )
    block = TransformerBlock(
        model.num_heads,
        mlp_ratio=model.mlp_ratio,
        attention_impl=model.attention_impl,
        **cfg,
    )
    ln_f = nn.LayerNorm(**cfg)
    head = nn.Dense(model.num_classes, **cfg)

    def embed(params, images):  # (mb, H, W, C) -> (mb, T, hidden)
        x = patch.apply({"params": params["patch_embed"]}, images)
        x = x.reshape(x.shape[0], -1, model.hidden_dim)
        return x + params["pos_embed"].astype(x.dtype)

    def apply_stage(stage_blocks, x):
        def body(x, p):
            return block.apply({"params": p}, x), None

        x, _ = lax.scan(body, x, stage_blocks)
        return x

    def apply_head(params, x):  # (mb, T, hidden) -> (mb, classes)
        x = ln_f.apply({"params": params["ln_f"]}, x)
        x = x.mean(axis=1)
        return head.apply({"params": params["head"]}, x).astype(jnp.float32)

    return embed, apply_stage, apply_head


def _pp_health_stats(health, *, loss, grads, params, updates, pipe_axis):
    """Flight-recorder stats for the pipeline layout (same schema as every
    other step builder — see ``tpu_ddp.health.stats``). The stacked
    ``blocks`` trees are VARYING over the pipeline axis (each stage holds
    its own chunk), so their sums-of-squares / non-finite counts are
    psum'd over the ring before joining the replicated embed/head
    contributions — every stage then reports the identical global
    numbers. Per-layer entries for the stacked blocks are reduced the
    same way and prefixed ``blocks/``."""

    def split(tree):
        return tree["blocks"], {k: v for k, v in tree.items()
                                if k != "blocks"}

    def reduced(tree, fn):
        blocks, rest = split(tree)
        return lax.psum(fn(blocks), pipe_axis) + fn(rest)

    pl = None
    if health.per_layer:
        def layer_norms(tree):
            blocks, rest = split(tree)
            out = {
                "blocks/" + k: jnp.sqrt(lax.psum(v, pipe_axis))
                for k, v in per_layer_sq(blocks).items()
            }
            out.update(
                {k: jnp.sqrt(v) for k, v in per_layer_sq(rest).items()})
            return out

        pl = {
            "grad_norm": layer_norms(grads),
            "param_norm": layer_norms(params),
        }
    return assemble_stats(
        loss=loss,
        grad_sq=reduced(grads, tree_sq),
        grad_bad=reduced(grads, tree_nonfinite),
        param_sq=reduced(params, tree_sq),
        update_sq=reduced(updates, tree_sq),
        update_bad=reduced(updates, tree_nonfinite),
        per_layer=pl,
    )


def pp_schedule_stats(n_stages: int, n_microbatches: int,
                      schedule: str) -> dict:
    """Analytic schedule profile: bubble fraction (idle slots over total
    schedule slots) and the in-flight activation bound — the numbers the
    dryrun/strategy output reports (round-4 verdict item 5: PP must state
    its bubble, not just demonstrate correctness).

    - gpipe: M+S-1 forward ticks then M+S-1 backward ticks; bubble
      (S-1)/(M+S-1) per pass; autodiff stores O(M) microbatch activations.
    - 1f1b: M+2(S-1) interleaved cycles (each one F and one B sub-tick);
      bubble 2(S-1)/(M+2(S-1)) of cycles, but in-flight activations are
      bounded by min(M, 2S-1) REGARDLESS of M — so M (and with it the
      relative bubble) can grow without growing activation memory, which
      is the whole point of 1F1B. Backward recomputes the stage forward
      from the stored stage input (Megatron's full-recompute variant:
      +1/3 FLOPs for O(S) instead of O(M) activation memory).
    """
    s, m = n_stages, n_microbatches
    if schedule == "gpipe":
        return {
            "schedule": "gpipe",
            "bubble_fraction": round((s - 1) / (m + s - 1), 4),
            "in_flight_microbatches": m,
            "recompute": False,
        }
    if schedule == "1f1b":
        return {
            "schedule": "1f1b",
            "bubble_fraction": round(2 * (s - 1) / (m + 2 * (s - 1)), 4),
            "in_flight_microbatches": min(m, 2 * s - 1),
            "recompute": True,
        }
    raise ValueError(f"unknown pp schedule {schedule!r}")


def make_pp_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    state_template: TrainState,
    *,
    n_microbatches: int,
    data_axis: str = DATA_AXIS,
    pipe_axis: str = PIPELINE_AXIS,
    loss_fn: Callable = cross_entropy_loss,
    donate: bool = True,
    schedule: str = "gpipe",
    health: Optional[HealthConfig] = None,
):
    """Compiled pipeline-parallel train step for a ``tpu_ddp.models.vit.ViT``.

    Returns ``(step, state_shardings)`` (same contract as the TP/FSDP
    factories in tpu_ddp.parallel.tensor_parallel); lay the state out with
    ``shard_train_state(state, state_shardings)``. ``state_template`` must
    use the pipeline param layout (``create_pp_train_state`` /
    ``to_pipeline_params``); the batch is the usual global
    {image, label, mask} sharded over ``data_axis``. The per-data-shard batch
    must divide into ``n_microbatches`` equal microbatches.

    ``schedule``: "gpipe" (autodiff backward, O(M) stored activations) or
    "1f1b" (interleaved manual backward with per-stage recompute, O(S)
    in-flight activations — see ``make_pp_1f1b_train_step``). Identical
    math either way, pinned by tests/test_pipeline.py.
    """
    if schedule == "1f1b":
        return make_pp_1f1b_train_step(
            model, tx, mesh, state_template,
            n_microbatches=n_microbatches, data_axis=data_axis,
            pipe_axis=pipe_axis, loss_fn=loss_fn, donate=donate,
            health=health,
        )
    if schedule != "gpipe":
        raise ValueError(f"unknown pp schedule {schedule!r}")
    n_stages = mesh.shape[pipe_axis]
    if model.depth % n_stages:
        raise ValueError(f"depth {model.depth} not divisible by {n_stages} stages")
    embed, apply_stage, apply_head = _vit_pieces(model)

    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def forward(params, images):
        """Per-device pipelined forward: images (local_batch, H, W, C) ->
        logits (local_batch, classes), replicated over the pipeline axis."""
        stage = lax.axis_index(pipe_axis)
        local = images.shape[0]
        assert local % n_microbatches == 0, (
            f"per-shard batch {local} not divisible into {n_microbatches} "
            "microbatches"
        )
        mb = local // n_microbatches
        embedded = embed(params, images).reshape(
            n_microbatches, mb, -1, model.hidden_dim
        )
        # Under shard_map the P(pipe_axis) spec already hands this device its
        # contiguous (depth/S, ...) chunk — stage s holds blocks
        # [s*depth/S, (s+1)*depth/S).
        stage_blocks = params["blocks"]

        m = n_microbatches
        outs = jnp.zeros_like(embedded)
        act = jnp.zeros(embedded.shape[1:], embedded.dtype)
        # The tick body makes the carry vary over the pipeline axis (stage
        # index, ppermute); shard_map's varying-axes tracking requires the
        # initial carry to carry the same marking.
        act = lax.pcast(act, (data_axis, pipe_axis), to="varying")
        outs = lax.pcast(outs, (pipe_axis,), to="varying")

        def tick(carry, t):
            act, outs = carry
            fresh = embedded[jnp.clip(t, 0, m - 1)]
            act = jnp.where(stage == 0, fresh, act)
            act = apply_stage(stage_blocks, act)
            m_out = t - (n_stages - 1)
            idx = jnp.clip(m_out, 0, m - 1)
            cur = lax.dynamic_index_in_dim(outs, idx, keepdims=False)
            new = jnp.where((stage == n_stages - 1) & (m_out >= 0), act, cur)
            outs = lax.dynamic_update_index_in_dim(outs, new, idx, 0)
            act = lax.ppermute(act, pipe_axis, fwd_perm)
            return (act, outs), None

        (_, outs), _ = lax.scan(
            tick, (act, outs), jnp.arange(m + n_stages - 1)
        )
        logits = apply_head(params, outs.reshape(local, -1, model.hidden_dim))
        # Only the last stage's logits are real; broadcast them. Gradient
        # flows back through the where-mask to the last stage alone.
        return lax.psum(
            jnp.where(stage == n_stages - 1, logits, jnp.zeros_like(logits)),
            pipe_axis,
        )

    def compute_loss(params, batch):
        logits = forward(params, batch["image"])
        loss = loss_fn(logits, batch["label"], batch.get("mask"))
        return lax.pmean(loss, data_axis), logits

    def shard_step(state: TrainState, batch):
        with jax.named_scope(FORWARD_BACKWARD_SCOPE):
            (loss, logits), grads = jax.value_and_grad(
                compute_loss, has_aux=True
            )(state.params, batch)
        with jax.named_scope(OPTIMIZER_SCOPE):
            updates, new_opt_state = tx.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        with jax.named_scope(METRICS_SCOPE):
            correct, count = masked_accuracy(
                logits, batch["label"], batch.get("mask"))
            metrics = {
                "loss": loss,
                "accuracy": lax.psum(correct, data_axis)
                / jnp.maximum(lax.psum(count, data_axis), 1.0),
            }
        if health is not None:
            hstats = _pp_health_stats(
                health, loss=loss, grads=grads, params=state.params,
                updates=updates, pipe_axis=pipe_axis,
            )
            new_params, new_opt_state = guard_step(
                health, hstats, (new_params, new_opt_state),
                (state.params, state.opt_state),
            )
            metrics["health"] = hstats
        return (
            state.replace(
                step=state.step + 1, params=new_params, opt_state=new_opt_state
            ),
            metrics,
        )

    specs = _pp_state_specs(state_template, pipe_axis)
    return _pp_jit(shard_step, mesh, specs, data_axis, donate)


def _pp_state_specs(state_template: TrainState, pipe_axis: str):
    """PartitionSpec tree for the pipeline state layout: the stacked
    ``blocks`` tree is stage-sharded over ``pipe_axis``; everything else
    (embed, head, step) replicated; opt_state mirrors params."""
    from tpu_ddp.parallel.partitioning import opt_state_specs

    def param_specs(params):
        return {
            k: (
                jax.tree.map(lambda _: P(pipe_axis), v)
                if k == "blocks"
                else jax.tree.map(lambda _: P(), v)
            )
            for k, v in params.items()
        }

    def state_specs(state):
        specs = param_specs(state.params)
        return state.replace(
            step=P(),
            params=specs,
            batch_stats=jax.tree.map(lambda _: P(), state.batch_stats),
            opt_state=opt_state_specs(state.opt_state, specs),
        )

    return state_specs(jax.eval_shape(lambda: state_template))


def _pp_jit(shard_step, mesh, specs, data_axis, donate):
    batch_specs = {
        "image": P(data_axis),
        "label": P(data_axis),
        "mask": P(data_axis),
    }
    sharded = jax.shard_map(
        shard_step,
        mesh=mesh,
        in_specs=(specs, batch_specs),
        out_specs=(specs, P()),
    )
    step = jax.jit(sharded, donate_argnums=(0,) if donate else ())
    from jax.sharding import NamedSharding

    shardings = jax.tree.map(
        lambda sp: NamedSharding(mesh, sp),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    return step, shardings


def _pcast_varying(tree, axes):
    """pcast every leaf to varying over whichever of ``axes`` it lacks —
    shared by the 1F1B carry init and its param-tree preparation (leaves
    derived from stage-sharded params are already pipeline-varying)."""
    def one(x):
        have = jax.typeof(x).vma
        need = tuple(a for a in axes if a not in have)
        return lax.pcast(x, need, to="varying") if need else x

    return jax.tree.map(one, tree)


def make_pp_1f1b_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    state_template: TrainState,
    *,
    n_microbatches: int,
    data_axis: str = DATA_AXIS,
    pipe_axis: str = PIPELINE_AXIS,
    loss_fn: Callable = cross_entropy_loss,
    donate: bool = True,
    health: Optional[HealthConfig] = None,
):
    """1F1B (PipeDream-flush) pipeline schedule with full recompute —
    Megatron's memory-lean configuration, compiled as ONE lax.scan.

    Unlike the GPipe mode (whole-forward scan + autodiff backward, which
    stores activations for every tick — O(M) microbatches live at once),
    this schedule interleaves one forward and one backward sub-tick per
    cycle and writes the backward BY HAND:

    - forward activations rotate up the ring (ppermute), cotangents rotate
      down; micro ``f = c - stage`` forwards and micro
      ``b = c - 2(S-1) + stage`` backwards at cycle ``c``;
    - each stage stores only its microbatch INPUTS in a
      ``min(M, 2S-1)``-slot ring buffer — the in-flight bound that makes M
      (and with it the relative bubble) free to grow;
    - the backward sub-tick recomputes the stage forward from the stored
      input under ``jax.vjp`` (the +1/3-FLOPs full-recompute trade);
    - embed and head+loss run PER MICROBATCH inline (vjp'd at stage 0 /
      S-1 respectively), so nothing O(M)-sized is ever materialized;
    - per-micro loss contributions are ``loss_fn(micro) * count_micro /
      count_local`` — summing to exactly the local masked-mean loss, so
      gradients match the GPipe schedule bit-for-bit up to float
      reassociation (pinned by tests/test_pipeline.py).

    Replicated-param gradients (embed/head) are psum'd over the pipeline
    axis (each is nonzero on exactly one stage) and pmean'd over data —
    the same DDP semantics autodiff derives for the GPipe mode.
    """
    n_stages = mesh.shape[pipe_axis]
    if model.depth % n_stages:
        raise ValueError(f"depth {model.depth} not divisible by {n_stages} stages")
    m = n_microbatches
    embed, apply_stage, apply_head = _vit_pieces(model)

    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    n_slots = min(m, 2 * n_stages - 1)
    n_cycles = m + 2 * (n_stages - 1)

    def shard_step(state: TrainState, batch):
        params = state.params
        stage = lax.axis_index(pipe_axis)
        images, labels = batch["image"], batch["label"]
        mask = batch.get("mask")
        local = images.shape[0]
        assert local % m == 0, (
            f"per-shard batch {local} not divisible into {m} microbatches")
        mb = local // m
        n_tokens = (images.shape[1] // model.patch_size) * (
            images.shape[2] // model.patch_size)
        if mask is None:
            mask = jnp.ones(local, bool)
        total_count = jnp.maximum(mask.astype(jnp.float32).sum(), 1.0)

        # Work on VARYING copies of every param tree: the manual backward
        # below owns ALL cross-device gradient reduction explicitly
        # (psum over pipe for single-stage contributions, pmean over data
        # for DDP averaging). Differentiating unvarying (replicated)
        # inputs with jax.vjp inside shard_map would add the vma system's
        # own implicit-reduction semantics on top and double-count.
        both_axes = (data_axis, pipe_axis)
        embed_params = _pcast_varying({
            "patch_embed": params["patch_embed"],
            "pos_embed": params["pos_embed"]}, both_axes)
        head_params = _pcast_varying({
            "ln_f": params["ln_f"], "head": params["head"]}, both_axes)
        stage_blocks = _pcast_varying(params["blocks"], both_axes)

        def micro(x, i):  # rows [i*mb, (i+1)*mb) of a local array
            return lax.dynamic_slice_in_dim(
                x, jnp.clip(i, 0, m - 1) * mb, mb, axis=0)

        def head_loss(hp, act, labels_b, mask_b):
            logits = apply_head(hp, act)
            count = mask_b.astype(jnp.float32).sum()
            contrib = loss_fn(logits, labels_b, mask_b) * count / total_count
            return contrib, logits

        def seed_like(x, ref):
            # vjp cotangent seeds must carry the primal output's varying
            # axes (fresh ones()/zeros() are device-invariant)
            have = jax.typeof(x).vma
            need = tuple(a for a in jax.typeof(ref).vma if a not in have)
            return lax.pcast(x, need, to="varying") if need else x

        zero_g_blocks = jax.tree.map(jnp.zeros_like, stage_blocks)
        zero_g_embed = jax.tree.map(jnp.zeros_like, embed_params)
        zero_g_head = jax.tree.map(jnp.zeros_like, head_params)
        # activations/cotangents carry in the model's compute dtype (the
        # embed/block outputs' dtype) so the scan carry type is stable
        act0 = jnp.zeros((mb, n_tokens, model.hidden_dim), model.dtype)
        carry0 = (
            act0,                                        # incoming act
            act0,                                        # incoming cotangent
            jnp.zeros((n_slots,) + act0.shape, act0.dtype),  # input ring buf
            zero_g_blocks, zero_g_embed, zero_g_head,
            jnp.zeros((), jnp.float32),                  # loss sum
            jnp.zeros((m, mb, model.num_classes), jnp.float32),  # logits
        )
        # every carry leaf becomes varying over BOTH axes in the body
        # (batch data + stage index / ppermute); the init must match.
        # Leaves derived from stage-sharded params (the block-grad zeros)
        # are ALREADY pipeline-varying — _pcast_varying casts only the
        # axes each one lacks.
        carry0 = _pcast_varying(carry0, both_axes)

        def cycle(carry, c):
            act_in, cot_in, buf, g_blocks, g_embed, g_head, loss_sum, \
                logits_buf = carry
            f = c - stage
            b = c - 2 * (n_stages - 1) + stage
            do_f = (f >= 0) & (f < m)
            do_b = (b >= 0) & (b < m)

            # ---- forward sub-tick: micro f through this stage ----
            fresh = embed(embed_params, micro(images, f))
            x_in = jnp.where(stage == 0, fresh, act_in)
            slot_f = jnp.where(do_f, f % n_slots, 0)
            buf = jnp.where(
                do_f,
                lax.dynamic_update_index_in_dim(buf, x_in, slot_f, 0),
                buf,
            )
            act_out = apply_stage(stage_blocks, x_in)

            # ---- backward sub-tick: micro b back through this stage ----
            # at the LAST stage micro b's forward completed THIS cycle
            # (b == f there): seed its cotangent from head+loss now
            labels_b, mask_b = micro(labels, b), micro(mask, b)
            (contrib, logits_b), head_vjp = jax.vjp(
                lambda hp, a: head_loss(hp, a, labels_b, mask_b),
                head_params, act_out,
            )
            d_head_b, cot_head = head_vjp(
                (seed_like(jnp.ones(()), contrib),
                 seed_like(jnp.zeros_like(logits_b), logits_b)))
            last = stage == n_stages - 1
            gate_last = (do_b & last).astype(jnp.float32)
            loss_sum = loss_sum + gate_last * contrib
            logits_buf = jnp.where(
                do_b & last,
                lax.dynamic_update_index_in_dim(
                    logits_buf, logits_b, jnp.where(do_b, b % m, 0), 0),
                logits_buf,
            )
            g_head = jax.tree.map(
                lambda g, d: g + gate_last * d, g_head, d_head_b)

            cot_out = jnp.where(last, cot_head, cot_in)
            x_stored = lax.dynamic_index_in_dim(
                buf, jnp.where(do_b, b % n_slots, 0), keepdims=False)
            # recompute the stage forward from the stored input (full
            # recompute: the O(S) memory bound is paid for with +1 stage-F)
            _, stage_vjp = jax.vjp(apply_stage, stage_blocks, x_stored)
            d_blocks_b, d_x_in = stage_vjp(cot_out)
            gate_b = do_b.astype(jnp.float32)
            g_blocks = jax.tree.map(
                lambda g, d: g + gate_b * d, g_blocks, d_blocks_b)
            # at stage 0 the input was the embed output: close the chain
            _, embed_vjp = jax.vjp(
                lambda ep: embed(ep, micro(images, b)), embed_params)
            (d_embed_b,) = embed_vjp(d_x_in)
            gate_0 = (do_b & (stage == 0)).astype(jnp.float32)
            g_embed = jax.tree.map(
                lambda g, d: g + gate_0 * d, g_embed, d_embed_b)

            act_next = lax.ppermute(act_out, pipe_axis, fwd_perm)
            cot_next = lax.ppermute(d_x_in, pipe_axis, bwd_perm)
            return (act_next, cot_next, buf, g_blocks, g_embed, g_head,
                    loss_sum, logits_buf), None

        # the schedule's own vjp calls carry the AD markers that tell a
        # stage's forward from its backward (telemetry/phases.py)
        with jax.named_scope(FORWARD_BACKWARD_SCOPE):
            carry, _ = lax.scan(cycle, carry0, jnp.arange(n_cycles))
        (_, _, _, g_blocks, g_embed, g_head, loss_sum, logits_buf) = carry

        # replicated-param grads: nonzero on exactly one stage -> psum over
        # the pipeline axis recovers the unique contribution everywhere;
        # then DDP-average over data. Stage-local block grads only average
        # over data.
        with jax.named_scope(GRAD_SYNC_SCOPE):
            g_embed = jax.tree.map(lambda g: lax.psum(g, pipe_axis), g_embed)
            g_head = jax.tree.map(lambda g: lax.psum(g, pipe_axis), g_head)
            grads = {
                "blocks": jax.tree.map(
                    lambda g: lax.pmean(g, data_axis), g_blocks),
                **{k: jax.tree.map(lambda g: lax.pmean(g, data_axis), v)
                   for k, v in (("patch_embed", g_embed["patch_embed"]),
                                ("pos_embed", g_embed["pos_embed"]),
                                ("ln_f", g_head["ln_f"]),
                                ("head", g_head["head"]))},
            }
        with jax.named_scope(OPTIMIZER_SCOPE):
            updates, new_opt_state = tx.update(
                grads, state.opt_state, params)
            new_params = optax.apply_updates(params, updates)

        with jax.named_scope(METRICS_SCOPE):
            loss = lax.pmean(lax.psum(loss_sum, pipe_axis), data_axis)
            logits = lax.psum(logits_buf, pipe_axis).reshape(
                local, model.num_classes)
            correct, count = masked_accuracy(logits, labels, mask)
            metrics = {
                "loss": loss,
                "accuracy": lax.psum(correct, data_axis)
                / jnp.maximum(lax.psum(count, data_axis), 1.0),
            }
        if health is not None:
            hstats = _pp_health_stats(
                health, loss=loss, grads=grads, params=params,
                updates=updates, pipe_axis=pipe_axis,
            )
            new_params, new_opt_state = guard_step(
                health, hstats, (new_params, new_opt_state),
                (params, state.opt_state),
            )
            metrics["health"] = hstats
        return (
            state.replace(
                step=state.step + 1, params=new_params,
                opt_state=new_opt_state,
            ),
            metrics,
        )

    specs = _pp_state_specs(state_template, pipe_axis)
    return _pp_jit(shard_step, mesh, specs, data_axis, donate)


def create_pp_train_state(model, tx, rng, input_shape=(1, 32, 32, 3)) -> TrainState:
    """Init a plain ViT and convert to the pipeline param layout (optimizer
    state initialized on the converted tree so momentum stacks match)."""
    variables = model.init(rng, jnp.zeros(input_shape, jnp.float32), train=False)
    params = to_pipeline_params(variables["params"], model.depth)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats={},
        opt_state=tx.init(params),
    )
