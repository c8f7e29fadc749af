"""Sequence-parallel (SP) training: data x sequence 2-D mesh.

First-class long-context training (build brief; absent from the reference —
SURVEY.md §5.7). The train step runs under ``jax.shard_map`` over BOTH mesh
axes: the batch dim is sharded over ``data`` and the image height (hence the
patch/token sequence) over ``sequence``. Inside, the SP-aware ViT
(``tpu_ddp.models.vit.ViT(sp_axis=...)``) does ring attention over the
sequence ring while gradient sync happens exactly like the DDP step: the
loss is pmean'd over both axes before AD, so the transpose + the
unvarying-params psum produce globally averaged gradients with XLA free to
overlap both collectives with compute.

Memory: each device holds T/n_seq tokens -> attention working set drops from
O(T^2) to O(T * T/n_seq), which is what makes long sequences fit at all.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_ddp.health.stats import HealthConfig, guard_step, health_stats
from tpu_ddp.parallel.mesh import DATA_AXIS, SEQUENCE_AXIS
from tpu_ddp.telemetry.phases import (
    FORWARD_BACKWARD_SCOPE,
    GRAD_COMPRESS_SCOPE,
    HEALTH_SCOPE,
    LOSS_SCOPE,
    METRICS_SCOPE,
    OPTIMIZER_SCOPE,
)
from tpu_ddp.train.losses import cross_entropy_loss
from tpu_ddp.train.optim import apply_optimizer
from tpu_ddp.train.state import TrainState


def make_sp_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    data_axis: str = DATA_AXIS,
    seq_axis: str = SEQUENCE_AXIS,
    loss_fn: Callable = cross_entropy_loss,
    donate: bool = True,
    health: Optional[HealthConfig] = None,
    zero1=None,
    compress=None,
):
    """Compiled train step for an SP-aware model (ViT with sp_axis=seq_axis).

    Batch layout: {image (N, H, W, C), label (N,), mask (N,)} — image sharded
    (data, sequence) on (N, H); labels/mask sharded on data only. H must be
    divisible by patch_size * mesh.shape[seq_axis].

    ``zero1`` (``tpu_ddp.parallel.zero.Zero1Partition``): the DATA half of
    the gradient sync becomes a reduce-scatter and the optimizer state
    scatters over ``data`` (replicated over ``sequence`` — the update space
    partitions over the DP axis only); the sequence-axis collective for the
    distributed attention partials is unchanged.

    ``compress`` (``tpu_ddp.parallel.compression.GradCompressor``): the
    DATA-axis gradient collective runs as the block-scaled quantized ring
    (--grad-compress). Seq-axis sync is untouched; the ring input is
    seq-identical after it, so the quantized output (and the
    error-feedback residual) stays replicated over ``sequence``.
    """
    from tpu_ddp.train.steps import _bind_compressor, state_specs_for

    _bind_compressor(zero1, compress)

    def compute_loss(params, batch):
        logits = model.apply({"params": params}, batch["image"], train=True)
        with jax.named_scope(LOSS_SCOPE):
            loss = loss_fn(logits, batch["label"], batch.get("mask"))
        # Gradient sync (see tpu_ddp.train.steps on why the pmean precedes
        # AD). Over `data_axis` ONLY: the SP model's mean-pool pmean already
        # made the loss invariant over `seq_axis`, and shard_map's
        # varying-axes tracking inserts the correct sequence-axis psums for
        # the distributed attention partials during the transpose.
        # zero1/compress: the data sync is the (ring) reduce-scatter —
        # the loss stays local.
        if zero1 is None and compress is None:
            return lax.pmean(loss, data_axis)
        return loss

    def shard_step(state: TrainState, batch):
        if zero1 is not None:
            p_in = zero1.varying(state.params)
        elif compress is not None:
            p_in = compress.varying(state.params)
        else:
            p_in = state.params
        with jax.named_scope(FORWARD_BACKWARD_SCOPE):
            loss, grads = jax.value_and_grad(compute_loss)(p_in, batch)
        if zero1 is not None or compress is not None:
            with jax.named_scope(METRICS_SCOPE):
                loss = lax.pmean(loss, data_axis)
        ef = compress is not None and compress.config.error_feedback
        want_err = compress is not None and (ef or health is not None)
        residual = state.grad_residual if ef else None
        err_state = None
        if zero1 is not None:
            with jax.named_scope(OPTIMIZER_SCOPE):
                new_params, new_opt_state, gshards, ushards, err_state = (
                    zero1.sharded_update(
                        grads, state.params, state.opt_state,
                        residual=residual, with_error=want_err)
                )
        else:
            if compress is not None:
                with jax.named_scope(GRAD_COMPRESS_SCOPE):
                    grads, err_state = compress.all_reduce_mean(
                        grads, residual, with_error=want_err)
            with jax.named_scope(OPTIMIZER_SCOPE):
                new_params, updates, new_opt_state = apply_optimizer(
                    tx, grads, state.opt_state, state.params)
        new_residual = err_state if ef else state.grad_residual
        metrics = {"loss": loss}
        if health is not None:
            # grads are synced over BOTH mesh axes by this point (zero1's
            # shards are seq-complete and data-scattered, psum'd back to
            # globals inside health_stats), so the stats are true globals —
            # same schema as the DP step
            with jax.named_scope(HEALTH_SCOPE):
                err_sq = compress.error_sq(err_state) if want_err else None
                if zero1 is not None:
                    hstats = zero1.health_stats(
                        loss=loss, grad_shards=gshards, params=state.params,
                        update_shards=ushards, per_layer=health.per_layer,
                        compress_error_sq=err_sq,
                    )
                else:
                    hstats = health_stats(
                        loss=loss, grads=grads, params=state.params,
                        updates=updates, per_layer=health.per_layer,
                        compress_error_sq=err_sq,
                    )
                (new_params, new_opt_state, new_residual) = guard_step(
                    health, hstats,
                    (new_params, new_opt_state, new_residual),
                    (state.params, state.opt_state, state.grad_residual),
                )
            metrics["health"] = hstats
        return (
            state.replace(
                step=state.step + 1, params=new_params,
                opt_state=new_opt_state, grad_residual=new_residual,
            ),
            metrics,
        )

    batch_specs = {
        "image": P(data_axis, seq_axis),
        "label": P(data_axis),
        "mask": P(data_axis),
    }
    state_specs = state_specs_for(zero1, compress, data_axis)
    sharded = jax.shard_map(
        shard_step,
        mesh=mesh,
        in_specs=(state_specs, batch_specs),
        out_specs=(state_specs, P()),
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())
