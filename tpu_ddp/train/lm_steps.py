"""Next-token training steps for the causal LM family.

Two layouts over the same math (the DP/SP pair mirrors the image steps
in ``train/steps.py`` / ``parallel/sequence_parallel.py``):

- ``make_lm_train_step`` — data parallel: tokens (B, T) batch-sharded,
  loss = mean CE of logits[:, :-1] vs tokens[:, 1:], pmean'd before
  differentiation so AD produces the DDP-averaged gradient.
- ``make_sp_lm_train_step`` — data x sequence parallel: tokens sharded
  over BOTH axes; the model runs causal ring attention over the sequence
  axis, and the next-token targets for each shard's LAST position live
  on the NEXT shard — one ``ppermute`` of the neighbors' first tokens
  closes the shift, and the global final position (which has no target)
  is masked on the last shard. Loss equals the DP step's exactly
  (pinned by tests/test_lm.py).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_ddp.health.stats import HealthConfig, guard_step, health_stats
from tpu_ddp.parallel.mesh import DATA_AXIS, SEQUENCE_AXIS
from tpu_ddp.telemetry.phases import (
    FORWARD_BACKWARD_SCOPE,
    GRAD_COMPRESS_SCOPE,
    METRICS_SCOPE,
    OPTIMIZER_SCOPE,
)
from tpu_ddp.train.optim import apply_optimizer
from tpu_ddp.train.state import TrainState
from tpu_ddp.train.steps import _bind_compressor, state_specs_for


def _with_health(health, *, loss, grads, params, updates, new_params,
                 new_opt_state, old_opt_state, compress_error_sq=None):
    """Shared flight-recorder tail for the LM steps: stats on the synced
    grads/updates + the optional skip-step guard. Returns
    ``(hstats, new_params, new_opt_state)``; no-op when health is None."""
    hstats = health_stats(
        loss=loss, grads=grads, params=params, updates=updates,
        per_layer=health.per_layer, compress_error_sq=compress_error_sq,
    )
    new_params, new_opt_state = guard_step(
        health, hstats, (new_params, new_opt_state),
        (params, old_opt_state),
    )
    return hstats, new_params, new_opt_state


def _token_nll(logits, targets):
    """Per-position negative log-likelihood, f32: (B, T', V), (B, T')."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]


def make_lm_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    data_axis: str = DATA_AXIS,
    donate: bool = True,
    health: Optional[HealthConfig] = None,
    zero1=None,
    compress=None,
) -> Callable:
    """step(state, {"tokens": (B, T) int32}) -> (state, {"loss"}).

    ``zero1`` (Zero1Partition): ZeRO-1 weight-update sharding — the grad
    pmean becomes a reduce-scatter and the optimizer state lives scattered
    over ``data_axis`` (parallel/zero.py). ``compress`` (GradCompressor):
    the sync's wire payloads are block-scaled quantized
    (parallel/compression.py)."""
    _bind_compressor(zero1, compress)

    def shard_step(state: TrainState, batch):
        tokens = batch["tokens"]

        def compute_loss(params):
            logits = model.apply({"params": params}, tokens, train=True)
            loss = _token_nll(logits[:, :-1], tokens[:, 1:]).mean()
            # pmean BEFORE differentiation: AD of the averaged loss emits
            # the cross-shard grad psum (the DDP semantics, exactly as in
            # train/steps.py). zero1/compress: the sync is the (ring)
            # reduce-scatter — the loss stays local.
            if zero1 is None and compress is None:
                return lax.pmean(loss, data_axis)
            return loss

        if zero1 is not None:
            p_in = zero1.varying(state.params)
        elif compress is not None:
            p_in = compress.varying(state.params)
        else:
            p_in = state.params
        with jax.named_scope(FORWARD_BACKWARD_SCOPE):
            loss, grads = jax.value_and_grad(compute_loss)(p_in)
        if zero1 is not None or compress is not None:
            with jax.named_scope(METRICS_SCOPE):
                loss = lax.pmean(loss, data_axis)
        ef = compress is not None and compress.config.error_feedback
        want_err = compress is not None and (ef or health is not None)
        residual = state.grad_residual if ef else None
        err_state = None
        if zero1 is not None:
            with jax.named_scope(OPTIMIZER_SCOPE):
                new_params, new_opt, gshards, ushards, err_state = (
                    zero1.sharded_update(
                        grads, state.params, state.opt_state,
                        residual=residual, with_error=want_err,
                    )
                )
        else:
            if compress is not None:
                with jax.named_scope(GRAD_COMPRESS_SCOPE):
                    grads, err_state = compress.all_reduce_mean(
                        grads, residual, with_error=want_err)
            with jax.named_scope(OPTIMIZER_SCOPE):
                new_params, updates, new_opt = apply_optimizer(
                    tx, grads, state.opt_state, state.params)
        new_residual = err_state if ef else state.grad_residual
        metrics = {"loss": loss}
        if health is not None:
            err_sq = compress.error_sq(err_state) if want_err else None
            if zero1 is not None:
                hstats = zero1.health_stats(
                    loss=loss, grad_shards=gshards, params=state.params,
                    update_shards=ushards, per_layer=health.per_layer,
                    compress_error_sq=err_sq,
                )
                (new_params, new_opt, new_residual) = guard_step(
                    health, hstats, (new_params, new_opt, new_residual),
                    (state.params, state.opt_state, state.grad_residual),
                )
                metrics["health"] = hstats
            else:
                metrics["health"], new_params, new_opt = _with_health(
                    health, loss=loss, grads=grads, params=state.params,
                    updates=updates, new_params=new_params,
                    new_opt_state=new_opt, old_opt_state=state.opt_state,
                    compress_error_sq=err_sq,
                )
                if ef:
                    (new_residual,) = guard_step(
                        health, metrics["health"], (new_residual,),
                        (state.grad_residual,))
        return (
            state.replace(step=state.step + 1, params=new_params,
                          opt_state=new_opt, grad_residual=new_residual),
            metrics,
        )

    state_specs = state_specs_for(zero1, compress, data_axis)
    sharded = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(state_specs, {"tokens": P(data_axis)}),
        out_specs=(state_specs, P()),
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def make_sp_lm_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    data_axis: str = DATA_AXIS,
    seq_axis: str = SEQUENCE_AXIS,
    donate: bool = True,
    health: Optional[HealthConfig] = None,
    zero1=None,
    compress=None,
) -> Callable:
    """Sequence-parallel next-token step. ``model`` must be built with
    ``sp_axis=seq_axis``; tokens arrive (B_local, T_local) per shard.

    ``zero1``: the data-axis half of the gradient sync becomes a
    reduce-scatter and the optimizer state scatters over ``data`` (it
    stays REPLICATED over ``sequence`` — the update space is partitioned
    over the DP axis only, parallel/zero.py). The sequence-axis psum of
    the attention partials is unchanged. ``compress`` quantizes the
    DATA-axis collective's wire payloads only (the seq-axis partials are
    seq-identical after their psum, so the quantized ring — a
    deterministic function of them — stays replicated over sequence,
    residual included)."""
    _bind_compressor(zero1, compress)
    n_seq = mesh.shape[seq_axis]
    shift_perm = [(i, (i - 1) % n_seq) for i in range(n_seq)]

    def shard_step(state: TrainState, batch):
        tokens = batch["tokens"]  # (B_local, T_local)

        def compute_loss(params):
            logits = model.apply({"params": params}, tokens, train=True)
            # targets: global left-shift — within the shard it's
            # tokens[:, 1:], and the LAST local position's target is the
            # NEXT shard's first token (one neighbor ppermute)
            next_first = lax.ppermute(tokens[:, :1], seq_axis, shift_perm)
            targets = jnp.concatenate([tokens[:, 1:], next_first], axis=1)
            nll = _token_nll(logits, targets)        # (B, T_local)
            # the global FINAL position has no target: mask it on the
            # last shard (its ppermute'd "next token" wrapped around)
            is_last = lax.axis_index(seq_axis) == n_seq - 1
            tail = jnp.where(is_last, 0.0, 1.0)
            mask = jnp.ones_like(nll).at[:, -1].set(tail)
            loss_sum = lax.psum((nll * mask).sum(), seq_axis)
            count = lax.psum(mask.sum(), seq_axis)
            # global mean over valid positions == the DP step's mean over
            # (B, T-1); then DDP-average over data
            loss = loss_sum / count  # already seq-invariant (psum above)
            # zero1/compress: keep the loss data-LOCAL (the ring
            # reduce-scatter is the data-axis sync); seq invariance
            # already holds
            if zero1 is not None or compress is not None:
                return loss
            return lax.pmean(loss, data_axis)

        if zero1 is not None:
            p_in = zero1.varying(state.params)
        elif compress is not None:
            p_in = compress.varying(state.params)
        else:
            p_in = state.params
        with jax.named_scope(FORWARD_BACKWARD_SCOPE):
            loss, grads = jax.value_and_grad(compute_loss)(p_in)
        if zero1 is not None or compress is not None:
            with jax.named_scope(METRICS_SCOPE):
                loss = lax.pmean(loss, data_axis)
        ef = compress is not None and compress.config.error_feedback
        want_err = compress is not None and (ef or health is not None)
        residual = state.grad_residual if ef else None
        err_state = None
        if zero1 is not None:
            with jax.named_scope(OPTIMIZER_SCOPE):
                new_params, new_opt, gshards, ushards, err_state = (
                    zero1.sharded_update(
                        grads, state.params, state.opt_state,
                        residual=residual, with_error=want_err,
                    )
                )
        else:
            if compress is not None:
                with jax.named_scope(GRAD_COMPRESS_SCOPE):
                    grads, err_state = compress.all_reduce_mean(
                        grads, residual, with_error=want_err)
            with jax.named_scope(OPTIMIZER_SCOPE):
                new_params, updates, new_opt = apply_optimizer(
                    tx, grads, state.opt_state, state.params)
        new_residual = err_state if ef else state.grad_residual
        metrics = {"loss": loss}
        if health is not None:
            # grads are fully synced over BOTH axes at this point (AD of
            # the psum'd/pmean'd loss, the explicit pmean-of-psum above,
            # the dequantized ring output, or the zero1 shards —
            # seq-complete, data-scattered), so the stats are
            # (data x seq)-replicated globals
            err_sq = compress.error_sq(err_state) if want_err else None
            if zero1 is not None:
                hstats = zero1.health_stats(
                    loss=loss, grad_shards=gshards, params=state.params,
                    update_shards=ushards, per_layer=health.per_layer,
                    compress_error_sq=err_sq,
                )
                (new_params, new_opt, new_residual) = guard_step(
                    health, hstats, (new_params, new_opt, new_residual),
                    (state.params, state.opt_state, state.grad_residual),
                )
                metrics["health"] = hstats
            else:
                metrics["health"], new_params, new_opt = _with_health(
                    health, loss=loss, grads=grads, params=state.params,
                    updates=updates, new_params=new_params,
                    new_opt_state=new_opt, old_opt_state=state.opt_state,
                    compress_error_sq=err_sq,
                )
                if ef:
                    (new_residual,) = guard_step(
                        health, metrics["health"], (new_residual,),
                        (state.grad_residual,))
        return (
            state.replace(step=state.step + 1, params=new_params,
                          opt_state=new_opt, grad_residual=new_residual),
            metrics,
        )

    state_specs = state_specs_for(zero1, compress, data_axis)
    sharded = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(state_specs, {"tokens": P(data_axis, seq_axis)}),
        out_specs=(state_specs, P()),
    )
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def create_lm_train_state(model, tx, rng, *, batch: int = 1,
                          seq_len: int = 16) -> TrainState:
    """Init an LM TrainState from a dummy token batch. For SP models the
    init must run through a PLAIN twin (``sp_axis=None``) — param shapes
    are identical by construction (full global pos table either way)."""
    init_model = model
    if getattr(model, "sp_axis", None) is not None:
        init_model = model.clone(sp_axis=None)
    variables = init_model.init(
        rng, jnp.zeros((batch, seq_len), jnp.int32), train=False)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=variables["params"],
        batch_stats={},
        opt_state=tx.init(variables["params"]),
    )
