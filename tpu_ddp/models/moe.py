"""Mixture-of-Experts ViT — the expert-parallel model family.

Absent from the reference (SURVEY.md §2.3: "Expert parallel (EP / MoE): NO");
built TPU-first as the classic GShard/Switch formulation, which exists
precisely because it maps onto XLA SPMD: routing is expressed as dense
one-hot dispatch/combine einsums with *static* shapes (a fixed per-expert
capacity), so the whole layer jits once, the expert matmuls stay large and
MXU-shaped, and sharding the stacked expert weights over an ``expert`` mesh
axis makes the partitioner insert the token all-to-all automatically.

Components:
- ``MoEMlp``      — top-k routed FFN (Switch top-1 default, GShard top-2+)
                    with a capacity factor + load-balance aux loss (sown
                    into the ``aux_loss`` collection).
- ``MoETransformerBlock`` — pre-LN block whose FFN is a ``MoEMlp``.
- ``MoEViT``      — ViT that interleaves dense and MoE blocks
                    (``moe_every``), same interface as ``models.vit.ViT``.

Expert-parallel layout rules live in ``tpu_ddp.parallel.expert_parallel``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from tpu_ddp.models.vit import MultiHeadSelfAttention, TransformerBlock
from tpu_ddp.models.zoo import register


class MoEMlp(nn.Module):
    """Top-k routed FFN over ``num_experts`` experts (Switch at ``top_k=1``
    — the default — GShard-style at ``top_k=2``+).

    Dispatch is the GShard dense formulation: a one-hot tensor
    ``(B, T, E, capacity)`` routes each (token, choice) to a slot in its
    expert's fixed-size buffer; slots past capacity are *dropped* (that
    choice's MLP output is zero — the residual connection in the enclosing
    block carries the token through unchanged, and with ``top_k>1`` a
    token's surviving choices still contribute). No re-routing: dropped is
    dropped, the standard Switch/GShard behavior, pinned by test.

    ``capacity_factor`` scales the per-expert buffer against the balanced
    load: ``capacity = ceil(T * top_k * capacity_factor / num_experts)``.
    Gate convention: ``top_k=1`` keeps Switch's raw top probability
    (combine weight < 1); ``top_k>1`` normalizes the selected
    probabilities to sum to 1 (GShard).  Router math runs in f32
    regardless of compute dtype (bf16 softmax routing is unstable).

    Expert weights are stacked with a leading ``E`` dim — ``w_up (E, C, H)``,
    ``w_down (E, H, C)`` — so expert parallelism is one PartitionSpec:
    ``P('expert', None, None)``.
    """

    num_experts: int
    top_k: int = 1
    capacity_factor: float = 1.25
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):  # (B, T, C) -> (B, T, C)
        B, T, C = x.shape
        E = self.num_experts
        K = self.top_k
        H = C * self.mlp_ratio
        capacity = max(1, int(np.ceil(T * K * self.capacity_factor / E)))

        # --- routing (f32) ---
        logits = nn.Dense(E, dtype=jnp.float32, name="router")(
            x.astype(jnp.float32)
        )  # (B, T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_i = jax.lax.top_k(probs, K)             # (B, T, K)
        if K == 1:
            gates = topk_p                                   # Switch: raw p1
        else:
            gates = topk_p / jnp.maximum(                    # GShard: renorm
                topk_p.sum(axis=-1, keepdims=True), 1e-9)

        # Switch load-balance loss over the FIRST choice (the paper's
        # definition; identical to the top-1 formula at K=1):
        # E * sum_e fraction_e * mean_prob_e == 1.0 at perfect balance.
        # Sown; the EP train step adds it to the task loss with a small
        # weight.
        mask0 = jax.nn.one_hot(topk_i[..., 0], E, dtype=jnp.float32)
        frac = mask0.mean(axis=1)                            # (B, E)
        mean_prob = probs.mean(axis=1)                       # (B, E)
        self.sow(
            "aux_loss",
            "load_balance",
            E * jnp.mean(jnp.sum(frac * mean_prob, axis=-1)),
        )

        # --- capacity + dispatch/combine tensors ---
        # choice-major slot assignment (GShard): all first choices claim
        # buffer positions before any second choice, so under pressure the
        # primary routes survive. Position is -1 where a (token, expert)
        # pair is unrouted; one_hot maps both -1 and >= capacity to the
        # zero row, which implements dropping for free.
        dispatch = jnp.zeros((B, T, E, capacity), jnp.float32)
        combine = jnp.zeros((B, T, E, capacity), jnp.float32)
        count = jnp.zeros((B, 1, E), jnp.float32)  # slots claimed so far
        for j in range(K):
            mask_j = jax.nn.one_hot(topk_i[..., j], E, dtype=jnp.float32)
            pos_j = jnp.where(
                mask_j > 0, jnp.cumsum(mask_j, axis=1) - 1.0 + count, -1.0
            )                                                # (B, T, E)
            disp_j = jax.nn.one_hot(
                pos_j.astype(jnp.int32), capacity, dtype=jnp.float32
            )                                                # (B, T, E, Cap)
            dispatch = dispatch + disp_j
            combine = combine + disp_j * gates[:, :, j, None, None]
            count = count + mask_j.sum(axis=1, keepdims=True)

        # --- expert computation (stacked, leading E dim) ---
        xd = jnp.einsum(
            "btec,btm->ebcm", dispatch.astype(self.dtype), x.astype(self.dtype)
        )  # (E, B, Cap, C): under EP this einsum IS the token all-to-all
        w_up = self.param(
            "w_up", nn.initializers.lecun_normal(), (E, C, H), jnp.float32
        )
        b_up = self.param("b_up", nn.initializers.zeros, (E, H), jnp.float32)
        w_down = self.param(
            "w_down", nn.initializers.lecun_normal(), (E, H, C), jnp.float32
        )
        b_down = self.param("b_down", nn.initializers.zeros, (E, C), jnp.float32)

        h = jnp.einsum(
            "ebcm,emh->ebch", xd, w_up.astype(self.dtype),
            preferred_element_type=jnp.float32,
        ).astype(self.dtype) + b_up[:, None, None, :].astype(self.dtype)
        h = nn.gelu(h)
        out = jnp.einsum(
            "ebch,ehm->ebcm", h, w_down.astype(self.dtype),
            preferred_element_type=jnp.float32,
        ).astype(self.dtype) + b_down[:, None, None, :].astype(self.dtype)

        y = jnp.einsum(
            "btec,ebcm->btm", combine.astype(self.dtype), out
        )  # (B, T, C): the return all-to-all + weighted un-dispatch
        return y


class MoETransformerBlock(nn.Module):
    """Pre-LN transformer block with a routed-MoE FFN (residuals carry
    capacity-dropped tokens through unchanged)."""

    num_heads: int
    num_experts: int
    top_k: int = 1
    capacity_factor: float = 1.25
    mlp_ratio: int = 4
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        del train
        y = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        x = x + MultiHeadSelfAttention(
            self.num_heads, dtype=self.dtype, name="attn"
        )(y)
        y = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        x = x + MoEMlp(
            self.num_experts,
            top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            mlp_ratio=self.mlp_ratio,
            dtype=self.dtype,
            name="moe",
        )(y)
        return x


class MoEViT(nn.Module):
    """ViT with every ``moe_every``-th FFN replaced by a routed MoE layer
    (the Switch/GShard interleave). Interface-compatible with ``vit.ViT``."""

    patch_size: int = 4
    hidden_dim: int = 192
    depth: int = 6
    num_heads: int = 3
    num_classes: int = 10
    num_experts: int = 8
    top_k: int = 1
    moe_every: int = 2
    capacity_factor: float = 1.25
    mlp_ratio: int = 4
    # per-block rematerialization, same convention as vit.ViT.remat
    # (param trees are identical either way)
    remat: bool = False
    dtype: jnp.dtype = jnp.float32
    # interface parity with the CNN zoo; a ViT has no BN
    bn_cross_replica_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        B = x.shape[0]
        x = nn.Conv(
            self.hidden_dim,
            kernel_size=(self.patch_size, self.patch_size),
            strides=(self.patch_size, self.patch_size),
            dtype=self.dtype,
            name="patch_embed",
        )(x)
        x = x.reshape(B, -1, self.hidden_dim)
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (1, x.shape[1], self.hidden_dim),
        )
        x = x + pos.astype(x.dtype)
        moe_cls, dense_cls = MoETransformerBlock, TransformerBlock
        if self.remat:
            moe_cls = nn.remat(MoETransformerBlock, static_argnums=(2,))
            dense_cls = nn.remat(TransformerBlock, static_argnums=(2,))
        for i in range(self.depth):
            if self.moe_every and (i + 1) % self.moe_every == 0:
                x = moe_cls(
                    self.num_heads,
                    num_experts=self.num_experts,
                    top_k=self.top_k,
                    capacity_factor=self.capacity_factor,
                    mlp_ratio=self.mlp_ratio,
                    dtype=self.dtype,
                    name=f"block_{i}",
                )(x, train)
            else:
                x = dense_cls(
                    self.num_heads,
                    mlp_ratio=self.mlp_ratio,
                    dtype=self.dtype,
                    name=f"block_{i}",
                )(x, train)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        x = x.mean(axis=1)
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)
        return x.astype(jnp.float32)


@register("vit_moe_s4")
def vit_moe_s4(num_classes: int = 10, bn_cross_replica_axis=None,
               dtype=jnp.float32):
    """Small MoE ViT for 32x32 inputs: 8 experts, MoE every other block."""
    return MoEViT(patch_size=4, hidden_dim=192, depth=6, num_heads=3,
                  num_classes=num_classes, num_experts=8, dtype=dtype)


@register("vit_moe_s4_top2")
def vit_moe_s4_top2(num_classes: int = 10, bn_cross_replica_axis=None,
                    dtype=jnp.float32):
    """vit_moe_s4 with GShard top-2 routing (normalized pair gates)."""
    return MoEViT(patch_size=4, hidden_dim=192, depth=6, num_heads=3,
                  num_classes=num_classes, num_experts=8, top_k=2,
                  dtype=dtype)


# -- routing without drops ---------------------------------------------------
#
# The layer of a sparse decoder (``models/decoder.py``): a router over all
# ``num_experts``, the grouped products over the experts held here, and a
# shared expert. Nothing has a capacity: every (token, choice) that lands on
# a held expert is computed, whatever the imbalance. Beside ``MoEMlp`` (dense
# one-hot dispatch, capacity, drops), which the ViT family keeps.

#: rows of the grouped kernel's tile: a rung of the ladder is whole tiles
_ROW_TILE = 512
#: the routed result's name to a recomputation policy (``checkpoint_name``)
ROUTED_NAME = "moe_routed"
#: likewise the router's float32 logits, the ids of the experts it chose and
#: their scores
LOGITS_NAME, IDS_NAME, SCORES_NAME = (
    "moe_router_logits", "moe_router_ids", "moe_router_scores")


def buffer_rungs(pairs: int, held: int, num_experts: int, top_k: int = 0):
    """The lengths the sorted buffer may take, ascending, the last one the
    most rows that can land here (no capacity, no drop): ``pairs``, a row
    for every (token, choice), or, told ``top_k`` larger than ``held``,
    ``held`` rows a token, since a token's choices are distinct experts. A
    fair router lands ``pairs * held / num_experts`` rows on the held
    experts; the short rung is twice that, in whole tiles, where that is
    shorter than the last. A whole share has the one rung. Two rungs and
    not more: each is one more copy of the routed path to trace, lower,
    compile and load (PERF.md section 6, PR 28)."""
    most = pairs // top_k * held if top_k > held else pairs
    fair = -(-pairs * held // num_experts)
    short = 2 * -(-fair // _ROW_TILE) * _ROW_TILE
    return (short, most) if short < most else (most,)


class _ByToken(NamedTuple):
    """Where a short buffer's landed rows sit once put in token order: a
    token's rows side by side, in the order of its choices. ``R + reach -
    1`` places, ``reach`` the most rows one token can land, so that the
    ``reach`` from any place on are there to read."""

    rows: jax.Array    # the buffer's row at each place of token order
    token: jax.Array   # that row's token; past landed, no token's number
    count: jax.Array   # (N,) rows each token landed


def _by_token(order, landed, count, k, reach):
    """``order`` is the first ``R`` of the sorted buffer's (token, choice)
    pairs, ``landed`` of them real; a token lands ``reach`` rows at most
    (``k``, or the experts held where those are fewer). One sort of ``R``
    keys."""
    tokens = count.shape[0]
    pair = jnp.where(jnp.arange(order.shape[0]) < landed, order, tokens * k)
    rows = jnp.argsort(pair).astype(jnp.int32)
    return _ByToken(
        jnp.concatenate([rows, jnp.zeros((reach - 1,), jnp.int32)]),
        jnp.concatenate([jnp.take(pair, rows) // k,
                         jnp.full((reach - 1,), tokens + 1, jnp.int32)]),
        count)


def _spread_rows(x, order, k):
    return jnp.take(x, order // k, axis=0)


def _collect_rows(y, back, k, reach=None):
    if not isinstance(back, _ByToken):  # a row for every pair: by ``inverse``
        rows = jnp.take(y, back, axis=0).astype(jnp.float32)
        return rows.reshape(-1, k, y.shape[-1]).sum(axis=1).astype(y.dtype)
    # a short buffer: its rows in token order, at each place the float32 sum
    # of the rows of that place's token from there on (``reach`` at most, as
    # ``_by_token`` was told; in the order of the choices, as the full buffer
    # sums them), and of these each token's first
    rows = jnp.take(y, back.rows, axis=0, mode="clip")
    rung = y.shape[0]
    total = rows[:rung].astype(jnp.float32)
    for j in range(1, reach):
        same = (back.token[j:j + rung] == back.token[:rung])[:, None]
        total = total + jnp.where(same, rows[j:j + rung], 0).astype(
            jnp.float32)
    first = jnp.cumsum(back.count) - back.count
    sums = jnp.take(total.astype(y.dtype), first, axis=0, mode="clip")
    return jnp.where((back.count > 0)[:, None], sums, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _spread(x, order, back, k, reach=None):
    """Row ``a`` of the result is ``x[order[a] // k]``: ``x``'s rows, one per
    (token, choice) that ``order`` names, in the order it gives. The
    transpose of ``_collect``; both ways are gathers, never a scatter."""
    return _spread_rows(x, order, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _collect(y, order, back, k, reach=None):
    """Rows of ``y`` put back where ``order`` took them from and summed over
    each token's choices, in float32: ``(R, C) -> (N, C)``. ``back`` says
    where: the inverse of ``order`` when ``y`` has a row for every (token,
    choice), a ``_ByToken`` when it has the first ``R`` of them, with the
    ``reach`` it was made for."""
    return _collect_rows(y, back, k, reach)


_spread.defvjp(
    lambda x, order, back, k, reach: (
        _spread_rows(x, order, k), (order, back)),
    lambda k, reach, res, g: (
        _collect_rows(g, res[1], k, reach), None, None))
_collect.defvjp(
    lambda y, order, back, k, reach: (
        _collect_rows(y, back, k, reach), (order, back)),
    lambda k, reach, res, g: (_spread_rows(g, res[0], k), None, None))


def _where_chosen(ids, experts, values, axis):
    """``values``, broadcast against (N, K, E), summed over ``axis`` where
    token ``n``'s choice ``k`` is expert ``e``, zero elsewhere."""
    chosen = ids[:, :, None] == jnp.arange(experts, dtype=ids.dtype)
    return jnp.where(chosen, values, 0).sum(axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _chosen(scored, ids, experts):
    """``scored[n, ids[n, k]]``, (N, E) and (N, K) -> (N, K), a token's ids
    distinct: ``jnp.take_along_axis``'s values and gradient to the bit, as
    compares against the expert axis (``experts`` wide), selects and sums,
    so that no gather, scatter or sort is made for them. Each sum has one
    term that is not zero. Its residual is ``ids``: the backward pass
    compares again, and neither pass writes anything (N, K, E) wide."""
    return _where_chosen(ids, experts, scored[:, None, :], 2)


_chosen.defvjp(
    lambda scored, ids, experts: (
        _where_chosen(ids, experts, scored[:, None, :], 2), ids),
    lambda experts, ids, g: (
        _where_chosen(ids, experts, g[:, :, None], 1), None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _switch(branches, index, routing, floats):
    """``branches[index](routing, *floats)``: ``routing`` integers, the
    result differentiated in ``floats``.
    Its residuals are its operands, and the backward pass switches again:
    ``lax.switch`` left to AD hands out every branch's residuals, zeros for
    the ones not taken."""
    return jax.lax.switch(index, branches, routing, *floats)


def _switch_bwd(branches, res, g):
    index, routing, floats = res
    pulls = [lambda routing, floats, g, branch=branch: jax.vjp(
        functools.partial(branch, routing), *floats)[1](g)
        for branch in branches]
    return None, None, jax.lax.switch(index, pulls, routing, floats, g)


_switch.defvjp(
    lambda branches, index, routing, floats: (
        jax.lax.switch(index, branches, routing, *floats),
        (index, routing, floats)),
    _switch_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` (R, K) rows in groups of ``group_sizes`` (G,) against ``rhs``
    (G, K, N): row ``r`` of group ``g`` times ``rhs[g]``. Rows past the
    groups' sum belong to no group: what comes out for them is unspecified
    and never read. ``jax.lax.ragged_dot``: on a TPU the compiler emits its
    own grouped-product kernel for it (a Mosaic custom call that walks only
    the row tiles of real groups, forward and both backward products), named
    here like every kernel call (``tpu_ddp.kernel.grouped_matmul``)."""
    from tpu_ddp.telemetry.phases import kernel_scope

    with jax.named_scope(kernel_scope("grouped_matmul")):
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def _routed(rung, k, dtype, routing, xf, w_gate, w_up, w_down, weights):
    """The held experts' part of the layer over a sorted buffer of ``rung``
    rows: ``xf`` (N, C) tokens, ``weights`` (N, k) of their choices,
    ``routing`` = ``order``, ``inverse`` (N * k,), ``load`` (held,) and, for
    a buffer shorter than ``order``, the rows each token landed (N,).
    ``rung`` holds what landed (``load.sum()``): the caller's to see to.
    An expert is ``down(silu(gate(x)) * up(x))``, or, with ``w_gate`` None,
    the plain ``down(relu(up(x)) ** 2)``."""
    from tpu_ddp.telemetry.phases import module_scope

    order, back, load = routing[:3]
    reach = None
    with jax.named_scope(module_scope("moe_dispatch")):
        if rung < order.shape[0]:
            # a token's choices are distinct experts, so it lands ``k`` rows
            # at most, or as many as there are experts held
            order, reach = order[:rung], min(k, load.shape[0])
            back = _by_token(order, load.sum(), routing[3], k, reach)
        # the buffer's first ``landed`` rows are real. What a grouped
        # product leaves in the others is unspecified (on the chip:
        # whatever the memory held, NaN included), so each of its results
        # is selected to zero there before anything reads it, forward and
        # (the select's transpose) backward
        real = (jnp.arange(rung) < load.sum())[:, None]
        keep = lambda a: jnp.where(real, a, 0)  # noqa: E731
        rows = keep(_spread(xf, order, back, k, reach))

    with jax.named_scope(module_scope("moe_experts")):
        if w_gate is None:
            h = keep(grouped_matmul(rows, w_up.astype(dtype), load))
            h = jnp.square(nn.relu(h))
        else:
            width = w_gate.shape[-1]
            w_in = jnp.concatenate([w_gate, w_up], axis=-1).astype(dtype)
            h = keep(grouped_matmul(rows, w_in, load))
            h = nn.silu(h[:, :width]) * h[:, width:]
        out = keep(grouped_matmul(h, w_down.astype(dtype), load))

    with jax.named_scope(module_scope("moe_combine")):
        w_sorted = jnp.take(weights.reshape(-1), order)[:, None]
        return _collect(out * w_sorted.astype(out.dtype), order, back, k,
                        reach)


#: traced once per rung and shapes: the layers of a stack, the forward and
#: the backward rule of ``_switch`` and a recomputed layer share the trace
_routed_once = jax.jit(_routed, static_argnums=(0, 1, 2))


class SwiGLU(nn.Module):
    """``down(silu(gate(x)) * up(x))``, no biases, float32 weights."""

    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        h = nn.silu(dense(self.width, "gate")(x)) * dense(self.width, "up")(x)
        return dense(x.shape[-1], "down")(h)


class Relu2MLP(nn.Module):
    """``down(relu(up(x)) ** 2)``: ``SwiGLU``'s sibling without a gate, no
    biases, float32 weights."""

    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        return dense(x.shape[-1], "down")(
            jnp.square(nn.relu(dense(self.width, "up")(x))))


class DroplessMoE(nn.Module):
    """Top-k routed experts without a capacity, the share of them that lives
    here, and a shared expert.

    ``share`` (``parallel.expert_parallel.ExpertShare``: ``num_experts``,
    ``held``, ``offset``) says which experts this layer holds. The router
    scores all ``num_experts`` in float32 (``score``: ``sigmoid`` of each
    logit, or a ``softmax`` over all of them; the ``top_k`` largest,
    normalised to sum to one, times ``scaling``); the (token, choice) pairs
    that name a held expert are sorted by expert, pushed through the grouped
    products, weighted and summed back onto their tokens; the shared expert
    (``shared_width`` > 0) is added for every token. With a share smaller
    than the whole the result is partial: what the other shares' experts
    would add is theirs to add. ``sum over shares of (y - shared) + shared``
    is the whole layer.

    The kinds of layer it is, by three options. ``gated`` (the default): an
    expert, routed or shared, is a SwiGLU, ``down(silu(gate(x)) * up(x))``;
    without it the plain ``down(relu(up(x)) ** 2)``, which has no ``w_gate``
    and no ``shared/gate``. ``latent`` > 0: the routed experts work in a
    space of that width, between ``latent_down`` (hidden to latent, on every
    token) and ``latent_up`` (latent to hidden, on the summed routed
    result); router and shared expert read the hidden state. Both
    projections are linear, so partial results still add.
    ``selection_bias``: a float32 leaf ``router_bias`` (num_experts,) is
    added to the scores for the choice only; the weights are the chosen
    scores without it, so no gradient reaches it and no decay applies to
    its one axis: it stays what it was set to.

    Static shapes without drops: the (token, choice) pairs are sorted by
    expert, the held ones first, and the routed path (``_routed``) walks the
    first ``R`` rows of that order, ``R`` a rung of a short static ladder
    read off the shapes and the share (``buffer_rungs``: twice what a fair
    router lands here, and the most that can land: a row for every pair, or
    ``held`` rows a token where a token makes more choices than that).
    Each call counts what landed and takes, on the device, the shortest
    rung that holds it (``_switch``; per shard under ``shard_map``), so a
    router however skewed drops nothing, and a whole share has the one rung
    and traces no switch. The grouped products compute only the rows of
    held experts, however many those are.

    Four values carry names a stack that recomputes its layers may keep
    (``save_only_these_names``; ``HybridDecoder`` keeps all four). The
    routed result, ``ROUTED_NAME`` ((tokens, ``C``) in ``dtype``): the
    backward pass then walks the ladder's branch once and not twice.
    Under ``selection_bias`` the router's logits, ``LOGITS_NAME`` ((tokens,
    ``num_experts``) float32), the chosen ids, ``IDS_NAME``, and their
    scores, ``SCORES_NAME`` ((tokens, ``top_k``) int32 and float32): the
    backward pass then makes neither the float32 product nor the ``top_k``
    again, only the sigmoid and the normalisation, elementwise, and the
    sorts of the dispatch. The scores are read off the sigmoid's result by
    ``_chosen``: compares of the ids against the expert axis, selects and
    sums in both passes, where ``take_along_axis`` was a gather and its
    transpose a scatter with a sort (on a v5e dearer than product and
    ``top_k`` together). At 16,384 tokens, 512 experts, 22 choices and a
    latent width of 1,024 what is kept is 33.5 MB, 33.5 MB and twice 1.4
    MB a block (PERF.md section 6, PR 33, PR 34 and PR 37).
    Without ``selection_bias`` scores and ids are the ``top_k``'s own
    results and nothing carries a name: that layer's program is what it
    was.

    Sows ``counters/expert_load``: (held,) int32, the (token, choice) pairs
    each held expert got this call; and ``counters/expert_rows_walked``:
    int32, the rung this call took. Stacked expert weights: ``w_gate``,
    ``w_up`` (held, C, F), ``w_down`` (held, F, C), ``C`` the latent width
    where there is one, so that expert parallelism is a ``PartitionSpec`` on
    the leading axis.
    """

    share: object
    top_k: int
    expert_width: int
    shared_width: int = 0
    scaling: float = 1.0
    dtype: jnp.dtype = jnp.float32
    gated: bool = True
    latent: int = 0
    selection_bias: bool = False
    #: ``sigmoid`` or ``softmax``: what the router makes of its logits
    score: str = "sigmoid"

    @nn.compact
    def __call__(self, x):  # (B, T, C) -> (B, T, C)
        from tpu_ddp.telemetry.phases import module_scope

        score = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[
            self.score]

        B, T, C = x.shape
        E, held, offset = (self.share.num_experts, self.share.held,
                           self.share.offset)
        K, F = self.top_k, self.expert_width
        W = self.latent or C  # the width the routed experts work in
        xf = x.reshape(B * T, C).astype(self.dtype)
        stacked = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        w_gate = self.param("w_gate", stacked, (held, W, F),
                            jnp.float32) if self.gated else None
        w_up = self.param("w_up", stacked, (held, W, F), jnp.float32)
        w_down = self.param("w_down", stacked, (held, F, W), jnp.float32)
        cast = lambda w: None if w is None else w.astype(  # noqa: E731
            self.dtype)

        with jax.named_scope(module_scope("moe_route")):
            logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST,
                              name="router")(xf.astype(jnp.float32))
            if self.selection_bias:
                bias = self.param("router_bias", nn.initializers.zeros, (E,),
                                  jnp.float32)
                scored = score(checkpoint_name(logits, LOGITS_NAME))
                _, ids = jax.lax.top_k(scored + bias, K)        # (N, K)
                ids = checkpoint_name(ids, IDS_NAME)
                scores = checkpoint_name(_chosen(scored, ids, E), SCORES_NAME)
            else:
                scores, ids = jax.lax.top_k(score(logits), K)
            weights = (scores / scores.sum(axis=-1, keepdims=True)
                       * self.scaling)
            self.sow("intermediates", "expert_ids", ids)

        tokens = xf
        if self.latent:
            with jax.named_scope(module_scope("moe_latent")):
                tokens = nn.Dense(W, use_bias=False, dtype=self.dtype,
                                  name="latent_down")(xf)

        rungs = buffer_rungs(B * T * K, held, E, K)
        with jax.named_scope(module_scope("moe_dispatch")):
            local = ids.reshape(-1) - offset                    # (N*K,)
            group = jnp.where((local >= 0) & (local < held), local, held)
            order = jnp.argsort(group, stable=True).astype(jnp.int32)
            # a buffer with a row for every pair finds its way back by this
            inverse = jnp.argsort(order).astype(
                jnp.int32) if rungs[-1] == order.shape[0] else None
            load = jnp.sum(group[:, None] == jnp.arange(held)[None, :],
                           axis=0, dtype=jnp.int32)             # (held,)
            self.sow("counters", "expert_load", load)

        def landed_by_token():
            return jnp.sum(group.reshape(-1, K) < held, axis=1,
                           dtype=jnp.int32)                     # (N,)

        walk = [functools.partial(_routed_once, rung, K, self.dtype)
                for rung in rungs]
        routing = (order, inverse, load)
        if len(rungs) == 1:
            if inverse is None:
                with jax.named_scope(module_scope("moe_dispatch")):
                    routing += (landed_by_token(),)
            y = _routed(rungs[0], K, self.dtype, routing, tokens, w_gate,
                        w_up, w_down, weights)
            walked = rungs[0]
        else:  # the shortest buffer that holds what landed, each call
            # the weights cross the switch as the matrix unit takes them,
            # and their gradients come back so: in float32 the gradients
            # of a layer's experts are 0.4 GB that nothing else would hold
            with jax.named_scope(module_scope("moe_experts")):
                floats = (tokens, cast(w_gate), cast(w_up), cast(w_down),
                          weights)
            with jax.named_scope(module_scope("moe_dispatch")):
                landed = load.sum()
                index = sum((landed > rung).astype(jnp.int32)
                            for rung in rungs[:-1])
                count = landed_by_token()
                walked = jnp.asarray(rungs, jnp.int32)[index]
            y = _switch(tuple(walk), index, routing + (count,), floats)
        self.sow("counters", "expert_rows_walked", jnp.int32(walked))
        y = checkpoint_name(y, ROUTED_NAME)

        if self.latent:
            with jax.named_scope(module_scope("moe_latent")):
                y = nn.Dense(C, use_bias=False, dtype=self.dtype,
                             name="latent_up")(y)
        if self.shared_width:
            with jax.named_scope(module_scope("moe_shared")):
                y = y + (SwiGLU if self.gated else Relu2MLP)(
                    self.shared_width, dtype=self.dtype, name="shared")(xf)
        return y.reshape(B, T, C)
