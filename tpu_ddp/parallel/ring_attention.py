"""Ring attention: sequence-parallel self-attention over a mesh axis.

First-class long-context support (build brief; the reference has no
attention or sequence dimension at all — SURVEY.md §5.7 documents the
absence). Each device holds a sequence shard of Q/K/V; K/V blocks rotate
around the ring via ``lax.ppermute`` (neighbor exchange over ICI) while a
numerically-stable online softmax (flash-attention style running max /
denominator) accumulates the output. Peak memory per device is O(T_local^2)
instead of O(T^2), and the K/V transfer overlaps with the current block's
compute under XLA's latency-hiding scheduler.

Usage: inside ``jax.shard_map`` with the sequence dimension sharded over
``axis_name`` — e.g. bind it as a ViT's ``attention_impl``:

    attn = functools.partial(ring_attention, axis_name="sequence")
    model = ViT(attention_impl=attn)

Semantics: exact (not approximate) — output equals full attention up to
float reassociation; pinned by tests/test_ring_attention.py. ``causal``
gives decoder attention over the global sequence: with sequence-sharded
chunks the only partial tile is the self-aligned diagonal (the initial
local block — the kernel's static ``causal`` flag, no offsets needed);
every rotated chunk is either fully visible (its source device precedes
this one) or skipped entirely via ``lax.cond``, so the causal ring does
~half the tile work of the bidirectional one. ``kv_mask`` (B, T_local,
nonzero = attend) handles padding: it rotates around the ring with its
K/V chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# Finite -inf stand-in and the shared full-tile visibility builder (see
# ops/flash_attention.py): exp(NEG - finite) underflows to exactly 0.0 in
# f32, and every jnp path here must mask identically to the kernels.
from tpu_ddp.ops.flash_attention import NEG, _bhqk_visibility


def _block(q, k, v, scale, causal=False, kv_mask=None):
    """One (q-block, k-block) attention tile with raw (unnormalized)
    accumulators: returns o = exp(s - m) @ v, running max m, denom l.
    ``causal`` is the self-aligned diagonal case (Tq == Tk); ``kv_mask``
    (B, Tk) masks key columns multiplicatively, so fully-masked rows carry
    l == 0 (the caller's final normalization guards the division)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # (B,H,Tq,Tk)
    vis = _bhqk_visibility(s.shape[-2], s.shape[-1], causal, kv_mask)
    if vis is not None:
        s = jnp.where(vis, s, NEG)
    m = s.max(axis=-1)  # (B,H,Tq)
    p = jnp.exp(s - m[..., None])
    if kv_mask is not None:
        # all-masked rows have m == NEG and p == 1 on masked entries;
        # restore exact zeros (causal-only rows always see >=1 column)
        p = p * vis
    l = p.sum(axis=-1)  # (B,H,Tq)
    o = jnp.einsum("bhqk,bkhd->bhqd", p, v)  # (B,H,Tq,D)
    return o, m, l


# Ring hops are unrolled below this axis size (a fixed chain XLA can
# software-pipeline: each hop's collective-permute overlaps the next
# tile's compute) and rolled into ONE lax.scan body above it — a
# 256-chip pod ring would otherwise unroll hundreds of hops (x 2 passes
# for the flash ring's custom VJP) into the HLO, exploding compile time.
# Compiler-friendly control flow is the point: the scan body is compiled
# once regardless of ring size. Shared by the plain ring, the flash-ring
# forward, and its backward.
_UNROLL_MAX = 8


def _unroll_or_scan(hop, carry, steps: int, start: int = 1):
    """Run ``carry = hop(carry, i)`` for i in [start, start+steps) —
    unrolled when small, one lax.scan otherwise. ``hop`` must be
    carry-type-preserving; ``i`` is a Python int on the unrolled path and
    a traced scalar under scan (callers' predicates handle both)."""
    if steps <= _UNROLL_MAX:
        for i in range(start, start + steps):
            carry = hop(carry, i)
        return carry
    carry, _ = lax.scan(lambda c, i: (hop(c, i), None), carry,
                        start + jnp.arange(steps))
    return carry


def _rotated(axis_name, perm, *xs):
    """ppermute each non-None array one hop around the ring."""
    return tuple(None if x is None else lax.ppermute(x, axis_name, perm)
                 for x in xs)


def ring_attention(q, k, v, *, axis_name: str, causal: bool = False,
                   kv_mask=None):
    """q,k,v: (B, T_local, H, D) sequence-sharded over `axis_name`.
    Returns (B, T_local, H, D) — this device's shard of exact full
    attention over the global sequence. ``causal`` masks by GLOBAL
    position (device order along `axis_name` is sequence order);
    ``kv_mask`` (B, T_local) is this device's key-padding shard and
    rotates with its K/V."""
    n = lax.axis_size(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.float32)

    # initial block = the self-aligned diagonal: the ONLY causal-partial
    # tile in the whole ring
    o, m, l = _block(q, k, v, scale, causal=causal, kv_mask=kv_mask)
    perm = [(i, (i + 1) % n) for i in range(n)]
    idx = lax.axis_index(axis_name)

    def hop(carry, i):
        o, m, l, k, v, km = carry
        k, v, km = _rotated(axis_name, perm, k, v, km)

        def visible(_):
            o2, m2, l2 = _block(q, k, v, scale, kv_mask=km)
            m_new = jnp.maximum(m, m2)
            a1 = jnp.exp(m - m_new)
            a2 = jnp.exp(m2 - m_new)
            return (o * a1[..., None] + o2 * a2[..., None],
                    m_new, l * a1 + l2 * a2)

        if causal:
            # after i hops this device holds chunk (idx - i) mod n, which
            # precedes every local q position iff i <= idx; otherwise the
            # whole chunk is in the future — skip its tile entirely
            o, m, l = lax.cond(i <= idx, visible, lambda _: (o, m, l), None)
        else:
            o, m, l = visible(None)
        return o, m, l, k, v, km

    carry = _unroll_or_scan(hop, (o, m, l, k, v, kv_mask), n - 1)
    o, m, l = carry[0], carry[1], carry[2]
    if kv_mask is not None:
        l = jnp.where(l > 0, l, 1.0)  # fully-masked rows output exact 0
    out = o / l[..., None]  # (B,H,Tq,D)
    return out.transpose(0, 2, 1, 3)  # -> (B, Tq, H, D)


# ------------------------------------------------------ flash ring --
# Ring attention with the Pallas flash kernel as the per-block tile
# (Ring Attention = blockwise flash attention with the KV blocks living
# on other devices). The jnp ring above materializes a full
# (B,H,T_local,T_local) score tile per step in f32; the flash version
# keeps tiles in VMEM at (block_q x block_k), so T_local scales to the
# long-context regime. Exactness is unchanged — same online-softmax
# math, pinned against full attention by tests/test_ring_attention.py.
#
# Gradients: the flash backward kernels consume the GLOBAL (out, lse,
# di=rowsum(g*out)) and a KV block, which is exactly the blockwise
# decomposition of full-attention's backward — so the backward is a
# second ring pass: dq accumulates locally while (k, v, dk, dv) rotate
# together; after n hops the dk/dv accumulators arrive back at their
# owning device complete.

def _canon_lse(lse_folded, B, H, T):
    # kernel layout (B*H, T, LANE) lane-broadcast -> canonical (B, H, T)
    return lse_folded[:, :, 0].reshape(B, H, T)


def _fold_lse(lse):
    from tpu_ddp.ops.flash_attention import LANE

    B, H, T = lse.shape
    return jnp.broadcast_to(
        lse.reshape(B * H, T, 1), (B * H, T, LANE)
    ).astype(jnp.float32)


def _use_kernels(q, block_q, block_k, interpret, kv_mask=None) -> bool:
    from tpu_ddp.ops.flash_attention import (
        _interpreted_under_shard_map,
        _resolve_interpret,
        _unpadded_plan,
    )

    # interpret-mode pallas under shard_map trips the hlo-interpreter vma
    # check (see ops/flash_attention.py) — the jnp tile there, CPU only
    if _interpreted_under_shard_map(q, _resolve_interpret(interpret)):
        return False
    # the kernels were asked for: a local block they cannot tile raises
    # (naming the shape) rather than quietly taking the jnp tile
    _unpadded_plan(q.shape, block_q, block_k, kv_mask is not None)
    return True


def _block_fwd(q, k, v, scale, use_kernels, block_q, block_k, interpret,
               causal=False, kv_mask=None):
    """(o_normalized f32 (B,T,H,D), lse (B,H,T)) for one KV block."""
    B, T, H, D = q.shape
    if use_kernels:
        from tpu_ddp.ops.flash_attention import (
            _flash_forward,
            _resolve_interpret,
        )

        o, lse_f = _flash_forward(
            q, k, v, kv_mask, block_q=block_q, block_k=block_k,
            interpret=_resolve_interpret(interpret), causal=causal,
        )
        return o.astype(jnp.float32), _canon_lse(lse_f, B, H, T)
    o_u, m, l = _block(q, k, v, scale, causal=causal, kv_mask=kv_mask)
    if kv_mask is not None:
        # fully-masked rows: o == 0 exactly, lse == NEG so _combine gives
        # them zero weight against any block that does see a key
        safe_l = jnp.where(l > 0, l, 1.0)
        o = (o_u / safe_l[..., None]).transpose(0, 2, 1, 3)
        return o.astype(jnp.float32), jnp.where(
            l > 0, m + jnp.log(safe_l), NEG)
    o = (o_u / l[..., None]).transpose(0, 2, 1, 3)  # -> (B,T,H,D)
    return o.astype(jnp.float32), m + jnp.log(l)


def _combine(o, lse, o2, lse2):
    """Merge two normalized blocks: o in (B,T,H,D) f32, lse in (B,H,T)."""
    lse_new = jnp.logaddexp(lse, lse2)
    w1 = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]  # (B,T,H,1)
    w2 = jnp.exp(lse2 - lse_new).transpose(0, 2, 1)[..., None]
    return o * w1 + o2 * w2, lse_new


def _ring_fwd_impl(q, k, v, kv_mask, axis_name, block_q, block_k,
                   interpret, causal):
    n = lax.axis_size(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    use_k = _use_kernels(q, block_q, block_k, interpret, kv_mask)
    perm = [(i, (i + 1) % n) for i in range(n)]
    idx = lax.axis_index(axis_name)

    # self-aligned diagonal: the only causal-partial tile (static flag)
    o, lse = _block_fwd(q, k, v, scale, use_k, block_q, block_k, interpret,
                        causal=causal, kv_mask=kv_mask)

    def hop(carry, i):
        o, lse, k, v, km = carry
        k, v, km = _rotated(axis_name, perm, k, v, km)

        def visible(_):
            o2, lse2 = _block_fwd(q, k, v, scale, use_k, block_q, block_k,
                                  interpret, kv_mask=km)
            return _combine(o, lse, o2, lse2)

        if causal:
            o, lse = lax.cond(i <= idx, visible, lambda _: (o, lse), None)
        else:
            o, lse = visible(None)
        return o, lse, k, v, km

    carry = _unroll_or_scan(hop, (o, lse, k, v, kv_mask), n - 1)
    o, lse = carry[0], carry[1]
    return o.astype(q.dtype), lse


def _block_bwd(q, k, v, out, lse, g, scale, use_kernels, block_q, block_k,
               interpret, causal=False, kv_mask=None):
    """(dq, dk, dv) contribution of ONE KV block to the global attention
    backward; ``out``/``lse`` are the COMBINED forward results."""
    if use_kernels:
        from tpu_ddp.ops.flash_attention import (
            _flash_backward,
            _resolve_interpret,
        )

        return _flash_backward(
            q, k, v, out, _fold_lse(lse), g, kv_mask,
            block_q=block_q, block_k=block_k,
            interpret=_resolve_interpret(interpret), causal=causal,
        )
    # jnp fallback: p = exp(s - lse_total); ds = p * (dP - di) * scale
    f32 = jnp.float32
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32), k.astype(f32)) * scale
    vis = _bhqk_visibility(s.shape[-2], s.shape[-1], causal, kv_mask)
    if vis is not None:
        s = jnp.where(vis, s, NEG)
    p = jnp.exp(s - lse[..., None])                       # (B,H,Tq,Tk)
    if kv_mask is not None:
        # dead rows carry lse == NEG: exp(NEG - NEG) == 1 there; restore 0
        p = p * vis
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, g.astype(f32))
    dp = jnp.einsum("bqhd,bkhd->bhqk", g.astype(f32), v.astype(f32))
    di = jnp.sum(g.astype(f32) * out.astype(f32), axis=-1)  # (B,Tq,H)
    ds = p * (dp - di.transpose(0, 2, 1)[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k.astype(f32))
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(f32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _ring_flash(q, k, v, kv_mask, axis_name: str, block_q: int,
                block_k: int, interpret: bool | None, causal: bool):
    out, _ = _ring_fwd_impl(q, k, v, kv_mask, axis_name, block_q, block_k,
                            interpret, causal)
    return out


def _rf_fwd(q, k, v, kv_mask, axis_name, block_q, block_k, interpret,
            causal):
    out, lse = _ring_fwd_impl(q, k, v, kv_mask, axis_name, block_q,
                              block_k, interpret, causal)
    return out, (q, k, v, kv_mask, out, lse)


def _rf_bwd(axis_name, block_q, block_k, interpret, causal, res, g):
    q, k, v, kv_mask, out, lse = res
    n = lax.axis_size(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    use_k = _use_kernels(q, block_q, block_k, interpret, kv_mask)
    perm = [(i, (i + 1) % n) for i in range(n)]
    idx = lax.axis_index(axis_name)

    f32 = jnp.float32
    # clean zeros marked varying over the inputs' full axis set — on a
    # 2-D data x sequence mesh the scan carry type must vary over BOTH
    # axes, which a plain jnp.zeros (device-invariant) does not. pvary,
    # not x*0: multiplying would turn a non-finite input element into a
    # NaN in the accumulator before any hop.
    def _zeros_like_varying(x):
        z = jnp.zeros(x.shape, f32)
        vma = tuple(jax.typeof(x).vma)
        return lax.pcast(z, vma, to="varying") if vma else z

    dq = _zeros_like_varying(q)
    dk = _zeros_like_varying(k)
    dv = _zeros_like_varying(v)

    def contribution(dq, dk, dv, k, v, km, blk_causal):
        dq_b, dk_b, dv_b = _block_bwd(
            q, k, v, out, lse, g, scale, use_k, block_q, block_k,
            interpret, causal=blk_causal, kv_mask=km,
        )
        return (dq + dq_b.astype(f32), dk + dk_b.astype(f32),
                dv + dv_b.astype(f32))

    def hop(carry, i):
        dq, dk, dv, k, v, km = carry
        # hop 0 is only ever the static pre-call below (scan covers i >= 1,
        # where i is traced — isinstance keeps the == off tracers)
        if causal and isinstance(i, int) and i == 0:
            # self-aligned diagonal, static causal kernel flag
            dq, dk, dv = contribution(dq, dk, dv, k, v, km, True)
        elif causal:
            # chunk (idx - i) mod n: in this device's past iff i <= idx
            dq, dk, dv = lax.cond(
                i <= idx,
                lambda _: contribution(dq, dk, dv, k, v, km, False),
                lambda _: (dq, dk, dv), None)
        else:
            dq, dk, dv = contribution(dq, dk, dv, k, v, km, False)
        # rotate the KV blocks AND their gradient accumulators together:
        # after the remaining hops they arrive home complete. (On the
        # unrolled path the final k/v rotation is dead code XLA drops.)
        k, v, km = _rotated(axis_name, perm, k, v, km)
        dk = lax.ppermute(dk, axis_name, perm)
        dv = lax.ppermute(dv, axis_name, perm)
        return dq, dk, dv, k, v, km

    # hop 0 (the diagonal) runs statically so the kernel's causal flag is
    # a compile-time constant; the remaining n-1 hops roll into a scan on
    # big rings like the forward
    carry = hop((dq, dk, dv, k, v, kv_mask), 0)
    carry = _unroll_or_scan(hop, carry, n - 1)
    dq, dk, dv = carry[0], carry[1], carry[2]
    dm = None if kv_mask is None else jnp.zeros_like(kv_mask)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dm


_ring_flash.defvjp(_rf_fwd, _rf_bwd)


def ring_flash_attention(q, k, v, axis_name: str, block_q: int = 128,
                         block_k: int = 128,
                         interpret: bool | None = None, *,
                         causal: bool = False, kv_mask=None):
    """Ring attention with Pallas flash tiles. Same contract as
    ``ring_attention`` (q,k,v: (B, T_local, H, D) sequence-sharded over
    ``axis_name``; exact attention over the global sequence, causal when
    ``causal``; ``kv_mask`` (B, T_local) key-padding shard rotates with
    its K/V); falls back to the fused-jnp tile when the shapes don't fit
    the kernel planner or under interpret-mode shard_map. Keyword-friendly
    wrapper: custom_vjp nondiff_argnums require positional passing
    internally."""
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.float32)
    return _ring_flash(q, k, v, kv_mask, axis_name, block_q, block_k,
                       interpret, causal)


def sequence_sharded_attention(mesh, axis_name: str = "sequence"):
    """Convenience: shard_map-wrapped ring attention for (B, T, H, D) global
    arrays with T sharded over `axis_name`. Mostly for tests/demos — inside
    a full SP model you call ring_attention directly from the model's
    shard_map context."""
    from jax.sharding import PartitionSpec as P

    import functools

    fn = functools.partial(ring_attention, axis_name=axis_name)
    spec = P(None, axis_name)  # shard T (dim 1)
    return jax.jit(
        jax.shard_map(
            lambda q, k, v: fn(q, k, v),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )
    )
