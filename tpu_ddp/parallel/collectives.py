"""Named-axis collective wrappers.

The reference's only collective is NCCL allreduce hidden inside DDP backward
hooks (``main.py:38,63``; SURVEY.md §3.3). Here collectives are explicit,
traceable ops lowered by XLA:TPU onto ICI (intra-slice) / DCN (cross-slice),
with comm/compute overlap handled by XLA's latency-hiding scheduler — the
in-tree replacement for DDP's C++ bucketing Reducer (SURVEY.md §2.6).

These are thin, named wrappers so call sites read as intent ("sync grads")
rather than mechanism; all of them are only valid inside shard_map/vmap with
the axis bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax._src.lax.parallel import all_gather_invariant

# ---- ring hop hook (the comms-observatory / chaos seam) ------------------
#
# When installed, every ring hop emits a host callback carrying the hop's
# identity (kind / wire dtype / axis / hop index / wire bytes) plus a
# traced probe scalar that forces data-dependent ordering. The gate is
# checked at TRACE time: with no hook installed the traced program is
# byte-identical to before (no custom-calls), so analyze/lint
# fingerprints and the compile cache stay clean. Install BEFORE the step
# compiles (the Trainer does this in __init__; an already-jitted step
# keeps whatever the hook state was when it traced).

_RING_HOP_HOOK = None

#: ring wire mode -> the HLO dtype token the hop's payload carries
_MODE_WIRE_DTYPE = {"f32": "f32", "bf16": "bf16", "int8": "s8"}


def set_ring_hop_hook(hook):
    """Install (or clear, with None) the process-wide ring hop hook:
    ``hook(probe, *, kind, dtype, axis, hop, n_hops, wire_bytes)``,
    called from ``jax.debug.callback`` once per device per hop. Returns
    the previous hook (restore-on-exit idiom)."""
    global _RING_HOP_HOOK
    prev = _RING_HOP_HOOK
    _RING_HOP_HOOK = hook
    return prev


def _dispatch_hop(probe, **info):
    hook = _RING_HOP_HOOK  # read at CALL time: a cleared hook goes quiet
    if hook is not None:
        hook(probe, **info)


def _emit_hop(probe, *, kind, mode, axis, hop, n_hops, wire_bytes):
    """Trace the hop callback (only reached when a hook was installed at
    trace time)."""
    jax.debug.callback(
        functools.partial(
            _dispatch_hop, kind=kind,
            dtype=_MODE_WIRE_DTYPE.get(mode, mode), axis=axis, hop=hop,
            n_hops=n_hops, wire_bytes=int(wire_bytes)),
        probe)


def psum(x, axis: str):
    return lax.psum(x, axis_name=axis)


def pmean(x, axis: str):
    return lax.pmean(x, axis_name=axis)


def all_gather(x, axis: str, *, tiled: bool = True):
    return lax.all_gather(x, axis_name=axis, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_dimension: int = 0):
    return lax.psum_scatter(x, axis_name=axis, scatter_dimension=scatter_dimension, tiled=True)


def ppermute(x, axis: str, perm):
    return lax.ppermute(x, axis_name=axis, perm=perm)


#: scope prefixes the ZeRO-3 prefetch schedule stamps on its collectives.
#: analysis/lint.py's COL001 zero3 pin greps the compiled HLO's op_name
#: metadata (and the traced jaxpr's name stacks) for EXACTLY these — the
#: schedule contract is carried in the program itself, not in a side
#: channel, so a rebuilt/rescheduled program is re-audited for free.
ZERO3_PREFETCH_SCOPE = "tpu_ddp.zero3_prefetch/b"
ZERO3_HANDOFF_SCOPE = "tpu_ddp.zero3_handoff/b"
ZERO3_SERIAL_SCOPE = "tpu_ddp.zero3_serial_gather"


def prefetched_block_gather(blocks, axis: str, *, prefetch: bool = True):
    """All-gather a layer-granular sequence of parameter blocks on the
    ZeRO-3 double-buffered prefetch schedule.

    ``blocks`` is a list of blocks, each a list of flat 1-D local shards
    laid out like :class:`~tpu_ddp.parallel.zero.Zero1Partition`'s update
    space (shard i owns rows ``[i*S, (i+1)*S)`` of the padded leaf).
    Returns the same nesting with every shard all-gathered (tiled) back
    to its full padded length.

    With ``prefetch=True`` (the product schedule) block ``k+1``'s
    gathers are ISSUED while block ``k`` is still the block about to
    compute, then both are tied together with one
    ``lax.optimization_barrier`` per boundary: block ``k``'s gathered
    leaves only become available to their first consuming op through the
    barrier that also carries block ``k+1``'s in-flight gather. That
    makes the overlap window STRUCTURAL — no scheduler (XLA's
    latency-hiding scheduler included) can sink the next block's
    all-gather below the current block's compute — while keeping the
    live-gathered set bounded at two blocks (current + next), which is
    the whole HBM story of parameter streaming. Each gather carries a
    ``tpu_ddp.zero3_prefetch/b<k>`` named scope and each boundary a
    ``tpu_ddp.zero3_handoff/b<k>`` scope; the COL001 zero3 order pin
    audits the compiled program by those names and fails closed when
    they are absent.

    ``prefetch=False`` is the serialized (no-lookahead) schedule kept
    ONLY as the injected violation: every block gathered just-in-time
    under one ``tpu_ddp.zero3_serial_gather`` scope, no handoff chain —
    the program ``tests/test_zero3.py`` feeds the linter to prove the
    pin trips.
    """
    if not prefetch:
        with jax.named_scope(ZERO3_SERIAL_SCOPE):
            return [[all_gather(x, axis, tiled=True) for x in blk]
                    for blk in blocks]

    def gather_block(k):
        with jax.named_scope(f"{ZERO3_PREFETCH_SCOPE}{k}"):
            return [all_gather(x, axis, tiled=True) for x in blocks[k]]

    out = []
    cur = gather_block(0) if blocks else []
    for k in range(len(blocks)):
        nxt = gather_block(k + 1) if k + 1 < len(blocks) else None
        if nxt is not None:
            with jax.named_scope(f"{ZERO3_HANDOFF_SCOPE}{k}"):
                cur, nxt = lax.optimization_barrier((cur, nxt))
        out.append(cur)
        cur = nxt
    return out


def ring_shift(x, axis: str, shift: int = 1):
    """Shift values around the ring on `axis` (neighbor exchange over ICI).
    Building block for ring attention / pipeline microbatch handoff."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    return lax.axis_size(axis)


def _quant(p, mode: str, block: int, kernels: bool):
    """One wire payload: the fused Pallas quantizer when the kernel
    switch is on (int8 only — f32/bf16 payloads are casts, nothing to
    fuse), else the jnp reference. Bit-identical by contract
    (``ops/fused_quant.py``)."""
    from tpu_ddp.parallel.compression import quantize_chunk

    if kernels and mode == "int8":
        from tpu_ddp.ops.fused_quant import fused_quant

        return fused_quant(p, block)
    return quantize_chunk(p, mode, block)


def _dequant(payload, mode: str, block: int, size: int, kernels: bool,
             add_to=None):
    """Payload -> f32 chunk, optionally fused with the ring's carry
    accumulate (one pass instead of dequantize-then-add)."""
    from tpu_ddp.parallel.compression import dequantize_chunk

    if kernels and mode == "int8":
        from tpu_ddp.ops.fused_quant import fused_dequant

        return fused_dequant(payload, block, size, add_to=add_to)
    d = dequantize_chunk(payload, mode, block, size)
    return d if add_to is None else add_to + d


def ring_reduce_scatter(x, axis: str, *, mode: str = "f32",
                        block: int = 256, with_error: bool = False,
                        kernels: bool = False,
                        _hook_kind: str = "ring-reduce-scatter",
                        _hook_total_hops: int = 0):
    """Ring reduce-scatter of a 1-D array built from ``ppermute``, with
    each hop's payload optionally quantized on the wire
    (``parallel/compression.py``) while accumulation stays f32 on-device.

    ``x``: per-device (length divisible by the axis size N). Device i
    returns the i-th of N equal chunks of the cross-device SUM — the
    ``lax.psum_scatter(scatter_dimension=0, tiled=True)`` layout. The
    schedule is the classic N-1-hop ring: device i starts holding its
    local partial for chunk i-1, and at every hop sends its partial one
    position around the ring (quantize -> wire -> dequantize) and adds
    its own local contribution for the chunk it just received, so chunk c
    accumulates visiting c+1, c+2, ..., c in f32.

    ``mode="f32"`` is the correctness anchor for the schedule: identity
    payloads make the ring compute exactly a reduce-scatter, equal to
    ``lax.psum_scatter`` up to float32 summation ORDER (the ring folds
    chunk c starting at device c+1; XLA:CPU folds every chunk in rank
    order — IEEE addition is commutative but not associative, so random
    floats match to ULPs and exact-arithmetic inputs match bit-for-bit;
    both pinned by tests/test_compression.py).

    Returns ``(chunk, err)``: ``err`` (when ``with_error``) is the
    quantization error THIS device introduced, a full-length f32 array
    with each hop's error at its chunk's offsets — the error-feedback
    residual contribution. ``err`` is None when not requested, all-zero
    in f32 mode.

    ``kernels`` routes the int8 payload ops through the fused Pallas
    quantize / dequantize-accumulate kernels (bit-identical wire bytes
    and error-feedback residuals — the roundtrip parity contract)."""
    n = lax.axis_size(axis)
    if x.shape[0] % n:
        raise ValueError(
            f"ring_reduce_scatter: length {x.shape[0]} not divisible by "
            f"axis size {n}"
        )
    s = x.shape[0] // n
    if n == 1:
        return x, (jnp.zeros_like(x) if with_error else None)
    chunks = x.reshape(n, s)
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    p = jnp.take(chunks, (idx - 1) % n, axis=0, mode="wrap")
    err = jnp.zeros_like(x) if with_error else None
    for step in range(n - 1):
        payload = _quant(p, mode, block, kernels)
        if with_error and mode != "f32":
            e = p - _dequant(payload, mode, block, s, kernels)
            # the chunk being sent this hop is (idx - 1 - step) mod n
            err = lax.dynamic_update_slice(
                err, e, (((idx - 1 - step) % n) * s,))
        payload = jax.tree.map(
            lambda t: lax.ppermute(t, axis, perm), payload)
        nxt = jnp.take(chunks, (idx - 2 - step) % n, axis=0, mode="wrap")
        if _RING_HOP_HOOK is not None:
            from tpu_ddp.parallel.compression import chunk_wire_bytes

            # the hook's probe must observe the BARE dequantized chunk
            # (pre-accumulate), so the fused accumulate stays off here
            p = _dequant(payload, mode, block, s, kernels)
            _emit_hop(
                p[0], kind=_hook_kind, mode=mode, axis=axis,
                hop=step + 1,
                n_hops=_hook_total_hops or (n - 1),
                wire_bytes=chunk_wire_bytes(s, mode, block))
            p = p + nxt
        else:
            p = _dequant(payload, mode, block, s, kernels, add_to=nxt)
    return p, err


def ring_all_reduce(x, axis: str, *, mode: str = "f32", block: int = 256,
                    with_error: bool = False, kernels: bool = False):
    """Ring all-reduce (SUM) with wire compression in BOTH phases:
    the compressed ring reduce-scatter above, then each device quantizes
    its reduced chunk ONCE and the payloads are all-gathered — every
    device (owner included) dequantizes the same bytes, so the result is
    bit-identical across the ring even in the lossy modes (the property
    DDP param consistency rests on). In ``mode="f32"`` this equals
    ``lax.psum`` up to the reduce-scatter's summation-order caveat.

    Returns ``(sum, err)`` with ``err`` as in ``ring_reduce_scatter``
    plus the owner-side all-gather-phase quantization error.
    ``kernels`` as in ``ring_reduce_scatter``."""
    n = lax.axis_size(axis)
    if n == 1:
        return x, (jnp.zeros_like(x) if with_error else None)
    s = x.shape[0] // n
    chunk, err = ring_reduce_scatter(
        x, axis, mode=mode, block=block, with_error=with_error,
        kernels=kernels, _hook_kind="ring-all-reduce", _hook_total_hops=n)
    payload = _quant(chunk, mode, block, kernels)
    if with_error and mode != "f32":
        e = chunk - _dequant(payload, mode, block, s, kernels)
        idx = lax.axis_index(axis)
        err = lax.dynamic_update_slice(err, e, (idx * s,))
    # the invariant gather: every device receives the same bytes, and the
    # shard_map checker types the result as replicated over ``axis`` — so
    # the synced grads (and the params updated from them) leave the step
    # under a P() out_spec
    gathered = jax.tree.map(
        lambda t: all_gather_invariant(t, axis, axis=0, tiled=False),
        payload)
    rows = jnp.stack([
        _dequant(jax.tree.map(lambda t: t[i], gathered),
                 mode, block, s, kernels)
        for i in range(n)
    ])
    out = rows.reshape(-1)
    if _RING_HOP_HOOK is not None:
        from tpu_ddp.parallel.compression import chunk_wire_bytes

        # the all-gather phase is the ring's FINAL hop (hop n of n):
        # each device receives the other n-1 quantized chunks
        _emit_hop(
            out[0], kind="ring-all-reduce", mode=mode, axis=axis,
            hop=n, n_hops=n,
            wire_bytes=(n - 1) * chunk_wire_bytes(s, mode, block))
    return out, err


def sync_gradients(grads, axis: str):
    """Gradient all-reduce-mean over the data axis — the explicit, one-line
    replacement for the reference's entire NCCL/DDP machinery (main.py:63).

    NOTE: only for grads that are still per-shard (varying), e.g. computed
    w.r.t. *sharded* params or outside shard_map's AD. Under shard_map,
    differentiating w.r.t. replicated (unvarying) params already psums the
    cotangents — pmean-ing those again double-counts. The train step in
    tpu_ddp.train.steps instead pmeans the LOSS before AD, which yields the
    allreduce-mean'd gradient directly."""
    return jax.tree.map(lambda g: lax.pmean(g, axis_name=axis), grads)
