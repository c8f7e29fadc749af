"""Fused block-scaled int8 quantize / dequantize Pallas kernels.

The grad-compress ring (``parallel/compression.py``) pays two XLA
round-trips per hop: ``quantize_chunk`` materializes abs/max/divide/
round/clip as separate HBM passes over the chunk, and
``dequantize_chunk`` does the scatter/gather in reverse. These kernels
collapse each direction into a single pass over the ``(n_blocks,
block)`` layout: one read of the chunk, one write of the int8 payload
plus its per-block scales (quantize); one read of payload+scales, one
write of the f32 chunk — optionally accumulating into a carried operand
in the same pass (dequantize-accumulate, the ring's ``p + take(...)``).

Bit-parity contract: the kernels reproduce ``quantize_chunk`` /
``dequantize_chunk`` EXPRESSION FOR EXPRESSION — max-abs/127 scale, the
zero-guarded divisor, round-clip to [-127, 127], dequantize by the RAW
scale (non-finite sentinel preservation) — so the error-feedback
residual ``p - dequant(quant(p))`` telescopes identically with kernels
on or off (pinned by ``tests/test_fused_kernels.py``).

Same house rules as ``flash_attention.py``: ``interpret=None`` resolves
to compiled-on-TPU / interpret-on-CPU via ``_resolve_interpret``; under
a shard_map the interpreter cannot run (vma-carrying avals), so the jnp
reference path is taken there (CPU only — a TPU never interprets). A
block the TPU tiling cannot serve (``block % 128 != 0``) raises: the
kernel asked for is the kernel run, never a quiet reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tpu_ddp.ops.flash_attention import (
    _interpreted_under_shard_map,
    _resolve_interpret,
    _sds,
)
from tpu_ddp.telemetry.phases import kernel_scope

LANE = 128
#: sublane multiple for f32 tiles — block rows per grid step are padded
#: to this so the (rows, block) tiling is always mosaic-legal
_SUBLANES = 8
#: rows (blocks) processed per grid step, before padding trims it
_MAX_ROWS = 256


def supports_block(block: int) -> bool:
    """The TPU tiling serves a block iff it fills whole lanes."""
    return block % LANE == 0


def _require_block(block: int) -> None:
    if not supports_block(block):
        raise ValueError(
            f"fused int8 quantize kernels need a block that fills whole "
            f"lanes (a multiple of {LANE}), got block={block}")


def _rows_plan(nb: int):
    """(rows_per_step, padded_rows): pad the block count up to a
    multiple of the per-step row tile so the 1-D grid divides evenly."""
    br = min(_MAX_ROWS, ((nb + _SUBLANES - 1) // _SUBLANES) * _SUBLANES)
    nb_pad = ((nb + br - 1) // br) * br
    return br, nb_pad


def _quant_kernel(x_ref, q_ref, s_ref):
    xb = x_ref[...]
    # quantize_chunk verbatim: max-abs/127 scale, zero-guarded divisor,
    # round-clip to the symmetric int8 range
    scale = jnp.max(jnp.abs(xb), axis=1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xb / safe[:, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale[:, None], s_ref.shape)


def fused_quant(x, block: int, *, interpret=None) -> dict:
    """``quantize_chunk(x, "int8", block)`` as one fused pass: 1-D f32
    chunk -> ``{"q": int8 (nb*block,), "scale": f32 (nb,)}``."""
    from tpu_ddp.parallel.compression import quantize_chunk

    _require_block(block)
    interpret = _resolve_interpret(interpret)
    size = x.shape[0]
    nb = -(-size // block)
    if _interpreted_under_shard_map(x, interpret):
        return quantize_chunk(x, "int8", block)
    pad = nb * block - size
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    br, nb_pad = _rows_plan(nb)
    xb = x.reshape(nb, block)
    if nb_pad != nb:
        xb = jnp.concatenate(
            [xb, jnp.zeros((nb_pad - nb, block), xb.dtype)])
    with jax.named_scope(kernel_scope("fused_quant")):
        q, s = pl.pallas_call(
            _quant_kernel,
            grid=(nb_pad // br,),
            in_specs=[pl.BlockSpec((br, block), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((br, block), lambda i: (i, 0)),
                       pl.BlockSpec((br, LANE), lambda i: (i, 0))],
            out_shape=[
                _sds((nb_pad, block), jnp.int8, xb),
                _sds((nb_pad, LANE), jnp.float32, xb),
            ],
            interpret=interpret,
        )(xb)
    return {"q": q[:nb].reshape(-1), "scale": s[:nb, 0]}


def _make_dequant_kernel(accumulate: bool):
    def kernel(q_ref, s_ref, *rest):
        qb = q_ref[...].astype(jnp.float32)
        # RAW scale multiply (dequantize_chunk verbatim): a non-finite
        # block scale poisons the whole block, by design
        d = qb * s_ref[..., :1]
        if accumulate:
            acc_ref, out_ref = rest
            out_ref[...] = acc_ref[...] + d
        else:
            (out_ref,) = rest
            out_ref[...] = d

    return kernel


def fused_dequant(payload: dict, block: int, size: int, *,
                  add_to=None, interpret=None):
    """``dequantize_chunk(payload, "int8", block, size)`` as one fused
    pass — with ``add_to`` given, the ring-hop accumulate ``add_to +
    dequant(payload)`` rides in the same pass (one read of each operand,
    one write)."""
    _require_block(block)
    interpret = _resolve_interpret(interpret)
    nb = -(-size // block)
    q = payload["q"]
    scale = payload["scale"]
    if _interpreted_under_shard_map(q, interpret):
        return _reference_dequant(payload, block, size, add_to=add_to)
    br, nb_pad = _rows_plan(nb)
    qb = q.reshape(nb, block)
    sb = jnp.broadcast_to(scale[:, None], (nb, LANE))
    acc = None
    if add_to is not None:
        acc = add_to
        if nb * block != size:
            acc = jnp.concatenate(
                [acc, jnp.zeros((nb * block - size,), acc.dtype)])
        acc = acc.reshape(nb, block)
    if nb_pad != nb:
        qb = jnp.concatenate(
            [qb, jnp.zeros((nb_pad - nb, block), qb.dtype)])
        sb = jnp.concatenate(
            [sb, jnp.zeros((nb_pad - nb, LANE), sb.dtype)])
        if acc is not None:
            acc = jnp.concatenate(
                [acc, jnp.zeros((nb_pad - nb, block), acc.dtype)])
    in_specs = [pl.BlockSpec((br, block), lambda i: (i, 0)),
                pl.BlockSpec((br, LANE), lambda i: (i, 0))]
    operands = [qb, sb]
    if acc is not None:
        in_specs.append(pl.BlockSpec((br, block), lambda i: (i, 0)))
        operands.append(acc)
    with jax.named_scope(kernel_scope("fused_dequant")):
        out = pl.pallas_call(
            _make_dequant_kernel(acc is not None),
            grid=(nb_pad // br,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((br, block), lambda i: (i, 0)),
            out_shape=_sds((nb_pad, block), jnp.float32, *operands),
            interpret=interpret,
        )(*operands)
    return out[:nb].reshape(-1)[:size]


def _reference_quant(x, block: int) -> dict:
    """The jnp reference (``quantize_chunk`` itself — one source of
    truth for the arithmetic the kernel must reproduce)."""
    from tpu_ddp.parallel.compression import quantize_chunk

    return quantize_chunk(x, "int8", block)


def _reference_dequant(payload: dict, block: int, size: int, *,
                       add_to=None):
    from tpu_ddp.parallel.compression import dequantize_chunk

    d = dequantize_chunk(payload, "int8", block, size)
    return d if add_to is None else add_to + d
