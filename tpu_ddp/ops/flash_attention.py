"""Flash attention (blockwise, online-softmax) as a Pallas TPU kernel.

Single-device counterpart of the cross-device ring attention
(``tpu_ddp.parallel.ring_attention``): same math, but the K/V blocks stream
through VMEM on one core instead of rotating around the ICI ring. Memory is
O(T_q_block * T) scores per step instead of materializing the full (T, T)
matrix in HBM, and the QK^T / PV matmuls hit the MXU tile-by-tile.

Layout: (B, T, H, D) like the rest of the framework; internally heads fold
into the grid. Head dims are zero-padded to the 128 lane width (padding k
contributes 0 to scores; padding v yields padded output columns that are
sliced away). Queries and keys may be of another width than values
(latent attention: 192 over 128): ``q`` and ``k`` (and ``dq``, ``dk``) are
padded to the lane width on their own, ``v``, ``o``, ``dO`` and ``dv`` on
theirs, and the scale is ``1 / sqrt(q.shape[-1])``. With one width the
kernels' programs are the ones a single ``D`` made.

Differentiation: forward AND backward are Pallas kernels (``jax.custom_vjp``).
The forward additionally emits the per-row logsumexp (broadcast along a
128-lane minor dim — the TPU-friendly layout for per-row stats *inside* the
kernels). What lives from the forward pass to the backward pass is lane 0 of
it, one float32 a row, widened again before the backward kernels read it
(``_fwd``, ``_bwd``); it and the output carry names (``OUT_NAME``,
``LSE_NAME``), so a recomputed layer whose policy keeps them runs the
forward kernel once a step, not once in each pass. The backward
is ONE kernel (``_bwd_kernel``) wherever the dk / dv of one key-value head's
whole sequence fit its VMEM budget (``_backward_fits``: 8,192 positions at
any width here): the dQ walk, kv-blocks innermost with dq in VMEM scratch,
whose tile — s, p = exp(s - lse), dP, dS, made once — also adds into the
whole-sequence dk / dv accumulators. A longer sequence (32,768 at latent
attention's widths, a pod-scale ring's local block) runs the standard
two-kernel split: that dQ kernel alone and a dK/dV kernel iterating q-blocks
innermost, each making the tile again. Either way p is recomputed
tile-by-tile instead of materializing the (T, T) probability matrix;
which one runs is decided by the shapes and nothing else. Every T gets a
tiling Mosaic accepts (``_plan``): blocks that divide T, else one
whole-axis block for short sequences, else T zero-padded to a block
multiple with the padding masked out through ``kv_mask`` — the kernel asked
for is the kernel run.

Masking (round-4 verdict item 3, the decoder regime): ``causal=True``
leaves out the tiles entirely above the diagonal and masks the tiles the
diagonal crosses; ``kv_mask`` (B, Tk) handles key padding via a
sublane-broadcast (B*H, 8, Tk) slab applied multiplicatively to p, so rows
with no visible key output exactly 0 with zero gradients (the ``NEG`` finite
-inf + safe l/lse discipline below). Both compose, both differentiate
through the Pallas backward kernels.

The decoder regime proper (a window, and key-value heads shared by groups
of query heads): ``window=W`` lets row ``i`` see columns ``i - W < j <= i``
(it implies ``causal``), and ``k``/``v`` may carry fewer heads than ``q``
(``H % KV == 0``; query heads ``g*H/KV .. (g+1)*H/KV - 1`` read key-value
head ``g``). The grid's inner dimension is the *band*: for a q block only
the kv blocks it can see are visited (``_Band``), in the forward and in
every backward kernel, so a tile wholly outside the band costs neither a
product nor a copy, and under ``causal`` the blocks above the diagonal are
not streamed. dk and dv sum over the query heads of their group: a grid
dimension outside the q blocks in the one-kernel pass, part of the inner
dimension in the dK/dV kernel. Tiles that no mask edge crosses take no mask
arithmetic.

A third visibility beside ``causal`` and ``window`` is block diffusion's,
``diffusion=(L, B)``: the sequence is ``[clean ‖ noisy]``, L positions and
their L noised copies, in blocks of B tokens; a clean row sees the clean
columns of its own and earlier blocks, a noisy row the clean columns of
earlier blocks and the noisy columns of its own block, and no clean row a
noisy column (``_bhqk_visibility`` states it, ``_tile_visibility`` applies it
to a tile). It is not a band: a noisy q block visits a run of clean kv blocks
**and then** its own noisy ones, so what the kernels and the index maps ask
is a list (``kv_at``, ``visits``, ``kv_index``, ``kv_width``), which ``_Band``
answers for a band and ``_DiffusionBlocks`` for this mask. The forward kernel
and the one-kernel backward pass walk either; the two-kernel split walks
bands only and refuses this mask by name.

The row statistics of the online softmax (``m``, ``l`` and the rescale
``alpha``; ``lse`` and ``di`` in the backward kernels) are kept
lane-replicated, (rows, 128), in the kernels as in their buffers
(``_lanes``); between the passes the logsumexp is (rows,), since a kept
(rows, 128) buffer is 268-537 MB a layer of a decoder cell. What that is
worth on the chip, what walking a tile in smaller pieces was not, what one
backward kernel is and what a forward kernel run once a step is, is PERF.md's
to say (section 6, PR 30, PR 40 and PR 42).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_ddp.telemetry.phases import kernel_scope

LANE = 128
_SUBLANES = 8
# Longest sequence served by ONE whole-axis block when the requested blocks
# do not divide it: the (T, T) f32 score tile and its backward companions
# stay a few MiB of VMEM.
_WHOLE_AXIS_MAX = 512
# Finite stand-in for -inf on masked logits: exp(NEG - finite_max)
# underflows to exactly 0.0 in f32, while (-inf) - (-inf) would be NaN when
# an entire tile row is masked.
NEG = -1e30
#: what the backward kernels read of the forward pass, by name to a
#: recomputation policy (``checkpoint_name``): the attention's output and
#: its rows' logsumexp, one float32 a row. A recomputed layer that keeps
#: both (``models/decoder.py::recomputed``) has no forward kernel in its
#: backward pass; a caller without such a policy sees no change.
OUT_NAME, LSE_NAME = "flash_out", "flash_lse"


def _bhqk_visibility(Tq: int, Tk: int, causal: bool, kv_mask,
                     window: int = 0, diffusion=None):
    """(…, Tq, Tk)-broadcastable bool visibility for full-tile jnp paths
    ((B,H,Tq,Tk) score layouts), or None when everything is visible. The
    ONE implementation shared by _reference and the ring's jnp tile/bwd
    fallbacks — these must stay numerically identical to each other (and
    to the kernels' per-tile _tile_visibility). ``diffusion`` = (L, B) is
    the block-diffusion visibility over ``[clean ‖ noisy]``, 2L positions
    in blocks of B (``flash_attention``), written here as it is stated."""
    vis = None
    if diffusion is not None:
        L, B = diffusion
        rows = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
        row_noisy, col_noisy = rows >= L, cols >= L
        rb, cb = rows % L // B, cols % L // B
        vis = jnp.where(
            col_noisy, jnp.logical_and(row_noisy, cb == rb),
            jnp.where(row_noisy, cb < rb, cb <= rb))[None, None]
    elif causal or window:
        rows = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
        vis = cols <= rows
        if window:
            vis = jnp.logical_and(vis, cols > rows - window)
        vis = vis[None, None]
    if kv_mask is not None:
        km = (kv_mask > 0)[:, None, None, :]
        vis = km if vis is None else jnp.logical_and(vis, km)
    return vis


def _reference(q, k, v, causal: bool = False, kv_mask=None,
               window: int = 0, diffusion=None):
    """Fused jnp attention, the numerics ground truth for the kernels.
    ``causal`` masks col > row (self-aligned square tiles); ``window``
    also masks col <= row - window; ``diffusion`` = (L, B) is the
    block-diffusion visibility in their place; ``kv_mask`` (B, Tk),
    nonzero = attend, masks key/value columns. ``k``/``v`` with fewer heads
    than ``q`` are shared by groups of query heads. Rows with no visible
    key (possible under kv_mask) output exactly 0 — the
    multiplicative-mask convention the kernels implement."""
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    vis = _bhqk_visibility(s.shape[-2], s.shape[-1], causal, kv_mask,
                           window, diffusion)
    if vis is not None:
        s = jnp.where(vis, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    if vis is not None:
        # all-NEG rows softmax to uniform garbage; the multiplicative mask
        # turns them into exact zeros
        p = p * vis
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _tile_visibility(s_shape, q_blk: int, kv_blk: int, causal: bool,
                     mask_row, window: int = 0, diffusion=None):
    """(bq, bk) bool visibility for one tile, or None when everything is
    visible. ``q_blk``/``kv_blk`` are the block indices of the tile;
    ``mask_row`` is the (1, bk) f32 kv-mask slab or None. Under
    ``diffusion`` = (L, B) a tile lies in one half by its rows and in one
    by its columns (``_DiffusionBlocks`` sees to it), so which rule holds is
    two scalars: a clean row sees the clean blocks up to its own, a noisy
    row those before its own, and of the noisy columns its own block."""
    bq, bk = s_shape
    vis = None
    if diffusion is not None:
        L, B = diffusion
        row_noisy, col_noisy = q_blk * bq // L, kv_blk * bk // L
        rb = (q_blk * bq - row_noisy * L + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)) // B
        cb = (kv_blk * bk - col_noisy * L + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 1)) // B
        vis = jnp.logical_and(cb <= rb - row_noisy * (1 - col_noisy),
                              cb >= rb * col_noisy)
    elif causal:
        rows = q_blk * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kv_blk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        vis = cols <= rows
        if window:
            vis = jnp.logical_and(vis, cols > rows - window)
    if mask_row is not None:
        mvis = mask_row > 0.0  # (1, bk) broadcasts over rows
        vis = mvis if vis is None else jnp.logical_and(vis, mvis)
    return vis


def _least(a, b):
    """min of two block indices, Python ints or traced."""
    if isinstance(a, int) and isinstance(b, int):
        return min(a, b)
    return jnp.minimum(a, b)


class _Band(NamedTuple):
    """Which tiles hold a visible (row, column) pair: the kv blocks a q
    block visits and, the other way round, the q blocks a kv block is seen
    by. The bounds are plain arithmetic on a block index, so they serve the
    kernels' program ids, the BlockSpec index maps and, on Python ints, the
    static width of the grid's inner dimension."""

    causal: bool
    window: int  # 0 = none
    bq: int
    bk: int
    n_q: int
    n_k: int

    def kv_lo(self, j):
        if not self.window:
            return 0 * j
        return -_least(self.window - 1 - j * self.bq, 0) // self.bk

    def kv_hi(self, j):
        if not self.causal:
            return 0 * j + self.n_k - 1
        return _least((j * self.bq + self.bq - 1) // self.bk, self.n_k - 1)

    # the list a q block visits, as the kernels and the index maps ask it
    diffusion = None

    def kv_at(self, j, t):
        """The kv block of step ``t`` of q block ``j``."""
        return self.kv_lo(j) + t

    def visits(self, j, t, kb):
        """Is step ``t`` of q block ``j`` (its kv block ``kb``) a visit?"""
        return kb <= self.kv_hi(j)

    def kv_index(self, j, t):
        """``kv_at`` for an index map: a step past the list's end repeats
        the last block, so nothing is copied for it."""
        return _least(self.kv_lo(j) + t, self.kv_hi(j))

    def q_lo(self, jk):
        if not self.causal:
            return 0 * jk
        return _least(jk * self.bk // self.bq, self.n_q - 1)

    def q_hi(self, jk):
        if not self.window:
            return 0 * jk + self.n_q - 1
        return _least(
            (jk * self.bk + self.bk + self.window - 2) // self.bq,
            self.n_q - 1)

    @property
    def kv_width(self) -> int:
        """kv blocks the widest q block visits."""
        return max(self.kv_hi(j) - self.kv_lo(j) + 1
                   for j in range(self.n_q))

    @property
    def q_width(self) -> int:
        return max(self.q_hi(j) - self.q_lo(j) + 1
                   for j in range(self.n_k))

    def edge_crosses(self, q_blk, kv_blk):
        """Does a mask edge (the diagonal, the window's far side) cross this
        tile? One it does not cross is, inside the band, wholly visible."""
        crosses = kv_blk * self.bk + self.bk - 1 > q_blk * self.bq
        if self.window:
            crosses = jnp.logical_or(
                crosses,
                kv_blk * self.bk <= q_blk * self.bq + self.bq - 1
                - self.window)
        return crosses


def _pick(cond, a, b):
    """``a if cond else b`` on block indices, Python ints or traced."""
    if isinstance(cond, (bool, int)):
        return a if cond else b
    return jnp.where(cond, a, b)


class _DiffusionBlocks(NamedTuple):
    """``_Band``'s part for the block-diffusion visibility: ``half`` clean
    positions and their ``half`` noisy copies after them, in diffusion
    blocks of ``size``. The kv blocks a q block visits are not one run: a
    clean q block visits the clean kv blocks up to the one its last row's
    block ends in; a noisy one the clean kv blocks that hold a block before
    its last row's, **and then** the noisy kv blocks its own rows' blocks
    lie in (one tile where the tiles are square). Step ``t`` of q block
    ``j`` is found by the same arithmetic on block indices, so it serves
    program ids, index maps and the static grid width as ``_Band``'s does.
    A tile lies in one half each way: ``bq`` and ``bk`` divide ``half``.
    The noisy run comes last and holds every noisy row's own position, so a
    row that saw nothing in the clean run (the first block's) is wiped
    clean by it, as a window's first tile is by the diagonal's."""

    half: int
    size: int
    bq: int
    bk: int
    n_q: int
    n_k: int

    causal = True   # it has edges: a tile may need the mask arithmetic
    window = 0

    @property
    def diffusion(self):
        return (self.half, self.size)

    def _runs(self, j):
        """(clean kv blocks visited, from block 0; the first noisy kv block
        visited; how many of those)."""
        q_half, k_half = self.half // self.bq, self.half // self.bk
        noisy = j // q_half                   # 0 a clean q block, 1 a noisy
        first = (j - noisy * q_half) * self.bq   # its rows, in their half
        last = first + self.bq - 1
        # the clean columns a row sees end where its block ends (a clean
        # row) or starts (a noisy one)
        reach = (last // self.size + 1 - noisy) * self.size
        n_clean = _least(-(-reach // self.bk), k_half)
        lo = k_half + first // self.size * self.size // self.bk
        hi = k_half + _least(
            ((last // self.size + 1) * self.size - 1) // self.bk, k_half - 1)
        return n_clean, lo, noisy * (hi - lo + 1)

    def kv_count(self, j):
        n_clean, _, n_noisy = self._runs(j)
        return n_clean + n_noisy

    def kv_at(self, j, t):
        n_clean, lo, _ = self._runs(j)
        return _pick(t < n_clean, t, lo + t - n_clean)

    def visits(self, j, t, kb):
        del kb
        return t < self.kv_count(j)

    def kv_index(self, j, t):
        return self.kv_at(j, _least(t, self.kv_count(j) - 1))

    @property
    def kv_width(self) -> int:
        return max(self.kv_count(j) for j in range(self.n_q))

    def edge_crosses(self, q_blk, kv_blk):
        """Is some pair of this visited tile hidden? Clean columns: the
        tile's last column lies past what its first row sees. Noisy
        columns: rows and columns are not all of one diffusion block."""
        q_half, k_half = self.half // self.bq, self.half // self.bk
        row_noisy, col_noisy = q_blk // q_half, kv_blk // k_half
        first = (q_blk - row_noisy * q_half) * self.bq
        col = (kv_blk - col_noisy * k_half) * self.bk
        block = first // self.size
        clean = col + self.bk - 1 >= (block + 1 - row_noisy) * self.size
        noisy = jnp.logical_or(
            (first + self.bq - 1) // self.size != block,
            jnp.logical_or(col // self.size != block,
                           (col + self.bk - 1) // self.size != block))
        return jnp.where(col_noisy > 0, noisy, clean)


def _band_dispatch(band: _Band, has_mask: bool, q_blk, kv_blk, visible,
                   compute):
    """Run ``compute(masked)`` for a tile inside the band: with the mask
    arithmetic where an edge crosses the tile (or a kv mask is given), and
    without it elsewhere."""
    if has_mask or not band.causal:
        pl.when(visible)(functools.partial(compute, has_mask or band.causal))
        return
    crosses = band.edge_crosses(q_blk, kv_blk)
    pl.when(jnp.logical_and(visible, crosses))(
        functools.partial(compute, True))
    pl.when(jnp.logical_and(visible, jnp.logical_not(crosses)))(
        functools.partial(compute, False))


def _lanes(x, n: int):
    """(rows, LANE) lane-replicated row statistics against (rows, n) values:
    a (rows, 1) column costs a register for every eight rows all the same,
    and every use of it a broadcast across the lanes."""
    if n % LANE:
        return x[:, 0:1]
    return x if n == LANE else jnp.tile(x, (1, n // LANE))


def _kernel(q_ref, k_ref, v_ref, *rest, scale: float, band: _Band,
            width: int, has_mask: bool):
    """One (q-block, kv-block) tile. The position in the band is the
    innermost grid dim, so for a fixed q block the kernel runs ``width``
    times back-to-back with VMEM scratch (acc/m/l) carrying the
    online-softmax state — only one (bq, d) + (bk, d) tile pair is resident
    per step; K/V stream from HBM block-by-block via the BlockSpec pipeline.
    The running maximum and denominator are lane-replicated, (bq, LANE) as
    their scratch is (``_lanes``). The final step also writes the row
    logsumexp (lane-broadcast) — the backward's residual.

    Step ``t`` of q block ``j`` is kv block ``band.kv_at(j, t)`` (a band's
    ``kv_lo(j) + t``); steps past the end of its list (a q block whose band
    is narrower than the widest: past ``kv_hi(j)``) do
    nothing, and their index map repeats the last block, so nothing is
    copied for them either. ``has_mask`` threads a (1, bk) kv-mask slab
    applied multiplicatively to p, so fully-masked rows accumulate exact
    zeros (l == 0, handled at finalize)."""
    if has_mask:
        mask_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
        mask_ref = None
    j = pl.program_id(1)
    t = pl.program_id(2)
    kb = band.kv_at(j, t)

    @pl.when(t == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    def _compute(masked):
        q = q_ref[0]  # (bq, d)
        s = jnp.dot(q, k_ref[0].T, preferred_element_type=jnp.float32) * scale
        vis = _tile_visibility(
            s.shape, j, kb, band.causal,
            mask_ref[0, 0:1, :] if has_mask else None, band.window,
            band.diffusion,
        ) if masked else None
        if vis is not None:
            s = jnp.where(vis, s, NEG)
        m_prev = m_ref[:]  # (bq, LANE), every lane the row's
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        if has_mask:
            # all-masked-so-far rows have m_new == NEG and p == exp(0) == 1
            # on masked entries; the multiplicative mask restores exact 0.
            # (Under a window a row's first tile may hold none of its
            # columns either, but its diagonal tile always follows, and
            # alpha == exp(NEG - m) == 0 there wipes what this one left.)
            p = p * vis
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * _lanes(alpha, acc_ref.shape[1]) + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32
        )
        m_ref[:] = m_new
        l_ref[:] = l_new

    _band_dispatch(band, has_mask, j, kb, band.visits(j, t, kb), _compute)

    @pl.when(t == width - 1)
    def _finalize():
        l = l_ref[:]  # lane-replicated, like m and like the lse written
        if has_mask:
            safe_l = jnp.where(l > 0, l, 1.0)
            o_ref[0] = (acc_ref[:] / _lanes(safe_l, acc_ref.shape[1])
                        ).astype(o_ref.dtype)
            lse_ref[0] = jnp.where(l > 0, m_ref[:] + jnp.log(safe_l), NEG)
        else:
            o_ref[0] = (acc_ref[:] / _lanes(l, acc_ref.shape[1])
                        ).astype(o_ref.dtype)
            lse_ref[0] = m_ref[:] + jnp.log(l)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


class _Plan(NamedTuple):
    bq: int
    bk: int
    d_pad: int
    t_pad: int  # == T unless the sequence must be zero-padded and masked


def _plan(q_shape, block_q: int, block_k: int, masked: bool = False) -> _Plan:
    """Tiling for a (B, T, H, D) problem that Mosaic accepts: a block's
    second-minor dim must be a multiple of 8 — and the kv-mask slab's
    minor dim (``bk``) a multiple of 128 — or span the whole axis.
    Deterministic, so the fwd and bwd passes always agree.

    The requested blocks are first rounded up to that alignment. Then, in
    order: blocks that divide T are used as they are; a T they do not
    divide but short enough for VMEM (e.g. ViT-B/16's 196 tokens) runs as
    one whole-axis block; any other T is served by padding it to a block
    multiple (``t_pad > T`` — the caller pads q/k/v and masks the padded
    keys, so the blocks are lane-aligned there)."""
    _, T, _, D = q_shape
    if T < 1:
        raise ValueError(f"flash attention: no tiling for shape {q_shape}")
    d_pad = _round_up(D, LANE)
    bq = _round_up(block_q, _SUBLANES)
    bk = _round_up(block_k, LANE if masked else _SUBLANES)
    if T % bq == 0 and T % bk == 0:
        return _Plan(bq, bk, d_pad, T)
    if T <= _WHOLE_AXIS_MAX:
        return _Plan(T, T, d_pad, T)
    bq, bk = _round_up(bq, LANE), _round_up(bk, LANE)
    return _Plan(bq, bk, d_pad, _round_up(T, math.lcm(bq, bk)))


def _fold(x, d_pad):  # (B,T,H,D) -> (B*H, T, Dpad)
    B, T, H, D = x.shape
    x = x.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    if d_pad != D:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, d_pad - D)))
    return x


def _unfold(x, shape):  # (B*H, T, Dpad) -> (B,T,H,D)
    B, T, H, D = shape
    return x[:, :, :D].reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _sds(shape, dtype, *like):
    """ShapeDtypeStruct carrying the operands' varying-mesh-axes marking:
    inside a shard_map (the DP/SP train steps) pallas_call outputs must
    declare their vma or tracing fails with check_vma=True."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _fold_mask(kv_mask, H: int):
    """(B, Tk) kv mask -> (B*H, 8, Tk) f32, matching _fold's b*H + h order.
    The sublane broadcast gives the (1, 8, bk) block a Mosaic-legal tile
    (2D (1, bk) blocks fail the second-minor divisible-by-8 rule)."""
    m = jnp.repeat(kv_mask.astype(jnp.float32), H, axis=0)  # (B*H, Tk)
    return jnp.broadcast_to(m[:, None, :],
                            (m.shape[0], _SUBLANES, m.shape[1]))


def _unpadded_plan(q_shape, block_q, block_k, masked) -> _Plan:
    """The plan for operands the kernels take as they are: a shape that
    would need padding is the caller's to pad (``flash_attention`` does)."""
    plan = _plan(q_shape, block_q, block_k, masked)
    if plan.t_pad != q_shape[1]:
        raise ValueError(
            f"flash attention kernel: blocks ({block_q}, {block_k}) do not "
            f"tile shape {tuple(q_shape)} (T={q_shape[1]}); pad the "
            f"sequence to {plan.t_pad} and mask the padding")
    return plan


def _interpreted_under_shard_map(x, interpret: bool) -> bool:
    """Interpret-mode pallas under shard_map: the HLO interpreter's internal
    dynamic_slices mix varying/unvarying operands and fail the vma check
    (jax hlo_interpreter.py limitation, not a kernel bug). CPU tests of
    models-under-shard_map take the fused jnp path; the kernel itself is
    covered by the standalone tests and the chip's (mosaic) lowering.
    Unreachable on a TPU, where ``interpret`` resolves to False."""
    return interpret and bool(jax.typeof(x).vma)


def _check_heads(q, k, v):
    H, KV = q.shape[2], k.shape[2]
    if k.shape[:3] != v.shape[:3] or H % KV or (
            q.shape[:2] + q.shape[3:] != k.shape[:2] + k.shape[3:]):
        raise ValueError(
            f"flash attention: q {q.shape} against k {k.shape}, v {v.shape}: "
            "key-value heads must divide the query heads, queries and keys "
            "share a width, the rest agree")
    return H // KV


def _visit_lists(T: int, bq: int, bk: int, causal: bool, window: int,
                 diffusion):
    """Which kv blocks each q block visits: a band, or the two runs of the
    block-diffusion visibility. That one is never silently a band: a shape
    its lists are not written for is refused by name."""
    if diffusion is None:
        return _Band(causal or bool(window), window, bq, bk, T // bq,
                     T // bk)
    half, size = diffusion
    if causal or window or T != 2 * half or half % size or (
            half % bq or half % bk):
        raise ValueError(
            f"flash attention: the block-diffusion mask {diffusion} over "
            f"{T} positions in ({bq}, {bk}) tiles: it takes 2 x {half} "
            "positions in whole blocks, tiles that divide a half, and "
            "neither causal nor a window beside it")
    return _DiffusionBlocks(half, size, bq, bk, T // bq, T // bk)


def _flash_forward(q, k, v, kv_mask=None, *, block_q: int, block_k: int,
                   interpret: bool, causal: bool = False, window: int = 0,
                   diffusion=None):
    """Returns (out, lse) — lse is None on the interpreted-under-shard_map
    jnp detour."""
    B, T, H, D = q.shape
    group = _check_heads(q, k, v)
    scale = 1.0 / np.sqrt(D)
    if _interpreted_under_shard_map(q, interpret):
        return _reference(q, k, v, causal=causal, kv_mask=kv_mask,
                          window=window, diffusion=diffusion), None
    bq, bk, d_pad, _ = _unpadded_plan(
        q.shape, block_q, block_k, kv_mask is not None)
    dv_pad = _round_up(v.shape[-1], LANE)  # values on their own width
    qf, kf, vf = _fold(q, d_pad), _fold(k, d_pad), _fold(v, dv_pad)
    band = _visit_lists(T, bq, bk, causal, window, diffusion)
    width = band.kv_width
    grid = (B * H, band.n_q, width)  # the band innermost: sequential carry
    has_mask = kv_mask is not None

    kv_block = band.kv_index

    def kv_spec(d):
        return pl.BlockSpec(
            (1, bk, d), lambda i, j, t: (i // group, kv_block(j, t), 0),
            memory_space=pltpu.VMEM)

    in_specs = [
        pl.BlockSpec((1, bq, d_pad), lambda i, j, t: (i, j, 0),
                     memory_space=pltpu.VMEM),
        kv_spec(d_pad), kv_spec(dv_pad),
    ]
    args = [qf, kf, vf]
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, _SUBLANES, bk), lambda i, j, t: (i, 0, kv_block(j, t)),
            memory_space=pltpu.VMEM))
        args.append(_fold_mask(kv_mask, H))
    with jax.named_scope(kernel_scope("flash_fwd")):
        out, lse = pl.pallas_call(
            functools.partial(_kernel, scale=scale, band=band, width=width,
                              has_mask=has_mask),
            out_shape=[
                _sds((B * H, T, dv_pad), q.dtype, qf),
                _sds((B * H, T, LANE), jnp.float32, qf),
            ],
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, bq, dv_pad), lambda i, j, t: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, LANE), lambda i, j, t: (i, j, 0),
                             memory_space=pltpu.VMEM),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, dv_pad), jnp.float32),  # acc
                pltpu.VMEM((bq, LANE), jnp.float32),   # running max
                pltpu.VMEM((bq, LANE), jnp.float32),   # running denom
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(*args)
    return _unfold(out, q.shape[:3] + v.shape[3:]), lse


def _tile_p(q, kb, lse, q_blk, kv_blk, scale, band: _Band, mask_row,
            masked: bool):
    """Recompute one tile's probabilities p = exp(s - lse) under the same
    visibility the forward applied — shared by both backward kernels.
    ``lse`` is the rows' logsumexp as it is stored, lane-replicated.
    Masked entries are exact zeros: causal and window masking underflow
    (lse is finite), kv-masked rows with lse == NEG are restored to 0 by
    the multiplicative mask."""
    s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
    vis = _tile_visibility(s.shape, q_blk, kv_blk, band.causal, mask_row,
                           band.window, band.diffusion) if masked else None
    if vis is not None:
        s = jnp.where(vis, s, NEG)
    p = jnp.exp(s - _lanes(lse, s.shape[1]))
    if mask_row is not None:
        p = p * vis
    return p


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, *rest,
               scale: float, band: _Band, width: int, has_mask: bool):
    """dQ: for a fixed q block, stream the kv blocks of its band (innermost
    grid dim) and accumulate ds @ k in VMEM scratch; p is recomputed from
    the saved row logsumexp, never materialized beyond one (bq, bk) tile."""
    if has_mask:
        mask_ref, dq_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
        mask_ref = None
    j = pl.program_id(1)
    t = pl.program_id(2)
    kb = band.kv_at(j, t)

    @pl.when(t == 0)
    def _init():
        dq_acc[:] = jnp.zeros(dq_acc.shape, jnp.float32)

    def _compute(masked):
        q = q_ref[0]
        k = k_ref[0]
        p = _tile_p(q, k, lse_ref[0], j, kb, scale, band,
                    mask_ref[0, 0:1, :] if has_mask else None, masked)
        dp = jnp.dot(do_ref[0], v_ref[0].T,
                     preferred_element_type=jnp.float32)  # (bq, bk)
        ds = p * (dp - _lanes(di_ref[0], dp.shape[1])) * scale
        dq_acc[:] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32)

    _band_dispatch(band, has_mask, j, kb, band.visits(j, t, kb), _compute)

    @pl.when(t == width - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, *rest,
                scale: float, band: _Band, width: int, group: int,
                has_mask: bool):
    """dK/dV: for a fixed kv block of one key-value head, stream the q
    blocks that see it, for each query head of its group in turn (innermost
    grid dim: step ``t`` is head ``t // width`` of the group and q block
    ``q_lo + t % width``), accumulating p^T @ do and ds^T @ q in VMEM
    scratch."""
    if has_mask:
        mask_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
        mask_ref = None
    jk = pl.program_id(1)   # kv-block index
    t = pl.program_id(2)
    qb = band.q_lo(jk) + t % width

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[:] = jnp.zeros(dv_acc.shape, jnp.float32)

    def _compute(masked):
        q = q_ref[0]
        do = do_ref[0]
        p = _tile_p(q, k_ref[0], lse_ref[0], qb, jk, scale, band,
                    mask_ref[0, 0:1, :] if has_mask else None, masked)
        dv_acc[:] += jnp.dot(p.astype(do.dtype).T, do,
                             preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_ref[0].T, preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(di_ref[0], dp.shape[1])) * scale
        dk_acc[:] += jnp.dot(ds.astype(q.dtype).T, q,
                             preferred_element_type=jnp.float32)

    _band_dispatch(band, has_mask, qb, jk, qb <= band.q_hi(jk), _compute)

    @pl.when(t == group * width - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, *rest,
                scale: float, band: _Band, width: int, group: int,
                has_mask: bool):
    """dQ, dK and dV in one walk: ``_dq_kernel``'s (for a query head's q
    block, the kv blocks of its band innermost, dq carried in (bq, d)
    scratch), and each tile's p and ds, made once, also add p^T @ do and
    ds^T @ q into the rows of their kv block in dk / dv accumulators that
    hold the whole sequence of one key-value head: float32 VMEM scratch,
    zeroed at the head's first step and written out at its last. The query
    heads of a group are the grid dimension outside the q blocks and add
    into the same rows, so a row's sum runs heads outer, q blocks
    ascending: ``_dkv_kernel``'s order."""
    if has_mask:
        mask_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
    else:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
        mask_ref = None
    h = pl.program_id(1)    # query head of the group
    j = pl.program_id(2)
    t = pl.program_id(3)
    kb = band.kv_at(j, t)
    bk = band.bk

    def kv_rows(blk):
        return pl.ds(0 if band.n_k == 1 else pl.multiple_of(blk * bk, bk), bk)

    def each_kv_block(body):
        """A whole-sequence value a kv block at a time: (T, d) at once is
        thousands of registers' worth of straight-line code."""
        if band.n_k == 1:
            body(0)
        else:
            jax.lax.fori_loop(
                0, band.n_k, lambda blk, _: body(blk), None)

    @pl.when((h == 0) & (j == 0) & (t == 0))
    def _init_kv():
        def zero(blk):
            dk_acc[kv_rows(blk), :] = jnp.zeros((bk, dk_acc.shape[1]),
                                                jnp.float32)
            dv_acc[kv_rows(blk), :] = jnp.zeros((bk, dv_acc.shape[1]),
                                                jnp.float32)
        each_kv_block(zero)

    @pl.when(t == 0)
    def _init():
        dq_acc[:] = jnp.zeros(dq_acc.shape, jnp.float32)

    def _compute(masked):
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        rows = kv_rows(kb)
        p = _tile_p(q, k, lse_ref[0], j, kb, scale, band,
                    mask_ref[0, 0:1, :] if has_mask else None, masked)
        dv_acc[rows, :] += jnp.dot(p.astype(do.dtype).T, do,
                                   preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_ref[0].T, preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(di_ref[0], dp.shape[1])) * scale
        dk_acc[rows, :] += jnp.dot(ds.astype(q.dtype).T, q,
                                   preferred_element_type=jnp.float32)
        dq_acc[:] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32)

    _band_dispatch(band, has_mask, j, kb, band.visits(j, t, kb), _compute)

    @pl.when(t == width - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when((h == group - 1) & (j == band.n_q - 1) & (t == width - 1))
    def _finalize_kv():
        def write(blk):
            rows = kv_rows(blk)
            dk_ref[0, rows, :] = dk_acc[rows, :].astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_acc[rows, :].astype(dv_ref.dtype)
        each_kv_block(write)


#: What the one-kernel backward pass may keep in VMEM for a key-value head's
#: whole sequence: its float32 dk / dv accumulators and the two buffers of
#: each of their output blocks. A v5e core has 128 MiB; Mosaic's scoped
#: default of 16 MiB stays for the tiles, as the other kernels have it.
_FUSED_CARRY_MAX = 32 << 20
_TILES_VMEM = 16 << 20


def _fused_carry_bytes(T: int, d_pad: int, dv_pad: int, itemsize: int) -> int:
    return T * (d_pad + dv_pad) * (4 + 2 * itemsize)


def _backward_fits(T: int, d_pad: int, dv_pad: int, itemsize: int) -> bool:
    """Does ``_bwd_kernel``'s whole-sequence carry fit its budget? 8,192
    positions do at either width (16.8 MB at 128 + 128 lanes, 25.2 MB at
    256 + 128, in bfloat16); 32,768 at 256 + 128 (100 MB) and a pod-scale
    131,072 do not, and run the dQ and dK/dV kernels. Shapes alone decide:
    the need is VMEM against a score tile made twice, not a preference."""
    return _fused_carry_bytes(T, d_pad, dv_pad, itemsize) <= _FUSED_CARRY_MAX


def _flash_backward(q, k, v, o, lse, g, kv_mask=None, *, block_q: int,
                    block_k: int, interpret: bool, causal: bool = False,
                    window: int = 0, diffusion=None):
    """(dq, dk, dv): one kernel (``flash_bwd``) where a key-value head's
    whole-sequence dk / dv fit its VMEM budget (``_backward_fits``), the
    dQ kernel and the dK/dV kernel (``flash_dq``, ``flash_dkv``) where they
    do not. The two share the operands made here and ``_tile_p``."""
    B, T, H, D = q.shape
    group = _check_heads(q, k, v)
    scale = 1.0 / np.sqrt(D)
    bq, bk, d_pad, _ = _unpadded_plan(
        q.shape, block_q, block_k, kv_mask is not None)
    dv_pad = _round_up(v.shape[-1], LANE)  # v, o, dO and dv on their own
    qf, kf, vf = _fold(q, d_pad), _fold(k, d_pad), _fold(v, dv_pad)
    gf = _fold(g, dv_pad)
    # di = rowsum(dO * O): cheap elementwise+reduce, XLA fuses it; stored
    # lane-broadcast like lse, as the kernels use both (``_lanes``).
    di = jnp.broadcast_to(
        jnp.sum(_fold(g.astype(jnp.float32), dv_pad)
                * _fold(o.astype(jnp.float32), dv_pad),
                axis=-1, keepdims=True),
        (B * H, T, LANE),
    )
    band = _visit_lists(T, bq, bk, causal, window, diffusion)
    fused = _backward_fits(T, d_pad, dv_pad, k.dtype.itemsize)
    if diffusion is not None and not fused:
        # a clean kv block is seen by two runs of q blocks (its own half's
        # and the noisy half's): the dK/dV kernel's ``q_lo..q_hi`` is one
        raise NotImplementedError(
            f"flash attention: the block-diffusion mask {diffusion} over "
            f"{T} positions is past the one-kernel backward pass's carry "
            "(_backward_fits), and the dQ and dK/dV kernels walk bands only")
    dq, dk, dv = (_one_kernel_backward if fused else _two_kernel_backward)(
        qf, kf, vf, gf, lse, di, kv_mask, band=band, group=group,
        scale=scale, interpret=interpret)
    return _unfold(dq, q.shape), _unfold(dk, k.shape), _unfold(dv, v.shape)


def _one_kernel_backward(qf, kf, vf, gf, lse, di, kv_mask, *, band: _Band,
                         group: int, scale: float, interpret: bool):
    """``_bwd_kernel`` over the grid (key-value head, query head of its
    group, q block, kv block of the band): a score tile, its exponentials
    and dP are made once a backward pass."""
    bq, bk = band.bq, band.bk
    (BH, T, d_pad), dv_pad = qf.shape, vf.shape[-1]
    BKV = BH // group
    has_mask = kv_mask is not None
    width = band.kv_width

    kv_block = band.kv_index

    def q_spec(d):
        return pl.BlockSpec(
            (1, bq, d), lambda i, h, j, t: (i * group + h, j, 0),
            memory_space=pltpu.VMEM)

    def kv_inner(d):
        return pl.BlockSpec(
            (1, bk, d), lambda i, h, j, t: (i, kv_block(j, t), 0),
            memory_space=pltpu.VMEM)

    def kv_whole(d):
        return pl.BlockSpec((1, T, d), lambda i, h, j, t: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    row_spec = q_spec(LANE)
    in_specs = [q_spec(d_pad), kv_inner(d_pad), kv_inner(dv_pad),
                q_spec(dv_pad), row_spec, row_spec]
    args = [qf, kf, vf, gf, lse, di]
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, _SUBLANES, bk), lambda i, h, j, t: (i, 0, kv_block(j, t)),
            memory_space=pltpu.VMEM))
        args.append(_fold_mask(kv_mask, BKV // kv_mask.shape[0]))
    with jax.named_scope(kernel_scope("flash_bwd")):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, scale=scale, band=band,
                              width=width, group=group, has_mask=has_mask),
            out_shape=[
                _sds((BH, T, d_pad), qf.dtype, gf),
                _sds((BKV, T, d_pad), kf.dtype, gf),
                _sds((BKV, T, dv_pad), vf.dtype, gf),
            ],
            grid=(BKV, group, band.n_q, width),
            in_specs=in_specs,
            out_specs=[q_spec(d_pad), kv_whole(d_pad), kv_whole(dv_pad)],
            scratch_shapes=[
                pltpu.VMEM((bq, d_pad), jnp.float32),
                pltpu.VMEM((T, d_pad), jnp.float32),
                pltpu.VMEM((T, dv_pad), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=_TILES_VMEM + _fused_carry_bytes(
                    T, d_pad, dv_pad, kf.dtype.itemsize)),
            interpret=interpret,
        )(*args)


def _two_kernel_backward(qf, kf, vf, gf, lse, di, kv_mask, *, band: _Band,
                         group: int, scale: float, interpret: bool):
    """The standard split, for a sequence whose dk / dv ``_bwd_kernel``
    cannot hold: a dQ kernel iterating kv blocks innermost and a dK/dV
    kernel iterating q blocks innermost, each making the tile's p."""
    bq, bk = band.bq, band.bk
    (BH, T, d_pad), dv_pad = qf.shape, vf.shape[-1]
    BKV = BH // group
    has_mask = kv_mask is not None
    kparams = dict(scale=scale, band=band, has_mask=has_mask)

    kv_block = band.kv_index

    def q_spec(d):
        return pl.BlockSpec((1, bq, d), lambda i, j, t: (i, j, 0),
                            memory_space=pltpu.VMEM)

    def kv_inner(d):
        return pl.BlockSpec(
            (1, bk, d), lambda i, j, t: (i // group, kv_block(j, t), 0),
            memory_space=pltpu.VMEM)

    row_spec = q_spec(LANE)
    in_specs = [q_spec(d_pad), kv_inner(d_pad), kv_inner(dv_pad),
                q_spec(dv_pad), row_spec, row_spec]
    args = [qf, kf, vf, gf, lse, di]
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, _SUBLANES, bk), lambda i, j, t: (i, 0, kv_block(j, t)),
            memory_space=pltpu.VMEM))
        args.append(_fold_mask(kv_mask, BH // kv_mask.shape[0]))
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    with jax.named_scope(kernel_scope("flash_dq")):
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, width=band.kv_width, **kparams),
            out_shape=_sds((BH, T, d_pad), qf.dtype, gf),
            grid=(BH, band.n_q, band.kv_width),  # dq carry in scratch
            in_specs=in_specs,
            out_specs=q_spec(d_pad),
            scratch_shapes=[pltpu.VMEM((bq, d_pad), jnp.float32)],
            compiler_params=semantics,
            interpret=interpret,
        )(*args)

    width = band.q_width

    def q_index(i, jk, t):
        """(folded query head, q block) of step ``t`` for kv head ``i``."""
        return (i * group + t // width,
                _least(band.q_lo(jk) + t % width, band.q_hi(jk)))

    def q_inner(d):
        return pl.BlockSpec(
            (1, bq, d), lambda i, jk, t: (*q_index(i, jk, t), 0),
            memory_space=pltpu.VMEM)

    def kv_spec(d):
        return pl.BlockSpec((1, bk, d), lambda i, jk, t: (i, jk, 0),
                            memory_space=pltpu.VMEM)

    row_inner = q_inner(LANE)
    in_specs = [q_inner(d_pad), kv_spec(d_pad), kv_spec(dv_pad),
                q_inner(dv_pad), row_inner, row_inner]
    args = [qf, kf, vf, gf, lse, di]
    if has_mask:
        in_specs.append(pl.BlockSpec((1, _SUBLANES, bk),
                                     lambda i, jk, t: (i, 0, jk),
                                     memory_space=pltpu.VMEM))
        args.append(_fold_mask(kv_mask, BKV // kv_mask.shape[0]))
    with jax.named_scope(kernel_scope("flash_dkv")):
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, width=width, group=group,
                              **kparams),
            out_shape=[
                _sds((BKV, T, d_pad), kf.dtype, gf),
                _sds((BKV, T, dv_pad), vf.dtype, gf),
            ],
            grid=(BKV, band.n_k, group * width),  # dk/dv carry in scratch
            in_specs=in_specs,
            out_specs=[kv_spec(d_pad), kv_spec(dv_pad)],
            scratch_shapes=[
                pltpu.VMEM((bk, d_pad), jnp.float32),
                pltpu.VMEM((bk, dv_pad), jnp.float32),
            ],
            compiler_params=semantics,
            interpret=interpret,
        )(*args)
    return dq, dk, dv


def _resolve_interpret(interpret):
    """interpret=None defaults to compiled (mosaic) on a TPU and to the
    Pallas interpreter anywhere else."""
    if interpret is None:
        from tpu_ddp.parallel.runtime import is_tpu_device

        return not is_tpu_device()
    return interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, kv_mask, block_q, block_k, interpret, causal, window,
           diffusion=None):
    out, _ = _flash_forward(
        q, k, v, kv_mask, block_q=block_q, block_k=block_k,
        interpret=_resolve_interpret(interpret), causal=causal,
        window=window, diffusion=diffusion,
    )
    return out


def _fwd(q, k, v, kv_mask, block_q, block_k, interpret, causal, window,
         diffusion):
    """What lives from the forward pass to the backward pass, beside the
    operands: ``out`` (``OUT_NAME``) and one float32 a row of the
    logsumexp, (B*H, T) (``LSE_NAME``): lane 0 of the kernel's
    lane-replicated buffer, which ``_bwd`` widens again beside ``di``. The
    (rows, 128) buffer does not outlive its pass: kept under a policy it
    would be 268-537 MB a layer at the decoder cells' sizes, where a value
    a row is 2-4 MB. The interpreted-under-``shard_map`` detour has no
    statistics and names ``out`` alone."""
    out, lse = _flash_forward(
        q, k, v, kv_mask, block_q=block_q, block_k=block_k,
        interpret=_resolve_interpret(interpret), causal=causal,
        window=window, diffusion=diffusion,
    )
    out = checkpoint_name(out, OUT_NAME)
    if lse is not None:
        lse = checkpoint_name(lse[:, :, 0], LSE_NAME)
    return out, (q, k, v, kv_mask, out, lse)


def _bwd(block_q, block_k, interpret, causal, window, diffusion, res, g):
    q, k, v, kv_mask, o, lse = res
    if lse is None:  # forward took the interpreted-under-shard_map detour
        _, vjp = jax.vjp(
            lambda a, b, c: _reference(a, b, c, causal=causal,
                                       kv_mask=kv_mask, window=window,
                                       diffusion=diffusion),
            q, k, v,
        )
        dq, dk, dv = vjp(g)
    else:
        # lane-replicated again, as the kernels read it (``_lanes``)
        lse = jnp.broadcast_to(lse[:, :, None], lse.shape + (LANE,))
        dq, dk, dv = _flash_backward(
            q, k, v, o, lse, g, kv_mask, block_q=block_q, block_k=block_k,
            interpret=_resolve_interpret(interpret), causal=causal,
            window=window, diffusion=diffusion,
        )
    dm = None if kv_mask is None else jnp.zeros_like(kv_mask)
    return dq, dk, dv, dm


_flash.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None, *, causal: bool = False,
                    kv_mask=None, window: int = 0, diffusion=None):
    """(B, T, H, D) attention as a Pallas TPU kernel (fwd + bwd). ``v`` may
    be of another width than ``q`` and ``k`` (``o`` is of ``v``'s); the
    scale is ``1 / sqrt(q.shape[-1])``.

    ``causal`` masks col > row; ``window`` (which implies it) also masks
    col <= row - window. Only the kv blocks a q block can see are visited
    (the decoder regime: the tiles on and under the diagonal, a band of
    them under a window). ``k`` and ``v`` may carry fewer heads
    than ``q``: (B, T, KV, D) with ``H % KV == 0``, each shared by a group
    of ``H // KV`` query heads. ``kv_mask`` (B, Tk),
    nonzero = attend, masks key/value columns (padding); rows with no
    visible key output exactly 0, with clean zero gradients. ``interpret``
    defaults to True off TPU (CPU tests) and False on TPU. A T the blocks
    cannot tile (``_plan``) is zero-padded here, outside the custom_vjp,
    with the padded keys masked and the padded rows sliced away — AD of
    the pad/slice keeps the gradients exact. No analog in the reference
    (attention-free CNN, SURVEY.md §5.7); the causal/masked forms cover
    the decoder workloads the ring-parallel long-context path implies.

    ``diffusion`` = (L, B) is a third visibility, in place of ``causal``
    and ``window``: the sequence is ``[clean ‖ noisy]``, L positions and
    their L noised copies, in blocks of B; with ``b(i) = (i mod L) // B``,
    a clean row sees the clean columns of blocks ``<= b(i)``, a noisy row
    the clean columns of blocks ``< b(i)`` and the noisy columns of block
    ``b(i)``, and no clean row a noisy column (block-diffusion training,
    arXiv:2503.09573). Only the tiles that hold a visible pair are visited
    (``_DiffusionBlocks``); the tiles must divide L."""
    B, T = q.shape[:2]
    window = int(window or 0)
    causal = bool(causal or window)
    if kv_mask is not None:
        kv_mask = kv_mask.astype(jnp.float32)
    if diffusion is not None:
        # tiles are planned on a half, so that none lies across both: a
        # short sequence runs as one whole-half block each way
        diffusion = tuple(int(n) for n in diffusion)
        plan = _plan((B, diffusion[0]) + q.shape[2:], block_q, block_k,
                     kv_mask is not None)
        if plan.t_pad != diffusion[0]:
            raise ValueError(
                f"flash attention: blocks ({block_q}, {block_k}) do not "
                f"tile a half of the block-diffusion mask {diffusion}, and "
                "it takes no padding")
        _visit_lists(T, plan.bq, plan.bk, causal, window, diffusion)
        return _flash(q, k, v, kv_mask, plan.bq, plan.bk, interpret, causal,
                      window, diffusion)
    plan = _plan(q.shape, block_q, block_k, kv_mask is not None)
    if plan.t_pad == T:
        return _flash(q, k, v, kv_mask, plan.bq, plan.bk, interpret, causal,
                      window)
    pad = plan.t_pad - T
    q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
               for x in (q, k, v))
    if kv_mask is None:
        kv_mask = jnp.ones((B, T), jnp.float32)
    kv_mask = jnp.pad(kv_mask, ((0, 0), (0, pad)))
    return _flash(q, k, v, kv_mask, plan.bq, plan.bk, interpret,
                  causal, window)[:, :T]
