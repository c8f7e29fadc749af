"""The state-space scan of a Mamba-2 mixer in its chunked form (state space
duality, arXiv:2405.21060) as two Pallas TPU kernels, forward and a backward
pass written by hand.

For each sequence and each head ``h`` of group ``g`` the recurrence is

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        (P x N, float32)
    y_t = S_t C_t                             a_t = exp(dt_t A_h),  A_h < 0

``ssd_scan(x, dt, A, B, C)`` computes it a chunk of ``chunk`` positions at a
time. Inside a chunk the outputs are one masked product: with ``la`` the
running sum of ``dt A`` from the chunk's start,

    y_i = sum_{j <= i} exp(la_i - la_j) (C_i . B_j) dt_j x_j + exp(la_i) S_0 C_i

and between chunks only the P x N state at each chunk's start is carried,
``S_0`` of the next chunk being ``exp(la_L) S_0 + sum_j exp(la_L - la_j) dt_j
x_j B_j^T``. The products run on the matrix unit in the operands' type with
float32 accumulation; decays, running sums and the carried states are
float32 whatever the operands are.

Both kernels walk the grid (sequence, chunk), the chunks of a sequence in
order (backward: from the last to the first), and take the model's own
arrays: ``x`` (B, T, heads x P) and ``B``, ``C`` (B, T, groups x N) in their
own type, ``dt`` (B, T, heads) float32. No (chunk, chunk) block goes to
HBM: a grid step makes, in VMEM, ``la`` (heads, chunk) with positions on
lanes (a running sum by doubling strides, float32), each head's ``la`` down
the sublanes too (``_over_lanes``), a group's ``C B^T`` once for all its
heads, and then, a lane tile of heads at a time (two heads of 64 in 128
lanes, so that no slice is narrower than a tile), a head's ``exp(la_i -
la_j)`` under the triangle and its masked product against the tile with the
other heads' lanes zeroed. Some tiles share a loop body (``_UNROLL_FWD``,
``_UNROLL_BWD``), for the scheduler to fill one tile's waits with another's
work.

The forward kernel (``tpu_ddp.kernel.ssd_scan_fwd``) carries the state of
every head, (heads x P, N) float32, in VMEM scratch from chunk to chunk and
writes ``y`` in ``x``'s type and the state at each chunk's start. The
backward kernel (``..._bwd``) carries the gradient of the state the same
way, from the last chunk down; it reads the operands, ``dy`` and the kept
states, builds each chunk's block again, and writes ``dx``, ``ddt``, ``dB``,
``dC`` and a sequence's share of ``dA``. The gradient of ``la`` is read off
the outputs, ``dla_i = dy_i . y_i - x_i . dx_i`` inside a chunk plus ``<dS,
S>`` at the chunk's end, each in float32 from float32 accumulators, so the
cancellation between the two terms stays inside one chunk.

The residuals of the ``custom_vjp`` are the operands and the states at the
chunk boundaries, (T / chunk) P N floats a head. Neither they nor ``y`` carry
a name for a recomputation policy: a recomputed block runs the forward
kernel in both passes, 0.3 ms a block at the benchmark's widths, where
keeping its 100 MB a block took the compiled step to the compiler's memory
budget and cost the step 5.6 ms (PERF.md section 6, PR 47). A length that
is not whole chunks is padded with ``dt = 0``: a padded position decays
nothing and adds nothing. Interpreted off the TPU, as the flash kernels are;
interpreted under a ``shard_map`` the recurrence itself stands in.
``ssd_scan_stepwise`` is that recurrence, one position at a time: what the
kernels have to equal.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_ddp.ops.selective_scan import _resolve_interpret, _sds
from tpu_ddp.telemetry.phases import kernel_scope

CHUNK = 128
LANE = 128
_VMEM_LIMIT = 64 << 20
#: tiles of heads to one loop body, forward and backward: what the backward
#: kernel gains from eight is lost to spills
_UNROLL_FWD, _UNROLL_BWD = 8, 4


@dataclasses.dataclass(frozen=True)
class _Plan:
    """Sizes of one call. Heads are taken ``tile_heads`` at a time, the most
    of one group that fit a lane tile; ``rep`` lanes hold a position's
    scalar wherever a (chunk, chunk) block, a tile or a state reads it."""

    batch: int
    T: int
    heads: int
    P: int
    groups: int
    N: int
    chunk: int

    @classmethod
    def of(cls, x, B, chunk: int):
        return cls(*x.shape, *B.shape[2:], chunk)

    @property
    def chunks(self) -> int:
        return self.T // self.chunk

    @property
    def tile_heads(self) -> int:
        per_group = self.heads // self.groups
        return max(d for d in range(1, per_group + 1)
                   if per_group % d == 0 and (d == 1 or d * self.P <= LANE))

    @property
    def tile(self) -> int:
        return self.tile_heads * self.P

    @property
    def group_tiles(self) -> int:
        return self.heads // self.groups // self.tile_heads

    @property
    def rep(self) -> int:
        return max(self.chunk, self.tile, self.N)


def _dot(a, b, dtype, contract=((1,), (0,))):
    """One product on the matrix unit: operands in ``dtype``, accumulated
    and returned in float32. ``contract``: the axes summed over."""
    return lax.dot_general(a.astype(dtype), b.astype(dtype),
                           (contract, ((), ())),
                           preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a b^T
_TN = ((0,), (0,))   # a^T b


def _aligned(first, multiple: int):
    """A slice's start that is a whole number of ``multiple``: said to the
    compiler where it is not a Python number."""
    return first if isinstance(first, int) else pl.multiple_of(first,
                                                               multiple)


def _of_group(g: int, N: int, *refs):
    """Group ``g``'s (chunk, N) block of each of ``refs``."""
    return [ref[0, :, g * N:(g + 1) * N] for ref in refs]


def _scores(plan: _Plan, b_ref, c_ref, scores_ref, dtype):
    """Each group's ``C B^T`` (chunk, chunk), once for all its heads."""
    for g in range(plan.groups):
        B, C = _of_group(g, plan.N, b_ref, c_ref)
        scores_ref[g] = _dot(C, B, dtype, _NT)


def _running_sum(v, reverse: bool = False):
    """The running sum of ``v`` (heads, chunk) over its lanes (``reverse``:
    from the last lane down), by doubling strides: float32 additions only."""
    n = v.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, v.shape, 1)
    stride = 1
    while stride < n:
        if reverse:   # lane i takes lane i + stride
            v = v + jnp.where(lane < n - stride,
                              pltpu.roll(v, n - stride, 1), 0.0)
        else:
            v = v + jnp.where(lane >= stride, pltpu.roll(v, stride, 1), 0.0)
        stride *= 2
    return v


def _over_lanes(row, lanes: int):
    """(chunk, lanes): a (1, chunk) row's positions down the sublanes, each
    over every lane: the row over sublanes, transposed (on the chip as long
    as a column laid over lanes directly, 0.70 against 0.64 us for sixteen:
    PERF.md section 6, PR 47; the scalars are kept as rows)."""
    return jnp.broadcast_to(row, (lanes, row.shape[1])).T


def _decays(plan: _Plan, dt_ref, a_ref, la_rep_ref, la_rows_ref,
            dt_rows_ref):
    """Positions on lanes: ``dt`` (heads, chunk) and ``la``, the running sum
    of ``dt A`` from the chunk's start, in VMEM scratch; and each head's
    ``la`` down the sublanes over ``rep`` lanes (heads, chunk, rep)."""
    dt = dt_ref[0].T
    la = _running_sum(dt * a_ref[...])
    dt_rows_ref[...] = dt
    la_rows_ref[...] = la

    def down(h, carry):
        la_rep_ref[h] = _over_lanes(la_rows_ref[pl.ds(h, 1), :], plan.rep)
        return carry

    lax.fori_loop(0, plan.heads, down, 0, unroll=True)


def _triangle(chunk: int):
    """(chunk, chunk): position j is visible to position i."""
    return (lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))


class _Tile:
    """What both kernels read of one lane tile of heads in one chunk."""

    def __init__(self, plan: _Plan, first_head, la_rep_ref, la_rows_ref,
                 dt_rows_ref):
        self.plan = plan
        chunk, width = plan.chunk, plan.tile
        self.heads = [first_head + j for j in range(plan.tile_heads)]
        lane = lax.broadcasted_iota(jnp.int32, (chunk, width), 1)
        #: the lanes of each of the tile's heads
        self.lanes = [(lane >= j * plan.P) & (lane < (j + 1) * plan.P)
                      for j in range(plan.tile_heads)]
        self.la_rep_ref, self.la_rows_ref = la_rep_ref, la_rows_ref
        la = la_rep_ref[self.heads[0], :, :width]
        for h, lanes in zip(self.heads[1:], self.lanes[1:]):
            la = jnp.where(lanes, la_rep_ref[h, :, :width], la)
        self.from_start = jnp.exp(la)                 # exp(la_i)
        self.to_end = jnp.exp(la[chunk - 1:chunk] - la)   # exp(la_L - la_j)
        # (chunk, tile): each head's ``dt`` over its own lanes
        row = lax.broadcasted_iota(jnp.int32, (width, chunk), 0)
        dt = jnp.broadcast_to(self.row(dt_rows_ref, 0), (width, chunk))
        for j in range(1, plan.tile_heads):
            dt = jnp.where(row >= j * plan.P, self.row(dt_rows_ref, j), dt)
        self.dt = dt.T

    def row(self, rows_ref, j: int):
        """(1, chunk): head ``j``'s row of a (heads, chunk) scratch."""
        return rows_ref[pl.ds(self.heads[j], 1), :]

    def only(self, j: int, tile):
        """``tile`` with the lanes of every head but the ``j``-th zeroed."""
        if self.plan.tile_heads == 1:
            return tile
        return jnp.where(self.lanes[j], tile, jnp.zeros_like(tile))

    def within(self, j: int, triangle):
        """(chunk, chunk): ``exp(la_i - la_j)`` of head ``j`` under the
        triangle, else 0 (never the exponential of a positive number)."""
        return jnp.exp(jnp.where(
            triangle,
            self.la_rep_ref[self.heads[j], :, :self.plan.chunk]
            - self.row(self.la_rows_ref, j), -jnp.inf))

    def whole(self, j: int):
        """(1, N): the decay over the whole chunk, ``exp(la_L)``."""
        chunk = self.plan.chunk
        return jnp.exp(
            self.la_rep_ref[self.heads[j], chunk - 1:chunk, :self.plan.N])

    def rows(self, j: int):
        """The rows of head ``j`` in a (heads x P, N) state."""
        P = self.plan.P
        return pl.ds(_aligned(self.heads[j] * P, P), P)

    def head_rows(self, j: int, of):
        """The rows of head ``j`` in a (tile, ...) value."""
        return of[j * self.plan.P:(j + 1) * self.plan.P]


def _group_tiles(plan: _Plan, unroll: int, body):
    """``body(g, first lane of the tile, first head)`` over the tiles of
    each group: groups unrolled, a group's tiles in a loop, ``unroll`` to a
    loop body where they divide: the scheduler fills one tile's waits for
    the matrix unit with the next tile's vector work (a grid step of the
    forward kernel is 3,980 bundles a tile at a time and 2,280 all eight:
    PERF.md section 6, PR 47)."""
    unroll = unroll if plan.group_tiles % unroll == 0 else 1
    for g in range(plan.groups):
        def some(k, carry, g=g):
            def one(i, carry):
                tile = g * plan.group_tiles + k * unroll + i
                body(g, _aligned(tile * plan.tile, plan.tile),
                     tile * plan.tile_heads)
                return carry

            # unrolled where the kernel is lowered, so traced once
            return lax.fori_loop(0, unroll, one, carry, unroll=True)

        if plan.group_tiles == unroll:
            some(0, 0)
        else:
            lax.fori_loop(0, plan.group_tiles // unroll, some, 0)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, starts_ref,
                state_ref, la_rep_ref, la_rows_ref, dt_rows_ref, scores_ref,
                *, plan: _Plan):
    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    dtype, N = x_ref.dtype, plan.N
    starts_ref[0, 0] = state_ref[...]
    _decays(plan, dt_ref, a_ref, la_rep_ref, la_rows_ref, dt_rows_ref)
    triangle = _triangle(plan.chunk)
    _scores(plan, b_ref, c_ref, scores_ref, dtype)

    def tile(g, first_lane, first_head):
        t = _Tile(plan, first_head, la_rep_ref, la_rows_ref, dt_rows_ref)
        lanes = pl.ds(first_lane, plan.tile)
        B, C = _of_group(g, N, b_ref, c_ref)
        xdt = (x_ref[0, :, lanes].astype(jnp.float32) * t.dt)
        # the carried state's share, then each head's masked product
        y = _dot(C, state_ref[lanes, :], dtype, _NT) * t.from_start
        for j in range(plan.tile_heads):
            weights = scores_ref[g] * t.within(j, triangle)
            y = y + _dot(weights, t.only(j, xdt), dtype)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        added = _dot(xdt * t.to_end, B, dtype, _TN)          # (tile, N)
        for j in range(plan.tile_heads):
            rows = t.rows(j)
            state_ref[rows, :] = (t.whole(j) * state_ref[rows, :]
                                  + t.head_rows(j, added))

    _group_tiles(plan, _UNROLL_FWD, tile)


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dy_ref, starts_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                dstate_ref, da_acc_ref, la_rep_ref, la_rows_ref, dt_rows_ref,
                scores_ref, dscores_ref, db_acc_ref, dc_acc_ref,
                direct_ref, dla_ref, *, plan: _Plan):
    @pl.when(pl.program_id(1) == 0)  # the last chunk: walked in reverse
    def _():
        dstate_ref[...] = jnp.zeros(dstate_ref.shape, jnp.float32)
        da_acc_ref[...] = jnp.zeros(da_acc_ref.shape, jnp.float32)

    dtype, N, chunk = x_ref.dtype, plan.N, plan.chunk
    _decays(plan, dt_ref, a_ref, la_rep_ref, la_rows_ref, dt_rows_ref)
    triangle = _triangle(chunk)
    at_last = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    _scores(plan, b_ref, c_ref, scores_ref, dtype)
    dscores_ref[...] = jnp.zeros(dscores_ref.shape, jnp.float32)
    db_acc_ref[...] = jnp.zeros(db_acc_ref.shape, jnp.float32)
    dc_acc_ref[...] = jnp.zeros(dc_acc_ref.shape, jnp.float32)

    def tile(g, first_lane, first_head):
        t = _Tile(plan, first_head, la_rep_ref, la_rows_ref, dt_rows_ref)
        lanes = pl.ds(first_lane, plan.tile)
        B, C = _of_group(g, N, b_ref, c_ref)
        x = x_ref[0, :, lanes]
        dy = dy_ref[0, :, lanes]
        x32, dy32 = x.astype(jnp.float32), dy.astype(jnp.float32)
        start, dend = starts_ref[0, 0, lanes, :], dstate_ref[lanes, :]
        xdt = x32 * t.dt
        to_end = xdt * t.to_end          # what each position adds to the end
        from_start = dy32 * t.from_start   # what each reads of the start

        # the outputs again, and ``u``: the gradient of ``dt_j x_j``
        y = _dot(C, start, dtype, _NT) * t.from_start
        u = _dot(B, dend, dtype, _NT) * t.to_end
        for j in range(plan.tile_heads):
            within = t.within(j, triangle)
            weights = (scores_ref[g] * within).astype(dtype)
            y = y + _dot(weights, t.only(j, xdt), dtype)
            u = u + _dot(weights, t.only(j, dy), dtype, _TN)
            pairs = _dot(t.only(j, dy), x, dtype, _NT) * within
            dscores_ref[g] += pairs * t.row(dt_rows_ref, j)
        dx_ref[0, :, lanes] = (t.dt * u).astype(dx_ref.dtype)
        dc_acc_ref[g] += _dot(from_start, start, dtype)
        db_acc_ref[g] += _dot(to_end, dend, dtype)

        added = _dot(to_end, B, dtype, _TN)                   # (tile, N)
        used = _dot(from_start, C, dtype, _TN)
        # d dt through dt_j x_j, and d la, read off the outputs: a head's
        # sums over its lanes, taken over sublanes of the transposed tile
        through_x, through_y = (x32 * u).T, (dy32 * y).T      # (tile, chunk)
        for j in range(plan.tile_heads):
            h, rows, whole = t.heads[j], t.rows(j), t.whole(j)
            dend_h = dstate_ref[rows, :]
            end = whole * starts_ref[0, 0, rows, :] + t.head_rows(j, added)
            at_end = jnp.sum(jnp.sum(dend_h * end, axis=1, keepdims=True),
                             axis=0, keepdims=True)
            dstate_ref[rows, :] = whole * dend_h + t.head_rows(j, used)
            direct = jnp.sum(t.head_rows(j, through_x), axis=0,
                             keepdims=True)                   # (1, chunk)
            dla = (jnp.sum(t.head_rows(j, through_y), axis=0, keepdims=True)
                   - t.row(dt_rows_ref, j) * direct)
            direct_ref[pl.ds(h, 1), :] = direct
            dla_ref[pl.ds(h, 1), :] = dla + jnp.where(at_last, at_end, 0.0)

    _group_tiles(plan, _UNROLL_BWD, tile)

    for g in range(plan.groups):
        B, C = _of_group(g, N, b_ref, c_ref)
        dscores = dscores_ref[g].astype(dtype)
        dc_ref[0, :, g * N:(g + 1) * N] = (
            _dot(dscores, B, dtype) + dc_acc_ref[g]).astype(dc_ref.dtype)
        db_ref[0, :, g * N:(g + 1) * N] = (
            _dot(dscores, C, dtype, _TN) + db_acc_ref[g]).astype(db_ref.dtype)

    later = _running_sum(dla_ref[...], reverse=True)          # (heads, chunk)
    ddt_ref[0] = (direct_ref[...] + a_ref[...] * later).T
    da_acc_ref[...] += jnp.sum(dt_rows_ref[...] * later, axis=1,
                               keepdims=True)
    da_ref[0] = da_acc_ref[...]


def _specs(plan: _Plan, chunk_of):
    """Block specs by kind of operand; ``chunk_of(c)`` is the chunk a grid
    step reads (the backward pass walks them in reverse)."""
    vmem = dict(memory_space=pltpu.VMEM)
    rows = lambda width: pl.BlockSpec(  # noqa: E731
        (1, plan.chunk, width), lambda b, c: (b, chunk_of(c), 0), **vmem)
    hp = plan.heads * plan.P
    return dict(
        x=rows(hp), dt=rows(plan.heads), bc=rows(plan.groups * plan.N),
        a=pl.BlockSpec((plan.heads, 1), lambda b, c: (0, 0), **vmem),
        da=pl.BlockSpec((1, plan.heads, 1), lambda b, c: (b, 0, 0), **vmem),
        states=pl.BlockSpec((1, 1, hp, plan.N),
                            lambda b, c: (b, chunk_of(c), 0, 0), **vmem))


def _scratch(plan: _Plan):
    """Float32 VMEM scratch by kind."""
    f32, chunk = jnp.float32, plan.chunk
    return dict(
        state=pltpu.VMEM((plan.heads * plan.P, plan.N), f32),
        rep=pltpu.VMEM((plan.heads, chunk, plan.rep), f32),
        rows=pltpu.VMEM((plan.heads, chunk), f32),
        blocks=pltpu.VMEM((plan.groups, chunk, chunk), f32),
        bc=pltpu.VMEM((plan.groups, chunk, plan.N), f32),
        a=pltpu.VMEM((plan.heads, 1), f32))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _flat(plan: _Plan, x, dt, A, B, C):
    """The kernels' operands: heads and groups side by side on lanes (no
    copy), ``dt`` float32 and ``A`` a float32 column."""
    b, T = plan.batch, plan.T
    return (x.reshape(b, T, -1), dt.astype(jnp.float32),
            A.astype(jnp.float32)[:, None], B.reshape(b, T, -1),
            C.reshape(b, T, -1))


@functools.partial(jax.jit, static_argnums=(0, 6))
def _forward(plan: _Plan, x, dt, a, B, C, interpret: bool):
    """(y in ``x``'s type, the float32 state at each chunk's start (batch,
    chunks, heads x P, N)) of flat operands. Jitted, as ``_backward`` is, so
    that a program traces and lowers a kernel once and not once a call site
    (fifteen in the benchmark's step, three seconds of every start: PERF.md
    section 6, PR 47); XLA inlines the calls under each site's own scopes."""
    s, v = _specs(plan, lambda c: c), _scratch(plan)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan),
        out_shape=[
            _sds(x.shape, x.dtype, x, dt),
            _sds((plan.batch, plan.chunks, x.shape[-1], plan.N),
                 jnp.float32, x, dt)],
        grid=(plan.batch, plan.chunks),
        in_specs=[s["x"], s["dt"], s["a"], s["bc"], s["bc"]],
        out_specs=[s["x"], s["states"]],
        scratch_shapes=[v["state"], v["rep"], v["rows"], v["rows"],
                        v["blocks"]],
        compiler_params=_params(),
        interpret=interpret,
    )(x, dt, a, B, C)


@functools.partial(jax.jit, static_argnums=(0, 8))
def _backward(plan: _Plan, x, dt, a, B, C, dy, starts, interpret: bool):
    """(dx, ddt float32, dA as (batch, heads, 1) float32, dB, dC) of flat
    operands."""
    last = plan.chunks - 1
    s, v = _specs(plan, lambda c: last - c), _scratch(plan)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        out_shape=[
            _sds(x.shape, x.dtype, x, dy),
            _sds(dt.shape, jnp.float32, x, dy),
            _sds((plan.batch, plan.heads, 1), jnp.float32, x, dy),
            _sds(B.shape, B.dtype, x, dy), _sds(C.shape, C.dtype, x, dy)],
        grid=(plan.batch, plan.chunks),
        in_specs=[s["x"], s["dt"], s["a"], s["bc"], s["bc"], s["x"],
                  s["states"]],
        out_specs=[s["x"], s["dt"], s["da"], s["bc"], s["bc"]],
        scratch_shapes=[
            v["state"], v["a"], v["rep"], v["rows"], v["rows"],
            v["blocks"], v["blocks"], v["bc"], v["bc"], v["rows"],
            v["rows"]],
        compiler_params=_params(),
        interpret=interpret,
    )(x, dt, a, B, C, dy, starts)


def _detour(x) -> bool:
    """Interpreted under a ``shard_map`` (a CPU test of a train step): the
    interpreter's own slices fail the mesh-axes check, as the flash
    kernels' do; the recurrence itself stands in."""
    return _resolve_interpret(None) and bool(jax.typeof(x).vma)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, dt, A, B, C, chunk):
    return _scan_fwd(x, dt, A, B, C, chunk)[0]


def _scan_fwd(x, dt, A, B, C, chunk):
    if _detour(x):
        y, starts = ssd_scan_stepwise(x, dt, A, B, C), None
    else:
        plan = _Plan.of(x, B, chunk)
        with jax.named_scope(kernel_scope("ssd_scan_fwd")):
            y, starts = _forward(plan, *_flat(plan, x, dt, A, B, C),
                                 _resolve_interpret(None))
        y = y.reshape(x.shape)
        starts = starts.reshape(plan.batch, plan.chunks, plan.groups, -1,
                                plan.P, plan.N)
    return y, (x, dt, A, B, C, starts)


def _scan_bwd(chunk, res, dy):
    *operands, starts = res
    x, dt, A, B, C = operands
    if starts is None:
        return jax.vjp(ssd_scan_stepwise, *operands)[1](dy)
    plan = _Plan.of(x, B, chunk)
    flat = _flat(plan, *operands)
    with jax.named_scope(kernel_scope("ssd_scan_bwd")):
        dx, ddt, dA, dB, dC = _backward(
            plan, *flat, dy.reshape(flat[0].shape),
            starts.reshape(plan.batch, plan.chunks, -1, plan.N),
            _resolve_interpret(None))
    return (dx.reshape(x.shape), ddt.astype(dt.dtype),
            jnp.sum(dA, axis=(0, 2)).astype(A.dtype), dB.reshape(B.shape),
            dC.reshape(C.shape))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, A, B, C, chunk: int = CHUNK):
    """``x`` (b, T, H, P) inputs, ``dt`` (b, T, H) step sizes (after their
    softplus), ``A`` (H,) negative decay rates, ``B`` and ``C`` (b, T, G, N)
    with ``H % G == 0``, the heads of a group sharing them. Returns ``y``
    (b, T, H, P) in ``x``'s type: the recurrence's outputs, without the skip
    ``D x``, which is the mixer's."""
    # under ``shard_map`` a parameter (``A``) is the same on every shard
    # and the activations are not: the backward rule's ``dA`` is a shard's
    # own, so ``A`` is marked varying here and AD's transpose of that mark
    # is the sum over shards
    varying = frozenset().union(*(jax.typeof(a).vma for a in (x, dt, B, C)))
    x, dt, A, B, C = (
        lax.pcast(a, tuple(varying - jax.typeof(a).vma), to="varying")
        if varying - jax.typeof(a).vma else a for a in (x, dt, A, B, C))
    t = x.shape[1]
    chunk = min(chunk, t)
    pad = -t % chunk
    if not pad:
        return _scan(x, dt, A, B, C, chunk)
    x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                   for a in (x, dt, B, C))
    return _scan(x, dt, A, B, C, chunk)[:, :t]


def ssd_scan_stepwise(x, dt, A, B, C):
    """The recurrence one position at a time, float32: what ``ssd_scan``
    has to equal, values and gradients."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # noqa: E731
    heads = lambda a: jnp.repeat(a, h // g, axis=1)            # noqa: E731

    def step(state, now):
        xt, dtt, Bt, Ct = now
        decay = jnp.exp(dtt * A.astype(jnp.float32))           # (b, h)
        state = (decay[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None]
                 * heads(Bt)[:, :, None, :])
        return state, jnp.sum(state * heads(Ct)[:, :, None, :], axis=-1)

    # zeros that vary over the mesh axes ``x`` varies over (``shard_map``)
    state = jnp.zeros((b, h, p, n), jnp.float32) * f32(x)[0, ..., None]
    _, y = lax.scan(step, state, (f32(x), f32(dt), f32(B), f32(C)))
    return jnp.moveaxis(y, 0, 1).astype(x.dtype)
