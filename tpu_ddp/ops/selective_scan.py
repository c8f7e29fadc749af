"""The selective scan of a Mamba-1 mixer (arXiv:2312.00752) as two Pallas TPU
kernels, forward and a backward pass written by hand.

For each sequence, channel ``c`` and state ``n`` the recurrence is

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] x_t[c]           (S float32)

The decay differs by channel *and* state, so there is no (position,
position) score to put on the matrix unit as the chunked scan of Mamba-2
does (``ops/ssd_scan.py``): the work is on the vector unit, and the state of
every position, ``T * channels * N`` floats, never goes to HBM.

``selective_scan(x, dt, A, B, C, D)``: ``x`` and ``dt`` (B, T, channels),
``A`` (channels, N), ``B`` and ``C`` (B, T, N), ``D`` (channels,); float32
inside, ``y`` in ``x``'s type. Both kernels walk the grid (sequence, time
block, channel block), time blocks in order (backward: in reverse), with
the state of every channel, ``(N, channels)`` with channels on lanes and
states on sublanes, in VMEM scratch across time blocks.

The kernels take the model's own arrays (PR 46). ``x`` (and the backward
pass's ``dy``) is read in its own type, bfloat16 in the model, and a block
is widened to float32 in VMEM once a grid step; ``dt`` is float32 and, where
nothing is padded, the caller's array. ``B`` and ``C`` reach the kernels as
(B, N, T) float32, positions on lanes, half a megabyte each where a row is
16,384 positions; a time block's lane-replicated (N, 128) tile a position
(a load and no shuffle in the sweep, as the flash kernels' row statistics
are) is built in VMEM at the time block's first channel block and serves
the others (a position's lane by a mask and a sum over lanes, in a loop: a
static slice a position is 7-8% less of the kernels' time on the chip
and five seconds more of every start, the step's trace and lowering:
``PERF.md`` section 6, PR 46). So a time block is whole lane groups, or the
whole length.

A position's sum over states (``y``; in the backward pass ``sum_n dS B``
and ``sum_n d(dt A) A``) is left as an (8, lanes) tile, and the eight
positions of an unrolled group are summed over sublanes together
(``_fold``: a butterfly, ten sublane rotations for eight sums where one at a
time takes twenty-four) into one dense tile of eight rows, which is also
what ``dx`` and ``ddt`` are then made from: the kernels are bound by the
vector unit's issue slots (96% full in the compiler's own count), so an
operation less is time less.

The forward kernel (``tpu_ddp.kernel.selective_scan_fwd``) writes ``y``
with the skip ``D x``, rounded once to ``x``'s type, and the state at each
time block's start, ``T / block_t * channels * N`` floats. The backward
kernel (``..._bwd``) rebuilds a block's states from its checkpoint into
VMEM, the decays ``exp(dt A)`` beside them, then sweeps the block in reverse
with the states' gradient carried:
``dx`` with the skip's ``D dy`` in ``x``'s type, ``ddt`` float32, ``dA``
and ``dD`` accumulated over the whole call in VMEM, ``dB`` and ``dC``
summed over channel blocks in VMEM and over lanes at a time block's last
channel block, (B, N, T) float32. What XLA is left around the two calls is
the transposes and casts of ``A``, ``B``, ``C`` and their gradients, a
megabyte in all. The residuals of the ``custom_vjp`` are the operands and
the checkpoints; ``y`` and the checkpoints carry names (``Y_NAME``,
``CKPT_NAME``), so that a recomputed layer that keeps both
(``models/decoder.py::KEPT_NAMES``) runs the forward kernel once.

A length that is not whole time blocks is padded with ``dt = 0`` and ``x =
0``: a padded position decays nothing and adds nothing. Channels pad to
whole lane blocks with ``A = 0``, states to whole sublane tiles with ``B = C
= 0``; a shape that needs none of it is not copied. Interpreted off the
TPU, as the flash kernels are.
``selective_scan_stepwise`` is the recurrence itself, one position at a
time, for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_ddp.telemetry.phases import kernel_scope

LANE = 128
_SUBLANES = 8
#: positions of a time block and lanes of a channel block; the backward
#: kernel holds a block's states and its decays, twice ``BLOCK_T * N *
#: BLOCK_C`` floats, in VMEM
BLOCK_T = 128
BLOCK_C = 512
#: positions of the sweep unrolled into one loop body, whose sums over
#: states leave as the eight rows of one tile (``_fold``); a time block is
#: a whole number of them
_UNROLL = _SUBLANES
_VMEM_LIMIT = 64 << 20
#: the scan's output and the state at each time block's start, by name to a
#: recomputation policy: a recomputed caller that keeps both runs the forward
#: kernel once
Y_NAME = "selective_scan_y"
CKPT_NAME = "selective_scan_checkpoints"


def _stepwise(x, dt, A, B, C):
    """The recurrence without the skip, one position at a time (``lax.scan``),
    float32 in and out."""
    x32, dt32 = x.astype(jnp.float32), dt.astype(jnp.float32)

    def step(state, now):
        xt, dtt, bt, ct = now
        state = (jnp.exp(dtt[..., None] * A) * state
                 + (dtt * xt)[..., None] * bt[:, None, :])
        return state, jnp.sum(state * ct[:, None, :], axis=-1)

    time_first = lambda a: jnp.moveaxis(  # noqa: E731
        a.astype(jnp.float32), 1, 0)
    # zeros that vary over the mesh axes ``x`` varies over (``shard_map``)
    state = jnp.zeros(A.shape, jnp.float32) * x32[:, 0, :, None]
    _, y = lax.scan(step, state, tuple(map(time_first, (x32, dt32, B, C))))
    return jnp.moveaxis(y, 0, 1)


def selective_scan_stepwise(x, dt, A, B, C, D):
    """The recurrence itself, with the skip; ``y`` in ``x``'s type."""
    return (_stepwise(x, dt, A, B, C)
            + D * x.astype(jnp.float32)).astype(x.dtype)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _row(ref, t):
    """Row ``t`` of a block's last two axes, (1, lanes): of an operand's (1,
    block_t, lanes) block or of (block_t, lanes) scratch."""
    return ref[(0,) * (len(ref.shape) - 2) + (pl.ds(t, 1), slice(None))]


def _lanes(tile, width: int):
    """A lane-replicated (N, 128) tile over ``width`` lanes."""
    return jnp.concatenate([tile] * (width // LANE), axis=-1)


def _lane_groups(tile):
    """The (N, 128) sum of a (N, width) tile's lane groups."""
    return sum(tile[:, i:i + LANE] for i in range(0, tile.shape[-1], LANE))


def _unrolled(positions: int, body, carry):
    """``lax.fori_loop(0, positions, body, carry)``, ``_UNROLL`` positions a
    loop body (Mosaic's own ``unroll`` is all or nothing)."""
    def group(g, carry):
        for i in range(_UNROLL):
            carry = body(g * _UNROLL + i, carry)
        return carry

    return lax.fori_loop(0, positions // _UNROLL, group, carry)


def _replicate(src_ref, rep_ref):
    """A time block of ``B`` or ``C``, (N, block_t) with positions on lanes,
    as a lane-replicated (N, 128) tile a position in VMEM scratch. A lane at
    a dynamic index is a mask and a sum over lanes: a loop of a few
    instructions, where a static slice a position is a program of
    thousands that every trace of the step pays for (and a gather along
    lanes, ``jnp.take_along_axis``, is slower than either on the chip)."""
    tile = src_ref[0]
    lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)

    def one(t, carry):
        column = jnp.sum(jnp.where(lane == t, tile, 0.0), axis=-1,
                         keepdims=True)
        rep_ref[t] = jnp.broadcast_to(column, rep_ref.shape[1:])
        return carry

    _unrolled(rep_ref.shape[0], one, 0)


def _lane_sums(acc_ref, out_ref):
    """The (N, 128) partial sums a position summed over lanes, into a
    (N, block_t) block with positions on lanes."""
    shape = out_ref.shape[1:]
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)

    def one(t, out):
        return jnp.where(
            lane == t, jnp.sum(acc_ref[t], axis=-1, keepdims=True), out)

    out_ref[0] = _unrolled(acc_ref.shape[0], one,
                           jnp.zeros(shape, jnp.float32))


def _halves(tile):
    """The (8, width) sum of a (N, width) tile's sublane tiles: what is left
    of a sum over states is a sum over eight sublanes."""
    return sum(tile[i:i + _SUBLANES] for i in range(0, tile.shape[0],
                                                    _SUBLANES))


def _fold(tiles):
    """(8, width) with row ``i`` the sum over the eight sublanes of
    ``tiles[i]``, eight (8, width) tiles: a butterfly of three stages, each
    halving the tiles (a pair keeps its own rows' halves and takes the
    other's, moved by the stage's stride), so that the eight sums cost ten
    sublane rotations and not twenty-four, and leave as one dense tile."""
    rows = lax.broadcasted_iota(jnp.int32, tiles[0].shape, 0)
    stride = 1
    while len(tiles) > 1:
        low = (rows & stride) == 0
        folded = []
        for a, b in zip(tiles[0::2], tiles[1::2]):
            swap = jnp.where(low, b, a)
            up = pltpu.roll(swap, _SUBLANES - stride, 0)  # swap[s + stride]
            moved = up if 2 * stride == _SUBLANES else jnp.where(
                low, up, pltpu.roll(swap, stride, 0))
            folded.append(jnp.where(low, a, b) + moved)
        tiles, stride = folded, 2 * stride
    return tiles[0]


def _sweep(positions: int, body, carry, emit=None, reverse: bool = False):
    """``carry`` through ``body(t, carry) -> (carry, tiles)`` over the
    positions in order (``reverse``: from the last down), ``_UNROLL``
    positions a loop body (Mosaic's own ``unroll`` is all or nothing). Each
    of ``tiles`` is a (8, width) tile whose sum over sublanes is a row of
    position ``t``; ``emit(first, rows)`` is handed a group's eight rows of
    each, folded into one (8, width) tile, with the group's first position.
    """
    def group(g, carry):
        first = pl.multiple_of(
            (positions // _UNROLL - 1 - g if reverse else g) * _UNROLL,
            _UNROLL)
        kept = []
        for i in range(_UNROLL):
            carry, tiles = body(
                first + (_UNROLL - 1 - i if reverse else i), carry)
            kept.append(tiles)
        if emit is not None:
            if reverse:
                kept.reverse()
            emit(first, [_fold(list(of)) for of in zip(*kept)])
        return carry

    return lax.fori_loop(0, positions // _UNROLL, group, carry)


def _fwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, ckpt_ref,
                state_ref, x32_ref, y32_ref, b_rep_ref, c_rep_ref, *,
                block_t: int):
    tb, j = pl.program_id(1), pl.program_id(2)

    @pl.when(tb == 0)
    def _():
        state_ref[j] = jnp.zeros(state_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)  # once a time block, for every channel block
    def _():
        _replicate(b_ref, b_rep_ref)
        _replicate(c_ref, c_rep_ref)

    # the block of ``x`` widened once: a row of a packed type at a dynamic
    # index is a shuffle a position
    x32_ref[...] = x_ref[0].astype(jnp.float32)
    a = a_ref[...]
    width = a.shape[-1]
    ckpt_ref[0, 0] = state_ref[j]

    def step(t, state):
        dt = _row(dt_ref, t)
        state = (jnp.exp(dt * a) * state
                 + (dt * _row(x32_ref, t)) * _lanes(b_rep_ref[t], width))
        return state, (_halves(state * _lanes(c_rep_ref[t], width)),)

    def rows(first, folded):
        y32_ref[pl.ds(first, _UNROLL), :] = folded[0]

    state_ref[j] = _sweep(block_t, step, state_ref[j], rows)
    y_ref[0] = (y32_ref[...] + d_ref[...] * x32_ref[...]).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, dy_ref, ckpt_ref,
                dx_ref, ddt_ref, da_ref, dd_ref, db_ref, dc_ref,
                dstate_ref, da_acc_ref, dd_acc_ref, states_ref, x32_ref,
                dy32_ref, dx32_ref, b_rep_ref, c_rep_ref, db_acc_ref,
                dc_acc_ref, decay_ref, *, block_t: int):
    tb, j = pl.program_id(1), pl.program_id(2)

    @pl.when(tb == 0)  # the last time block: the grid walks them in reverse
    def _():
        dstate_ref[j] = jnp.zeros(dstate_ref.shape[1:], jnp.float32)
        da_acc_ref[j] = jnp.zeros(da_acc_ref.shape[1:], jnp.float32)
        dd_acc_ref[j] = jnp.zeros(dd_acc_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _():
        _replicate(b_ref, b_rep_ref)
        _replicate(c_ref, c_rep_ref)
        db_acc_ref[...] = jnp.zeros(db_acc_ref.shape, jnp.float32)
        dc_acc_ref[...] = jnp.zeros(dc_acc_ref.shape, jnp.float32)

    x32_ref[...] = x_ref[0].astype(jnp.float32)
    dy32_ref[...] = dy_ref[0].astype(jnp.float32)
    a = a_ref[...]
    width = a.shape[-1]

    # the block's states again, from the one at its start: states_ref[t] is
    # the state *before* position t, ``last`` the one after the whole block;
    # decay_ref[t] is position t's ``exp(dt A)``, made here once for both
    # sweeps
    def rebuild(t, state):
        states_ref[t] = state
        dt = _row(dt_ref, t)
        decay = jnp.exp(dt * a)
        decay_ref[t] = decay
        return (decay * state + (dt * _row(x32_ref, t))
                * _lanes(b_rep_ref[t], width)), ()

    # through scratch: under a ``shard_map`` a value loaded from an operand
    # or a result varies over the mesh as that does and one loaded from
    # scratch does not, and a loop's carry has to be of one kind; so the
    # loops start from scratch, and ``dA`` is summed there too
    states_ref[0] = ckpt_ref[0, 0]
    last = _sweep(block_t, rebuild, states_ref[0])

    def sweep(t, carry):
        dstate, da, after = carry     # d/dS_t from the future; S_t
        dt, x, dy = _row(dt_ref, t), _row(x32_ref, t), _row(dy32_ref, t)
        before = states_ref[t]
        dstate = dstate + dy * _lanes(c_rep_ref[t], width)
        dc_acc_ref[t] += _lane_groups(dy * after)
        db_acc_ref[t] += _lane_groups(dstate * (dt * x))
        through = dstate * decay_ref[t]            # d/dS_{t-1}
        grown = through * before                   # d/d(dt A), elementwise
        return (through, da + grown * dt, before), (
            _halves(dstate * _lanes(b_rep_ref[t], width)),
            _halves(grown * a))

    def rows(first, folded):
        by_input, by_decay = folded     # sum_n dS B, sum_n d(dt A) A
        group = pl.ds(first, _UNROLL)
        dx32_ref[group, :] = dt_ref[0, group, :] * by_input
        ddt_ref[0, group, :] = x32_ref[group, :] * by_input + by_decay

    dstate, da, _ = _sweep(block_t, sweep,
                           (dstate_ref[j], da_acc_ref[j], last), rows,
                           reverse=True)
    dstate_ref[j] = dstate
    da_acc_ref[j] = da
    da_ref[0, j] = da
    # the skip's share: ``D dy`` into ``dx``, ``dD`` summed a channel
    dy = dy32_ref[...]
    dx_ref[0] = (dx32_ref[...] + d_ref[...] * dy).astype(dx_ref.dtype)
    dd_acc_ref[j] += jnp.sum(dy * x32_ref[...], axis=0, keepdims=True)
    dd_ref[0, j] = dd_acc_ref[j]

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        _lane_sums(db_acc_ref, db_ref)
        _lane_sums(dc_acc_ref, dc_ref)


class _Plan:
    """Blocks and padded sizes of one call. ``B`` and ``C`` reach the
    kernels with positions on lanes, so a time block is whole lane groups
    unless one block holds the whole length."""

    def __init__(self, T: int, channels: int, N: int, block_t: int,
                 block_c: int):
        self.block_t = min(_round_up(block_t, LANE), _round_up(T, _UNROLL))
        self.block_c = min(block_c, _round_up(channels, LANE))
        self.T = _round_up(T, self.block_t)
        self.channels = _round_up(channels, self.block_c)
        self.N = _round_up(N, _SUBLANES)
        self.n_t = self.T // self.block_t
        self.n_c = self.channels // self.block_c


def _specs(plan: _Plan, time_block):
    """Block specs by kind of operand; ``time_block(tb)`` is the time block
    a grid step reads (the backward pass walks them in reverse)."""
    bt, bc, N = plan.block_t, plan.block_c, plan.N
    vmem = dict(memory_space=pltpu.VMEM)
    return dict(
        row=pl.BlockSpec((1, bt, bc),
                         lambda b, tb, j: (b, time_block(tb), j), **vmem),
        a=pl.BlockSpec((N, bc), lambda b, tb, j: (0, j), **vmem),
        d=pl.BlockSpec((1, bc), lambda b, tb, j: (0, j), **vmem),
        bc=pl.BlockSpec((1, N, bt),
                        lambda b, tb, j: (b, 0, time_block(tb)), **vmem),
        ckpt=pl.BlockSpec((1, 1, N, bc),
                          lambda b, tb, j: (b, time_block(tb), 0, j), **vmem))


def _scratch(plan: _Plan):
    """Float32 VMEM scratch by kind: a state a channel block (``per_block(N)``
    rows), a block's rows, a lane-replicated tile a position."""
    bt, bc, f32 = plan.block_t, plan.block_c, jnp.float32
    return dict(
        per_block=lambda rows: pltpu.VMEM((plan.n_c, rows, bc), f32),
        rows=pltpu.VMEM((bt, bc), f32),
        tiles=pltpu.VMEM((bt, plan.N, LANE), f32),
        states=pltpu.VMEM((bt, plan.N, bc), f32))


def _sds(shape, dtype, *like):
    """A result that varies over the mesh axes its operands vary over:
    inside a ``shard_map`` (the train steps) a ``pallas_call``'s outputs
    must say so."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _forward(plan: _Plan, x, dt, a_t, d, b_t, c_t, interpret: bool):
    """(y with the skip, in ``x``'s type; checkpoints) of padded operands."""
    batch = x.shape[0]
    s, v = _specs(plan, lambda tb: tb), _scratch(plan)
    with jax.named_scope(kernel_scope("selective_scan_fwd")):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, block_t=plan.block_t),
            out_shape=[
                _sds(x.shape, x.dtype, x, dt),
                _sds((batch, plan.n_t, plan.N, plan.channels), jnp.float32,
                     x, dt)],
            grid=(batch, plan.n_t, plan.n_c),
            in_specs=[s["row"], s["row"], s["a"], s["d"], s["bc"], s["bc"]],
            out_specs=[s["row"], s["ckpt"]],
            scratch_shapes=[v["per_block"](plan.N), v["rows"], v["rows"],
                            v["tiles"], v["tiles"]],
            compiler_params=_params(),
            interpret=interpret,
        )(x, dt, a_t, d, b_t, c_t)


def _backward(plan: _Plan, x, dt, a_t, d, b_t, c_t, dy, ckpt,
              interpret: bool):
    """(dx in ``x``'s type, ddt, dA as (batch, channel block, N, lanes), dD
    as (batch, channel block, 1, lanes), dB and dC as (batch, N, T)) of
    padded operands."""
    batch = x.shape[0]
    last = plan.n_t - 1
    s, v = _specs(plan, lambda tb: last - tb), _scratch(plan)
    bc, N, f32 = plan.block_c, plan.N, jnp.float32
    whole = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, plan.n_c, rows, bc), lambda b, tb, j: (b, 0, 0, 0),
        memory_space=pltpu.VMEM)
    with jax.named_scope(kernel_scope("selective_scan_bwd")):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, block_t=plan.block_t),
            out_shape=[
                _sds(x.shape, x.dtype, x, dy), _sds(x.shape, f32, x, dy),
                _sds((batch, plan.n_c, N, bc), f32, x, dy),
                _sds((batch, plan.n_c, 1, bc), f32, x, dy),
                _sds(b_t.shape, f32, x, dy), _sds(c_t.shape, f32, x, dy)],
            grid=(batch, plan.n_t, plan.n_c),
            in_specs=[s["row"], s["row"], s["a"], s["d"], s["bc"], s["bc"],
                      s["row"], s["ckpt"]],
            out_specs=[s["row"], s["row"], whole(N), whole(1), s["bc"],
                       s["bc"]],
            scratch_shapes=[
                v["per_block"](N), v["per_block"](N), v["per_block"](1),
                v["states"], v["rows"], v["rows"], v["rows"],
                v["tiles"], v["tiles"], v["tiles"], v["tiles"], v["states"]],
            compiler_params=_params(),
            interpret=interpret,
        )(x, dt, a_t, d, b_t, c_t, dy, ckpt)


def _cut(a, shape):
    """The leading ``shape`` of a padded result; ``a`` itself where it has
    it."""
    if a.shape == tuple(shape):
        return a
    return a[tuple(slice(n) for n in shape)]


def _pad_to(a, shape):
    """``a`` with zeros up to ``shape``; ``a`` itself where it has it."""
    if a.shape == tuple(shape):
        return a
    return jnp.pad(a, [(0, n - m) for m, n in zip(a.shape, shape)])


def _operands(plan: _Plan, x, dt, A, B, C, D):
    """The kernels' operands, padded: ``x`` in its own type, ``dt`` float32,
    ``A`` states-first, ``D`` a row, ``B`` and ``C`` float32 with positions
    last. At whole shapes ``x`` and ``dt`` are the caller's arrays."""
    batch = x.shape[0]
    rows = (batch, plan.T, plan.channels)
    f32 = jnp.float32
    positions_last = lambda a: _pad_to(  # noqa: E731
        jnp.swapaxes(a, 1, 2).astype(f32), (batch, plan.N, plan.T))
    return (_pad_to(x, rows), _pad_to(dt.astype(f32), rows),
            _pad_to(A.astype(f32), (plan.channels, plan.N)).T,
            _pad_to(D.astype(f32), (plan.channels,))[None],
            positions_last(B), positions_last(C))


def _resolve_interpret(interpret):
    if interpret is None:
        from tpu_ddp.parallel.runtime import is_tpu_device

        return not is_tpu_device()
    return interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(x, dt, A, B, C, D, block_t, block_c, interpret):
    return _scan_fwd(x, dt, A, B, C, D, block_t, block_c, interpret)[0]


def _scan_fwd(x, dt, A, B, C, D, block_t, block_c, interpret):
    interpret = _resolve_interpret(interpret)
    if interpret and jax.typeof(x).vma:
        # interpreted under a ``shard_map`` (a CPU test of a train step):
        # the interpreter's own slices fail the mesh-axes check, as the
        # flash kernels' do; the recurrence itself stands in, no checkpoints
        y, ckpt = selective_scan_stepwise(x, dt, A, B, C, D), None
    else:
        (_, T, channels), N = x.shape, A.shape[1]
        plan = _Plan(T, channels, N, block_t, block_c)
        y, ckpt = _forward(plan, *_operands(plan, x, dt, A, B, C, D),
                           interpret)
        y = _cut(y, x.shape)
        ckpt = checkpoint_name(ckpt, CKPT_NAME)
    return checkpoint_name(y, Y_NAME), (x, dt, A, B, C, D, ckpt)


def _scan_bwd(block_t, block_c, interpret, res, dy):
    *inputs, ckpt = res
    x, dt, A, B, C, D = inputs
    if ckpt is None:
        return jax.vjp(selective_scan_stepwise, *inputs)[1](dy)
    (batch, T, channels), N = x.shape, A.shape[1]
    plan = _Plan(T, channels, N, block_t, block_c)
    operands = _operands(plan, *inputs)
    dx, ddt, da, dd, db, dc = _backward(
        plan, *operands, _pad_to(dy, operands[0].shape), ckpt,
        _resolve_interpret(interpret))
    # (batch, channel block, rows, lanes) -> (rows, channels), over the
    # sequences
    by_channel = lambda a: jnp.moveaxis(  # noqa: E731
        jnp.sum(a, axis=0), 1, 0).reshape(a.shape[2], plan.channels)
    positions_first = lambda a, like: jnp.swapaxes(  # noqa: E731
        _cut(a, (batch, N, T)), 1, 2).astype(like.dtype)
    return (_cut(dx, x.shape), _cut(ddt, x.shape).astype(dt.dtype),
            _cut(by_channel(da).T, A.shape).astype(A.dtype),
            positions_first(db, B), positions_first(dc, C),
            _cut(by_channel(dd)[0], D.shape).astype(D.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, dt, A, B, C, D, *, block_t: int = BLOCK_T,
                   block_c: int = BLOCK_C, interpret: bool | None = None):
    """``y`` (B, T, channels) in ``x``'s type: the recurrence of the module
    docstring with its skip, by the two kernels."""
    # under a ``shard_map`` the parameters do not vary over the mesh and the
    # activations do: ``A`` and ``D`` enter the ``custom_vjp`` as varying as
    # they, so that their gradients leave it as a shard's own and AD sums
    # the shards'
    missing = tuple(jax.typeof(x).vma - jax.typeof(A).vma)
    if missing:
        A, D = (lax.pcast(p, missing, to="varying") for p in (A, D))
    return _scan(x, dt, A, B, C, D, block_t, block_c, interpret)
