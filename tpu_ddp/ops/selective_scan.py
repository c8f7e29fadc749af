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
states on sublanes, in VMEM scratch across time blocks. A position's
``B_t`` and ``C_t`` reach the kernels lane-replicated, (T, N, 128), as the
flash kernels' row statistics do, so that a position's (N, lanes) tile is a
load and no shuffle; the time-block-outer order reads them once a call.

The forward kernel (``tpu_ddp.kernel.selective_scan_fwd``) writes ``y``
without the skip and the state at each time block's start, ``T / block_t *
channels * N`` floats. The backward kernel (``..._bwd``) rebuilds a block's
states from its checkpoint into VMEM, then sweeps the block in reverse with
the states' gradient carried: ``dx``, ``ddt`` a row a position, ``dA``
accumulated over the whole call in VMEM, ``dB`` and ``dC`` as (N, 128)
partial sums a position over lane groups and channel blocks, whose last sum
over lanes is XLA's. The skip ``D x``, and so ``dD``, is plain ``jnp``
beside the kernel calls. The residuals of the ``custom_vjp`` are the
operands and the checkpoints; ``y`` carries a name (``Y_NAME``) so that a
recomputed layer may keep it.

A length that is not whole time blocks is padded with ``dt = 0`` and ``x =
0``: a padded position decays nothing and adds nothing. Channels pad to
whole lane blocks with ``A = 0``, states to whole sublane tiles with ``B = C
= 0``. Interpreted off the TPU, as the flash kernels are.
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
#: kernel holds a block's states, ``BLOCK_T * N * BLOCK_C`` floats, in VMEM
BLOCK_T = 128
BLOCK_C = 512
#: positions of the sweep unrolled into one loop body; a time block is a
#: whole number of them
_UNROLL = _SUBLANES
_VMEM_LIMIT = 64 << 20
#: the scan's output before the skip, by name to a recomputation policy
Y_NAME = "selective_scan_y"


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


def _rows(ref, t):
    """Row ``t`` of a (1, block_t, lanes) block, over ``N`` sublanes."""
    return ref[0, pl.ds(t, 1), :]


def _lanes(tile, width: int):
    """A lane-replicated (N, 128) tile over ``width`` lanes."""
    return jnp.concatenate([tile] * (width // LANE), axis=-1)


def _lane_groups(tile):
    """The (N, 128) sum of a (N, width) tile's lane groups."""
    return sum(tile[:, i:i + LANE] for i in range(0, tile.shape[-1], LANE))


def _sweep(positions: int, body, carry):
    """``lax.fori_loop(0, positions, body, carry)``, ``_UNROLL`` positions a
    loop body (Mosaic's own ``unroll`` is all or nothing)."""
    def group(g, carry):
        for i in range(_UNROLL):
            carry = body(g * _UNROLL + i, carry)
        return carry

    return lax.fori_loop(0, positions // _UNROLL, group, carry)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, ckpt_ref,
                state_ref, *, block_t: int):
    tb, j = pl.program_id(1), pl.program_id(2)

    @pl.when(tb == 0)
    def _():
        state_ref[j] = jnp.zeros(state_ref.shape[1:], jnp.float32)

    a = a_ref[...]
    width = a.shape[-1]
    ckpt_ref[0, 0] = state_ref[j]

    def step(t, state):
        dt = _rows(dt_ref, t)
        state = (jnp.exp(dt * a) * state
                 + (dt * _rows(x_ref, t)) * _lanes(b_ref[0, t], width))
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(
            state * _lanes(c_ref[0, t], width), axis=0, keepdims=True)
        return state

    state_ref[j] = _sweep(block_t, step, state_ref[j])


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dy_ref, ckpt_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                dstate_ref, da_acc_ref, states_ref, *, block_t: int):
    tb, j = pl.program_id(1), pl.program_id(2)

    @pl.when(tb == 0)  # the last time block: the grid walks them in reverse
    def _():
        dstate_ref[j] = jnp.zeros(dstate_ref.shape[1:], jnp.float32)
        da_acc_ref[j] = jnp.zeros(da_acc_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _():
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

    a = a_ref[...]
    width = a.shape[-1]

    # the block's states again, from the one at its start: states_ref[t] is
    # the state *before* position t, ``last`` the one after the whole block
    def rebuild(t, state):
        states_ref[t] = state
        dt = _rows(dt_ref, t)
        return (jnp.exp(dt * a) * state
                + (dt * _rows(x_ref, t)) * _lanes(b_ref[0, t], width))

    # through scratch: under a ``shard_map`` a value loaded from an operand
    # or a result varies over the mesh as that does and one loaded from
    # scratch does not, and a loop's carry has to be of one kind; so the
    # loops start from scratch, and ``dA`` is summed there too
    states_ref[0] = ckpt_ref[0, 0]
    last = _sweep(block_t, rebuild, states_ref[0])

    def sweep(i, carry):
        dstate, da, after = carry     # d/dS_t from the future; S_t
        t = block_t - 1 - i
        dt, x, dy = _rows(dt_ref, t), _rows(x_ref, t), _rows(dy_ref, t)
        before = states_ref[t]
        b, c = _lanes(b_ref[0, t], width), _lanes(c_ref[0, t], width)
        dstate = dstate + dy * c
        dc_ref[0, t] += _lane_groups(dy * after)
        db_ref[0, t] += _lane_groups(dstate * (dt * x))
        through = dstate * jnp.exp(dt * a)         # d/dS_{t-1}
        grown = through * before                   # d/d(dt A), elementwise
        by_input = jnp.sum(dstate * b, axis=0, keepdims=True)
        dx_ref[0, pl.ds(t, 1), :] = dt * by_input
        ddt_ref[0, pl.ds(t, 1), :] = x * by_input + jnp.sum(
            grown * a, axis=0, keepdims=True)
        return through, da + grown * dt, before

    dstate, da, _ = _sweep(block_t, sweep,
                           (dstate_ref[j], da_acc_ref[j], last))
    dstate_ref[j] = dstate
    da_acc_ref[j] = da
    da_ref[0, j] = da


class _Plan:
    """Blocks and padded sizes of one call."""

    def __init__(self, T: int, channels: int, N: int, block_t: int,
                 block_c: int):
        self.block_t = _round_up(min(block_t, T), _UNROLL)
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
        bc=pl.BlockSpec((1, bt, N, LANE),
                        lambda b, tb, j: (b, time_block(tb), 0, 0), **vmem),
        ckpt=pl.BlockSpec((1, 1, N, bc),
                          lambda b, tb, j: (b, time_block(tb), 0, j), **vmem))


def _sds(shape, *like):
    """A float32 result that varies over the mesh axes its operands vary
    over: inside a ``shard_map`` (the train steps) a ``pallas_call``'s
    outputs must say so."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in like))
    return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _forward(plan: _Plan, x, dt, a_t, b_rep, c_rep, interpret: bool):
    """(y without the skip, checkpoints) of padded float32 operands."""
    batch = x.shape[0]
    s = _specs(plan, lambda tb: tb)
    with jax.named_scope(kernel_scope("selective_scan_fwd")):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, block_t=plan.block_t),
            out_shape=[
                _sds(x.shape, x, dt),
                _sds((batch, plan.n_t, plan.N, plan.channels), x, dt)],
            grid=(batch, plan.n_t, plan.n_c),
            in_specs=[s["row"], s["row"], s["a"], s["bc"], s["bc"]],
            out_specs=[s["row"], s["ckpt"]],
            scratch_shapes=[pltpu.VMEM((plan.n_c, plan.N, plan.block_c),
                                       jnp.float32)],
            compiler_params=_params(),
            interpret=interpret,
        )(x, dt, a_t, b_rep, c_rep)


def _backward(plan: _Plan, x, dt, a_t, b_rep, c_rep, dy, ckpt,
              interpret: bool):
    """(dx, ddt, dA as (batch, channel block, N, lanes), dB and dC as
    (batch, T, N, 128) partial sums) of padded float32 operands."""
    batch = x.shape[0]
    last = plan.n_t - 1
    s = _specs(plan, lambda tb: last - tb)
    bt, bc, N = plan.block_t, plan.block_c, plan.N
    da_spec = pl.BlockSpec((1, plan.n_c, N, bc), lambda b, tb, j: (b, 0, 0, 0),
                           memory_space=pltpu.VMEM)
    with jax.named_scope(kernel_scope("selective_scan_bwd")):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, block_t=bt),
            out_shape=[
                _sds(x.shape, x, dy), _sds(x.shape, x, dy),
                _sds((batch, plan.n_c, N, bc), x, dy),
                _sds(b_rep.shape, x, dy), _sds(c_rep.shape, x, dy)],
            grid=(batch, plan.n_t, plan.n_c),
            in_specs=[s["row"], s["row"], s["a"], s["bc"], s["bc"], s["row"],
                      s["ckpt"]],
            out_specs=[s["row"], s["row"], da_spec, s["bc"], s["bc"]],
            scratch_shapes=[
                pltpu.VMEM((plan.n_c, N, bc), jnp.float32),
                pltpu.VMEM((plan.n_c, N, bc), jnp.float32),
                pltpu.VMEM((bt, N, bc), jnp.float32)],
            compiler_params=_params(),
            interpret=interpret,
        )(x, dt, a_t, b_rep, c_rep, dy, ckpt)


def _pad_to(a, shape):
    return jnp.pad(a.astype(jnp.float32),
                   [(0, n - m) for m, n in zip(a.shape, shape)])


def _operands(plan: _Plan, x, dt, A, B, C):
    """The kernels' operands: float32, padded, ``A`` states-first and ``B``,
    ``C`` lane-replicated."""
    batch = x.shape[0]
    rows = (batch, plan.T, plan.channels)
    replicated = lambda a: jnp.broadcast_to(  # noqa: E731
        _pad_to(a, (batch, plan.T, plan.N))[..., None],
        (batch, plan.T, plan.N, LANE))
    return (_pad_to(x, rows), _pad_to(dt, rows),
            _pad_to(A, (plan.channels, plan.N)).T, replicated(B),
            replicated(C))


def _resolve_interpret(interpret):
    if interpret is None:
        from tpu_ddp.parallel.runtime import is_tpu_device

        return not is_tpu_device()
    return interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(x, dt, A, B, C, block_t, block_c, interpret):
    return _scan_fwd(x, dt, A, B, C, block_t, block_c, interpret)[0]


def _scan_fwd(x, dt, A, B, C, block_t, block_c, interpret):
    interpret = _resolve_interpret(interpret)
    if interpret and jax.typeof(x).vma:
        # interpreted under a ``shard_map`` (a CPU test of a train step):
        # the interpreter's own slices fail the mesh-axes check, as the
        # flash kernels' do; the recurrence itself stands in, no checkpoints
        y, ckpt = _stepwise(x, dt, A, B, C), None
    else:
        plan = _Plan(x.shape[1], x.shape[2], A.shape[1], block_t, block_c)
        y, ckpt = _forward(plan, *_operands(plan, x, dt, A, B, C), interpret)
    y = checkpoint_name(
        y[:, :x.shape[1], :x.shape[2]].astype(x.dtype), Y_NAME)
    return y, (x, dt, A, B, C, ckpt)


def _scan_bwd(block_t, block_c, interpret, res, dy):
    x, dt, A, B, C, ckpt = res
    if ckpt is None:
        grads = jax.vjp(_stepwise, x, dt, A, B, C)[1](
            dy.astype(jnp.float32))
        return tuple(g.astype(a.dtype) for g, a in zip(grads, res))
    (_, T, channels), N = x.shape, A.shape[1]
    plan = _Plan(T, channels, N, block_t, block_c)
    operands = _operands(plan, x, dt, A, B, C)
    dx, ddt, da, db, dc = _backward(
        plan, *operands, _pad_to(dy, operands[0].shape), ckpt,
        _resolve_interpret(interpret))
    # (batch, channel block, N, lanes) -> (channels, N), over the sequences
    da = jnp.moveaxis(jnp.sum(da, axis=0), 1, 0).reshape(
        plan.N, plan.channels).T
    return (dx[:, :T, :channels].astype(x.dtype),
            ddt[:, :T, :channels].astype(dt.dtype),
            da[:channels, :N].astype(A.dtype),
            jnp.sum(db, axis=-1)[:, :T, :N].astype(B.dtype),
            jnp.sum(dc, axis=-1)[:, :T, :N].astype(C.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x, dt, A, B, C, D, *, block_t: int = BLOCK_T,
                   block_c: int = BLOCK_C, interpret: bool | None = None):
    """``y`` (B, T, channels) in ``x``'s type: the recurrence of the module
    docstring by the two kernels, and the skip ``D x`` beside them."""
    # under a ``shard_map`` the parameters do not vary over the mesh and the
    # activations do: ``A`` enters the ``custom_vjp`` as varying as they, so
    # that its gradient leaves it as a shard's own and AD sums the shards'
    missing = tuple(jax.typeof(x).vma - jax.typeof(A).vma)
    if missing:
        A = jax.lax.pvary(A, missing)
    y = _scan(x, dt, A, B, C, block_t, block_c, interpret)
    return (y.astype(jnp.float32)
            + D * x.astype(jnp.float32)).astype(x.dtype)
