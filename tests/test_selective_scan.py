"""The selective scan's two Pallas kernels (``ops/selective_scan.py``),
interpreted, against the recurrence itself one position at a time: values
and all six gradients, the skip's among them, with ``x`` (and ``B``, ``C``
and ``dy``) in bfloat16 as the model has them and in float32, at whole
shapes and at lengths that are not whole time blocks, channels that are not
whole lane blocks and states that are not whole sublane tiles; what XLA is
left to do around the two calls; what lives from the forward pass to the
backward pass; the names a recomputed caller keeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.ops import selective_scan as ss

NAMES = ("x", "dt", "A", "B", "C", "D")


def operands(b, t, channels, n, seed=0, dtype=jnp.float32):
    """``x``, ``B``, ``C`` and the weights of the loss (so ``dy``) in
    ``dtype``; ``dt``, ``A`` and ``D`` float32, as the model holds them."""
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (b, t, channels)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, channels)) - 2)
    A = -jnp.exp(0.5 * jax.random.normal(k[2], (channels, n)))
    B = jax.random.normal(k[3], (b, t, n)).astype(dtype)
    C = jax.random.normal(k[4], (b, t, n)).astype(dtype)
    D = jax.random.normal(k[5], (channels,))
    weights = jax.random.normal(k[6], (b, t, channels)).astype(dtype)
    return (x, dt, A, B, C, D), weights


#: (sequences, positions, channels, states, block_t, block_c)
CASES = {
    # nothing to pad: two time blocks of 128, two lane blocks of 128
    "whole": (1, 256, 256, 16, 128, 128),
    # 300 positions in blocks of 128: two whole blocks and 44 positions;
    # 200 channels in lane blocks of 128: one whole and 72 lanes; 12 states
    # in sublane tiles of 8
    "padded_length_channels_and_states": (2, 300, 200, 12, 128, 128),
    # three lane groups' worth in two blocks of 256, one time block of 40
    "blocks_of_two_lane_groups": (1, 40, 300, 16, 8, 256),
    # shorter than a time block, 12 states in sublane tiles of 8
    "one_short_block_ragged_states": (1, 5, 64, 12, 128, 512),
}
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@pytest.fixture(scope="module", params=[
    (case, dtype) for case in sorted(CASES) for dtype in sorted(DTYPES)],
    ids="-".join)
def both(request):
    """((y, gradients) of the kernels on operands of the case's types, the
    same of the recurrence on the same values in float32, the operands)."""
    case, dtype = request.param
    b, t, channels, n, block_t, block_c = CASES[case]
    args, weights = operands(b, t, channels, n, dtype=DTYPES[dtype])
    weights = weights.astype(jnp.float32)

    def by(scan, *args):
        def loss(*a):
            y = scan(*a)
            return jnp.sum(y.astype(jnp.float32) * weights), y
        (_, y), grads = jax.value_and_grad(
            loss, argnums=range(6), has_aux=True)(*args)
        return y, grads

    return (by(lambda *a: ss.selective_scan(
        *a, block_t=block_t, block_c=block_c), *args),
        by(ss.selective_scan_stepwise,
           *(a.astype(jnp.float32) for a in args)), args)


def _close(got, want, like):
    """``got``, in ``like``'s type, is the float32 ``want``: to float32's
    last bits, or in bfloat16 to one rounding of it."""
    assert got.shape == want.shape and got.dtype == like.dtype
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0
    rounding = 2.0 ** -8 if like.dtype == jnp.bfloat16 else 0.0
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=rounding,
                               atol=2e-6 * scale + 1e-7)


def test_the_kernels_give_the_recurrences_values(both):
    (y, _), (want, _), args = both
    _close(y, want, args[0])


@pytest.mark.parametrize("i", range(6), ids=NAMES)
def test_the_kernels_give_the_recurrences_gradient(both, i):
    (_, grads), (_, want), args = both
    _close(grads[i], want[i], args[i])


def _outside_the_kernels(jaxpr):
    """Every equation of a jaxpr and of what it calls, the bodies of the
    ``pallas_call``s left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _outside_the_kernels(sub)


def test_xla_is_left_no_pass_over_the_rows_at_whole_shapes():
    """With ``x`` and ``dy`` in bfloat16 and nothing to pad, forward and
    backward are the two kernel calls and small change: no float32 copy of
    a (sequences, positions, channels) array is made outside them, and no
    (sequences, positions, states, 128) array exists: the kernels widen
    their blocks and replicate ``B`` and ``C`` in VMEM."""
    b, t, channels, n = 1, 256, 256, 16
    args, weights = operands(b, t, channels, n, dtype=jnp.bfloat16)
    traced = jax.make_jaxpr(lambda dy, *a: jax.vjp(
        lambda *a: ss.selective_scan(*a, block_t=128, block_c=128), *a)[1](
            dy))(weights, *args)
    eqns = list(_outside_the_kernels(traced.jaxpr))
    assert sum(e.primitive.name == "pallas_call" for e in eqns) == 2
    rows, replicated = (b, t, channels), (b, t, n, ss.LANE)
    shapes = lambda vs: [getattr(v.aval, "shape", None)  # noqa: E731
                         for v in vs]
    for eqn in eqns:
        assert replicated not in shapes(eqn.invars) + shapes(eqn.outvars), eqn
        if eqn.primitive.name in ("convert_element_type", "pad"):
            assert rows not in shapes(eqn.invars), eqn


def test_what_lives_between_the_passes_is_a_state_a_time_block():
    """The residuals of the ``custom_vjp``: the operands and the state at
    each time block's start, (sequences, time blocks, states, channels)
    padded, never a state a position; ``y`` comes back in ``x``'s type, and
    it and the checkpoints under their names."""
    (x, dt, A, B, C, D), _ = operands(2, 300, 200, 16)
    x = x.astype(jnp.bfloat16)
    y, residuals = ss._scan_fwd(x, dt, A, B, C, D, 128, 128, True)
    assert y.dtype == jnp.bfloat16 and y.shape == x.shape
    assert residuals[-1].shape == (2, 3, 16, 256)
    assert residuals[-1].dtype == jnp.float32
    # the first checkpoint is the empty state, the second the state after
    # 128 positions
    np.testing.assert_array_equal(residuals[-1][:, 0], 0.0)
    assert float(jnp.max(jnp.abs(residuals[-1][:, 1, :, :200]))) > 0
    np.testing.assert_array_equal(residuals[-1][:, :, :, 200:], 0.0)
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *b: ss._scan(*b, 128, 128, True), *a)[0])(x, dt, A, B, C, D))
    assert f"name={ss.Y_NAME}" in text and f"name={ss.CKPT_NAME}" in text


def test_a_recomputed_caller_may_keep_y_by_name():
    """Under a policy that saves ``Y_NAME`` and ``CKPT_NAME``, everything
    the forward kernel writes, the backward pass of a recomputed caller
    does not run it again: one forward call and the backward one, where a
    caller that keeps nothing has two and one."""
    (x, dt, A, B, C, D), weights = operands(1, 16, 128, 8)

    def loss(policy):
        scan = jax.checkpoint(
            lambda *a: ss.selective_scan(*a, block_t=8, block_c=128),
            policy=policy)
        return lambda *a: jnp.sum(jnp.square(scan(*a)) * weights)

    names = jax.checkpoint_policies.save_only_these_names
    both_names = names(ss.Y_NAME, ss.CKPT_NAME)
    kept = jax.make_jaxpr(jax.grad(loss(both_names)))(x, dt, A, B, C, D)
    plain = jax.make_jaxpr(jax.grad(loss(names())))(x, dt, A, B, C, D)
    np.testing.assert_allclose(
        jax.grad(loss(both_names))(x, dt, A, B, C, D),
        jax.grad(loss(names()))(x, dt, A, B, C, D), rtol=1e-6, atol=1e-6)
    assert str(kept).count("pallas_call") == 2
    assert str(plain).count("pallas_call") == 3
    # ``y`` alone is not enough: the checkpoints are the kernel's too
    y_alone = jax.make_jaxpr(jax.grad(loss(names(ss.Y_NAME))))(
        x, dt, A, B, C, D)
    assert str(y_alone).count("pallas_call") == 3


def test_the_time_block_is_whole_unrolled_groups():
    plan = ss._Plan(16384, 5120, 16, ss.BLOCK_T, ss.BLOCK_C)
    assert (plan.block_t, plan.block_c) == (ss.BLOCK_T, ss.BLOCK_C)
    assert (plan.n_t, plan.n_c) == (16384 // ss.BLOCK_T, 5120 // ss.BLOCK_C)
    assert ss.BLOCK_T % ss._UNROLL == 0
    short = ss._Plan(5, 64, 12, 128, 512)
    assert (short.block_t, short.T, short.block_c, short.channels,
            short.N) == (8, 8, 128, 128, 16)
    # ``B`` and ``C`` have positions on lanes: more than one time block are
    # whole lane groups, whatever was asked for
    ragged = ss._Plan(300, 200, 12, 16, 128)
    assert (ragged.block_t, ragged.T, ragged.n_t) == (128, 384, 3)
    assert ss._Plan(37, 200, 16, 16, 128).block_t == 40


def test_the_kernels_trace_inside_a_shard_map():
    """The train steps call the kernels inside a ``shard_map`` over
    ``data``, where activations vary over the mesh and parameters do not:
    the kernels' loops carry values of one kind (everything through
    scratch), and ``A``'s gradient is summed over the shards by AD. Traced
    as the chip compiles it (``interpret=False``), not lowered."""
    from jax.sharding import Mesh, PartitionSpec as P

    (x, dt, A, B, C, D), _ = operands(1, 32, 256, 16)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def shard(x, dt, A, B, C, D):
        def loss(A, D):
            return jnp.sum(ss.selective_scan(
                x, dt, A, B, C, D, block_t=16, block_c=128, interpret=False))

        value, grads = jax.value_and_grad(loss, argnums=(0, 1))(A, D)
        return jax.lax.pmean(value, "data"), grads

    traced = jax.make_jaxpr(jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P("data"), P("data"), P(), P("data"), P("data"), P()),
        out_specs=(P(), P())))(x, dt, A, B, C, D)
    text = str(traced)
    assert text.count("pallas_call") == 2 and "psum" in text
