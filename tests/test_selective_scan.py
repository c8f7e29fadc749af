"""The selective scan's two Pallas kernels (``ops/selective_scan.py``),
interpreted, against the recurrence itself one position at a time: values
and all six gradients, at lengths that are not whole time blocks, channels
that are not whole lane blocks and states that are not whole sublane tiles;
what lives from the forward pass to the backward pass; the name ``y``
carries."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.ops import selective_scan as ss

NAMES = ("x", "dt", "A", "B", "C", "D")


def operands(b, t, channels, n, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (b, t, channels))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, channels)) - 2)
    A = -jnp.exp(0.5 * jax.random.normal(k[2], (channels, n)))
    B = jax.random.normal(k[3], (b, t, n))
    C = jax.random.normal(k[4], (b, t, n))
    D = jax.random.normal(k[5], (channels,))
    weights = jax.random.normal(k[6], (b, t, channels))
    return (x, dt, A, B, C, D), weights


#: (sequences, positions, channels, states, block_t, block_c)
CASES = {
    # 37 positions in blocks of 16: two whole blocks and five positions;
    # 200 channels in lane blocks of 128: one whole and 72 lanes
    "ragged_length_and_channels": (2, 37, 200, 16, 16, 128),
    # three channel blocks' worth in two blocks of 256, five time blocks
    "blocks_of_two_lane_groups": (1, 40, 300, 16, 8, 256),
    # shorter than a time block, 12 states in sublane tiles of 8
    "one_short_block_ragged_states": (1, 5, 64, 12, 128, 512),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def both(request):
    """((y, gradients) of the kernels, the same of the recurrence)."""
    b, t, channels, n, block_t, block_c = CASES[request.param]
    args, weights = operands(b, t, channels, n)

    def by(scan):
        def loss(*a):
            y = scan(*a)
            return jnp.sum(y * weights), y
        (_, y), grads = jax.value_and_grad(
            loss, argnums=range(6), has_aux=True)(*args)
        return y, grads

    return (by(lambda *a: ss.selective_scan(
        *a, block_t=block_t, block_c=block_c)),
        by(ss.selective_scan_stepwise))


def test_the_kernels_give_the_recurrences_values(both):
    (y, _), (want, _) = both
    assert y.shape == want.shape and y.dtype == want.dtype
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("i", range(6), ids=NAMES)
def test_the_kernels_give_the_recurrences_gradient(both, i):
    (_, grads), (_, want) = both
    assert grads[i].shape == want[i].shape
    scale = float(jnp.max(jnp.abs(want[i])))
    assert scale > 0
    np.testing.assert_allclose(grads[i], want[i], rtol=0,
                               atol=2e-6 * scale + 1e-7)


def test_what_lives_between_the_passes_is_a_state_a_time_block():
    """The residuals of the ``custom_vjp``: the operands and the state at
    each time block's start, (sequences, time blocks, states, channels)
    padded, never a state a position; ``y`` comes back in ``x``'s type
    under its name."""
    (x, dt, A, B, C, _), _ = operands(2, 37, 200, 16)
    x = x.astype(jnp.bfloat16)
    y, residuals = ss._scan_fwd(x, dt, A, B, C, 16, 128, True)
    assert y.dtype == jnp.bfloat16 and y.shape == x.shape
    assert residuals[-1].shape == (2, 3, 16, 256)
    assert residuals[-1].dtype == jnp.float32
    # the first checkpoint is the empty state, the second the state after
    # sixteen positions
    np.testing.assert_array_equal(residuals[-1][:, 0], 0.0)
    assert float(jnp.max(jnp.abs(residuals[-1][:, 1, :, :200]))) > 0
    np.testing.assert_array_equal(residuals[-1][:, :, :, 200:], 0.0)
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *b: ss._scan(*b, 16, 128, True), *a)[0])(x, dt, A, B, C))
    assert f"name={ss.Y_NAME}" in text


def test_a_recomputed_caller_may_keep_y_by_name():
    """Under a policy that saves ``Y_NAME`` the backward pass of a
    recomputed caller holds ``y`` and does not make it again for its own
    readers: one forward kernel call beside the backward one's own."""
    (x, dt, A, B, C, D), weights = operands(1, 16, 128, 8)

    def loss(policy):
        scan = jax.checkpoint(
            lambda *a: ss.selective_scan(*a, block_t=8, block_c=128),
            policy=policy)
        return lambda *a: jnp.sum(jnp.square(scan(*a)) * weights)

    names = jax.checkpoint_policies.save_only_these_names
    kept = jax.make_jaxpr(jax.grad(loss(names(ss.Y_NAME))))(
        x, dt, A, B, C, D)
    plain = jax.make_jaxpr(jax.grad(loss(names())))(x, dt, A, B, C, D)
    np.testing.assert_allclose(
        jax.grad(loss(names(ss.Y_NAME)))(x, dt, A, B, C, D),
        jax.grad(loss(names()))(x, dt, A, B, C, D), rtol=1e-6, atol=1e-6)
    assert str(kept) != str(plain)


def test_the_time_block_is_whole_unrolled_groups():
    plan = ss._Plan(16384, 5120, 16, ss.BLOCK_T, ss.BLOCK_C)
    assert (plan.block_t, plan.block_c) == (ss.BLOCK_T, ss.BLOCK_C)
    assert (plan.n_t, plan.n_c) == (16384 // ss.BLOCK_T, 5120 // ss.BLOCK_C)
    assert ss.BLOCK_T % ss._UNROLL == 0
    short = ss._Plan(5, 64, 12, 128, 512)
    assert (short.block_t, short.T, short.block_c, short.channels,
            short.N) == (8, 8, 128, 128, 16)


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
