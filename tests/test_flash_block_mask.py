"""The flash kernels under the block-diffusion visibility (``diffusion`` =
(L, B): ``[clean ‖ noisy]``, 2L positions in blocks of B), interpreted on
the CPU: the forward pass and all three gradients against ``_reference``,
the visit lists against a brute-force count of the tiles that hold a
visible pair, and the shapes the mask refuses by name."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ``tpu_ddp.ops`` exports a function of the module's name
fa = importlib.import_module("tpu_ddp.ops.flash_attention")

#: L, B, heads, kv_heads, (block_q, block_k), kv mask
CASES = {
    # tiles that hold several diffusion blocks, halves of several tiles
    "b4_tiles_of_16": (64, 4, 4, 2, (16, 16), False),
    "b16_tiles_of_16": (64, 16, 4, 2, (16, 16), False),
    "b4_tiles_of_32_by_16": (64, 4, 2, 1, (32, 16), False),
    "b16_tiles_of_16_by_32": (64, 16, 2, 2, (16, 32), False),
    # a diffusion block of several tiles: the noisy run is two kv blocks
    "b16_tiles_of_8": (32, 16, 2, 1, (8, 8), False),
    "b4_a_half_is_a_tile": (32, 4, 4, 1, (32, 32), False),
    "b4_group_of_8_kv_mask": (128, 4, 8, 1, (128, 128), True),
}


def _operands(name, dtype=jnp.float32):
    L, _, heads, kv_heads, _, masked = CASES[name]
    ks = jax.random.split(jax.random.key(len(name)), 5)
    q, g = (jax.random.normal(k, (2, 2 * L, heads, 16), dtype)
            for k in ks[:2])
    k, v = (jax.random.normal(k, (2, 2 * L, kv_heads, 16), dtype)
            for k in ks[2:4])
    kv_mask = None
    if masked:
        kv_mask = (jax.random.uniform(ks[4], (2, 2 * L)) > 0.3).astype(
            jnp.float32)
    return q, k, v, g, kv_mask


def _visible(L, B):
    """(2L, 2L) bool, from the statement of the mask and nothing else."""
    i = np.arange(2 * L)
    noisy, block = i >= L, i % L // B
    rn, cn = noisy[:, None], noisy[None, :]
    rb, cb = block[:, None], block[None, :]
    return ((~rn & ~cn & (cb <= rb)) | (rn & ~cn & (cb < rb))
            | (rn & cn & (cb == rb)))


@pytest.mark.parametrize("L,B", [(64, 4), (64, 16), (32, 1), (48, 12)])
def test_the_references_visibility_is_the_masks_statement(L, B):
    vis = fa._bhqk_visibility(2 * L, 2 * L, False, None, 0, (L, B))
    np.testing.assert_array_equal(np.asarray(vis)[0, 0], _visible(L, B))


@pytest.mark.parametrize("name", list(CASES))
def test_forward_and_three_gradients_match_the_reference(name):
    L, B, _, _, (bq, bk), _ = CASES[name]
    q, k, v, g, kv_mask = _operands(name)

    def through(attend):
        out, vjp = jax.vjp(lambda q, k, v: attend(q, k, v), q, k, v)
        return (out,) + vjp(g)

    got = through(lambda q, k, v: fa.flash_attention(
        q, k, v, bq, bk, True, kv_mask=kv_mask, diffusion=(L, B)))
    want = through(lambda q, k, v: fa._reference(
        q, k, v, kv_mask=kv_mask, diffusion=(L, B)))
    for what, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5, err_msg=what)


def test_bfloat16_operands_stay_near_the_reference():
    L, B, _, _, (bq, bk), _ = CASES["b4_tiles_of_16"]
    q, k, v, g, _ = _operands("b4_tiles_of_16", jnp.bfloat16)
    loss = lambda attend: lambda q, k, v: jnp.sum(  # noqa: E731
        attend(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))
    got = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, bq, bk, True, diffusion=(L, B))), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: fa._reference(
        q, k, v, diffusion=(L, B))), (0, 1, 2))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b in zip(got, want):
        assert float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                     / jnp.linalg.norm(b)) < 0.03


@pytest.mark.parametrize("L,B,bq,bk", [
    (64, 4, 16, 16), (64, 16, 16, 16), (64, 4, 32, 16), (64, 16, 16, 32),
    (32, 16, 8, 8), (32, 4, 32, 32), (96, 12, 24, 48), (4096, 4, 512, 512)])
def test_a_q_block_visits_the_tiles_that_hold_a_visible_pair(L, B, bq, bk):
    """The list of every q block, step by step, is the kv blocks with a
    visible pair, ascending; ``edge_crosses`` is true of the visited tiles
    that also hold a hidden pair; the index map repeats the last block
    past the list's end."""
    lists = fa._visit_lists(2 * L, bq, bk, False, 0, (L, B))
    vis = _visible(L, B)
    n_q, n_k = 2 * L // bq, 2 * L // bk
    tiles = vis.reshape(n_q, bq, n_k, bk)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    for j in range(n_q):
        want = [kb for kb in range(n_k) if some[j, kb]]
        count = lists.kv_count(j)
        got = [lists.kv_at(j, t) for t in range(count)]
        assert got == want, (j, got, want)
        assert [bool(lists.visits(j, t, None))
                for t in range(lists.kv_width)] == [
                    t < count for t in range(lists.kv_width)]
        assert lists.kv_index(j, lists.kv_width) == want[-1]
        for kb in want:
            assert bool(lists.edge_crosses(j, kb)) == (not every[j, kb])
    assert lists.kv_width == int(some.sum(axis=1).max())


def test_the_cells_lists_at_4096_tokens_in_blocks_of_4():
    """80 tiles a (row, head) where the causal band of 8,192 positions has
    136; 24 of them carry mask arithmetic."""
    lists = fa._visit_lists(8192, 512, 512, False, 0, (4096, 4))
    visited = [(j, lists.kv_at(j, t)) for j in range(16)
               for t in range(lists.kv_count(j))]
    assert len(visited) == 80 and lists.kv_width == 9
    assert sum(bool(lists.edge_crosses(j, kb)) for j, kb in visited) == 24
    band = fa._visit_lists(8192, 512, 512, True, 0, None)
    assert sum(band.kv_hi(j) - band.kv_lo(j) + 1 for j in range(16)) == 136
    for j in range(8):  # clean tile j: 0..j; noisy tile j: 0..j and its own
        assert [kb for jq, kb in visited if jq == j] == list(range(j + 1))
        assert [kb for jq, kb in visited if jq == 8 + j] == list(
            range(j + 1)) + [8 + j]


def test_traced_block_indices_give_the_same_lists():
    lists = fa._visit_lists(128, 16, 16, False, 0, (64, 4))
    j, t = np.meshgrid(np.arange(8), np.arange(lists.kv_width),
                       indexing="ij")
    at = jax.jit(jax.vmap(jax.vmap(lists.kv_index)))(
        jnp.asarray(j, jnp.int32), jnp.asarray(t, jnp.int32))
    want = [[lists.kv_index(int(a), int(b)) for a, b in zip(ja, ta)]
            for ja, ta in zip(j, t)]
    np.testing.assert_array_equal(np.asarray(at), np.asarray(want))


def test_shapes_the_mask_is_not_written_for_are_refused_by_name(monkeypatch):
    q = jnp.zeros((1, 128, 2, 16))
    ask = lambda **how: fa.flash_attention(  # noqa: E731
        q, q, q, how.pop("bq", 16), 16, True, **how)
    with pytest.raises(ValueError, match="block-diffusion"):
        ask(diffusion=(32, 4))          # 2L is not the sequence
    with pytest.raises(ValueError, match="block-diffusion"):
        ask(diffusion=(64, 5))          # blocks do not divide a half
    with pytest.raises(ValueError, match="block-diffusion"):
        ask(diffusion=(64, 4), causal=True)
    with pytest.raises(ValueError, match="block-diffusion"):
        ask(diffusion=(64, 4), window=8)
    # tiles are planned on a half: blocks wider than one run it whole
    np.testing.assert_allclose(
        ask(diffusion=(64, 4), bq=128),
        fa._reference(q, q, q, diffusion=(64, 4)), atol=1e-6)
    long = jnp.zeros((1, 1200, 2, 16))  # 600 a half: 512 would need padding
    with pytest.raises(ValueError, match="block-diffusion"):
        fa.flash_attention(long, long, long, 512, 512, True,
                           diffusion=(600, 4))
    with pytest.raises(ValueError, match="block-diffusion"):
        fa._visit_lists(128, 128, 16, False, 0, (64, 4))  # across halves
    # the two-kernel backward pass walks bands only, and says so
    monkeypatch.setattr(fa, "_backward_fits", lambda *shape: False)
    with pytest.raises(NotImplementedError, match="block-diffusion"):
        jax.grad(lambda x: jnp.sum(fa.flash_attention(
            x, x, x, 16, 16, True, diffusion=(64, 4))))(q)


def test_the_kernels_keep_their_scopes_under_the_mask():
    import re

    q = jnp.zeros((1, 64, 2, 16))
    text = jax.jit(jax.grad(lambda x: jnp.sum(fa.flash_attention(
        x, x, x, 16, 16, True, diffusion=(32, 4))))).lower(q).as_text(
            debug_info=True)
    assert set(re.findall(r"tpu_ddp\.kernel\.(\w+)", text)) == {
        "flash_fwd", "flash_bwd"}
