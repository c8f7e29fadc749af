"""The one-kernel flash backward pass (``_bwd_kernel``, scope
``tpu_ddp.kernel.flash_bwd``) against the dQ and dK/dV kernels on the same
operands, interpreted on the CPU: equal to the bit, because a row's sums run
in the pair's order (heads of a group outer, q blocks ascending; kv blocks
ascending for dq); against the jnp reference through ``jax.grad``; under the
ring's per-block backward with the global ``o`` and ``lse``; and the rule
that picks one or the other from the shape alone."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ``tpu_ddp.ops`` exports a function of the module's name
fa = importlib.import_module("tpu_ddp.ops.flash_attention")

#: t, heads, kv_heads, dqk, dv, (block_q, block_k), causal, window, kv mask
CASES = {
    "causal": (64, 2, 2, 16, 16, (16, 16), True, 0, False),
    "window": (64, 2, 2, 16, 16, (16, 16), True, 24, False),
    "window_of_a_block": (64, 2, 2, 16, 16, (16, 16), True, 16, False),
    "not_causal": (64, 2, 2, 16, 16, (16, 32), False, 0, False),
    "kv_mask_with_a_dead_row": (256, 2, 2, 16, 16, (128, 128), False, 0,
                                True),
    "kv_mask_causal": (256, 2, 1, 16, 16, (128, 128), True, 0, True),
    "group_of_1": (32, 4, 4, 16, 16, (16, 16), True, 0, False),
    "group_of_4": (32, 4, 1, 16, 16, (16, 16), True, 0, False),
    "group_of_8": (32, 8, 1, 16, 16, (16, 16), True, 8, False),
    "192_over_128": (32, 2, 2, 192, 128, (16, 16), True, 0, False),
    "24_over_16_grouped": (64, 4, 2, 24, 16, (32, 16), True, 0, False),
    "whole_axis_block": (196, 3, 3, 64, 64, (128, 128), False, 0, False),
    "unequal_blocks": (64, 2, 1, 16, 16, (32, 16), True, 24, False),
}


def _operands(name, dtype=jnp.bfloat16):
    t, heads, kv_heads, dqk, dv, _, _, _, masked = CASES[name]
    ks = jax.random.split(jax.random.key(len(name)), 5)
    q, g, k, v = (
        jax.random.normal(key, (2, t, n, d), dtype)
        for key, n, d in zip(ks, (heads, heads, kv_heads, kv_heads),
                             (dqk, dv, dqk, dv)))
    kv_mask = None
    if masked:
        kv_mask = (jax.random.uniform(ks[4], (2, t)) > 0.3).astype(
            jnp.float32).at[0].set(0.0)  # batch row 0 sees no key at all
    return q, k, v, g, kv_mask


@functools.lru_cache(maxsize=None)
def _both(name):
    """{(path, gradient): array} of one case, each path's pass made once."""
    (bq, bk), causal, window = CASES[name][5:8]
    q, k, v, g, kv_mask = _operands(name)
    how = dict(block_q=bq, block_k=bk, interpret=True, causal=causal,
               window=window)
    o, lse = fa._flash_forward(q, k, v, kv_mask, **how)
    out = {}
    for path in ("one", "two"):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(fa, "_backward_fits",
                          lambda *shape, path=path: path == "one")
            jaxpr = str(jax.make_jaxpr(lambda *a: fa._flash_backward(
                *a, kv_mask, **how))(q, k, v, o, lse, g))
            assert jaxpr.count("pallas_call[") == {"one": 1, "two": 2}[path]
            grads = fa._flash_backward(q, k, v, o, lse, g, kv_mask, **how)
        for what, grad in zip(("dq", "dk", "dv"), grads):
            out[path, what] = np.asarray(grad.astype(jnp.float32))
    return out


@pytest.mark.parametrize("what", ["dq", "dk", "dv"])
@pytest.mark.parametrize("name", list(CASES))
def test_one_kernel_makes_the_two_kernels_gradients_to_the_bit(name, what):
    both = _both(name)
    assert np.isfinite(both["one", what]).all()
    assert np.abs(both["two", what]).max() > 0
    np.testing.assert_array_equal(both["one", what], both["two", what])


def test_a_dead_rows_gradients_are_exact_zeros():
    """A batch row whose keys are all masked: ``lse == NEG`` there and
    ``exp(NEG - NEG) == 1``, which the multiplicative mask puts back to 0."""
    both = _both("kv_mask_with_a_dead_row")
    for what in ("dq", "dk", "dv"):
        assert not both["one", what][0].any()
        assert both["one", what][1].any()


@pytest.mark.parametrize("name", ["causal", "window", "not_causal",
                                  "kv_mask_causal", "group_of_4",
                                  "192_over_128", "whole_axis_block"])
def test_grad_through_flash_attention_matches_the_reference(name):
    """``jax.grad`` through ``flash_attention`` (the one-kernel backward
    pass: every shape here fits) against the jnp reference, in float32."""
    (bq, bk), causal, window = CASES[name][5:8]
    q, k, v, g, kv_mask = _operands(name, jnp.float32)
    if kv_mask is not None:
        kv_mask = kv_mask.at[0].set(1.0)  # a dead row is the test above's

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * g)

    got = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, bq, bk, True, causal=causal, window=window,
        kv_mask=kv_mask)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: fa._reference(
        q, k, v, causal=causal, window=window, kv_mask=kv_mask)),
        (0, 1, 2))(q, k, v)
    for a, b, what in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, atol=3e-5, err_msg=what)


@pytest.mark.parametrize("causal", [False, True])
def test_a_padded_sequence_differentiates_the_same_either_way(causal,
                                                              monkeypatch):
    """600 positions, which (128, 128) blocks do not tile: padded to 640
    with the padding masked, outside the ``custom_vjp``."""
    ks = jax.random.split(jax.random.key(3), 4)
    q, k, v, g = (jax.random.normal(key, (1, 600, 2, 16), jnp.bfloat16)
                  for key in ks)

    def grads():
        return jax.grad(lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, 128, 128, True, causal=causal)
            .astype(jnp.float32) * g), (0, 1, 2))(q, k, v)

    one = grads()
    monkeypatch.setattr(fa, "_backward_fits", lambda *shape: False)
    for a, b in zip(one, grads()):
        assert a.shape == (1, 600, 2, 16)
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


def test_the_rings_block_backward_is_unchanged(monkeypatch):
    """``ring_flash_attention``'s per-block backward hands
    ``_flash_backward`` the GLOBAL ``o`` and ``lse`` of two key-value
    blocks (a 2-device ring, outside ``shard_map``, as
    ``tests/test_ring_attention.py`` simulates it): the gradients of both
    blocks are the two-kernel pass's to the bit, the causal diagonal block's
    too."""
    from tpu_ddp.parallel.ring_attention import (
        _block_bwd, _block_fwd, _combine)

    ks = jax.random.split(jax.random.key(9), 6)
    q, k, v, k2, v2, g = (jax.random.normal(key, (1, 256, 4, 64))
                          for key in ks)
    k, v, k2, v2 = (x[:, :, :2] for x in (k, v, k2, v2))  # groups of 2
    scale = 1.0 / jnp.sqrt(jnp.asarray(64, jnp.float32))
    o1, lse1 = _block_fwd(q, k, v, scale, True, 128, 128, True, causal=True)
    o2, lse2 = _block_fwd(q, k2, v2, scale, True, 128, 128, True)
    out, lse = _combine(o1, lse1, o2, lse2)

    def blocks():
        return (_block_bwd(q, k, v, out, lse, g, scale, True, 128, 128,
                           True, causal=True)
                + _block_bwd(q, k2, v2, out, lse, g, scale, True, 128, 128,
                             True))

    one = blocks()
    monkeypatch.setattr(fa, "_backward_fits", lambda *shape: False)
    for a, b in zip(one, blocks()):
        assert np.abs(np.asarray(a)).max() > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("tokens,d_pad,dv_pad,itemsize,fits", [
    (8192, 128, 128, 2, True),     # laguna-xs2, nemotron3-super: 16.8 MB
    (8192, 256, 128, 2, True),     # joyai-llm-flash (192 -> 256): 25.2 MB
    (196, 128, 128, 2, True),      # ViT-B/16
    (16384, 128, 128, 2, True),    # 32 MiB: the budget itself
    (16384, 256, 128, 2, False),   # 50 MB
    (32768, 256, 128, 2, False),   # models/lm.py's long sequences: 100 MB
    (131072, 128, 128, 2, False),  # a pod-scale ring's local block
    (8192, 128, 128, 4, True),     # float32 operands, 12 B an element
    (16384, 128, 128, 4, False),
])
def test_the_shape_alone_picks_the_backward_pass(tokens, d_pad, dv_pad,
                                                 itemsize, fits):
    assert fa._backward_fits(tokens, d_pad, dv_pad, itemsize) is fits
    carry = fa._fused_carry_bytes(tokens, d_pad, dv_pad, itemsize)
    assert carry == tokens * (d_pad + dv_pad) * (4 + 2 * itemsize)
    assert fits == (carry <= 32 << 20)


@pytest.mark.parametrize("tokens,picked", [(256, "flash_bwd"),
                                           (65536, "flash_dq")])
def test_flash_attention_takes_no_argument_for_it(tokens, picked):
    """Nothing but the operands' shapes reaches the rule: the same call, a
    longer sequence, the other pass (traced only: nothing runs)."""
    x = jax.ShapeDtypeStruct((1, tokens, 1, 128), jnp.bfloat16)
    text = jax.jit(jax.grad(lambda q, k, v: fa.flash_attention(
        q, k, v, 128, 128, True, causal=True).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(x, x, x).as_text(debug_info=True)
    assert f"tpu_ddp.kernel.{picked}" in text
    other = {"flash_bwd": "flash_dkv", "flash_dq": "flash_bwd"}[picked]
    assert f"tpu_ddp.kernel.{other}" not in text
