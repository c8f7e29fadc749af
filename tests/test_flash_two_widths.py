"""The flash kernels where queries and keys are of another width than
values (latent attention: 192 over 128), interpreted on the CPU against the
jnp reference, forward and all three gradients; and with one width the
kernels' programs are the parent's."""

import hashlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.ops.flash_attention import (
    _check_heads, _reference, flash_attention)


def _operands(t, heads, kv_heads, dqk, dv, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    shape = lambda h, d: (2, t, h, d)  # noqa: E731
    return (jax.random.normal(ks[0], shape(heads, dqk)),
            jax.random.normal(ks[1], shape(kv_heads, dqk)),
            jax.random.normal(ks[2], shape(kv_heads, dv)),
            jax.random.normal(ks[3], shape(heads, dv)))


CASES = {  # t, heads, kv_heads, dqk, dv, block, window
    "192_over_128": (32, 2, 2, 192, 128, 16, 0),
    "24_over_16": (64, 4, 4, 24, 16, 16, 0),
    "24_over_16_grouped": (64, 4, 2, 24, 16, 16, 0),
    "24_over_16_window": (64, 4, 4, 24, 16, 16, 24),
    "16_over_24": (64, 2, 2, 16, 24, 32, 0),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
def test_two_widths_match_the_reference(case, what):
    t, heads, kv_heads, dqk, dv, block, window = CASES[case]
    q, k, v, g = _operands(t, heads, kv_heads, dqk, dv)
    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, block, block, True, causal=True, window=window)
    plain = lambda q, k, v: _reference(  # noqa: E731
        q, k, v, causal=True, window=window)
    if what == "forward":
        out = flash(q, k, v)
        assert out.shape == (2, t, heads, dv)
        np.testing.assert_allclose(out, plain(q, k, v), atol=5e-6)
        return
    arg = ("dq", "dk", "dv").index(what)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * g), arg)(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * g), arg)(q, k, v)
    assert got.shape == (q, k, v)[arg].shape
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_scale_is_the_key_widths():
    """``1 / sqrt(192)``, not ``1 / sqrt(128)``: the reference the kernels
    are held to scales by the queries' width."""
    q, k, v, _ = _operands(16, 1, 1, 192, 128)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(192)
    vis = jnp.tril(jnp.ones((16, 16), bool))
    want = jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(vis, s, -jnp.inf), -1), v)
    np.testing.assert_allclose(_reference(q, k, v, causal=True), want,
                               atol=1e-6)
    np.testing.assert_allclose(
        flash_attention(q, k, v, 16, 16, True, causal=True), want, atol=5e-6)


def test_keys_of_another_width_than_queries_are_refused():
    q, k, v, _ = _operands(16, 2, 2, 24, 16)
    with pytest.raises(ValueError, match="share a width"):
        _check_heads(q, k[..., :16], v)
    assert _check_heads(q, k, v) == 1


#: sha256 of the three ``pallas_call`` equations (forward, dQ, dK/dV) in the
#: jaxpr of the function below, each printed by itself, source positions
#: taken out, read at 6673acc (PR 42's parent, ``git archive``, this
#: container's jax) and equal there and here: with one width the kernels,
#: their grids, block shapes and scratch are what a single ``D`` made
#: (PR 36 pinned the whole text at f4db89a; the kernels' equations alone
#: since PR 42, whose ``_fwd`` names the output and a float32 a row of the
#: logsumexp and whose ``_bwd`` widens it again: equations between the calls
#: that are no kernel's). The text is jax's, so another jax makes another.
#: Since PR 40 a shape this small runs the one-kernel backward pass
#: (``tests/test_flash_one_backward.py``); the pair the pin describes serves
#: what that kernel's carry does not fit, and is asked for here as
#: ``_backward_fits`` would for such a shape.
PARENT = {
    0: "c37086a5d06218c3312b578e80e20c3dbd4730d95f063d0218abca51f13f63b5",
    8: "880aa2d1adcb731ae043e22b58ce68f9df621ceb5918f1cab9dcaf9cad3b6369",
}


def _kernel_calls(jaxpr) -> list:
    """Every ``pallas_call`` equation under ``jaxpr``, in order, each as
    its own text."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append(str(eqn))
        else:
            for inner in jax.core.jaxprs_in_params(eqn.params):
                calls += _kernel_calls(inner)
    return calls


@pytest.mark.parametrize("window", list(PARENT))
def test_with_one_width_the_kernels_are_the_parents(window, monkeypatch):
    # ``tpu_ddp.ops`` exports a function of the module's name
    monkeypatch.setattr(sys.modules[flash_attention.__module__],
                        "_backward_fits", lambda *shape: False)
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, 16, 16, True, causal=True,
                                       window=window) ** 2)

    calls = _kernel_calls(
        jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2)))(q, k, v).jaxpr)
    assert len(calls) == 3
    text = re.sub(r" at [^\s\]]*flash_attention.py:\d+", "",
                  "\n".join(calls))
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT[window]
