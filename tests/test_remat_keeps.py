"""A recomputed layer keeps what its backward pass would otherwise make
again with a kernel call (``models/decoder.py::recomputed``, ``KEPT_NAMES``):
the flash kernels' output and a float32 a row of their logsumexp
(``ops/flash_attention.py::_fwd``). On the CPU, kernels interpreted: the
loss and every gradient leaf of a recomputed stack are a stored stack's to
the last bit, and the recomputed stack's program holds one forward kernel
call and one backward call a layer, where a bare ``nn.remat`` held two and
one. What that is worth on the chip is PERF.md's to say (section 6, PR 42);
that the compiler for the chip drops the second call too, and what the kept
values weigh there, is ``tests/test_chip_compile.py``'s."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import decoder_tiny
import hybrid_tiny
import joyai_tiny
import sdar_tiny


def _flash(block=8):
    from tpu_ddp.ops.flash_attention import flash_attention

    return functools.partial(flash_attention, block_q=block, block_k=block,
                             interpret=True)


def _sparse(tiny, **how):
    from tpu_ddp.models.decoder import SparseDecoder

    return functools.partial(SparseDecoder, tiny.spec(), **how)


def _hybrid(**how):
    from tpu_ddp.models.hybrid import HybridDecoder

    return functools.partial(HybridDecoder, hybrid_tiny.spec(), **how)


def _sdar_tokens():
    clean = np.asarray(sdar_tiny.tokens(2, seed=3)[0])
    noisy = np.where(np.arange(clean.shape[1]) % 3 == 0, sdar_tiny.VOCAB - 1,
                     clean)
    return jnp.asarray(np.concatenate([clean, noisy], axis=1))


#: case: (model without its ``remat``, tokens, attention layers)
STACKS = {
    "laguna": lambda: (_sparse(decoder_tiny, attention_impl=_flash()),
                       jnp.asarray(decoder_tiny.tokens(2, seed=3)[0]), 5),
    # the prediction module's layer is a sixth of a kind, its names the same
    "joyai_mtp": lambda: (
        _sparse(joyai_tiny, attention_impl=_flash(4)),
        jnp.asarray(joyai_tiny.tokens(2, seed=3)[0]),
        joyai_tiny.LAYERS + 1),
    "sdar_block_mask": lambda: (_sparse(sdar_tiny, attention_impl=_flash()),
                                _sdar_tokens(), sdar_tiny.LAYERS),
    # one attention block in five keeps the same two names
    "hybrid": lambda: (_hybrid(attention_impl=_flash(4)),
                       jnp.asarray(hybrid_tiny.tokens(2, seed=3)[0]), 1),
}


def _loss_of(model):
    def loss(tree, tokens):
        out, _ = model.apply({"params": tree}, tokens, mutable=["counters"])
        outs = out if isinstance(out, tuple) else (out,)
        return sum(jnp.mean(o ** 2) for o in outs)

    return loss


def _same_bits(a, b):
    flat_a, tree_a = jax.tree.flatten(a)
    flat_b, tree_b = jax.tree.flatten(b)
    assert tree_a == tree_b
    for x, y in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("case", list(STACKS))
def test_a_recomputed_stack_is_the_stored_one_with_one_forward_call_a_layer(
        case, monkeypatch):
    """To the last bit wherever a recomputed layer keeps the attention's two
    names and an expert layer's routed result. A router with a selection
    bias (``joyai``, the hybrid stack) keeps its float32 logits, ids and
    scores too, and XLA:CPU then fuses the backward pass of the scores
    otherwise: with the whole tuple a recomputed stack is there the stored
    one to float32's rounding, and ``joyai`` to the last bit with the
    attention's two names alone. (The hybrid stack's recomputed blocks
    round otherwise than its stored ones whatever they keep, its loss
    already: the state-space mixer's, before this test existed.)"""
    from tpu_ddp.models import decoder
    from tpu_ddp.ops.flash_attention import LSE_NAME, OUT_NAME

    make, tokens, layers = STACKS[case]()
    stored, recomputed = make(remat=False), make(remat=True)
    tree = stored.init(jax.random.key(0), tokens)["params"]
    step = {m: jax.value_and_grad(_loss_of(m)) for m in (stored, recomputed)}
    want = jax.jit(step[stored])(tree, tokens)
    got = jax.jit(step[recomputed])(tree, tokens)
    if case in ("joyai_mtp", "hybrid"):
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6), want, got)
    if case == "joyai_mtp":
        monkeypatch.setattr(decoder, "KEPT_NAMES", (OUT_NAME, LSE_NAME))
        got = jax.jit(jax.value_and_grad(_loss_of(make(remat=True))))(
            tree, tokens)
        monkeypatch.undo()
    if case != "hybrid":
        _same_bits(want, got)
    # a forward and a backward kernel a layer, recomputed or not: the
    # forward kernel's second call went with the names (3 a layer before).
    # The hybrid stack's two Mamba-2 blocks have the scan's kernels beside
    # (PR 47), and keep nothing of them: a recomputed one runs the forward
    # kernel in both passes
    scans = hybrid_tiny.PATTERN.count("M") if case == "hybrid" else 0
    for model in (stored, recomputed):
        traced = jax.make_jaxpr(step[model])(tree, tokens)
        assert _kernel_calls(traced.jaxpr) == 2 * layers + scans * (
            3 if model.remat else 2), case
        assert ("remat2" in str(traced)) == model.remat


def _kernel_calls(jaxpr) -> int:
    """The ``pallas_call`` equations of a jaxpr and of what it calls, each
    call site counted (the scan's calls are jitted, and a jaxpr's text
    prints a jitted function once however often it is called)."""
    return sum(
        1 if eqn.primitive.name == "pallas_call" else sum(
            _kernel_calls(sub)
            for sub in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


def _attend(q, k, v, kv_mask, w):
    """A layer in small: a projection either side of the kernel, so that the
    recomputation has something to recompute and the kernel's operands are
    not the function's arguments."""
    from tpu_ddp.ops.flash_attention import flash_attention

    o = flash_attention(q @ w, k @ w, v, 8, 128, True, causal=True,
                        kv_mask=kv_mask)
    return jnp.sum((o @ w) ** 2)


@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "kv_mask"])
def test_the_names_keep_the_kernels_residuals_with_and_without_a_kv_mask(
        masked):
    """``jax.checkpoint`` over ``KEPT_NAMES`` around the kernel itself: the
    kv mask's rows with no visible key (logsumexp ``NEG``, output 0) come
    through the kept float32 a row as they came through 128 lanes."""
    from tpu_ddp.models.decoder import KEPT_NAMES

    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 256, 2, 16)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.standard_normal((16, 16)) / 4, jnp.float32)
    kv_mask = None
    if masked:
        mask = rng.random((2, 256)) > 0.3
        mask[1, :40] = False  # rows 0..39 of sequence 1 see no key at all
        kv_mask = jnp.asarray(mask, jnp.float32)
    stored = jax.value_and_grad(_attend, (0, 1, 2, 4))
    kept = jax.value_and_grad(jax.checkpoint(
        _attend, policy=jax.checkpoint_policies.save_only_these_names(
            *KEPT_NAMES)), (0, 1, 2, 4))
    bare = jax.value_and_grad(jax.checkpoint(_attend), (0, 1, 2, 4))
    args = (q, k, v, kv_mask, w)
    _same_bits(jax.jit(stored)(*args), jax.jit(kept)(*args))
    calls = {name: str(jax.make_jaxpr(f)(*args)).count("pallas_call")
             for name, f in dict(stored=stored, kept=kept, bare=bare).items()}
    assert calls == {"stored": 2, "kept": 2, "bare": 3}


def test_what_lives_from_pass_to_pass_is_a_float_a_row():
    """The residuals of the kernel's ``custom_vjp``: the operands, the
    output, and the logsumexp as (B*H, T) float32: no (rows, 128) buffer
    outlives the forward pass."""
    fa = importlib.import_module("tpu_ddp.ops.flash_attention")

    q = jnp.ones((2, 32, 3, 16), jnp.float32)
    _, res = fa._fwd(q, q, q, None, 8, 8, True, True, 0, None)
    *_, out, lse = res
    assert out.shape == q.shape
    assert (lse.shape, lse.dtype) == ((2 * 3, 32), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda q: fa._fwd(q, q, q, None, 8, 8, True, True, 0, None))(q))
    assert f"name={fa.OUT_NAME}" in text and f"name={fa.LSE_NAME}" in text


def test_the_interpreted_detour_under_shard_map_names_its_output_alone():
    """Interpreted under ``shard_map`` the forward pass is the fused jnp
    reference and there are no statistics (``lse is None``): the output
    alone is named, the backward pass differentiates the reference, and a
    recomputed stack is still the stored one to the last bit."""
    fa = importlib.import_module("tpu_ddp.ops.flash_attention")

    make, tokens, _ = STACKS["laguna"]()
    stored, recomputed = make(remat=False), make(remat=True)
    tree = stored.init(jax.random.key(0), tokens)["params"]
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))

    def sharded(model):
        def step(tree, tokens):
            return jax.value_and_grad(lambda t: jax.lax.pmean(
                _loss_of(model)(t, tokens), "data"))(tree)

        return jax.shard_map(step, mesh=mesh, in_specs=(P(), P("data")),
                             out_specs=P())

    _same_bits(jax.jit(sharded(stored))(tree, tokens),
               jax.jit(sharded(recomputed))(tree, tokens))
    text = str(jax.make_jaxpr(sharded(recomputed))(tree, tokens))
    assert "pallas_call" not in text
    assert f"name={fa.OUT_NAME}" in text and fa.LSE_NAME not in text
