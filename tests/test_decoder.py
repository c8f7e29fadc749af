"""The sparse decoder (``models/decoder.py``, ``models/moe.py::DroplessMoE``,
the windowed grouped-query flash kernel) against the benchmark's plain
reference (``chipbench/reference/laguna-xs2.py``) on seeded weights, at a
size a CPU holds, for a stack with every kind of layer the published model
has; and the ``Trainer`` driving it through the one step builder.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import decoder_tiny as tiny  # noqa: E402


@pytest.fixture(scope="module")
def ref():
    return tiny.reference()


@pytest.fixture(scope="module")
def seeded(ref):
    arch = tiny.arch()
    params = ref.init_params(arch, 7)
    tokens = jnp.asarray(tiny.tokens(2, seed=3)[0])
    return arch, params, tokens


def _program(params, ref, arch, **kwargs):
    from tpu_ddp.models.decoder import SparseDecoder

    return (SparseDecoder(tiny.spec(**kwargs.pop("spec", {})), **kwargs),
            tiny.program_tree(ref, arch, params))


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_logits_loss_and_gradients_match_the_reference(ref, seeded, remat):
    arch, params, tokens = seeded
    model, tree = _program(params, ref, arch, remat=remat)
    init = model.init(jax.random.key(0), tokens[:, :8])["params"]
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, tree)
    mask = jnp.ones(tokens.shape, bool)

    def program_loss(tree):
        logits, _ = model.apply({"params": tree}, tokens,
                                mutable=["counters"])
        return ref.next_token_loss(logits, tokens, mask), logits

    def reference_loss(params):
        logits = ref.forward(arch, params, tokens)
        return ref.next_token_loss(logits, tokens, mask), logits

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            program_loss, has_aux=True)(tree)
        (want, want_logits), want_grads = jax.value_and_grad(
            reference_loss, has_aux=True)(params)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    got = tiny.program_tree(ref, arch, want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(got)):
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_the_program_task_loss_is_the_reference_loss(ref, seeded):
    from tpu_ddp.train.tasks import NEXT_TOKEN

    arch, params, tokens = seeded
    logits = ref.forward(arch, params, tokens)
    loss_mask = np.ones(tokens.shape, bool)
    loss_mask[0, 10:] = False
    rows = np.array([True, False])
    batch = {"tokens": tokens, "loss_mask": jnp.asarray(loss_mask),
             "mask": jnp.asarray(rows)}
    want = ref.next_token_loss(logits, tokens, ref.target_mask(
        {k: np.asarray(v) for k, v in batch.items()}))
    np.testing.assert_allclose(NEXT_TOKEN.loss(None, logits, batch), want,
                               rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: the partial results of all the shares, the
    shared expert counted once, are the uncut reference's layer."""
    from tpu_ddp.models.moe import DroplessMoE
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    whole = tiny.arch(layers=2, held=tiny.EXPERTS, offset=0)
    params = ref.init_params(whole, 11)
    p = {k.split(".", 1)[1]: v for k, v in params.items()
         if k.startswith("layer_1.")}
    x = jax.random.normal(jax.random.key(5), (2, tiny.T, tiny.HIDDEN))
    with jax.default_matmul_precision("highest"):
        want = ref._moe(whole, p, x, (0, tiny.EXPERTS), "float32_highest")
        shared = ref.swiglu(x, p["moe.shared.gate"], p["moe.shared.up"],
                            p["moe.shared.down"], "float32_highest")
        total, landed = shared, 0
        shares = 4
        for position in range(shares):
            share = ExpertShare.of_position(tiny.EXPERTS, position, shares)
            rows = slice(share.offset, share.offset + share.held)
            layer = DroplessMoE(
                share, top_k=tiny.TOP_K, expert_width=24, shared_width=24,
                scaling=2.5)
            tree = {"router": {"kernel": p["moe.router"]},
                    "w_gate": p["moe.w_gate"][rows],
                    "w_up": p["moe.w_up"][rows],
                    "w_down": p["moe.w_down"][rows],
                    "shared": {k: {"kernel": p[f"moe.shared.{k}"]}
                               for k in ("gate", "up", "down")}}
            y, mut = layer.apply({"params": tree}, x, mutable=["counters"])
            # the reference given the same share computes the same part
            np.testing.assert_allclose(
                y, ref._moe(whole, dict(p, **{
                    f"moe.{k}": p[f"moe.{k}"][rows]
                    for k in ("w_gate", "w_up", "w_down")}), x,
                    (share.offset, share.held), "float32_highest"),
                atol=2e-5)
            total = total + (y - shared)
            landed += int(mut["counters"]["expert_load"][0].sum())
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert landed == 2 * tiny.T * tiny.TOP_K  # every pair landed once


def test_a_skewed_router_drops_no_token(ref):
    """Most tokens to one expert: no capacity, so every pair that names a
    held expert is computed, and the layer is still the reference's."""
    from tpu_ddp.models.moe import DroplessMoE
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    arch = tiny.arch(layers=2, held=4, offset=0)
    params = ref.init_params(arch, 13)
    p = {k.split(".", 1)[1]: v for k, v in params.items()
         if k.startswith("layer_1.")}
    # a router that scores expert 2 far above the rest, for every token
    router = np.array(p["moe.router"]) * 0.01
    x = jnp.abs(jax.random.normal(jax.random.key(9),
                                  (2, tiny.T, tiny.HIDDEN))) + 0.1
    router[:, 2] += 1.0
    p["moe.router"] = jnp.asarray(router)
    layer = DroplessMoE(ExpertShare(tiny.EXPERTS, 4, 0), top_k=tiny.TOP_K,
                        expert_width=24, shared_width=24, scaling=2.5)
    tree = {"router": {"kernel": p["moe.router"]},
            "w_gate": p["moe.w_gate"], "w_up": p["moe.w_up"],
            "w_down": p["moe.w_down"],
            "shared": {k: {"kernel": p[f"moe.shared.{k}"]}
                       for k in ("gate", "up", "down")}}
    with jax.default_matmul_precision("highest"):
        y, mut = layer.apply({"params": tree}, x, mutable=["counters"])
        want = ref._moe(arch, p, x, (0, 4), "float32_highest")
    load = np.asarray(mut["counters"]["expert_load"][0])
    assert load[2] == 2 * tiny.T, load  # every token chose expert 2
    np.testing.assert_allclose(y, want, atol=2e-5)


def test_expert_share_is_checked():
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    assert ExpertShare.of_position(256, 0, 8) == ExpertShare(256, 32, 0)
    with pytest.raises(ValueError):
        ExpertShare(256, 32, 240)
    with pytest.raises(ValueError):
        ExpertShare.of_position(256, 0, 7)


def test_rotary_tables_are_the_references(ref):
    from tpu_ddp.models import decoder as D

    arch = tiny.arch()
    arch["head_dim"] = 128
    arch["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 4096
    for kind, spec in (("full_attention", D._LAGUNA_FULL),
                       ("sliding_attention", D._LAGUNA_SLIDING)):
        cos, sin, dims = ref.rotary_tables(
            arch["rope_parameters"][kind], 128, 300)
        got_cos, got_sin = spec.tables(300)
        assert dims == spec.dims
        np.testing.assert_array_equal(cos, got_cos)
        np.testing.assert_array_equal(sin, got_sin)
    # YaRN leaves the fastest dimension alone and slows the slowest by 64
    full = D._LAGUNA_FULL.tables(2)[1] / 1.4158883083359672
    plain = dataclasses.replace(D._LAGUNA_FULL, yarn=None).tables(2)[1]
    np.testing.assert_allclose(full[1, 0], plain[1, 0], rtol=1e-6)
    np.testing.assert_allclose(full[1, -1] * 64, plain[1, -1], rtol=1e-5)


def test_published_sizes_count_the_published_parameters():
    from tpu_ddp.models import decoder as D

    def count(spec):
        model = D.SparseDecoder(spec)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0),
                               jnp.zeros((1, 8), jnp.int32))["params"])
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))

    assert count(D.laguna_xs2_spec()) == 33_442_596_864
    assert count(D.laguna_xs2_spec(   # the benchmark cell's share
        num_layers=5, experts_held=16, vocab_rows=12544)) == 490_297_344


# -- the windowed, grouped-query flash kernel ----------------------------------

@pytest.mark.parametrize("t,heads,kv,window,bq,bk", [
    (64, 4, 2, 0, 16, 16),     # full causal, groups of two
    (64, 4, 2, 24, 16, 16),    # a window that ends inside a block
    (64, 6, 2, 16, 16, 32),    # groups of three, blocks of unequal sizes
    (64, 4, 4, 40, 32, 16),    # no grouping, a window wider than a block
    (64, 4, 1, 8, 16, 16),     # one key-value head, a window inside a block
    (128, 2, 1, 100, 32, 64),
])
def test_windowed_grouped_flash_matches_the_blocked_reference(
        ref, t, heads, kv, window, bq, bk):
    """Interpret mode against the reference's blocked attention, forward and
    backward, window edge and head grouping included."""
    from tpu_ddp.ops.flash_attention import flash_attention

    key = jax.random.key(t + heads + window)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), shape)
                  for i, shape in enumerate([
                      (2, t, heads, 16), (2, t, kv, 16), (2, t, kv, 16),
                      (2, t, heads, 16)]))

    def kernel(q, k, v):
        return flash_attention(q, k, v, bq, bk, True, causal=True,
                               window=window)

    def blocked(q, k, v):
        return ref.blocked_attention(q, k, v, window, "float32_highest")

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(kernel(q, k, v), blocked(q, k, v),
                                   atol=2e-6)
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(blocked(*a) * w),
                        (0, 1, 2))(q, k, v)
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, atol=5e-6)


def test_the_band_visits_only_tiles_that_hold_a_visible_pair():
    from tpu_ddp.ops.flash_attention import _Band

    band = _Band(True, 512, 512, 512, 16, 16)
    assert (band.kv_width, band.q_width) == (2, 2)      # of 16 blocks
    assert [band.kv_lo(j) for j in (0, 1, 5)] == [0, 0, 4]
    assert [band.q_hi(j) for j in (0, 14, 15)] == [1, 15, 15]
    full = _Band(True, 0, 512, 512, 16, 16)
    assert (full.kv_width, full.kv_hi(3), full.q_lo(3)) == (16, 3, 3)
    every = _Band(False, 0, 128, 128, 4, 4)
    assert (every.kv_width, every.q_width, every.kv_hi(0)) == (4, 4, 3)
    # exhaustively: a tile is inside the band iff it holds a visible pair
    for bq, bk, window in ((16, 32, 24), (32, 16, 40), (16, 16, 8)):
        band = _Band(True, window, bq, bk, 128 // bq, 128 // bk)
        for j in range(band.n_q):
            for kb in range(band.n_k):
                rows = np.arange(j * bq, (j + 1) * bq)[:, None]
                cols = np.arange(kb * bk, (kb + 1) * bk)[None, :]
                any_visible = bool(np.any(
                    (cols <= rows) & (cols > rows - window)))
                assert (band.kv_lo(j) <= kb <= band.kv_hi(j)) == any_visible
                assert (band.q_lo(kb) <= j <= band.q_hi(kb)) == any_visible
