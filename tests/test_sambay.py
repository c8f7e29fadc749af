"""The decoder-hybrid-decoder (``models/sambay.py``: Mamba-1 over
``ops/selective_scan.py``, differential attention, Gated Memory Units and
cross-attention over one layer's keys and values) against the benchmark's
plain reference (``chipbench/reference/phi4-mini-flash.py``) on seeded
weights at a size a CPU holds; the tensors that live across layers and
their gradients over two readers; a slice of the published layers against
the same layers of the whole; the published parameter counts; the slices
the factory refuses."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import sambay_tiny as tiny  # noqa: E402


@pytest.fixture(scope="module")
def ref():
    return tiny.reference()


@pytest.fixture(scope="module")
def seeded(ref):
    """(arch, the reference's seeded leaves, tokens) of the tiny model."""
    a = tiny.arch()
    toks, _ = tiny.tokens(2, seed=1)
    return a, ref.init_params(a, 3), jnp.asarray(toks)


def _decoder(**kwargs):
    from tpu_ddp.models.sambay import SambaYDecoder

    return SambaYDecoder(tiny.spec(), **kwargs)


def _logits(model, tree, toks):
    return model.apply({"params": tree}, toks, mutable=["counters"])[0]


@pytest.fixture(scope="module")
def reference_side(ref, seeded):
    a, params, toks = seeded
    mask = jnp.ones_like(toks, bool)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: ref.sequence_loss(a, p, toks, mask))(params)
        return ref.forward(a, params, toks), loss, grads


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_model_is_the_plain_reference(ref, seeded, reference_side, remat):
    """Logits, loss and every leaf's gradient, with and without layers
    recomputed: the tiny model has every kind of layer, two readers of the
    memory and two of the keys and values."""
    a, params, toks = seeded
    model = _decoder(remat=remat)
    want_logits, want_loss, want = reference_side
    shapes = jax.eval_shape(model.init, jax.random.key(0), toks)["params"]
    tree = tiny.program_tree(ref, a, params)
    assert (jax.tree.map(lambda x: x.shape, shapes)
            == jax.tree.map(lambda x: x.shape, tree))

    def loss(p):
        return ref.next_token_loss(
            _logits(model, tiny.program_tree(ref, a, p), toks), toks,
            jnp.ones_like(toks, bool))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(_logits(model, tree, toks), want_logits,
                                   rtol=0, atol=1e-4)
        got_loss, got = jax.value_and_grad(loss)(params)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert set(got) == set(want)
    for leaf in want:
        scale = float(jnp.max(jnp.abs(want[leaf])))
        assert scale > 0, leaf
        np.testing.assert_allclose(got[leaf], want[leaf], rtol=0,
                                   atol=2e-4 * scale, err_msg=leaf)


def test_the_layers_are_of_the_published_kinds():
    from tpu_ddp.models import sambay

    spec = sambay.phi4_mini_flash_spec()
    kinds = [spec.kind(l) for l in range(32)]
    assert kinds[0:17:2] == [sambay.MAMBA] * 9
    assert kinds[1:16:2] == [sambay.ATTENTION] * 8
    assert kinds[17] == sambay.ATTENTION
    assert kinds[18::2] == [sambay.GMU] * 7
    assert kinds[19::2] == [sambay.CROSS] * 7
    assert (spec.memory_layer, spec.kv_layer) == (16, 17)
    share = sambay.phi4_mini_flash_spec(first_layer=14, num_layers=6,
                                        vocab_rows=25008)
    assert [share.kind(l) for l in share.held] == [
        sambay.MAMBA, sambay.ATTENTION, sambay.MAMBA, sambay.ATTENTION,
        sambay.GMU, sambay.CROSS]
    assert (share.readers(sambay.GMU), share.readers(sambay.CROSS)) == (1, 1)
    assert sambay.lambda_init(15) == 0.8 - 0.6 * math.exp(-4.5)


def test_the_counters_say_how_many_layers_read_what_was_handed_on(seeded):
    _, _, toks = seeded
    model = _decoder()
    variables = model.init(jax.random.key(0), toks)
    _, counted = model.apply({"params": variables["params"]}, toks,
                             mutable=["counters"])
    counted = {k: int(v[0]) for k, v in counted["counters"].items()}
    assert counted == {"memory_readers": 2, "kv_readers": 2}


# -- tensors that live across layers ------------------------------------------

def _stack(spec, tree, first, last):
    """Layers ``first..last`` of ``spec`` applied one by one with the
    model's own leaves: ``(h, memory, keys, values) -> the same``, each
    layer given what its kind reads."""
    from tpu_ddp.models import sambay

    def run(h, memory=None, keys=None, values=None, reads=None):
        """``reads`` = {layer: (memory, keys, values)} gives a layer other
        tensors than the ones handed on."""
        for l in range(first, last + 1):
            layer = sambay.SambaYLayer(l, spec)
            m, k, v = (reads or {}).get(l, (memory, keys, values))
            kind = spec.kind(l)
            given = {sambay.GMU: (m,), sambay.CROSS: (None, k, v)}.get(
                kind, ())
            h, handed = layer.apply(
                {"params": tree[f"layer_{l - spec.first_layer}"]}, h, *given)
            if l == spec.memory_layer:
                memory = handed
            elif l == spec.kv_layer:
                keys, values = handed
        return h, memory, keys, values

    return run


def test_the_gradient_of_what_is_handed_on_sums_over_its_readers(ref, seeded):
    """Layers 8-11 of the tiny model (two Gated Memory Units, two
    cross-attention layers) as a function of the memory and of the keys and
    values: the gradient with both readers on one tensor is the sum of the
    gradients with a tensor each, and each reader's is not zero."""
    a, params, toks = seeded
    spec = tiny.spec()
    tree = tiny.program_tree(ref, a, params)
    lower = _stack(spec, tree, 0, 7)
    upper = _stack(spec, tree, 8, 11)
    h = jnp.asarray(params["embed"])[toks]
    h, memory, keys, values = lower(h)
    assert memory.shape == (2, tiny.T, 2 * tiny.HIDDEN)
    assert keys.shape == (2, tiny.T, 2, 8) and values.shape == (
        2, tiny.T, 1, 16)

    def loss(first, second):
        """Readers 8 and 9 read ``first``, 10 and 11 ``second``, each
        (memory, keys, values)."""
        out, _, _, _ = upper(h, reads={8: first, 9: first, 10: second,
                                       11: second})
        return jnp.sum(jnp.square(out))

    shared = (memory, keys, values)
    together = jax.grad(lambda t: loss(t, t))(shared)
    apart = jax.grad(loss, argnums=(0, 1))(shared, shared)
    for total, one, other in zip(together, *apart):
        assert float(jnp.max(jnp.abs(one))) > 0
        assert float(jnp.max(jnp.abs(other))) > 0
        np.testing.assert_allclose(total, one + other, rtol=1e-5, atol=1e-6)
    # and the two halves are the stack: the same output from the same leaves
    whole, _, _, _ = _stack(spec, tree, 0, 11)(jnp.asarray(
        params["embed"])[toks])
    out, _, _, _ = upper(h, memory, keys, values)
    np.testing.assert_allclose(out, whole, rtol=1e-5, atol=1e-5)


def test_a_slice_is_the_same_layers_of_the_whole_model(ref):
    """Published layers 14-19 of a tiny 32-layer model, given the slice's
    own input (the embedding), are the slice: the same leaves under the
    slice's names, ``lambda_init`` by the published index."""
    from tpu_ddp.models.sambay import SambaYDecoder

    whole_arch = tiny.arch(layers=32)
    part_arch = tiny.arch(layers=32, first_layer=14, num_layers=6)
    whole = ref.init_params(whole_arch, 5)
    part = {leaf: whole[leaf if not leaf.startswith("layer_") else
                        "layer_%d.%s" % (int(leaf.split(".")[0][6:]) + 14,
                                         leaf.split(".", 1)[1])]
            for leaf in ref.param_shapes(part_arch)}
    toks = jnp.asarray(tiny.tokens(2, seed=2)[0])
    part_spec = tiny.spec(layers=32, first_layer=14, num_layers=6)
    logits = _logits(SambaYDecoder(part_spec),
                     tiny.program_tree(ref, part_arch, part), toks)
    whole_spec = tiny.spec(layers=32)
    whole_tree = tiny.program_tree(ref, whole_arch, whole)
    h, _, _, _ = _stack(whole_spec, whole_tree, 14, 19)(
        jnp.asarray(whole["embed"])[toks])
    with jax.default_matmul_precision("highest"):
        want = ref.layer_norm(h, whole["final_norm.scale"],
                              whole["final_norm.bias"], 1e-5,
                              "float32_highest") @ whole["embed"].T
        np.testing.assert_allclose(logits, want, rtol=0, atol=1e-4)
        # and the reference's slice is the program's
        np.testing.assert_allclose(ref.forward(part_arch, part, toks),
                                   logits, rtol=0, atol=1e-4)


# -- the published model --------------------------------------------------------

@pytest.mark.parametrize("share,parameters", [
    ({}, 3_852_457_984),
    (dict(first_layer=14, num_layers=6, vocab_rows=25008), 697_073_792),
], ids=["published", "share"])
def test_the_published_models_parameters_are_counted_by_eval_shape(
        share, parameters):
    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.train.trainer import TrainConfig, build_model

    model = build_model(TrainConfig(
        model="phi4_mini_flash", model_overrides=share or None))
    assert "phi4_mini_flash" in MODEL_REGISTRY and model.task == "next_token"
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 16), jnp.int32))["params"]
    assert sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(
        shapes)) == parameters


def test_the_reference_counts_the_same_parameters_and_flops(ref):
    import json

    with open(os.path.join(tiny.REPO, "chipbench", "configs",
                           "phi4-mini-flash.json")) as f:
        a = json.load(f)
    assert sum(math.prod(shape) for shape, _ in ref.param_shapes(
        a).values()) == 697_073_792
    whole = dict(a, first_layer=0, layers_here=32, vocab_size=200064)
    assert sum(math.prod(shape) for shape, _ in ref.param_shapes(
        whole).values()) == 3_852_457_984
    parts = ref.forward_flops_by_part(a, 16384)
    total = sum(parts.values())
    # 1,654 MFLOP a token forward, 81.3 TFLOP a sequence trained
    assert total / 16384 == pytest.approx(1654.2e6, rel=1e-3)
    assert ref.train_flops_per_example(
        a, {"dataset": {"seq_len": 16384}}) == 3 * total
    assert parts["attention"] / total == pytest.approx(0.157, abs=2e-3)
    assert parts["scan"] == 2 * 6.0 * 16384 * 5120 * 16
    assert parts["mlp"] / total == pytest.approx(0.570, abs=2e-3)


@pytest.mark.parametrize("share", [
    dict(first_layer=18, num_layers=2),    # a reader of the memory alone
    dict(first_layer=17, num_layers=2),    # keys and values, no memory
    dict(first_layer=19, num_layers=1),    # a reader of keys and values
    dict(first_layer=30, num_layers=4),    # past the published layers
], ids=["gmu_alone", "no_memory", "cross_alone", "past_the_end"])
def test_a_slice_without_its_producer_is_refused(share):
    from tpu_ddp.train.trainer import TrainConfig, build_model

    with pytest.raises(ValueError, match="layer"):
        build_model(TrainConfig(model="phi4_mini_flash",
                                model_overrides=share))


def test_a_slice_that_hands_on_and_reads_nothing_is_built():
    from tpu_ddp.train.trainer import TrainConfig, build_model

    model = build_model(TrainConfig(
        model="phi4_mini_flash",
        model_overrides=dict(first_layer=2, num_layers=4)))
    assert model.spec.readers("gmu") == 0
