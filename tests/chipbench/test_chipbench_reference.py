"""Each plain reference against the program's own model at a tiny size on the
CPU: float32 agrees, the program in bfloat16 disagrees beyond the tolerance,
and the lower precisions of the reference itself (the control) read far above
the float32 noise."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import compare  # noqa: E402
from chipbench import run as harness  # noqa: E402
from chipbench.adapters.trainer import _flatten, _unflatten  # noqa: E402
from chipbench.reference import common  # noqa: E402
from chipbench_tiny import NETRESDEEP_PUBLISHED  # noqa: E402

TOLERANCE = 1e-3  # float32 reads ~1e-6..1e-4 here, bfloat16 above 1e-2

TINY = {
    "resnet50-cifar": {"stage_sizes": [1, 1], "num_filters": 8,
                       "expansion": 4, "image_size": 16, "channels": 3,
                       "stem": "cifar", "num_classes": 10},
    "netresdeep": {"n_chans1": 8, "n_blocks": 3, "tied_blocks": True,
                   "fc_width": 32, "image_size": 32, "channels": 3,
                   "num_classes": 10},
}


def reference(name):
    return harness.load_module(
        os.path.join(REPO, "chipbench", "reference", name + ".py"),
        "ref_" + name.replace("-", "_"))


def program_model(name, arch, dtype):
    if name == "netresdeep":
        from tpu_ddp.models import NetResDeep

        return NetResDeep(n_chans1=arch["n_chans1"],
                          n_blocks=arch["n_blocks"], tied=True, dtype=dtype)
    from tpu_ddp.models.resnet_family import ResNet, _Bottleneck

    return ResNet(tuple(arch["stage_sizes"]), _Bottleneck,
                  num_filters=arch["num_filters"], cifar_stem=True,
                  dtype=dtype)


def program_loss_and_grads(name, arch, params, images, labels, dtype):
    from tpu_ddp.train.losses import cross_entropy_loss

    ref = reference(name)
    model = program_model(name, arch, dtype)
    variables = model.init(jax.random.key(0), images[:1], train=False)
    names = ref.program_names(arch)
    assert set(_flatten(dict(variables["params"]))) == set(names.values())
    tree = _unflatten({names[k]: v for k, v in params.items()})

    def loss(p):
        logits, _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]}, images,
            train=True, mutable=["batch_stats"])
        return cross_entropy_loss(logits, labels,
                                  jnp.ones(labels.shape, bool))

    value, grads = jax.value_and_grad(loss)(tree)
    back = {v: k for k, v in names.items()}
    flat = {back[p]: g for p, g in _flatten(grads).items()}
    return float(value), compare.norms(flat)


def reference_loss_and_grads(name, arch, params, images, labels, precision):
    ref = reference(name)

    def loss(p):
        return common.cross_entropy(
            ref.forward(arch, p, images, precision), labels,
            jnp.ones(labels.shape, bool))

    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(loss)(params)
    return float(value), compare.norms(grads)


@pytest.fixture(scope="module", params=sorted(TINY))
def case(request):
    name = request.param
    arch = TINY[name]
    rng = np.random.default_rng(7)
    side = arch["image_size"]
    images = jnp.asarray(rng.normal(size=(16, side, side, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=16), jnp.int32)
    params = reference(name).init_params(arch, 7)
    loss, grads = reference_loss_and_grads(
        name, arch, params, images, labels, "float32_highest")
    return name, arch, params, images, labels, loss, grads


def test_the_reference_agrees_with_the_program_in_float32(case):
    name, arch, params, images, labels, loss, grads = case
    got_loss, got = program_loss_and_grads(
        name, arch, params, images, labels, jnp.float32)
    assert abs(got_loss - loss) / loss < 1e-5
    gap, leaf = compare.worst_leaf_gap(got, grads)
    assert gap < TOLERANCE, (gap, leaf)


def test_the_program_in_bfloat16_disagrees_beyond_the_tolerance(case):
    name, arch, params, images, labels, loss, grads = case
    _, got = program_loss_and_grads(
        name, arch, params, images, labels, jnp.bfloat16)
    gap, _ = compare.worst_leaf_gap(got, grads)
    assert gap > 3 * TOLERANCE, gap


@pytest.mark.parametrize("precision", ["bfloat16", "float8"])
def test_the_reference_one_notch_lower_is_a_control_that_fails(case, precision):
    name, arch, params, images, labels, loss, grads = case
    _, got = reference_loss_and_grads(
        name, arch, params, images, labels, precision)
    gap, _ = compare.worst_leaf_gap(got, grads)
    assert gap > 3 * TOLERANCE, gap


def test_published_sizes_give_the_published_leaves():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "resnet50-cifar.json")) as f:
        published = {"resnet50-cifar": json.load(f),
                     "netresdeep": NETRESDEEP_PUBLISHED}
    for name, n_leaves in (("resnet50-cifar", 161), ("netresdeep", 9)):
        ref, arch = reference(name), published[name]
        shapes = ref.param_shapes(arch)
        assert len(shapes) == n_leaves
        assert set(ref.program_names(arch)) == set(shapes)
        assert set(ref.OUTPUT_LEAVES) <= set(shapes)


def test_the_worst_leaf_gap_is_a_gap_of_norms_against_the_larger_floor():
    ref = {"a": 1.0, "b": 1e-9, "c": 2.0}
    got = {"a": 1.1, "b": 0.5, "c": 2.0}
    gap, leaf = compare.worst_leaf_gap(got, ref)
    # b's own norm is all but zero: measured against the median leaf (1.0)
    assert leaf == "b" and abs(gap - 0.5) < 1e-8
    gap, leaf = compare.worst_leaf_gap({"a": float("nan"), "b": 0, "c": 2},
                                       ref)
    assert leaf == "a" and gap != gap
    # the norm of the difference sees what a gap of norms cannot: a rotation
    a = {"w": np.array([1.0, 0.0]), "v": np.array([0.0, 2.0])}
    b = {"w": np.array([0.0, 1.0]), "v": np.array([0.0, 2.0])}
    assert compare.worst_leaf_gap(compare.norms(b), compare.norms(a))[0] == 0
    assert abs(compare.relative_difference(b, a) - (2 / 5) ** 0.5) < 1e-12
    assert compare.relative_difference(a, a) == 0.0
    assert compare.decide({"x": (float("nan"), "a")}, {"x": 1.0},
                          out=lambda *_: None) is False
    assert compare.decide({"x": (0.5, "a")}, {"x": 1.0},
                          out=lambda *_: None) is True


def test_three_steps_is_plain_sgd_with_momentum_over_shards():
    def forward(p, x, precision):
        return x.reshape(x.shape[0], -1)[:, :3] @ p["w"]

    rng = np.random.default_rng(0)
    params = {"w": np.asarray(rng.normal(size=(3, 4)), np.float32)}
    batches = [(np.asarray(rng.normal(size=(8, 3)), np.float32),
                np.asarray(rng.integers(0, 4, size=8), np.int32),
                np.ones(8, bool)) for _ in range(3)]
    one = common.three_steps(forward, params, batches, shards=1, lr=0.1,
                             momentum=0.9)
    # no batch statistics here, so four shards of two rows give the same
    # loss and gradient as one shard of eight
    four = common.three_steps(forward, params, batches, shards=4, lr=0.1,
                              momentum=0.9)
    np.testing.assert_allclose(one["losses"], four["losses"], rtol=1e-5)
    np.testing.assert_allclose(one["params"]["w"], four["params"]["w"],
                               rtol=1e-4, atol=1e-6)
    # by hand: v1 = g1, p1 = p0 - lr*g1; v2 = g2 + 0.9*g1, p2 = p1 - lr*v2
    def grad(p, batch):
        x, y, m = batch
        return jax.grad(lambda w: common.cross_entropy(
            forward({"w": w}, x, None), y, m))(jnp.asarray(p))

    g1 = np.asarray(grad(params["w"], batches[0]))
    p1 = params["w"] - 0.1 * g1
    np.testing.assert_allclose(one["params_after_first"]["w"], p1,
                               rtol=1e-6, atol=1e-7)
    two = common.three_steps(forward, params, batches[:2], shards=1,
                             lr=0.1, momentum=0.9)
    g2 = np.asarray(grad(p1, batches[1]))
    np.testing.assert_allclose(two["params"]["w"], p1 - 0.1 * (g2 + 0.9 * g1),
                               rtol=1e-5, atol=1e-6)
