"""FLOPs from shapes: the three counts the issue pins, by the reference's own
forward pass."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import flops  # noqa: E402
from chipbench import run as harness  # noqa: E402
from chipbench_tiny import NETRESDEEP_PUBLISHED  # noqa: E402


def reference(name):
    return harness.load_module(
        os.path.join(REPO, "chipbench", "reference", name + ".py"),
        "ref_" + name.replace("-", "_"))


def arch_of(name):
    if name == "netresdeep":
        return NETRESDEEP_PUBLISHED
    with open(os.path.join(REPO, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def macs(ref, arch):
    shapes = {k: jax.ShapeDtypeStruct(s, jnp.float32)
              for k, (s, _) in ref.param_shapes(arch).items()}
    side = arch["image_size"]
    image = jax.ShapeDtypeStruct((1, side, side, 3), jnp.float32)
    return flops.forward_macs(
        lambda p, x: ref.forward(arch, p, x), shapes, image)


@pytest.mark.parametrize("name,arch_edit,lo,hi,params", [
    ("resnet50-cifar", {}, 1.2975e9, 1.2985e9, 23_520_842),
    ("resnet50-cifar", {"stem": "imagenet", "image_size": 224,
                        "num_classes": 1000}, 4.0e9, 4.2e9, 25_557_032),
    ("netresdeep", {}, 24.4e6, 24.6e6, 76_074),
])
def test_forward_macs_from_shapes(name, arch_edit, lo, hi, params):
    import math

    ref = reference(name)
    arch = dict(arch_of(name), **arch_edit)
    assert lo <= macs(ref, arch) <= hi
    n = sum(math.prod(s) for s, _ in ref.param_shapes(arch).values())
    assert n == params


def test_a_training_step_is_three_forward_passes_of_two_flops_a_mac():
    assert flops.train_flops_per_image(1.0) == 6.0
    # 1.298 GMAC forward -> 7.79 GFLOP forward and backward per image
    assert abs(flops.train_flops_per_image(1.297829888e9) - 7.787e9) < 1e6


def test_contractions_inside_nested_calls_are_counted():
    def f(x, w):
        return jax.jit(lambda a, b: a @ b)(x, w).sum()

    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert flops.forward_macs(f, x, w) == 4 * 8 * 16
    # the backward pass of a matmul is two more matmuls of the same size
    assert flops.forward_macs(jax.grad(f, argnums=(0, 1)), x, w) == (
        3 * 4 * 8 * 16)


def test_a_scan_body_counts_once_per_trip_and_a_while_is_refused():
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 8), jnp.float32)

    def scanned(x, w):
        return jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=5)[0]

    assert flops.forward_macs(scanned, x, w) == 5 * 4 * 8 * 8

    def looped(x, w):
        return jax.lax.fori_loop(0, x.shape[0] + jnp.int32(1),
                                 lambda _, c: c @ w, x)

    with pytest.raises(ValueError):
        flops.forward_macs(looped, x, w)
