"""Plain reference for ``netresdeep``: the model of the repository this
framework re-implements (BaamPark/DistributedDataParallel-Cifar10,
``model/resnet.py``): conv 3->C 3x3 pad 1 with bias, ReLU, max-pool 2,
``n_blocks`` residual blocks, max-pool 2, flatten, fc -> 32, ReLU, fc ->
classes. A residual block is conv CxC 3x3 pad 1 without bias, batch norm,
ReLU, plus its input.

The source builds its blocks as ``n_blocks * [ResBlock(...)]``: ONE block
object repeated, so the ten applications share one set of weights and one
BatchNorm, whose batch statistics are taken afresh at each application. The
reference follows the source (``tied_blocks``), not any module of the
program.

Departures: NHWC activations and HWIO kernels, so the flattened feature order
differs from the source's NCHW ``view`` (a fixed permutation of fc1's rows).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import common as C


def param_shapes(arch) -> dict:
    c = arch["n_chans1"]
    side = arch["image_size"] // 4
    if not arch.get("tied_blocks", True):
        raise ValueError("the source ties its blocks; untied is not its model")
    return {
        "conv1.kernel": ((3, 3, arch.get("channels", 3), c), "uniform"),
        "conv1.bias": ((c,), "uniform:%d" % (9 * arch.get("channels", 3))),
        "resblock.conv": ((3, 3, c, c), "kaiming"),
        "resblock.bn.scale": ((c,), "half"),
        "resblock.bn.bias": ((c,), "zeros"),
        "fc1.kernel": ((side * side * c, arch["fc_width"]), "uniform"),
        "fc1.bias": ((arch["fc_width"],), "uniform:%d" % (side * side * c)),
        "fc2.kernel": ((arch["fc_width"], arch["num_classes"]), "uniform"),
        "fc2.bias": ((arch["num_classes"],), "uniform:%d" % arch["fc_width"]),
    }


def init_params(arch, seed: int) -> dict:
    """Seeded float32 weights in one jitted call, by the source's rules:
    torch's default U(-1/sqrt(fan_in), 1/sqrt(fan_in)) for conv1 and the fc
    layers and their biases; Kaiming normal (fan-in, ReLU) for the block's
    convolution; BN scale 0.5, bias 0."""
    shapes = param_shapes(arch)

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            fan_in = math.prod(shape[:-1])
            if kind == "half":
                out[name] = jnp.full(shape, 0.5, jnp.float32)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            elif kind == "kaiming":
                out[name] = math.sqrt(2.0 / fan_in) * jax.random.normal(
                    k, shape, jnp.float32)
            else:
                if ":" in kind:
                    fan_in = int(kind.split(":")[1])
                bound = 1.0 / math.sqrt(fan_in)
                out[name] = jax.random.uniform(
                    k, shape, jnp.float32, -bound, bound)
        return out

    return jax.jit(make)(jax.random.key(seed))


def forward(arch, params, images, precision="float32_highest"):
    """Training-mode forward pass: logits (N, num_classes) in float32."""
    x = C.conv(C.hold(images, precision), params["conv1.kernel"], precision)
    x = C.hold(x + params["conv1.bias"].astype(x.dtype), precision)
    x = C.max_pool(jax.nn.relu(x), 2, 2)
    for _ in range(arch["n_blocks"]):
        y = C.conv(x, params["resblock.conv"], precision)
        y = C.batch_norm(y, params["resblock.bn.scale"],
                         params["resblock.bn.bias"], precision)
        x = C.hold(jax.nn.relu(y) + x, precision)
    x = C.max_pool(x, 2, 2)
    x = x.reshape((x.shape[0], -1))
    x = jax.nn.relu(C.dense(x, params["fc1.kernel"], params["fc1.bias"],
                            precision))
    logits = C.dense(x, params["fc2.kernel"], params["fc2.bias"], precision)
    return logits.astype(jnp.float32)


#: the output layer's leaves: their gradient sees the whole forward pass and
#: no backward pass (``chipbench/compare.py``, ``out_grad_diff``)
OUTPUT_LEAVES = ("fc2.kernel", "fc2.bias")


def program_names(arch) -> dict:
    """reference leaf name -> path in the program's parameter tree
    (``tpu_ddp.models.resnet.NetResDeep`` with ``tied=True``)."""
    del arch
    return {
        "conv1.kernel": ("conv1", "kernel"), "conv1.bias": ("conv1", "bias"),
        "resblock.conv": ("resblock", "conv", "kernel"),
        "resblock.bn.scale": ("resblock", "batch_norm", "scale"),
        "resblock.bn.bias": ("resblock", "batch_norm", "bias"),
        "fc1.kernel": ("fc1", "kernel"), "fc1.bias": ("fc1", "bias"),
        "fc2.kernel": ("fc2", "kernel"), "fc2.bias": ("fc2", "bias"),
    }
