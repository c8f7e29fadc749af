"""Plain reference for ``resnet50-cifar``: the bottleneck ResNet of He et al.,
"Deep Residual Learning for Image Recognition" (arXiv:1512.03385), Table 1,
50-layer column: stages of 3-4-6-3 bottleneck blocks at widths 64/128/256/512
(x4 out), batch normalisation after every convolution, ReLU, a projection
shortcut (1x1 convolution + BN) where the shape changes, global average pool,
one fully connected layer.

Departures, each as the configuration file lists it:
  * the stride of a down-sampling block sits on its 3x3 convolution ("v1.5",
    as every current implementation has it), not on the first 1x1;
  * ``stem == "cifar"``: one 3x3 convolution at stride 1 and no max-pool, for
    32x32 inputs; ``stem == "imagenet"`` is the published 7x7/2 + 3x3/2 pool;
  * NHWC activations and HWIO kernels.

Sizes come from the configuration file's top-level keys, so the unit tests
run the same code at a tiny size.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference import common as C


#: initial scale of the last BN of every residual branch (see init_params)
BRANCH_SCALE = 0.25


def _blocks(arch):
    """(ref prefix, in_ch, mid_ch, out_ch, stride) per bottleneck block."""
    expansion = arch.get("expansion", 4)
    in_ch = arch["num_filters"]
    for stage, n_blocks in enumerate(arch["stage_sizes"]):
        mid = arch["num_filters"] * 2**stage
        for b in range(n_blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            yield f"s{stage}.b{b}", in_ch, mid, mid * expansion, stride
            in_ch = mid * expansion


def param_shapes(arch) -> dict:
    """name -> (shape, init kind)."""
    f = arch["num_filters"]
    k = 3 if arch["stem"] == "cifar" else 7
    shapes = {"stem.conv": ((k, k, arch.get("channels", 3), f), "he"),
              "stem.bn.scale": ((f,), "ones"), "stem.bn.bias": ((f,), "zeros")}

    def bn(name, ch):
        shapes[f"{name}.scale"] = ((ch,), "ones")
        shapes[f"{name}.bias"] = ((ch,), "zeros")

    for name, cin, mid, cout, stride in _blocks(arch):
        shapes[f"{name}.conv1"] = ((1, 1, cin, mid), "he")
        bn(f"{name}.bn1", mid)
        shapes[f"{name}.conv2"] = ((3, 3, mid, mid), "he")
        bn(f"{name}.bn2", mid)
        shapes[f"{name}.conv3"] = ((1, 1, mid, cout), "he")
        bn(f"{name}.bn3", cout)
        shapes[f"{name}.bn3.scale"] = ((cout,), "branch")
        if cin != cout or stride != 1:
            shapes[f"{name}.proj.conv"] = ((1, 1, cin, cout), "he")
            bn(f"{name}.proj.bn", cout)
        last = cout
    shapes["head.kernel"] = ((last, arch["num_classes"]), "lecun")
    shapes["head.bias"] = ((arch["num_classes"],), "zeros")
    return shapes


def init_params(arch, seed: int) -> dict:
    """Seeded float32 weights, made on the device in one jitted call.
    Convolutions: He normal over the fan-out; BN scale 1 and bias 0, but the
    last BN of every residual branch starts at ``BRANCH_SCALE``; head: normal
    with variance 1/fan-in.

    With every scale at 1 the sixteen branches double the stream's variance
    sixteen times and the first steps are chaotic: at lr 0.1 the loss went
    2.8 -> 19 -> 40 and bfloat16 read 16-38% off the float32 gradient, as far
    as float8 did (my chip runs, PR 23). The usual cure is to start that scale
    at 0 (Goyal et al., arXiv:1706.02677, section 5.1), which leaves seven of
    a block's nine leaves without a first gradient to compare; a quarter
    keeps every leaf live and the stream's variance within 3x."""
    shapes = param_shapes(arch)

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            if kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "branch":
                out[name] = jnp.full(shape, BRANCH_SCALE, jnp.float32)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                fan = (shape[0] * shape[1] * shape[3] if kind == "he"
                       else shape[0])
                std = math.sqrt((2.0 if kind == "he" else 1.0) / fan)
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return jax.jit(make)(jax.random.key(seed))


def forward(arch, params, images, precision="float32_highest"):
    """Training-mode forward pass: logits (N, num_classes) in float32."""
    x = C.hold(images, precision)
    if arch["stem"] == "cifar":
        x = C.conv(x, params["stem.conv"], precision, stride=1, padding=1)
    else:
        x = C.conv(x, params["stem.conv"], precision, stride=2, padding=3)
    x = jax.nn.relu(C.batch_norm(
        x, params["stem.bn.scale"], params["stem.bn.bias"], precision))
    if arch["stem"] != "cifar":
        x = C.max_pool(x, 3, 2, padding=1)
    for name, cin, mid, cout, stride in _blocks(arch):
        def bn(y, which):
            return C.batch_norm(y, params[f"{name}.{which}.scale"],
                                params[f"{name}.{which}.bias"], precision)
        y = C.conv(x, params[f"{name}.conv1"], precision, padding=0)
        y = jax.nn.relu(bn(y, "bn1"))
        y = C.conv(y, params[f"{name}.conv2"], precision, stride=stride)
        y = jax.nn.relu(bn(y, "bn2"))
        y = C.conv(y, params[f"{name}.conv3"], precision, padding=0)
        y = bn(y, "bn3")
        if f"{name}.proj.conv" in params:
            x = C.conv(x, params[f"{name}.proj.conv"], precision,
                       stride=stride, padding=0)
            x = bn(x, "proj.bn")
        x = C.hold(jax.nn.relu(y + x), precision)
    x = C.hold(jnp.mean(x.astype(jnp.float32), axis=(1, 2)), precision)
    logits = C.dense(x, params["head.kernel"], params["head.bias"], precision)
    return logits.astype(jnp.float32)


#: the output layer's leaves: their gradient sees the whole forward pass and
#: no backward pass (``chipbench/compare.py``, ``out_grad_diff``)
OUTPUT_LEAVES = ("head.kernel", "head.bias")


def program_names(arch) -> dict:
    """reference leaf name -> path of the same leaf in the program's
    parameter tree (flax's automatic names in ``tpu_ddp.models.resnet_family``:
    blocks numbered in order, ``Conv_3``/``BatchNorm_3`` the projection)."""
    names = {"stem.conv": ("stem_conv", "kernel"),
             "stem.bn.scale": ("stem_bn", "scale"),
             "stem.bn.bias": ("stem_bn", "bias"),
             "head.kernel": ("head", "kernel"), "head.bias": ("head", "bias")}
    for i, (name, *_rest) in enumerate(_blocks(arch)):
        block = f"_Bottleneck_{i}"
        for j, conv in enumerate(("conv1", "conv2", "conv3", "proj.conv")):
            names[f"{name}.{conv}"] = (block, f"Conv_{j}", "kernel")
        for j, bn in enumerate(("bn1", "bn2", "bn3", "proj.bn")):
            names[f"{name}.{bn}.scale"] = (block, f"BatchNorm_{j}", "scale")
            names[f"{name}.{bn}.bias"] = (block, f"BatchNorm_{j}", "bias")
    shapes = param_shapes(arch)
    return {k: v for k, v in names.items() if k in shapes}
