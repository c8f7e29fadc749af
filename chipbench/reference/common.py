"""What the plain references share: precisions, primitive layers, the loss,
SGD, the three-step reading that ``correct`` compares, and the defaults of
what a configuration's reference file may say of its task and its optimizer
(``task``, at the end).

Plain ``jax.numpy`` in float32 under ``highest`` matmul precision. Imports
nothing of ``tpu_ddp``. A *precision* is data (a name from ``PRECISIONS``): the
same reference computed one notch lower is the control that ``correct`` has to
fail (PERF.md section 2), so the lower precisions live here as parameters of
the same code and not as a second code path.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: name -> (array dtype, float8 operands?, matmul precision). Where the float8
#: flag is set the reference is computed as a float8 training recipe computes
#: it: arrays in bfloat16, every contraction's two operands rounded to e4m3
#: (scaled per tensor) and the cotangent of its output, the operand of both
#: backward contractions, rounded to ``COTANGENT_DTYPE`` (e5m2), the usual
#: pairing. Nothing else is rounded: this is the step a later PR would take,
#: so it is the one ``correct`` has to fail.
PRECISIONS = {
    "float32_highest": (jnp.float32, False, lax.Precision.HIGHEST),
    "bfloat16": (jnp.bfloat16, False, lax.Precision.DEFAULT),
    "float8": (jnp.bfloat16, True, lax.Precision.DEFAULT),
}
OPERAND_DTYPE = jnp.float8_e4m3fn
COTANGENT_DTYPE = jnp.float8_e5m2

#: the nearest precision below the one a configuration states
ONE_NOTCH_LOWER = {
    "float32": "bfloat16",
    "bfloat16": "float8",
}


def _round_to(x, qdtype):
    """Per-tensor scaled cast to ``qdtype`` and back."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.where(amax > 0, float(jnp.finfo(qdtype).max) / amax, 1.0)
    q = (x.astype(jnp.float32) * scale).astype(qdtype).astype(jnp.float32)
    return (q / scale).astype(x.dtype)


@jax.custom_vjp
def _quantised_cotangent(y):
    """Identity whose cotangent is rounded to ``COTANGENT_DTYPE``."""
    return y


_quantised_cotangent.defvjp(
    lambda y: (y, None), lambda _, g: (_round_to(g, COTANGENT_DTYPE),))


def hold(x, precision):
    """``x`` in the precision's array type."""
    return x.astype(PRECISIONS[precision][0])


def _operand(x, precision):
    """``x`` as a contraction takes it: held, and for float8 rounded to
    e4m3 (straight-through)."""
    x = hold(x, precision)
    if not PRECISIONS[precision][1]:
        return x
    return x + lax.stop_gradient(_round_to(x, OPERAND_DTYPE) - x)


def _contracted(y, precision):
    """A contraction's output: for float8 its cotangent is rounded on the
    way back, before the two backward contractions take it."""
    y = hold(y, precision)
    return _quantised_cotangent(y) if PRECISIONS[precision][1] else y


def conv(x, w, precision, *, stride=1, padding=1):
    """NHWC x HWIO convolution."""
    prec = PRECISIONS[precision][2]
    return _contracted(lax.conv_general_dilated(
        _operand(x, precision), _operand(w, precision), (stride, stride),
        [(padding, padding)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec),
        precision)


def dense(x, w, b, precision):
    prec = PRECISIONS[precision][2]
    y = _contracted(jnp.dot(_operand(x, precision), _operand(w, precision),
                            precision=prec), precision)
    return y + b.astype(y.dtype)


def batch_norm(x, scale, bias, precision, eps=1e-5):
    """Training-mode batch normalisation over (N, H, W): the batch's own mean
    and biased variance, computed in float32 whatever ``x``'s type."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x32 - mean), axis=(0, 1, 2))
    y = (x32 - mean) * lax.rsqrt(var + eps) * scale + bias
    return hold(y, precision)


def max_pool(x, window, stride, padding=0):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1), (1, stride, stride, 1),
        [(0, 0), (padding, padding), (padding, padding), (0, 0)])


def cross_entropy(logits, labels, mask):
    """Mean negative log-likelihood over the unmasked rows."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


def three_steps(forward, params, batches, *, shards, lr, momentum,
                precision="float32_highest"):
    """Follow ``len(batches)`` SGD steps of data-parallel training from
    ``params`` (flat dict of float32 arrays).

    Each batch is ``(images, labels, mask)`` in shard-major layout: shard d
    owns rows ``[d*n/shards, (d+1)*n/shards)``. As DistributedDataParallel
    does, every shard normalises by its own batch statistics, the loss is the
    mean of the shards' losses and the gradient the mean of their gradients.
    SGD with momentum: ``v = g + momentum*v; p = p - lr*v`` (no dampening, no
    Nesterov), ``momentum == 0`` is plain SGD.

    Returns the per-step losses and the float32 parameters after the first
    and after the last step. The first gradient is read from the first
    update, ``(p0 - p1) / lr``, for the reference as for the program: the
    float32 rounding of ``p1`` is then the same in both and cancels in their
    difference (read straight, it was most of the sound program's
    ``grad_diff``: 6e-8 |p| / lr against gradient entries of 1e-3).
    """
    def shard_loss(p, images, labels, mask):
        return cross_entropy(forward(p, images, precision), labels, mask)

    grad_fn = jax.jit(jax.value_and_grad(shard_loss))
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, after_first = [], None
    with jax.default_matmul_precision("highest"):
        for images, labels, mask in batches:
            n = images.shape[0] // shards
            loss_sum, grad_sum = 0.0, None
            for d in range(shards):
                rows = slice(d * n, (d + 1) * n)
                loss, grads = grad_fn(
                    params, jnp.asarray(images[rows], jnp.float32),
                    jnp.asarray(labels[rows]), jnp.asarray(mask[rows]))
                loss_sum += float(loss)
                grads = {k: g.astype(jnp.float32) for k, g in grads.items()}
                grad_sum = grads if grad_sum is None else {
                    k: grad_sum[k] + grads[k] for k in grads}
            grads = {k: g / shards for k, g in grad_sum.items()}
            velocity = {k: grads[k] + momentum * velocity[k] for k in grads}
            params = {k: params[k] - lr * velocity[k] for k in params}
            if after_first is None:
                after_first = params
            losses.append(loss_sum / shards)
    return {"losses": losses, "params_after_first": after_first,
            "params": params}


# -- the task and the optimizer: the reference file's own, or these ----------
#
# What ``correct`` and ``step_mfu`` need to know of a task (what a batch
# holds, what its loss is, which rows are examples, what an example costs) and
# of an optimizer (how it steps, where its first gradient can be read) they
# ask of the configuration's reference file. A file that gives none of the
# names below is an image classifier under SGD, the defaults here.

#: fields of the optimizer's state (optax names, e.g. ``("mu",)``) that the
#: probe copies out after the first step, as ``check["state1"]``
OPTIMIZER_STATE = ()


def optimizer_of(train_config: dict) -> dict:
    """Name and hyperparameters of the optimizer a configuration's
    ``train_config`` (with the mix's overlays) states; what it leaves out is
    the program's ``TrainConfig`` default."""
    return {"name": str(train_config.get("optimizer", "sgd")),
            "lr": float(train_config.get("lr", 1e-2)),
            "momentum": float(train_config.get("momentum", 0.0)),
            "weight_decay": float(train_config.get("weight_decay", 0.0))}


def default_follow(ref):
    def follow(arch, check, *, shards, optimizer, precision):
        """The reference's steps over ``check["batches"]`` as fed: here
        ``three_steps`` over ``image`` / ``label`` / ``mask`` under SGD."""
        if optimizer["name"] != "sgd" or optimizer["weight_decay"]:
            raise ValueError(
                f"the default follows plain SGD, not {optimizer}: the "
                "configuration's reference file gives its own follow()")
        fed = [(b["image"], b["label"], b["mask"]) for b in check["batches"]]
        return three_steps(
            lambda p, x, prec: ref.forward(arch, p, x, prec),
            check["params0"], fed, shards=shards, lr=optimizer["lr"],
            momentum=optimizer["momentum"], precision=precision)
    return follow


def first_gradient(optimizer, params0, params1, state1=None) -> dict:
    """The first step's gradient as the optimizer got it, leaf -> float64
    array; one rule for the program's side and the reference's. SGD's first
    update is linear in it: ``(p0 - p1) / lr`` (``three_steps`` says why it
    is read so)."""
    del state1
    if optimizer["name"] != "sgd":
        raise ValueError(
            f"(p0 - p1) / lr is not the gradient under {optimizer['name']}: "
            "the configuration's reference file gives its own "
            "first_gradient() and names the OPTIMIZER_STATE it reads")
    return {k: (np.asarray(params0[k]).astype("float64")
                - np.asarray(params1[k], "float64")) / optimizer["lr"]
            for k in params0}


def rows(batch):
    """The examples of one batch as fed, first axis the example: they must
    all differ over the checked steps."""
    return batch["image"]


def batches(data, *, rows, steps):
    """The first ``steps`` batches of ``rows`` examples of a training set as
    the program's loader would hand them to the step, for a control that is
    read without the program (``control.py --read control``)."""
    images, labels = data
    return [{"image": images[i * rows:(i + 1) * rows],
             "label": labels[i * rows:(i + 1) * rows],
             "mask": np.ones(rows, bool)} for i in range(steps)]


def default_train_flops_per_example(ref):
    def train_flops_per_example(arch, traffic) -> float:
        """Required FLOPs of training on one example, from shapes: here the
        contractions of the reference's forward jaxpr over one
        ``(1, image_size, image_size, channels)`` image, times three
        (``chipbench/flops.py``)."""
        from chipbench import flops

        del traffic
        shapes = {k: jax.ShapeDtypeStruct(s, jnp.float32)
                  for k, (s, _) in ref.param_shapes(arch).items()}
        side = arch["image_size"]
        image = jax.ShapeDtypeStruct(
            (1, side, side, arch.get("channels", 3)), jnp.float32)
        macs = flops.forward_macs(
            lambda p, x: ref.forward(arch, p, x), shapes, image)
        return flops.train_flops_per_image(macs)
    return train_flops_per_example


def task(ref) -> types.SimpleNamespace:
    """``follow``, ``first_gradient``, ``rows``, ``batches``,
    ``train_flops_per_example`` and ``OPTIMIZER_STATE`` of the reference
    module ``ref``: its own where it gives them, else the defaults above."""
    defaults = {
        "follow": default_follow(ref), "first_gradient": first_gradient,
        "rows": rows, "batches": batches,
        "train_flops_per_example": default_train_flops_per_example(ref),
        "OPTIMIZER_STATE": OPTIMIZER_STATE}
    return types.SimpleNamespace(**{
        name: getattr(ref, name, default)
        for name, default in defaults.items()})
