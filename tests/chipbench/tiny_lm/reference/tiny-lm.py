"""Plain reference for ``tiny-lm``: a learned-position pre-LN decoder (token
embedding + position table, ``depth`` blocks of LayerNorm, causal multi-head
attention, LayerNorm, GELU feed-forward, then LayerNorm and an untied
vocabulary head), trained on next-token prediction under AdamW.

It gives every optional function of a reference file
(``chipbench/reference/common.py::task``), because nothing of this task is
the default's: a batch is ``tokens`` (B, T) int32 with ``mask`` (B, T) bool
(False where a row is padding), the loss is over positions whose target is a
real token, the optimizer is not linear in the gradient, and an example's
FLOPs are a function of shapes. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common as C

#: optax.adamw's defaults, which the program's ``make_optimizer`` leaves alone
B1, B2, EPS = 0.9, 0.999, 1e-8
LN_EPS = 1e-6  # flax.linen.LayerNorm's default

#: the probe copies Adam's first moment out after step 1
OPTIMIZER_STATE = ("mu",)
OUTPUT_LEAVES = ("head.kernel", "head.bias")


def param_shapes(arch) -> dict:
    c, v, t = arch["hidden_dim"], arch["vocab_size"], arch["seq_len"]
    shapes = {"tok_embed": ((v, c), "normal"),
              "pos_embed": ((1, t, c), "normal")}
    for i in range(arch["depth"]):
        b = f"block_{i}."
        shapes.update({
            b + "ln1.scale": ((c,), "ones"), b + "ln1.bias": ((c,), "zeros"),
            b + "qkv.kernel": ((c, 3 * c), "lecun"),
            b + "qkv.bias": ((3 * c,), "zeros"),
            b + "proj.kernel": ((c, c), "lecun"),
            b + "proj.bias": ((c,), "zeros"),
            b + "ln2.scale": ((c,), "ones"), b + "ln2.bias": ((c,), "zeros"),
            b + "mlp_up.kernel": ((c, arch["mlp_ratio"] * c), "lecun"),
            b + "mlp_up.bias": ((arch["mlp_ratio"] * c,), "zeros"),
            b + "mlp_down.kernel": ((arch["mlp_ratio"] * c, c), "lecun"),
            b + "mlp_down.bias": ((c,), "zeros"),
        })
    shapes.update({"ln_f.scale": ((c,), "ones"), "ln_f.bias": ((c,), "zeros"),
                   "head.kernel": ((c, v), "lecun"),
                   "head.bias": ((v,), "zeros")})
    return shapes


def init_params(arch, seed: int) -> dict:
    shapes = param_shapes(arch)

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                std = 0.02 if kind == "normal" else 1 / math.sqrt(shape[0])
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.jit(make)(jax.random.key(seed))


def _layer_norm(x, scale, bias, precision):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return C.hold((x32 - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias,
                  precision)


def forward(arch, params, tokens, precision="float32_highest"):
    """Logits (B, T, vocab) in float32."""
    prec = C.PRECISIONS[precision][2]
    heads = arch["num_heads"]
    x = C.hold(params["tok_embed"][tokens] + params["pos_embed"], precision)
    b, t, c = x.shape
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(arch["depth"]):
        p = {k.split(".", 1)[1]: v for k, v in params.items()
             if k.startswith(f"block_{i}.")}
        y = _layer_norm(x, p["ln1.scale"], p["ln1.bias"], precision)
        qkv = C.dense(y, p["qkv.kernel"], p["qkv.bias"], precision)
        q, k, v = (a.reshape(b, t, heads, c // heads)
                   for a in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(causal, scores / math.sqrt(c // heads), -jnp.inf)
        weights = C.hold(jax.nn.softmax(scores, axis=-1), precision)
        o = C.hold(jnp.einsum("bhqk,bkhd->bqhd", weights, v, precision=prec),
                   precision)
        x = x + C.dense(o.reshape(b, t, c), p["proj.kernel"], p["proj.bias"],
                        precision)
        y = _layer_norm(x, p["ln2.scale"], p["ln2.bias"], precision)
        h = jax.nn.gelu(C.dense(y, p["mlp_up.kernel"], p["mlp_up.bias"],
                                precision), approximate=True)
        x = x + C.dense(h, p["mlp_down.kernel"], p["mlp_down.bias"],
                        precision)
    x = _layer_norm(x, params["ln_f.scale"], params["ln_f.bias"], precision)
    return C.dense(x, params["head.kernel"], params["head.bias"],
                   precision).astype(jnp.float32)


def program_names(arch) -> dict:
    """reference leaf -> path in ``tpu_ddp.models.lm.CausalTransformerLM``."""
    names = {"tok_embed": ("tok_embed", "embedding"),
             "pos_embed": ("pos_embed",)}
    for leaf in param_shapes(arch):
        if "." in leaf:
            *module, last = leaf.split(".")
            if module[-1] in ("qkv", "proj"):
                module.insert(-1, "attn")
            names[leaf] = tuple(module) + (last,)
    return names


def next_token_loss(logits, tokens, mask):
    """Mean negative log-likelihood of token t+1 at position t, over the
    positions whose target is a real token."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    w = mask[:, 1:].astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def follow(arch, check, *, shards, optimizer, precision):
    """AdamW over ``check["batches"]`` as fed: every shard's loss is its own
    mean, the gradient the mean of the shards' (data parallel). Decoupled
    weight decay on the leaves of two or more axes, as the program masks it.
    Also returns Adam's first moment after the first step."""
    if optimizer["name"] != "adamw":
        raise ValueError(f"tiny-lm follows adamw, not {optimizer['name']}")
    lr, decay = optimizer["lr"], optimizer["weight_decay"]

    def shard_loss(p, tokens, mask):
        return next_token_loss(forward(arch, p, tokens, precision), tokens,
                               mask)

    grad_fn = jax.jit(jax.value_and_grad(shard_loss))
    params = {k: jnp.asarray(v, jnp.float32)
              for k, v in check["params0"].items()}
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = dict(mu)
    losses, after_first, mu_first = [], None, None
    with jax.default_matmul_precision("highest"):
        for step, batch in enumerate(check["batches"], start=1):
            n = batch["tokens"].shape[0] // shards
            loss_sum, grads = 0.0, {k: 0.0 for k in params}
            for d in range(shards):
                rows = slice(d * n, (d + 1) * n)
                loss, g = grad_fn(params, jnp.asarray(batch["tokens"][rows]),
                                  jnp.asarray(batch["mask"][rows]))
                loss_sum += float(loss)
                grads = {k: grads[k] + g[k].astype(jnp.float32) / shards
                         for k in g}
            mu = {k: B1 * mu[k] + (1 - B1) * grads[k] for k in grads}
            nu = {k: B2 * nu[k] + (1 - B2) * jnp.square(grads[k])
                  for k in grads}
            for k in params:
                update = (mu[k] / (1 - B1 ** step)) / (
                    jnp.sqrt(nu[k] / (1 - B2 ** step)) + EPS)
                if params[k].ndim >= 2:
                    update = update + decay * params[k]
                params[k] = params[k] - lr * update
            if after_first is None:
                after_first, mu_first = dict(params), dict(mu)
            losses.append(loss_sum / shards)
    return {"losses": losses, "params_after_first": after_first,
            "params": params, "state_after_first": {"mu": mu_first}}


def first_gradient(optimizer, params0, params1, state1) -> dict:
    """Adam's update is the gradient's sign at step 1, so ``(p0 - p1) / lr``
    says nothing; its first moment after one step is ``(1 - B1) * g``."""
    del optimizer, params0, params1
    return {k: np.asarray(v, np.float64) / (1 - B1)
            for k, v in state1["mu"].items()}


def rows(batch):
    return batch["tokens"]


def batches(data, *, rows, steps):
    tokens, mask = data
    return [{"tokens": tokens[i * rows:(i + 1) * rows],
             "mask": mask[i * rows:(i + 1) * rows]} for i in range(steps)]


def train_flops_per_example(arch, traffic) -> float:
    """Required FLOPs of one sequence, from shapes: two a multiply-accumulate,
    three passes. Per token each block's four projections (12 c^2 with
    ``mlp_ratio`` 4) and the head; per sequence the causal half of the two
    attention products; the embedding is a lookup."""
    c, t = arch["hidden_dim"], int(traffic["dataset"]["seq_len"])
    per_token = arch["depth"] * (4 + 2 * arch["mlp_ratio"]) * c * c + (
        c * arch["vocab_size"])
    attention = arch["depth"] * 2 * c * t * (t + 1) // 2
    return 3.0 * 2.0 * (t * per_token + attention)
