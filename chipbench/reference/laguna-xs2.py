"""Plain reference for ``laguna-xs2``: poolside's Laguna-XS.2 decoder
(``https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json``), the
forward pass in ``jax.numpy``, float32 at ``highest``, trained on next-token
prediction under AdamW. Imports nothing of the program.

The model, as the ``config`` gives it. Pre-norm decoder layers on a hidden
size of 2048, RMSNorm (eps 1e-6), no biases, embedding and head untied:

    y = x + Attn(RMSNorm(x));  z = y + FFN(RMSNorm(y));  head(RMSNorm(z_last))

*Attention.* Heads of 128, 8 key-value heads in every layer, 48 query heads
in a ``full_attention`` layer and 64 in a ``sliding_attention`` one
(``num_attention_heads_per_layer``); a group of ``heads / 8`` query heads
shares one key-value head. A sliding layer is causal over the last 512
positions and rotates all 128 dimensions of a head (theta 10,000). A full
layer is causal over everything and rotates the first 64 dimensions only
(theta 500,000, YaRN: factor 64 from 4,096 positions, ``beta_fast`` 64,
``beta_slow`` 1, cos and sin times 1.4158883). ``gating``: an output gate
on attention.

*Feed-forward.* Layer 0 is a dense SwiGLU of width 8,192. Every later layer
is sparse: a router of 256 outputs, 8 experts a token, each a SwiGLU of
width 512, the weighted sum of the eight times 2.5, plus one shared SwiGLU
of width 512 for every token.

**What the config does not settle** (the configuration file lists the same
under ``assumed``; each is one line below to change):

1. ``gating``: head-wise, ``o_h = sigmoid(RMSNorm(x) w_h) * attn_h``, one
   column of a ``hidden x heads`` matrix a head, before the output
   projection (``_attention``). An element-wise gate would add 0.53 B
   parameters and miss the published 33.4 B.
2. Router: sigmoid of float32 logits, the eight largest, normalised to sum
   to one, times 2.5; no selection bias, no groups (``_route``).
3. No normalisation of queries and keys, no gate on the shared expert, no
   auxiliary loss.
4. The window's edge: position ``i`` sees ``j`` with ``i - 512 < j <= i``
   (``_visible``).
5. YaRN as in arXiv:2309.00071 over the 64 rotary dimensions, computed
   once for all lengths (``rotary_tables``).
6. AdamW as the program's ``make_optimizer`` builds it (``follow``).

**The cut** (``model-configs`` guide, section 4). This chip is one of the
chips that share each layer: the file's ``num_experts`` counts the routed
experts held here (ids ``expert_offset`` and up) of the
``published.num_experts`` the router scores, and ``vocab_size`` the rows of
the embedding and the head held here; ``layers_here`` counts the leading
layers of the published ``num_hidden_layers`` that run here. The router scores every published
expert; the layer adds what its own experts give for the tokens
routed to them and the shared expert, and hands that partial result on.
What the absent experts would have added is left out, here as in the
program. ``forward(..., share=(offset, held))`` computes another share of
the same weights, which is what the share test adds up.

Attention runs in blocks of query rows (64 heads x 8192 x 8192 scores in
float32 are 17 GB), the held experts as a plain loop over experts, each
over every token with the weight zero where the token was not routed to it.
``train_flops_per_example`` is therefore a function of shapes: a count of
this file's jaxpr would count every held expert for every token.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import common as C

#: optax.adamw's defaults, which the program's ``make_optimizer`` leaves alone
B1, B2, EPS = 0.9, 0.999, 1e-8

#: the probe copies Adam's first moment out after step 1
OPTIMIZER_STATE = ("mu",)
OUTPUT_LEAVES = ("head",)
#: query rows of one attention block, at most
Q_BLOCK = 128


# -- sizes -------------------------------------------------------------------

def routed_experts(arch) -> int:
    """Outputs of the router: the published count of routed experts."""
    return arch["published"]["num_experts"]


def layer_kinds(arch):
    """[(attention kind, query heads, feed-forward kind)] of the layers run."""
    n = arch["layers_here"]
    return list(zip(arch["layer_types"][:n],
                    arch["num_attention_heads_per_layer"][:n],
                    arch["mlp_layer_types"][:n]))


def param_shapes(arch) -> dict:
    """leaf -> (shape, kind of init)."""
    c, d, kv = arch["hidden_size"], arch["head_dim"], arch["num_key_value_heads"]
    e, f = arch["num_experts"], arch["moe_intermediate_size"]
    shapes = {"embed": ((arch["vocab_size"], c), "unit")}
    for i, (_, heads, ffn) in enumerate(layer_kinds(arch)):
        p = f"layer_{i}."
        shapes.update({
            p + "attn_norm": ((c,), "ones"),
            p + "attn.q": ((c, heads * d), "lecun"),
            p + "attn.k": ((c, kv * d), "lecun"),
            p + "attn.v": ((c, kv * d), "lecun"),
            p + "attn.gate": ((c, heads), "lecun"),
            p + "attn.o": ((heads * d, c), "lecun"),
            p + "mlp_norm": ((c,), "ones"),
        })
        if ffn == "dense":
            w = arch["intermediate_size"]
            shapes.update({p + "mlp.gate": ((c, w), "lecun"),
                           p + "mlp.up": ((c, w), "lecun"),
                           p + "mlp.down": ((w, c), "lecun")})
        else:
            s = arch["shared_expert_intermediate_size"]
            shapes.update({
                p + "moe.router": ((c, routed_experts(arch)), "lecun"),
                p + "moe.w_gate": ((e, c, f), "lecun_stacked"),
                p + "moe.w_up": ((e, c, f), "lecun_stacked"),
                p + "moe.w_down": ((e, f, c), "lecun_stacked"),
                p + "moe.shared.gate": ((c, s), "lecun"),
                p + "moe.shared.up": ((c, s), "lecun"),
                p + "moe.shared.down": ((s, c), "lecun"),
            })
    shapes.update({"final_norm": ((c,), "ones"),
                   "head": ((c, arch["vocab_size"]), "lecun")})
    return shapes


def init_params(arch, seed: int) -> dict:
    """Seeded float32 weights, one jitted call: the embedding N(0, 1), every
    matrix N(0, 1 / fan-in) (a stacked expert's fan-in is its own), norm
    scales 1. The residual stream then stays of order one through the cut
    stack and the router's logits are of order one, as trained ones are."""
    shapes = param_shapes(arch)

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            fan_in = {"unit": 1, "lecun": shape[0],
                      "lecun_stacked": shape[-2]}[kind]
            out[name] = jax.random.normal(k, shape, jnp.float32) / math.sqrt(
                fan_in)
        return out

    return jax.jit(make)(jax.random.key(seed))


def program_names(arch) -> dict:
    """reference leaf -> path in ``tpu_ddp.models.decoder.SparseDecoder``."""
    names = {}
    for leaf in param_shapes(arch):
        path = tuple(leaf.split("."))
        if leaf == "embed":
            names[leaf] = ("embed", "embedding")
        elif path[-1].endswith("norm"):
            names[leaf] = path + ("scale",)
        elif path[-1].startswith("w_"):
            names[leaf] = path           # stacked expert weights are bare
        else:
            names[leaf] = path + ("kernel",)
    return names


# -- layers ------------------------------------------------------------------

def _dot(x, w, precision):
    """A contraction without bias, by ``common``'s precisions."""
    return C._contracted(jnp.dot(C._operand(x, precision),
                                 C._operand(w, precision),
                                 precision=C.PRECISIONS[precision][2]),
                         precision)


def rms_norm(x, scale, eps, precision):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                        + eps)
    return C.hold(y * scale, precision)


def swiglu(x, gate, up, down, precision):
    h = jax.nn.silu(_dot(x, gate, precision)) * _dot(x, up, precision)
    return _dot(h, down, precision)


def rotary_tables(rope: dict, head_dim: int, length: int):
    """(cos, sin), each ``(length, rotary dims / 2)`` float32, and the number
    of rotary dimensions, from one entry of the config's ``rope_parameters``.
    ``default``: ``theta ** (-2i / dims)``. ``yarn`` (arXiv:2309.00071):
    each frequency is blended between itself and itself over ``factor`` by a
    linear ramp between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over the original context, and cos and sin are scaled
    by ``attention_factor``. Computed once, for every length (assumed 5)."""
    dims = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    exponents = np.arange(0, dims, 2, dtype=np.float64) / dims
    inv_freq = rope["rope_theta"] ** -exponents
    scale = 1.0
    if rope["rope_type"] == "yarn":
        original = rope["original_max_position_embeddings"]

        def turns_at(rotations):  # the dimension that turns this often
            return dims * math.log(original / (rotations * 2 * math.pi)) / (
                2 * math.log(rope["rope_theta"]))

        low = max(math.floor(turns_at(rope["beta_fast"])), 0)
        high = min(math.ceil(turns_at(rope["beta_slow"])), dims - 1)
        ramp = np.clip((np.arange(dims // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        inv_freq = (inv_freq / rope["factor"]) * ramp + inv_freq * (1 - ramp)
        scale = rope["attention_factor"]
    elif rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    angles = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(angles) * scale, jnp.float32),
            jnp.asarray(np.sin(angles) * scale, jnp.float32), dims)


def rotate(x, cos, sin, dims):
    """``x`` (B, T, H, D) with its first ``dims`` dimensions rotated, half
    against half (``x * cos + rotate_half(x) * sin``), the rest passed."""
    x32 = x.astype(jnp.float32)
    a, b, rest = (x32[..., :dims // 2], x32[..., dims // 2:dims],
                  x32[..., dims:])
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1).astype(x.dtype)


def _visible(rows, cols, window):
    """Position ``i`` sees ``j <= i`` and, under a window, ``j > i - window``
    (assumed 4)."""
    vis = cols[None, :] <= rows[:, None]
    if window:
        vis = jnp.logical_and(vis, cols[None, :] > rows[:, None] - window)
    return vis


def blocked_attention(q, k, v, window, precision):
    """Causal (and windowed) grouped-query attention, (B, T, H, D) queries
    against (B, T, KV, D) keys and values, a block of query rows at a time
    against the keys it can see: under a window the ``block + window - 1``
    positions that end with the block, else all of them (masked above the
    diagonal). One loop over blocks, each recomputed in the backward pass,
    so one block's scores are all that is ever held."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    prec = C.PRECISIONS[precision][2]
    rows_per = max(n for n in range(1, min(Q_BLOCK, t) + 1) if t % n == 0)
    blocks = t // rows_per
    reach = window - 1 if window else 0  # positions before a block it sees
    span = rows_per + reach if window else t
    if window:
        k, v = (jnp.pad(x, ((0, 0), (reach, 0), (0, 0), (0, 0)))
                for x in (k, v))
    q = jnp.moveaxis(q.reshape(b, blocks, rows_per, kv, h // kv, d), 1, 0)

    @jax.checkpoint
    def block(args):
        qb, i = args
        first = i * rows_per if window else 0  # in the padded keys
        kb, vb = (lax.dynamic_slice_in_dim(x, first, span, axis=1)
                  for x in (k, v))
        rows = i * rows_per + jnp.arange(rows_per)
        cols = first - reach + jnp.arange(span)
        s = jnp.einsum("bqkgd,bskd->bkgqs", C._operand(qb, precision),
                       C._operand(kb, precision), precision=prec,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        vis = jnp.logical_and(_visible(rows, cols, window), cols >= 0)
        p = C.hold(jax.nn.softmax(jnp.where(vis, s, -jnp.inf), axis=-1),
                   precision)
        return C._contracted(jnp.einsum(
            "bkgqs,bskd->bqkgd", C._operand(p, precision),
            C._operand(vb, precision), precision=prec), precision)

    out = lax.map(block, (q, jnp.arange(blocks)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, d)


def _attention(arch, p, x, kind, heads, tables, precision):
    b, t, _ = x.shape
    d, kv = arch["head_dim"], arch["num_key_value_heads"]
    cos, sin, dims = tables[kind]
    q = rotate(_dot(x, p["attn.q"], precision).reshape(b, t, heads, d),
               cos[:t], sin[:t], dims)
    k = rotate(_dot(x, p["attn.k"], precision).reshape(b, t, kv, d),
               cos[:t], sin[:t], dims)
    v = _dot(x, p["attn.v"], precision).reshape(b, t, kv, d)
    window = arch["sliding_window"] if kind == "sliding_attention" else 0
    o = blocked_attention(q, k, v, window, precision)
    gate = jax.nn.sigmoid(_dot(x, p["attn.gate"], precision))  # (assumed 1)
    o = o * gate[..., None].astype(o.dtype)
    return _dot(o.reshape(b, t, heads * d), p["attn.o"], precision)


def _route(arch, x, router):
    """(weights (B, T, k) float32, expert ids (B, T, k)) (assumed 2): in
    float32 whatever the precision, as the configuration states."""
    logits = jnp.dot(x.astype(jnp.float32), router,
                     precision=lax.Precision.HIGHEST)
    scores, ids = lax.top_k(jax.nn.sigmoid(logits),
                            arch["num_experts_per_tok"])
    weights = scores / jnp.sum(scores, axis=-1, keepdims=True)
    return weights * arch["moe_routed_scaling_factor"], ids


def _moe(arch, p, x, share, precision, taps=None):
    """The share's part of the sparse layer: its experts' weighted outputs
    for the tokens routed to them, and the shared expert."""
    offset, held = share
    weights, ids = _route(arch, x, p["moe.router"])
    if taps is not None:
        taps.append(ids)

    @jax.checkpoint
    def one_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        y = swiglu(x, w_gate, w_up, w_down, precision)
        return out + w[..., None] * y.astype(jnp.float32), None

    experts = (offset + jnp.arange(held), p["moe.w_gate"], p["moe.w_up"],
               p["moe.w_down"])
    routed, _ = lax.scan(one_expert, jnp.zeros(x.shape, jnp.float32), experts)
    shared = swiglu(x, p["moe.shared.gate"], p["moe.shared.up"],
                    p["moe.shared.down"], precision)
    return C.hold(routed, precision) + shared


def forward(arch, params, tokens, precision="float32_highest", *, share=None,
            taps=None):
    """Logits (B, T, vocab rows held) in float32. ``share`` is ``(offset,
    held)`` of the routed experts, the configuration's own by default;
    ``taps`` (a list) collects each sparse layer's expert ids."""
    share = share or (arch.get("expert_offset", 0), arch["num_experts"])
    eps = arch["rms_norm_eps"]
    t = tokens.shape[1]
    tables = {kind: rotary_tables(rope, arch["head_dim"], t)
              for kind, rope in arch["rope_parameters"].items()
              if isinstance(rope, dict)}
    x = C.hold(params["embed"][tokens], precision)

    def layer(x, p, kind, heads, ffn):
        y = x + _attention(arch, p, rms_norm(x, p["attn_norm"], eps, precision),
                           kind, heads, tables, precision)
        h = rms_norm(y, p["mlp_norm"], eps, precision)
        if ffn == "dense":
            return y + swiglu(h, p["mlp.gate"], p["mlp.up"], p["mlp.down"],
                              precision)
        return y + _moe(arch, p, h, share, precision, taps)

    for i, (kind, heads, ffn) in enumerate(layer_kinds(arch)):
        p = {k.split(".", 1)[1]: v for k, v in params.items()
             if k.startswith(f"layer_{i}.")}
        step = layer if taps is not None else jax.checkpoint(
            layer, static_argnums=(2, 3, 4))
        x = step(x, p, kind, heads, ffn)
    x = rms_norm(x, params["final_norm"], eps, precision)
    return _dot(x, params["head"], precision).astype(jnp.float32)


# -- the task and the optimizer ----------------------------------------------

def next_token_loss(logits, tokens, mask):
    """Mean negative log-likelihood of token t+1 at position t over the
    positions whose target is a real token, in float32."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    w = mask[:, 1:].astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def target_mask(batch):
    """(B, T) bool: which tokens are real targets; a row the loader padded
    the epoch's last batch with (``mask`` False) has none."""
    return np.logical_and(batch["loss_mask"], batch["mask"][:, None])


def _host_gb() -> str:
    """This process's resident set now, for the lines that say what the
    check costs on the host. On the chip's machine it counts 13.6 GB that
    appear when the TPU runtime starts and are not the host's memory: a run
    whose resident set read 48.2 GB stayed inside the machine's 40 GiB (my
    chip run, PR 27, call 10)."""
    try:
        with open("/proc/self/status") as f:
            kb = next(int(line.split()[1]) for line in f
                      if line.startswith("VmRSS:"))
    except (OSError, StopIteration):
        return "resident set unknown"
    return f"resident set {kb / 1e6:.1f} GB"


class _HandedOver(dict):
    """Leaves that live on the device and come to the host one at a time: a
    leaf read is taken out, so that its device buffer and the host copy jax
    keeps beside it go when the reader is done with it. The harness reads
    each leaf once, for its float64 difference; a whole second host copy of
    the weights is 2.8 GB of the one-chip machine's 40 GiB."""

    def __getitem__(self, key):
        return np.asarray(self.pop(key))


def follow(arch, check, *, shards, optimizer, precision):
    """AdamW over ``check["batches"]`` as fed (``tokens``, ``loss_mask``,
    the loader's row ``mask``): every shard's loss is its own mean, the
    gradient the mean of the shards'. Decoupled weight decay on the leaves of
    two or more axes, as the program masks it. Also returns Adam's first
    moment after the first step.

    Where things live: the weights, one set of gradients and both moments on
    the device; on the host the first moment after step 1, which is handed
    back, and the weights after the last step one leaf at a time
    (``_HandedOver``). That is beside the harness's own four host copies of
    the program's state and its two float64 updates."""
    if optimizer["name"] != "adamw":
        raise ValueError(f"laguna-xs2 follows adamw, not {optimizer['name']}")
    lr, decay = optimizer["lr"], optimizer["weight_decay"]

    def shard_loss(p, tokens, mask):
        return next_token_loss(forward(arch, p, tokens, precision), tokens,
                               mask)

    grad_fn = jax.jit(jax.value_and_grad(shard_loss))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adamw(p, g, mu, nu, step):
        mu = B1 * mu + (1 - B1) * g
        nu = B2 * nu + (1 - B2) * jnp.square(g)
        update = (mu / (1 - B1 ** step)) / (
            jnp.sqrt(nu / (1 - B2 ** step)) + EPS)
        if p.ndim >= 2:
            update = update + decay * p
        return p - lr * update, mu, nu

    print(f"chipbench: reference: follow({precision}) starts, {_host_gb()}",
          flush=True)
    params = {k: jnp.asarray(v, jnp.float32)
              for k, v in check["params0"].items()}
    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, mu_first = [], None
    with jax.default_matmul_precision("highest"):
        for step, batch in enumerate(check["batches"], start=1):
            n = batch["tokens"].shape[0] // shards
            mask = target_mask(batch)
            loss_sum, grads = 0.0, None
            for d in range(shards):
                rows = slice(d * n, (d + 1) * n)
                loss, g = grad_fn(params, jnp.asarray(batch["tokens"][rows]),
                                  jnp.asarray(mask[rows]))
                loss_sum += float(loss)
                grads = g if grads is None else {
                    k: grads[k] + g[k] for k in g}
            del g
            for k in params:
                params[k], mu[k], nu[k] = adamw(
                    params[k], grads.pop(k) / shards, mu[k], nu[k],
                    float(step))
            if mu_first is None:
                # through a copy on the device, so that the host copy jax
                # keeps beside an array it has fetched goes with the copy
                # and not, a step later, with the donated moment
                mu_first = {k: np.asarray(jnp.array(v, copy=True))
                            for k, v in mu.items()}
            losses.append(loss_sum / shards)
            print(f"chipbench: reference: step {step} loss {losses[-1]!r}, "
                  f"{_host_gb()}", flush=True)
    # ``first_gradient`` reads Adam's first moment, not the weights after
    # the first step: no copy of them is kept
    del mu, nu
    return {"losses": losses, "params_after_first": None,
            "params": _HandedOver(params),
            "state_after_first": {"mu": mu_first}}


def first_gradient(optimizer, params0, params1, state1) -> dict:
    """Adam's update is the gradient's sign at step 1, so ``(p0 - p1) / lr``
    says nothing; its first moment after one step is ``(1 - B1) * g``. The
    moment is handed on as it is, factor and all: every number compared is a
    ratio of the program's to the reference's, read by this one rule on both
    sides, and a copy of the gradient is 2.8 GB of host memory a side."""
    del optimizer, params0, params1
    return state1["mu"]


def rows(batch):
    return batch["tokens"]


def batches(data, *, rows, steps):
    tokens, mask = data
    return [{"tokens": tokens[i * rows:(i + 1) * rows],
             "loss_mask": mask[i * rows:(i + 1) * rows],
             "mask": np.ones(rows, bool)} for i in range(steps)]


# -- required work, from shapes ----------------------------------------------

def visible_pairs(t: int, window: int) -> int:
    """(query, key) pairs one head computes over ``t`` positions."""
    if not window or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def forward_macs_by_part(arch, t: int) -> dict:
    """Multiply-accumulates of one forward pass over one sequence of ``t``
    tokens, by part. Visible pairs counted exactly; routed work as
    ``num_experts_per_tok * held / published`` experts a token and layer, which is what a router that favours no expert sends here; the
    embedding is a lookup."""
    c, d, kv = arch["hidden_size"], arch["head_dim"], arch["num_key_value_heads"]
    routed_share = (arch["num_experts_per_tok"] * arch["num_experts"]
                    / routed_experts(arch))
    parts = dict.fromkeys(("projections", "attention", "dense", "routed",
                           "shared_and_router", "head"), 0.0)
    for kind, heads, ffn in layer_kinds(arch):
        parts["projections"] += t * c * (2 * heads * d + 2 * kv * d + heads)
        window = arch["sliding_window"] if kind == "sliding_attention" else 0
        parts["attention"] += heads * visible_pairs(t, window) * 2 * d
        if ffn == "dense":
            parts["dense"] += t * 3 * c * arch["intermediate_size"]
        else:
            parts["routed"] += (t * routed_share * 3 * c
                                * arch["moe_intermediate_size"])
            parts["shared_and_router"] += t * c * (
                3 * arch["shared_expert_intermediate_size"]
                + routed_experts(arch))
    parts["head"] = t * c * arch["vocab_size"]
    return parts


def train_flops_per_example(arch, traffic) -> float:
    """Required FLOPs of training on one sequence: two a multiply-accumulate,
    three passes (forward, backward by input, backward by weight); no
    recomputation counted."""
    t = int(traffic["dataset"]["seq_len"])
    return 3.0 * 2.0 * sum(forward_macs_by_part(arch, t).values())
