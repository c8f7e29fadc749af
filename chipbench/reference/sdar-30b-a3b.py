"""Plain reference for ``sdar-30b-a3b``: JetLM's SDAR-30B-A3B-Chat
(``https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json``,
``model_type`` ``sdar_moe``), the forward pass in ``jax.numpy``, float32 at
``highest``, trained by block diffusion under AdamW. Imports nothing of the
program.

The model, as the ``config`` gives it. 48 pre-norm decoder layers, all
alike, on a hidden size of 2048, RMSNorm (eps 1e-6), no biases, embedding
and head untied (u = RMSNorm(x)):

    h = x + W_o Attn(u);  y = h + MoE(RMSNorm(h));  W_head RMSNorm(z)

*Attention.* 32 query heads over 4 key-value heads of 128 (beside a hidden
size of 2048: the ``qwen3_moe`` layout); query head ``g`` reads key-value
head ``g // 8``. Queries and keys are normalised over a head's 128
dimensions (one learned scale each, shared by the heads) and then turned,
all 128 dimensions, half against half, theta 1e6, no scaling. No window, no
gate. Scores over ``sqrt(128)``.

*Feed-forward.* Every layer is sparse (``decoder_sparse_step`` 1,
``mlp_only_layers`` []): a router of 128 outputs, a float32 softmax over all
of them, the 8 largest renormalised to sum to one (``norm_topk_prob``), each
expert a SwiGLU of width 768; no scaling factor, no shared expert.
``intermediate_size`` 6144 is published and used by no layer.

*Block diffusion* (the vectorised objective of BD3-LMs, arXiv:2503.09573,
which SDAR's report follows). A sequence of L tokens in L / B blocks of
B = 4. For each row and block a level ``t_b ~ U(t_min, 1]``; a token of the
block is masked with probability ``t_b``: ``x~_i = MASK`` where masked, else
``x_i``. The model reads ``[x ‖ x~]``, 2L positions, position ``i`` at the
rotary angle of ``i mod L``. With ``b(i) = (i mod L) // B``, (i, j) is
visible iff (i clean, j clean, b(j) <= b(i)) or (i noisy, j clean,
b(j) < b(i)) or (i noisy, j noisy, b(j) = b(i)) (``visible``, from index
arithmetic). The final norm and the head run on the noisy half; position
``i`` predicts token ``i``, unshifted. The loss is the cross-entropy at the
masked real positions, each over its block's ``t``, summed and divided by
the count of real positions.

**The noise is drawn here, by this file's own lines.** The probe copies a
batch as it was fed, before the step noises it, and ``follow`` is not told
the run's seed. So ``init_params(arch, seed)`` keeps the seed it was called
with (the harness calls it with the folded ``--seed``, which it also gives
the program as ``TrainConfig.seed``; ``control.py`` calls it too; where the
mix draws the weights from a seed of its own, ``fixed_work``, ``run_seed``
is told the run's afterwards), and
``noise`` makes the draw of (that seed, the step's index from 0, the
shard): ``key(seed)`` folded with the step, the shard and 2, split in two;
levels ``1 - u (1 - t_min)`` from the first, a uniform a position from the
second, masked where it lies under the level. jax's threefry generator
gives the same bits on every platform.

**What the config does not settle** (the configuration file lists the same
under ``assumed``): the block length and the schedule above; the mask's id
(published inside the vocabulary; here the last row of the slice held); the
query and key norms and the half-against-half pairing (the ``qwen3_moe``
layout); no auxiliary loss; AdamW as the program's ``make_optimizer`` builds
it.

**The cut.** This chip is one of eight that share each layer: the file's
``num_experts`` counts the routed experts held here (ids ``expert_offset``
and up) of the ``published.num_experts`` the router scores, ``vocab_size``
the rows of embedding and head held here, ``layers_here`` the leading layers
that run here. What the absent experts would have added is left out, here
as in the program; ``forward(..., share=(offset, held))`` computes another
share of the same weights, which is what the share test adds up.

Attention runs in blocks of query rows, the clean rows against the clean
keys and the noisy rows against all (32 heads x 8192 x 8192 scores in
float32 are 8.6 GB), the held experts as a plain loop over experts, each
over every token with the weight zero where the token was not routed to it.
``train_flops_per_example`` is therefore a function of shapes.
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

#: the seed ``init_params`` was last called with: the noise's (module
#: docstring)
_SEED = None


# -- sizes -------------------------------------------------------------------

def routed_experts(arch) -> int:
    """Outputs of the router: the published count of routed experts."""
    return arch["published"]["num_experts"]


def param_shapes(arch) -> dict:
    """leaf -> (shape, kind of init)."""
    c, d = arch["hidden_size"], arch["head_dim"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    e, f = arch["num_experts"], arch["moe_intermediate_size"]
    shapes = {"embed": ((arch["vocab_size"], c), "unit")}
    for i in range(arch["layers_here"]):
        p = f"layer_{i}."
        shapes.update({
            p + "attn_norm": ((c,), "ones"),
            p + "attn.q": ((c, heads * d), "lecun"),
            p + "attn.k": ((c, kv * d), "lecun"),
            p + "attn.v": ((c, kv * d), "lecun"),
            p + "attn.q_norm": ((d,), "ones"),
            p + "attn.k_norm": ((d,), "ones"),
            p + "attn.o": ((heads * d, c), "lecun"),
            p + "mlp_norm": ((c,), "ones"),
            p + "moe.router": ((c, routed_experts(arch)), "lecun"),
            p + "moe.w_gate": ((e, c, f), "lecun_stacked"),
            p + "moe.w_up": ((e, c, f), "lecun_stacked"),
            p + "moe.w_down": ((e, f, c), "lecun_stacked"),
        })
    shapes.update({"final_norm": ((c,), "ones"),
                   "head": ((c, arch["vocab_size"]), "lecun")})
    return shapes


def init_params(arch, seed: int) -> dict:
    """Seeded float32 weights, one jitted call: the embedding N(0, 1), every
    matrix N(0, 1 / fan-in) (a stacked expert's fan-in is its own), norm
    scales 1. Keeps ``seed`` for ``noise`` (module docstring)."""
    global _SEED
    _SEED = int(seed)
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


def run_seed(seed: int) -> None:
    """The seed the program's step draws its noise by (``TrainConfig.seed``,
    the run's ``--seed``), where the weights were drawn from another (a mix
    with ``fixed_work``: ``datagen.tell_run_seed``)."""
    global _SEED
    _SEED = int(seed)


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


# -- the noise ---------------------------------------------------------------

def noise(arch, tokens, *, seed: int, step: int, shard: int):
    """(``[x ‖ x~]`` (n, 2L) int32, masked (n, L) bool, t (n, L) float32)
    of one shard's rows at one step (module docstring)."""
    block, t_min = arch["block_length"], arch["noise_t_min"]
    n, length = tokens.shape
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.key(seed), step), shard), 2)
    level_key, mask_key = jax.random.split(key)
    u = jax.random.uniform(level_key, (n, length // block), jnp.float32)
    t = jnp.repeat(1.0 - u * (1.0 - t_min), block, axis=1)
    masked = jax.random.uniform(mask_key, (n, length), jnp.float32) < t
    tokens = jnp.asarray(tokens)
    noisy = jnp.where(masked, jnp.int32(arch["mask_token_id"]), tokens)
    return jnp.concatenate([tokens, noisy], axis=1), masked, t


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


def rotary_tables(arch, length: int):
    """(cos, sin), each ``(length, head_dim / 2)`` float32: plain
    frequencies ``theta ** (-2i / head_dim)``, float64 on the host."""
    dims = arch["head_dim"]
    inv_freq = arch["rope_theta"] ** -(
        np.arange(0, dims, 2, dtype=np.float64) / dims)
    angles = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def rotate(x, cos, sin):
    """``x`` (B, T, H, D), every dimension turned, half against half."""
    x32 = x.astype(jnp.float32)
    a, b = jnp.split(x32, 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def visible(rows, cols, half: int, block: int):
    """(rows, cols) bool over positions of ``[clean ‖ noisy]``."""
    rows, cols = rows[:, None], cols[None, :]
    row_noisy, col_noisy = rows >= half, cols >= half
    rb, cb = rows % half // block, cols % half // block
    return ((~row_noisy & ~col_noisy & (cb <= rb))
            | (row_noisy & ~col_noisy & (cb < rb))
            | (row_noisy & col_noisy & (cb == rb)))


def blocked_attention(q, k, v, block: int, precision):
    """Grouped-query attention under the block-diffusion visibility, (B, 2L,
    H, D) queries against (B, 2L, KV, D) keys and values, a block of query
    rows at a time: the clean rows against the clean keys, the noisy rows
    against all of them. Each block is recomputed in the backward pass, so
    one block's scores are all that is ever held."""
    b, t, h, d = q.shape
    kv, half = k.shape[2], t // 2
    prec = C.PRECISIONS[precision][2]
    rows_per = max(n for n in range(1, min(Q_BLOCK, half) + 1)
                   if half % n == 0)

    def part(first_row, keys):
        """Rows ``first_row .. first_row + half`` against columns 0..keys."""
        kb, vb = k[:, :keys], v[:, :keys]
        qs = jnp.moveaxis(
            q[:, first_row:first_row + half].reshape(
                b, half // rows_per, rows_per, kv, h // kv, d), 1, 0)

        @jax.checkpoint
        def one(args):
            qb, i = args
            rows = first_row + i * rows_per + jnp.arange(rows_per)
            s = jnp.einsum("bqkgd,bskd->bkgqs", C._operand(qb, precision),
                           C._operand(kb, precision), precision=prec,
                           preferred_element_type=jnp.float32) / math.sqrt(d)
            vis = visible(rows, jnp.arange(keys), half, block)
            p = C.hold(jax.nn.softmax(jnp.where(vis, s, -jnp.inf), axis=-1),
                       precision)
            return C._contracted(jnp.einsum(
                "bkgqs,bskd->bqkgd", C._operand(p, precision),
                C._operand(vb, precision), precision=prec), precision)

        out = lax.map(one, (qs, jnp.arange(half // rows_per)))
        return jnp.moveaxis(out, 0, 1).reshape(b, half, h, d)

    return jnp.concatenate([part(0, half), part(half, t)], axis=1)


def _attention(arch, p, x, tables, precision):
    b, t, _ = x.shape
    d, kv = arch["head_dim"], arch["num_key_value_heads"]
    heads, eps = arch["num_attention_heads"], arch["rms_norm_eps"]
    cos, sin = tables
    q = rotate(rms_norm(_dot(x, p["attn.q"], precision).reshape(
        b, t, heads, d), p["attn.q_norm"], eps, precision), cos, sin)
    k = rotate(rms_norm(_dot(x, p["attn.k"], precision).reshape(
        b, t, kv, d), p["attn.k_norm"], eps, precision), cos, sin)
    v = _dot(x, p["attn.v"], precision).reshape(b, t, kv, d)
    o = blocked_attention(q, k, v, arch["block_length"], precision)
    return _dot(o.reshape(b, t, heads * d), p["attn.o"], precision)


def _route(arch, x, router):
    """(weights (B, T, k) float32, expert ids (B, T, k)): a float32 softmax
    over every published expert, the eight largest, renormalised."""
    logits = jnp.dot(x.astype(jnp.float32), router,
                     precision=lax.Precision.HIGHEST)
    scores, ids = lax.top_k(jax.nn.softmax(logits, axis=-1),
                            arch["num_experts_per_tok"])
    return scores / jnp.sum(scores, axis=-1, keepdims=True), ids


def _moe(arch, p, x, share, precision, taps=None):
    """The share's part of the sparse layer: its experts' weighted outputs
    for the tokens routed to them."""
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
    return C.hold(routed, precision)


def layer(arch, p, x, tables, share, precision, taps=None):
    eps = arch["rms_norm_eps"]
    y = x + _attention(arch, p, rms_norm(x, p["attn_norm"], eps, precision),
                       tables, precision)
    return y + _moe(arch, p, rms_norm(y, p["mlp_norm"], eps, precision),
                    share, precision, taps)


def forward(arch, params, tokens, precision="float32_highest", *, share=None,
            taps=None):
    """Float32 logits (B, L, vocab rows held) of the noisy half of
    ``tokens`` (B, 2L) = ``[x ‖ x~]``. ``share`` is ``(offset, held)`` of
    the routed experts, the configuration's own by default; ``taps`` (a
    list) collects each layer's expert ids."""
    share = share or (arch.get("expert_offset", 0), arch["num_experts"])
    half = tokens.shape[1] // 2
    if tokens.shape[1] != 2 * half or half % arch["block_length"]:
        raise ValueError(f"[clean, noisy] of whole blocks, not "
                         f"{tokens.shape[1]} positions")
    cos, sin = rotary_tables(arch, half)
    tables = (jnp.concatenate([cos, cos]), jnp.concatenate([sin, sin]))
    x = C.hold(params["embed"][tokens], precision)
    for i in range(arch["layers_here"]):
        p = {k.split(".", 1)[1]: v for k, v in params.items()
             if k.startswith(f"layer_{i}.")}
        step = functools.partial(layer, arch, taps=taps) if (
            taps is not None) else jax.checkpoint(
                functools.partial(layer, arch), static_argnums=(3, 4))
        x = step(p, x, tables, share, precision)
    x = rms_norm(x[:, half:], params["final_norm"], arch["rms_norm_eps"],
                 precision)
    return _dot(x, params["head"], precision).astype(jnp.float32)


# -- the task and the optimizer ----------------------------------------------

def diffusion_loss(logits, targets, masked, t, weights):
    """``sum(m * w * CE(logits_i, x_i) / t) / max(sum(w), 1)``, float32:
    ``w`` the real target positions."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    w = weights.astype(jnp.float32)
    return jnp.sum(masked.astype(jnp.float32) * w * nll / t) / jnp.maximum(
        jnp.sum(w), 1.0)


def sequence_loss(arch, params, tokens, mask, *, seed, step, shard,
                  precision="float32_highest"):
    """The loss of one shard's rows ``tokens`` (n, L) at one step, with the
    noise of (``seed``, ``step``, ``shard``)."""
    fed, masked, t = noise(arch, tokens, seed=seed, step=step, shard=shard)
    return diffusion_loss(forward(arch, params, fed, precision),
                          jnp.asarray(tokens), masked, t, mask)


def target_mask(batch):
    """(B, L) bool: which tokens are real targets; a row the loader padded
    the epoch's last batch with (``mask`` False) has none."""
    return np.logical_and(batch["loss_mask"], batch["mask"][:, None])


def _host_gb() -> str:
    """This process's resident set now (on the chip's machine it counts
    13.6 GB that appear when the TPU runtime starts and are not the
    host's: PERF.md section 6, PR 27)."""
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
    keeps beside it go when the reader is done with it."""

    def __getitem__(self, key):
        return np.asarray(self.pop(key))


def follow(arch, check, *, shards, optimizer, precision):
    """AdamW over ``check["batches"]`` as fed (``tokens``, ``loss_mask``,
    the loader's row ``mask``), each shard's rows noised by this file's own
    draw of (the seed ``init_params`` was called with, the step's index from
    0, the shard): every shard's loss is its own, the gradient the mean of
    the shards'. Decoupled weight decay on the leaves of two or more axes,
    as the program masks it. Also returns Adam's first moment after the
    first step. The weights, one set of gradients and both moments live on
    the device; the first moment after step 1 and, a leaf at a time, the
    weights after the last step come to the host."""
    if optimizer["name"] != "adamw":
        raise ValueError(
            f"sdar-30b-a3b follows adamw, not {optimizer['name']}")
    if _SEED is None:
        raise RuntimeError(
            "follow() draws the step's noise from the seed init_params() was "
            "called with, and it was not called in this process")
    lr, decay, seed = optimizer["lr"], optimizer["weight_decay"], _SEED

    @jax.jit
    def grad_fn(p, tokens, mask, step, shard):
        return jax.value_and_grad(sequence_loss, argnums=1)(
            arch, p, tokens, mask, seed=seed, step=step, shard=shard,
            precision=precision)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adamw(p, g, mu, nu, step):
        mu = B1 * mu + (1 - B1) * g
        nu = B2 * nu + (1 - B2) * jnp.square(g)
        update = (mu / (1 - B1 ** step)) / (
            jnp.sqrt(nu / (1 - B2 ** step)) + EPS)
        if p.ndim >= 2:
            update = update + decay * p
        return p - lr * update, mu, nu

    print(f"chipbench: reference: follow({precision}) starts, noise of seed "
          f"{seed}, {_host_gb()}", flush=True)
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
                                  jnp.asarray(mask[rows]),
                                  jnp.int32(step - 1), jnp.int32(d))
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
    del mu, nu
    return {"losses": losses, "params_after_first": None,
            "params": _HandedOver(params),
            "state_after_first": {"mu": mu_first}}


def first_gradient(optimizer, params0, params1, state1) -> dict:
    """Adam's first moment after one step is ``(1 - B1) * g``; handed on as
    it is, factor and all, by this one rule on both sides."""
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

def visible_pairs(length: int, block: int) -> int:
    """(query, key) pairs one head computes over ``[x ‖ x~]`` of ``length``
    tokens in ``n = length / block`` blocks: ``block ** 2 * n * (n + 1)``
    (clean on clean ``n (n + 1) / 2`` block pairs, noisy on clean
    ``n (n - 1) / 2``, noisy on its own ``n``)."""
    n = length // block
    return block * block * n * (n + 1)


def forward_macs_by_part(arch, length: int) -> dict:
    """Multiply-accumulates of one forward pass over one sequence of
    ``length`` tokens, fed as ``2 * length`` positions, by part. Visible
    pairs counted exactly; routed work as ``num_experts_per_tok * held /
    published`` experts a position and layer, which is what a router that
    favours no expert sends here; the head on ``length`` positions; the
    embedding is a lookup."""
    c, d, layers = arch["hidden_size"], arch["head_dim"], arch["layers_here"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    positions = 2 * length
    routed_share = (arch["num_experts_per_tok"] * arch["num_experts"]
                    / routed_experts(arch))
    return {
        "projections": layers * positions * c * (2 * heads * d + 2 * kv * d),
        "attention": layers * heads * visible_pairs(
            length, arch["block_length"]) * 2 * d,
        "routed": layers * positions * routed_share * 3 * c
        * arch["moe_intermediate_size"],
        "router": layers * positions * c * routed_experts(arch),
        "head": length * c * arch["vocab_size"],
    }


def train_flops_per_example(arch, traffic) -> float:
    """Required FLOPs of training on one sequence: two a multiply-accumulate,
    three passes (forward, backward by input, backward by weight); no
    recomputation counted."""
    length = int(traffic["dataset"]["seq_len"])
    return 3.0 * 2.0 * sum(forward_macs_by_part(arch, length).values())
