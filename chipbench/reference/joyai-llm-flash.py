"""Plain reference for ``joyai-llm-flash``: JD's JoyAI-LLM-Flash decoder
(``https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json``,
``model_type`` ``joyai_llm_flash``, 48B-A2.7B), the forward pass in
``jax.numpy``, float32 at ``highest``, trained on next-token prediction with
its multi-token-prediction module as a second loss term, under AdamW. Imports
nothing of the program.

The model, as this file reads the ``config``: DeepSeek-V3's layout, whose
keys the config carries one for one (arXiv:2412.19437, sections 2.1 and 2.2;
latent attention is arXiv:2405.04434's). Pre-norm decoder layers on a hidden
size of 2048, RMSNorm (eps 1e-6), no biases, embedding and head untied:

    y = x + MLA(RMSNorm(x));  z = y + FFN(RMSNorm(y));  head(RMSNorm(z_last))

*Latent attention*, 32 heads. ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank``
1536); ``q = c_q W_qb`` as 32 x (128 + 64). ``[c_kv, r] = x W_kva``
(``kv_lora_rank`` 512 + ``qk_rope_head_dim`` 64); ``[k_nope, v] =
RMSNorm(c_kv) W_kvb`` as 32 x (128 + 128). The 64 rotary dimensions of every
query head and ``r`` are turned (theta 32e6, ``rope_scaling`` null, so no
``mscale``); ``r`` is **one** key head that all 32 heads share:
``k_h = [k_nope_h, rot(r)]`` (192); ``o_h = softmax(q_h k_h^T / sqrt(192),
causal) v_h`` (128); out ``concat(o) W_o`` (4096 -> 2048).

*Feed-forward.* Layer 0 (``first_k_dense_replace`` 1) is a dense SwiGLU of
width 7,168. Every later layer is sparse: ``s = sigmoid(x W_r)`` over 256 in
float32; the 8 largest of ``s + b`` (``topk_method`` ``noaux_tc``; ``n_group``
1 and ``topk_group`` 1: no groups); weights the chosen ``s`` without ``b``,
normalised to sum to one (``norm_topk_prob``), times 2.5; SwiGLU experts of
width 768; plus one shared SwiGLU expert of width 768 on every token.

*The prediction module* (``num_nextn_predict_layers`` 1), DeepSeek-V3's: for
position ``i``, ``h'_i = W_eh [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))]``
(4096 -> 2048), then one more sparse layer as above, then the stack's own
final norm and head predict ``t_{i+2}``; the embedding is the stack's too.
``loss = L_next + lambda * L_mtp``, each a mean over its real targets.

**What the config does not settle** (the configuration file lists the same
under ``assumed``; each is one line below to change):

1. The module's equations and the order of the concatenation, hidden state
   first: the paper's equation 21 (``_mtp_input``).
2. ``lambda`` 0.3: the config has no key for it; DeepSeek-V3's for most of
   its pre-training (the file's ``mtp_loss_weight``; ``sequence_loss``).
3. ``h_i`` is the stack's output **before** the final norm: the module has a
   norm of its own for it (``RMSNorm_h``), the paper's figure 3 draws the
   arrow from under the output head, whose first step the final norm is, and
   a final norm before ``RMSNorm_h`` would only be normalised again
   (``hidden``).
4. The module runs on every position; position ``i`` reads ``Emb(t_{i+1})``
   and the last one a pad (id 0) that no loss term reads: positions ``T - 2``
   and ``T - 1`` have no ``t_{i+2}`` (``_mtp_input``, ``mtp_targets``).
5. Rotary pairs: half against half within the 64. ``rope_interleave`` true
   is a layout of the checkpoint's columns; with seeded weights any fixed
   pairing applied to ``q`` and ``r`` alike is the same model (``rotate``).
6. ``router_bias`` seeded N(0, 0.01 ** 2) (``BIAS_SCALE``): a trained
   model's balances its experts; at 0.1 the bias chose by itself
   (``nemotron3-super``, PERF.md section 6, PR 33). It gets no gradient and
   no decay; its update rule is outside this benchmark.
7. AdamW as the program's ``make_optimizer`` builds it (``follow``).

**The cut** (``model-configs`` guide, section 4). This chip is one of the
chips that share each layer: the file's ``n_routed_experts`` counts the
routed experts held here (ids ``expert_offset`` and up) of the
``published.n_routed_experts`` the router scores, ``vocab_size`` the rows of
the embedding and the head held here, ``layers_here`` the leading layers of
the published ``num_hidden_layers`` that run here; the module is whole. The
router scores every published expert; a layer adds what its own experts give
for the tokens routed to them and the shared expert, and hands that partial
result on. What the absent experts would have added is left out, here as in
the program. ``forward(..., share=(offset, held))`` computes another share
of the same weights, which is what the share test adds up.

Attention runs a group of heads at a time in blocks of query rows (32 heads
x 8192 x 8192 scores in float32 are 8.6 GB), the held experts as a plain
loop over experts, each over every token with the weight zero where the
token was not routed to it, and the loss takes head and softmax
``LOSS_BLOCK`` positions at a time (two sets of float32 logits, their
softmax and their gradient are 1.06 GB each).
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
#: heads whose queries, keys and values are held at once, at most
HEAD_GROUP = 8
#: positions whose logits the loss holds at once
LOSS_BLOCK = 1024
#: deviation of the seeded selection bias (assumed 6)
BIAS_SCALE = 0.01


# -- sizes -------------------------------------------------------------------

def routed_experts(arch) -> int:
    """Outputs of the router: the published count of routed experts."""
    return arch["published"]["n_routed_experts"]


def layer_kinds(arch):
    """The feed-forward kind of each layer run: ``dense`` or ``sparse``."""
    return ["dense" if i < arch["first_k_dense_replace"] else "sparse"
            for i in range(arch["layers_here"])]


def layer_prefixes(arch):
    """[(leaf prefix, feed-forward kind)]: the stack's layers, then the
    prediction module's."""
    found = [(f"layer_{i}.", kind) for i, kind in enumerate(layer_kinds(arch))]
    return found + [("mtp_layer.", "sparse")] * arch[
        "num_nextn_predict_layers"]


def param_shapes(arch) -> dict:
    """leaf -> (shape, kind of init)."""
    c, h = arch["hidden_size"], arch["num_attention_heads"]
    qr, kvr = arch["q_lora_rank"], arch["kv_lora_rank"]
    nope, rope, vd = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                      arch["v_head_dim"])
    e, f = arch["n_routed_experts"], arch["moe_intermediate_size"]
    shapes = {"embed": ((arch["vocab_size"], c), "unit")}
    for p, ffn in layer_prefixes(arch):
        shapes.update({
            p + "attn_norm": ((c,), "ones"),
            p + "attn.q_a": ((c, qr), "lecun"),
            p + "attn.q_norm": ((qr,), "ones"),
            p + "attn.q_b": ((qr, h * (nope + rope)), "lecun"),
            p + "attn.kv_a": ((c, kvr + rope), "lecun"),
            p + "attn.kv_norm": ((kvr,), "ones"),
            p + "attn.kv_b": ((kvr, h * (nope + vd)), "lecun"),
            p + "attn.o": ((h * vd, c), "lecun"),
            p + "mlp_norm": ((c,), "ones"),
        })
        if ffn == "dense":
            w = arch["intermediate_size"]
            shapes.update({p + "mlp.gate": ((c, w), "lecun"),
                           p + "mlp.up": ((c, w), "lecun"),
                           p + "mlp.down": ((w, c), "lecun")})
        else:
            s = arch["n_shared_experts"] * f
            shapes.update({
                p + "moe.router": ((c, routed_experts(arch)), "lecun"),
                p + "moe.router_bias": ((routed_experts(arch),), "bias"),
                p + "moe.w_gate": ((e, c, f), "lecun_stacked"),
                p + "moe.w_up": ((e, c, f), "lecun_stacked"),
                p + "moe.w_down": ((e, f, c), "lecun_stacked"),
                p + "moe.shared.gate": ((c, s), "lecun"),
                p + "moe.shared.up": ((c, s), "lecun"),
                p + "moe.shared.down": ((s, c), "lecun"),
            })
    if arch["num_nextn_predict_layers"]:
        shapes.update({"mtp_hidden_norm": ((c,), "ones"),
                       "mtp_embed_norm": ((c,), "ones"),
                       "mtp_proj": ((2 * c, c), "lecun")})
    shapes.update({"final_norm": ((c,), "ones"),
                   "head": ((c, arch["vocab_size"]), "lecun")})
    return shapes


def init_params(arch, seed: int) -> dict:
    """Seeded float32 weights, one jitted call: the embedding N(0, 1), every
    matrix N(0, 1 / fan-in) (a stacked expert's fan-in is its own), norm
    scales 1, the selection bias N(0, ``BIAS_SCALE`` ** 2). The residual
    stream then stays of order one through the cut stack and the router's
    logits are of order one, as trained ones are."""
    shapes = param_shapes(arch)

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            deviation = BIAS_SCALE if kind == "bias" else 1 / math.sqrt(
                {"unit": 1, "lecun": shape[0],
                 "lecun_stacked": shape[-2]}[kind])
            out[name] = jax.random.normal(k, shape, jnp.float32) * deviation
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
        elif path[-1].startswith("w_") or path[-1] == "router_bias":
            names[leaf] = path           # stacked weights and the bias are bare
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


def rotary_tables(arch, length: int):
    """(cos, sin), each ``(length, qk_rope_head_dim / 2)`` float32:
    ``theta ** (-2i / dims)``, no scaling (``rope_scaling`` null)."""
    if arch.get("rope_scaling") is not None:
        raise ValueError("this reference turns by plain frequencies only")
    dims = arch["qk_rope_head_dim"]
    inv_freq = arch["rope_theta"] ** -(
        np.arange(0, dims, 2, dtype=np.float64) / dims)
    angles = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def rotate(x, cos, sin):
    """``x`` (B, T, H, dims) turned, half against half (assumed 5)."""
    x32 = x.astype(jnp.float32)
    a, b = jnp.split(x32, 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def blocked_attention(q, k, v, precision):
    """Causal attention of (B, T, H, Dqk) queries and keys over (B, T, H,
    Dv) values, scores over ``sqrt(Dqk)``, a block of query rows at a time
    against every key (masked above the diagonal), each block recomputed in
    the backward pass, so one block's scores are all that is ever held."""
    b, t, h, d = q.shape
    prec = C.PRECISIONS[precision][2]
    rows_per = max(n for n in range(1, min(Q_BLOCK, t) + 1) if t % n == 0)
    blocks = t // rows_per
    q = jnp.moveaxis(q.reshape(b, blocks, rows_per, h, d), 1, 0)
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        qb, i = args
        rows = i * rows_per + jnp.arange(rows_per)
        s = jnp.einsum("bqhd,bshd->bhqs", C._operand(qb, precision),
                       C._operand(k, precision), precision=prec,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        vis = cols[None, :] <= rows[:, None]
        p = C.hold(jax.nn.softmax(jnp.where(vis, s, -jnp.inf), axis=-1),
                   precision)
        return C._contracted(jnp.einsum(
            "bhqs,bshd->bqhd", C._operand(p, precision),
            C._operand(v, precision), precision=prec), precision)

    out = lax.map(block, (q, jnp.arange(blocks)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, v.shape[-1])


def _attention(arch, p, x, tables, precision):
    """Latent attention on the normalised input ``x``, ``HEAD_GROUP`` heads
    at a time: a group's queries, keys and values from the two latents, its
    attention, and its rows of ``W_o``, summed over the groups (``concat(o)
    W_o`` is that sum), each group recomputed in the backward pass. In
    float32 all 32 heads' queries, keys, values and their cotangents are
    3.7 GB at the timed sizes."""
    b, t, c = x.shape
    h, eps = arch["num_attention_heads"], arch["rms_norm_eps"]
    nope, rope, vd = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                      arch["v_head_dim"])
    kvr = arch["kv_lora_rank"]
    cos, sin = tables
    c_q = rms_norm(_dot(x, p["attn.q_a"], precision), p["attn.q_norm"], eps,
                   precision)
    kv = _dot(x, p["attn.kv_a"], precision)
    c_kv = rms_norm(kv[..., :kvr], p["attn.kv_norm"], eps, precision)
    r = rotate(kv[..., kvr:].reshape(b, t, 1, rope), cos[:t], sin[:t])
    g = max(n for n in range(1, min(HEAD_GROUP, h) + 1) if h % n == 0)
    by_group = lambda w, width: jnp.moveaxis(  # noqa: E731
        w.reshape(w.shape[0], h // g, g * width), 1, 0)

    @jax.checkpoint
    def some_heads(out, own):
        w_q, w_kv, w_o = own
        q = _dot(c_q, w_q, precision).reshape(b, t, g, nope + rope)
        q = jnp.concatenate(
            [q[..., :nope], rotate(q[..., nope:], cos[:t], sin[:t])], axis=-1)
        k_v = _dot(c_kv, w_kv, precision).reshape(b, t, g, nope + vd)
        k = jnp.concatenate(
            [k_v[..., :nope], jnp.broadcast_to(r, (b, t, g, rope))], axis=-1)
        o = blocked_attention(q, k, k_v[..., nope:], precision)
        return out + _dot(o.reshape(b, t, g * vd), w_o,
                          precision).astype(jnp.float32), None

    out, _ = lax.scan(some_heads, jnp.zeros((b, t, c), jnp.float32), (
        by_group(p["attn.q_b"], nope + rope), by_group(p["attn.kv_b"],
                                                       nope + vd),
        p["attn.o"].reshape(h // g, g * vd, c)))
    return C.hold(out, precision)


def _route(arch, x, router, bias):
    """(weights (B, T, k) float32, expert ids (B, T, k)): in float32
    whatever the precision, as the configuration states. The bias chooses
    and does not weigh."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), router,
                                    precision=lax.Precision.HIGHEST))
    _, ids = lax.top_k(scores + bias, arch["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return weights * arch["routed_scaling_factor"], ids


def _moe(arch, p, x, share, precision, taps=None):
    """The share's part of the sparse layer: its experts' weighted outputs
    for the tokens routed to them, and the shared expert (``share[2]``
    False: without it, for a test that counts it once)."""
    offset, held, with_shared = share
    weights, ids = _route(arch, x, p["moe.router"], p["moe.router_bias"])
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
    routed = C.hold(routed, precision)
    if not with_shared:
        return routed
    return routed + swiglu(x, p["moe.shared.gate"], p["moe.shared.up"],
                           p["moe.shared.down"], precision)


def _own(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def layer(arch, p, x, ffn, tables, share, precision, taps=None):
    """One decoder layer; ``p`` its leaves without the prefix."""
    eps = arch["rms_norm_eps"]
    y = x + _attention(arch, p, rms_norm(x, p["attn_norm"], eps, precision),
                       tables, precision)
    h = rms_norm(y, p["mlp_norm"], eps, precision)
    if ffn == "dense":
        return y + swiglu(h, p["mlp.gate"], p["mlp.up"], p["mlp.down"],
                          precision)
    return y + _moe(arch, p, h, share, precision, taps)


def _mtp_input(arch, params, x, tokens, precision):
    """``W_eh [RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))]`` for every
    position, the last reading a pad (assumed 1, 3, 4)."""
    eps = arch["rms_norm_eps"]
    ahead = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    both = jnp.concatenate(
        [rms_norm(x, params["mtp_hidden_norm"], eps, precision),
         rms_norm(C.hold(params["embed"][ahead], precision),
                  params["mtp_embed_norm"], eps, precision)], axis=-1)
    return _dot(both, params["mtp_proj"], precision)


def hidden(arch, params, tokens, precision="float32_highest", *, share=None,
           taps=None):
    """What the head reads, each (B, T, hidden) after the final norm: the
    stack's output, and the prediction module's (None without a module).
    ``share`` is ``(offset, held)`` of the routed experts, the
    configuration's own by default; ``taps`` (a list) collects each sparse
    layer's expert ids."""
    offset, held = share or (arch.get("expert_offset", 0),
                             arch["n_routed_experts"])
    eps = arch["rms_norm_eps"]
    tables = rotary_tables(arch, tokens.shape[1])
    x = C.hold(params["embed"][tokens], precision)

    def run(x, p, ffn):
        return layer(arch, p, x, ffn, tables, (offset, held, True), precision,
                     taps)

    step = run if taps is not None else jax.checkpoint(
        run, static_argnums=(2,))
    for prefix, ffn in layer_prefixes(arch)[:arch["layers_here"]]:
        x = step(x, _own(params, prefix), ffn)
    first = rms_norm(x, params["final_norm"], eps, precision)
    if not arch["num_nextn_predict_layers"]:
        return first, None
    x = step(_mtp_input(arch, params, x, tokens, precision),
             _own(params, "mtp_layer."), "sparse")
    return first, rms_norm(x, params["final_norm"], eps, precision)


def forward(arch, params, tokens, precision="float32_highest", *, share=None,
            taps=None):
    """(logits, module's logits), each (B, T, vocab rows held) in float32:
    position ``i`` of the first predicts token ``i + 1``, of the second
    token ``i + 2``."""
    first, second = hidden(arch, params, tokens, precision, share=share,
                           taps=taps)
    project = lambda x: None if x is None else _dot(  # noqa: E731
        x, params["head"], precision).astype(jnp.float32)
    return project(first), project(second)


# -- the task and the optimizer ----------------------------------------------

def mtp_targets(tokens, mask, ahead: int):
    """(targets, weights), each (B, T): position ``i`` is held against token
    ``i + ahead`` where that is a real token; the last ``ahead`` positions
    have no target and weigh zero, so that the positions divide into
    blocks."""
    pad = ((0, 0), (0, ahead))
    return (jnp.pad(tokens[:, ahead:], pad),
            jnp.pad(mask[:, ahead:].astype(jnp.float32), pad))


def _blocked_mean_nll(x, head, targets, weights, precision):
    """Mean over the weighted positions of ``-log softmax(x head)[target]``,
    head and softmax ``LOSS_BLOCK`` positions at a time, each block
    recomputed in the backward pass."""
    b, t, _ = x.shape
    per = max(n for n in range(1, min(LOSS_BLOCK, t) + 1) if t % n == 0)
    cut = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((b, t // per, per) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def block(part):
        xb, tb, wb = part
        logits = _dot(xb, head, precision).astype(jnp.float32)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits), tb[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum(nll * wb)

    total = jnp.sum(lax.map(block, (cut(x), cut(targets), cut(weights))))
    return total / jnp.maximum(jnp.sum(weights), 1.0)


def loss_terms(arch, params, tokens, mask, precision="float32_highest"):
    """(``L_next``, ``L_mtp``): mean negative log-likelihood of token ``i +
    1`` at position ``i`` of the stack, and of token ``i + 2`` at position
    ``i`` of the module, each over the positions whose target is a real
    token, in float32."""
    first, second = hidden(arch, params, tokens, precision)
    terms = []
    for x, ahead in ((first, 1), (second, 2)):
        terms.append(jnp.float32(0.0) if x is None else _blocked_mean_nll(
            x, params["head"], *mtp_targets(tokens, mask, ahead), precision))
    return tuple(terms)


def sequence_loss(arch, params, tokens, mask, precision="float32_highest"):
    """``L_next + lambda * L_mtp`` (assumed 2)."""
    l_next, l_mtp = loss_terms(arch, params, tokens, mask, precision)
    return l_next + arch["mtp_loss_weight"] * l_mtp


def target_mask(batch):
    """(B, T) bool: which tokens are real targets; a row the loader padded
    the epoch's last batch with (``mask`` False) has none."""
    return np.logical_and(batch["loss_mask"], batch["mask"][:, None])


def _host_gb() -> str:
    """This process's resident set now (on the chip's machine it counts
    13.6 GB that appear when the TPU runtime starts: PERF.md section 6,
    PR 27)."""
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
    the loader's row ``mask``) on the two-term loss: every shard's loss is
    its own mean, the gradient the mean of the shards'. Decoupled weight
    decay on the leaves of two or more axes, as the program masks it (the
    selection bias has one axis and no gradient: it stays). Also returns
    Adam's first moment after the first step. The weights, one set of
    gradients and both moments live on the device; the first moment after
    step 1 and, a leaf at a time, the weights after the last step come to
    the host. ``losses`` are the sums the program reports as ``loss``."""
    if optimizer["name"] != "adamw":
        raise ValueError(
            f"joyai-llm-flash follows adamw, not {optimizer['name']}")
    lr, decay = optimizer["lr"], optimizer["weight_decay"]

    def shard_loss(p, tokens, mask):
        return sequence_loss(arch, p, tokens, mask, precision)

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

def forward_macs_by_part(arch, t: int) -> dict:
    """Multiply-accumulates of one forward pass over one sequence of ``t``
    tokens, by part, the prediction module's layer, its ``W_eh`` and its use
    of the head with the stack's. The (query, key) pairs under the diagonal
    counted exactly, at 192 for the scores and 128 for the values a head;
    routed work as ``num_experts_per_tok * held / published`` experts a
    token and layer, which is what a router that favours no expert sends
    here; the embedding is a lookup."""
    c, h = arch["hidden_size"], arch["num_attention_heads"]
    qr, kvr = arch["q_lora_rank"], arch["kv_lora_rank"]
    nope, rope, vd = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                      arch["v_head_dim"])
    f = arch["moe_intermediate_size"]
    routed_share = (arch["num_experts_per_tok"] * arch["n_routed_experts"]
                    / routed_experts(arch))
    modules = arch["num_nextn_predict_layers"]
    parts = dict.fromkeys(("attention", "mla_projections", "head", "dense",
                           "shared", "routed", "mtp_proj", "routers"), 0.0)
    for _, ffn in layer_prefixes(arch):
        parts["attention"] += h * (t * (t + 1) // 2) * (nope + rope + vd)
        parts["mla_projections"] += t * (
            c * qr + qr * h * (nope + rope) + c * (kvr + rope)
            + kvr * h * (nope + vd) + h * vd * c)
        if ffn == "dense":
            parts["dense"] += t * 3 * c * arch["intermediate_size"]
        else:
            parts["routed"] += t * routed_share * 3 * c * f
            parts["shared"] += t * 3 * c * arch["n_shared_experts"] * f
            parts["routers"] += t * c * routed_experts(arch)
    parts["mtp_proj"] = modules * t * 2 * c * c
    parts["head"] = (1 + modules) * t * c * arch["vocab_size"]
    return parts


def train_flops_per_example(arch, traffic) -> float:
    """Required FLOPs of training on one sequence: two a multiply-accumulate,
    three passes (forward, backward by input, backward by weight); no
    recomputation counted."""
    t = int(traffic["dataset"]["seq_len"])
    return 3.0 * 2.0 * sum(forward_macs_by_part(arch, t).values())
