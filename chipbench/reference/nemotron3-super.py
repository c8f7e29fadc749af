"""Plain reference for ``nemotron3-super``: NVIDIA's
Nemotron-3-Super-120B-A12B
(``https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json``,
``model_type`` ``nemotron_h``), the forward pass in ``jax.numpy``, float32 at
``highest``, trained on next-token prediction under AdamW. Imports nothing
of the program.

The model, as the ``config`` gives it. A stack of pre-norm blocks on a
hidden size of 4096, one mixer a block, RMSNorm (eps 1e-5), no biases but
the convolution's, embedding and head untied:

    h = embed(tokens);  h = h + Mixer(RMSNorm(h)) a block;  head(RMSNorm(h))

The mixer is named by the block's character in ``hybrid_override_pattern``.

*``M``, Mamba-2* (``mamba_num_heads`` H of ``mamba_head_dim`` P = 64,
``n_groups`` G, ``ssm_state_size`` N = 128, ``conv_kernel`` 4):
``[z, xBC, dt] = u W_in`` (widths H P, H P + 2 G N, H); ``xBC =
silu(causal depthwise conv(xBC) + b)``, split into ``x`` (H, P) and ``B``,
``C`` (G, N); ``dt = softplus(dt + dt_bias)``; for head ``h`` of group
``g`` the recurrence ``S_t = exp(-dt_t exp(A_log_h)) S_{t-1} + dt_t x_t
B_t^T`` (P x N, float32), ``y_t = S_t C_t + D_h x_t``; then ``y =
RMSNorm(y * silu(z)) * w`` within each group's ``H P / G`` channels; out ``y
W_out``. Run here one position at a time (``_recurrence``), never in the
chunked form the program uses.

*``*``, attention*: ``num_attention_heads`` query heads over
``num_key_value_heads`` key-value heads of 128, causal over everything,
scale ``128 ** -0.5``; q, k, v, o without bias.

*``E``, latent mixture*: ``s = sigmoid(float32(u) W_r)`` over the 512
published experts; the 22 largest of ``s + bias`` are chosen; their weights
are the chosen ``s``, without the bias, normalised to sum to one, times
``routed_scaling_factor`` 5. ``l = u W_down`` (4096 -> ``moe_latent_size``
1024); expert ``e`` is ``relu(l W1_e) ** 2 W2_e`` (1024 -> 2688 -> 1024, no
gate); routed ``= (sum_k w_k expert_k(l)) W_up`` (1024 -> 4096); the shared
expert ``relu(u V1) ** 2 V2`` (width 5376) reads the hidden state; out
routed + shared.

**What the config does not settle** (the configuration file lists the same
under ``assumed``):

1. Attention has no position encoding and no gate (``_attention``): the
   ``nemotron_h`` family publishes none in its attention; ``rope_theta`` and
   ``partial_rotary_factor`` are carried by the config and read by nothing.
2. The gated norm is applied after the gate, per group (``_mamba``).
3. ``dt`` is not clamped after its softplus.
4. The selection bias is a seeded constant (``init_params``): its update
   rule belongs to the training recipe, not to the config. It enters the
   choice only, so no gradient reaches it, and AdamW decays no leaf of one
   axis: it stays what the seed made it. Its scale is ``BIAS_SCALE``: a
   token's 22 chosen scores lie between 0.85 and 1, so a bias as wide as
   0.1 chooses by itself (an expert one deviation down gets a twentieth of
   a fair load, one up three times it), which is the opposite of what the
   bias is trained for; at 0.01 it moves about three of a token's 22
   choices and an expert's load by a sixth.
5. No auxiliary loss; ``n_group`` 1 and ``topk_group`` 1 make the router's
   groups one.
6. ``rescale_prenorm_residual`` is an initialisation; the benchmark seeds
   its own: embedding N(0, 1), every matrix N(0, 1 / fan-in), the
   convolution's taps N(0, 1 / 4) and its bias N(0, 0.1 ** 2), norm scales and
   ``D`` 1, ``dt_bias`` the inverse softplus of a step drawn log-uniformly
   between ``time_step_min`` and ``time_step_max`` (not below
   ``time_step_floor``), ``A_log`` the logarithm of a rate drawn uniformly
   from 1 to 16 (the family's range; no key gives it), the selection bias
   N(0, 0.01 ** 2).
7. AdamW as the program's ``make_optimizer`` builds it (``follow``).
8. The multi-token prediction module (``num_nextn_predict_layers``, blocks
   ``*E`` after the 88th) lies on the last pipeline stage: not here, and no
   second loss term.

**The cut** (``model-configs`` guide, section 4). This chip is one of the
chips that share each block. The file's ``n_routed_experts`` counts the
routed experts held here (ids ``expert_offset`` and up) of the
``published.n_routed_experts`` the router scores; ``mamba_num_heads``,
``n_groups``, ``num_attention_heads`` and ``num_key_value_heads`` the heads
and groups held here, position ``head_position`` of the eight of a
tensor-parallel group; ``vocab_size`` the rows of embedding and head;
``layers_here`` the leading blocks of the pattern. Every mixer adds what its
own heads or experts give and hands that partial result on; what the absent
ones would add is left out, here as in the program.
``forward(..., share=(head_position, expert_offset))`` computes another
share: the expert offset moves the ids the held experts answer to; a head's
arithmetic does not depend on which head it is, so the head position only
says which slice of an uncut set of weights ``take_share`` cuts out. The
share test adds those up.

Attention runs in blocks of query rows, the held experts as a plain loop
over experts, each over every token with the weight zero where the token was
not routed to it, the recurrence in segments that are recomputed in the
backward pass (a state a position is 8.6 GB a block otherwise).
``train_flops_per_example`` is a function of shapes.
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
#: positions of one recomputed segment of the recurrence, at most
SEGMENT = 128
#: positions whose logits are held at once by ``sequence_loss``, at most
LOSS_BLOCK = 1024
#: blocks of one recomputed run of the stack
RUN = 4
#: the range ``A`` is drawn from (assumed 6)
A_RANGE = (1.0, 16.0)
#: standard deviation of the seeded selection bias (assumed 4)
BIAS_SCALE = 0.01
#: standard deviation of the leaves drawn N(0, scale ** 2), by kind
SCALES = {"small": 0.1, "bias": BIAS_SCALE}


# -- sizes -------------------------------------------------------------------

def routed_experts(arch) -> int:
    """Outputs of the router: the published count of routed experts."""
    return arch["published"]["n_routed_experts"]


def pattern(arch) -> str:
    """One character a block run here."""
    return arch["hybrid_override_pattern"][:arch["layers_here"]]


def mamba_widths(arch):
    """(inner, B or C, heads): widths of ``[z | x | B | C | dt]``."""
    return (arch["mamba_num_heads"] * arch["mamba_head_dim"],
            arch["n_groups"] * arch["ssm_state_size"],
            arch["mamba_num_heads"])


def param_shapes(arch) -> dict:
    """leaf -> (shape, kind of init)."""
    c, d = arch["hidden_size"], arch["head_dim"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    inner, bc, h = mamba_widths(arch)
    e, f = arch["n_routed_experts"], arch["moe_intermediate_size"]
    lat, s = arch["moe_latent_size"], arch["moe_shared_expert_intermediate_size"]
    shapes = {"embed": ((arch["vocab_size"], c), "unit")}
    for i, kind in enumerate(pattern(arch)):
        p = f"block_{i}."
        shapes[p + "norm"] = ((c,), "ones")
        p += "mixer."
        if kind == "M":
            shapes.update({
                p + "in_proj": ((c, 2 * inner + 2 * bc + h), "lecun"),
                p + "conv_kernel": ((arch["conv_kernel"], inner + 2 * bc),
                                    "lecun"),
                p + "conv_bias": ((inner + 2 * bc,), "small"),
                p + "A_log": ((h,), "log_rate"),
                p + "D": ((h,), "ones"),
                p + "dt_bias": ((h,), "time_step"),
                p + "norm_scale": ((inner,), "ones"),
                p + "out_proj": ((inner, c), "lecun"),
            })
        elif kind == "*":
            shapes.update({
                p + "q": ((c, heads * d), "lecun"),
                p + "k": ((c, kv * d), "lecun"),
                p + "v": ((c, kv * d), "lecun"),
                p + "o": ((heads * d, c), "lecun"),
            })
        elif kind == "E":
            shapes.update({
                p + "router": ((c, routed_experts(arch)), "lecun"),
                p + "router_bias": ((routed_experts(arch),), "bias"),
                p + "latent_down": ((c, lat), "lecun"),
                p + "latent_up": ((lat, c), "lecun"),
                p + "w_up": ((e, lat, f), "lecun_stacked"),
                p + "w_down": ((e, f, lat), "lecun_stacked"),
                p + "shared.up": ((c, s), "lecun"),
                p + "shared.down": ((s, c), "lecun"),
            })
        else:
            raise ValueError(f"no mixer {kind!r} in the pattern")
    shapes.update({"final_norm": ((c,), "ones"),
                   "head": ((c, arch["vocab_size"]), "lecun")})
    return shapes


def init_params(arch, seed: int) -> dict:
    """Seeded float32 weights, one jitted call (assumed 6)."""
    shapes = param_shapes(arch)
    lo, hi = arch["time_step_min"], arch["time_step_max"]

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind in SCALES:
                out[name] = SCALES[kind] * jax.random.normal(
                    k, shape, jnp.float32)
            elif kind == "log_rate":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, *A_RANGE))
            elif kind == "time_step":
                step = jnp.maximum(jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(lo), math.log(hi))),
                    arch["time_step_floor"])
                out[name] = step + jnp.log(-jnp.expm1(-step))
            else:
                fan_in = {"unit": 1, "lecun": shape[0],
                          "lecun_stacked": shape[-2]}[kind]
                out[name] = jax.random.normal(
                    k, shape, jnp.float32) / math.sqrt(fan_in)
        return out

    return jax.jit(make)(jax.random.key(seed))


#: leaves the program holds as bare parameters, not as a ``kernel``
BARE = ("conv_kernel", "conv_bias", "A_log", "D", "dt_bias", "norm_scale",
        "router_bias", "w_up", "w_down")


def program_names(arch) -> dict:
    """reference leaf -> path in ``tpu_ddp.models.hybrid.HybridDecoder``."""
    names = {}
    for leaf in param_shapes(arch):
        path = tuple(leaf.split("."))
        if leaf == "embed":
            names[leaf] = ("embed", "embedding")
        elif path[-1] in ("norm", "final_norm"):
            names[leaf] = path + ("scale",)
        elif path[-1] in BARE:
            names[leaf] = path
        else:
            names[leaf] = path + ("kernel",)
    return names


def take_share(arch, params, *, positions: int, head_position: int,
               experts_held: int, expert_offset: int):
    """(arch, params) of one share of an uncut set: position
    ``head_position`` of ``positions`` equal shares of the heads (Mamba heads
    with their groups; query heads with the key-value head they read) and
    ``experts_held`` routed experts from ``expert_offset`` up. Router, bias,
    latent projections, shared expert, norms, embedding and head are every
    chip's."""
    def part(n):  # (held, first) of n heads or groups
        held = max(n // positions, 1)
        return held, head_position * n // positions

    p, n, d = arch["mamba_head_dim"], arch["ssm_state_size"], arch["head_dim"]
    (h, h0), (g, g0) = part(arch["mamba_num_heads"]), part(arch["n_groups"])
    (q, q0), (kv, kv0) = (part(arch["num_attention_heads"]),
                          part(arch["num_key_value_heads"]))
    inner, bc, _ = mamba_widths(arch)
    span = lambda first, count, width: np.arange(  # noqa: E731
        first * width, (first + count) * width)
    x_cols, b_cols = span(h0, h, p), span(g0, g, n)
    conv_cols = np.concatenate([x_cols, inner + b_cols, inner + bc + b_cols])
    in_cols = np.concatenate([x_cols, inner + conv_cols,
                              2 * inner + 2 * bc + span(h0, h, 1)])
    experts = slice(expert_offset, expert_offset + experts_held)
    cut = {
        "in_proj": lambda w: w[:, in_cols],
        "conv_kernel": lambda w: w[:, conv_cols],
        "conv_bias": lambda w: w[conv_cols],
        "A_log": lambda w: w[h0:h0 + h], "D": lambda w: w[h0:h0 + h],
        "dt_bias": lambda w: w[h0:h0 + h],
        "norm_scale": lambda w: w[x_cols], "out_proj": lambda w: w[x_cols],
        "q": lambda w: w[:, span(q0, q, d)], "o": lambda w: w[span(q0, q, d)],
        "k": lambda w: w[:, span(kv0, kv, d)],
        "v": lambda w: w[:, span(kv0, kv, d)],
        "w_up": lambda w: w[experts], "w_down": lambda w: w[experts],
    }
    here = dict(arch, mamba_num_heads=h, n_groups=g, num_attention_heads=q,
                num_key_value_heads=kv, n_routed_experts=experts_held,
                head_position=head_position, expert_offset=expert_offset)
    return here, {leaf: cut.get(leaf.split(".")[-1], lambda w: w)(w)
                  for leaf, w in params.items()}


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


def relu2_mlp(x, up, down, precision):
    return _dot(jnp.square(jax.nn.relu(_dot(x, up, precision))), down,
                precision)


def causal_conv(x, kernel, bias):
    """``y_t = sum_k kernel[k] x_{t - (K - 1) + k} + bias`` a channel, zeros
    before the sequence; float32."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, k:k + t] * kernel[k] for k in range(taps)) + bias


def _recurrence(x, dt, a_log, B, C_):
    """``S_t = exp(-dt_t exp(A_log)) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t
    C_t``, one position at a time in float32: ``x`` (b, t, h, p), ``dt``
    (b, t, h), ``B`` and ``C_`` (b, t, g, n), the heads of a group sharing
    them. Segments of ``SEGMENT`` positions, each recomputed in the backward
    pass, so that only the states between segments are held."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    per = max(s for s in range(1, min(SEGMENT, t) + 1) if t % s == 0)
    rate = jnp.exp(a_log.astype(jnp.float32))
    heads = lambda a: jnp.repeat(a, h // g, axis=1)  # noqa: E731

    def step(state, now):
        xt, dtt, bt, ct = now
        state = (jnp.exp(-dtt * rate)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * heads(bt)[:, :, None])
        return state, jnp.sum(state * heads(ct)[:, :, None], axis=-1)

    @jax.checkpoint
    def segment(state, part):
        return lax.scan(step, state, part)

    parts = tuple(
        jnp.moveaxis(a.astype(jnp.float32), 1, 0).reshape(
            (t // per, per) + a.shape[:1] + a.shape[2:])
        for a in (x, dt, B, C_))
    _, y = lax.scan(segment, jnp.zeros((b, h, p, n), jnp.float32), parts)
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def _mamba(arch, p, u, precision):
    b, t, _ = u.shape
    inner, bc, h = mamba_widths(arch)
    g = arch["n_groups"]
    z, xbc, dt = jnp.split(_dot(u, p["in_proj"], precision),
                           [inner, 2 * inner + 2 * bc], axis=-1)
    xbc = C.hold(jax.nn.silu(causal_conv(xbc, p["conv_kernel"],
                                         p["conv_bias"])), precision)
    x, B, C_ = jnp.split(xbc, [inner, inner + bc], axis=-1)
    x = x.reshape(b, t, h, arch["mamba_head_dim"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (assumed 3)
    y = _recurrence(x, dt, p["A_log"], B.reshape(b, t, g, -1),
                    C_.reshape(b, t, g, -1))
    y = y + p["D"][:, None] * x.astype(jnp.float32)
    # the gate, then the norm, a group at a time (assumed 2)
    y = (y.reshape(b, t, g, inner // g)
         * jax.nn.silu(z.astype(jnp.float32)).reshape(b, t, g, inner // g))
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                      + arch["layer_norm_epsilon"])
    y = C.hold(y.reshape(b, t, inner) * p["norm_scale"], precision)
    return _dot(y, p["out_proj"], precision)


def blocked_attention(q, k, v, precision):
    """Causal grouped-query attention, (B, T, H, D) queries against
    (B, T, KV, D) keys and values, a block of query rows at a time against
    every key (masked above the diagonal), each block recomputed in the
    backward pass."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    prec = C.PRECISIONS[precision][2]
    rows_per = max(n for n in range(1, min(Q_BLOCK, t) + 1) if t % n == 0)
    blocks = t // rows_per
    q = jnp.moveaxis(q.reshape(b, blocks, rows_per, kv, h // kv, d), 1, 0)
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        qb, i = args
        rows = i * rows_per + jnp.arange(rows_per)
        s = jnp.einsum("bqkgd,bskd->bkgqs", C._operand(qb, precision),
                       C._operand(k, precision), precision=prec,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        vis = cols[None, :] <= rows[:, None]
        p = C.hold(jax.nn.softmax(jnp.where(vis, s, -jnp.inf), axis=-1),
                   precision)
        return C._contracted(jnp.einsum(
            "bkgqs,bskd->bqkgd", C._operand(p, precision),
            C._operand(v, precision), precision=prec), precision)

    out = lax.map(block, (q, jnp.arange(blocks)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, d)


def _attention(arch, p, u, precision):
    """No position encoding, no gate (assumed 1)."""
    b, t, _ = u.shape
    d = arch["head_dim"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    q = _dot(u, p["q"], precision).reshape(b, t, heads, d)
    k = _dot(u, p["k"], precision).reshape(b, t, kv, d)
    v = _dot(u, p["v"], precision).reshape(b, t, kv, d)
    o = blocked_attention(q, k, v, precision)
    return _dot(o.reshape(b, t, heads * d), p["o"], precision)


def _route(arch, u, router, bias):
    """(weights (B, T, k) float32, expert ids (B, T, k)): in float32
    whatever the precision, as the configuration states. The bias chooses
    and does not weigh (assumed 4)."""
    scores = jax.nn.sigmoid(jnp.dot(u.astype(jnp.float32), router,
                                    precision=lax.Precision.HIGHEST))
    _, ids = lax.top_k(scores + bias, arch["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return weights * arch["routed_scaling_factor"], ids


def _experts(arch, p, u, expert_offset, precision, taps=None):
    """The share's part of the block: its experts' weighted outputs for the
    tokens routed to them, in the latent space and back, and the shared
    expert."""
    weights, ids = _route(arch, u, p["router"], p["router_bias"])
    if taps is not None:
        taps.append(ids)
    latent = _dot(u, p["latent_down"], precision)

    @jax.checkpoint
    def one_expert(out, expert):
        e, w_up, w_down = expert
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        y = relu2_mlp(latent, w_up, w_down, precision)
        return out + w[..., None] * y.astype(jnp.float32), None

    held = p["w_up"].shape[0]
    routed, _ = lax.scan(
        one_expert, jnp.zeros(latent.shape, jnp.float32),
        (expert_offset + jnp.arange(held), p["w_up"], p["w_down"]))
    routed = _dot(C.hold(routed, precision), p["latent_up"], precision)
    return routed + relu2_mlp(u, p["shared.up"], p["shared.down"], precision)


def mixer(arch, kind, p, u, precision="float32_highest", *, expert_offset=0,
          taps=None):
    """One block's mixer on its normalised input, by the block's character;
    ``p`` its leaves without the ``block_<i>.mixer.`` in front."""
    if kind == "M":
        return _mamba(arch, p, u, precision)
    if kind == "*":
        return _attention(arch, p, u, precision)
    return _experts(arch, p, u, expert_offset, precision, taps)


def hidden(arch, params, tokens, precision="float32_highest", *, share=None,
           taps=None):
    """The stack's output after the final norm, (B, T, hidden). ``share`` is
    ``(head_position, expert_offset)``, the configuration's own by default
    (the docstring says what each moves); ``taps`` (a list) collects each
    expert block's expert ids."""
    _, expert_offset = share or (arch.get("head_position", 0),
                                 arch.get("expert_offset", 0))
    eps = arch["norm_eps"]
    x = C.hold(params["embed"][tokens], precision)

    def block(x, p, kind):
        u = rms_norm(x, p["norm"], eps, precision)
        own = {k.split(".", 1)[1]: v for k, v in p.items() if "." in k}
        return x + mixer(arch, kind, own, u, precision,
                         expert_offset=expert_offset, taps=taps)

    def run(x, own, kinds):
        """Some blocks in a row, each recomputed in the backward pass."""
        for p, kind in zip(own, kinds):
            x = block(x, p, kind) if taps is not None else jax.checkpoint(
                block, static_argnums=(2,))(x, p, kind)
        return x

    kinds = pattern(arch)
    own = [{k.split(".", 1)[1]: v for k, v in params.items()
            if k.startswith(f"block_{i}.")} for i in range(len(kinds))]
    # the blocks' inputs are 268 MB each at the timed sizes: only every
    # ``RUN``-th is held through the backward pass, the others are made
    # again a run at a time
    for first in range(0, len(kinds), RUN):
        part = slice(first, first + RUN)
        step = run if taps is not None else jax.checkpoint(
            run, static_argnums=(2,))
        x = step(x, own[part], kinds[part])
    return rms_norm(x, params["final_norm"], eps, precision)


def forward(arch, params, tokens, precision="float32_highest", *, share=None,
            taps=None):
    """Logits (B, T, vocab rows held) in float32."""
    x = hidden(arch, params, tokens, precision, share=share, taps=taps)
    return _dot(x, params["head"], precision).astype(jnp.float32)


# -- the task and the optimizer ----------------------------------------------

def next_token_loss(logits, tokens, mask):
    """Mean negative log-likelihood of token t+1 at position t over the
    positions whose target is a real token, in float32."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    w = mask[:, 1:].astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def sequence_loss(arch, params, tokens, mask, precision="float32_highest"):
    """``next_token_loss(forward(...), tokens, mask)`` with the head and the
    softmax taken ``LOSS_BLOCK`` positions at a time, each block recomputed
    in the backward pass: at the timed sizes the float32 logits, their
    softmax and their gradient are 1.07 GB each, which the chip cannot hold
    beside the weights, their gradient, both moments and the blocks' inputs.
    The last position has no target: it is weighed zero, not cut off, so
    that the positions divide into blocks."""
    x = hidden(arch, params, tokens, precision)
    b, t, c = x.shape
    targets = jnp.roll(tokens, -1, axis=1)
    weights = mask.astype(jnp.float32).at[:, 0].set(0.0)
    weights = jnp.roll(weights, -1, axis=1)
    per = max(n for n in range(1, min(LOSS_BLOCK, t) + 1) if t % n == 0)
    cut = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((b, t // per, per) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def block(part):
        xb, tb, wb = part
        logits = _dot(xb, params["head"], precision).astype(jnp.float32)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits), tb[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum(nll * wb)

    total = jnp.sum(lax.map(block, (cut(x), cut(targets), cut(weights))))
    return total / jnp.maximum(jnp.sum(weights), 1.0)


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
    the loader's row ``mask``): every shard's loss is its own mean, the
    gradient the mean of the shards'. Decoupled weight decay on the leaves
    of two or more axes, as the program masks it. Also returns Adam's first
    moment after the first step. The weights, one set of gradients and both
    moments live on the device; the first moment after step 1 and, a leaf at
    a time, the weights after the last step come to the host."""
    if optimizer["name"] != "adamw":
        raise ValueError(
            f"nemotron3-super follows adamw, not {optimizer['name']}")
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
    tokens, by part. Visible pairs counted exactly; the scan as its chunked
    form's products (``chipbench/ssd_costs.py``); routed work as
    ``num_experts_per_tok * held / published`` experts a token and block,
    which is what a router that favours no expert sends here; the embedding
    is a lookup and the convolution four multiplies a channel."""
    from chipbench import ssd_costs

    c, d = arch["hidden_size"], arch["head_dim"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    inner, bc, h = mamba_widths(arch)
    lat = arch["moe_latent_size"]
    routed_share = (arch["num_experts_per_tok"] * arch["n_routed_experts"]
                    / routed_experts(arch))
    parts = dict.fromkeys((
        "mamba_projections", "scan", "attention_projections", "attention",
        "router_and_latent", "routed", "shared", "head"), 0.0)
    for kind in pattern(arch):
        if kind == "M":
            parts["mamba_projections"] += t * (
                c * (2 * inner + 2 * bc + h) + inner * c
                + arch["conv_kernel"] * (inner + 2 * bc))
            parts["scan"] += ssd_costs.scan_forward_macs(
                tokens=t, heads=h, head_dim=arch["mamba_head_dim"],
                groups=arch["n_groups"], state=arch["ssm_state_size"],
                chunk=arch["chunk_size"])
        elif kind == "*":
            parts["attention_projections"] += t * c * 2 * (heads + kv) * d
            parts["attention"] += heads * (t * (t + 1) // 2) * 2 * d
        else:
            parts["router_and_latent"] += t * c * (
                routed_experts(arch) + 2 * lat)
            parts["routed"] += (t * routed_share * 2 * lat
                                * arch["moe_intermediate_size"])
            parts["shared"] += (
                t * 2 * c * arch["moe_shared_expert_intermediate_size"])
    parts["head"] = t * c * arch["vocab_size"]
    return parts


def train_flops_per_example(arch, traffic) -> float:
    """Required FLOPs of training on one sequence: two a multiply-accumulate,
    three passes (forward, backward by input, backward by weight); no
    recomputation counted."""
    t = int(traffic["dataset"]["seq_len"])
    return 3.0 * 2.0 * sum(forward_macs_by_part(arch, t).values())
