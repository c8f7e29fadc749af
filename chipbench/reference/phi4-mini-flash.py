"""Plain reference for ``phi4-mini-flash``: Microsoft's
Phi-4-mini-flash-reasoning
(``https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json``,
``model_type`` ``phi4flash``), the SambaY decoder-hybrid-decoder
(arXiv:2507.06607) in ``jax.numpy``, float32 at ``highest``, trained on
next-token prediction under AdamW. Imports nothing of the program.

The model. ``L`` = ``num_hidden_layers`` 32 layers on a hidden size of 2560,
LayerNorm with scale and bias (``layer_norm_eps``), no other bias but the
convolution's and ``dt``'s, no position encoding, the head tied to the
embedding:

    h = embed(tokens)
    h = h + Mixer_l(LN(h));  h = h + (silu(g) * v) W_down,  [g, v] = LN(h) W_gate_up
    logits = LN(h) embed^T

The mixer by the published layer index ``l`` (``mb_per_layer`` 2):

*``l`` even, ``l <= L/2``, Mamba-1* (``_mamba``): ``[x, z] = u W_in`` (2560
-> 2 x 5120); ``x = silu(causal depthwise conv(x) + b)``; ``[r, B, C] = x
W_x`` (5120 -> 160 + 16 + 16); ``dt = softplus(r W_dt + b_dt)``; ``A =
-exp(A_log)`` (5120 x 16); ``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T``;
``y_t = S_t C_t + D x_t``; out ``(y * silu(z)) W_out``. ``S``, ``dt``, ``A``
and ``D`` in float32 whatever the precision. Run one position at a time
(``_recurrence``). Layer ``L/2`` hands on ``m = y``, before the gate.

*``l`` odd, ``l < L/2``, differential attention under ``sliding_window``;
``l = L/2 + 1`` the same, full causal, and it hands on its keys and values*
(``_attention``): ``[q, k, v] = u W_qkv`` (2560 -> 40 x 64 + 2 x 20 x 64);
query heads in two sets (even heads, odd heads) of 20, key and value heads
in two sets of 10; query pair ``j`` reads key-value pair ``j // 2``; ``V =
[v1 ; v2]`` (128 wide);

    a1 = softmax(q1 k1^T / 8 + mask) V;  a2 = softmax(q2 k2^T / 8 + mask) V
    o = RMSNorm_128(a1 - lambda a2) * w * (1 - lambda_init)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 l)

out ``concat(o) W_o``.

*``l`` even, ``l >= L/2 + 2``, Gated Memory Unit*: ``(m * silu(u W_1)) W_2``.

*``l`` odd, ``l >= L/2 + 3``, cross-attention*: ``q = u W_q``, layer ``L/2 +
1``'s keys and values, full causal, the differential form with this layer's
own ``lambda`` vectors and norm; ``W_o``.

**What the config does not settle** (the configuration file lists the same
under ``assumed``):

1. Mamba's sizes: ``mamba_d_state`` 16, ``mamba_d_conv`` 4, ``mamba_expand``
   2, ``mamba_dt_rank`` ceil(2560 / 16) = 160, a bias on the convolution and
   none on the projections (the ``phi4flash`` configuration class's
   defaults; arXiv:2312.00752).
2. Which layer is of which kind (``modeling_phi4flash.py``): Mamba where
   ``l % mb_per_layer == 0``; from ``L/2`` on the scan hands on its output,
   from ``L/2 + 1`` the attention its keys and values, from ``L/2 + 2``
   layers read them. The memory is taken before the gate.
3. Differential attention, its head pairing, ``lambda_init`` and the norm
   over a pair (arXiv:2410.05258, the flash form; SambaY's abstract names
   it for this model); ``head_dim`` = ``hidden_size / num_attention_heads``.
4. The window on every self-attention layer before ``L/2`` and none on
   ``L/2 + 1``; no positions.
5. Initialisation, the benchmark's own: every matrix N(0, 1 / fan-in), the
   convolution's taps N(0, 1 / 4) and its bias N(0, 0.1 ** 2), the embedding
   N(0, 1 / hidden) (the head is tied: the logits are then of order one),
   norm scales and ``D`` 1, norm biases 0, ``A_log = log(1..16)`` a channel,
   ``b_dt`` the inverse softplus of U[1e-3, 0.1], ``lambda`` vectors N(0,
   0.1 ** 2).
6. AdamW as the program's ``make_optimizer`` builds it (``follow``).

**The cut** (``model-configs`` guide, section 4). Layers are held whole;
``layers_here`` layers from published layer ``first_layer`` up, and
``vocab_size`` rows of the tied embedding. ``lambda_init`` goes by the
published index.

``LEFT_OUT`` names pieces a control leaves out of the model (the second
softmax: ``lambda`` 0; the memory: ones in its place): nothing in a
benchmark run sets it.

Attention runs in blocks of query rows, the MLP and the other row-wise
products in blocks of positions, the recurrence in segments, each
recomputed in the backward pass. ``train_flops_per_example`` is a function
of shapes.
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
#: the head is tied: the embedding's gradient is the head's plus the
#: lookup's, which has the whole backward pass behind it. Nothing but the
#: head lies behind the final LayerNorm's scale and bias
OUTPUT_LEAVES = ("final_norm.scale", "final_norm.bias")
#: query rows of one attention block, at most
Q_BLOCK = 128
#: positions of one recomputed segment of the recurrence, at most
SEGMENT = 128
#: positions of one block of a row-wise product, at most
ROW_BLOCK = 2048
#: positions whose logits are held at once by ``sequence_loss``, at most
LOSS_BLOCK = 1024
#: pieces of the model a control leaves out: ``second_softmax``, ``memory``
LEFT_OUT = frozenset()
#: standard deviation of the leaves drawn N(0, scale ** 2), by kind
SCALES = {"small": 0.1}
#: the range ``dt``'s first steps are drawn from (assumed 5)
DT_RANGE = (1e-3, 0.1)


# -- sizes -------------------------------------------------------------------

def layer_kinds(arch) -> list:
    """``(published index, kind)`` of each layer held here."""
    half, every = arch["num_hidden_layers"] // 2, arch["mb_per_layer"]
    out = []
    for l in range(arch["first_layer"],
                   arch["first_layer"] + arch["layers_here"]):
        if l % every == 0:
            out.append((l, "mamba" if l <= half else "gmu"))
        else:
            out.append((l, "attention" if l <= half + 1 else "cross"))
    return out


def mamba_widths(arch):
    """(channels, state, rank of ``dt``)."""
    return (arch["mamba_expand"] * arch["hidden_size"],
            arch["mamba_d_state"], arch["mamba_dt_rank"])


def lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def param_shapes(arch) -> dict:
    """leaf -> (shape, kind of init)."""
    c, f, d = arch["hidden_size"], arch["intermediate_size"], arch["head_dim"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    inner, n, rank = mamba_widths(arch)
    shapes = {"embed": ((arch["vocab_size"], c), "rows")}
    lambdas = {f"mixer.lambda_{a}": ((d,), "small")
               for a in ("q1", "k1", "q2", "k2")}
    lambdas["mixer.sub_norm"] = ((2 * d,), "ones")
    for i, (_, kind) in enumerate(layer_kinds(arch)):
        if kind == "mamba":
            own = {
                "mixer.in_proj": ((c, 2 * inner), "lecun"),
                "mixer.conv_kernel": ((arch["mamba_d_conv"], inner), "lecun"),
                "mixer.conv_bias": ((inner,), "small"),
                "mixer.x_proj": ((inner, rank + 2 * n), "lecun"),
                "mixer.dt_proj": ((rank, inner), "lecun"),
                "mixer.dt_bias": ((inner,), "time_step"),
                "mixer.A_log": ((inner, n), "log_states"),
                "mixer.D": ((inner,), "ones"),
                "mixer.out_proj": ((inner, c), "lecun"),
            }
        elif kind == "attention":
            own = {"mixer.qkv": ((c, (heads + 2 * kv) * d), "lecun"),
                   "mixer.o": ((heads * d, c), "lecun"), **lambdas}
        elif kind == "gmu":
            own = {"mixer.in_proj": ((c, inner), "lecun"),
                   "mixer.out_proj": ((inner, c), "lecun")}
        else:
            own = {"mixer.q": ((c, heads * d), "lecun"),
                   "mixer.o": ((heads * d, c), "lecun"), **lambdas}
        own.update({
            "mixer_norm.scale": ((c,), "ones"),
            "mixer_norm.bias": ((c,), "zeros"),
            "mlp_norm.scale": ((c,), "ones"),
            "mlp_norm.bias": ((c,), "zeros"),
            "mlp.gate_up": ((c, 2 * f), "lecun"),
            "mlp.down": ((f, c), "lecun"),
        })
        shapes.update({f"layer_{i}.{k}": v for k, v in own.items()})
    shapes.update({"final_norm.scale": ((c,), "ones"),
                   "final_norm.bias": ((c,), "zeros")})
    return shapes


def init_params(arch, seed: int) -> dict:
    """Seeded float32 weights, one jitted call (assumed 5)."""
    shapes = param_shapes(arch)

    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if kind in ("ones", "zeros"):
                out[name] = jnp.full(shape, float(kind == "ones"),
                                     jnp.float32)
            elif kind in SCALES:
                out[name] = SCALES[kind] * jax.random.normal(
                    k, shape, jnp.float32)
            elif kind == "log_states":
                out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=jnp.float32)), shape)
            elif kind == "time_step":
                step = jax.random.uniform(k, shape, jnp.float32, *DT_RANGE)
                out[name] = step + jnp.log(-jnp.expm1(-step))
            else:
                fan_in = {"rows": shape[1], "lecun": shape[0]}[kind]
                out[name] = jax.random.normal(
                    k, shape, jnp.float32) / math.sqrt(fan_in)
        return out

    return jax.jit(make)(jax.random.key(seed))


#: leaves the program holds as bare parameters, not as a ``kernel``
BARE = ("conv_kernel", "conv_bias", "dt_bias", "A_log", "D", "sub_norm",
        "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "scale", "bias")


def program_names(arch) -> dict:
    """reference leaf -> path in ``tpu_ddp.models.sambay.SambaYDecoder``."""
    names = {}
    for leaf in param_shapes(arch):
        path = tuple(leaf.split("."))
        if leaf == "embed":
            names[leaf] = ("embed", "embedding")
        elif path[-1] in BARE:
            names[leaf] = path
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


def _divisor(t: int, most: int) -> int:
    return max(n for n in range(1, min(most, t) + 1) if t % n == 0)


def by_rows(fn, *arrays):
    """``fn`` over blocks of at most ``ROW_BLOCK`` positions of (B, T, ...)
    arrays, each block recomputed in the backward pass; ``fn`` returns an
    array or a tuple of them, (B, rows, ...)."""
    b, t = arrays[0].shape[:2]
    per = _divisor(t, ROW_BLOCK)
    cut = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((b, t // per, per) + a.shape[2:]), 1, 0)
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(  # noqa: E731
        (b, t) + a.shape[3:])
    out = lax.map(jax.checkpoint(lambda parts: fn(*parts)),
                  tuple(map(cut, arrays)))
    return jax.tree.map(join, out)


def layer_norm(x, scale, bias, eps, precision):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return C.hold((x32 - mean) * lax.rsqrt(var + eps) * scale + bias,
                  precision)


def causal_conv(x, kernel, bias):
    """``y_t = sum_k kernel[k] x_{t - (K - 1) + k} + bias`` a channel, zeros
    before the sequence; float32."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, k:k + t] * kernel[k] for k in range(taps)) + bias


def _recurrence(x, dt, A, B, C_):
    """``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T``, ``y_t = S_t C_t``,
    one position at a time in float32: ``x`` and ``dt`` (b, t, channels),
    ``A`` (channels, n), ``B`` and ``C_`` (b, t, n). Segments of ``SEGMENT``
    positions, each recomputed in the backward pass, so that only the states
    between segments are held."""
    b, t, channels = x.shape
    per = _divisor(t, SEGMENT)

    def step(state, now):
        xt, dtt, bt, ct = now
        state = (jnp.exp(dtt[..., None] * A) * state
                 + (dtt * xt)[..., None] * bt[:, None, :])
        return state, jnp.sum(state * ct[:, None, :], axis=-1)

    @jax.checkpoint
    def segment(state, part):
        return lax.scan(step, state, part)

    parts = tuple(
        jnp.moveaxis(a.astype(jnp.float32), 1, 0).reshape(
            (t // per, per) + a.shape[:1] + a.shape[2:])
        for a in (x, dt, B, C_))
    _, y = lax.scan(segment, jnp.zeros((b,) + A.shape, jnp.float32), parts)
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def _mamba(arch, p, u, precision):
    """(the mixer's output, ``y`` before the gate)."""
    inner, n, rank = mamba_widths(arch)
    x, z = by_rows(lambda rows: tuple(jnp.split(
        _dot(rows, p["in_proj"], precision), 2, axis=-1)), u)
    x = C.hold(jax.nn.silu(causal_conv(x, p["conv_kernel"], p["conv_bias"])),
               precision)

    def steps(rows):
        low, B, C_ = jnp.split(_dot(rows, p["x_proj"], precision),
                               [rank, rank + n], axis=-1)
        dt = jax.nn.softplus(
            _dot(low, p["dt_proj"], precision).astype(jnp.float32)
            + p["dt_bias"])
        return dt, B, C_

    dt, B, C_ = by_rows(steps, x)
    y = _recurrence(x, dt, -jnp.exp(p["A_log"]), B, C_)
    y = C.hold(y + p["D"] * x.astype(jnp.float32), precision)
    out = by_rows(lambda rows, gate: _dot(
        C.hold(rows.astype(jnp.float32)
               * jax.nn.silu(gate.astype(jnp.float32)), precision),
        p["out_proj"], precision), y, z)
    return out, y


def _gmu(p, u, memory, precision):
    def rows(u, m):
        gate = jax.nn.silu(_dot(u, p["in_proj"], precision).astype(
            jnp.float32))
        return _dot(C.hold(m.astype(jnp.float32) * gate, precision),
                    p["out_proj"], precision)

    return by_rows(rows, u, memory)


def _sets(a):
    """(even heads, odd heads) of (B, T, heads, d)."""
    return a[:, :, 0::2], a[:, :, 1::2]


def differential_attention(q, k, v, lam, window, precision):
    """``a1 - lam * a2`` (B, T, heads / 2, 2 d) of (B, T, heads, d) queries
    against (B, T, kv, d) keys and values (module docstring), a block of
    query rows at a time against every key, masked above the diagonal and
    below the window, two explicit softmaxes; each block recomputed in the
    backward pass."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    prec = C.PRECISIONS[precision][2]
    (q1, q2), (k1, k2) = _sets(q), _sets(k)
    value = jnp.concatenate(_sets(v), axis=-1)        # (B, T, kv / 2, 2 d)
    rows_per = _divisor(t, Q_BLOCK)
    blocks, pairs, group = t // rows_per, kv // 2, h // kv
    cut = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(b, blocks, rows_per, pairs, group, d), 1, 0)
    cols = jnp.arange(t)

    def attend(qb, keys, vis):
        s = jnp.einsum("bqkgd,bskd->bkgqs", C._operand(qb, precision),
                       C._operand(keys, precision), precision=prec,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        p = C.hold(jax.nn.softmax(jnp.where(vis, s, -jnp.inf), axis=-1),
                   precision)
        return C._contracted(jnp.einsum(
            "bkgqs,bskd->bqkgd", C._operand(p, precision),
            C._operand(value, precision), precision=prec), precision)

    @jax.checkpoint
    def block(args):
        qb1, qb2, i = args
        rows = i * rows_per + jnp.arange(rows_per)
        vis = cols[None, :] <= rows[:, None]
        if window:
            vis = jnp.logical_and(vis, cols[None, :] > rows[:, None] - window)
        a1 = attend(qb1, k1, vis).astype(jnp.float32)
        if "second_softmax" in LEFT_OUT:
            return a1
        return a1 - lam * attend(qb2, k2, vis).astype(jnp.float32)

    out = lax.map(block, (cut(q1), cut(q2), jnp.arange(blocks)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h // 2, 2 * d)


def _attention(arch, p, u, l, window, precision, handed=None):
    """(the mixer's output, its keys, its values); ``handed`` = (keys,
    values) of the layer a cross-attention layer reads."""
    b, t, _ = u.shape
    d = arch["head_dim"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    if handed is None:
        q, k, v = jnp.split(_dot(u, p["qkv"], precision),
                            [heads * d, (heads + kv) * d], axis=-1)
        k, v = k.reshape(b, t, kv, d), v.reshape(b, t, kv, d)
    else:
        q = _dot(u, p["q"], precision)
        k, v = handed
    first = lambda_init(l)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + first)
    o = differential_attention(q.reshape(b, t, heads, d), k, v, lam, window,
                               precision)
    o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                      + arch["layer_norm_eps"])
    o = C.hold(o * p["sub_norm"] * (1.0 - first), precision)
    out = by_rows(lambda rows: _dot(rows, p["o"], precision),
                  o.reshape(b, t, heads * d))
    return out, k, v


def _mlp(arch, p, h, precision):
    """``h + MLP(LN(h))``, a block of positions at a time."""
    def rows(h):
        u = layer_norm(h, p["mlp_norm.scale"], p["mlp_norm.bias"],
                       arch["layer_norm_eps"], precision)
        g, v = jnp.split(_dot(u, p["mlp.gate_up"], precision), 2, axis=-1)
        return h + _dot(jax.nn.silu(g) * v, p["mlp.down"], precision)

    return by_rows(rows, h)


def hidden(arch, params, tokens, precision="float32_highest"):
    """The stack's output after the final norm, (B, T, hidden)."""
    eps = arch["layer_norm_eps"]
    half = arch["num_hidden_layers"] // 2
    h = C.hold(params["embed"][tokens], precision)

    def layer(h, p, l, kind, memory, handed):
        """(h, what the layer hands on): one layer, recomputed in the
        backward pass."""
        u = layer_norm(h, p["mixer_norm.scale"], p["mixer_norm.bias"], eps,
                       precision)
        own = {k.split(".", 1)[1]: v for k, v in p.items()
               if k.startswith("mixer.")}
        out_of_it = None
        if kind == "mamba":
            out, y = _mamba(arch, own, u, precision)
            out_of_it = y if l == half else None
        elif kind == "gmu":
            out = _gmu(own, u, memory, precision)
        elif kind == "attention":
            out, k, v = _attention(
                arch, own, u, l, arch["sliding_window"] if l < half else 0,
                precision)
            out_of_it = (k, v) if l == half + 1 else None
        else:
            out, _, _ = _attention(arch, own, u, l, 0, precision, handed)
        return _mlp(arch, p, h + out, precision), out_of_it

    memory = handed = None
    for i, (l, kind) in enumerate(layer_kinds(arch)):
        own = {k.split(".", 1)[1]: v for k, v in params.items()
               if k.startswith(f"layer_{i}.")}
        if kind == "gmu" and "memory" in LEFT_OUT:
            memory = jnp.ones_like(memory)
        h, out_of_it = jax.checkpoint(layer, static_argnums=(2, 3))(
            h, own, l, kind, memory if kind == "gmu" else None,
            handed if kind == "cross" else None)
        if l == half:
            memory = out_of_it
        elif l == half + 1:
            handed = out_of_it
    return layer_norm(h, params["final_norm.scale"],
                      params["final_norm.bias"], eps, precision)


def forward(arch, params, tokens, precision="float32_highest"):
    """Logits (B, T, vocab rows held) in float32; the head is the
    embedding."""
    x = hidden(arch, params, tokens, precision)
    return _dot(x, params["embed"].T, precision).astype(jnp.float32)


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
    in the backward pass (the float32 logits of 16,384 positions are 1.6
    GB). The last position has no target: it is weighed zero, not cut off,
    so that the positions divide into blocks."""
    x = hidden(arch, params, tokens, precision)
    b, t, c = x.shape
    targets = jnp.roll(tokens, -1, axis=1)
    weights = mask.astype(jnp.float32).at[:, 0].set(0.0)
    weights = jnp.roll(weights, -1, axis=1)
    per = _divisor(t, LOSS_BLOCK)
    cut = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((b, t // per, per) + a.shape[2:]), 1, 0)
    head = params["embed"].T

    @jax.checkpoint
    def block(part):
        xb, tb, wb = part
        logits = _dot(xb, head, precision).astype(jnp.float32)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits), tb[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum(nll * wb)

    total = jnp.sum(lax.map(block, (cut(x), cut(targets), cut(weights))))
    return total / jnp.maximum(jnp.sum(weights), 1.0)


def target_mask(batch):
    """(B, T) bool: which tokens are real targets; a row the loader padded
    the epoch's last batch with (``mask`` False) has none."""
    return np.logical_and(batch["loss_mask"], batch["mask"][:, None])


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
    moment after the first step. The weights and one set of gradients live
    on the device; both moments wait on the host while a gradient is taken
    and visit the device a leaf at a time for the update (with them the
    device would hold 11.2 GB before the first activation, and a Mamba
    layer's backward pass here takes some 3 GB: 16,384 positions by 5120
    channels are 336 MB a float32 tensor); a leaf at a time, the weights
    after the last step come to the host."""
    if optimizer["name"] != "adamw":
        raise ValueError(
            f"phi4-mini-flash follows adamw, not {optimizer['name']}")
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

    params = {k: jnp.asarray(v, jnp.float32)
              for k, v in check["params0"].items()}
    mu = {k: np.zeros(v.shape, np.float32) for k, v in params.items()}
    nu = {k: np.zeros(v.shape, np.float32) for k, v in params.items()}
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
                params[k], first, second = adamw(
                    params[k], grads.pop(k) / shards, jnp.asarray(mu[k]),
                    jnp.asarray(nu[k]), float(step))
                mu[k], nu[k] = np.asarray(first), np.asarray(second)
            if mu_first is None:
                mu_first = dict(mu)  # a step replaces a moment, never writes
            losses.append(loss_sum / shards)
            print(f"chipbench: reference: step {step} loss {losses[-1]!r}",
                  flush=True)
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

def visible_pairs(t: int, window: int) -> int:
    if not window or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def forward_flops_by_part(arch, t: int) -> dict:
    """FLOPs of one forward pass over one sequence of ``t`` tokens, by part:
    two a multiply-accumulate of a matrix product; attention by visible
    pairs, two score products and two value products (twice a head wide) a
    pair of query heads; the scan six a (position, channel, state): the
    decay's product and exponential, the state's two products and sum, the
    output's product and sum; the convolution its taps a channel; the
    embedding is a lookup."""
    c, f, d = arch["hidden_size"], arch["intermediate_size"], arch["head_dim"]
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    inner, n, rank = mamba_widths(arch)
    parts = dict.fromkeys((
        "mamba_projections", "scan", "attention_projections", "attention",
        "gmu", "mlp", "head"), 0.0)
    for l, kind in layer_kinds(arch):
        if kind == "mamba":
            parts["mamba_projections"] += 2.0 * t * (
                c * 2 * inner + arch["mamba_d_conv"] * inner
                + inner * (rank + 2 * n) + rank * inner + inner * c)
            parts["scan"] += 6.0 * t * inner * n
        elif kind == "gmu":
            parts["gmu"] += 2.0 * t * 2 * c * inner
        else:
            projected = (heads + 2 * kv if kind == "attention" else heads) * d
            parts["attention_projections"] += 2.0 * t * c * (
                projected + heads * d)
            window = (arch["sliding_window"]
                      if l < arch["num_hidden_layers"] // 2 else 0)
            parts["attention"] += 2.0 * (heads // 2) * visible_pairs(
                t, window) * (2 * d + 2 * 2 * d)
        parts["mlp"] += 2.0 * t * 3 * c * f
    parts["head"] = 2.0 * t * c * arch["vocab_size"]
    return parts


def train_flops_per_example(arch, traffic) -> float:
    """Required FLOPs of training on one sequence: three passes (forward,
    backward by input, backward by weight); no recomputation counted."""
    t = int(traffic["dataset"]["seq_len"])
    return 3.0 * sum(forward_flops_by_part(arch, t).values())
