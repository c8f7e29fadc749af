"""Sparse decoder: pre-norm layers of grouped-query attention (full or
windowed, a head count and rotary settings per layer, a head-wise output
gate) and a feed-forward that is a dense SwiGLU or routed experts with a
shared expert (``models/moe.py::DroplessMoE``); RMSNorm, no biases, untied
embedding and head. One flax module, ``SparseDecoder``, described by a
``DecoderSpec``; ``laguna_xs2`` registers poolside's Laguna-XS.2 at its
published sizes, ``joyai_llm_flash`` JD's JoyAI-LLM-Flash and
``sdar_30b_a3b`` JetLM's SDAR-30B-A3B-Chat at theirs.

    y = x + Attn(RMSNorm(x));  z = y + FFN(RMSNorm(y));  head(RMSNorm(z_last))

A layer whose ``LayerSpec`` carries ``latent`` widths has latent attention
in place of the grouped-query kind (``LatentAttention``: queries and
key-values through low-rank projections, keys of 192 over values of 128,
one rotary key shared by all heads). A ``DecoderSpec`` with ``mtp`` grows
DeepSeek-V3's multi-token-prediction module after the stack (one more layer
over ``W_eh [RMSNorm(h_i); RMSNorm(Emb(t_{i+1}))]``, predicting ``t_{i+2}``
through the stack's own final norm and head); the model then returns
``(logits, mtp_logits)`` and trains on ``next_token_mtp``: ``L_next +
mtp_weight * L_mtp``.

``GroupedQueryAttention`` is also the attention mixer of the hybrid stack
(``models/hybrid.py``), whose blocks have one mixer each: a ``LayerSpec``
with ``rotary`` None rotates nothing, one with ``gate`` False has no output
gate, and its ``heads`` and the ``kv_heads`` beside it are the heads held
here (``parallel.expert_parallel.HeadShare``), whichever of the published
ones those are.

A ``DecoderSpec`` with ``diffusion`` (``BlockDiffusion``: block length,
the mask token's id, the least noise level) is a block-diffusion model
(BD3-LMs, arXiv:2503.09573): it reads ``[x ‖ x~]``, a sequence of L tokens
and its noised copy after it, 2L positions, which its task builds inside
the step; position ``i`` has the rotary angle of ``i mod L``; attention is
the block-diffusion visibility (``ops/flash_attention.py``, ``diffusion``),
under the module scope ``attention_block``; the final norm and the head run
on the noisy half only, so the logits are (B, L, rows held), unshifted:
position ``i`` predicts token ``i``. ``qk_norm`` gives queries and keys an
RMSNorm over a head's width (one learned scale each, shared by the heads)
before the rotary turn; ``router_score`` says what the router makes of its
logits (``sigmoid``, or a ``softmax`` over all experts).

Input ``tokens`` (B, T) int32, output float32 logits (B, T, vocabulary rows
held). The sequence length is the data's. What the model trains on is its
``task`` (``train/tasks.py``): tokens in and a masked next-token loss out
(with a second term where there is a prediction module), or, for a
block-diffusion model, ``block_diffusion``: the noise drawn in the step and
a 1/t-weighted loss at the masked positions.

One chip of an expert-parallel deployment holds a share of each layer:
``experts_held`` of the routed experts from ``expert_offset`` up
(``parallel.expert_parallel.ExpertShare``), ``vocab_rows`` of the embedding
and the head, and the first ``num_layers`` layers. Those are the registry
factory's overrides (``TrainConfig.model_overrides``); every width stays.

Parameters are float32; ``dtype`` is what activations and matrix-unit
operands are held in. The router, normalisation statistics and the rotary
tables are float32 whatever ``dtype``; the head's logits come out float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tpu_ddp.models.moe import (IDS_NAME, LOGITS_NAME, ROUTED_NAME,
                                SCORES_NAME, DroplessMoE, SwiGLU)
from tpu_ddp.models.zoo import register
from tpu_ddp.ops.flash_attention import LSE_NAME, OUT_NAME
from tpu_ddp.ops.selective_scan import CKPT_NAME, Y_NAME
from tpu_ddp.telemetry.phases import module_scope

#: What a recomputed layer or block keeps from its forward pass, by name
#: (``checkpoint_name``), because its backward pass would otherwise make it
#: again with a kernel call: the flash kernels' output and one float32 a row
#: of their logsumexp (``ops/flash_attention.py::_fwd``), so that no
#: forward kernel runs in the backward pass; an expert layer's routed
#: result, and under a selection bias its router's float32 logits, chosen
#: ids and their scores (``models/moe.py``); a selective scan's output and
#: the state at each time block's start (``ops/selective_scan.py``: what its
#: forward kernel writes, so that it too runs once). A name a model does not
#: produce is simply absent, and one that no backward rule reads (a sparse
#: layer's routed result: it is only added to the residual stream) is not
#: kept by the compiler, so one list serves every stack.
KEPT_NAMES = (OUT_NAME, LSE_NAME, ROUTED_NAME, LOGITS_NAME, IDS_NAME,
              SCORES_NAME, Y_NAME, CKPT_NAME)


def recomputed(module_cls):
    """``module_cls`` recomputed in the backward pass, all but
    ``KEPT_NAMES``: what ``remat: true`` means to the decoder stacks. Their
    layers are functions of the stream alone; ``models/sambay.py``'s take
    and hand on further tensors through the same wrapper."""
    return nn.remat(module_cls, policy=jax.checkpoint_policies
                    .save_only_these_names(*KEPT_NAMES))


@dataclasses.dataclass(frozen=True)
class Rotary:
    """Rotary position settings of one kind of layer: ``dims`` leading
    dimensions of a head are rotated (half against half), frequencies
    ``theta ** (-2i / dims)``. ``yarn`` = (factor, original positions,
    beta_fast, beta_slow, cos/sin scale) blends each frequency between itself
    and itself over ``factor`` (arXiv:2309.00071), computed once for every
    length."""

    dims: int
    theta: float
    yarn: Optional[Tuple[float, int, float, float, float]] = None

    def tables(self, length: int):
        """(cos, sin), each (length, dims / 2) float32; float64 on the host,
        so that position 8,191 is as exact as position 0."""
        exponents = np.arange(0, self.dims, 2, dtype=np.float64) / self.dims
        inv_freq = self.theta ** -exponents
        scale = 1.0
        if self.yarn is not None:
            factor, original, beta_fast, beta_slow, scale = self.yarn

            def turns_at(rotations):
                return self.dims * math.log(
                    original / (rotations * 2 * math.pi)) / (
                        2 * math.log(self.theta))

            low = max(math.floor(turns_at(beta_fast)), 0)
            high = min(math.ceil(turns_at(beta_slow)), self.dims - 1)
            ramp = np.clip(
                (np.arange(self.dims // 2) - low) / max(high - low, 1e-3),
                0.0, 1.0)
            inv_freq = (inv_freq / factor) * ramp + inv_freq * (1 - ramp)
        angles = np.arange(length, dtype=np.float64)[:, None] * inv_freq
        return (jnp.asarray(np.cos(angles) * scale, jnp.float32),
                jnp.asarray(np.sin(angles) * scale, jnp.float32))


def rotate(x, cos, sin):
    """``x`` (B, T, H, D): its first ``2 * cos.shape[-1]`` dimensions rotated,
    the rest passed; in float32, back in ``x``'s type."""
    half = cos.shape[-1]
    x32 = x.astype(jnp.float32)
    a, b, rest = x32[..., :half], x32[..., half:2 * half], x32[..., 2 * half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """Widths of latent attention (DeepSeek-V2's MLA, arXiv:2405.04434): the
    ranks of the query and of the joint key-value compression, a head's
    part without positions and its rotary part (a query and a key are the
    two side by side), and a value head."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    heads: int           # query heads
    window: int          # 0 = full causal attention
    rotary: Optional[Rotary]  # None = no position encoding in attention
    sparse: bool         # routed experts + shared expert, else dense SwiGLU
    gate: bool = True    # the head-wise output gate
    #: latent attention of these widths, and not grouped-query attention
    latent: Optional[LatentSpec] = None


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """Settings of block-diffusion training: tokens a block, the id that
    stands for a masked token (inside the vocabulary rows held; the data
    never holds it), and the least noise level a block draws."""

    block: int
    mask_id: int
    t_min: float = 1e-3


@dataclasses.dataclass(frozen=True)
class DecoderSpec:
    vocab_rows: int
    hidden: int
    head_dim: int
    kv_heads: int
    layers: Tuple[LayerSpec, ...]
    dense_width: int
    num_experts: int     # outputs of the router
    experts_held: int
    expert_offset: int
    top_k: int
    expert_width: int
    shared_width: int
    routed_scaling: float
    norm_eps: float = 1e-6
    #: the router chooses by its scores plus a bias that no gradient reaches
    selection_bias: bool = False
    #: the multi-token-prediction module's one layer, or None for no module
    mtp: Optional[LayerSpec] = None
    #: weight of the module's term in the loss
    mtp_weight: float = 0.0
    #: an RMSNorm over a head's width on queries and on keys, before the
    #: rotary turn (grouped-query layers)
    qk_norm: bool = False
    #: what the router makes of its float32 logits: ``sigmoid`` or ``softmax``
    router_score: str = "sigmoid"
    #: a block-diffusion model's settings, or None for a causal decoder
    diffusion: Optional[BlockDiffusion] = None


def reference_attention(q, k, v, *, causal=True, window=0, diffusion=None):
    """Fused jnp attention (``ops/flash_attention._reference``): every score
    at once, for sequences a CPU test can hold. ``diffusion`` is a
    visibility of its own, in place of causal."""
    from tpu_ddp.ops.flash_attention import _reference

    return _reference(q, k, v, causal=causal and diffusion is None,
                      window=window, diffusion=diffusion)


class GroupedQueryAttention(nn.Module):
    """Causal attention of ``spec.heads`` query heads over ``kv_heads``
    shared key-value heads, an optional window, and, as ``spec`` says,
    rotary positions (``cos``, ``sin`` of ``spec.rotary``'s tables) and a
    head-wise output gate ``o_h = sigmoid(x w_h) * attn_h`` before the output
    projection. A share of the heads is the same module with fewer of them:
    its output is then partial, the other shares' to add to. ``qk_norm`` =
    an epsilon: queries and keys are normalised over a head's width
    (``q_norm``, ``k_norm``) before they are turned. ``diffusion_block`` =
    B > 0: ``x`` is ``[clean ‖ noisy]`` and the visibility the
    block-diffusion one over halves of ``T / 2`` in blocks of B, in place of
    causal."""

    spec: LayerSpec
    kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.float32
    attention_impl: Optional[Callable] = None
    qk_norm: Optional[float] = None
    diffusion_block: int = 0

    @nn.compact
    def __call__(self, x, cos=None, sin=None):
        B, T, C = x.shape
        H, KV, D = self.spec.heads, self.kv_heads, self.head_dim
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        turn = (lambda a: a) if self.spec.rotary is None else (
            lambda a: rotate(a, cos, sin))

        def head_norm(a, name):
            if self.qk_norm is None:
                return a
            return nn.RMSNorm(epsilon=self.qk_norm, dtype=self.dtype,
                              name=name)(a)

        q = turn(head_norm(dense(H * D, "q")(x).reshape(B, T, H, D),
                           "q_norm"))
        k = turn(head_norm(dense(KV * D, "k")(x).reshape(B, T, KV, D),
                           "k_norm"))
        v = dense(KV * D, "v")(x).reshape(B, T, KV, D)
        attend = self.attention_impl or reference_attention
        if self.diffusion_block:
            kind = "attention_block"
            how = dict(diffusion=(T // 2, self.diffusion_block))
        else:
            kind = ("attention_window" if self.spec.window
                    else "attention_full")
            how = dict(causal=True, window=self.spec.window)
        with jax.named_scope(module_scope(kind)):
            o = attend(q, k, v, **how)
        if self.spec.gate:
            o = o * nn.sigmoid(dense(H, "gate")(x))[..., None]
        return dense(C, "o")(o.reshape(B, T, H * D))


class LatentAttention(nn.Module):
    """Causal latent attention of ``spec.heads`` heads by ``spec.latent``'s
    widths:

        c_q = RMSNorm(x W_qa);  q_h = c_q W_qb = [q_nope_h, q_rope_h]
        [c_kv, r] = x W_kva;  [k_nope_h, v_h] = RMSNorm(c_kv) W_kvb
        k_h = [k_nope_h, rot(r)];  o_h = softmax(rot(q_h) k_h^T / sqrt(d)) v_h

    ``rot`` turns the rotary part only; ``r`` is one key head that all heads
    share, broadcast over them before the kernel is called (a kernel that
    reads it once is not written yet). Keys are ``nope_dim + rope_dim`` wide
    and values ``v_dim``: ``attention_impl`` takes the two widths."""

    spec: LayerSpec
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    attention_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        B, T, C = x.shape
        H, w = self.spec.heads, self.spec.latent
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=self.norm_eps, dtype=self.dtype, name=name)
        with jax.named_scope(module_scope("mla_q")):
            q = dense(H * (w.nope_dim + w.rope_dim), "q_b")(
                norm("q_norm")(dense(w.q_rank, "q_a")(x)))
            q = q.reshape(B, T, H, w.nope_dim + w.rope_dim)
        with jax.named_scope(module_scope("mla_kv")):
            c_kv, r = jnp.split(dense(w.kv_rank + w.rope_dim, "kv_a")(x),
                                [w.kv_rank], axis=-1)
            k_nope, v = jnp.split(
                dense(H * (w.nope_dim + w.v_dim), "kv_b")(
                    norm("kv_norm")(c_kv)).reshape(
                        B, T, H, w.nope_dim + w.v_dim),
                [w.nope_dim], axis=-1)
            q = jnp.concatenate(
                [q[..., :w.nope_dim],
                 rotate(q[..., w.nope_dim:], cos, sin)], axis=-1)
            r = rotate(r[:, :, None, :], cos, sin)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(r, (B, T, H, w.rope_dim))], axis=-1)
        attend = self.attention_impl or reference_attention
        with jax.named_scope(module_scope("attention_latent")):
            o = attend(q, k, v, causal=True, window=0)
        with jax.named_scope(module_scope("mla_out")):
            return dense(C, "o")(o.reshape(B, T, H * w.v_dim))


class DecoderLayer(nn.Module):
    spec: LayerSpec
    model: DecoderSpec
    dtype: jnp.dtype = jnp.float32
    attention_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, cos, sin):
        m = self.model
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=m.norm_eps, dtype=self.dtype, name=name)
        if self.spec.latent is None:
            attn = GroupedQueryAttention(
                self.spec, m.kv_heads, m.head_dim, dtype=self.dtype,
                attention_impl=self.attention_impl,
                qk_norm=m.norm_eps if m.qk_norm else None,
                diffusion_block=m.diffusion.block if m.diffusion else 0,
                name="attn")
        else:
            attn = LatentAttention(
                self.spec, m.norm_eps, dtype=self.dtype,
                attention_impl=self.attention_impl, name="attn")
        x = x + attn(norm("attn_norm")(x), cos, sin)
        h = norm("mlp_norm")(x)
        if not self.spec.sparse:
            return x + SwiGLU(m.dense_width, dtype=self.dtype, name="mlp")(h)
        from tpu_ddp.parallel.expert_parallel import ExpertShare

        return x + DroplessMoE(
            ExpertShare(m.num_experts, m.experts_held, m.expert_offset),
            top_k=m.top_k, expert_width=m.expert_width,
            shared_width=m.shared_width, scaling=m.routed_scaling,
            dtype=self.dtype, selection_bias=m.selection_bias,
            score=m.router_score, name="moe",
        )(h)


class SparseDecoder(nn.Module):
    spec: DecoderSpec
    dtype: jnp.dtype = jnp.float32
    #: ``(q, k, v, *, causal, window) -> o`` on (B, T, H, D) queries and
    #: (B, T, KV, D) keys and values; None = the fused jnp reference
    attention_impl: Optional[Callable] = None
    #: recompute each layer in the backward pass (``resolve_remat``), all
    #: but ``KEPT_NAMES``: a layer keeps its attention's output (134-268 MB
    #: at 16,384 tokens) and a float32 a row of its logsumexp (2-4 MB), so
    #: ``flash_fwd`` runs once a layer and step, and under a selection bias
    #: its router's logits, ids and scores (PERF.md section 6, PR 42)
    remat: bool = False
    #: (block_q, block_k) of ``--attention flash``: a decoder's sequences
    #: are long, and a grid step of 128 x 128 is mostly its own overhead
    flash_blocks = (512, 512)

    @property
    def task(self) -> str:
        """What the model reads from a batch and which loss it takes."""
        if self.spec.diffusion is not None:
            return "block_diffusion"
        return "next_token" if self.spec.mtp is None else "next_token_mtp"

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout, no batch statistics
        s = self.spec
        embed = nn.Embed(s.vocab_rows, s.hidden, dtype=self.dtype,
                         name="embed")
        x = embed(tokens)
        tables = {}
        layer_cls = recomputed(DecoderLayer) if self.remat else DecoderLayer
        positions = tokens.shape[1]
        if s.diffusion is not None:
            if s.mtp is not None or positions % (2 * s.diffusion.block):
                raise ValueError(
                    f"a block-diffusion decoder reads [clean, noisy], twice "
                    f"a whole number of blocks of {s.diffusion.block}, not "
                    f"{positions} positions; and has no prediction module")
            positions //= 2  # both halves are positions 0 .. L - 1
            self.sow("counters", "block_masked_tokens", jnp.sum(
                tokens[:, positions:] == s.diffusion.mask_id,
                dtype=jnp.int32))

        def layer_of(layer, name):
            if layer.rotary not in tables:
                cos, sin = layer.rotary.tables(positions)
                if s.diffusion is not None:
                    cos, sin = (jnp.concatenate([a, a]) for a in (cos, sin))
                tables[layer.rotary] = (cos, sin)
            return functools.partial(
                layer_cls(layer, s, dtype=self.dtype,
                          attention_impl=self.attention_impl, name=name),
                cos=tables[layer.rotary][0], sin=tables[layer.rotary][1])

        for i, layer in enumerate(s.layers):
            x = layer_of(layer, f"layer_{i}")(x)
        final_norm = nn.RMSNorm(epsilon=s.norm_eps, dtype=self.dtype,
                                name="final_norm")
        # operands in ``dtype``, logits accumulated and kept in float32
        head = nn.Dense(
            s.vocab_rows, use_bias=False, dtype=self.dtype, name="head",
            dot_general=functools.partial(
                jax.lax.dot_general, preferred_element_type=jnp.float32))
        if s.diffusion is not None:
            x = x[:, positions:]  # the noisy half is what is predicted from
        logits = head(final_norm(x)).astype(jnp.float32)
        if s.mtp is None:
            return logits
        # The prediction module, on every position: position i reads the
        # stack's output h_i (before the final norm: the module has a norm
        # of its own for it) and the embedding of token i + 1, the last
        # position a pad that no target follows, so that both stacks give
        # the kernels one shape. Embedding, final norm and head are the
        # stack's own.
        with jax.named_scope(module_scope("mtp")):
            norm = lambda name: nn.RMSNorm(  # noqa: E731
                epsilon=s.norm_eps, dtype=self.dtype, name=name)
            ahead = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
            h = nn.Dense(s.hidden, use_bias=False, dtype=self.dtype,
                         name="mtp_proj")(jnp.concatenate(
                             [norm("mtp_hidden_norm")(x),
                              norm("mtp_embed_norm")(embed(ahead))], axis=-1))
            h = layer_of(s.mtp, "mtp_layer")(h)
            return logits, head(final_norm(h)).astype(jnp.float32)


# -- poolside/Laguna-XS.2 ----------------------------------------------------
# https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json

_LAGUNA_FULL = Rotary(dims=64, theta=500000.0,
                      yarn=(64.0, 4096, 64.0, 1.0, 1.4158883083359672))
_LAGUNA_SLIDING = Rotary(dims=128, theta=10000.0)


def laguna_xs2_spec(*, num_layers: int = 40, experts_held: int = 256,
                    expert_offset: int = 0,
                    vocab_rows: int = 100352) -> DecoderSpec:
    """The published model: 40 layers, ``full, sliding, sliding, sliding``
    ten times (48 query heads in a full layer, 64 in a sliding one, window
    512), layer 0 dense (width 8,192), the others 256 routed experts of
    width 512 with 8 a token (times 2.5) and one shared expert of width 512.
    The arguments are one chip's share; no width changes."""
    layers = tuple(
        LayerSpec(heads=48, window=0, rotary=_LAGUNA_FULL, sparse=i > 0)
        if i % 4 == 0 else
        LayerSpec(heads=64, window=512, rotary=_LAGUNA_SLIDING, sparse=True)
        for i in range(num_layers))
    return DecoderSpec(
        vocab_rows=vocab_rows, hidden=2048, head_dim=128, kv_heads=8,
        layers=layers, dense_width=8192, num_experts=256,
        experts_held=experts_held, expert_offset=expert_offset, top_k=8,
        expert_width=512, shared_width=512, routed_scaling=2.5)


@register("laguna_xs2")
def laguna_xs2(num_classes: int = 10, bn_cross_replica_axis=None,
               dtype=jnp.float32, **share):
    del num_classes, bn_cross_replica_axis  # a classifier's
    return SparseDecoder(laguna_xs2_spec(**share), dtype=dtype)


# -- jdopensource/JoyAI-LLM-Flash ---------------------------------------------
# https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json

def joyai_llm_flash_spec(*, num_layers: int = 40, experts_held: int = 256,
                         expert_offset: int = 0,
                         vocab_rows: int = 129280) -> DecoderSpec:
    """The published model (``model_type`` ``joyai_llm_flash``, DeepSeek-V3's
    layout): 40 layers of latent attention (32 heads; queries through rank
    1,536, keys and values through rank 512; 128 + 64 rotary a query and
    key, 128 a value; theta 32e6, no scaling), layer 0 dense (width 7,168),
    the others 256 routed experts of width 768 with 8 a token chosen by
    sigmoid scores plus a selection bias (weights without it, normalised,
    times 2.5) and one shared expert of width 768; one multi-token
    prediction module, its term weighted 0.3 (the config has no key for the
    weight: DeepSeek-V3's for most of its pre-training). The arguments are
    one chip's share; no width changes."""
    layer = LayerSpec(
        heads=32, window=0, rotary=Rotary(dims=64, theta=32000000.0),
        sparse=True, gate=False,
        latent=LatentSpec(q_rank=1536, kv_rank=512, nope_dim=128,
                          rope_dim=64, v_dim=128))
    layers = tuple(dataclasses.replace(layer, sparse=i > 0)
                   for i in range(num_layers))
    return DecoderSpec(
        vocab_rows=vocab_rows, hidden=2048, head_dim=192, kv_heads=32,
        layers=layers, dense_width=7168, num_experts=256,
        experts_held=experts_held, expert_offset=expert_offset, top_k=8,
        expert_width=768, shared_width=768, routed_scaling=2.5,
        selection_bias=True, mtp=layer, mtp_weight=0.3)


@register("joyai_llm_flash")
def joyai_llm_flash(num_classes: int = 10, bn_cross_replica_axis=None,
                    dtype=jnp.float32, **share):
    del num_classes, bn_cross_replica_axis  # a classifier's
    return SparseDecoder(joyai_llm_flash_spec(**share), dtype=dtype)


# -- JetLM/SDAR-30B-A3B-Chat --------------------------------------------------
# https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json

def sdar_30b_a3b_spec(*, num_layers: int = 48, experts_held: int = 128,
                      expert_offset: int = 0,
                      vocab_rows: int = 151936) -> DecoderSpec:
    """The published model (``model_type`` ``sdar_moe``, the ``qwen3_moe``
    layout): 48 layers alike, 32 query heads over 4 key-value heads of 128
    (beside a hidden size of 2,048), queries and keys normalised over a
    head, all 128 dimensions turned (theta 1e6, no scaling), no window, no
    output gate; 128 routed experts of width 768 with 8 a token by a float32
    softmax over all 128, the chosen eight renormalised, no scaling factor,
    no shared expert, no dense layer. Trained by block diffusion: blocks of
    4 tokens, as the released Chat checkpoints generate with, the mask the
    last vocabulary row held (the config gives neither the block length nor
    the schedule: ``chipbench/configs/sdar-30b-a3b.json``, ``assumed``).
    The arguments are one chip's share; no width changes."""
    layer = LayerSpec(heads=32, window=0,
                      rotary=Rotary(dims=128, theta=1000000.0), sparse=True,
                      gate=False)
    return DecoderSpec(
        vocab_rows=vocab_rows, hidden=2048, head_dim=128, kv_heads=4,
        layers=(layer,) * num_layers, dense_width=6144, num_experts=128,
        experts_held=experts_held, expert_offset=expert_offset, top_k=8,
        expert_width=768, shared_width=0, routed_scaling=1.0, qk_norm=True,
        router_score="softmax",
        diffusion=BlockDiffusion(block=4, mask_id=vocab_rows - 1))


@register("sdar_30b_a3b")
def sdar_30b_a3b(num_classes: int = 10, bn_cross_replica_axis=None,
                 dtype=jnp.float32, **share):
    del num_classes, bn_cross_replica_axis  # a classifier's
    return SparseDecoder(sdar_30b_a3b_spec(**share), dtype=dtype)
