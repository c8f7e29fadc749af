"""Decoder-hybrid-decoder (SambaY, arXiv:2507.06607): a self-decoder of
Mamba-1 and windowed differential-attention layers, one full-attention
layer, and a cross-decoder whose layers compute no state of their own but
read one layer's: Gated Memory Units the last Mamba layer's scan output,
cross-attention layers the full-attention layer's keys and values.

    h = embed(tokens)
    h = h + Mixer_l(LN(h));  h = h + MLP(LN(h))      for each layer l
    logits = LN(h) embed^T                            (the head is tied)

``LN`` is a LayerNorm with scale and bias, ``MLP(u) = (silu(g) * v) W_down``
with ``[g, v] = u W_gate_up``; no position encoding anywhere (the scan
carries position). With ``L`` published layers the mixer of layer ``l`` is
(``SambaYSpec.kind``):

* ``l`` even, ``l <= L/2``: Mamba-1 (``Mamba1Mixer``, its scan
  ``ops/selective_scan.py``). Layer ``L/2`` also hands on its scan's output
  ``m`` (with the skip, before the gate): the memory.
* ``l`` odd, ``l < L/2``: differential attention (arXiv:2410.05258) under a
  window; ``l = L/2 + 1``: the same, full causal, and it hands on its keys
  and values.
* ``l`` even, ``l >= L/2 + 2``: a Gated Memory Unit, ``(m * silu(u W_1))
  W_2``.
* ``l`` odd, ``l >= L/2 + 3``: cross-attention, differential too: a query
  projection of its own and layer ``L/2 + 1``'s keys and values.

So a layer here is not a function of the residual stream alone, as the
blocks of ``models/hybrid.py`` and the layers of ``models/decoder.py`` are:
``SambaYLayer.__call__(h, memory, keys, values) -> (h, handed on)``, under
``decoder.recomputed`` like theirs, the handed-on tensors living across
layers and their gradients summing over their readers.

Differential attention: query heads in two sets (even and odd heads),
key and value heads likewise; for pair ``i`` of key-value heads ``V_i = [v1_i
; v2_i]`` (twice a head wide) and for each query pair over it

    o = RMSNorm(softmax(q1 k1^T / sqrt(d)) V - lambda softmax(q2 k2^T /
        sqrt(d)) V) * (1 - lambda_init)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 l)        (l the published index)

through one call of ``attention_impl`` (``ops/flash_attention.py``: keys of
``head_dim`` under values of twice that), ``[q1 ; q2]`` over ``[k1 ; k2]``
and ``[V ; V]``.

``phi4_mini_flash`` registers Microsoft's Phi-4-mini-flash-reasoning at its
published sizes. One chip holds whole layers, ``num_layers`` of them from
published layer ``first_layer`` up, and ``vocab_rows`` of the tied
embedding: the registry factory's overrides. A slice that reads the memory
or the keys and values and does not hold the layer that makes them is
refused; nothing stands in for an absent layer.

Input ``tokens`` (B, T) int32, output float32 logits (B, T, rows held); the
task is ``next_token``. Parameters are float32; ``dtype`` is what
activations and matrix-unit operands are held in. The scan's state, ``dt``,
``A`` and ``D``, ``lambda`` and normalisation statistics are float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_ddp.models.decoder import recomputed, reference_attention
from tpu_ddp.models.hybrid import causal_conv
from tpu_ddp.models.zoo import register
from tpu_ddp.ops.selective_scan import selective_scan
from tpu_ddp.telemetry.phases import module_scope

MAMBA, ATTENTION, GMU, CROSS = "mamba", "attention", "gmu", "cross"


@dataclasses.dataclass(frozen=True)
class SambaYSpec:
    layers: int          # published layers, L
    first_layer: int     # published index of the first layer held here
    num_layers: int      # layers held here
    vocab_rows: int
    hidden: int
    mlp_width: int
    heads: int           # query heads
    kv_heads: int
    head_dim: int
    window: int          # of the self-attention layers before L/2
    mamba_every: int     # layer l is of the Mamba kind where l % this == 0
    inner: int           # channels of the scan
    state: int
    conv_kernel: int
    dt_rank: int
    norm_eps: float = 1e-5

    def kind(self, l: int) -> str:
        half = self.layers // 2
        if l % self.mamba_every == 0:
            return MAMBA if l <= half else GMU
        return ATTENTION if l <= half + 1 else CROSS

    @property
    def memory_layer(self) -> int:
        return self.layers // 2

    @property
    def kv_layer(self) -> int:
        return self.layers // 2 + 1

    @property
    def held(self) -> range:
        return range(self.first_layer, self.first_layer + self.num_layers)

    def readers(self, kind: str) -> int:
        """Layers held here of ``kind`` (``GMU`` or ``CROSS``)."""
        return sum(self.kind(l) == kind for l in self.held)

    def check(self):
        if self.layers % (2 * self.mamba_every):
            raise ValueError(
                f"layer {self.layers // 2} of {self.layers} hands on the "
                f"memory: it has to be of the Mamba kind")
        if not (0 <= self.first_layer and self.num_layers >= 1
                and self.held[-1] < self.layers):
            raise ValueError(
                f"layers {self.first_layer}..{self.held[-1]} are not "
                f"{self.layers} published layers' own")
        for kind, producer, what in ((GMU, self.memory_layer, "memory"),
                                     (CROSS, self.kv_layer,
                                      "keys and values")):
            if self.readers(kind) and producer not in self.held:
                raise ValueError(
                    f"layers {self.first_layer}..{self.held[-1]} read the "
                    f"{what} of layer {producer}, which is not among them: "
                    "a slice holds its producer, nothing stands in for it")


def lambda_init(l: int) -> float:
    """Of the Differential Transformer, by the published layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def _dense(width, name, dtype):
    return nn.Dense(width, use_bias=False, dtype=dtype, name=name)


def _inverse_softplus_steps(lo: float = 1e-3, hi: float = 0.1):
    """``dt`` bias: the inverse softplus of a step drawn uniformly."""
    def init(key, shape, dtype=jnp.float32):
        step = jax.random.uniform(key, shape, dtype, lo, hi)
        return step + jnp.log(-jnp.expm1(-step))

    return init


def _a_log(key, shape, dtype=jnp.float32):
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


class Mamba1Mixer(nn.Module):
    """``[x, z] = u W_in``; ``x = silu(conv(x) + b)``; ``[r, B, C] = x W_x``;
    ``dt = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; the selective scan
    with its skip ``D``; out ``(y * silu(z)) W_out``. Returns the output and
    ``y``, the scan's output before the gate."""

    inner: int
    state: int
    conv_kernel: int
    dt_rank: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        c, n, r = self.inner, self.state, self.dt_rank
        with jax.named_scope(module_scope("mamba1_in")):
            x, z = jnp.split(_dense(2 * c, "in_proj", self.dtype)(u), 2,
                             axis=-1)
        with jax.named_scope(module_scope("mamba1_conv")):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (self.conv_kernel, c), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros, (c,),
                              jnp.float32)
            x = nn.silu(causal_conv(x, kernel, bias)).astype(self.dtype)
        with jax.named_scope(module_scope("mamba1_dt")):
            low, B, C = jnp.split(
                _dense(r + 2 * n, "x_proj", self.dtype)(x), [r, r + n],
                axis=-1)
            dt_bias = self.param("dt_bias", _inverse_softplus_steps(), (c,),
                                 jnp.float32)
            dt = jax.nn.softplus(
                _dense(c, "dt_proj", self.dtype)(low).astype(jnp.float32)
                + dt_bias)
        with jax.named_scope(module_scope("selective_scan")):
            a_log = self.param("A_log", _a_log, (c, n), jnp.float32)
            skip = self.param("D", nn.initializers.ones, (c,), jnp.float32)
            y = selective_scan(x, dt, -jnp.exp(a_log), B, C, skip)
        with jax.named_scope(module_scope("mamba1_out")):
            gated = (y.astype(jnp.float32)
                     * nn.silu(z.astype(jnp.float32))).astype(self.dtype)
            return _dense(u.shape[-1], "out_proj", self.dtype)(gated), y


class GatedMemoryUnit(nn.Module):
    """``(m * silu(u W_1)) W_2``: the memory gated by this layer's input."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u, memory):
        with jax.named_scope(module_scope("gmu")):
            gate = nn.silu(_dense(memory.shape[-1], "in_proj", self.dtype)(
                u).astype(jnp.float32))
            gated = (memory.astype(jnp.float32) * gate).astype(self.dtype)
            return _dense(u.shape[-1], "out_proj", self.dtype)(gated)


def paired(k, v):
    """Keys and values as the attention call takes them: ``k`` (B, T, KV, d)
    as ``[k1 ; k2]`` (the even heads, then the odd), ``v`` as (B, T, KV / 2,
    2 d), pair ``i`` the even head ``i`` beside the odd."""
    return (jnp.concatenate([k[:, :, 0::2], k[:, :, 1::2]], axis=2),
            jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], axis=-1))


class DifferentialAttention(nn.Module):
    """Differential attention of ``heads`` query heads (module docstring).
    A self-attention layer (``keys`` None) projects ``[q, k, v] = u W_qkv``
    and returns its paired keys and values beside its output; a
    cross-attention layer projects ``q`` alone and reads the ones given."""

    heads: int
    kv_heads: int
    head_dim: int
    window: int
    layer: int           # the published index, for ``lambda_init``
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32
    attention_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, u, keys=None, values=None):
        b, t, c = u.shape
        h, kv, d = self.heads, self.kv_heads, self.head_dim
        cross = keys is not None
        if cross:
            q = _dense(h * d, "q", self.dtype)(u)
        else:
            q, k, v = jnp.split(
                _dense((h + 2 * kv) * d, "qkv", self.dtype)(u),
                [h * d, (h + kv) * d], axis=-1)
            keys, values = paired(k.reshape(b, t, kv, d),
                                  v.reshape(b, t, kv, d))
        q = q.reshape(b, t, h, d)
        attend = self.attention_impl or reference_attention
        vector = lambda name: self.param(  # noqa: E731
            name, nn.initializers.normal(0.1), (d,), jnp.float32)
        with jax.named_scope(module_scope(
                "attention_cross" if cross else "attention_diff")):
            o = attend(
                jnp.concatenate([q[:, :, 0::2], q[:, :, 1::2]], axis=2), keys,
                jnp.concatenate([values, values], axis=2), causal=True,
                window=self.window).astype(jnp.float32)
            first = lambda_init(self.layer)
            lam = (jnp.exp(jnp.sum(vector("lambda_q1") * vector("lambda_k1")))
                   - jnp.exp(jnp.sum(vector("lambda_q2")
                                     * vector("lambda_k2"))) + first)
            o = o[:, :, :h // 2] - lam * o[:, :, h // 2:]
            o = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                + self.norm_eps)
            scale = self.param("sub_norm", nn.initializers.ones, (2 * d,),
                               jnp.float32)
            o = (o * scale * (1.0 - first)).astype(self.dtype)
        return (_dense(c, "o", self.dtype)(o.reshape(b, t, h * d)), keys,
                values)


class GatedMLP(nn.Module):
    """``(silu(g) * v) W_down``, ``[g, v] = u W_gate_up``; no biases."""

    width: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        g, v = jnp.split(_dense(2 * self.width, "gate_up", self.dtype)(u), 2,
                         axis=-1)
        return _dense(u.shape[-1], "down", self.dtype)(nn.silu(g) * v)


class SambaYLayer(nn.Module):
    """Published layer ``index``: ``(h, memory, keys, values) -> (h, what it
    hands on)``, the latter the memory (layer ``L/2``), ``(keys, values)``
    (layer ``L/2 + 1``) or None. A layer reads only what its kind reads."""

    index: int
    model: SambaYSpec
    dtype: jnp.dtype = jnp.float32
    attention_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, h, memory=None, keys=None, values=None):
        m, l = self.model, self.index
        kind = m.kind(l)
        norm = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=m.norm_eps, dtype=self.dtype, name=name)
        u = norm("mixer_norm")(h)
        handed = None
        if kind == MAMBA:
            out, y = Mamba1Mixer(m.inner, m.state, m.conv_kernel, m.dt_rank,
                                 dtype=self.dtype, name="mixer")(u)
            handed = y if l == m.memory_layer else None
        elif kind == GMU:
            out = GatedMemoryUnit(dtype=self.dtype, name="mixer")(u, memory)
        else:
            attention = DifferentialAttention(
                m.heads, m.kv_heads, m.head_dim,
                m.window if l < m.memory_layer else 0, l, m.norm_eps,
                dtype=self.dtype, attention_impl=self.attention_impl,
                name="mixer")
            if kind == CROSS:
                out, _, _ = attention(u, keys, values)
            else:
                out, k, v = attention(u)
                handed = (k, v) if l == m.kv_layer else None
        h = h + out
        h = h + GatedMLP(m.mlp_width, dtype=self.dtype, name="mlp")(
            norm("mlp_norm")(h))
        return h, handed


class SambaYDecoder(nn.Module):
    spec: SambaYSpec
    dtype: jnp.dtype = jnp.float32
    #: ``(q, k, v, *, causal, window) -> o``; None = the fused jnp reference
    attention_impl: Optional[Callable] = None
    #: recompute each layer in the backward pass (``decoder.recomputed``):
    #: what a layer hands on is an output of its recomputed function, so it
    #: lives from its layer to its last reader's backward pass like a
    #: layer's input does
    remat: bool = False
    task = "next_token"
    flash_blocks = (512, 512)  # as the other decoders', and for their reason

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout, no batch statistics
        s = self.spec
        embed = nn.Embed(s.vocab_rows, s.hidden, dtype=self.dtype,
                         name="embed")
        h = embed(tokens)
        layer_cls = recomputed(SambaYLayer) if self.remat else SambaYLayer
        memory = keys = values = None
        for l in s.held:
            layer = layer_cls(l, s, dtype=self.dtype,
                              attention_impl=self.attention_impl,
                              name=f"layer_{l - s.first_layer}")
            kind = s.kind(l)
            if kind == GMU:
                h, _ = layer(h, memory)
            elif kind == CROSS:
                h, _ = layer(h, None, keys, values)
            else:
                h, handed = layer(h)
                if l == s.memory_layer:
                    memory = handed
                elif l == s.kv_layer:
                    keys, values = handed
        self.sow("counters", "memory_readers",
                 jnp.asarray(s.readers(GMU), jnp.int32))
        self.sow("counters", "kv_readers",
                 jnp.asarray(s.readers(CROSS), jnp.int32))
        h = nn.LayerNorm(epsilon=s.norm_eps, dtype=self.dtype,
                         name="final_norm")(h)
        # the tied head: operands in ``dtype``, logits accumulated and kept
        # in float32 (``embed.attend`` would keep them in ``dtype``)
        return jax.lax.dot_general(
            h, embed.embedding.astype(self.dtype),
            (((h.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.float32)


# -- microsoft/Phi-4-mini-flash-reasoning ------------------------------------
# https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json

def phi4_mini_flash_spec(*, first_layer: int = 0, num_layers: int = 32,
                         vocab_rows: int = 200064) -> SambaYSpec:
    """The published model (``model_type`` ``phi4flash``): 32 layers on a
    hidden size of 2560, ``mb_per_layer`` 2; Mamba-1 of 5120 channels,
    state 16, a convolution of 4 and ``dt`` rank 160; differential attention
    of 40 query heads over 20 key-value heads of 64, a window of 512 before
    layer 16; an MLP of 10240; 200,064 tied rows. The arguments are one
    chip's share, whole layers and rows of the vocabulary; no width
    changes."""
    return SambaYSpec(
        layers=32, first_layer=first_layer, num_layers=num_layers,
        vocab_rows=vocab_rows, hidden=2560, mlp_width=10240, heads=40,
        kv_heads=20, head_dim=64, window=512, mamba_every=2, inner=5120,
        state=16, conv_kernel=4, dt_rank=160)


@register("phi4_mini_flash")
def phi4_mini_flash(num_classes: int = 10, bn_cross_replica_axis=None,
                    dtype=jnp.float32, **share):
    del num_classes, bn_cross_replica_axis  # a classifier's
    spec = phi4_mini_flash_spec(**share)
    spec.check()
    return SambaYDecoder(spec, dtype=dtype)
