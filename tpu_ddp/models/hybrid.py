"""Hybrid decoder: a stack of pre-norm blocks with one mixer each,

    h = embed(tokens);  h = h + Mixer_i(RMSNorm(h)) for each block;
    logits = head(RMSNorm(h))

the mixer of block ``i`` named by character ``i`` of a pattern string: ``M``
a Mamba-2 state-space mixer (``Mamba2Mixer``, its scan ``ops/ssd_scan.py``),
``*`` causal grouped-query attention without position encoding and without
a gate (``models/decoder.py::GroupedQueryAttention``), ``E`` routed experts
in a latent space with a shared expert (``models/moe.py::DroplessMoE``:
plain ``relu ** 2`` experts, a selection bias). RMSNorm, no biases but the
convolution's, untied embedding and head. One flax module,
``HybridDecoder``, described by a ``HybridSpec``; ``nemotron3_super``
registers NVIDIA's Nemotron-3-Super-120B-A12B at its published sizes.
A block here is a function of the residual stream alone; a stack whose
layers also read another layer's tensors is ``models/sambay.py``.

Input ``tokens`` (B, T) int32, output float32 logits (B, T, vocabulary rows
held); the task is ``next_token`` (``train/tasks.py``), as the sparse
decoder's.

One chip of the deployment holds a share of each block: the routed experts
``expert_offset`` and up (``ExpertShare``), the heads of position
``head_position`` of ``head_positions`` (``HeadShare``: Mamba heads with
their B/C groups and the gated norm's groups, query heads over their
key-value head), ``vocab_rows`` of the embedding and the head, and the first
``num_layers`` blocks. Those are the registry factory's overrides
(``TrainConfig.model_overrides``); every width stays. A mixer built with a
share computes that share's part of its output and the block hands the
partial sum on: nothing stands in for the absent chips.

Parameters are float32; ``dtype`` is what activations and matrix-unit
operands are held in. Router, selection bias and choice, the scan's state
and decays, ``dt``, ``A`` and ``D``, and normalisation statistics are
float32 whatever ``dtype``; the head's logits come out float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_ddp.models.decoder import (GroupedQueryAttention, LayerSpec,
                                    recomputed)
from tpu_ddp.models.moe import DroplessMoE
from tpu_ddp.models.zoo import register
from tpu_ddp.ops.ssd_scan import ssd_scan
from tpu_ddp.telemetry.phases import module_scope


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    pattern: str         # one character a block: M, E or *
    vocab_rows: int
    hidden: int
    # attention blocks: the heads held here
    heads: int
    kv_heads: int
    head_dim: int
    # Mamba-2 blocks: heads and B/C groups held here
    mamba_heads: int
    mamba_head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    # expert blocks
    num_experts: int     # outputs of the router
    experts_held: int
    expert_offset: int
    top_k: int
    expert_width: int
    shared_width: int
    latent: int
    routed_scaling: float
    norm_eps: float = 1e-5


def causal_conv(x, kernel, bias):
    """Depthwise causal convolution over time: ``y_t = sum_k kernel[k] *
    x_{t - (K - 1) + k} + bias``, ``x`` (B, T, channels), ``kernel`` (K,
    channels); positions before the sequence read zero. Float32 sums."""
    taps, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, k:k + t] * kernel[k] for k in range(taps)) + bias


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm(y * silu(z)) * scale``: the gate first, then the norm within
    each of ``groups`` equal runs of the last axis, in float32."""
    shape = y.shape
    y = (y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))).reshape(
        shape[:-1] + (groups, shape[-1] // groups))
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return y.reshape(shape) * scale


class Mamba2Mixer(nn.Module):
    """``[z, xBC, dt] = u W_in``; ``xBC = silu(conv(xBC) + b)``, split into
    ``x`` (heads, head_dim) and ``B``, ``C`` (groups, state); ``dt =
    softplus(dt + dt_bias)``; the scan ``S_t = exp(-dt_t exp(A_log)) S_{t-1}
    + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` a head; ``y =
    RMSNorm(y * silu(z))`` within each group's ``heads * head_dim / groups``
    channels, times a scale; out ``y W_out``. ``heads`` and ``groups`` are
    those held here; with fewer than the published the output is partial."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        b, t, c = u.shape
        h, p, g, n = self.heads, self.head_dim, self.groups, self.state
        inner, bc = h * p, g * n
        dense = lambda width, name: nn.Dense(  # noqa: E731
            width, use_bias=False, dtype=self.dtype, name=name)
        with jax.named_scope(module_scope("mamba_in")):
            z, xbc, dt = jnp.split(
                dense(2 * inner + 2 * bc + h, "in_proj")(u),
                [inner, 2 * inner + 2 * bc], axis=-1)
        with jax.named_scope(module_scope("mamba_conv")):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                (self.conv_kernel, inner + 2 * bc),
                                jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (inner + 2 * bc,), jnp.float32)
            xbc = nn.silu(causal_conv(xbc, kernel, bias)).astype(self.dtype)
            x, B, C = jnp.split(xbc, [inner, inner + bc], axis=-1)
        with jax.named_scope(module_scope("ssm_scan")):
            a_log = self.param("A_log", nn.initializers.zeros, (h,),
                               jnp.float32)
            skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,),
                                 jnp.float32)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            x = x.reshape(b, t, h, p)
            y = ssd_scan(x, dt, -jnp.exp(a_log), B.reshape(b, t, g, n),
                         C.reshape(b, t, g, n), self.chunk)
            y = (y.astype(jnp.float32)
                 + skip[:, None] * x.astype(jnp.float32))
        with jax.named_scope(module_scope("mamba_norm")):
            scale = self.param("norm_scale", nn.initializers.ones, (inner,),
                               jnp.float32)
            y = gated_group_norm(y.reshape(b, t, inner), z, scale, g,
                                 self.norm_eps).astype(self.dtype)
        with jax.named_scope(module_scope("mamba_out")):
            return dense(c, "out_proj")(y)


class HybridBlock(nn.Module):
    kind: str
    model: HybridSpec
    dtype: jnp.dtype = jnp.float32
    attention_impl: Optional[Callable] = None

    @nn.compact
    def __call__(self, x):
        m = self.model
        h = nn.RMSNorm(epsilon=m.norm_eps, dtype=self.dtype, name="norm")(x)
        if self.kind == "M":
            mixer = Mamba2Mixer(
                m.mamba_heads, m.mamba_head_dim, m.groups, m.state,
                m.conv_kernel, m.chunk, m.norm_eps, dtype=self.dtype,
                name="mixer")
        elif self.kind == "*":
            mixer = GroupedQueryAttention(
                LayerSpec(heads=m.heads, window=0, rotary=None, sparse=False,
                          gate=False),
                m.kv_heads, m.head_dim, dtype=self.dtype,
                attention_impl=self.attention_impl, name="mixer")
        elif self.kind == "E":
            from tpu_ddp.parallel.expert_parallel import ExpertShare

            mixer = DroplessMoE(
                ExpertShare(m.num_experts, m.experts_held, m.expert_offset),
                top_k=m.top_k, expert_width=m.expert_width,
                shared_width=m.shared_width, scaling=m.routed_scaling,
                dtype=self.dtype, gated=False, latent=m.latent,
                selection_bias=True, name="mixer")
        else:
            raise ValueError(f"no mixer {self.kind!r}: M, E or *")
        return x + mixer(h)


class HybridDecoder(nn.Module):
    spec: HybridSpec
    dtype: jnp.dtype = jnp.float32
    #: ``(q, k, v, *, causal, window) -> o``; None = the fused jnp reference
    attention_impl: Optional[Callable] = None
    #: recompute each block in the backward pass (``resolve_remat``), all
    #: but ``decoder.KEPT_NAMES``: an expert block's routed result (33.5 MB
    #: a block at 16,384 tokens), so that the ladder's branch runs forward
    #: and backward and not a third time between (0.9% of the step: PERF.md
    #: section 6, PR 33: ``latent_up``'s weight gradient reads it); its
    #: router's float32 logits, chosen ids and their scores (33.5 MB and
    #: twice 1.4 MB), so that the six-pass product, the ``top_k`` of 22
    #: over 512 and the gather of the chosen scores run once a block and
    #: step (PR 34); and an attention block's output and a float32 a row of
    #: its logsumexp (16.8 MB), so that ``flash_fwd`` runs once (PR 42).
    #: A Mamba block keeps nothing of its scan: ``ssd_scan_fwd`` runs again
    #: in the backward pass, 0.29 ms a block, where keeping what it writes
    #: (33.5 MB of ``y`` and 67 MB of states a block, 0.50 GB) cost the
    #: step 5.6 ms (PERF.md section 6, PR 47)
    remat: bool = False
    task = "next_token"
    flash_blocks = (512, 512)  # as the sparse decoder's, and for its reason

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train  # no dropout, no batch statistics
        s = self.spec
        x = nn.Embed(s.vocab_rows, s.hidden, dtype=self.dtype,
                     name="embed")(tokens)
        block_cls = recomputed(HybridBlock) if self.remat else HybridBlock
        for i, kind in enumerate(s.pattern):
            x = block_cls(kind, s, dtype=self.dtype,
                          attention_impl=self.attention_impl,
                          name=f"block_{i}")(x)
        x = nn.RMSNorm(epsilon=s.norm_eps, dtype=self.dtype,
                       name="final_norm")(x)
        # operands in ``dtype``, logits accumulated and kept in float32
        return nn.Dense(
            s.vocab_rows, use_bias=False, dtype=self.dtype, name="head",
            dot_general=functools.partial(
                jax.lax.dot_general, preferred_element_type=jnp.float32),
        )(x).astype(jnp.float32)


# -- nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 ---------------------------
# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json

NEMOTRON3_SUPER_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME")


def nemotron3_super_spec(*, num_layers: int = 88, experts_held: int = 512,
                         expert_offset: int = 0, vocab_rows: int = 131072,
                         head_positions: int = 1,
                         head_position: int = 0) -> HybridSpec:
    """The published model (``model_type`` ``nemotron_h``): 88 blocks by
    ``hybrid_override_pattern``, hidden 4096; Mamba-2 with 128 heads of 64,
    8 B/C groups, state 128, a convolution of 4, chunks of 128; attention of
    32 query heads over 2 key-value heads of 128; 512 routed experts of
    width 2688 in a latent space of 1024 with 22 a token (times 5) and a
    shared expert of width 5376, all ``relu ** 2``. The multi-token
    prediction module after block 88 is not part of it. The arguments are
    one chip's share; no width changes."""
    from tpu_ddp.parallel.expert_parallel import HeadShare

    share = HeadShare(head_positions, head_position)
    return HybridSpec(
        pattern=NEMOTRON3_SUPER_PATTERN[:num_layers], vocab_rows=vocab_rows,
        hidden=4096, heads=share.of(32)[0], kv_heads=share.of(2)[0],
        head_dim=128, mamba_heads=share.of(128)[0], mamba_head_dim=64,
        groups=share.of(8)[0], state=128, conv_kernel=4, chunk=128,
        num_experts=512, experts_held=experts_held,
        expert_offset=expert_offset, top_k=22, expert_width=2688,
        shared_width=5376, latent=1024, routed_scaling=5.0)


@register("nemotron3_super")
def nemotron3_super(num_classes: int = 10, bn_cross_replica_axis=None,
                    dtype=jnp.float32, **share):
    del num_classes, bn_cross_replica_axis  # a classifier's
    return HybridDecoder(nemotron3_super_spec(**share), dtype=dtype)
