"""Vision Transformer — BASELINE.json configs[4] (ViT-B/16 stretch goal).

Absent from the reference entirely (its only model is the 76K-param CNN,
SURVEY.md §2.2); built fresh and TPU-first: NHWC patch-embed conv onto the
MXU, pre-LN blocks, mean-pool head (no CLS token — mean-pool keeps every
token homogeneous, which is what lets the sequence dimension shard cleanly
for ring-attention sequence parallelism, tpu_ddp.parallel.ring_attention).

``attention_impl`` is pluggable: the default is full softmax attention
(XLA fuses it well at these sizes); under sequence-parallel shard_map the
same module runs with ``ring_attention`` bound instead.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

from tpu_ddp.models.zoo import register


def full_attention(q, k, v):
    """q,k,v: (B, T, H, D) -> (B, T, H, D). Non-causal softmax attention.

    Scores accumulate and softmax in f32 regardless of compute dtype
    (standard mixed-precision practice: bf16 logits saturate sharp
    distributions); the PV matmul also accumulates f32, then casts back.
    """
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    p = nn.softmax(s.astype(jnp.float32), axis=-1)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o.astype(q.dtype)


class MultiHeadSelfAttention(nn.Module):
    num_heads: int
    attention_impl: Callable = staticmethod(full_attention)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        head_dim = C // self.num_heads
        qkv = nn.Dense(3 * C, dtype=self.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, self.num_heads, head_dim)
        k = k.reshape(B, T, self.num_heads, head_dim)
        v = v.reshape(B, T, self.num_heads, head_dim)
        o = self.attention_impl(q, k, v)
        return nn.Dense(C, dtype=self.dtype, name="proj")(o.reshape(B, T, C))


class TransformerBlock(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    attention_impl: Callable = staticmethod(full_attention)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        del train  # no dropout in v0; interface kept uniform with CNNs
        y = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        x = x + MultiHeadSelfAttention(
            self.num_heads, attention_impl=self.attention_impl,
            dtype=self.dtype, name="attn"
        )(y)
        y = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        h = nn.Dense(x.shape[-1] * self.mlp_ratio, dtype=self.dtype,
                     name="mlp_up")(y)
        h = nn.gelu(h)
        x = x + nn.Dense(x.shape[-1], dtype=self.dtype, name="mlp_down")(h)
        return x


class ViT(nn.Module):
    """``sp_axis``: when set (inside a shard_map whose mesh has that axis),
    the module runs SEQUENCE-PARALLEL: the input's height dim arrives
    sharded, each device embeds its stripe of patches, position embeddings
    are sliced by ring position, attention is ring attention over the axis,
    and the mean-pool closes with a pmean. Parameter shapes (incl. the full
    global pos table) are identical to the non-SP module, so the same
    checkpoint runs either way."""

    patch_size: int = 4
    hidden_dim: int = 192
    depth: int = 6
    num_heads: int = 3
    num_classes: int = 10
    mlp_ratio: int = 4
    attention_impl: Callable = staticmethod(full_attention)
    sp_axis: Optional[str] = None
    # SP only: per-ring-block attention runs the Pallas flash kernel
    # (VMEM tiles) instead of the fused-jnp score tile — the long-context
    # configuration (parallel/ring_attention.py::ring_flash_attention)
    sp_flash: bool = False
    # PER-BLOCK rematerialization: each TransformerBlock recomputes its
    # internals in the backward, so only block BOUNDARY activations are
    # stored — the granularity that actually shrinks peak HBM (a single
    # whole-forward jax.checkpoint rematerializes everything at once and
    # saves nothing; measured in tools/memplan.py). Param names/shapes are
    # identical either way, so checkpoints are interchangeable.
    remat: bool = False
    dtype: jnp.dtype = jnp.float32
    # kept for CLI/model-zoo interface parity with the CNNs; ViT has no BN
    bn_cross_replica_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        from jax import lax

        B = x.shape[0]
        x = nn.Conv(
            self.hidden_dim,
            kernel_size=(self.patch_size, self.patch_size),
            strides=(self.patch_size, self.patch_size),
            dtype=self.dtype,
            name="patch_embed",
        )(x)  # (B, H/p, W/p, C)
        x = x.reshape(B, -1, self.hidden_dim)  # (B, T_local, C)
        t_local = x.shape[1]

        if self.sp_axis is not None:
            import functools

            from tpu_ddp.parallel.ring_attention import (
                ring_attention,
                ring_flash_attention,
            )

            n_shards = lax.axis_size(self.sp_axis)
            pos = self.param(
                "pos_embed",
                nn.initializers.normal(0.02),
                (1, t_local * n_shards, self.hidden_dim),
            )
            # this device's stripe of patch rows is contiguous in the
            # row-major token order, so the pos slice is contiguous too
            start = lax.axis_index(self.sp_axis) * t_local
            pos = lax.dynamic_slice_in_dim(pos, start, t_local, axis=1)
            attention_impl = functools.partial(
                ring_flash_attention if self.sp_flash else ring_attention,
                axis_name=self.sp_axis,
            )
        else:
            pos = self.param(
                "pos_embed",
                nn.initializers.normal(0.02),
                (1, t_local, self.hidden_dim),
            )
            attention_impl = self.attention_impl

        x = x + pos.astype(x.dtype)
        # static_argnums=(2,): `train` is a Python bool, not a tracer
        block_cls = (nn.remat(TransformerBlock, static_argnums=(2,))
                     if self.remat else TransformerBlock)
        for i in range(self.depth):
            x = block_cls(
                self.num_heads,
                mlp_ratio=self.mlp_ratio,
                attention_impl=attention_impl,
                dtype=self.dtype,
                name=f"block_{i}",
            )(x, train)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        x = x.mean(axis=1)  # mean-pool: SP-friendly (a pmean over sequence)
        if self.sp_axis is not None:
            x = lax.pmean(x, self.sp_axis)
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)
        return x.astype(jnp.float32)  # f32 logits for the loss


@register("vit_s4")
def vit_s4(num_classes: int = 10, bn_cross_replica_axis=None, dtype=jnp.float32):
    """Small ViT for 32x32 inputs (patch 4 -> 64 tokens)."""
    return ViT(patch_size=4, hidden_dim=192, depth=6, num_heads=3,
               num_classes=num_classes, dtype=dtype)


@register("vit_b16")
def vit_b16(num_classes: int = 1000, bn_cross_replica_axis=None, dtype=jnp.float32):
    """ViT-B/16 (224x224 -> 196 tokens) — the BASELINE.json stretch config."""
    return ViT(patch_size=16, hidden_dim=768, depth=12, num_heads=12,
               num_classes=num_classes, dtype=dtype)
