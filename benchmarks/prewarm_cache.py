#!/usr/bin/env python
"""Best-effort pre-warm of the persistent XLA cache — no chip needed.

Compiles every bench-leg program ahead of time with the image's local
libtpu toolchain, for a described (not attached) v5e chip, into the same
persistent cache the live entry points use (``enable_compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/`` in the checkout).

HONESTY NOTE on expected effect: cache-key fidelity between these
deviceless compiles and a live run's is NOT established. The key moves
with the input-sharding construction (concrete live state vs abstract
ShapeDtypeStructs), and jax cannot read a deviceless entry back without a
chip (it warns and compiles again). So a live run may recompile anyway;
the value of this tool is bounded below by zero (a cache miss falls back
to a normal compile). What it does show, at no chip time: that every
bench-leg program still compiles for the chip.

Run it whenever the repo's step builders change:
    python benchmarks/prewarm_cache.py
(Uses the CPU platform + a compile-only v5e topology. One libtpu process
at a time: two collide on its lock file in /tmp.)
"""

from __future__ import annotations

import os
import sys
import time

# Before ANY jax import: this tool must never take a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from tpu_ddp.models import NetResDeep
    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.parallel import (
        MeshSpec,
        batch_sharding,
        create_mesh,
        stacked_batch_sharding,
    )
    from tpu_ddp.parallel.partitioning import abstract_train_state
    from tpu_ddp.parallel.runtime import enable_compile_cache
    from tpu_ddp.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )

    # The bench runs on ONE chip; the smallest deviceless v5e topology is
    # 2x2 — a 1-device mesh over its first device stands in for the live
    # 1-device mesh.
    CACHE_DIR = enable_compile_cache()
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    mesh = create_mesh(MeshSpec(data=-1), topo.devices[:1])
    bs = batch_sharding(mesh)
    sbs = stacked_batch_sharding(mesh)

    def flat_batch(gb):
        return {
            "image": jax.ShapeDtypeStruct((gb, 32, 32, 3), jnp.float32,
                                          sharding=bs),
            "label": jax.ShapeDtypeStruct((gb,), jnp.int32, sharding=bs),
            "mask": jax.ShapeDtypeStruct((gb,), bool, sharding=bs),
        }

    def stacked_batch(k, gb):
        return {
            "image": jax.ShapeDtypeStruct((k, gb, 32, 32, 3), jnp.float32,
                                          sharding=sbs),
            "label": jax.ShapeDtypeStruct((k, gb), jnp.int32, sharding=sbs),
            "mask": jax.ShapeDtypeStruct((k, gb), bool, sharding=sbs),
        }

    def astate(model, tx):
        return abstract_train_state(jax.eval_shape(
            lambda: create_train_state(model, tx, jax.random.key(0))
        ))

    jobs = []

    # bench._bench_dispatch_baseline: netresdeep f32, b32, one step/call
    def baseline():
        model, tx = NetResDeep(), make_optimizer(lr=1e-2)
        step = make_train_step(model, tx, mesh)
        return step.trace(astate(model, tx), flat_batch(32))

    jobs.append(("baseline_dispatch_per_step", baseline))

    # bench's compute_bound leg: resnet50 bf16, b256
    def compute():
        model = MODEL_REGISTRY["resnet50"](num_classes=10,
                                           dtype=jnp.bfloat16)
        tx = make_optimizer(lr=1e-1, momentum=0.9)
        step = make_train_step(model, tx, mesh)
        return step.trace(astate(model, tx), flat_batch(256))

    jobs.append(("compute_bound_resnet50_bf16_b256", compute))

    # bench._bench_attention: vit_s4 bf16 b128, full + flash
    def attention(impl):
        def go():
            from tpu_ddp.ops.flash_attention import flash_attention

            model = MODEL_REGISTRY["vit_s4"](num_classes=10,
                                             dtype=jnp.bfloat16)
            if impl == "flash":
                # interpret=False explicitly: in this CPU process the
                # None-default resolves to interpret mode and the trace
                # would silently take the jnp fallback — a different
                # program than the live on-chip bench compiles
                model = model.clone(
                    attention_impl=lambda q, k, v: flash_attention(
                        q, k, v, 128, 128, False
                    )
                )
            tx = make_optimizer(lr=1e-2, momentum=0.9)
            step = make_train_step(model, tx, mesh)
            return step.trace(astate(model, tx), flat_batch(128))
        return go

    jobs.append(("attention_full_vit_bf16_b128", attention("full")))
    jobs.append(("attention_flash_vit_bf16_b128", attention("flash")))

    # Attention-op fwd+bwd trace points (bench._time_attn_impl's program
    # shape), shared by the T=2048 microbench pair, the causal row, and
    # the T=8192 longseq pair — ONE recipe so a timing-discipline change
    # in bench.py has a single prewarm mirror to update.
    def attention_point(impl_name, B, T, causal=False):
        def go():
            from tpu_ddp.ops.flash_attention import (
                _reference,
                flash_attention,
            )

            if impl_name == "full":
                fn = (lambda a, b, c: _reference(a, b, c, causal=causal))
            else:
                fn = (lambda a, b, c: flash_attention(
                    a, b, c, 128, 128, False, causal=causal))
            # The topology sharding is REQUIRED here even though the live
            # microbench jits plain unsharded arrays: without it the
            # deviceless trace targets the CPU backend, where the
            # non-interpret Pallas kernel refuses to compile at all. The
            # key-fidelity cost is the tool's documented caveat — an
            # unshared-key miss just means a normal compile on-chip.
            H, D = 8, 128
            sh = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()
            )
            qs = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16,
                                      sharding=sh)
            loss = jax.jit(jax.value_and_grad(
                lambda a, b, c: fn(a, b, c).astype(jnp.float32).mean(),
                (0, 1, 2),
            ))
            return loss.trace(qs, qs, qs)
        return go

    jobs.append(("attention_op_full_T2048", attention_point("full", 4, 2048)))
    jobs.append(("attention_op_flash_T2048",
                 attention_point("flash", 4, 2048)))

    # bench sweep points: scan K x per-shard batch
    for k in (32, 128):
        for per_shard in (32, 256):
            def sweep(k=k, per_shard=per_shard):
                model, tx = NetResDeep(), make_optimizer(lr=1e-2)
                step = make_train_step(model, tx, mesh, steps_per_call=k)
                return step.trace(astate(model, tx),
                                  stacked_batch(k, per_shard))
            jobs.append((f"sweep_scan{k}_b{per_shard}", sweep))

    # bench legs compute_b128 / compute_b512: resnet50 bf16 sweep points
    for per_shard in (128, 512):
        def point(per_shard=per_shard):
            model = MODEL_REGISTRY["resnet50"](num_classes=10,
                                               dtype=jnp.bfloat16)
            tx = make_optimizer(lr=1e-1, momentum=0.9)
            step = make_train_step(model, tx, mesh)
            return step.trace(astate(model, tx), flat_batch(per_shard))
        jobs.append((f"compute_point_b{per_shard}", point))

    # bench leg compute_fused: scan-fused K=8 resnet50 bf16 b256
    def fused():
        model = MODEL_REGISTRY["resnet50"](num_classes=10,
                                           dtype=jnp.bfloat16)
        tx = make_optimizer(lr=1e-1, momentum=0.9)
        step = make_train_step(model, tx, mesh, steps_per_call=8)
        return step.trace(astate(model, tx), stacked_batch(8, 256))

    jobs.append(("compute_fused_scan8_b256", fused))

    # bench leg compute_imagenet: resnet50 bf16, ImageNet stem, 224x224
    def imagenet():
        model = MODEL_REGISTRY["resnet50"](
            num_classes=1000, cifar_stem=False, dtype=jnp.bfloat16)
        tx = make_optimizer(lr=1e-1, momentum=0.9)
        step = make_train_step(model, tx, mesh)
        state224 = abstract_train_state(jax.eval_shape(
            lambda: create_train_state(model, tx, jax.random.key(0),
                                       input_shape=(1, 224, 224, 3))
        ))
        batch224 = {
            "image": jax.ShapeDtypeStruct((64, 224, 224, 3), jnp.float32,
                                          sharding=bs),
            "label": jax.ShapeDtypeStruct((64,), jnp.int32, sharding=bs),
            "mask": jax.ShapeDtypeStruct((64,), bool, sharding=bs),
        }
        return step.trace(state224, batch224)

    jobs.append(("compute_imagenet_b64_224", imagenet))

    # bench leg compute_wrn: WRN-28-10 bf16 b128 (CIFAR shape)
    def wrn():
        model = MODEL_REGISTRY["wrn28_10"](num_classes=10,
                                           dtype=jnp.bfloat16)
        tx = make_optimizer(lr=1e-1, momentum=0.9, weight_decay=5e-4)
        step = make_train_step(model, tx, mesh)
        return step.trace(astate(model, tx), flat_batch(128))

    jobs.append(("compute_wrn28_10_b128", wrn))

    # one program each: causal flash at the
    # attention_op shape, and the T=8192 ring-tile points
    jobs.append(("attention_causal_T2048",
                 attention_point("flash", 4, 2048, causal=True)))
    jobs.append(("longseq_full_T8192", attention_point("full", 1, 8192)))
    jobs.append(("longseq_flash_T8192", attention_point("flash", 1, 8192)))

    # dense_step / moe_step — vit_s4 vs vit_moe_s4 train steps, bf16 b128
    def vit_step(model_name):
        def go():
            model = MODEL_REGISTRY[model_name](num_classes=10,
                                               dtype=jnp.bfloat16)
            tx = make_optimizer(lr=1e-2, momentum=0.9)
            step = make_train_step(model, tx, mesh)
            return step.trace(astate(model, tx), flat_batch(128))
        return go

    jobs.append(("dense_step_vit_s4_b128", vit_step("vit_s4")))
    jobs.append(("moe_step_vit_moe_s4_b128", vit_step("vit_moe_s4")))

    before = set(os.listdir(CACHE_DIR)) if os.path.isdir(CACHE_DIR) else set()
    for name, job in jobs:
        t0 = time.time()
        try:
            job().lower().compile()
            status = "ok"
        except Exception as e:  # keep warming the rest
            status = f"FAILED: {type(e).__name__}: {e}"
        print(f"prewarm: {name}: {status} [{time.time() - t0:.1f}s]",
              flush=True)
    after = set(os.listdir(CACHE_DIR)) if os.path.isdir(CACHE_DIR) else set()
    print(f"prewarm: cache entries {len(before)} -> {len(after)} "
          f"(+{len(after - before)} new)", flush=True)


if __name__ == "__main__":
    main()
