#!/usr/bin/env python
"""Benchmark: steady-state CIFAR-10 training throughput + MFU, on a TPU.

A measurement of the chip or nothing: the bench child exits non-zero, with
``{"ok": false, ...}`` as its last line, when the default platform is not
``tpu``, and after the last leg when any leg recorded an ``error`` or was
skipped at the deadline. There is no CPU fallback and no replay of an
earlier capture — a number printed here was measured by this run, on the
device it names.

- **One process per chip**: the parent is stdlib-only and NEVER imports
  jax (a parent that has touched jax holds the chip, and the child could
  not get it); it starts ONE child, which does all the work, and exits
  with the child's code.
- **Hard cap**: the child runs under one deadline (``TOTAL_BUDGET_S``,
  default 540s); at the deadline the parent TERMs, then KILLs, and exits
  124. Legs that would start inside the last minute are skipped, named in
  ``skipped_legs``, and make the run fail (``"ok": false``, exit 1).
- **Print early**: the child *streams* to stdout (inherited fd,
  PYTHONUNBUFFERED) and prints the headline JSON line the moment the
  flagship number exists, then again after every leg — a kill
  mid-sub-bench still leaves a parsed headline in the tail.

Configs measured:

- **flagship** — NetResDeep, f32, per-shard batch 32: the reference recipe
  (``/root/reference/main.py:27,61``). Dispatch-bound at this size, so the
  framework fuses K=32 optimizer steps into one ``lax.scan`` dispatch
  (semantically identical: test_scan_multi_step_matches_sequential).
- **compute-bound** — ResNet-50, bf16, per-shard batch 256: an
  MXU-saturating config where MFU is meaningful.
- **attention** — flash (Pallas, compiled) vs fused-jnp attention on a ViT
  step; numerics are checked against the jnp reference before timing.

MFU = XLA cost-model FLOPs of the compiled step (fusion/scan-aware) /
wall-clock / bf16 peak of the device kind (``tpu_ddp/metrics/mfu.py``).

``bench.py --config <winner.json>`` measures a tuner-emitted winner
config verbatim (``tpu-ddp tune --emit-config``; docs/tuning.md)
instead of the standard suite — same parent/child split, one measured
leg through the tuner's own trial runner.

Timing methodology (all configs): the timed window ends in
``jax.block_until_ready`` on the last call's outputs — dispatch is
asynchronous, so a window without it measures the enqueue
(``chip_smoke.py`` checks on the chip that the fence is honest).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# vs_baseline denominator: the dispatch-per-step path (the reference's
# per-batch hot-loop pattern, main.py:32-41) on the SAME hardware,
# MEASURED in the same run (`baseline` record below). This constant is only
# the fallback denominator for the early headline line, printed before the
# baseline leg has run; it is a builder capture of 2026-07-30 on one v5e
# chip and is clearly labeled when used (`vs_baseline_source`).
FALLBACK_BASELINE_IMAGES_PER_SEC_PER_CHIP = 16892.0

TOTAL_BUDGET_S = float(os.environ.get("BENCH_TOTAL_BUDGET_S", 540))
_REPO = os.path.dirname(os.path.abspath(__file__))
_DEADLINE_ENV = "BENCH_DEADLINE_TS"

_ACTIVE_CHILD = None  # the currently-running bench child (see _on_term)


def _emit(result: dict) -> None:
    """Print one result line, flushed, to (inherited) stdout."""
    print(json.dumps(result), flush=True)


def _child_deadline() -> float:
    return float(os.environ.get(_DEADLINE_ENV, time.time() + 300))


def _terminate_gracefully(proc, grace: float = 15.0) -> None:
    """TERM, wait ``grace``, then KILL: a TERM'd child between dispatches
    tears down its PJRT client and frees the chip for the next process."""
    if proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


# ----------------------------------------------------------------- child --

def _measure(step, state, batch, *, target_seconds=8.0, max_calls=50):
    """(new_state, calls, elapsed): warm up (compile), then time `calls`
    executions, each window ending in ``block_until_ready``."""
    import jax

    for _ in range(2):
        state, metrics = step(state, batch)
    # Fence the warmup BEFORE calibrating: with async dispatch the two
    # warmup executions would otherwise still be in flight and inflate the
    # single-call measurement ~3x (undersizing the timed window).
    jax.block_until_ready((state, metrics))
    per_call_t0 = time.perf_counter()
    state, metrics = step(state, batch)
    jax.block_until_ready((state, metrics))
    per_call = max(time.perf_counter() - per_call_t0, 1e-6)
    calls = int(max(3, min(max_calls, target_seconds / per_call)))

    start = time.perf_counter()
    for _ in range(calls):
        state, metrics = step(state, batch)
    jax.block_until_ready((state, metrics))
    elapsed = time.perf_counter() - start
    return state, calls, elapsed


def _scan_point(
    model, tx, *, steps_per_call: int, per_shard: int, seed: int = 0,
    target_seconds: float = 8.0, max_calls: int = 50,
) -> dict:
    """ONE scan-fused measurement point (K optimizer steps per dispatch on
    32x32 inputs): the single implementation of the K-stacked batch build
    and the K-aware rate math, shared by the flagship leg and the fused
    compute leg so their 'same measurement discipline' is code, not a
    hand-kept convention."""
    import jax
    import numpy as np

    from tpu_ddp.data import synthetic_cifar10
    from tpu_ddp.metrics.mfu import compiled_flops, mfu
    from tpu_ddp.parallel import MeshSpec, create_mesh, stacked_batch_sharding
    from tpu_ddp.train import create_train_state, make_train_step

    devices = jax.devices()
    n_chips = len(devices)
    mesh = create_mesh(MeshSpec(data=-1), devices)
    state = create_train_state(model, tx, jax.random.key(0))
    step = make_train_step(model, tx, mesh, steps_per_call=steps_per_call)

    global_batch = per_shard * n_chips
    imgs, labels = synthetic_cifar10(steps_per_call * global_batch, seed=seed)
    batch = {
        "image": imgs.astype(np.float32).reshape(
            steps_per_call, global_batch, 32, 32, 3
        ),
        "label": labels.reshape(steps_per_call, global_batch),
        "mask": np.ones((steps_per_call, global_batch), bool),
    }
    batch = jax.device_put(batch, stacked_batch_sharding(mesh))

    flops_per_call = compiled_flops(step, state, batch)
    _, calls, elapsed = _measure(
        step, state, batch,
        target_seconds=target_seconds, max_calls=max_calls,
    )
    per_chip = calls * steps_per_call * global_batch / elapsed / n_chips
    return {
        "images_per_sec_per_chip": round(per_chip, 1),
        "mfu": mfu(flops_per_call, calls / elapsed),
        "per_shard_batch": per_shard,
        "steps_per_call": steps_per_call,
        "n_chips": n_chips,
    }


def _bench_flagship() -> dict:
    from tpu_ddp.models import NetResDeep
    from tpu_ddp.train import make_optimizer

    point = _scan_point(
        NetResDeep(), make_optimizer(lr=1e-2),
        steps_per_call=32, per_shard=32, seed=0,
    )
    return {"model": "netresdeep", "dtype": "float32", **point}


def _bench_dispatch_baseline() -> dict:
    """The reference's execution pattern — ONE optimizer step per host
    dispatch (``main.py:32-41``'s per-batch loop) — on the same model,
    per-shard batch, and hardware as the flagship. Measured in the same
    bench run so ``vs_baseline`` is self-contained evidence rather than a
    constant."""
    import jax
    import numpy as np

    from tpu_ddp.data import synthetic_cifar10
    from tpu_ddp.models import NetResDeep
    from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
    from tpu_ddp.train import create_train_state, make_optimizer, make_train_step

    devices = jax.devices()
    n_chips = len(devices)
    mesh = create_mesh(MeshSpec(data=-1), devices)
    model = NetResDeep()
    tx = make_optimizer(lr=1e-2)
    state = create_train_state(model, tx, jax.random.key(0))
    step = make_train_step(model, tx, mesh)

    per_shard = 32
    global_batch = per_shard * n_chips
    imgs, labels = synthetic_cifar10(global_batch, seed=0)
    batch = {
        "image": imgs.astype(np.float32),
        "label": labels,
        "mask": np.ones(global_batch, bool),
    }
    batch = jax.device_put(batch, batch_sharding(mesh))
    _, calls, elapsed = _measure(
        step, state, batch, target_seconds=4.0, max_calls=400
    )
    per_chip = calls * global_batch / elapsed / n_chips
    return {
        "images_per_sec_per_chip": round(per_chip, 1),
        "model": "netresdeep",
        "dtype": "float32",
        "per_shard_batch": per_shard,
        "steps_per_call": 1,
        "n_chips": n_chips,
    }


def _bench_zero1() -> dict:
    """ZeRO-1 weight-update sharding (--zero1) on the SAME model/batch as
    the dispatch-per-step DP baseline: one row with images/sec/chip plus
    the compiled step's per-device memory next to the replicated row's —
    the bench-JSON evidence for the 1/N optimizer-state claim
    (parallel/zero.py; AOT ground truth in benchmarks/aot_v5e.json)."""
    import jax
    import numpy as np

    from tpu_ddp.data import synthetic_cifar10
    from tpu_ddp.models import NetResDeep
    from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
    from tpu_ddp.parallel.zero import Zero1Partition
    from tpu_ddp.train import create_train_state, make_optimizer, make_train_step

    devices = jax.devices()
    n_chips = len(devices)
    mesh = create_mesh(MeshSpec(data=-1), devices)
    model = NetResDeep()
    # momentum so there IS param-sized optimizer state to shard (the
    # reference's SGD lr=1e-2 is stateless — nothing to scatter)
    tx_rep = make_optimizer(lr=1e-2, momentum=0.9)
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_train_state(model, tx_rep, jax.random.key(0))
    part = Zero1Partition(tx, state.params, n_chips)
    state = part.shard_state(state, mesh)
    step = make_train_step(model, tx, mesh, zero1=part)

    per_shard = 32
    global_batch = per_shard * n_chips
    imgs, labels = synthetic_cifar10(global_batch, seed=0)
    batch = jax.device_put(
        {
            "image": imgs.astype(np.float32),
            "label": labels,
            "mask": np.ones(global_batch, bool),
        },
        batch_sharding(mesh),
    )
    _, calls, elapsed = _measure(
        step, state, batch, target_seconds=4.0, max_calls=400
    )
    per_chip = calls * global_batch / elapsed / n_chips
    row = {
        "images_per_sec_per_chip": round(per_chip, 1),
        "model": "netresdeep",
        "dtype": "float32",
        "per_shard_batch": per_shard,
        "steps_per_call": 1,
        "momentum": 0.9,
        "n_chips": n_chips,
        "optimizer_state_accounting": part.accounting(),
    }
    try:  # compiler-ground-truth per-device bytes (backend permitting)
        rep_step = make_train_step(model, tx_rep, mesh)
        rep_state = create_train_state(model, tx_rep, jax.random.key(0))
        for name, s, st in (("zero1", step, state),
                            ("replicated", rep_step, rep_state)):
            ma = s.trace(st, batch).lower().compile().memory_analysis()
            if ma is not None:
                row[f"{name}_argument_bytes_per_device"] = int(
                    ma.argument_size_in_bytes)
                row[f"{name}_temp_bytes_per_device"] = int(
                    ma.temp_size_in_bytes)
    except Exception:
        pass
    return row


def _bench_grad_compress_int8() -> dict:
    """--grad-compress int8 on the SAME model/batch as the dispatch-per-
    step DP baseline: images/sec/chip with the block-scaled quantized
    ring gradient sync plus the static wire-byte accounting — the bench-
    JSON evidence for the ~4x gradient-bytes claim (parallel/
    compression.py; compiler-side HLO evidence in benchmarks/aot_v5e.json
    dp_zero1_int8_resnet50_bf16_b256x8)."""
    import jax
    import numpy as np

    from tpu_ddp.data import synthetic_cifar10
    from tpu_ddp.models import NetResDeep
    from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
    from tpu_ddp.parallel.compression import GradCompression, GradCompressor
    from tpu_ddp.train import create_train_state, make_optimizer, make_train_step

    devices = jax.devices()
    n_chips = len(devices)
    mesh = create_mesh(MeshSpec(data=-1), devices)
    model = NetResDeep()
    tx = make_optimizer(lr=1e-2, momentum=0.9)
    state = create_train_state(model, tx, jax.random.key(0))
    comp = GradCompressor(
        GradCompression(mode="int8", error_feedback=True),
        state.params, n_chips,
    )
    state = state.replace(grad_residual=comp.init_residual(mesh))
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    state = state.replace(
        step=jax.device_put(state.step, rep),
        params=jax.device_put(state.params, rep),
        batch_stats=jax.device_put(state.batch_stats, rep),
        opt_state=jax.device_put(state.opt_state, rep),
    )
    step = make_train_step(model, tx, mesh, compress=comp)

    per_shard = 32
    global_batch = per_shard * n_chips
    imgs, labels = synthetic_cifar10(global_batch, seed=0)
    batch = jax.device_put(
        {
            "image": imgs.astype(np.float32),
            "label": labels,
            "mask": np.ones(global_batch, bool),
        },
        batch_sharding(mesh),
    )
    _, calls, elapsed = _measure(
        step, state, batch, target_seconds=4.0, max_calls=400
    )
    per_chip = calls * global_batch / elapsed / n_chips
    return {
        "images_per_sec_per_chip": round(per_chip, 1),
        "model": "netresdeep",
        "dtype": "float32",
        "per_shard_batch": per_shard,
        "steps_per_call": 1,
        "momentum": 0.9,
        "n_chips": n_chips,
        "grad_compress": "int8",
        "error_feedback": True,
        "wire_accounting": comp.accounting(),
    }


def _cifar_compute_point(model, tx, *, per_shard: int, seed: int = 1,
                         max_calls: int = 50) -> dict:
    """ONE unfused CIFAR-shape (32x32) measurement point: the single
    implementation of the flat-batch build and rate math shared by the
    ResNet-50 headline/sweep legs and the WRN compute leg."""
    import jax
    import numpy as np

    from tpu_ddp.data import synthetic_cifar10
    from tpu_ddp.metrics.mfu import compiled_flops, mfu
    from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
    from tpu_ddp.train import create_train_state, make_train_step

    devices = jax.devices()
    n_chips = len(devices)
    mesh = create_mesh(MeshSpec(data=-1), devices)
    state = create_train_state(model, tx, jax.random.key(0))
    step = make_train_step(model, tx, mesh)

    global_batch = per_shard * n_chips
    imgs, labels = synthetic_cifar10(global_batch, seed=seed)
    batch = {
        "image": imgs.astype(np.float32),
        "label": labels,
        "mask": np.ones(global_batch, bool),
    }
    batch = jax.device_put(batch, batch_sharding(mesh))

    flops_per_call = compiled_flops(step, state, batch)
    _, calls, elapsed = _measure(step, state, batch, max_calls=max_calls)
    per_chip = calls * global_batch / elapsed / n_chips
    return {
        "images_per_sec_per_chip": round(per_chip, 1),
        "mfu": mfu(flops_per_call, calls / elapsed),
        "per_shard_batch": per_shard,
        "n_chips": n_chips,
    }


def _resnet50_bf16_point(per_shard: int, *, max_calls: int = 50) -> dict:
    """ONE measured ResNet-50 bf16 train-step point at the given per-shard
    batch. The headline compute leg and the batch sweep both call this, so
    the sweep is structurally the SAME measurement as the headline — same
    optimizer knobs, same seed, same measurement discipline — varying only
    the batch."""
    import jax.numpy as jnp

    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.train import make_optimizer

    model = MODEL_REGISTRY["resnet50"](num_classes=10, dtype=jnp.bfloat16)
    tx = make_optimizer(lr=1e-1, momentum=0.9)
    return _cifar_compute_point(model, tx, per_shard=per_shard, seed=1,
                                max_calls=max_calls)


def _bench_vit_compute() -> dict:
    """ViT-B/16 bf16 at 224x224 (196 tokens, hidden 768): the
    matmul-dominated compute leg. ResNet-50 on 32x32 CIFAR leaves the MXU
    under-tiled by tiny spatial maps; this is the config that shows what
    the framework's train step does when the FLOPs are MXU-shaped."""
    import jax.numpy as jnp

    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.train import make_optimizer

    model = MODEL_REGISTRY["vit_b16"](num_classes=1000, dtype=jnp.bfloat16)
    point = _image224_point(
        model, make_optimizer(lr=1e-3, momentum=0.9),
        num_classes=1000, per_shard=64, seed=3, max_calls=30,
    )
    return {"model": "vit_b16", "dtype": "bfloat16", **point}


def _bench_compute_point(per_shard: int) -> dict:
    """ONE ResNet-50 bf16 row at the given per-shard batch — the
    batch-sweep unit."""
    return {
        "model": "resnet50", "dtype": "bfloat16",
        **_resnet50_bf16_point(per_shard),
    }


def _image224_point(model, tx, *, num_classes: int, per_shard: int,
                    seed: int, max_calls: int) -> dict:
    """ONE unfused 224x224 measurement point: the single implementation of
    the ImageNet-shape batch build and rate math shared by the ViT and
    ResNet-50 compute-capability legs."""
    import jax
    import numpy as np

    from tpu_ddp.metrics.mfu import compiled_flops, mfu
    from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
    from tpu_ddp.train import create_train_state, make_train_step

    devices = jax.devices()
    n_chips = len(devices)
    mesh = create_mesh(MeshSpec(data=-1), devices)
    state = create_train_state(
        model, tx, jax.random.key(0), input_shape=(1, 224, 224, 3)
    )
    step = make_train_step(model, tx, mesh)

    global_batch = per_shard * n_chips
    rng = np.random.default_rng(seed)
    batch = {
        "image": rng.standard_normal(
            (global_batch, 224, 224, 3), dtype=np.float32),
        "label": rng.integers(0, num_classes, global_batch),
        "mask": np.ones(global_batch, bool),
    }
    batch = jax.device_put(batch, batch_sharding(mesh))

    flops_per_call = compiled_flops(step, state, batch)
    _, calls, elapsed = _measure(step, state, batch, max_calls=max_calls)
    per_chip = calls * global_batch / elapsed / n_chips
    return {
        "images_per_sec_per_chip": round(per_chip, 1),
        "mfu": mfu(flops_per_call, calls / elapsed),
        "image_size": 224,
        "per_shard_batch": per_shard,
        "n_chips": n_chips,
    }


def _bench_attention() -> dict:
    """flash (Pallas, compiled) vs full (fused jnp) attention on the same
    ViT train step: the measured justification for --attention flash.
    Numerics are verified against the jnp reference before timing, so a
    silently-wrong compiled kernel can't report a speedup."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_ddp.data import synthetic_cifar10
    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.ops.flash_attention import _reference, flash_attention
    from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
    from tpu_ddp.train import create_train_state, make_optimizer, make_train_step

    # Compiled-kernel correctness first (fwd + bwd vs jnp reference).
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (2, 128, 2, 64), jnp.float32) for kk in ks)
    out = flash_attention(q, k, v)
    ref = _reference(q, k, v)
    fwd_err = float(jnp.max(jnp.abs(out - ref)))
    g_fl = jax.grad(lambda a, b, c: flash_attention(a, b, c).sum(), (0, 1, 2))(q, k, v)
    g_rf = jax.grad(lambda a, b, c: _reference(a, b, c).sum(), (0, 1, 2))(q, k, v)
    bwd_err = float(max(jnp.max(jnp.abs(x - y)) for x, y in zip(g_fl, g_rf)))
    # On a TPU, BOTH programs round their f32 matmuls through the MXU's
    # bf16 pass at default precision, so kernel-vs-reference max-abs error
    # lands at bf16 rounding scale — that is accumulation-order noise, not
    # a wrong kernel. (The tight f32 bound is the CPU suite's: tests/
    # test_ops.py.)
    assert fwd_err < 8e-3 and bwd_err < 1.5e-2, (fwd_err, bwd_err)

    devices = jax.devices()
    n_chips = len(devices)
    mesh = create_mesh(MeshSpec(data=-1), devices)
    per_shard = 128
    global_batch = per_shard * n_chips
    imgs, labels = synthetic_cifar10(global_batch, seed=2)
    batch = {
        "image": imgs.astype(np.float32),
        "label": labels,
        "mask": np.ones(global_batch, bool),
    }
    batch = jax.device_put(batch, batch_sharding(mesh))

    out = {"compiled_fwd_max_err": round(fwd_err, 7),
           "compiled_bwd_max_err": round(bwd_err, 7)}
    for name, impl in (("full", None), ("flash", flash_attention)):
        model = MODEL_REGISTRY["vit_s4"](
            num_classes=10, dtype=jax.numpy.bfloat16
        )
        if impl is not None:
            model = model.clone(attention_impl=impl)
        tx = make_optimizer(lr=1e-2, momentum=0.9)
        state = create_train_state(model, tx, jax.random.key(0))
        step = make_train_step(model, tx, mesh)
        _, calls, elapsed = _measure(step, state, batch, target_seconds=5.0)
        out[name] = round(calls * global_batch / elapsed / n_chips, 1)
    out["flash_speedup"] = round(out["flash"] / out["full"], 3)
    return out


def _time_attn_impl(fn, q, k, v) -> float:
    """fwd+bwd (grad wrt q,k,v) calls/sec for one attention impl — the ONE
    implementation of the attention-op timing discipline, shared by every
    attention microbench leg. Same fencing discipline as _measure: compile,
    fence, size the timed window from one FENCED call (async dispatch
    returns in microseconds — an unfenced wall-clock budget never binds and
    would enqueue hundreds of in-flight multi-MB output sets)."""
    import jax
    import jax.numpy as jnp

    loss = jax.jit(jax.value_and_grad(
        lambda a, b, c: fn(a, b, c).astype(jnp.float32).mean(),
        (0, 1, 2),
    ))
    val, _ = loss(q, k, v)
    val.block_until_ready()
    t0 = time.perf_counter()
    val, _ = loss(q, k, v)
    val.block_until_ready()
    per_call = max(time.perf_counter() - t0, 1e-6)
    calls = int(max(3, min(100, 3.0 / per_call)))
    t0 = time.perf_counter()
    for _ in range(calls):
        val, _ = loss(q, k, v)
    val.block_until_ready()
    return calls / (time.perf_counter() - t0)


def _attn_qkv(B: int, T: int, H: int, D: int, seed: int):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
                 for kk in ks)


def _attention_op_microbench() -> dict:
    """Raw attention-op timing at T=2048 (bf16, B=4, H=8, D=128): the
    long-sequence regime where the flash kernel's VMEM tiling matters,
    timed fwd+bwd (grad wrt q,k,v) for both the Pallas kernel and the
    fused-jnp reference on the same device."""
    from tpu_ddp.ops.flash_attention import _reference, flash_attention

    B, T, H, D = 4, 2048, 8, 128
    q, k, v = _attn_qkv(B, T, H, D, seed=3)
    full_ips = _time_attn_impl(_reference, q, k, v)
    flash_ips = _time_attn_impl(flash_attention, q, k, v)
    return {
        "shape": [B, T, H, D], "dtype": "bfloat16",
        "full_calls_per_sec": round(full_ips, 2),
        "flash_calls_per_sec": round(flash_ips, 2),
        "flash_speedup": round(flash_ips / full_ips, 3),
    }


def _read_winner_config(path: str) -> dict:
    """The TrainConfig field dict out of a tuner artifact: either the
    ``--emit-config`` winner shape ({"tune_winner_schema_version",
    "config"}) or the full ``tune --json`` table ({"winner_config"})."""
    with open(path) as f:
        art = json.load(f)
    version = art.get("tune_winner_schema_version")
    if isinstance(version, int) and version > 1:
        raise ValueError(
            f"{path}: tune_winner_schema_version {version} is newer "
            "than this bench understands (1)"
        )
    cfg = art.get("config")
    if not isinstance(cfg, dict):
        cfg = art.get("winner_config")
    if not isinstance(cfg, dict):
        raise ValueError(
            f"{path}: no 'config' / 'winner_config' dict — pass the "
            "artifact `tpu-ddp tune --emit-config` (or --json) wrote"
        )
    return cfg


def _bench_tune_winner(path: str) -> dict:
    """Measure a tuner-emitted winner config verbatim: the SAME short
    measured trial ``tpu-ddp tune --validate-top`` runs
    (``tuner/validate.py::measure_config`` — real Trainer, telemetry
    join through the run-metadata header), a few more dispatches for a
    steadier p50."""
    import tempfile

    from tpu_ddp.tuner.validate import measure_config

    cfg = _read_winner_config(path)
    run_dir = os.path.join(
        tempfile.mkdtemp(prefix="bench_tune_winner_"), "run")
    measured = measure_config(cfg, run_dir, trial_calls=6)
    return {"config": cfg, **measured}


def _require_tpu() -> tuple:
    """(platform, device_kind) of the default device — after exiting
    non-zero, ``{"ok": false}`` printed, when it is not a TPU: a bench
    number is a chip measurement or it is not printed."""
    import jax

    from tpu_ddp.parallel.runtime import is_tpu_device

    dev = jax.devices()[0]
    if not is_tpu_device():
        _emit({"ok": False,
               "error": f"bench.py measures a TPU; the default platform "
                        f"is {dev.platform!r}",
               "device": {"platform": dev.platform,
                          "kind": dev.device_kind,
                          "count": len(jax.devices())}})
        raise SystemExit(1)
    return dev.platform, dev.device_kind


def config_child_main(path: str) -> None:
    """``bench.py --child --config winner.json``: one measured leg of
    the tuner's winner, emitted in the bench headline shape."""
    import traceback

    import jax

    from tpu_ddp.parallel.runtime import enable_compile_cache

    platform, kind = _require_tpu()
    enable_compile_cache()
    from tpu_ddp.telemetry.provenance import artifact_provenance

    provenance = artifact_provenance(
        descriptor={"artifact": "bench.py --config",
                    "config_path": os.path.basename(path)},
        device_kind=kind, jax_version=jax.__version__,
    )
    try:
        row = _bench_tune_winner(path)
        result = {
            "metric": "tune_winner_images_per_sec_per_chip",
            "value": row["measured_images_per_sec_per_chip"],
            "unit": "images/sec/chip",
            "backend": platform,
            "device_kind": kind,
            "tune_winner": row,
        }
    except Exception:
        result = {
            "ok": False,
            "metric": "tune_winner_images_per_sec_per_chip",
            "value": 0.0,
            "unit": "images/sec/chip",
            "error": traceback.format_exc(limit=2).strip(),
        }
    result["provenance"] = provenance
    _emit(result)
    if "error" in result:
        # a failed winner measurement must fail the invocation: a CI
        # step gating on `bench.py --config` (or a registry ingesting
        # the record) must never read a 0.0 rate as a clean pass
        raise SystemExit(1)


def child_main() -> None:
    """Runs the bench configs in priority order, emitting the headline JSON
    line as soon as the flagship number exists. Exits non-zero when the
    platform is not a TPU, and — after every leg has had its turn — when
    any leg recorded an ``error`` or was skipped at the deadline."""
    import traceback

    import jax

    from tpu_ddp.parallel.runtime import enable_compile_cache

    backend, kind = _require_tpu()
    # Persistent compile cache: a retried child skips recompiling
    # identical programs.
    enable_compile_cache()
    deadline = _child_deadline()
    print(
        f"bench child: backend={backend} kind={kind} "
        f"budget={deadline - time.time():.0f}s",
        file=sys.stderr, flush=True,
    )
    # Provenance header (same fields as a run dir's metadata): which
    # commit produced this record, which logical bench config (the
    # deterministic digest keys the perf-registry series), which chip.
    from tpu_ddp.telemetry.provenance import artifact_provenance

    provenance = artifact_provenance(
        descriptor={"artifact": "bench.py", "n_chips": len(jax.devices())},
        device_kind=kind, jax_version=jax.__version__,
    )
    try:
        flagship = _bench_flagship()
    except Exception:
        flagship = {"error": traceback.format_exc(limit=2).strip()}
    per_chip = flagship.get("images_per_sec_per_chip")
    mfu_val = flagship.get("mfu")
    headline = {
        "metric": "cifar10_train_images_per_sec_per_chip",
        "value": per_chip if per_chip is not None else 0.0,
        "unit": "images/sec/chip",
        "vs_baseline": round(
            (per_chip or 0.0) / FALLBACK_BASELINE_IMAGES_PER_SEC_PER_CHIP, 3
        ),
        "vs_baseline_source": "fallback_constant",
        "mfu": None if mfu_val is None else round(mfu_val, 4),
        "backend": backend,
        "device_kind": kind,
        "flagship": {k: v for k, v in flagship.items() if k != "error"},
        "provenance": provenance,
    }
    failed, skipped = [], []
    if "error" in flagship:
        headline["error"] = flagship["error"]
        failed.append("flagship")
    _emit(headline)
    out = dict(headline)

    def _leg(key: str, fn) -> dict:
        # Each completed leg re-emits the updated result line immediately:
        # a child killed at the deadline still leaves every finished
        # sub-bench in the output. A leg that raises records its error and
        # the rest still run; a leg the deadline left no room for is
        # recorded as skipped — either way the child then exits non-zero:
        # an incomplete record is not a whole one.
        print(f"bench child: leg {key} starting "
              f"({deadline - time.time():.0f}s left)",
              file=sys.stderr, flush=True)
        if time.time() >= deadline - 60:
            r = {"skipped": "deadline"}
            skipped.append(key)
        else:
            try:
                r = fn()
            except Exception:
                r = {"error": traceback.format_exc(limit=2).strip()}
                failed.append(key)
        out[key] = r
        _emit(out)
        return r

    # The reference's dispatch-per-step pattern on the same hardware: the
    # measured vs_baseline denominator (round-2 verdict: the constant was
    # unverifiable).
    base = _leg("baseline_dispatch_per_step", _bench_dispatch_baseline)
    base_v = base.get("images_per_sec_per_chip")
    if per_chip and base_v:
        out["vs_baseline"] = round(per_chip / base_v, 3)
        out["vs_baseline_source"] = "measured_same_run"
    # ZeRO-1 row: same model/batch as the baseline, sharded weight update
    # (--zero1) — throughput + per-device memory next to the replicated
    # row.
    _leg("zero1_weight_update_sharding", _bench_zero1)
    # Quantized gradient collectives (--grad-compress int8): same
    # model/batch again, int8 ring sync + wire-byte accounting.
    _leg("grad_compress_int8", _bench_grad_compress_int8)
    # Cheapest compiles first: a blown deadline then costs the most
    # expensive legs only.
    _leg("attention_bench", _bench_attention)
    # the regime the flash kernel exists for (vit_s4's 64 tokens is not
    # it); its own leg so a deadline kill mid-microbench cannot lose the
    # already-emitted model rows
    _leg("attention_op_T2048", _attention_op_microbench)
    _leg("compute_bound", lambda: _bench_compute_point(256))
    # matmul-shaped compute (ViT-B/16 @224): the MXU ceiling the conv
    # stack can't reach on 32x32 inputs
    _leg("vit_compute", _bench_vit_compute)
    _promote_compute_headline(out)
    out["ok"] = not (failed or skipped)
    if failed:
        out["failed_legs"] = failed
    if skipped:
        out["skipped_legs"] = skipped
    _emit(out)
    if not out["ok"]:
        raise SystemExit(1)


def _promote_compute_headline(out: dict) -> None:
    """Round-3 verdict item 7: one ``value`` field must not conflate
    dispatch-fusion throughput (the 76K-param flagship, a number dominated
    by scan amortization) with compute throughput. Both configs become
    named ``rows``; when the compute-bound leg has a number it IS the
    headline (top-level metric/value/mfu). ``vs_baseline`` stays the
    framework-vs-reference-pattern ratio on the reference's own model (the
    flagship row) — ``vs_baseline_row`` says so explicitly."""
    flagship_row = {
        "metric": "cifar10_train_images_per_sec_per_chip",
        "value": out.get("value"),
        "unit": "images/sec/chip",
        "mfu": out.get("mfu"),
        "vs_baseline": out.get("vs_baseline"),
        "vs_baseline_source": out.get("vs_baseline_source"),
        "note": "scan-fused dispatch throughput on the 76K-param reference "
                "model; measures dispatch amortization, not MXU compute",
    }
    rows = {"dispatch_fused_flagship": flagship_row}
    cb = out.get("compute_bound") or {}
    cb_v = cb.get("images_per_sec_per_chip") if isinstance(cb, dict) else None
    if cb_v:
        rows["compute_bound_resnet50_bf16"] = {
            "metric": "resnet50_bf16_train_images_per_sec_per_chip",
            "value": cb_v,
            "unit": "images/sec/chip",
            "mfu": cb.get("mfu"),
            "note": "compute-bound config: ResNet-50 bf16, the MXU number",
        }
        out["metric"] = "resnet50_bf16_train_images_per_sec_per_chip"
        out["value"] = cb_v
        out["mfu"] = cb.get("mfu")
        out["headline_row"] = "compute_bound_resnet50_bf16"
    else:
        out["headline_row"] = "dispatch_fused_flagship"
    vc = out.get("vit_compute") or {}
    vc_v = vc.get("images_per_sec_per_chip") if isinstance(vc, dict) else None
    if vc_v:
        rows["matmul_bound_vit_b16_bf16"] = {
            "metric": "vit_b16_bf16_train_images_per_sec_per_chip",
            "value": vc_v,
            "unit": "images/sec/chip",
            "mfu": vc.get("mfu"),
            "note": "matmul-shaped compute: ViT-B/16 bf16 at 224x224; the "
                    "headline stays the reference-family CNN",
        }
    out["vs_baseline_row"] = "dispatch_fused_flagship"
    out["rows"] = rows


# ---------------------------------------------------------------- parent --

def run_child(argv, timeout_s: float, *, env=None, grace: float = 20.0):
    """The ONE child choreography of the chip tools that capture their
    child's output (tpu_curve.py arms, tpu_recipe.py): spawn with merged
    stdout, register in ``_ACTIVE_CHILD`` so the caller's SIGTERM handler
    reaps a child that holds the chip, and on timeout TERM-then-KILL via
    ``_terminate_gracefully``. Returns ``(out, err, wall)``: ``err`` is
    None on success, else a timeout message or an ``rc=N: tail`` summary
    of the child's last output lines."""
    global _ACTIVE_CHILD
    t0 = time.time()
    proc = subprocess.Popen(
        argv, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=_REPO,
    )
    _ACTIVE_CHILD = proc
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        _terminate_gracefully(proc, grace=grace)
        out, _ = proc.communicate()
        return (out or "", f"timed out after {timeout_s:.0f}s",
                time.time() - t0)
    finally:
        _ACTIVE_CHILD = None
    wall = time.time() - t0
    if proc.returncode != 0:
        tail = " | ".join((out or "").strip().splitlines()[-4:])
        return out or "", f"rc={proc.returncode}: {tail}", wall
    return out or "", None, wall


def _config_path_arg() -> str:
    i = sys.argv.index("--config")
    if i + 1 >= len(sys.argv):
        raise SystemExit("bench.py --config needs a winner.json path")
    return sys.argv[i + 1]


def main() -> None:
    if "--child" in sys.argv:
        if "--config" in sys.argv:
            config_child_main(_config_path_arg())
        else:
            child_main()
        return

    # The parent: stdlib-only, one child, the child's exit code.
    import signal

    def _on_term(signum, frame):
        # A caller's TERM must not orphan a child that holds the chip:
        # forward it and give the child a moment to tear down its client.
        child = _ACTIVE_CHILD
        if child is not None:
            _terminate_gracefully(child)
        raise SystemExit(124)

    signal.signal(signal.SIGTERM, _on_term)

    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--child"]
    if "--config" in sys.argv:
        # measure a tuner-emitted winner config (tpu-ddp tune
        # --emit-config) instead of the standard bench suite
        cmd += ["--config", _config_path_arg()]
    timeout_s = max(60.0, TOTAL_BUDGET_S - 30)
    env = dict(os.environ)
    env[_DEADLINE_ENV] = str(time.time() + timeout_s)
    env["PYTHONUNBUFFERED"] = "1"
    global _ACTIVE_CHILD
    # INHERITED stdout: the child's JSON lines stream out as produced
    proc = subprocess.Popen(cmd, env=env, cwd=_REPO)
    _ACTIVE_CHILD = proc
    try:
        rc = proc.wait(timeout=timeout_s + 30)
    except subprocess.TimeoutExpired:
        _terminate_gracefully(proc, grace=20)
        _emit({"ok": False,
               "error": f"bench child timed out after {timeout_s:.0f}s"})
        rc = 124
    finally:
        _ACTIVE_CHILD = None
    raise SystemExit(rc)


if __name__ == "__main__":
    main()
