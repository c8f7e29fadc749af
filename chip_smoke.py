#!/usr/bin/env python
"""Does the trainer still start on the chip? One run of the main path.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # the data-parallel path on four

ONE process: this process uses the chip and starts no children (a chip
belongs to one process at a time). It never selects a platform; it FAILS —
non-zero exit, ``"ok": false`` — when ``jax.devices()[0].platform`` is not
``"tpu"``, and when any phase raises: nothing is caught and skipped, nothing
falls back to the CPU, an interpreter or a reference.

Every phase prints one JSON line — its wall seconds, the seconds of it
spent compiling, the device it ran on, what it checked — and the last line
of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as jax reports it. The numbers are smoke observations (is
it alive, is it right), not benchmark numbers.

Phases, cheapest compile first (``--chips 4`` runs ``data_parallel`` and
nothing else):

- ``reference_recipe``: NetResDeep f32, per-shard batch 32, SGD 1e-2,
  through the CLI entry (``tpu_ddp.cli.train.main``) with an eval each
  epoch, a checkpoint save, a ``--resume`` that must print the step it
  resumed from, and once more under ``--steps-per-call 8``; the loss must
  be finite and fall.
- ``full_width_cli``: ResNet-50 bf16, per-shard batch 256, through the
  same entry — the largest model the ``Trainer`` path reaches.
- ``full_width_step``: ViT-B/16 bf16 at 224x224, batch 64, through
  ``make_train_step`` (no CLI path feeds 224x224 images) — the largest
  model the repo supports; it also checks that ``block_until_ready`` does
  not return before the work is done.
- ``kernels_direct``: flash attention forward and backward at each kind of
  plan the kernel makes (tiled, causal and not; ViT-B/16's 196 tokens as
  one whole-axis block; a length padded to a block multiple and masked),
  the int8 quantize/dequantize pair and the fused optimizer update, each
  against its jnp reference within a stated tolerance and each found as a
  ``tpu_custom_call`` in its compiled program.
- ``kernels_cli``: a ``vit_s4 --attention flash`` run and a ``--kernels``
  run through the CLI entry, with the same custom-call assertion on the
  step the ``Trainer`` compiled.
- ``data_parallel`` (``--chips 4``): NetResDeep DP on a ``data=4`` mesh
  against the single-device run (the comparison of
  ``tests/test_parallel.py::test_dp_matches_single_device``), then
  ``--zero1`` the same way; the batch must sit in quarters on four distinct
  devices, the params on all four, and the compiled step must contain its
  collectives.

The phase functions take their sizes as arguments: ``tests/test_chip_smoke
.py`` runs each at a tiny size on the CPU. The script has no option for it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import re
import sys
import tempfile
import time
from unittest import mock

#: relative tolerances (max-abs error over max-abs reference) for the
#: kernels on bf16 operands: the kernel rounds its output to bf16 once
#: (2^-9 relative), the backward additionally differences bf16-rounded
#: residuals — a wrong mask, scale or tile shows as O(1), not O(1e-2)
FLASH_FWD_TOL = 1e-2
FLASH_BWD_TOL = 3e-2
#: fused optimizer update vs the optax chain: same f32 expressions, the
#: compiled kernel may contract/reassociate them differently
UPDATE_RTOL, UPDATE_ATOL = 1e-5, 1e-6
#: --zero1 against DP on the same mesh, final loss and params: the same
#: math in another reduction order — which the chip's bf16-pass f32
#: convolutions amplify step by step (observed on four v5e chips: 5e-3 max
#: param difference after 16 steps at lr 0.02, against 4.5e-8 on the CPU;
#: the phase trains at lr 0.005). A broken sync — a missing gather, a sum
#: for a mean — shows as O(0.1) and more.
ZERO1_VS_DP_TOL = 1e-2

CUSTOM_CALL = "tpu_custom_call"

#: (shape, causal): one case for each kind of plan ``flash_attention``'s
#: ``_plan`` makes — 128x128 tiles that divide T (the old bench's shape);
#: ViT-B/16's own 196 tokens as one whole-axis block; and a T no block
#: divides, zero-padded to 1024 with the padding masked through ``kv_mask``
FLASH_CASES = (
    ((4, 2048, 8, 128), False),
    ((4, 2048, 8, 128), True),
    ((64, 196, 12, 64), False),
    ((2, 1000, 4, 64), True),
)


class SmokeFailure(Exception):
    """A phase found something wrong."""


def check(ok, message) -> None:
    """Raise unless ``ok`` (a statement ``python -O`` cannot strip)."""
    if not ok:
        raise SmokeFailure(str(message))


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def device_record() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _compile_counters() -> dict:
    """Compile and persistent-cache traffic so far, from the repo's own
    jax.monitoring bridge (telemetry/jax_hooks.py)."""
    from tpu_ddp.telemetry.registry import default_registry

    reg = default_registry()
    counters = reg.snapshot()["counters"]
    return {
        "compile_s": reg.histogram("jax/compile_seconds").sum,
        "compilations": int(counters.get("jax/compilations", 0)),
        "cache_hits": int(counters.get("jax/cache/cache_hits", 0)),
        "cache_misses": int(counters.get("jax/cache/cache_misses", 0)),
    }


def run_phase(name: str, fn, *args, **kwargs) -> None:
    """Run one phase and print its line. An exception propagates: the run
    fails at the first phase that does."""
    before = _compile_counters()
    t0 = time.perf_counter()
    info = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    after = _compile_counters()
    _emit({
        "phase": name,
        "wall_s": round(wall, 3),
        "compile_s": round(after["compile_s"] - before["compile_s"], 3),
        "compilations": after["compilations"] - before["compilations"],
        "device": device_record(),
        **info,
    })


def peak_bytes_in_use():
    """The device's high-water mark since the process started (None where
    the backend keeps no memory stats, as on the CPU)."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# ---- the CLI entry ---------------------------------------------------------

class _Tee:
    """Write-through to the real stdout that also keeps the text."""

    def __init__(self, stream):
        self._stream, self.text = stream, []

    def write(self, s):
        self.text.append(s)
        return self._stream.write(s)

    def flush(self):
        self._stream.flush()


def cli_train(argv) -> tuple:
    """``tpu_ddp.cli.train.main(argv)`` — what ``main.py`` calls — returning
    ``(metrics, trainer, stdout_text)``. The Trainer ``main`` builds is
    kept (by subclassing the name ``main`` looks up, no new option) so a
    phase can read the step it compiled and the arrays it placed."""
    from tpu_ddp.cli import train as cli

    built = []

    class _KeptTrainer(cli.Trainer):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            built.append(self)

    tee = _Tee(sys.stdout)
    with mock.patch.object(cli, "Trainer", _KeptTrainer), \
            contextlib.redirect_stdout(tee):
        metrics = cli.main([str(a) for a in argv])
    return metrics, built[-1], "".join(tee.text)


def _epoch_losses(jsonl_path: str) -> list:
    with open(jsonl_path) as f:
        records = [json.loads(line) for line in f if line.strip()]
    return [r["train_loss"] for r in records if "train_loss" in r]


def _check_falling(losses, what: str) -> None:
    check(len(losses) >= 2, f"{what}: need two epochs, got {losses}")
    check(all(math.isfinite(x) for x in losses),
          f"{what}: non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"{what}: loss did not fall: {losses}")


def compiled_step_text(trainer) -> str:
    """Optimized HLO of the step the Trainer trains with (the scan-fused
    one under ``--steps-per-call``), lowered ahead of time from the real
    jitted function and the layouts it runs in."""
    import jax

    state, batch = trainer.abstract_step_inputs()
    step = trainer.train_step
    if trainer.multi_step is not None:
        step = trainer.multi_step
        batch = {
            k: jax.ShapeDtypeStruct(
                (trainer.steps_per_call,) + v.shape, v.dtype,
                sharding=trainer.stacked_sharding)
            for k, v in batch.items()
        }
    return step.lower(state, batch).compile().as_text()


# ---- phases ----------------------------------------------------------------

def reference_recipe(run_dir: str, *, device: str = "tpu",
                     synthetic_size: int = 1024, epochs: int = 3,
                     batch: int = 32, steps_per_call: int = 8,
                     extra=()) -> dict:
    """The reference recipe through the CLI: train with eval + checkpoint,
    resume, then the scan-fused dispatch."""
    steps_per_epoch = synthetic_size // batch
    ckpt = os.path.join(run_dir, "ckpt")
    jsonl = os.path.join(run_dir, "recipe.jsonl")
    base = ["--device", device, "--n-devices", 1, "--synthetic-data",
            "--synthetic-size", synthetic_size, "--batch-size", batch,
            "--lr", 1e-2, "--log-every-epochs", 1, "--eval-each-epoch",
            *extra]
    saved = ["--checkpoint-dir", ckpt, "--checkpoint-every-epochs", 1]

    metrics, _, _ = cli_train(
        base + saved + ["--epochs", epochs, "--jsonl", jsonl])
    losses = _epoch_losses(jsonl)
    _check_falling(losses, "reference recipe")
    check(math.isfinite(metrics["test_accuracy"]),
          f"reference recipe: eval gave {metrics}")
    saved_steps = sorted(int(d) for d in os.listdir(ckpt) if d.isdigit())
    check(saved_steps and saved_steps[-1] == epochs * steps_per_epoch,
          f"checkpoint dir holds steps {saved_steps}")

    _, trainer, out = cli_train(
        base + saved + ["--epochs", epochs + 1, "--resume"])
    match = re.search(r"resumed from step (\d+)", out)
    check(match, "--resume printed no 'resumed from step N'")
    resumed = int(match.group(1))
    check(resumed == epochs * steps_per_epoch == trainer.resumed_step,
          f"resumed from step {resumed} (trainer: {trainer.resumed_step}), "
          f"saved at {epochs * steps_per_epoch}")
    check(int(trainer.state.step) == (epochs + 1) * steps_per_epoch,
          f"resumed run ended at step {int(trainer.state.step)}")

    scan_jsonl = os.path.join(run_dir, "scan.jsonl")
    cli_train(base + ["--epochs", epochs, "--jsonl", scan_jsonl,
                      "--steps-per-call", steps_per_call])
    scan_losses = _epoch_losses(scan_jsonl)
    _check_falling(scan_losses, f"--steps-per-call {steps_per_call}")
    return {
        "steps": epochs * steps_per_epoch,
        "loss_first_epoch": losses[0], "loss_last_epoch": losses[-1],
        "test_accuracy": metrics["test_accuracy"],
        "resumed_from_step": resumed,
        "scan_steps_per_call": steps_per_call,
        "scan_loss_first_epoch": scan_losses[0],
        "scan_loss_last_epoch": scan_losses[-1],
    }


def full_width_cli(run_dir: str, *, device: str = "tpu",
                   model: str = "resnet50", batch: int = 256,
                   synthetic_size: int = 1024, extra=()) -> dict:
    """One epoch of a full-width model through the CLI entry."""
    jsonl = os.path.join(run_dir, f"{model}.jsonl")
    metrics, _, _ = cli_train([
        "--device", device, "--n-devices", 1, "--synthetic-data",
        "--synthetic-size", synthetic_size, "--model", model,
        "--compute-dtype", "bfloat16", "--batch-size", batch,
        "--epochs", 1, "--log-every-epochs", 1, "--jsonl", jsonl, *extra])
    (loss,) = _epoch_losses(jsonl)
    check(math.isfinite(loss), f"{model}: loss {loss}")
    check(math.isfinite(metrics["test_accuracy"]),
          f"{model}: eval gave {metrics}")
    return {"model": model, "dtype": "bfloat16", "per_shard_batch": batch,
            "steps": synthetic_size // batch, "loss": loss,
            "peak_bytes_in_use": peak_bytes_in_use()}


def full_width_step(model, *, image_size: int = 224, batch: int = 64,
                    num_classes: int = 1000, steps: int = 3) -> dict:
    """A few steps of ``make_train_step`` on one device, on one batch
    staged before the first of them — and the one-line check that
    ``block_until_ready`` is honest: once it returns, fetching a value that
    depends on every step must cost no more than a copy."""
    import jax
    import numpy as np

    from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
    from tpu_ddp.parallel.mesh import replicated_sharding
    from tpu_ddp.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )

    mesh = create_mesh(MeshSpec(data=-1), jax.devices()[:1])
    tx = make_optimizer(lr=1e-3, momentum=0.9)
    # placed in the layout the step returns it in: a fresh (uncommitted)
    # state compiles the step once, its mesh-replicated output once more
    state = jax.device_put(
        create_train_state(model, tx, jax.random.key(0),
                           input_shape=(1, image_size, image_size, 3)),
        replicated_sharding(mesh))
    step = make_train_step(model, tx, mesh)
    rng = np.random.default_rng(3)
    host_batch = {
        "image": rng.standard_normal(
            (batch, image_size, image_size, 3), dtype=np.float32),
        "label": rng.integers(0, num_classes, batch),
        "mask": np.ones(batch, bool),
    }
    dev_batch = jax.device_put(host_batch, batch_sharding(mesh))

    state, metrics = step(state, dev_batch)  # compiles
    first = float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, dev_batch)
    t_enqueued = time.perf_counter()
    jax.block_until_ready((state, metrics))
    t_blocked = time.perf_counter()
    last = float(metrics["loss"])
    t_fetched = time.perf_counter()
    check(math.isfinite(first) and math.isfinite(last),
          f"non-finite loss: {first} -> {last}")
    run_s = t_blocked - t0
    fetch_s = t_fetched - t_blocked
    # a fence that returned early would leave the step time in the fetch
    honest = fetch_s < max(0.05 * run_s, 5e-3)
    check(honest,
          f"block_until_ready returned before the work was done: {steps} "
          f"steps fenced in {run_s:.4f}s, the fetch after took "
          f"{fetch_s:.4f}s")
    return {
        "model": type(model).__name__, "image_size": image_size,
        "batch": batch, "steps": 1 + steps,
        "loss_first": first, "loss_last": last,
        "enqueue_s": round(t_enqueued - t0, 4),
        "enqueue_to_fence_s": round(run_s, 4),
        "fetch_after_fence_s": round(fetch_s, 6),
        "block_until_ready_honest": honest,
        "peak_bytes_in_use": peak_bytes_in_use(),
    }


def _rel_err(got, want) -> float:
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _custom_calls(fn, *args) -> int:
    import jax

    return jax.jit(fn).lower(*args).compile().as_text().count(CUSTOM_CALL)


def _check_flash(shape, dtype, causal: bool, require: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_ddp.ops.flash_attention import _reference, flash_attention

    ks = jax.random.split(jax.random.key(7), 4)
    q, k, v, g = (jax.random.normal(kk, shape, dtype) for kk in ks)

    def kernel(a, b, c):
        return flash_attention(a, b, c, causal=causal)

    def reference(a, b, c):  # f32 math on the same (bf16-exact) operands
        return _reference(a.astype(jnp.float32), b.astype(jnp.float32),
                          c.astype(jnp.float32), causal=causal)

    def grads(fn):  # (q, k, v, cotangent) -> (dq, dk, dv)
        return jax.grad(
            lambda a, b, c, ct: (fn(a, b, c).astype(jnp.float32)
                                 * ct.astype(jnp.float32)).sum(),
            (0, 1, 2))

    fwd_err = _rel_err(jax.jit(kernel)(q, k, v), jax.jit(reference)(q, k, v))
    bwd_err = max(
        _rel_err(a, b)
        for a, b in zip(jax.jit(grads(kernel))(q, k, v, g),
                        jax.jit(grads(reference))(q, k, v, g)))
    check(fwd_err < FLASH_FWD_TOL and bwd_err < FLASH_BWD_TOL,
          f"flash {shape} causal={causal}: fwd {fwd_err} bwd {bwd_err} "
          f"against its reference")
    calls = (_custom_calls(kernel, q, k, v),
             _custom_calls(grads(kernel), q, k, v, g))
    if require:  # fwd: one kernel; bwd: fwd recompute + the one backward
        check(calls == (1, 2),
              f"flash {shape} causal={causal}: {calls} kernels")
    return {"shape": list(shape), "causal": causal,
            "fwd_rel_err": fwd_err, "bwd_rel_err": bwd_err,
            "custom_calls_fwd_bwd": list(calls)}


def _check_quant(n_elements: int, block: int, require: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_ddp.ops.fused_quant import (
        _reference_dequant,
        _reference_quant,
        fused_dequant,
        fused_quant,
    )

    x = jax.random.normal(jax.random.key(11), (n_elements,), jnp.float32)
    acc = jax.random.normal(jax.random.key(12), (n_elements,), jnp.float32)

    def quant(v):
        return fused_quant(v, block)

    def dequant(p, a):
        return fused_dequant(p, block, n_elements, add_to=a)

    got = jax.jit(quant)(x)
    want = jax.jit(lambda v: _reference_quant(v, block))(x)
    # the payload may differ from XLA's by one step where x/scale lands on
    # a rounding tie; the scales are one max and one divide: equal
    q_diff = jnp.abs(got["q"].astype(jnp.int32) - want["q"].astype(jnp.int32))
    differing = float((q_diff > 0).mean())
    check(int(q_diff.max()) <= 1 and differing < 1e-3,
          f"fused_quant payload: max step {int(q_diff.max())}, "
          f"{differing:.2e} of elements differ")
    check(bool(jnp.allclose(got["scale"], want["scale"], rtol=1e-6)),
          "fused_quant: block scales differ from the reference")
    back = jax.jit(dequant)(got, acc)
    ref_back = jax.jit(
        lambda p, a: _reference_dequant(p, block, n_elements, add_to=a)
    )(got, acc)
    check(bool(jnp.allclose(back, ref_back, rtol=1e-6, atol=1e-6)),
          "fused_dequant: differs from the reference on the same payload")
    # the round trip: within half a quantization step of its block
    step = jnp.repeat(got["scale"], block)[:n_elements]
    round_trip = jnp.abs((back - acc) - x)
    check(bool(jnp.all(round_trip <= 0.5 * step * (1 + 1e-3) + 1e-6)),
          "quantize/dequantize round trip left its half-step bound")
    calls = (_custom_calls(quant, x), _custom_calls(dequant, got, acc))
    if require:
        check(min(calls) >= 1, f"fused_quant/dequant: {calls} kernels")
    return {"elements": n_elements, "block": block,
            "payload_steps_differing": differing,
            "custom_calls_quant_dequant": list(calls)}


def _check_update(name: str, leaf_shapes, require: bool, **opt) -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_ddp.train.optim import apply_optimizer, make_optimizer

    keys = jax.random.split(jax.random.key(13), 2 * len(leaf_shapes))
    params = {f"leaf{i}": {"kernel": jax.random.normal(keys[2 * i], s)}
              for i, s in enumerate(leaf_shapes)}
    grads = {f"leaf{i}": {"kernel": jax.random.normal(keys[2 * i + 1], s)}
             for i, s in enumerate(leaf_shapes)}
    plain = make_optimizer(**opt)
    fused = make_optimizer(**opt, kernels=True)
    check(getattr(fused, "fused", None) is not None,
          f"{name}: make_optimizer(kernels=True) attached no fused update")

    def two_steps(tx):
        def run(p, g):
            state = tx.init(p)
            for _ in range(2):  # the second step sees non-zero moments
                p, _, state = apply_optimizer(tx, g, state, p)
            return p, state
        return run

    got = jax.jit(two_steps(fused))(params, grads)
    want = jax.jit(two_steps(plain))(params, grads)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        check(bool(jnp.allclose(a, b, rtol=UPDATE_RTOL, atol=UPDATE_ATOL)),
              f"{name}: fused update differs from the optax chain")
    calls = _custom_calls(two_steps(fused), params, grads)
    if require:
        check(calls >= 1, f"{name}: no kernel in the compiled update")
    return {"custom_calls": calls}


def kernels_direct(*, flash_cases=FLASH_CASES, dtype="bfloat16",
                   quant_elements: int = 2_359_296, quant_block: int = 256,
                   update_leaves=((768, 3072), (3, 3, 512, 512)),
                   require_custom_call: bool = True) -> dict:
    """Each Pallas kernel called directly, against its jnp reference."""
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    req = require_custom_call
    return {
        "flash_dtype": dtype,
        "flash_tolerance_fwd_bwd": [FLASH_FWD_TOL, FLASH_BWD_TOL],
        "flash": [_check_flash(tuple(shape), dt, causal, req)
                  for shape, causal in flash_cases],
        "fused_quant": _check_quant(quant_elements, quant_block, req),
        "update_leaves": [list(s) for s in update_leaves],
        "fused_update_sgd_momentum": _check_update(
            "sgd+momentum", update_leaves, req, lr=1e-2, momentum=0.9),
        "fused_update_adamw": _check_update(
            "adamw", update_leaves, req, lr=1e-3, optimizer="adamw",
            weight_decay=0.05),
    }


def kernels_cli(run_dir: str, *, device: str = "tpu", batch: int = 64,
                synthetic_size: int = 256, extra=(),
                require_custom_call: bool = True) -> dict:
    """The two kernel switches through the CLI entry; the kernel must be
    in the step the Trainer compiled."""
    out = {}
    runs = {
        "attention_flash": ["--model", "vit_s4", "--attention", "flash"],
        "kernels": ["--kernels", "--optimizer", "adamw",
                    "--weight-decay", 0.05, "--lr", 1e-3, *extra],
    }
    for name, flags in runs.items():
        jsonl = os.path.join(run_dir, f"{name}.jsonl")
        _, trainer, _ = cli_train([
            "--device", device, "--n-devices", 1, "--synthetic-data",
            "--synthetic-size", synthetic_size, "--batch-size", batch,
            "--epochs", 1, "--log-every-epochs", 1, "--jsonl", jsonl,
            *flags])
        (loss,) = _epoch_losses(jsonl)
        check(math.isfinite(loss), f"{name}: loss {loss}")
        calls = compiled_step_text(trainer).count(CUSTOM_CALL)
        if require_custom_call:
            check(calls >= 1, f"{name}: no kernel in the Trainer's step")
        out[name] = {"loss": loss, "custom_calls_in_step": calls}
    return out


def _dp_run(run_dir: str, name: str, *, device, n_devices, per_shard,
            synthetic_size, epochs, extra):
    jsonl = os.path.join(run_dir, f"{name}.jsonl")
    _, trainer, _ = cli_train([
        "--device", device, "--n-devices", n_devices, "--synthetic-data",
        "--synthetic-size", synthetic_size, "--batch-size", per_shard,
        # a calm recipe: at lr 0.02 the first steps overshoot (epoch loss
        # 4-9) and the loose comparison below turns into a coin toss
        "--no-shuffle", "--lr", 0.005, "--momentum", 0.9,
        "--epochs", epochs, "--log-every-epochs", 1, "--jsonl", jsonl,
        *extra])
    losses = _epoch_losses(jsonl)
    check(all(math.isfinite(x) for x in losses),
          f"{name}: non-finite loss in {losses}")
    return losses[-1], trainer


def data_parallel(run_dir: str, *, device: str = "tpu", n_devices: int = 4,
                  per_shard: int = 8, synthetic_size: int = 256,
                  epochs: int = 2, extra=()) -> dict:
    """DP, then ``--zero1``, on an ``n_devices`` data mesh through the CLI
    entry, each against the single-device run over the same global batches
    (``--no-shuffle``: n x per_shard == 1 x n*per_shard; BN normalizes per
    shard, so the trajectories agree only loosely — the comparison
    ``test_dp_matches_single_device`` makes). The two mesh runs share their
    BN semantics, so they must agree closely with each other."""
    import jax
    import jax.numpy as jnp

    check(jax.device_count() >= n_devices,
          f"needs {n_devices} devices, jax found {jax.device_count()}")
    common = dict(device=device, synthetic_size=synthetic_size,
                  epochs=epochs, extra=extra)
    single, _ = _dp_run(run_dir, "single", n_devices=1,
                        per_shard=n_devices * per_shard, **common)
    out = {"n_devices": n_devices, "per_shard_batch": per_shard,
           "loss_single_device": single}
    finals = {}
    for name, flags, wanted in (
        ("dp", (), ("all-reduce",)),
        # XLA may lower the psum_scatter as an all-reduce and a slice
        ("zero1", ("--zero1",), ("reduce-scatter|all-reduce", "all-gather")),
    ):
        loss, trainer = _dp_run(
            run_dir, name, n_devices=n_devices, per_shard=per_shard,
            **{**common, "extra": (*extra, *flags)})
        check(abs(loss - single) < 0.6 and loss < 3.0,
              f"{name}: loss {loss} against the single device's {single}")

        mesh_devices = set(trainer.mesh.devices.flat)
        check(len(mesh_devices) == n_devices,
              f"{name}: mesh spans {len(mesh_devices)} devices")
        # the batch, placed by the Trainer's own means: a quarter per device
        host = next(iter(trainer.train_loader))
        image = trainer._put(host)["image"]
        shards = image.addressable_shards
        check({s.device for s in shards} == mesh_devices,
              f"{name}: batch shards sit on {[s.device for s in shards]}")
        check(all(s.data.shape[0] * n_devices == image.shape[0]
                  for s in shards),
              f"{name}: batch of {image.shape[0]} split into "
              f"{[s.data.shape[0] for s in shards]}")
        # the params: whole, on every device
        for leaf in jax.tree.leaves(trainer.state.params):
            check(leaf.sharding.device_set == mesh_devices
                  and all(s.data.shape == leaf.shape
                          for s in leaf.addressable_shards),
                  f"{name}: a param leaf is not whole on every device: "
                  f"{leaf.sharding}")
        text = compiled_step_text(trainer)
        for pattern in wanted:
            check(re.search(rf" ({pattern})(-start)?\(", text),
                  f"{name}: no {pattern} in the compiled step")
        finals[name] = jax.device_get(trainer.state.params)
        out[f"loss_{name}"] = loss
        out[f"collectives_{name}"] = list(wanted)
    drift = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(finals["dp"]),
                        jax.tree.leaves(finals["zero1"])))
    check(abs(out["loss_dp"] - out["loss_zero1"]) < ZERO1_VS_DP_TOL,
          f"zero1 loss {out['loss_zero1']} against DP's {out['loss_dp']}")
    check(drift < ZERO1_VS_DP_TOL, f"zero1 params drifted {drift} from DP's")
    out["zero1_vs_dp_max_param_diff"] = drift
    return out


# ---- the run ---------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the data-parallel phase, on a "
                         "four-chip mesh (default: the one-chip phases)")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from tpu_ddp import native
    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.parallel.runtime import enable_compile_cache, is_tpu_device
    from tpu_ddp.telemetry.jax_hooks import install_jax_hooks

    device = device_record()
    if not is_tpu_device():
        _emit({"ok": False, "device": device,
               "error": f"chip_smoke.py needs a TPU; jax's default platform "
                        f"is {device['platform']!r}"})
        return 1
    install_jax_hooks()
    cache_dir = enable_compile_cache()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # a libtpu installed some other way: version unknown
        libtpu = None
    _emit({"phase": "start", "chips": args.chips, "device": device,
           "jax": jax.__version__, "jaxlib": jaxlib.__version__,
           "libtpu": libtpu, "native_available": native.AVAILABLE,
           "compile_cache_dir": cache_dir})
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as run_dir:
            if args.chips == 4:
                check(jax.device_count() == 4,
                      f"--chips 4 needs four chips, jax found "
                      f"{jax.device_count()}")
                run_phase("data_parallel", data_parallel, run_dir)
            else:
                import jax.numpy as jnp

                run_phase("reference_recipe", reference_recipe, run_dir)
                run_phase("full_width_cli", full_width_cli, run_dir)
                run_phase(
                    "full_width_step", full_width_step,
                    MODEL_REGISTRY["vit_b16"](num_classes=1000,
                                              dtype=jnp.bfloat16))
                run_phase("kernels_direct", kernels_direct)
                run_phase("kernels_cli", kernels_cli, run_dir)
    except BaseException as e:
        import traceback

        traceback.print_exc()
        _emit({"ok": False, "device": device,
               "error": f"{type(e).__name__}: {e}"[:2000]})
        if not isinstance(e, Exception):
            raise  # an interrupt or exit stays one
        return 1
    counters = _compile_counters()
    _emit({"phase": "compile_cache", "dir": cache_dir,
           "hits": counters["cache_hits"], "misses": counters["cache_misses"],
           "compilations": counters["compilations"],
           "compile_s": round(counters["compile_s"], 3)})
    _emit({"phase": "memory_stats",
           "stats": jax.devices()[0].memory_stats()})
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
