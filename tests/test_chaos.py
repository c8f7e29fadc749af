"""Chaos harness + verified checkpoints (docs/resilience.md).

Fast tier: spec validation, fire-once state, the save-flake hook, the
checksum manifest lifecycle (write/verify/refuse/sweep/fallback), the
Checkpointer's retry + verified-restore integration, the watchdog-abort
escalation (with ``os._exit`` stubbed). Slow tier: the cross-layout
elastic resume (8 devices -> 4 survivors with ``--zero1`` +
error-feedback residual, bit-consistent) and the second-SIGTERM
force-abort drain — both compile real Trainers.
"""

from __future__ import annotations

import json
import os
import signal
import time

import numpy as np
import pytest

from tpu_ddp.chaos.inject import (
    KILL_EXIT_CODE,
    ChaosInjector,
    capacity_file,
    load_spec,
)
from tpu_ddp.checkpoint import manifest

# -- chaos spec validation -------------------------------------------------


def _spec(tmp_path, faults, **extra):
    path = str(tmp_path / "spec.json")
    with open(path, "w") as f:
        json.dump({"chaos_schema_version": 1, "seed": 0,
                   "faults": faults, **extra}, f)
    return path


def test_spec_validates_kinds_and_fields(tmp_path):
    good = _spec(tmp_path, [
        {"kind": "kill_host", "step": 6, "survivors": 4},
        {"kind": "hang", "step": 5},
        {"kind": "checkpoint_corrupt", "step": 7, "await_step": 6},
        {"kind": "save_io_flake", "step": 2, "times": 2},
        {"kind": "data_stall", "step": 3, "stall_s": 0.5},
    ])
    spec = load_spec(good)
    assert len(spec["faults"]) == 5

    for faults, needle in (
        ([{"kind": "melt_down", "step": 1}], "unknown kind"),
        ([{"kind": "hang"}], "'step'"),
        ([{"kind": "hang", "step": -1}], "'step'"),
        ([{"kind": "save_io_flake", "step": 1, "times": 0}], "'times'"),
        ([{"kind": "kill_host", "step": 1, "survivors": 0}],
         "'survivors'"),
        ([], "non-empty"),
    ):
        with pytest.raises(ValueError, match=needle):
            load_spec(_spec(tmp_path, faults))
    # future schema refuses by name
    with pytest.raises(ValueError, match="chaos_schema_version"):
        load_spec(_spec(tmp_path, [{"kind": "hang", "step": 1}],
                        chaos_schema_version=99))


def test_trainconfig_validates_chaos_spec(tmp_path):
    from tpu_ddp.train.trainer import TrainConfig

    path = _spec(tmp_path, [{"kind": "bogus", "step": 1}])
    with pytest.raises(ValueError, match="unknown kind"):
        TrainConfig(synthetic_data=True, chaos_spec=path,
                    telemetry_dir=str(tmp_path)).validate()
    with pytest.raises(ValueError, match="telemetry-dir"):
        TrainConfig(synthetic_data=True, chaos_spec=path).validate()
    with pytest.raises(ValueError, match="watchdog-abort"):
        TrainConfig(synthetic_data=True, watchdog_abort=True).validate()


# -- fire-once semantics ---------------------------------------------------


def test_data_stall_fires_once_per_logical_run(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    path = _spec(tmp_path, [
        {"kind": "data_stall", "step": 2, "stall_s": 0.0}])
    inj = ChaosInjector(path, run_dir)
    inj.on_step(1)
    assert inj._load_state()["fired"] == []
    inj.on_step(2)
    assert json.load(open(os.path.join(run_dir, "chaos-state.json")))[
        "fired"] == [0]
    # a resumed incarnation replaying past the trigger must NOT re-fire
    inj2 = ChaosInjector(path, run_dir)
    inj2.on_step(5)  # would trigger were the state not persisted
    assert inj2._load_state()["fired"] == [0]


def test_faults_target_their_host(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    path = _spec(tmp_path, [
        {"kind": "data_stall", "step": 1, "process_index": 3,
         "stall_s": 0.0}])
    inj = ChaosInjector(path, run_dir, process_index=0)
    inj.on_step(9)
    assert inj._load_state()["fired"] == []
    inj3 = ChaosInjector(path, run_dir, process_index=3)
    inj3.on_step(9)
    assert inj3._load_state()["fired"] == [0]


def test_save_flake_hook_raises_exactly_times(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    path = _spec(tmp_path, [
        {"kind": "save_io_flake", "step": 3, "times": 2}])
    inj = ChaosInjector(path, run_dir)
    inj.save_fault_hook(1, 0)  # before the trigger step: quiet
    with pytest.raises(OSError, match="injected save IO failure"):
        inj.save_fault_hook(3, 0)
    # the remaining count persists across a restart (no fresh allowance)
    inj2 = ChaosInjector(path, run_dir)
    with pytest.raises(OSError):
        inj2.save_fault_hook(3, 1)
    inj2.save_fault_hook(3, 2)  # budget spent: the save goes through
    inj2.save_fault_hook(6, 0)


def test_kill_host_writes_capacity_then_exits(tmp_path, monkeypatch):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    path = _spec(tmp_path, [
        {"kind": "kill_host", "step": 6, "survivors": 4}])
    exits = []
    monkeypatch.setattr(os, "_exit", lambda code: exits.append(code))
    inj = ChaosInjector(path, run_dir)
    inj.on_step(6)
    assert exits == [KILL_EXIT_CODE]
    cap = json.load(open(capacity_file(run_dir)))
    assert cap["devices"] == 4
    # the fired record landed BEFORE the exit (crash-loop prevention)
    assert inj._load_state()["fired"] == [0]


# -- checksum manifests ----------------------------------------------------


def _fake_ckpt(tmp_path, step, payload=b"x" * 4096):
    root = tmp_path / str(step) / "data"
    root.mkdir(parents=True)
    (root / "array.bin").write_bytes(payload)
    (tmp_path / str(step) / "meta.json").write_text("{}")
    return str(tmp_path)


def test_manifest_roundtrip_and_refusal(tmp_path):
    d = _fake_ckpt(tmp_path, 4)
    _fake_ckpt(tmp_path, 8)
    for step in (4, 8):
        manifest.write_manifest(d, step)
    assert manifest.committed_steps(d) == [4, 8]
    assert manifest.verify_step(d, 8) == (True, [])
    # flip one bit in step 8's payload
    target = tmp_path / "8" / "data" / "array.bin"
    raw = bytearray(target.read_bytes())
    raw[100] ^= 1
    target.write_bytes(bytes(raw))
    verdict, problems = manifest.verify_step(d, 8)
    assert verdict is False
    assert any("sha256 mismatch" in p for p in problems)
    # newest-first walk refuses 8 BY NAME and falls back to 4
    step, refusals = manifest.latest_verified_step(d)
    assert step == 4
    assert [r["step"] for r in refusals
            if r["verdict"] == "refused"] == [8]


def test_manifest_missing_and_extra_files(tmp_path):
    d = _fake_ckpt(tmp_path, 2)
    manifest.write_manifest(d, 2)
    (tmp_path / "2" / "data" / "array.bin").unlink()
    verdict, problems = manifest.verify_step(d, 2)
    assert verdict is False and any("missing" in p for p in problems)
    d2 = _fake_ckpt(tmp_path / "b", 3)
    manifest.write_manifest(d2, 3)
    (tmp_path / "b" / "3" / "extra.bin").write_bytes(b"y")
    verdict, problems = manifest.verify_step(d2, 3)
    assert verdict is False and any("not in manifest" in p
                                    for p in problems)


def test_unmanifested_step_is_unverifiable_not_refused(tmp_path):
    d = _fake_ckpt(tmp_path, 5)  # legacy: no manifest at all
    step, refusals = manifest.latest_verified_step(d)
    assert step == 5
    assert refusals[0]["verdict"] == "unverifiable"
    assert manifest.verify_step(d, 5)[0] is None


def test_sweep_manifests(tmp_path):
    d = _fake_ckpt(tmp_path, 1)
    _fake_ckpt(tmp_path, 2)
    manifest.write_manifest(d, 1)
    manifest.write_manifest(d, 2)
    manifest.sweep_manifests(d, [2])
    assert manifest.read_manifest(d, 1) is None
    assert manifest.read_manifest(d, 2) is not None


def test_checkpoint_corrupt_fault_defeats_the_manifest(tmp_path):
    run_dir = str(tmp_path / "run")
    ckpt = tmp_path / "ckpt"
    os.makedirs(run_dir)
    _fake_ckpt(ckpt, 6)
    manifest.write_manifest(str(ckpt), 6)
    path = _spec(tmp_path, [
        {"kind": "checkpoint_corrupt", "step": 7, "await_step": 6,
         "timeout_s": 2}])
    inj = ChaosInjector(path, run_dir, checkpoint_dir=str(ckpt))
    inj.on_step(7)
    verdict, problems = manifest.verify_step(str(ckpt), 6)
    assert verdict is False and problems
    # deterministic: the same seed flips the same bit
    assert inj._load_state()["fired"] == [0]


def test_checkpoint_corrupt_requires_checkpoint_dir(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    path = _spec(tmp_path, [
        {"kind": "checkpoint_corrupt", "step": 1}])
    with pytest.raises(ValueError, match="checkpoint dir"):
        ChaosInjector(path, run_dir, checkpoint_dir=None)


# -- Checkpointer integration (orbax; small states, tier-1) ---------------


def _tiny_state():
    import jax.numpy as jnp

    return {"w": jnp.arange(16, dtype=jnp.float32),
            "b": jnp.ones((4,), jnp.float32)}


def test_checkpointer_save_retry_counts_and_succeeds(tmp_path):
    from tpu_ddp.checkpoint import Checkpointer

    calls = []

    def flake(step, attempt):
        if len(calls) < 2:
            calls.append((step, attempt))
            raise OSError("transient blob-store flake")

    ck = Checkpointer(str(tmp_path / "ck"), fault_hook=flake,
                      save_retry_base_s=0.01)
    ck.save(3, _tiny_state(), wait=True)
    assert calls == [(3, 0), (3, 1)]  # attempts 0 and 1 flaked, 2 won
    assert manifest.verify_step(str(tmp_path / "ck"), 3) == (True, [])
    ck.close()


def test_checkpointer_exhausted_retries_raise_only_on_wait(tmp_path):
    from tpu_ddp.checkpoint import Checkpointer

    def always(step, attempt):
        raise OSError("dead disk")

    ck = Checkpointer(str(tmp_path / "ck"), fault_hook=always,
                      save_attempts=2, save_retry_base_s=0.01)
    # cadence save: recorded, swallowed — training must not die for it
    ck.save(3, _tiny_state())
    assert ck.manager.latest_step() is None
    # final save: a silent drop would fake a clean exit — raise
    with pytest.raises(OSError, match="dead disk"):
        ck.save(4, _tiny_state(), wait=True)
    ck.close()


def test_checkpointer_restore_refuses_corrupt_and_falls_back(tmp_path):
    from tpu_ddp.checkpoint import Checkpointer

    d = str(tmp_path / "ck")
    ck = Checkpointer(d)
    state = _tiny_state()
    ck.save(2, state, wait=True)
    ck.save(5, {"w": state["w"] * 2, "b": state["b"] * 2}, wait=True)
    assert manifest.committed_steps(d) == [2, 5]
    # bit-flip step 5's largest file
    root = os.path.join(d, "5")
    files = [os.path.join(dp, f)
             for dp, _, fs in os.walk(root) for f in fs]
    target = max(files, key=os.path.getsize)
    with open(target, "r+b") as f:
        f.seek(os.path.getsize(target) // 2)
        byte = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([byte[0] ^ 1]))
    assert ck.verified_restore_step() == 2
    restored = ck.restore(_tiny_state())
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(16, dtype=np.float32))
    # an EXPLICITLY requested corrupt step refuses loudly — no fallback
    with pytest.raises(ValueError, match="REFUSED"):
        ck.restore(_tiny_state(), step=5)
    ck.close()


def test_async_save_gets_a_manifest_from_the_writer_thread(tmp_path):
    from tpu_ddp.checkpoint import Checkpointer

    d = str(tmp_path / "ck")
    ck = Checkpointer(d)
    ck.save(1, _tiny_state())          # async initiation
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if manifest.read_manifest(d, 1) is not None:
            break
        time.sleep(0.05)
    assert manifest.verify_step(d, 1) == (True, [])
    ck.close()


# -- watchdog abort escalation --------------------------------------------


def test_watchdog_abort_escalates_after_dump(monkeypatch):
    from tpu_ddp.telemetry import watchdog as wd

    exits = []
    monkeypatch.setattr(wd.os, "_exit",
                        lambda code: exits.append(code))
    dumps = []
    dog = wd.HangWatchdog(
        0.05, poll_interval=0.01, abort_on_hang=True,
        on_hang=dumps.append,
    ).start()
    try:
        deadline = time.monotonic() + 5
        while not exits and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        dog.close()
    assert exits and exits[0] == wd.HANG_EXIT_CODE
    assert dumps and "thread stacks follow" in dumps[0]


def test_watchdog_without_abort_only_dumps():
    from tpu_ddp.telemetry import watchdog as wd

    dumps = []
    dog = wd.HangWatchdog(
        0.05, poll_interval=0.01, on_hang=dumps.append,
    ).start()
    try:
        deadline = time.monotonic() + 5
        while not dumps and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        dog.close()
    assert dog.fired and dumps  # and the process is, visibly, alive


# -- slow tier: real Trainers ---------------------------------------------


def _elastic_config(ckpt_dir, **overrides):
    from tpu_ddp.train.trainer import TrainConfig

    base = dict(
        synthetic_data=True,
        synthetic_size=192,
        epochs=1,
        per_shard_batch=8,
        model="netresdeep",
        n_chans1=4,
        n_blocks=1,
        n_devices=8,
        prefetch_depth=0,
        momentum=0.9,
        zero1=True,
        grad_compress="int8",
        grad_compress_error_feedback=True,
        checkpoint_dir=ckpt_dir,
        log_every_epochs=99,
    )
    base.update(overrides)
    return TrainConfig(**base)


class _KillAfter:
    def __init__(self, inner, n_batches):
        self._inner, self._n = inner, n_batches

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __iter__(self):
        for i, batch in enumerate(self._inner):
            if i >= self._n:
                raise RuntimeError("simulated hard kill")
            yield batch

    def __len__(self):
        return len(self._inner)


@pytest.mark.slow
def test_cross_layout_elastic_resume_is_bit_consistent(tmp_path):
    """Kill at step N on an 8-device mesh, restart on 4 devices: the
    zero1 opt shards AND the grad-compress error-feedback residual must
    re-scatter bit-consistently through the de-sharded checkpoint
    layout, and training must continue finite ('rejoins the seed band'
    end to end is checked by no test)."""
    import jax
    import jax.tree_util as jtu

    from tpu_ddp.train.trainer import Trainer

    ckpt = str(tmp_path / "ckpt")
    t0 = Trainer(_elastic_config(ckpt))
    t0.train_loader = _KillAfter(t0.train_loader, 2)
    with pytest.raises(RuntimeError, match="simulated hard kill"):
        t0.run(close=False)
    saved = jax.device_get(t0._ckpt_state())
    t0.checkpointer.save(int(t0.state.step), t0._ckpt_state(), wait=True)
    t0.checkpointer.close()
    res_l1 = sum(float(np.abs(x).sum())
                 for x in jax.tree.leaves(saved.grad_residual))
    assert res_l1 > 0, "int8 EF steps must leave a nonzero residual"

    t1 = Trainer(_elastic_config(
        ckpt, n_devices=4, per_shard_batch=16, resume=True))
    assert t1.resumed_step == 2
    restored = jax.device_get(t1._ckpt_state())
    for (path, a), (_, b) in zip(
        jtu.tree_flatten_with_path(saved)[0],
        jtu.tree_flatten_with_path(restored)[0],
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"leaf {jtu.keystr(path)} drifted across the "
                    "8->4 re-mesh")
    # the recipe identity survives the re-mesh (the band join key)
    assert (t0.run_meta["quality_digest"]
            == t1.run_meta["quality_digest"])
    t1.run()
    assert all(bool(np.isfinite(x).all())
               for x in jax.tree.leaves(jax.device_get(t1.state.params)))


@pytest.mark.slow
def test_second_sigterm_skips_final_checkpoint(tmp_path):
    """First SIGTERM: drain + final checkpoint. Second SIGTERM during
    the drain: exit WITHOUT the final save — the last cadence save
    stays the (verified) resume point instead of a torn newest step."""
    from tpu_ddp.train.trainer import Trainer, TrainConfig

    def config(ckpt):
        return TrainConfig(
            synthetic_data=True, synthetic_size=320, epochs=3,
            per_shard_batch=8, model="netresdeep", n_chans1=4,
            n_blocks=1, n_devices=4, prefetch_depth=0,
            checkpoint_dir=ckpt, checkpoint_steps=4,
            log_every_epochs=99,
        )

    class SignalAt:
        """Send signal(s) to ourselves at batch K, from the loader."""

        def __init__(self, inner, at, count):
            self._inner, self._at, self._count = inner, at, count

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def __iter__(self):
            for i, batch in enumerate(self._inner):
                if i == self._at:
                    for _ in range(self._count):
                        os.kill(os.getpid(), signal.SIGTERM)
                        time.sleep(0.05)
                yield batch

        def __len__(self):
            return len(self._inner)

    # path 1: single SIGTERM -> drained WITH a final checkpoint
    ckpt1 = str(tmp_path / "one")
    t = Trainer(config(ckpt1))
    t.train_loader = SignalAt(t.train_loader, 6, 1)
    metrics = t.run()
    assert metrics.get("preempted")
    from tpu_ddp.checkpoint import Checkpointer

    final_step = Checkpointer(ckpt1).latest_step()
    assert final_step is not None and final_step > 4  # past the cadence

    # path 2: double SIGTERM -> force-abort, final checkpoint SKIPPED
    ckpt2 = str(tmp_path / "two")
    t2 = Trainer(config(ckpt2))
    t2.train_loader = SignalAt(t2.train_loader, 6, 2)
    metrics = t2.run()
    assert metrics.get("preempted")
    ck = Checkpointer(ckpt2)
    assert ck.latest_step() == 4  # the cadence save, nothing newer
    # ... and what remains verifies (nothing died mid-save)
    assert ck.verified_restore_step() == 4
    ck.close()
