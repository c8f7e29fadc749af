"""Numerics flight recorder (tpu_ddp/health/): in-graph stats, sentinels,
skip-step recovery, anomaly dumps, and the `tpu-ddp health` CLI.

The acceptance contract (ISSUE 2): health off leaves trajectories
bit-identical to a build without the feature (DP, grad-accum, SP parity
pinned here); health on computes the shared schema in-graph in every
step-builder family with no extra dispatch; an injected NaN batch produces
a one-shot anomaly dump and, under skip_step, training recovers with
finite params and an in-sync optimizer.
"""

import json
import math
import os

import jax
import numpy as np
import pytest

from tpu_ddp.health import HealthConfig
from tpu_ddp.health.monitor import HealthMonitor, SpikeDetector
from tpu_ddp.health.summarize import summarize_health
from tpu_ddp.models import NetResDeep
from tpu_ddp.parallel import MeshSpec, create_mesh
from tpu_ddp.train import create_train_state, make_optimizer, make_train_step
from tpu_ddp.telemetry import reset_default_registry
from tpu_ddp.train.trainer import TrainConfig, Trainer

HC = HealthConfig(per_layer=True, skip_nonfinite=True)


@pytest.fixture(autouse=True)
def _isolate_registry():
    """The counters registry is process-wide by design; the Trainer runs
    here must not leak train/steps etc. into later tests' snapshots (the
    telemetry suite asserts exact counts)."""
    reset_default_registry()
    yield
    reset_default_registry()


def _model():
    return NetResDeep(n_chans1=4, n_blocks=2, num_classes=10)


def _batch(seed=0, n=32, nan_rows=()):
    r = np.random.RandomState(seed)
    img = r.randn(n, 32, 32, 3).astype(np.float32)
    for row in nan_rows:
        img[row] = np.nan
    return {
        "image": img,
        "label": r.randint(0, 10, n),
        "mask": np.ones(n, bool),
    }


def _trees_equal(a, b) -> bool:
    eq = jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
        a, b,
    )
    return all(jax.tree.leaves(eq))


# -- in-graph stats -------------------------------------------------------


def test_health_stats_values_and_sentinels():
    from tpu_ddp.health import health_stats

    grads = {"a": np.array([3.0, 4.0]), "b": np.array([[0.0]])}
    params = {"a": np.array([1.0, 0.0]), "b": np.array([[2.0]])}
    updates = {"a": np.array([-0.3, -0.4]), "b": np.array([[0.0]])}
    s = health_stats(loss=np.float32(1.5), grads=grads, params=params,
                     updates=updates, per_layer=True)
    assert float(s["grad_norm"]) == pytest.approx(5.0)
    assert float(s["param_norm"]) == pytest.approx(math.sqrt(5.0))
    assert float(s["update_norm"]) == pytest.approx(0.5)
    assert float(s["update_ratio"]) == pytest.approx(0.5 / math.sqrt(5.0))
    assert bool(s["all_finite"])
    assert float(s["per_layer"]["grad_norm"]["a"]) == pytest.approx(5.0)
    # one NaN anywhere flips the matching sentinel (counted, not norm'd)
    bad = {"a": np.array([np.nan, 4.0]), "b": np.array([[0.0]])}
    s = health_stats(loss=np.float32(1.5), grads=bad, params=params,
                     updates=updates)
    assert not bool(s["grads_finite"]) and not bool(s["all_finite"])
    assert bool(s["loss_finite"]) and bool(s["updates_finite"])
    # inf overflow in the norm must NOT read as non-finite values
    big = {"a": np.full(2, 3e38, np.float32), "b": np.array([[0.0]],
                                                            np.float32)}
    s = health_stats(loss=np.float32(1.5), grads=big, params=params,
                     updates=updates)
    assert math.isinf(float(s["grad_norm"]))
    assert bool(s["grads_finite"])


def test_spike_detector_median_mad():
    det = SpikeDetector(window=64, threshold=10.0, warmup=20)
    r = np.random.RandomState(0)
    flagged = [det.observe(1.0 + 0.05 * r.randn()) for _ in range(40)]
    assert not any(flagged)  # steady series never trips
    assert det.observe(50.0)  # 50x the plateau does
    assert not det.observe(float("nan"))  # non-finite: separate class
    assert not det.observe(1.0)  # ...and did not poison the window


# -- config validation (satellite) ---------------------------------------


def test_config_validation_fails_fast():
    with pytest.raises(ValueError, match="jsonl, chrome, summary"):
        TrainConfig(telemetry_sinks="jsonl,bogus").validate()
    with pytest.raises(ValueError, match="warn, skip_step, halt"):
        TrainConfig(health="on", health_policy="explode").validate()
    with pytest.raises(ValueError, match="off, on"):
        TrainConfig(health="loud").validate()
    with pytest.raises(ValueError, match="health_per_layer_stride"):
        TrainConfig(health_per_layer_stride=-1).validate()
    assert TrainConfig().validate() is not None
    # Trainer construction validates too (programmatic use)
    with pytest.raises(ValueError, match="valid sinks"):
        Trainer(TrainConfig(synthetic_data=True,
                            telemetry_sinks="chrme"))


# -- bit-parity: recorder on vs off ---------------------------------------


def test_dp_parity_bitwise(devices):
    mesh = create_mesh(MeshSpec(data=-1))
    model, tx = _model(), make_optimizer(lr=0.01)
    off = make_train_step(model, tx, mesh, donate=False)
    on = make_train_step(model, tx, mesh, donate=False, health=HC)
    s_off = create_train_state(model, tx, jax.random.key(0))
    s_on = create_train_state(model, tx, jax.random.key(0))
    for i in range(3):
        s_off, _ = off(s_off, _batch(i))
        s_on, m = on(s_on, _batch(i))
    assert _trees_equal(s_off.params, s_on.params)
    assert _trees_equal(s_off.opt_state, s_on.opt_state)
    assert _trees_equal(s_off.batch_stats, s_on.batch_stats)
    h = m["health"]
    assert bool(np.asarray(h["all_finite"]))
    assert set(h["per_layer"]) == {"grad_norm", "param_norm"}


def test_grad_accum_parity_bitwise(devices):
    mesh = create_mesh(MeshSpec(data=-1))
    model, tx = _model(), make_optimizer(lr=0.01)
    off = make_train_step(model, tx, mesh, accum_steps=2, donate=False)
    on = make_train_step(model, tx, mesh, accum_steps=2, donate=False,
                         health=HC)
    s_off = create_train_state(model, tx, jax.random.key(1))
    s_on = create_train_state(model, tx, jax.random.key(1))
    for i in range(2):
        s_off, _ = off(s_off, _batch(i))
        s_on, m = on(s_on, _batch(i))
    assert _trees_equal(s_off.params, s_on.params)
    assert _trees_equal(s_off.opt_state, s_on.opt_state)
    assert bool(np.asarray(m["health"]["all_finite"]))


@pytest.mark.slow  # ~25s SP compile; dp/pipeline parity stay fast — make test-all
def test_sp_parity_bitwise(devices):
    from tpu_ddp.models.vit import ViT
    from tpu_ddp.parallel.sequence_parallel import make_sp_train_step

    mesh = create_mesh(MeshSpec(data=2, sequence=4))
    sp_model = ViT(depth=2, hidden_dim=64, num_heads=2, sp_axis="sequence")
    ref_model = ViT(depth=2, hidden_dim=64, num_heads=2)
    tx = make_optimizer(lr=0.05)
    off = make_sp_train_step(sp_model, tx, mesh, donate=False)
    on = make_sp_train_step(sp_model, tx, mesh, donate=False, health=HC)
    s_off = create_train_state(ref_model, tx, jax.random.key(0))
    s_on = create_train_state(ref_model, tx, jax.random.key(0))
    batch = _batch(3, n=16)
    for _ in range(2):
        s_off, _ = off(s_off, batch)
        s_on, m = on(s_on, batch)
    assert _trees_equal(s_off.params, s_on.params)
    assert bool(np.asarray(m["health"]["all_finite"]))


def test_scan_fused_health_carries_step_axis(devices):
    mesh = create_mesh(MeshSpec(data=-1))
    model, tx = _model(), make_optimizer(lr=0.01)
    step = make_train_step(model, tx, mesh, steps_per_call=3,
                           donate=False, health=HC)
    stacked = {
        k: np.stack([_batch(i)[k] for i in range(3)]) for k in _batch(0)
    }
    state = create_train_state(model, tx, jax.random.key(0))
    _, m = step(state, stacked)
    assert m["health"]["grad_norm"].shape == (3,)
    assert m["health"]["all_finite"].shape == (3,)


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_pipeline_parity_and_schema(devices):
    """GPipe: stage-sharded block stats psum over the pipe axis into the
    same global schema; recorder on vs off stays bit-identical."""
    from tpu_ddp.models.vit import ViT
    from tpu_ddp.parallel.partitioning import shard_train_state
    from tpu_ddp.parallel.pipeline import (
        create_pp_train_state,
        make_pp_train_step,
    )

    mesh = create_mesh(MeshSpec(data=-1, pipeline=2))
    vit = ViT(patch_size=4, hidden_dim=16, depth=2, num_heads=2,
              num_classes=10)
    tx = make_optimizer(lr=0.01)
    template = create_pp_train_state(vit, tx, jax.random.key(0))
    off, sh = make_pp_train_step(vit, tx, mesh, template, n_microbatches=2)
    on, _ = make_pp_train_step(vit, tx, mesh, template, n_microbatches=2,
                               health=HC)
    s_off = shard_train_state(
        create_pp_train_state(vit, tx, jax.random.key(0)), sh)
    s_on = shard_train_state(
        create_pp_train_state(vit, tx, jax.random.key(0)), sh)
    batch = _batch(0, n=16)
    s_off, _ = off(s_off, batch)
    s_on, m = on(s_on, batch)
    assert _trees_equal(s_off.params, s_on.params)
    h = jax.device_get(m["health"])
    assert bool(h["all_finite"]) and float(h["grad_norm"]) > 0
    # per-layer names cover the stacked stages and the replicated ends
    names = set(h["per_layer"]["grad_norm"])
    assert any(n.startswith("blocks/") for n in names)
    assert any(n.startswith("patch_embed") for n in names)


def test_fsdp_parity_and_schema(devices):
    """GSPMD family (fsdp here, same builder as tp/fsdp_tp/ep): stats on
    the ZeRO-scattered state match the replicated-math trajectory."""
    from tpu_ddp.parallel.partitioning import shard_train_state
    from tpu_ddp.parallel.tensor_parallel import make_fsdp_train_step

    mesh = create_mesh(MeshSpec(data=-1))
    model, tx = _model(), make_optimizer(lr=0.01)
    template = create_train_state(model, tx, jax.random.key(0))
    off, sh = make_fsdp_train_step(model, tx, mesh, template,
                                   has_batch_stats=True, donate=False)
    on, _ = make_fsdp_train_step(model, tx, mesh, template,
                                 has_batch_stats=True, donate=False,
                                 health=HC)
    s_off = shard_train_state(
        create_train_state(model, tx, jax.random.key(0)), sh)
    s_on = shard_train_state(
        create_train_state(model, tx, jax.random.key(0)), sh)
    s_off, _ = off(s_off, _batch(0))
    s_on, m = on(s_on, _batch(0))
    assert _trees_equal(s_off.params, s_on.params)
    assert bool(np.asarray(m["health"]["all_finite"]))
    assert float(m["health"]["grad_norm"]) > 0


# -- skip_step guard ------------------------------------------------------


def test_skip_step_discards_nan_update_and_recovers(devices):
    mesh = create_mesh(MeshSpec(data=-1))
    model = _model()
    tx = make_optimizer(lr=0.01, momentum=0.9)  # stateful: desync visible
    step = make_train_step(model, tx, mesh, donate=False, health=HC)
    state = create_train_state(model, tx, jax.random.key(0))
    state, _ = step(state, _batch(0))
    before = jax.device_get((state.params, state.batch_stats,
                             state.opt_state))
    state, m = step(state, _batch(1, nan_rows=range(8)))
    h = jax.device_get(m["health"])
    assert not bool(h["all_finite"])
    after = jax.device_get((state.params, state.batch_stats,
                            state.opt_state))
    # poisoned update discarded wholesale: params AND momentum AND BN stats
    assert _trees_equal(before, after)
    assert int(state.step) == 2  # the batch was still consumed
    state, m = step(state, _batch(2))
    assert bool(np.asarray(m["health"]["all_finite"]))
    assert all(
        bool(np.isfinite(leaf).all())
        for leaf in jax.tree.leaves(jax.device_get(state.params))
    )


# -- Trainer end to end ---------------------------------------------------


def _poisoned_data(n_batches=6, per_shard=4, poison_batch=2, world=8):
    from tpu_ddp.data.cifar10 import synthetic_cifar10

    global_batch = per_shard * world
    images, labels = synthetic_cifar10(global_batch * n_batches, 10, seed=0)
    images = np.array(images)
    lo = poison_batch * global_batch
    images[lo:lo + global_batch] = np.nan
    return images, labels


def _trainer_config(tmp_path=None, **overrides):
    cfg = dict(
        synthetic_data=True,
        epochs=1,
        per_shard_batch=4,
        n_chans1=8,
        n_blocks=2,
        shuffle=False,
        prefetch_depth=0,
        log_every_epochs=1,
    )
    cfg.update(overrides)
    return TrainConfig(**cfg)


def test_trainer_nan_anomaly_dump_and_skip_recovery(devices, tmp_path):
    run_dir = str(tmp_path / "run")
    config = _trainer_config(
        telemetry_dir=run_dir,
        telemetry_sinks="jsonl",
        health="on",
        health_policy="skip_step",
        health_per_layer_stride=1,
    )
    trainer = Trainer(config, train_data=_poisoned_data())
    trainer.run()
    # skip_step held: params finite after the poisoned batch
    assert all(
        bool(np.isfinite(leaf).all())
        for leaf in jax.tree.leaves(jax.device_get(trainer.state.params))
    )
    assert trainer._health_monitor.nonfinite_steps == 1
    # per-step JSONL record with the shared schema
    health_path = os.path.join(run_dir, "health-p0.jsonl")
    records = [json.loads(line) for line in open(health_path)]
    steps = [r for r in records if r.get("type") == "health"]
    assert len(steps) == 6
    assert {"grad_norm", "param_norm", "update_norm", "update_ratio",
            "all_finite", "per_layer"} <= set(steps[0])
    bad = [r for r in steps if not r["all_finite"]]
    assert [r["step"] for r in bad] == [2]
    assert bad[0]["anomaly"] == "nonfinite"
    # one-shot anomaly dump: meta + stats/history + the offending batch
    dump_dir = os.path.join(run_dir, "anomalies", "step_00000002")
    assert sorted(os.listdir(dump_dir)) == [
        "batch.npz", "health.json", "meta.json"]
    meta = json.load(open(os.path.join(dump_dir, "meta.json")))
    assert meta["reason"] == "nonfinite" and meta["step"] == 2
    assert meta["config"]["health_policy"] == "skip_step"
    dumped = np.load(os.path.join(dump_dir, "batch.npz"))
    assert np.isnan(dumped["image"]).all()
    health_json = json.load(open(os.path.join(dump_dir, "health.json")))
    assert health_json["stats"]["per_layer"]["grad_norm"]
    assert len(health_json["history"]) >= 1
    # telemetry counters carry the health counts
    trace = [json.loads(line)
             for line in open(os.path.join(run_dir, "trace-p0.jsonl"))]
    counters = [r for r in trace if r.get("type") == "counters"][-1]
    assert counters["attrs"]["counters"]["health/nonfinite_steps"] == 1
    assert counters["attrs"]["counters"]["health/skipped_steps"] == 1
    assert "health/grad_norm" in counters["attrs"]["gauges"]
    # the CLI renders the timeline + the anomaly
    out = summarize_health(run_dir)
    assert "non-finite: 1" in out
    assert "step_00000002" in out
    from tpu_ddp.cli.main import main as cli_main

    assert cli_main(["health", run_dir]) == 0


def test_trainer_halt_policy_drains(devices, tmp_path):
    config = _trainer_config(
        health="on",
        health_policy="halt",
        health_dir=str(tmp_path / "health_only"),  # no telemetry needed
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every_epochs=100,  # only the final save fires
    )
    trainer = Trainer(config, train_data=_poisoned_data(poison_batch=2))
    metrics = trainer.run()
    assert metrics.get("health_halted") is True
    # stopped right after the poisoned step, not at epoch end
    assert int(trainer.state.step) == 3
    # halt applies the poisoned update (no skip guard compiled) — the
    # drain must NOT checkpoint the NaN state as the newest checkpoint
    assert trainer.checkpointer.latest_step() is None
    # health records exist even without a telemetry dir
    assert os.path.exists(
        os.path.join(str(tmp_path / "health_only"), "health-p0.jsonl"))


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_trainer_health_parity_and_warn_policy(devices):
    """Trainer-level parity: recorder on (warn) vs off, identical clean
    data -> bit-identical loss history and final params; warn leaves the
    poisoned update APPLIED (documented contrast with skip_step)."""
    base = dict(seed=3)
    t_off = Trainer(_trainer_config(**base))
    t_off.run()
    t_on = Trainer(_trainer_config(health="on", health_policy="warn",
                                   **base))
    t_on.run()
    assert t_off.history["train_loss"] == t_on.history["train_loss"]
    assert _trees_equal(t_off.state.params, t_on.state.params)
    t_warn = Trainer(
        _trainer_config(health="on", health_policy="warn"),
        train_data=_poisoned_data(),
    )
    t_warn.run()
    finite = all(
        bool(np.isfinite(leaf).all())
        for leaf in jax.tree.leaves(jax.device_get(t_warn.state.params)))
    assert not finite  # warn observes, does not intervene
    assert t_warn._health_monitor.nonfinite_steps >= 1


# -- eval gauges into the trace (satellite) -------------------------------


def test_final_and_per_epoch_eval_gauges_in_trace(devices, tmp_path):
    run_dir = str(tmp_path / "run")
    config = _trainer_config(
        telemetry_dir=run_dir,
        telemetry_sinks="jsonl",
        eval_each_epoch=True,
    )
    trainer = Trainer(config)
    trainer.run(close=False)
    acc, loss = trainer.evaluate()
    trainer.record_final_eval(accuracy=acc, loss=loss)
    trainer.close()
    trace = [json.loads(line)
             for line in open(os.path.join(run_dir, "trace-p0.jsonl"))]
    gauges = [r for r in trace if r.get("type") == "counters"][-1][
        "attrs"]["gauges"]
    assert gauges["eval/test_accuracy"] == pytest.approx(acc)
    assert gauges["eval/final_test_accuracy"] == pytest.approx(acc)
    assert gauges["eval/final_test_loss"] == pytest.approx(loss)


# -- monitor + CLI without a Trainer --------------------------------------


def _fake_stats(loss=1.0, finite=True):
    return {
        "loss": loss,
        "grad_norm": 2.0,
        "param_norm": 4.0,
        "update_norm": 0.02,
        "update_ratio": 0.005,
        "loss_finite": finite,
        "grads_finite": finite,
        "updates_finite": True,
        "all_finite": finite,
        "per_layer": {"grad_norm": {"fc/kernel": 2.0},
                      "param_norm": {"fc/kernel": 4.0}},
    }


def test_monitor_one_shot_dump_and_summarize(tmp_path):
    run_dir = str(tmp_path)
    mon = HealthMonitor(run_dir=run_dir, policy="warn",
                        per_layer_stride=2, run_meta={"model": "toy"})
    for step in range(6):
        assert mon.on_step(step, _fake_stats()) == "ok"
    assert mon.on_step(6, _fake_stats(loss=float("nan"), finite=False),
                       batch_provider=lambda: {"image": np.zeros(2)}
                       ) == "warn"
    # second anomaly: counted, NOT dumped again (one-shot)
    assert mon.on_step(7, _fake_stats(loss=float("nan"), finite=False)
                       ) == "warn"
    mon.close()
    assert mon.dumps_written == 1 and mon.anomaly_count == 2
    dumps = os.listdir(os.path.join(run_dir, "anomalies"))
    assert dumps == ["step_00000006"]
    out = summarize_health(run_dir)
    assert "non-finite: 2" in out
    assert "!" in out  # sparkline marks the poisoned bucket
    # per-layer landed only on the stride steps + the anomaly steps
    records = [json.loads(line)
               for line in open(os.path.join(run_dir, "health-p0.jsonl"))]
    with_layers = [r["step"] for r in records if "per_layer" in r]
    assert with_layers == [0, 2, 4, 6, 7]


def test_health_summarize_multihost_skew_line(tmp_path):
    """Satellite: a multihost health dir merges every health-p<i>.jsonl
    and names the host whose grad-norm p50 diverges from the fleet
    median — the stats are replicated globals, so any real delta means
    a diverged host."""
    import json

    from tpu_ddp.health.summarize import summarize_health

    for host, gn in enumerate((1.0, 1.0, 1.0, 9.0)):
        with open(tmp_path / f"health-p{host}.jsonl", "w") as f:
            f.write(json.dumps({"schema_version": 1, "type": "header",
                                "pid": host, "policy": "warn"}) + "\n")
            for step in range(8):
                f.write(json.dumps({
                    "schema_version": 1, "type": "health", "step": step,
                    "pid": host, "loss": 2.0, "grad_norm": gn,
                    "all_finite": True,
                }) + "\n")
    out = summarize_health(str(tmp_path))
    assert "per-host skew: grad_norm" in out
    assert "host 3" in out

    solo = tmp_path / "solo"
    solo.mkdir()
    with open(solo / "health-p0.jsonl", "w") as f:
        f.write(json.dumps({"schema_version": 1, "type": "health",
                            "step": 0, "pid": 0, "loss": 2.0,
                            "grad_norm": 1.0, "all_finite": True}) + "\n")
    assert "per-host skew" not in summarize_health(str(solo))
