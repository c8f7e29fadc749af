"""Convergence observatory: curve extraction across incarnations, the
seed-band CRV rules, the A/B diff oracle, the TRN001 plateau alert, and
the registry/compare-gate integrations (docs/curves.md).

The expensive fixtures are REAL runs on the virtual CPU mesh, shared
module-wide:

- ``recipe`` — three seeded baselines of one recipe + a clean fourth
  seed + an injected lr×30 divergence (momentum 0.9 makes the lr×30
  run leave the envelope while staying finite).
- ``incident_dir`` — a kill→``--resume`` run (the test_ledger pattern):
  extraction must stitch both lives and dedup the replayed steps.

Band math and the CRV001/CRV003/CRV004 injections run on synthetic
curve records where the exact trip condition is constructed, not
hoped for.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import pytest

from tpu_ddp.curves import (
    BandConfig,
    band_from_registry,
    build_band,
    curve_artifact,
    diff_curves,
    extract_curve,
    judge_curve,
    load_curve,
)
from tpu_ddp.curves.extract import CURVES_SCHEMA_VERSION
from tpu_ddp.telemetry import reset_default_registry
from tpu_ddp.telemetry.provenance import quality_digest
from tpu_ddp.train.trainer import TrainConfig, Trainer

KILL_AT_STEP = 7
CHECKPOINT_STEPS = 4


@pytest.fixture(autouse=True)
def _isolate_registry():
    """The counters registry is process-wide by design; the Trainer
    runs here must not leak train/steps etc. into later tests' exact-
    count snapshots."""
    reset_default_registry()
    yield
    reset_default_registry()


def _config(run_dir, **overrides):
    base = dict(
        synthetic_data=True,
        synthetic_size=320,
        epochs=2,
        per_shard_batch=8,
        model="netresdeep",
        n_chans1=8,
        n_blocks=2,
        n_devices=4,
        prefetch_depth=0,
        momentum=0.9,
        lr=1e-2,
        log_every_epochs=99,
        eval_each_epoch=True,
        health="on",
        telemetry_dir=run_dir,
        telemetry_sinks="jsonl",
    )
    base.update(overrides)
    return TrainConfig(**base)


def _run(run_dir, **overrides):
    trainer = Trainer(_config(run_dir, **overrides).validate())
    metrics = trainer.run(close=False)
    trainer.record_final_eval(accuracy=metrics.get("test_accuracy"))
    trainer.close()
    return run_dir


@pytest.fixture(scope="module")
def recipe(tmp_path_factory):
    """{name: run_dir} for 3 baseline seeds, a clean 4th seed, and the
    injected lr×30 divergence."""
    root = tmp_path_factory.mktemp("curves")
    reset_default_registry()
    dirs = {}
    for seed in (0, 1, 2, 3):
        dirs[f"s{seed}"] = _run(str(root / f"s{seed}"), seed=seed)
    dirs["lr30"] = _run(str(root / "lr30"), seed=7, lr=0.3)
    reset_default_registry()
    return dirs


@pytest.fixture(scope="module")
def curves(recipe):
    return {name: extract_curve(d) for name, d in recipe.items()}


@pytest.fixture(scope="module")
def band(curves):
    return build_band([curves["s0"], curves["s1"], curves["s2"]])


class _KillAfter:
    """Raise after N batches: a simulated SIGKILL (no run_end lands)."""

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


@pytest.fixture(scope="module")
def incident_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("curves_incident")
    run_dir = str(root / "incident")
    reset_default_registry()
    over = dict(epochs=1, eval_each_epoch=False,
                checkpoint_dir=os.path.join(run_dir, "ckpt"),
                checkpoint_steps=CHECKPOINT_STEPS)
    t0 = Trainer(_config(run_dir, **over).validate())
    t0.train_loader = _KillAfter(t0.train_loader, KILL_AT_STEP)
    with pytest.raises(RuntimeError, match="simulated hard kill"):
        t0.run(close=False)  # the dead life writes no run_end
    t1 = Trainer(_config(run_dir, resume=True, **over).validate())
    assert t1.incarnation == 1
    t1.run(close=False)
    t1.close()
    reset_default_registry()
    return run_dir


def _synthetic_curve(loss, *, steps=None, quality="qd0", run_id="r0",
                     acc=None, seed=0, **over):
    curve = {
        "curves_schema_version": CURVES_SCHEMA_VERSION,
        "run_dir": f"/synthetic/{run_id}",
        "run_id": run_id,
        "quality_digest": quality,
        "seed": seed,
        "strategy": "dp",
        "device_kind": "cpu",
        "stride": 1,
        "incarnations": 1,
        "total_steps": len(loss),
        "steps": steps if steps is not None else list(range(len(loss))),
        "loss": list(loss),
        "grad_norm": [1.0] * len(loss),
        "nonfinite_steps": 0,
        "eval_points": [],
        "final_train_loss": next(
            (v for v in reversed(loss)
             if isinstance(v, (int, float)) and math.isfinite(v)), None),
        "final_eval_loss": None,
        "final_eval_accuracy": acc,
        "target_loss": None,
        "time_to_target_steps": None,
        "notes": [],
    }
    curve.update(over)
    return curve


def _baseline_trio(**kw):
    """Three agreeing baselines descending 2.0 -> ~1.0 over 20 steps."""
    out = []
    for i, (jitter, acc) in enumerate(((0.0, 0.80), (0.02, 0.82),
                                       (-0.02, 0.78))):
        loss = [2.0 - 0.05 * s + jitter for s in range(20)]
        out.append(_synthetic_curve(loss, run_id=f"base{i}", acc=acc,
                                    seed=i, **kw))
    return out


# -- quality digest --------------------------------------------------------

def test_quality_digest_excludes_seed_and_run_local_paths():
    a = dataclasses.asdict(TrainConfig(seed=0, telemetry_dir="/a",
                                       checkpoint_dir="/ck1"))
    b = dataclasses.asdict(TrainConfig(seed=9, telemetry_dir="/b",
                                       checkpoint_dir=None, resume=True))
    assert quality_digest(a) == quality_digest(b)
    # run_id (the full-config digest) still tells them apart
    from tpu_ddp.telemetry.provenance import config_digest

    assert config_digest(a) != config_digest(b)


def test_quality_digest_sensitive_to_learning_knobs():
    base = dataclasses.asdict(TrainConfig())
    for knob, value in (("lr", 0.1), ("per_shard_batch", 64),
                        ("grad_compress", "int8"), ("zero1", True),
                        ("model", "vit_t8"), ("weight_decay", 0.1)):
        other = dataclasses.asdict(TrainConfig(**{knob: value}))
        assert quality_digest(base) != quality_digest(other), knob


def test_run_meta_quality_digest_stamped(curves):
    qs = {curves[f"s{i}"]["quality_digest"] for i in range(4)}
    assert len(qs) == 1 and None not in qs
    assert curves["lr30"]["quality_digest"] not in qs  # lr is recipe
    run_ids = {curves[f"s{i}"]["run_id"] for i in range(4)}
    assert len(run_ids) == 4  # seed folds into run_id, not quality


# -- eval instants + trace summarize ---------------------------------------

def test_eval_instants_survive_into_summaries(recipe):
    from tpu_ddp.telemetry.summarize import summarize, summarize_json

    text = summarize(recipe["s0"])
    assert "eval history" in text and "final" in text
    js = summarize_json(recipe["s0"])
    points = js["eval_points"]
    assert any(p["final"] for p in points)
    epochs = [p["epoch"] for p in points if not p["final"]]
    assert epochs == [1, 2]
    for p in points:
        if not p["final"]:
            assert isinstance(p["test_loss"], float)
            assert isinstance(p["test_accuracy"], float)
    assert js["provenance"].get("quality_digest")


# -- extraction ------------------------------------------------------------

def test_extract_basic_shape(curves):
    c = curves["s0"]
    assert c["total_steps"] == 20 and len(c["steps"]) == 20
    assert all(math.isfinite(v) for v in c["loss"])
    assert c["strategy"] == "dp" and c["seed"] == 0
    assert c["incarnations"] == 1 and c["nonfinite_steps"] == 0
    assert isinstance(c["final_eval_accuracy"], float)
    assert isinstance(c["final_eval_loss"], float)
    assert c["final_train_loss"] == c["loss"][-1]


def test_extract_stride_keeps_last_step(recipe):
    c = extract_curve(recipe["s0"], stride=7)
    assert c["steps"] == [0, 7, 14, 19]
    full = extract_curve(recipe["s0"])
    by_step = dict(zip(full["steps"], full["loss"]))
    assert c["loss"] == [by_step[s] for s in c["steps"]]


def test_extract_stitches_kill_resume_and_dedups_replay(incident_dir):
    c = extract_curve(incident_dir)
    assert c["incarnations"] == 2
    # 10 optimizer steps total; the replayed window (checkpoint..kill)
    # appears ONCE, keyed by step, with the surviving life's values
    assert c["steps"] == sorted(set(c["steps"])) == list(range(10))
    assert all(math.isfinite(v) for v in c["loss"])
    assert c["run_id"] and c["quality_digest"]


def test_extract_refuses_runs_without_health(tmp_path):
    (tmp_path / "trace-p0.jsonl").write_text("{}\n")
    with pytest.raises(FileNotFoundError, match="--health on"):
        extract_curve(str(tmp_path))
    with pytest.raises(ValueError, match="stride"):
        extract_curve(str(tmp_path), stride=0)


# -- band build ------------------------------------------------------------

def test_band_from_real_seeds(band, curves):
    assert band.n_runs == 3
    assert band.quality_digest == curves["s0"]["quality_digest"]
    assert band.steps == list(range(20))
    for lo, med, up in zip(band.loss_lower, band.loss_median,
                           band.loss_upper):
        assert lo < med < up
    assert band.final is not None
    assert band.final["metric"] == "final_eval_accuracy"
    assert band.target_loss is not None


def test_band_refusals():
    trio = _baseline_trio()
    with pytest.raises(ValueError, match="needs >= 3"):
        build_band(trio[:2])
    mixed = trio[:2] + [_synthetic_curve([2.0] * 20, quality="other")]
    with pytest.raises(ValueError, match="multiple quality digests"):
        build_band(mixed)
    disjoint = trio[:2] + [_synthetic_curve(
        [2.0] * 20, steps=list(range(100, 120)))]
    with pytest.raises(ValueError, match="no sampled steps"):
        build_band(disjoint)
    with pytest.raises(ValueError, match="min_runs"):
        BandConfig(min_runs=1).validate()


# -- judging: real injections ----------------------------------------------

def test_clean_seed_stays_quiet(band, curves):
    assert judge_curve(dict(curves["s3"]), band) == []


def test_lr30_trips_the_envelope(band, curves):
    candidate = dict(curves["lr30"])
    findings = judge_curve(candidate, band)
    rules = {f.rule for f in findings}
    assert "CRV002" in rules           # loss left the envelope
    assert "CRV004" not in rules       # divergent but finite
    assert candidate["rule_counts"]["CRV002"] == 1
    assert candidate["target_loss"] == band.target_loss
    crv2 = next(f for f in findings if f.rule == "CRV002")
    assert crv2.severity == "critical" and crv2.step is not None


# -- judging: synthetic per-rule injections --------------------------------

def test_crv001_final_metric_below_band():
    band = build_band(_baseline_trio())
    bad = _synthetic_curve([2.0 - 0.05 * s for s in range(20)],
                           run_id="cand", acc=0.10)
    findings = judge_curve(bad, band)
    assert [f.rule for f in findings] == ["CRV001"]
    assert bad["rule_counts"]["CRV001"] == 1


def test_crv002_needs_w_consecutive_points():
    band = build_band(_baseline_trio())
    base = [2.0 - 0.05 * s for s in range(20)]
    spike3 = list(base)
    spike3[10:13] = [4.0, 4.0, 4.0]
    c3 = _synthetic_curve(spike3, run_id="c3", acc=0.80)
    assert {f.rule for f in judge_curve(c3, band)} == {"CRV002"}
    spike2 = list(base)
    spike2[10:12] = [4.0, 4.0]  # W-1: stays quiet
    c2 = _synthetic_curve(spike2, run_id="c2", acc=0.80)
    assert judge_curve(c2, band) == []


def test_crv003_slower_to_target():
    band = build_band(_baseline_trio())
    # tracks the band on its steps (so CRV002 stays quiet), then stalls
    # just ABOVE the target loss and only reaches it at step 30 — past
    # the band's time-to-target limit
    slow = ([2.0 - 0.05 * s for s in range(19)] + [1.06] * 11 + [1.0])
    c = _synthetic_curve(slow, run_id="slow", acc=0.80)
    findings = judge_curve(c, band)
    assert [f.rule for f in findings] == ["CRV003"]
    assert findings[0].severity == "warning"
    assert c["time_to_target_steps"] == findings[0].step == 30


def test_crv001_missing_metric_fails_closed():
    # baselines all evaluated; a candidate with NO eval (crashed before
    # its first one, or a lost eval history) must not pass the final-
    # metric gate by omission
    band = build_band(_baseline_trio())
    c = _synthetic_curve([2.0 - 0.05 * s for s in range(20)],
                         run_id="noeval")  # acc defaults to None
    findings = judge_curve(c, band)
    assert [f.rule for f in findings] == ["CRV001"]
    assert "missing" in findings[0].message


def test_band_rejects_nonfinite_accuracy_baselines():
    # one NaN baseline accuracy would poison the band median and disarm
    # CRV001 forever — the band must fall back to the train-loss metric
    trio = _baseline_trio()
    trio[1]["final_eval_accuracy"] = float("nan")
    band = build_band(trio)
    assert band.final is not None
    assert band.final["metric"] == "final_train_loss"
    assert math.isfinite(band.final["median"])


def test_crv004_nonfinite():
    band = build_band(_baseline_trio())
    loss = [2.0 - 0.05 * s for s in range(20)]
    loss[7] = float("nan")
    c = _synthetic_curve(loss, run_id="nan", acc=0.80,
                         nonfinite_steps=1)
    rules = {f.rule for f in judge_curve(c, band)}
    assert "CRV004" in rules


# -- diff ------------------------------------------------------------------

def test_diff_verdict_both_ways(curves):
    same = diff_curves(curves["s0"], dict(curves["s0"]))
    assert same["verdict"] == "pass" and same["max_loss_drift"] == 0.0
    drifted = diff_curves(curves["s0"], curves["lr30"], tolerance=0.05)
    assert drifted["verdict"] == "fail"
    reverse = diff_curves(curves["lr30"], curves["s0"], tolerance=0.05)
    assert reverse["verdict"] == "fail"
    assert drifted["max_loss_drift"] == pytest.approx(
        reverse["max_loss_drift"])
    # smoothing: the gated figure never exceeds the raw figure
    assert drifted["max_loss_drift"] <= drifted["raw_max_loss_drift"]


def test_diff_gates_nonfinite_asymmetry():
    a = _synthetic_curve([2.0] * 10)
    b = _synthetic_curve([2.0] * 10, run_id="r1", nonfinite_steps=1)
    result = diff_curves(a, b)
    assert result["verdict"] == "fail"
    assert any("non-finite" in r for r in result["regressions"])


def test_diff_refuses_disjoint_curves():
    a = _synthetic_curve([2.0] * 10)
    b = _synthetic_curve([2.0] * 10, steps=list(range(50, 60)))
    with pytest.raises(ValueError, match="share only"):
        diff_curves(a, b)


# -- TRN001 loss plateau ---------------------------------------------------

def _snap(losses):
    from tpu_ddp.monitor.aggregate import FleetSnapshot

    return FleetSnapshot(wall_time=1.0, run_dir="/x",
                         loss_series=list(losses))


def test_trn001_fires_resolves_and_disables():
    from tpu_ddp.monitor.aggregate import MonitorConfig
    from tpu_ddp.monitor.alerts import AlertEngine

    cfg = MonitorConfig(loss_plateau_window=8).validate()
    engine = AlertEngine(cfg)
    edges = engine.evaluate(_snap([2.0] * 12))
    assert [(e.rule, e.state) for e in edges] == [("TRN001", "firing")]
    assert engine.evaluate(_snap([2.0] * 12)) == []  # edge-triggered
    improving = [2.0] * 4 + [2.0 - 0.1 * i for i in range(8)]
    edges = engine.evaluate(_snap(improving))
    assert [(e.rule, e.state) for e in edges] == [("TRN001", "resolved")]

    disabled = AlertEngine(MonitorConfig(loss_plateau_window=0))
    assert disabled.evaluate(_snap([2.0] * 40)) == []

    with pytest.raises(ValueError, match="loss_plateau_window"):
        MonitorConfig(loss_plateau_window=4).validate()


def test_trn001_in_rule_registry():
    from tpu_ddp.monitor.alerts import ALERT_RULES

    rule = ALERT_RULES["TRN001"]
    assert rule["severity"] == "warning" and rule["kind"] == "trend"
    assert "curves" in rule["fix"]


# -- artifacts, registry, compare gates ------------------------------------

def test_artifact_roundtrip_and_future_schema(tmp_path, curves):
    art = curve_artifact(dict(curves["s0"]))
    assert art["provenance"]["config_digest"] == \
        curves["s0"]["quality_digest"]
    assert art["provenance"]["run_id"] == curves["s0"]["run_id"]
    path = tmp_path / "c.json"
    path.write_text(json.dumps(art))
    assert load_curve(str(path))["run_id"] == curves["s0"]["run_id"]
    art["curves_schema_version"] = CURVES_SCHEMA_VERSION + 1
    path.write_text(json.dumps(art))
    with pytest.raises(ValueError, match="newer than"):
        load_curve(str(path))
    (tmp_path / "bad.json").write_text("{\"not\": \"a curve\"}")
    with pytest.raises(ValueError, match="curve"):
        load_curve(str(tmp_path / "bad.json"))


def test_registry_classifies_curves_kind(tmp_path, curves):
    from tpu_ddp.registry.store import read_entries, record_artifact

    path = tmp_path / "c.json"
    path.write_text(json.dumps(curve_artifact(dict(curves["s1"]))))
    entry = record_artifact(str(tmp_path / "reg"), str(path))
    assert entry.artifact_kind == "curves"
    assert entry.config_digest == curves["s1"]["quality_digest"]
    assert entry.provenance["run_id"] == curves["s1"]["run_id"]
    metrics = entry.metrics
    assert "curves/quality/final_eval_accuracy" in metrics
    [back] = read_entries(str(tmp_path / "reg"))
    assert back.programs["curves"]["run_id"] == curves["s1"]["run_id"]


def _record_trio(reg_dir, curves_list):
    from tpu_ddp.registry.store import record_artifact

    for i, c in enumerate(curves_list):
        path = os.path.join(reg_dir, f"src{i}.json")
        with open(path, "w") as f:
            json.dump(curve_artifact(dict(c)), f)
        record_artifact(reg_dir, path)


def test_band_from_registry_and_refusals(tmp_path, curves):
    reg = str(tmp_path / "reg")
    os.makedirs(reg)
    _record_trio(reg, [curves["s0"], curves["s1"], curves["s2"]])
    band, refusal = band_from_registry(
        reg, quality_digest=curves["s0"]["quality_digest"],
        device_kind="cpu", allow_dirty=True)
    assert refusal is None and band.n_runs == 3
    assert judge_curve(dict(curves["s3"]), band) == []
    # the candidate's own run never baselines itself
    band2, _ = band_from_registry(
        reg, quality_digest=curves["s0"]["quality_digest"],
        device_kind="cpu", allow_dirty=True,
        exclude_run_id=curves["s0"]["run_id"],
        config=BandConfig(min_runs=2))
    assert band2.n_runs == 2
    # wrong digest / empty registry refuse by name
    band3, refusal = band_from_registry(
        reg, quality_digest="feedfeed00", device_kind="cpu",
        allow_dirty=True)
    assert band3 is None and "feedfeed00" in refusal
    band4, refusal = band_from_registry(
        str(tmp_path / "empty"), quality_digest="x", device_kind="cpu")
    assert band4 is None and "empty" in refusal
    band5, refusal = band_from_registry(
        reg, quality_digest=None, device_kind="cpu")
    assert band5 is None and "quality_digest" in refusal


def test_compare_gates_curves_both_directions(band, curves):
    from tpu_ddp.analysis.regress import compare, normalize_artifact

    clean = dict(curves["s3"])
    bad = dict(curves["lr30"])
    judge_curve(clean, band)
    judge_curve(bad, band)
    old = normalize_artifact(curve_artifact(clean))
    new = normalize_artifact(curve_artifact(bad))
    result = compare(old, new)
    text = "\n".join(result["regressions"])
    assert "lint/CRV002" in text            # CRV counts gate exactly
    assert "final_eval_accuracy" in text    # quality key drops
    # reverse direction: the CRV counts read as improvements
    back = compare(new, old)
    assert not any("CRV" in r for r in back["regressions"])
    assert any("lint/CRV002" in i for i in back["improvements"])
    # self-compare is silent
    assert compare(old, old)["regressions"] == []


def test_compare_unit_size_keys_gate_without_byte_floor():
    from tpu_ddp.analysis.regress import compare

    old = {"curves": {"time_to_target_steps": 10,
                      "final_eval_loss": 1.0}}
    new = {"curves": {"time_to_target_steps": 20,
                      "final_eval_loss": 1.3}}
    result = compare(old, new)
    text = "\n".join(result["regressions"])
    assert "time_to_target_steps" in text and "final_eval_loss" in text
    assert compare(new, old)["regressions"] == []


# -- CLI -------------------------------------------------------------------

def test_cli_exit_codes(tmp_path, recipe, curves, capsys):
    from tpu_ddp.curves.report import main as curves_main

    assert curves_main([recipe["s0"]]) == 0
    out = capsys.readouterr().out
    assert "loss" in out and "eval history" in out

    assert curves_main([str(tmp_path / "nope")]) == 2
    assert curves_main([recipe["s0"], "--against",
                        str(tmp_path / "empty_reg")]) == 2

    reg = str(tmp_path / "reg")
    os.makedirs(reg)
    _record_trio(reg, [curves["s0"], curves["s1"], curves["s2"]])
    assert curves_main([recipe["s3"], "--against", reg,
                        "--allow-dirty"]) == 0
    capsys.readouterr()
    rc = curves_main([recipe["lr30"], "--against", reg, "--allow-dirty",
                      "--band-quality", curves["s0"]["quality_digest"],
                      "--json"])
    assert rc == 1
    art = json.loads(capsys.readouterr().out)
    assert {f["rule"] for f in art["findings"]} >= {"CRV002"}
    assert art["band"]["n_runs"] == 3

    assert curves_main(["diff", recipe["s0"], recipe["s0"]]) == 0
    assert curves_main(["diff", recipe["s0"], recipe["lr30"]]) == 1
    assert curves_main(["diff", recipe["s0"],
                        str(tmp_path / "nope")]) == 2
    # a future-schema artifact refuses loudly, never misjudges
    art_path = tmp_path / "future.json"
    future = curve_artifact(dict(curves["s0"]))
    future["curves_schema_version"] = CURVES_SCHEMA_VERSION + 1
    art_path.write_text(json.dumps(future))
    assert curves_main(["diff", recipe["s0"], str(art_path)]) == 2


def test_umbrella_cli_routes_curves(recipe, capsys):
    from tpu_ddp.cli.main import main as cli_main

    assert cli_main(["curves", recipe["s0"]]) == 0
    assert "curves:" in capsys.readouterr().out
