"""Elastic runtime: policy budgets/backoff, re-mesh planning + tuner
fallback, recovery assessment, the supervisor loop (fake child), the
elastic.jsonl decision log, and the goodput join (docs/resilience.md).

Everything here is stdlib-fast: the supervisor under test drives an
injected ``run_child`` that fabricates trace evidence, so the loop's
classify → decide → re-mesh → verify → log circuit is pinned without
compiling a Trainer (a real child under the supervisor is checked by no
test).
"""

from __future__ import annotations

import json
import os

import pytest

from tpu_ddp.elastic import (
    BackoffPolicy,
    RemeshRefusal,
    RestartPolicy,
    fallback_from_tune,
    parse_budgets,
    plan_remesh,
    read_capacity,
    read_decisions,
    resume_assessment,
)
from tpu_ddp.elastic.supervisor import (
    Supervisor,
    child_flag_value,
    classify_exit,
    rewrite_child_args,
    strip_flag,
)

# -- policy ----------------------------------------------------------------


def test_budget_exhaustion_stops_a_crash_loop():
    policy = RestartPolicy({"killed": 2},
                           BackoffPolicy(base_s=0.0))
    assert policy.decide("killed").action == "restart"
    assert policy.decide("killed").action == "restart"
    final = policy.decide("killed")
    assert final.action == "stop"
    assert "budget exhausted" in final.reason


def test_preemption_budget_is_effectively_unbounded():
    policy = RestartPolicy(backoff=BackoffPolicy(base_s=0.0))
    for _ in range(50):
        assert policy.decide("preempted").action == "restart"


def test_health_halt_never_restarts():
    decision = RestartPolicy().decide("health_halt")
    assert decision.action == "stop"
    assert "deliberate" in decision.reason


def test_unknown_class_gets_one_attempt():
    policy = RestartPolicy(backoff=BackoffPolicy(base_s=0.0))
    assert policy.decide("exotic_future_class").action == "restart"
    assert policy.decide("exotic_future_class").action == "stop"


def test_classes_budget_independently():
    policy = RestartPolicy({"killed": 1, "hang": 1},
                           BackoffPolicy(base_s=0.0))
    assert policy.decide("killed").action == "restart"
    assert policy.decide("hang").action == "restart"  # own budget
    assert policy.decide("killed").action == "stop"


def test_backoff_grows_exponentially_with_bounded_jitter():
    backoff = BackoffPolicy(base_s=1.0, cap_s=60.0, jitter_frac=0.25,
                            seed=7)
    delays = [backoff.delay_s("killed", n) for n in (1, 2, 3, 4)]
    for i, base in enumerate((1.0, 2.0, 4.0, 8.0)):
        assert base <= delays[i] <= base * 1.25
    # deterministic: same seed, same jitter
    assert delays == [backoff.delay_s("killed", n) for n in (1, 2, 3, 4)]
    # capped
    assert backoff.delay_s("killed", 30) <= 60.0 * 1.25
    # preemptions skip the exponential ramp
    assert backoff.delay_s("preempted", 5) <= 1.0 * 1.25


def test_parse_budgets():
    budgets = parse_budgets("killed=9,hang=0")
    assert budgets["killed"] == 9 and budgets["hang"] == 0
    assert budgets["preempted"] > 1000  # defaults survive
    with pytest.raises(ValueError, match="unknown failure class"):
        parse_budgets("melted=1")
    with pytest.raises(ValueError, match="class=N"):
        parse_budgets("killed")


# -- re-mesh planning ------------------------------------------------------


def test_shrink_data_only_mesh():
    plan = plan_remesh(n_devices=4, global_batch=64)
    assert plan.n_devices == 4 and plan.mesh is None
    assert any("16 rows/shard" in n for n in plan.notes)


def test_shrink_keeps_strategy_axes():
    plan = plan_remesh(n_devices=4, parallelism="tp",
                       mesh={"data": 4, "model": 2})
    assert plan.mesh == {"data": 2, "model": 2}
    assert plan.mesh_arg() == "data=2,model=2"


def test_refusals_are_named():
    with pytest.raises(RemeshRefusal, match="non-data axes.*model.*: 2"):
        plan_remesh(n_devices=3, parallelism="tp",
                    mesh={"data": 4, "model": 2})
    with pytest.raises(RemeshRefusal,
                       match="global batch 64 does not divide"):
        plan_remesh(n_devices=3, global_batch=64)
    with pytest.raises(RemeshRefusal, match="no survivors"):
        plan_remesh(n_devices=0)
    with pytest.raises(RemeshRefusal, match="unknown mesh axis"):
        plan_remesh(n_devices=4, mesh={"warp": 2})


def _tune_artifact(tmp_path, ranked):
    path = str(tmp_path / "tune.json")
    with open(path, "w") as f:
        json.dump({"tune_schema_version": 1, "ranked": ranked}, f)
    return path


def test_fallback_walks_rank_order_and_fits(tmp_path):
    path = _tune_artifact(tmp_path, [
        {"name": "tp_m2", "parallelism": "tp",
         "mesh": {"data": 4, "model": 2}, "per_shard_batch": 8},
        {"name": "dp_plain", "parallelism": "dp", "mesh": {"data": 8},
         "zero1": True, "grad_compress": "int8", "steps_per_call": 4,
         "per_shard_batch": 8},
    ])
    # 3 survivors: tp's model=2 cannot fit; dp can
    plan = fallback_from_tune(path, n_devices=3)
    assert plan.candidate_name == "dp_plain"
    assert plan.source == "fallback"
    assert any("fallback to tuner candidate 'dp_plain'" in n
               for n in plan.notes)  # the decision-log attribution
    assert plan.extra_flags == {"--zero1": "", "--grad-compress": "int8",
                               "--steps-per-call": "4"}


def test_fallback_refusal_names_every_candidate(tmp_path):
    path = _tune_artifact(tmp_path, [
        {"name": "tp_m2", "parallelism": "tp",
         "mesh": {"data": 2, "model": 2}},
    ])
    with pytest.raises(RemeshRefusal, match="tp_m2"):
        fallback_from_tune(path, n_devices=3)
    with pytest.raises(RemeshRefusal, match="unreadable"):
        fallback_from_tune(str(tmp_path / "missing.json"), n_devices=4)
    with pytest.raises(RemeshRefusal, match="no ranked"):
        fallback_from_tune(_tune_artifact(tmp_path, []), n_devices=4)


# -- argv surgery ----------------------------------------------------------


def test_child_flag_value_and_strip():
    args = ["--n-devices", "8", "--mesh=data=8", "--resume", "--lr", "0.1"]
    assert child_flag_value(args, "--n-devices") == "8"
    assert child_flag_value(args, "--mesh") == "data=8"
    assert child_flag_value(args, "--epochs") is None
    assert strip_flag(list(args), "--n-devices", True) == [
        "--mesh=data=8", "--resume", "--lr", "0.1"]
    assert strip_flag(list(args), "--resume", False) == [
        "--n-devices", "8", "--mesh=data=8", "--lr", "0.1"]


def test_rewrite_child_args_shrink_and_fallback():
    base = ["--epochs", "2", "--n-devices", "8", "--telemetry-dir", "/r"]
    plan = plan_remesh(n_devices=4)
    out = rewrite_child_args(base, plan, resume=True)
    assert out.count("--n-devices") == 1
    assert out[out.index("--n-devices") + 1] == "4"
    assert "--resume" in out
    fallback = plan_remesh(n_devices=4, parallelism="tp",
                           mesh={"model": 2}, source="fallback")
    fallback.extra_flags = {"--zero1": ""}
    out = rewrite_child_args(base + ["--parallelism", "dp"], fallback,
                             resume=True)
    assert out[out.index("--parallelism") + 1] == "tp"
    assert "--zero1" in out and "--mesh" in out


# -- recovery assessment + capacity ---------------------------------------


def _fake_ckpt(root, step, payload=b"z" * 2048):
    from tpu_ddp.checkpoint import manifest

    d = root / str(step)
    (d / "data").mkdir(parents=True)
    (d / "data" / "a.bin").write_bytes(payload)
    manifest.write_manifest(str(root), step)
    return str(root)


def test_resume_assessment_refuses_corrupt_newest(tmp_path):
    ckpt = tmp_path / "ckpt"
    _fake_ckpt(ckpt, 3)
    _fake_ckpt(ckpt, 6)
    target = ckpt / "6" / "data" / "a.bin"
    raw = bytearray(target.read_bytes())
    raw[7] ^= 4
    target.write_bytes(bytes(raw))
    assessment = resume_assessment(str(ckpt))
    assert assessment["resume_step"] == 3
    assert assessment["verified"] is True
    assert [r["step"] for r in assessment["refused"]] == [6]
    assert resume_assessment(None)["resume_step"] is None


def test_read_capacity(tmp_path):
    path = str(tmp_path / "capacity.json")
    assert read_capacity(path, default=8) == 8
    with open(path, "w") as f:
        json.dump({"devices": 4}, f)
    assert read_capacity(path) == 4
    with open(path, "w") as f:
        f.write("torn{")
    assert read_capacity(path, default=2) == 2


# -- trace classification --------------------------------------------------


def _write_trace(run_dir, incarnation, *, run_end, hang=False,
                 preempt=False):
    os.makedirs(run_dir, exist_ok=True)
    name = ("trace-p0.jsonl" if incarnation == 0
            else f"trace-p0.i{incarnation}.jsonl")
    records = [
        {"type": "header", "schema_version": 1, "epoch_unix": 1000.0
         + incarnation * 100, "run_meta": {"incarnation": incarnation}},
        {"type": "span", "name": "compiled_step", "ts_s": 1.0,
         "dur_s": 0.5, "step": 0, "depth": 0},
    ]
    if hang:
        records.append({"type": "instant", "name": "watchdog_hang",
                        "ts_s": 2.0})
    if preempt:
        records.append({"type": "instant", "name": "preempt_drain",
                        "ts_s": 2.5})
    if run_end:
        records.append({"type": "instant", "name": "run_end",
                        "ts_s": 3.0})
    with open(os.path.join(run_dir, name), "w") as f:
        for record in records:
            f.write(json.dumps(record) + "\n")


def test_classify_exit_from_trace_evidence(tmp_path):
    run_dir = str(tmp_path / "run")
    assert classify_exit(run_dir, 0) is None  # no trace: spawn failure
    _write_trace(run_dir, 0, run_end=False)
    assert classify_exit(run_dir, 0) == "killed"
    _write_trace(run_dir, 1, run_end=False, hang=True)
    assert classify_exit(run_dir, 1) == "hang"
    _write_trace(run_dir, 2, run_end=True, preempt=True)
    assert classify_exit(run_dir, 2) == "preempted"
    _write_trace(run_dir, 3, run_end=True)
    assert classify_exit(run_dir, 3) == "clean"
    # the "nothing NEW appeared" guard
    assert classify_exit(run_dir, 4) is None


# -- the supervisor loop (fake child) -------------------------------------


class FakeFleet:
    """Scripted children: each entry fabricates the trace evidence a
    real child would leave, plus an optional capacity-file write."""

    def __init__(self, run_dir, script):
        self.run_dir = run_dir
        self.script = list(script)
        self.argv_log = []
        self.next_incarnation = 0

    def __call__(self, argv):
        self.argv_log.append(list(argv))
        kind, rc, survivors = self.script.pop(0)
        if kind is not None:
            _write_trace(
                self.run_dir, self.next_incarnation,
                run_end=kind in ("clean", "preempted"),
                hang=kind == "hang", preempt=kind == "preempted")
            self.next_incarnation += 1
        if survivors is not None:
            with open(os.path.join(self.run_dir, "capacity.json"),
                      "w") as f:
                json.dump({"devices": survivors}, f)
        return rc


def _supervisor(run_dir, script, **kw):
    fleet = FakeFleet(run_dir, script)
    sup = Supervisor(
        ["--telemetry-dir", run_dir, "--n-devices", "8",
         "--global-batch-size", "64"],
        policy=RestartPolicy(backoff=BackoffPolicy(base_s=0.0)),
        run_child=fleet,
        **kw,
    )
    return sup, fleet


def test_supervisor_kill_remesh_then_clean(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    sup, fleet = _supervisor(run_dir, [
        ("killed", 137, 4),   # dies, scheduler reports 4 survivors
        ("clean", 0, None),
    ])
    assert sup.run() == 0
    # second launch re-meshed to 4 and resumed
    argv = fleet.argv_log[1]
    assert argv[argv.index("--n-devices") + 1] == "4"
    assert "--resume" in argv
    decisions = read_decisions(run_dir)
    events = [d["event"] for d in decisions]
    assert events == ["launch", "restart", "exit"]
    restart = decisions[1]
    assert restart["exit_class"] == "killed"
    assert restart["plan"]["n_devices"] == 4
    assert decisions[2]["exit_class"] == "clean"


def test_supervisor_stops_on_exhausted_budget(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    sup, fleet = _supervisor(
        run_dir,
        [("killed", 137, None)] * 3,
        )
    sup.policy = RestartPolicy({"killed": 1},
                               BackoffPolicy(base_s=0.0))
    assert sup.run() == 1
    decisions = read_decisions(run_dir)
    assert decisions[-1]["event"] == "stop"
    assert "budget exhausted" in decisions[-1]["reason"]
    assert len(fleet.argv_log) == 2  # initial + the one budgeted retry


def test_supervisor_stops_on_health_halt(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)

    def halt_child(argv):
        _write_trace(run_dir, 0, run_end=True)
        # health_halt_drain instant marks the deliberate stop
        path = os.path.join(run_dir, "trace-p0.jsonl")
        with open(path, "a") as f:
            f.write(json.dumps({"type": "instant",
                                "name": "health_halt_drain",
                                "ts_s": 2.9}) + "\n")
        return 0

    sup = Supervisor(
        ["--telemetry-dir", run_dir],
        policy=RestartPolicy(backoff=BackoffPolicy(base_s=0.0)),
        run_child=halt_child,
    )
    assert sup.run() == 1
    assert read_decisions(run_dir)[-1]["reason"].startswith(
        "'health_halt'")


def test_supervisor_remesh_refusal_without_fallback_stops(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    fleet = FakeFleet(run_dir, [("killed", 137, 3)])
    sup = Supervisor(
        ["--telemetry-dir", run_dir, "--n-devices", "8",
         "--parallelism", "tp", "--mesh", "data=4,model=2",
         "--global-batch-size", "64"],
        policy=RestartPolicy(backoff=BackoffPolicy(base_s=0.0)),
        run_child=fleet,
    )
    assert sup.run() == 1
    stop = read_decisions(run_dir)[-1]
    assert stop["event"] == "stop"
    assert "re-mesh refused" in stop["reason"]
    assert "model" in stop["reason"]


def test_supervisor_fallback_plan_rescues_the_refusal(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    tune = str(tmp_path / "tune.json")
    with open(tune, "w") as f:
        json.dump({"ranked": [
            {"name": "dp_z1", "parallelism": "dp", "mesh": {"data": 8},
             "zero1": True},
        ]}, f)
    fleet = FakeFleet(run_dir, [("killed", 137, 3), ("clean", 0, None)])
    sup = Supervisor(
        ["--telemetry-dir", run_dir, "--n-devices", "8",
         "--parallelism", "tp", "--mesh", "data=4,model=2"],
        policy=RestartPolicy(backoff=BackoffPolicy(base_s=0.0)),
        fallback_plan=tune,
        run_child=fleet,
    )
    assert sup.run() == 0
    argv = fleet.argv_log[1]
    assert argv[argv.index("--parallelism") + 1] == "dp"
    assert "--zero1" in argv
    restart = [d for d in read_decisions(run_dir)
               if d["event"] == "restart"][0]
    assert restart["plan"]["candidate_name"] == "dp_z1"
    assert restart["remesh_refusal"]  # the shrink refusal is recorded


def test_supervisor_requires_telemetry_dir():
    with pytest.raises(SystemExit, match="telemetry-dir"):
        Supervisor(["--epochs", "2"])


def test_supervisor_stops_when_every_checkpoint_refused(tmp_path):
    run_dir = str(tmp_path / "run")
    ckpt = tmp_path / "ckpt"
    os.makedirs(run_dir)
    _fake_ckpt(ckpt, 4)
    target = ckpt / "4" / "data" / "a.bin"
    raw = bytearray(target.read_bytes())
    raw[3] ^= 1
    target.write_bytes(bytes(raw))
    fleet = FakeFleet(run_dir, [("killed", 137, None)])
    sup = Supervisor(
        ["--telemetry-dir", run_dir, "--checkpoint-dir", str(ckpt)],
        policy=RestartPolicy(backoff=BackoffPolicy(base_s=0.0)),
        run_child=fleet,
    )
    assert sup.run() == 1
    stop = read_decisions(run_dir)[-1]
    assert "no verifiable checkpoint" in stop["reason"]
    assert [r["step"] for r in stop["recovery"]["refused"]] == [4]


def test_max_incarnations_is_the_absolute_ceiling(tmp_path):
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    sup, fleet = _supervisor(
        run_dir, [("preempted", 0, None)] * 4, max_incarnations=3)
    assert sup.run() == 1
    assert read_decisions(run_dir)[-1]["reason"].startswith(
        "--max-incarnations")


# -- the goodput join ------------------------------------------------------


def test_goodput_joins_the_decision_log(tmp_path):
    from tpu_ddp.elastic.recovery import append_decision
    from tpu_ddp.ledger import build_ledger, stitch_run
    from tpu_ddp.ledger.report import ledger_json, render_ledger

    run_dir = str(tmp_path / "run")
    _write_trace(run_dir, 0, run_end=False)
    _write_trace(run_dir, 1, run_end=True)
    append_decision(run_dir, {"event": "launch", "incarnation": 0,
                              "action": "start",
                              "plan": {"n_devices": 8}})
    append_decision(run_dir, {
        "event": "restart", "incarnation": 1, "exit_class": "killed",
        "action": "restart", "attempt": 1, "backoff_s": 0.5,
        "plan": {"n_devices": 4, "mesh": {"data": 4}},
        "recovery": {"resume_step": 3,
                     "refused": [{"step": 6, "problems": ["x"]}]},
    })
    append_decision(run_dir, {"event": "exit", "incarnation": 1,
                              "exit_class": "clean", "action": "done"})
    ledger = build_ledger(stitch_run(run_dir))
    artifact = ledger_json(ledger)
    joined = artifact["ledger"]["elastic"]["decisions"]
    assert len(joined) == 3
    text = render_ledger(ledger)
    assert "elastic decisions" in text
    assert "re-mesh -> 4 device(s) mesh data=4" in text
    assert "checkpoint step 6 refused by manifest" in text
    assert "restart_gap" in json.dumps(artifact)  # category still there


def test_unsupervised_run_has_no_elastic_section(tmp_path):
    from tpu_ddp.ledger import build_ledger, stitch_run
    from tpu_ddp.ledger.report import ledger_json, render_ledger

    run_dir = str(tmp_path / "run")
    _write_trace(run_dir, 0, run_end=True)
    ledger = build_ledger(stitch_run(run_dir))
    assert "elastic" not in ledger_json(ledger)["ledger"]
    assert "elastic decisions" not in render_ledger(ledger)


def test_torn_and_future_decision_lines_are_skipped(tmp_path):
    from tpu_ddp.elastic.recovery import append_decision

    run_dir = str(tmp_path / "run")
    append_decision(run_dir, {"event": "launch", "incarnation": 0})
    with open(os.path.join(run_dir, "elastic.jsonl"), "a") as f:
        f.write('{"torn": \n')
        f.write(json.dumps({"elastic_schema_version": 99,
                            "event": "from_the_future"}) + "\n")
    decisions = read_decisions(run_dir)
    assert len(decisions) == 1 and decisions[0]["event"] == "launch"


# -- quality digest mesh-invariance (the band join key) -------------------


def test_quality_digest_is_mesh_invariant_with_data_size():
    import dataclasses

    from tpu_ddp.telemetry.provenance import quality_digest
    from tpu_ddp.train.trainer import TrainConfig

    eight = dataclasses.asdict(TrainConfig(
        synthetic_data=True, n_devices=8, per_shard_batch=8))
    four = dataclasses.asdict(TrainConfig(
        synthetic_data=True, n_devices=4, per_shard_batch=16))
    # same global batch (64): one recipe, one band series
    assert (quality_digest(eight, data_size=8)
            == quality_digest(four, data_size=4))
    # different global batch: different recipe
    half = dataclasses.asdict(TrainConfig(
        synthetic_data=True, n_devices=4, per_shard_batch=8))
    assert (quality_digest(eight, data_size=8)
            != quality_digest(half, data_size=4))
    # chaos/watchdog wiring never changes the recipe identity
    chaotic = dataclasses.asdict(TrainConfig(
        synthetic_data=True, n_devices=8, per_shard_batch=8,
        chaos_spec="/tmp/spec.json", watchdog_abort=True,
        watchdog_deadline_seconds=60.0, telemetry_dir="/tmp/r"))
    assert (quality_digest(eight, data_size=8)
            == quality_digest(chaotic, data_size=8))
    # without data_size the layout keys conservatively stay in
    assert quality_digest(eight) != quality_digest(four)
