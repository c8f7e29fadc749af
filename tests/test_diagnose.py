"""tpu-ddp diagnose: the cross-observatory root-cause engine.

The chaos-verified contract: every injected fault kind is diagnosed as
EXACTLY its own DIA rule (no cross-attribution), a clean run fires
nothing, every citation resolves to a real artifact on disk, absent
sources refuse by name, and the diagnose artifact round-trips through
the registry and the compare gate (a fresh suspect class regresses).

Also home of the exit-code consistency audit: all six
artifact-consuming subcommands follow 0 / 1-finding / 2-refusal and
exit 2 on future-schema artifacts (docs/diagnose.md).
"""

import glob
import json
import os
import sys

import pytest

from tpu_ddp.cli.main import main as cli_main
from tpu_ddp.diagnose.cli import main as diagnose_main
from tpu_ddp.diagnose.evidence import (
    DIAG_SCHEMA_VERSION,
    SOURCE_NAMES,
    gather_evidence,
)
from tpu_ddp.diagnose.rules import (
    RULES,
    diagnose,
    likely_cause,
    rule_counts,
)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from fleet_fixture import write_fleet  # noqa: E402


# -- fault builders: one synthetic run dir per chaos kind -------------------


def _j(run_dir, name, rec):
    path = os.path.join(str(run_dir), name)
    with open(path, "w") as f:
        json.dump(rec, f)
    return path


def _jsonl(run_dir, name, records):
    path = os.path.join(str(run_dir), name)
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return path


def _clean(run_dir):
    write_fleet(run_dir)


def _data_stall(run_dir):
    write_fleet(run_dir)
    _j(run_dir, "data-health-p0.json", {
        "data_health_schema_version": 1, "process_index": 0,
        "step": 10, "stages": {},
        "in_flight": {"stage": "augment", "since_unix": 1000.0},
    })


def _comm_stall(run_dir):
    write_fleet(run_dir)
    _j(run_dir, "comms-health-p0.json", {
        "comms_health_schema_version": 1, "process_index": 0,
        "in_flight": {"key": "ring-all-reduce/s8/data",
                      "kind": "ring-all-reduce", "dtype": "s8",
                      "axis": "data", "hop": 2, "n_hops": 6},
        "last_collective": "ring-all-reduce/s8/data",
    })


def _hbm(run_dir):
    write_fleet(run_dir)
    _jsonl(run_dir, "mem-p0.jsonl", [
        {"type": "header", "mem_schema_version": 1, "pid": 0,
         "incarnation": 0, "epoch_unix": 1000.0},
        {"type": "mem", "step": 5, "devices": [
            {"d": 0, "kind": "cpu", "bytes_in_use": 95 * 2**20,
             "peak_bytes_in_use": 98 * 2**20,
             "bytes_limit": 100 * 2**20, "source": "stats"}]},
    ])


def _kill_host(run_dir):
    write_fleet(run_dir)
    _j(run_dir, "capacity.json", {
        "capacity_schema_version": 1, "devices": 4,
        "wall_time": 1000.0, "source": "chaos kill_host fault #0"})
    _jsonl(run_dir, "elastic.jsonl", [
        {"elastic_schema_version": 1, "wall_time": 1000.0,
         "event": "launch", "incarnation": 0},
        {"elastic_schema_version": 1, "wall_time": 1001.0,
         "event": "restart", "incarnation": 1, "exit_class": "killed",
         "attempt": 1, "backoff_s": 0.0, "plan": {"n_devices": 4}},
    ])


def _lost_host(run_dir):
    write_fleet(run_dir, lost_host=3)


def _recompile(run_dir):
    write_fleet(run_dir)
    with open(os.path.join(str(run_dir), "trace-p0.jsonl"), "a") as f:
        f.write(json.dumps({
            "schema_version": 1, "type": "counters", "ts_s": 50.0,
            "pid": 0, "attrs": {
                "counters": {"jax/cache/misses": 12,
                             "jax/cache/hits": 1},
                "gauges": {}}}) + "\n")


def _warm_start(run_dir):
    """What a warm start of ResNet-50 on the chip leaves (PERF.md): every
    program a load from the persistent cache, no miss. ``jax/compilations``
    counts real compilations only, so it reads 0 here."""
    write_fleet(run_dir)
    with open(os.path.join(str(run_dir), "trace-p0.jsonl"), "a") as f:
        f.write(json.dumps({
            "schema_version": 1, "type": "counters", "ts_s": 50.0,
            "pid": 0, "attrs": {
                "counters": {"jax/cache/cache_hits": 221,
                             "jax/cache/compile_requests_use_cache": 221,
                             "jax/cache/tasks_using_cache": 1,
                             "jax/cache_loads": 221,
                             "jax/compilations": 0},
                "gauges": {}}}) + "\n")


def _injected_nan(run_dir):
    write_fleet(run_dir, nan_host=2)


def _checkpoint_corrupt(run_dir):
    write_fleet(run_dir)
    _jsonl(run_dir, "elastic.jsonl", [
        {"elastic_schema_version": 1, "wall_time": 1000.0,
         "event": "launch", "incarnation": 0},
        {"elastic_schema_version": 1, "wall_time": 1001.0,
         "event": "stop", "incarnation": 0, "exit_class": "killed",
         "reason": "no verifiable checkpoint",
         "recovery": {"refused": [
             {"step": 4, "reason": "digest mismatch"}]}},
    ])


def _restart_churn(run_dir):
    os.makedirs(str(run_dir), exist_ok=True)
    for inc in range(4):
        name = ("trace-p0.jsonl" if inc == 0
                else f"trace-p0.i{inc}.jsonl")
        records = [
            {"type": "header", "schema_version": 1,
             "epoch_unix": 1000.0 + inc * 100,
             "run_meta": {"incarnation": inc, "run_id": "churn"}},
            {"type": "span", "name": "compiled_step", "ts_s": 1.0,
             "dur_s": 0.5, "step": inc * 10, "depth": 0},
        ]
        if inc == 3:  # only the last life drains cleanly
            records.append({"type": "instant", "name": "run_end",
                            "ts_s": 3.0})
        _jsonl(run_dir, name, records)


def _zero3_serialized(run_dir):
    os.makedirs(str(run_dir), exist_ok=True)
    _jsonl(run_dir, "trace-p0.jsonl", [
        {"type": "header", "schema_version": 1, "epoch_unix": 1000.0,
         "run_meta": {"run_id": "z3", "strategy": "dp+zero3",
                      "config": {"zero3": True}}},
        {"type": "span", "name": "compiled_step", "ts_s": 1.0,
         "dur_s": 0.030, "step": 0, "depth": 0},
        {"type": "instant", "name": "run_end", "ts_s": 2.0},
    ])
    _j(run_dir, "lint.json", {
        "lint_schema_version": 1,
        "programs": {"train_step": {"rule_counts": {"COL001": 2}}}})


FAULT_MATRIX = [
    ("clean", _clean, None),
    ("warm_start_all_cache_loads", _warm_start, None),
    ("data_stall", _data_stall, "DIA001"),
    ("comm_stall", _comm_stall, "DIA002"),
    ("hbm_pressure", _hbm, "DIA003"),
    ("kill_host", _kill_host, "DIA004"),
    ("lost_host", _lost_host, "DIA004"),
    ("recompile_churn", _recompile, "DIA005"),
    ("injected_nan", _injected_nan, "DIA006"),
    ("checkpoint_corrupt", _checkpoint_corrupt, "DIA007"),
    ("restart_churn", _restart_churn, "DIA008"),
    ("zero3_serialized", _zero3_serialized, "DIA009"),
]


# -- the chaos-fault -> verdict matrix --------------------------------------


@pytest.mark.parametrize("fault,build,expected",
                         FAULT_MATRIX, ids=[f[0] for f in FAULT_MATRIX])
def test_fault_matrix_exact_attribution(tmp_path, capsys, fault, build,
                                        expected):
    run = str(tmp_path / fault)
    build(run)
    verdicts = diagnose(gather_evidence(run))
    counts = rule_counts(verdicts)
    if expected is None:
        assert counts == {}, f"clean run fired {counts}"
        assert diagnose_main([run]) == 0
        assert "no suspect" in capsys.readouterr().out
    else:
        # EXACTLY its own root cause: no cross-attribution
        assert counts == {expected: 1}, (
            f"{fault}: expected only {expected}, got {counts}")
        assert diagnose_main([run]) == 1
        out = capsys.readouterr().out
        assert expected in out
        assert RULES[expected]["title"] in out


def test_verdicts_name_their_suspects(tmp_path):
    run = str(tmp_path / "stall")
    _data_stall(run)
    (v,) = diagnose(gather_evidence(run))
    assert v.suspect["stage"] == "augment"
    assert "augment" in v.message

    run = str(tmp_path / "comm")
    _comm_stall(run)
    (v,) = diagnose(gather_evidence(run))
    assert v.suspect["collective"] == "ring-all-reduce/s8/data"
    assert "ring-all-reduce" in v.message

    run = str(tmp_path / "nan")
    _injected_nan(run)
    (v,) = diagnose(gather_evidence(run))
    assert v.suspect["step"] == 20  # write_fleet poisons n_steps // 2
    assert "step 20" in v.message

    run = str(tmp_path / "lost")
    _lost_host(run)
    (v,) = diagnose(gather_evidence(run))
    assert v.suspect == {"host": 3, "kind": "lost_host"}


def test_wedged_collective_suppresses_downstream_data_wedge(tmp_path):
    # a loader stage caught in flight WHILE a collective is wedged is
    # back-pressure behind the held devices — the root cause is the
    # collective, so only DIA002 may fire (no DIA001 riding along)
    run = str(tmp_path / "both")
    _comm_stall(run)
    _j(run, "data-health-p0.json", {
        "data_health_schema_version": 1, "process_index": 0,
        "step": 10, "stages": {},
        "in_flight": {"stage": "shard", "since_unix": 1000.0},
    })
    verdicts = diagnose(gather_evidence(run))
    assert [v.rule for v in verdicts] == ["DIA002"]


@pytest.mark.parametrize("fault,build,expected",
                         [f for f in FAULT_MATRIX if f[2]],
                         ids=[f[0] for f in FAULT_MATRIX if f[2]])
def test_citations_resolve_to_real_files(tmp_path, fault, build,
                                         expected):
    run = str(tmp_path / fault)
    build(run)
    for v in diagnose(gather_evidence(run)):
        assert v.citations, f"{v.rule} carries no citations"
        for c in v.citations:
            assert set(c) == {"path", "field"} and c["field"]
            hits = glob.glob(c["path"])
            assert hits or os.path.exists(c["path"]), (
                f"{v.rule} cites {c['path']} which resolves to nothing")


# -- refusals: absent families are named, never invented --------------------


def test_absent_sources_refuse_by_name(tmp_path, capsys):
    run = str(tmp_path)
    write_fleet(run)
    ev = gather_evidence(run)
    loaded = {n for n, s in ev.sources.items() if s.ok}
    assert loaded == {"trace", "ledger", "health"}
    refused = {r["source"] for r in ev.refusals}
    assert refused == set(SOURCE_NAMES) - loaded
    for r in ev.refusals:
        assert r["reason"], f"{r['source']} refused without a reason"
    # the text report prints every refusal by name
    assert diagnose_main([run]) == 0
    out = capsys.readouterr().out
    for name in refused:
        assert f"cannot judge {name}:" in out


def test_missing_run_dir_is_a_refusal(tmp_path, capsys):
    assert diagnose_main([str(tmp_path / "nope")]) == 2
    assert "not a directory" in capsys.readouterr().err


def test_registry_source_needs_against(tmp_path):
    run = str(tmp_path / "run")
    write_fleet(run)
    from tpu_ddp.registry.store import record_artifact

    art = tmp_path / "lint.json"
    art.write_text(json.dumps({
        "lint_schema_version": 1,
        "programs": {"train_step": {"rule_counts": {}}}}))
    record_artifact(str(tmp_path / "reg"), str(art))
    ev = gather_evidence(run, registry_dir=str(tmp_path / "reg"))
    reg = ev.data("registry")
    assert reg["n_entries"] == 1 and reg["kinds"] == {"lint": 1}
    assert not gather_evidence(run).source("registry").ok


# -- exit-code consistency audit (all six artifact consumers) ---------------


def _future_trace(d):
    _jsonl(d, "trace-p0.jsonl", [
        {"type": "header", "schema_version": 99, "epoch_unix": 1000.0}])


def _future_health(d):
    _jsonl(d, "health-p0.jsonl", [
        {"type": "header", "schema_version": 99, "pid": 0}])


def _future_mem(d):
    _jsonl(d, "mem-p0.jsonl", [
        {"type": "header", "mem_schema_version": 99, "pid": 0,
         "incarnation": 0}])


def _future_comms(d):
    _j(d, "comms-health-p0.json", {
        "comms_health_schema_version": 99, "process_index": 0,
        "in_flight": None, "last_collective": "x/y/z"})


SIX_CLIS = [
    ("curves", lambda d: ["curves", d], _future_health),
    ("comms", lambda d: ["comms", "forensics", d], _future_comms),
    ("data", lambda d: ["data", "report", d], _future_trace),
    ("mem", lambda d: ["mem", d], _future_mem),
    ("goodput", lambda d: ["goodput", d], _future_trace),
    ("diagnose", lambda d: ["diagnose", d], _future_trace),
]


@pytest.mark.parametrize("name,argv,plant", SIX_CLIS,
                         ids=[c[0] for c in SIX_CLIS])
def test_future_schema_artifacts_exit_2(tmp_path, capsys, name, argv,
                                        plant):
    """The house convention, pinned across every artifact-consuming
    subcommand: a future-schema artifact is a refusal (exit 2), never a
    silent misread or a fake finding."""
    run = str(tmp_path)
    plant(run)
    assert cli_main(argv(run)) == 2
    capsys.readouterr()


def test_refusal_exit_2_without_evidence(tmp_path, capsys):
    """Same audit, empty-dir flavor: nothing to judge is exit 2."""
    run = str(tmp_path)
    assert cli_main(["comms", "forensics", run]) == 2
    assert cli_main(["data", "report", run]) == 2
    assert cli_main(["mem", run]) == 2
    assert cli_main(["goodput", run]) == 2
    assert cli_main(["curves", run]) == 2
    assert cli_main(["diagnose", run]) == 2
    capsys.readouterr()


# -- artifact: schema, registry round-trip, compare gate --------------------


def test_diagnose_artifact_shape_and_registry(tmp_path, capsys):
    run = str(tmp_path / "run")
    _data_stall(run)
    out_path = str(tmp_path / "diag.json")
    assert diagnose_main([run, "--json", "--out", out_path]) == 1
    art = json.loads(capsys.readouterr().out)
    with open(out_path) as f:
        assert json.load(f) == art
    assert art["diagnose_schema_version"] == DIAG_SCHEMA_VERSION
    diag = art["diagnose"]
    assert diag["run_id"] == "demo-fleet"
    assert diag["rule_counts"] == {"DIA001": 1}
    assert set(diag["sources"]) == set(SOURCE_NAMES)
    assert diag["sources"]["trace"]["ok"] is True
    assert {r["source"] for r in diag["refusals"]} \
        == {n for n, s in diag["sources"].items() if not s["ok"]}
    # run-identity provenance: the run's own config digest IS the id
    assert art["provenance"]["config_digest"] == "demo-fleet"

    from tpu_ddp.registry.store import record_artifact

    entry = record_artifact(str(tmp_path / "reg"), out_path)
    assert entry.artifact_kind == "diagnose"
    assert entry.metrics.get("diagnose/count/lint/DIA001") == 1.0


def test_compare_gates_on_fresh_suspect_class(tmp_path, capsys):
    clean = str(tmp_path / "clean")
    write_fleet(clean)
    faulty = str(tmp_path / "faulty")
    _data_stall(faulty)
    old = str(tmp_path / "old.json")
    new = str(tmp_path / "new.json")
    assert diagnose_main([clean, "--json", "--out", old]) == 0
    assert diagnose_main([faulty, "--json", "--out", new]) == 1
    capsys.readouterr()
    # a fresh suspect class appearing is a regression...
    assert cli_main(["bench", "compare", old, new]) == 1
    assert "DIA001" in capsys.readouterr().out
    # ...and the suspect disappearing is an improvement
    assert cli_main(["bench", "compare", new, old]) == 0
    capsys.readouterr()


# -- wiring: supervisor death records, watch --once, ledger stall row -------


def test_supervisor_death_record_carries_diagnose_verdict(tmp_path):
    from tpu_ddp.elastic.recovery import read_decisions
    from tpu_ddp.elastic.supervisor import (
        BackoffPolicy,
        RestartPolicy,
        Supervisor,
    )

    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    script = [("killed", 137, 4), ("clean", 0, None)]

    def fake_child(argv):
        kind, rc, survivors = script.pop(0)
        inc = 1 if script == [] else 0
        name = ("trace-p0.jsonl" if inc == 0
                else f"trace-p0.i{inc}.jsonl")
        records = [
            {"type": "header", "schema_version": 1,
             "epoch_unix": 1000.0 + inc * 100,
             "run_meta": {"incarnation": inc}},
            {"type": "span", "name": "compiled_step", "ts_s": 1.0,
             "dur_s": 0.5, "step": 0, "depth": 0},
        ]
        if kind == "clean":
            records.append({"type": "instant", "name": "run_end",
                            "ts_s": 3.0})
        _jsonl(run_dir, name, records)
        if survivors is not None:
            _j(run_dir, "capacity.json", {
                "capacity_schema_version": 1, "devices": survivors,
                "source": "scheduler"})
        return rc

    sup = Supervisor(
        ["--telemetry-dir", run_dir, "--n-devices", "8",
         "--global-batch-size", "64"],
        policy=RestartPolicy(backoff=BackoffPolicy(base_s=0.0)),
        run_child=fake_child,
    )
    assert sup.run() == 0
    restart = [d for d in read_decisions(run_dir)
               if d["event"] == "restart"][0]
    # the death record carries the diagnose verdict: capacity dropped
    # + a killed exit is the lost-host signature
    assert restart["diagnose"]["rule"] == "DIA004"
    assert restart["diagnose"]["suspect"]["kind"] == "lost_host"


def test_watch_once_likely_cause(tmp_path, capsys):
    from tpu_ddp.monitor.watch import main as watch_main

    bad = str(tmp_path / "bad")
    write_fleet(bad, nan_host=2)
    watch_main([bad, "--once", "--json", "--no-alerts-file"])
    report = json.loads(capsys.readouterr().out)
    assert report["likely_cause"]["rule"] == "DIA006"

    clean = str(tmp_path / "clean")
    write_fleet(clean)
    rc = watch_main([clean, "--once", "--no-alerts-file"])
    assert rc == 0
    assert "likely cause: none" in capsys.readouterr().out


def test_goodput_stall_row_names_the_diagnose_verdict(tmp_path, capsys):
    """Satellite contract: the ledger's stall bucket gains diagnose
    attribution, report-only — the sum identity is untouched."""
    run = str(tmp_path)
    _jsonl(run, "trace-p0.jsonl", [
        {"type": "header", "schema_version": 1, "epoch_unix": 1000.0},
        {"type": "span", "name": "compiled_step", "ts_s": 1.0,
         "dur_s": 0.5, "step": 0, "depth": 0},
        {"type": "instant", "name": "watchdog_hang", "ts_s": 8.0},
    ])
    _j(run, "comms-health-p0.json", {
        "comms_health_schema_version": 1, "process_index": 0,
        "in_flight": {"key": "ring-all-reduce/s8/data",
                      "kind": "ring-all-reduce", "dtype": "s8",
                      "axis": "data", "hop": 2, "n_hops": 6},
        "last_collective": "ring-all-reduce/s8/data"})
    assert cli_main(["goodput", run, "--json"]) == 0
    art = json.loads(capsys.readouterr().out)
    ledger = art["ledger"]
    stall = ledger["category_seconds"].get("stall", 0.0)
    assert stall > 0, "fixture regression: the hang must book stall"
    assert ledger["stall_attribution"]["rule"] == "DIA002"
    # sum identity unchanged by the attribution join
    assert sum(ledger["category_seconds"].values()) \
        == pytest.approx(ledger["elapsed_s"], rel=1e-6)
    # text mode points the stall row at diagnose
    assert cli_main(["goodput", run]) == 0
    out = capsys.readouterr().out
    assert "DIA002" in out and "tpu-ddp diagnose" in out


def test_likely_cause_never_raises(tmp_path):
    assert likely_cause(str(tmp_path / "missing")) is None
    run = str(tmp_path / "run")
    _injected_nan(run)
    cause = likely_cause(run)
    assert cause["rule"] == "DIA006"
    assert set(cause) == {"rule", "title", "message", "suspect",
                          "action"}
