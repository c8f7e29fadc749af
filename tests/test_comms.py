"""Comms observatory (docs/comms.md): α-β fits, link-model lookup
rules, the COM001 collapse alert, and stuck-collective forensics.

All but one test here is stdlib-only and sub-second; that one runs
``comms bench`` for real. The live circuit (a real comm_stall, a real
watchdog hang) is checked by no test.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from tpu_ddp.comms.forensics import (
    HopMonitor,
    _suspect_of,
    match_program_order,
    suspect_from_files,
    write_hang_bundle,
)
from tpu_ddp.comms.model import (
    AlphaBeta,
    LinkModel,
    axis_baselines,
    comms_model_for_chip,
    fit_alpha_beta,
    link_key,
    split_link_key,
)


# -- the α-β fit -----------------------------------------------------------


def test_fit_alpha_beta_recovers_a_hand_computed_line():
    # points exactly on t = 100us + bytes / 1 GB/s
    alpha, beta = 1e-4, 1e9
    xs = [1e3, 1e4, 1e5, 1e6]
    ys = [alpha + x / beta for x in xs]
    ab = fit_alpha_beta(xs, ys)
    assert ab.alpha_s == pytest.approx(alpha, rel=1e-6)
    assert ab.beta_bytes_per_s == pytest.approx(beta, rel=1e-6)
    assert ab.samples == 4
    # and the line round-trips through the artifact JSON shape
    back = AlphaBeta.from_json(ab.to_json())
    assert back is not None and back.time_s(1e6) == pytest.approx(
        ab.time_s(1e6))


def test_fit_is_monotone_even_on_noise_tilted_downward():
    # bigger payloads measured FASTER (pure noise): the slope clamp
    # keeps β finite-positive so modeled time never decreases in bytes
    ab = fit_alpha_beta([1e3, 1e6], [2e-3, 1e-3])
    assert ab.alpha_s >= 0.0 and ab.beta_bytes_per_s > 0.0
    assert ab.time_s(1e6) >= ab.time_s(1e3)
    # a negative intercept is noise, not negative latency
    steep = fit_alpha_beta([1e3, 2e3], [1e-3, 3e-3])
    assert steep.alpha_s >= 0.0


def test_fit_refuses_degenerate_inputs():
    with pytest.raises(ValueError, match="payloads vs"):
        fit_alpha_beta([1.0, 2.0], [1.0])
    with pytest.raises(ValueError, match="distinct payload"):
        fit_alpha_beta([4096.0, 4096.0], [1e-3, 2e-3])


# -- lookup rules ----------------------------------------------------------


def test_link_model_lookup_exact_then_conservative_fallbacks():
    fast = AlphaBeta(1e-5, 4e9, 2)
    slow = AlphaBeta(1e-5, 1e9, 2)
    model = LinkModel(chip="cpu", links={
        link_key("all-reduce", "f32", "data"): fast,
        link_key("all-reduce", "bf16", "data"): slow,
    })
    # exact key wins
    assert model.lookup("all-reduce", "f32", "data") is fast
    # same kind + named axis, unmeasured dtype: the SLOWEST measured
    # dtype stands in (conservative, never flattering)
    assert model.lookup("all-reduce", "s8", "data") is slow
    # an unattributed axis may borrow, dtype match preferred
    assert model.lookup("all-reduce", "f32", "unknown") is fast
    assert model.lookup("all-reduce", "s8", "all") is slow
    # wrong-AXIS evidence never prices a named axis it didn't see
    assert model.lookup("all-reduce", "f32", "model") is None
    # wrong KIND finds nothing at all
    assert model.lookup("all-gather", "f32", "data") is None
    assert model.time_for("all-gather", "f32", "data", 1e6) is None
    # α is charged per invocation
    t = model.time_for("all-reduce", "f32", "data", 2e6, count=4)
    assert t == pytest.approx(4 * 1e-5 + 2e6 / 4e9)


def _bench_artifact(tmp_path, name, device_kind, links):
    path = tmp_path / name
    path.write_text(json.dumps({
        "type": "comms",
        "comms_schema_version": 1,
        "comms": {
            "chip": device_kind,
            "device_kind": device_kind,
            "n_devices": 4,
            "links": links,
        },
    }))
    return str(path)


def test_comms_model_for_chip_ignores_wrong_chip_evidence(tmp_path):
    cpu_link = {"alpha_s": 1e-5, "beta_bytes_per_s": 1e9, "samples": 4}
    tpu_link = {"alpha_s": 1e-6, "beta_bytes_per_s": 9e10, "samples": 4}
    cpu_art = _bench_artifact(
        tmp_path, "cpu.json", "cpu",
        {"ring-all-reduce/s8/data": cpu_link})
    tpu_art = _bench_artifact(
        tmp_path, "v5e.json", "TPU v5 lite",
        {"ring-all-reduce/s8/data": tpu_link,
         "all-gather/f32/model": tpu_link})
    model = comms_model_for_chip("cpu", sources=[cpu_art, tpu_art])
    assert set(model.links) == {"ring-all-reduce/s8/data"}
    assert model.links["ring-all-reduce/s8/data"].beta_bytes_per_s \
        == pytest.approx(1e9)
    # the v5e's flattering β never leaked into the cpu model
    assert model.lookup("all-gather", "f32", "model") is None


def test_axis_baselines_prefers_ring_links():
    rec = {"links": {
        # the XLA all-reduce is faster, but COM001 compares against
        # what the hop monitor actually times: the explicit rings
        "all-reduce/f32/data": {"achieved_bw_bytes_per_s": 9e9},
        "ring-all-reduce/s8/data": {"achieved_bw_bytes_per_s": 5e8},
        "ring-all-reduce/f32/data": {"achieved_bw_bytes_per_s": 4e8},
        "all-gather/f32/model": {"achieved_bw_bytes_per_s": 2e9},
    }}
    base = axis_baselines(rec)
    assert base["data"] == pytest.approx(5e8)   # best RING, not best
    assert base["model"] == pytest.approx(2e9)  # no ring: any kind
    assert axis_baselines({}) == {}
    assert axis_baselines({"links": {"junk": {}}}) == {}


# -- the artifact as a registry/compare citizen ----------------------------


def test_comms_artifact_classifies_and_gates_both_directions(tmp_path):
    from tpu_ddp.analysis.regress import compare, normalize_artifact
    from tpu_ddp.registry.store import _artifact_kind

    def art(bw):
        return {
            "type": "comms", "comms_schema_version": 1,
            "comms": {"chip": "cpu",
                      "achieved_bw_bytes_per_s": bw,
                      "alpha_s": 1e-5,
                      "links": {}, "sweeps": [{"raw": 1}], "skipped": []},
        }

    assert _artifact_kind(art(1e9)) == "comms"
    old = normalize_artifact(art(1.0e9))
    assert "comms" in old and "sweeps" not in old["comms"]
    # a measured bandwidth DROP beyond tolerance regresses...
    res = compare(old, normalize_artifact(art(0.5e9)), tolerance=0.05)
    assert any("achieved_bw" in r for r in res["regressions"])
    # ...a rise improves, and within-tolerance wobble gates nothing
    res = compare(old, normalize_artifact(art(2.0e9)), tolerance=0.05)
    assert not res["regressions"]
    assert any("achieved_bw" in r for r in res["improvements"])
    res = compare(old, normalize_artifact(art(1.01e9)), tolerance=0.05)
    assert not res["regressions"] and not any(
        "achieved_bw" in r for r in res["improvements"])


def test_comms_bench_measures_rings_into_monotone_fits(tmp_path):
    """``tpu-ddp comms bench`` on four virtual devices: the XLA
    all-reduce and the f32 and int8 rings are really run at two payload
    sizes, every fitted line rises with the bytes on the wire, the int8
    ring moves fewer bytes than the f32 ring at equal payload (from the
    measured rows), and the artifact records as kind ``comms``."""
    from tpu_ddp.comms.cli import main as comms_main
    from tpu_ddp.registry.store import record_artifact

    out = tmp_path / "comms.json"
    assert comms_main([
        "bench", "--mesh", "data=4",
        "--kinds", "all-reduce,ring-all-reduce", "--ring-modes", "f32,int8",
        "--sizes", "4096,16384", "--reps", "1", "--out", str(out)]) == 0
    comms = json.loads(out.read_text())["comms"]
    assert {"ring-all-reduce/f32/data",
            "ring-all-reduce/s8/data"} <= set(comms["links"])
    for link in comms["links"].values():
        assert link["alpha_s"] >= 0.0 and link["beta_bytes_per_s"] > 0.0
    wire = {(r["dtype"], r["size"]): r["wire_bytes"]
            for r in comms["sweeps"] if r["kind"] == "ring-all-reduce"}
    for size in (4096, 16384):
        assert wire[("s8", size)] < wire[("f32", size)]
    entry = record_artifact(str(tmp_path / "reg"), str(out))
    assert entry.artifact_kind == "comms"


# -- the hop monitor's health file -----------------------------------------


def test_hop_monitor_health_file_and_fault_hook_order(tmp_path):
    seen = []

    def hook(axis, hop):
        # the health write must ALREADY be on disk when chaos runs —
        # a stall that never returns still left the suspect behind
        rec = json.load(open(os.path.join(
            tmp_path, "comms-health-p0.json")))
        seen.append((axis, hop, (rec.get("in_flight") or {}).get("key")))

    mon = HopMonitor(str(tmp_path), process_index=0, n_devices=4,
                     fault_hook=hook, min_write_interval_s=0.0)
    mon.on_hop(None, kind="ring-all-reduce", dtype="s8", axis="data",
               hop=1, n_hops=4, wire_bytes=1024)
    assert seen == [("data", 1, "ring-all-reduce/s8/data")]
    rec = json.load(open(mon.path))
    assert rec["in_flight"]["hop"] == 1
    assert rec["axis_bytes_window"]["data"] == 1024
    # the final hop completes the collective: in_flight clears,
    # last_collective records what ran
    mon.on_hop(None, kind="ring-all-reduce", dtype="s8", axis="data",
               hop=4, n_hops=4, wire_bytes=1024)
    mon.close()
    rec = json.load(open(mon.path))
    assert rec["in_flight"] is None
    assert rec["last_collective"] == "ring-all-reduce/s8/data"
    assert rec["hops"] == 2 and rec["n_devices"] == 4


# -- forensics: naming the suspect -----------------------------------------


def test_suspect_precedence_in_flight_over_last_collective():
    flight = {"key": "ring-all-reduce/s8/data", "kind": "ring-all-reduce",
              "dtype": "s8", "axis": "data", "hop": 2, "n_hops": 6}
    s = _suspect_of({"in_flight": flight, "last_collective": "x/y/z"})
    assert s["source"] == "in_flight" and s["hop"] == 2
    s = _suspect_of({"in_flight": None,
                     "last_collective": "ring-all-reduce/s8/data"})
    assert s["source"] == "last_collective"
    assert (s["kind"], s["dtype"], s["axis"]) \
        == ("ring-all-reduce", "s8", "data")
    assert _suspect_of({"in_flight": None, "last_collective": ""}) is None


def test_hang_bundle_joins_health_heartbeat_and_stack(tmp_path):
    run_dir = str(tmp_path)
    with open(os.path.join(run_dir, "comms-health-p0.json"), "w") as f:
        json.dump({"process_index": 0, "in_flight": {
            "key": "ring-all-reduce/s8/data", "kind": "ring-all-reduce",
            "dtype": "s8", "axis": "data", "hop": 3, "n_hops": 6}}, f)
    with open(os.path.join(run_dir, "heartbeat-p0.json"), "w") as f:
        json.dump({"step": 41, "wall_time": time.time()}, f)
    rec = write_hang_bundle(
        run_dir, process_index=0,
        dump_text="... in ring_all_reduce\n parallel/collectives.py:10")
    assert rec["suspect_collective"]["key"] == "ring-all-reduce/s8/data"
    assert rec["last_step"] == 41 and rec["stack_mentions_ring"]
    # the bundle on disk is what the supervisor/ledger join reads, and
    # it wins over the raw health files
    with open(os.path.join(run_dir, "comms-health-p0.json"), "w") as f:
        json.dump({"in_flight": None, "last_collective": "other/f32/data"},
                  f)
    suspect = suspect_from_files(run_dir)
    assert suspect["key"] == "ring-all-reduce/s8/data"
    assert suspect["source"] == "in_flight"


def test_suspect_from_files_falls_back_to_raw_health(tmp_path):
    assert suspect_from_files(str(tmp_path)) is None
    with open(os.path.join(tmp_path, "comms-health-p1.json"), "w") as f:
        json.dump({"in_flight": None,
                   "last_collective": "ring-reduce-scatter/bf16/data"}, f)
    s = suspect_from_files(str(tmp_path))
    assert s["key"] == "ring-reduce-scatter/bf16/data"
    assert s["source"] == "last_collective"


def test_match_program_order_lowers_rings_to_collective_permute():
    order = [
        "all-gather/f32/data/g4",
        "collective-permute/s8/data/g4",
        "all-reduce/f32/data/g4",
    ]
    # the explicit ring never appears by its own name in HLO: the match
    # goes through its lowered kind and wire dtype
    m = match_program_order(
        {"kind": "ring-all-reduce", "dtype": "int8", "axis": "data"},
        order)
    assert m == {"index": 1, "entry": "collective-permute/s8/data/g4"}
    m = match_program_order(
        {"kind": "all-reduce", "dtype": "f32", "axis": "data"}, order)
    assert m["index"] == 2
    # a suspect the program never contained is a finding, not a match
    assert match_program_order(
        {"kind": "all-to-all", "dtype": "f32", "axis": "data"},
        order) is None
    assert match_program_order(None, order) is None
    assert match_program_order({"kind": "all-reduce"}, []) is None


# -- COM001: measured collapse vs calibrated baseline ----------------------


def _health_rec(now, *, age_s, axis_bw, bytes_win, span_s, in_flight):
    return {
        "comms_health_schema_version": 1,
        "updated_unix": now - age_s,
        "process_index": 0,
        "n_devices": 4,
        "step": 7,
        "axis_bw": {"data": axis_bw},
        "axis_bytes_window": {"data": bytes_win},
        "window_span_s": {"data": span_s},
        "in_flight": in_flight,
        "last_collective": "ring-all-reduce/s8/data",
    }


def test_comms_host_view_staleness_decay():
    from tpu_ddp.monitor.aggregate import comms_host_view

    now = 1000.0
    flight = {"key": "ring-all-reduce/s8/data", "hop": 1, "n_hops": 6}
    # wedged mid-collective for 9s: the frozen 1s window's bytes spread
    # over 10s of wall clock -> the figure decays 10x
    view = comms_host_view(_health_rec(
        now, age_s=9.0, axis_bw=1e6, bytes_win=4e6, span_s=1.0,
        in_flight=flight), now)
    assert view["axis_bw"]["data"] == pytest.approx(4e6 / (10.0 * 4))
    assert view["age_s"] == pytest.approx(9.0)
    # idle between collectives is NOT a wedge: no decay without
    # something in flight
    view = comms_host_view(_health_rec(
        now, age_s=9.0, axis_bw=1e6, bytes_win=4e6, span_s=1.0,
        in_flight=None), now)
    assert view["axis_bw"]["data"] == pytest.approx(1e6)
    assert comms_host_view(None, now) == {}


def test_com001_fires_on_collapse_and_stays_quiet_otherwise(tmp_path):
    from tpu_ddp.monitor.aggregate import (
        FleetSnapshot,
        HostSnapshot,
        MonitorConfig,
    )
    from tpu_ddp.monitor.alerts import AlertEngine

    baseline = _bench_artifact(
        tmp_path, "bench.json", "cpu",
        {"ring-all-reduce/s8/data": {
            "alpha_s": 1e-5, "beta_bytes_per_s": 1e9, "samples": 4,
            "achieved_bw_bytes_per_s": 1e8}})
    cfg = MonitorConfig(comms_baseline=baseline).validate()

    def snap(axis_bw, in_flight):
        host = HostSnapshot(host=0, step=7, comms={
            "axis_bw": {"data": axis_bw},
            "in_flight": in_flight,
            "last_collective": "ring-all-reduce/s8/data"})
        return FleetSnapshot(wall_time=1000.0, run_dir=str(tmp_path),
                             hosts=[host], fleet={"n_hosts": 1})

    flight = {"key": "ring-all-reduce/s8/data", "hop": 2, "n_hops": 6}
    engine = AlertEngine(cfg, once=True)
    edges = engine.evaluate(snap(1e6, flight))     # 1% of calibrated
    assert [(a.rule, a.host, a.state) for a in edges] \
        == [("COM001", 0, "firing")]
    assert "calibrated" in edges[0].message
    assert "ring-all-reduce/s8/data" in edges[0].message
    # recovery resolves the edge
    resolved = engine.evaluate(snap(9e7, None))
    assert [(a.rule, a.state) for a in resolved] \
        == [("COM001", "resolved")]
    # healthy bandwidth never fires
    quiet = AlertEngine(cfg, once=True)
    assert quiet.evaluate(snap(9e7, flight)) == []
    # no baseline artifact -> the rule is disabled, not crashing
    dark = AlertEngine(MonitorConfig(
        comms_baseline=str(tmp_path / "missing.json")).validate(),
        once=True)
    assert dark.evaluate(snap(1e3, flight)) == []
    # threshold knob is validated where every other knob is
    with pytest.raises(ValueError, match="comms_collapse_frac"):
        MonitorConfig(comms_collapse_frac=0.0).validate()


# -- chaos comm_stall + trainer wiring -------------------------------------


def _spec(tmp_path, faults):
    path = tmp_path / "chaos.json"
    path.write_text(json.dumps({
        "chaos_schema_version": 1, "seed": 0, "faults": faults}))
    return str(path)


def test_comm_stall_spec_validation(tmp_path):
    from tpu_ddp.chaos.inject import load_spec

    good = _spec(tmp_path, [
        {"kind": "comm_stall", "step": 3, "delay_s": 5.0, "hops": 2}])
    assert load_spec(good)["faults"][0]["kind"] == "comm_stall"
    with pytest.raises(ValueError, match="delay_s"):
        load_spec(_spec(tmp_path, [
            {"kind": "comm_stall", "step": 3, "delay_s": 0}]))
    with pytest.raises(ValueError, match="hops"):
        load_spec(_spec(tmp_path, [
            {"kind": "comm_stall", "step": 3, "delay_s": 1.0,
             "hops": 0}]))


def test_comm_stall_hook_stalls_exactly_n_hops_once(tmp_path):
    from tpu_ddp.chaos.inject import ChaosInjector

    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir)
    spec = _spec(tmp_path, [
        {"kind": "comm_stall", "step": 2, "delay_s": 0.01, "hops": 2}])
    inj = ChaosInjector(spec, run_dir)
    assert inj.wants_comm_stall()
    inj.on_step(0)
    t0 = time.monotonic()
    inj.comm_stall_hook("data", 1)      # step 1 in flight: not yet due
    assert time.monotonic() - t0 < 0.009
    inj.on_step(1)                      # next step (2) is the trigger
    t0 = time.monotonic()
    inj.comm_stall_hook("data", 1)
    inj.comm_stall_hook("data", 2)
    assert time.monotonic() - t0 >= 0.02    # both hops stalled
    t0 = time.monotonic()
    inj.comm_stall_hook("data", 3)          # budget spent: full speed
    assert time.monotonic() - t0 < 0.009
    # fire-once across a resume: persisted state, not process memory
    inj2 = ChaosInjector(spec, run_dir)
    inj2.on_step(5)
    t0 = time.monotonic()
    inj2.comm_stall_hook("data", 1)
    assert time.monotonic() - t0 < 0.009


def test_trainconfig_comms_monitor_rules(tmp_path):
    from tpu_ddp.train.trainer import TrainConfig

    with pytest.raises(ValueError, match="telemetry-dir"):
        TrainConfig(synthetic_data=True, comms_monitor=True).validate()
    with pytest.raises(ValueError, match="lint-on-start"):
        TrainConfig(synthetic_data=True, comms_monitor=True,
                    lint_on_start=True,
                    telemetry_dir=str(tmp_path)).validate()
    # a comm_stall spec without the monitor is a no-op chaos run: refuse
    spec = _spec(tmp_path, [
        {"kind": "comm_stall", "step": 2, "delay_s": 1.0}])
    with pytest.raises(ValueError, match="comms-monitor"):
        TrainConfig(synthetic_data=True, chaos_spec=spec,
                    telemetry_dir=str(tmp_path)).validate()
    cfg = TrainConfig(synthetic_data=True, comms_monitor=True,
                      chaos_spec=spec,
                      telemetry_dir=str(tmp_path)).validate()
    assert cfg.comms_monitor


def test_ledger_note_names_the_suspect_for_hang_incarnations(tmp_path):
    from tpu_ddp.ledger.stitch import stitch_run

    run_dir = str(tmp_path)
    epoch = time.time() - 100
    with open(os.path.join(run_dir, "trace-p0.jsonl"), "w") as f:
        f.write(json.dumps({
            "type": "header", "trace_schema_version": 3,
            "ts_s": 0.0, "epoch_unix": epoch}) + "\n")
        f.write(json.dumps({
            "type": "span", "name": "compiled_step", "depth": 0,
            "ts_s": 1.0, "dur_s": 1.0, "step": 0}) + "\n")
        f.write(json.dumps({
            "type": "instant", "name": "watchdog_hang",
            "ts_s": 30.0}) + "\n")
    with open(os.path.join(run_dir, "comms-health-p0.json"), "w") as f:
        json.dump({"in_flight": {
            "key": "ring-all-reduce/s8/data", "kind": "ring-all-reduce",
            "dtype": "s8", "axis": "data", "hop": 1, "n_hops": 6}}, f)
    stitched = stitch_run(run_dir)
    inc = stitched.incarnations[0]
    assert inc.exit == "hang"
    assert any("ring-all-reduce/s8/data" in n for n in inc.notes)
    assert any("in_flight" in n for n in inc.notes)


def test_split_link_key_roundtrip():
    assert split_link_key(link_key("all-reduce", "f32", "data")) == {
        "kind": "all-reduce", "dtype": "f32", "axis": "data"}
    assert split_link_key("no-slashes") is None
    assert split_link_key("a/b") is None
    assert split_link_key("a//c") is None
