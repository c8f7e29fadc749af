"""Anomaly-triggered profiler (docs/profiling.md): capture windows, host
stack sampling, per-op attribution, the POST /profile route, the
capture_profile alert action, and the `tpu-ddp profile` report CLI.

All tier-1 and CPU-only, like the monitor suite this extends: the host
sampler is backend-free by design, the capture manager is driven with a
hand-rolled step loop, and the one jax-backed piece (the per-op anatomy
join) runs devicelessly on the 8-virtual-device CPU mesh.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from tpu_ddp.monitor.aggregate import (
    FleetSnapshot,
    HostSnapshot,
    MonitorConfig,
)
from tpu_ddp.monitor.alerts import AlertEngine, alert_history
from tpu_ddp.monitor.exporter import MonitorExporter
from tpu_ddp.profiler.capture import (
    PROFILE_SCHEMA_VERSION,
    CaptureManager,
    _is_loopback,
    list_bundles,
    parse_profile_steps,
    post_profile_trigger,
    read_bundle_meta,
)
from tpu_ddp.profiler.device import measured_step_from_meta
from tpu_ddp.profiler.host import (
    HostSampler,
    frame_shares,
    parse_folded,
    top_frames,
)
from tpu_ddp.profiler.report import main as profile_main
from tpu_ddp.profiler.report import straggler_diff
from tpu_ddp.telemetry import build_telemetry, reset_default_registry


@pytest.fixture(autouse=True)
def _isolate_registry():
    """The counters registry is process-wide by design; captures here
    must not leak profiler/* counts into the telemetry suite's exact
    snapshots (same contract as test_monitor.py)."""
    reset_default_registry()
    yield
    reset_default_registry()


# -- host sampler ----------------------------------------------------------

def _injected_sleepy_worker(stop):
    while not stop.is_set():
        time.sleep(0.005)


def test_host_sampler_catches_injected_sleep_frame():
    stop = threading.Event()
    worker = threading.Thread(
        target=_injected_sleepy_worker, args=(stop,), daemon=True)
    worker.start()
    sampler = HostSampler(hz=250).start()
    time.sleep(0.4)
    sampler.stop()
    stop.set()
    worker.join(timeout=5)
    assert sampler.samples > 10
    folded = sampler.folded()
    assert "_injected_sleepy_worker" in folded
    top = sampler.top_frames()
    hit = next(
        (r for r in top if "_injected_sleepy_worker" in r["frame"]), None)
    assert hit is not None and hit["self"] > 0 and 0 < hit["share"] <= 1


def test_folded_roundtrip_and_frame_shares():
    text = (
        "MainThread;a (f.py:1);b (f.py:2) 30\n"
        "MainThread;a (f.py:1);c (f.py:3) 10\n"
        "worker;d (g.py:9) 10\n"
        "\n"
        "torn-line-without-count\n"
    )
    folded = parse_folded(text)
    assert folded["MainThread;a (f.py:1);b (f.py:2)"] == 30
    assert len(folded) == 3
    shares = frame_shares(folded)
    assert shares["b (f.py:2)"] == pytest.approx(0.6)
    assert shares["d (g.py:9)"] == pytest.approx(0.2)
    rows = top_frames(folded)
    assert rows[0]["frame"] == "b (f.py:2)" and rows[0]["total"] == 30
    # inclusive counts: 'a' appears on 40 samples but never as leaf
    assert all(r["frame"] != "a (f.py:1)" for r in rows)


def test_sampler_rejects_bad_hz():
    with pytest.raises(ValueError):
        HostSampler(hz=0)


# -- capture manager -------------------------------------------------------

def test_parse_profile_steps():
    assert parse_profile_steps(None) is None
    assert parse_profile_steps("") is None
    assert parse_profile_steps("3:7") == (3, 7)
    assert parse_profile_steps(" 10 : 20 ") == (10, 20)
    for bad in ("7:3", "5:5", "a:b", "3", "3:4:5", "-1:4"):
        with pytest.raises(ValueError):
            parse_profile_steps(bad)


def _drive_window(run_dir, tel, *, arm, steps=range(1, 8),
                  span_s=0.005) -> list:
    cm = CaptureManager(run_dir, window_steps=2, host_hz=400,
                        telemetry=tel,
                        run_meta={"run_id": "t", "strategy": "dp"},
                        device_trace=False)
    arm(cm)
    for step in steps:
        with tel.span("compiled_step"):
            time.sleep(span_s)
        with tel.span("data_wait"):
            time.sleep(span_s / 5)
        cm.on_step(step)
    return cm, list_bundles(run_dir)


def test_capture_bundle_schema_roundtrip(tmp_path):
    run_dir = str(tmp_path)
    tel = build_telemetry(run_dir, "jsonl", run_meta={"run_id": "t"})
    try:
        _, bundles = _drive_window(
            run_dir, tel, arm=lambda cm: cm.arm_window(2, 5))
    finally:
        tel.close()
    assert len(bundles) == 1
    meta = read_bundle_meta(bundles[0]["path"])
    assert meta["schema_version"] == PROFILE_SCHEMA_VERSION
    assert meta["trigger"] == {"source": "config", "rule": None,
                               "host": None, "requested_steps": 3}
    assert meta["window"]["start_step"] == 2
    assert meta["window"]["end_step"] == 5
    assert meta["window"]["steps"] == 3
    assert meta["measured_phases"]["compiled_step"]["count"] == 3
    assert meta["measured_phases"]["data_wait"]["count"] == 3
    assert meta["run_meta"]["strategy"] == "dp"
    assert meta["sources"]["host"]["samples"] >= 1
    assert "note" in meta["sources"]["device"]
    assert os.path.isfile(
        os.path.join(bundles[0]["path"], "host_stacks.folded"))
    with open(os.path.join(bundles[0]["path"], "host_top.json")) as f:
        assert isinstance(json.load(f), list)
    # the satellite counters: surfaced via /metrics and trace summarize
    snap = tel.registry.snapshot()
    assert snap["counters"]["profiler/captures_total"] == 1
    assert snap["counters"]["profiler/capture_seconds"] > 0
    # measured per-step span derives from the bundle alone
    per_step = measured_step_from_meta(meta)
    assert per_step == pytest.approx(
        meta["measured_phases"]["compiled_step"]["total_s"] / 3)


def test_capture_request_single_flight_and_cap(tmp_path):
    run_dir = str(tmp_path)
    tel = build_telemetry(run_dir, "jsonl")
    try:
        cm = CaptureManager(run_dir, window_steps=2, host_hz=400,
                            telemetry=tel, max_captures=1,
                            device_trace=False)
        assert cm.request(source="http") is True
        assert cm.request(source="http") is False  # already armed
        for step in range(1, 5):
            with tel.span("compiled_step"):
                pass
            cm.on_step(step)
        assert cm.completed == 1
        # per-run cap: a second request is refused once max_captures hit
        assert cm.request(source="http") is False
        assert cm.request(steps=0) is False  # degenerate window refused
    finally:
        tel.close()
    assert len(list_bundles(run_dir)) == 1
    meta = read_bundle_meta(list_bundles(run_dir)[0]["path"])
    assert meta["trigger"]["source"] == "http"
    assert meta["window"]["steps"] == 2


def test_capture_close_writes_truncated_bundle(tmp_path):
    run_dir = str(tmp_path)
    tel = build_telemetry(run_dir, "jsonl")
    try:
        cm = CaptureManager(run_dir, window_steps=100, host_hz=400,
                            telemetry=tel, device_trace=False)
        cm.request(source="http", rule="DWT001")
        # scan-fused cadence: each dispatch advances the global step by
        # 4 but records ONE compiled span — the truncated window must
        # count optimizer steps off the step counter, not span counts
        for step in (4, 8, 12):
            with tel.span("compiled_step", steps=4):
                pass
            cm.on_step(step)   # opens at 4, never reaches 104
        cm.close()
        cm.close()      # idempotent
    finally:
        tel.close()
    bundles = list_bundles(run_dir)
    assert len(bundles) == 1
    meta = read_bundle_meta(bundles[0]["path"])
    assert "truncated" in meta["note"]
    assert meta["trigger"]["rule"] == "DWT001"
    assert meta["window"]["start_step"] == 4
    assert meta["window"]["end_step"] == 12
    assert meta["window"]["steps"] == 8  # 2 fused dispatches x 4 steps
    assert meta["measured_phases"]["compiled_step"]["count"] == 2


def test_read_bundle_refuses_future_schema(tmp_path):
    bundle = tmp_path / "profiles" / "step_1-p0"
    bundle.mkdir(parents=True)
    (bundle / "meta.json").write_text(json.dumps(
        {"schema_version": PROFILE_SCHEMA_VERSION + 1}))
    with pytest.raises(ValueError, match="newer"):
        read_bundle_meta(str(bundle))


# -- POST /profile route ---------------------------------------------------

def _post(port, path):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=5) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_post_profile_arms_and_refuses():
    calls = []

    def trigger(**kw):
        calls.append(kw)
        return len(calls) == 1

    exporter = MonitorExporter(port=0, host="127.0.0.1",
                               profile_trigger=trigger).start()
    try:
        code, body = _post(
            exporter.port,
            "/profile?steps=4&source=alert&rule=DWT001&host=2")
        assert (code, body) == (200, {"armed": True, "steps": 4})
        assert calls[0] == {"steps": 4, "source": "alert",
                            "rule": "DWT001", "host": 2}
        # second arm refused by the manager -> 429
        code, body = _post(exporter.port, "/profile")
        assert code == 429 and body["armed"] is False
        # bad parameters -> 400, unknown POST path -> 404
        assert _post(exporter.port, "/profile?steps=zero")[0] == 400
        assert _post(exporter.port, "/metrics")[0] == 404
        # GET routes unaffected
        with urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/metrics",
                timeout=5) as resp:
            assert resp.status == 200
    finally:
        exporter.close()


def test_post_profile_denied_without_capture_manager():
    exporter = MonitorExporter(port=0, host="127.0.0.1").start()
    try:
        code, body = _post(exporter.port, "/profile")
        assert code == 503 and "capture manager" in body["error"]
    finally:
        exporter.close()


def test_post_profile_loopback_gate():
    assert _is_loopback("127.0.0.1")
    assert _is_loopback("127.8.8.8")
    assert _is_loopback("::1")
    assert _is_loopback("::ffff:127.0.0.1")
    assert not _is_loopback("10.0.0.5")
    assert not _is_loopback("192.168.1.2")
    exporter = MonitorExporter(port=0, host="127.0.0.1",
                               profile_trigger=lambda **kw: True)
    try:
        # remote peer refused by default...
        code, body = exporter.arm_profile("", "10.0.0.5")
        assert code == 403 and "--monitor-allow-remote-trigger" in \
            body["error"]
        # ...allowed once the operator opted in
        exporter.allow_remote_trigger = True
        code, body = exporter.arm_profile("", "10.0.0.5")
        assert code == 200 and body["armed"] is True
        # loopback always allowed
        exporter.allow_remote_trigger = False
        assert exporter.arm_profile("", "127.0.0.1")[0] == 200
    finally:
        exporter.close()


def test_post_profile_trigger_discovers_endpoints(tmp_path):
    """The default capture_profile action: run-dir endpoint discovery ->
    POST — end to end against a real exporter."""
    run_dir = str(tmp_path)
    calls = []
    exporter = MonitorExporter(
        port=0, host="127.0.0.1", run_dir=run_dir, process_index=0,
        profile_trigger=lambda **kw: calls.append(kw) or True,
    ).start()
    try:
        assert post_profile_trigger(run_dir, host=0, rule="STR001",
                                    steps=6) is True
        assert calls[0]["rule"] == "STR001" and calls[0]["steps"] == 6
        # an unknown host has no endpoint file: nothing armed
        assert post_profile_trigger(run_dir, host=7) is False
    finally:
        exporter.close()
    # endpoints gone (no exporter files): quietly False
    assert post_profile_trigger(str(tmp_path / "empty")) is False


# -- capture_profile alert action ------------------------------------------

def _dwt_snapshot(run_dir, n_bad=1):
    hosts = [
        HostSnapshot(host=h,
                     data_wait_share=0.9 if h < n_bad else 0.05)
        for h in range(4)
    ]
    return FleetSnapshot(wall_time=1.0, run_dir=run_dir, hosts=hosts,
                         fleet={})


def test_alert_action_rate_limited(tmp_path):
    calls = []
    engine = AlertEngine(
        MonitorConfig(max_auto_profiles=1),
        run_dir=str(tmp_path), actions=("capture_profile",), once=True,
        profile_trigger=lambda **kw: calls.append(kw) or True,
    )
    edges = engine.evaluate(_dwt_snapshot(str(tmp_path), n_bad=2))
    assert {e.rule for e in edges} == {"DWT001"} and len(edges) == 2
    # two firing edges, ONE armed capture: the budget is per run
    assert len(calls) == 1 and engine.auto_profiles == 1
    assert calls[0]["rule"] == "DWT001" and calls[0]["host"] is not None


def test_alert_action_edge_triggered_not_per_poll(tmp_path):
    calls = []
    engine = AlertEngine(
        MonitorConfig(max_auto_profiles=10),
        run_dir=str(tmp_path), actions=("capture_profile",),
        profile_trigger=lambda **kw: calls.append(kw) or True,
    )
    snap = _dwt_snapshot(str(tmp_path))
    engine.evaluate(snap)
    engine.evaluate(snap)  # condition persists: same episode, no new arm
    assert len(calls) == 1


def test_alert_action_ignores_non_capture_rules(tmp_path):
    calls = []
    engine = AlertEngine(
        MonitorConfig(), run_dir=str(tmp_path),
        actions=("capture_profile",), once=True,
        profile_trigger=lambda **kw: calls.append(kw) or True,
    )
    hosts = [HostSnapshot(host=h,
                          health={"nonfinite_steps": 1 if h == 0 else 0})
             for h in range(4)]
    edges = engine.evaluate(FleetSnapshot(
        wall_time=1.0, run_dir=str(tmp_path), hosts=hosts, fleet={}))
    assert {e.rule for e in edges} == {"NUM002"}
    assert calls == []  # numerics alerts have their own evidence path


def test_monitor_config_rejects_negative_cap():
    with pytest.raises(ValueError):
        MonitorConfig(max_auto_profiles=-1).validate()


# -- straggler diff --------------------------------------------------------

def _fleet_shares(straggler_host=2):
    shares = {}
    for host in range(4):
        s = {"compiled (steps.py:5)": 1.0}
        if host == straggler_host:
            s = {"compiled (steps.py:5)": 0.55,
                 "_injected_input_stall (demo.py:7)": 0.45}
        shares[host] = s
    return shares


def test_straggler_diff_names_the_injected_frame():
    diff = straggler_diff(_fleet_shares())
    assert diff["host"] == 2  # auto-picked: most divergent from median
    assert diff["frames"][0]["frame"] == \
        "_injected_input_stall (demo.py:7)"
    assert diff["frames"][0]["delta"] == pytest.approx(0.45)
    # explicit flagged host overrides auto-pick
    diff0 = straggler_diff(_fleet_shares(), flagged=0)
    assert diff0["host"] == 0 and diff0["frames"] == []
    assert straggler_diff({0: {"a": 1.0}}) is None  # needs >= 2 hosts


# -- report CLI ------------------------------------------------------------

def _write_bundle(run_dir, host, *, rule=None, alert_host=None,
                  extra_frame=None):
    bundle = os.path.join(run_dir, "profiles", f"step_100-p{host}")
    os.makedirs(bundle)
    lines = ["MainThread;run (train.py:10);compiled (steps.py:5) 90"]
    if extra_frame:
        lines.append(f"MainThread;run (train.py:10);{extra_frame} 60")
    with open(os.path.join(bundle, "host_stacks.folded"), "w") as f:
        f.write("\n".join(lines) + "\n")
    with open(os.path.join(bundle, "host_top.json"), "w") as f:
        f.write("[]")
    meta = {
        "schema_version": PROFILE_SCHEMA_VERSION, "process_index": host,
        "trigger": {"source": "alert" if rule else "config",
                    "rule": rule, "host": alert_host,
                    "requested_steps": 8},
        "window": {"start_step": 100, "end_step": 108, "steps": 8,
                   "start_wall": 1000.0 + host, "duration_s": 0.4},
        "measured_phases": {
            "compiled_step": {"count": 8, "total_s": 0.08}},
        "sources": {
            "host": {"file": "host_stacks.folded", "samples": 90,
                     "hz": 97},
            "device": {"note": "jax.profiler trace unavailable: test"}},
        "run_meta": {},
    }
    with open(os.path.join(bundle, "meta.json"), "w") as f:
        json.dump(meta, f)
    return bundle


def _run_cli(argv):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = profile_main(argv)
    return rc, out.getvalue(), err.getvalue()


def test_profile_cli_renders_fleet_and_diff(tmp_path):
    run_dir = str(tmp_path)
    for host in range(4):
        _write_bundle(
            run_dir, host, rule="STR001", alert_host=2,
            extra_frame=("_injected_input_stall (demo.py:7)"
                         if host == 2 else None))
    rc, out, _ = _run_cli([run_dir])
    assert rc == 0
    assert "trigger: alert STR001 host 2" in out
    assert "straggler diff: host 2" in out
    assert "_injected_input_stall" in out
    assert "device note: jax.profiler trace unavailable" in out
    # --host narrows rendering but the diff still spans the fleet
    rc, out, _ = _run_cli([run_dir, "--host", "2"])
    assert rc == 0 and out.count("profile bundle:") == 1
    assert "straggler diff: host 2" in out


def test_profile_cli_exit_codes(tmp_path):
    rc, _, err = _run_cli([str(tmp_path / "nope")])
    assert rc == 2 and "no profile bundles" in err
    # a dir with no bundles is the same refusal
    rc, _, err = _run_cli([str(tmp_path)])
    assert rc == 2
    # single-bundle target renders without a diff, writes --json
    bundle = _write_bundle(str(tmp_path), 0)
    report_path = str(tmp_path / "report.json")
    rc, out, _ = _run_cli([bundle, "--json", report_path])
    assert rc == 0 and "straggler diff" not in out
    with open(report_path) as f:
        report = json.load(f)
    assert report["bundles"][0]["meta"]["process_index"] == 0
    # no run directory above a lone bundle held a program map
    assert report["bundles"][0]["program_map"] is None


# -- alert history + watch integration -------------------------------------

def test_alert_history_pairs_episodes():
    records = [
        {"type": "alert", "rule": "STR001", "host": 2, "state": "firing",
         "wall_time": 10.0, "severity": "warning", "message": "m",
         "step": 5},
        {"type": "alert", "rule": "DWT001", "host": 0, "state": "firing",
         "wall_time": 11.0, "severity": "warning", "message": "m2",
         "step": 6},
        {"type": "alert", "rule": "STR001", "host": 2,
         "state": "resolved", "wall_time": 53.0, "severity": "warning",
         "message": "resolved: m", "step": 9},
    ]
    episodes = alert_history(records)
    assert len(episodes) == 2
    assert episodes[0]["duration_s"] == pytest.approx(43.0)
    assert episodes[1]["resolved_wall"] is None  # still open
    assert alert_history([]) == []


def test_watch_once_json_includes_profiles_and_history(tmp_path):
    from tpu_ddp.monitor.watch import main as watch_main
    from fleet_fixture import write_fleet

    run_dir = str(tmp_path)
    write_fleet(run_dir)
    _write_bundle(run_dir, 0, rule="DWT001", alert_host=0)
    with open(os.path.join(run_dir, "alerts.jsonl"), "w") as f:
        for state, wall in (("firing", 100.0), ("resolved", 160.0)):
            f.write(json.dumps({
                "schema_version": 1, "type": "alert", "rule": "STR001",
                "severity": "warning", "state": state, "host": 1,
                "message": "m", "wall_time": wall, "step": 3}) + "\n")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = watch_main([run_dir, "--once", "--json", "--no-alerts-file",
                         "--stale-seconds", "3600"])
    report = json.loads(out.getvalue())
    assert rc == 0
    assert report["schema_version"] == 2
    assert len(report["profiles"]) == 1
    assert report["profiles"][0]["rule"] == "DWT001"
    assert report["history"][0]["duration_s"] == pytest.approx(60.0)
    # the dashboard text renders both sections
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        watch_main([run_dir, "--once", "--no-alerts-file",
                    "--stale-seconds", "3600"])
    text = out.getvalue()
    assert "alert history (1 resolved episode(s)" in text
    assert "profile captures: 1 bundle(s)" in text


# -- config guards + Trainer wiring ----------------------------------------

def test_train_config_profile_guards(tmp_path):
    from tpu_ddp.train.trainer import TrainConfig

    with pytest.raises(ValueError, match="A:B"):
        TrainConfig(profile_steps="oops",
                    telemetry_dir=str(tmp_path)).validate()
    with pytest.raises(ValueError, match="telemetry-dir"):
        TrainConfig(profile_steps="2:4").validate()
    with pytest.raises(ValueError, match="profile_window_steps"):
        TrainConfig(profile_window_steps=0).validate()
    with pytest.raises(ValueError, match="profile_host_hz"):
        TrainConfig(profile_host_hz=0).validate()
    TrainConfig(profile_steps="2:4",
                telemetry_dir=str(tmp_path)).validate()


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_trainer_config_window_end_to_end(tmp_path):
    """--profile-steps on a real (tiny) run: the bundle lands, carries
    the run metadata + measured window phases, the report points at the
    run's program map, and trace summarize surfaces the counters."""
    from tpu_ddp.cli.main import main as cli_main
    from tpu_ddp.telemetry.summarize import summarize
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    run_dir = str(tmp_path)
    config = TrainConfig(
        synthetic_data=True, synthetic_size=256, epochs=1,
        per_shard_batch=4, model="netresdeep", n_chans1=8, n_blocks=2,
        prefetch_depth=0, log_every_epochs=1, telemetry_dir=run_dir,
        telemetry_sinks="jsonl", profile_steps="2:4",
        profile_host_hz=300.0,
    )
    trainer = Trainer(config)
    trainer.run()

    bundles = list_bundles(run_dir)
    assert len(bundles) == 1
    meta = read_bundle_meta(bundles[0]["path"])
    assert meta["trigger"]["source"] == "config"
    assert meta["window"] == {**meta["window"], "start_step": 2,
                              "end_step": 4, "steps": 2}
    assert meta["measured_phases"]["compiled_step"]["count"] == 2
    assert meta["run_meta"]["strategy"] == "dp"

    assert "profiler: 1 capture window(s)" in summarize(run_dir)

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli_main(["profile", run_dir])
    assert rc == 0
    text = out.getvalue()
    assert "host top stacks" in text
    assert "program map:" in text and "train_step (jit_shard_step)" in text


@pytest.mark.slow  # ~16s; the config-window e2e keeps the fast lane — make test-all
def test_trainer_post_profile_arms_live_capture(tmp_path):
    """POST /profile on the live exporter arms a window mid-run — the
    operator path, exercised against a real Trainer."""
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    run_dir = str(tmp_path)
    config = TrainConfig(
        synthetic_data=True, synthetic_size=512, epochs=3,
        per_shard_batch=4, model="netresdeep", n_chans1=8, n_blocks=2,
        prefetch_depth=0, log_every_epochs=1, telemetry_dir=run_dir,
        telemetry_sinks="jsonl", monitor_port=-1,
        profile_window_steps=3, profile_host_hz=300.0,
    )
    trainer = Trainer(config)
    done = threading.Event()

    def run():
        try:
            trainer.run()
        finally:
            done.set()

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    endpoint = os.path.join(run_dir, "exporter-p0.json")
    deadline = time.time() + 120
    armed = False
    try:
        while time.time() < deadline and not done.is_set():
            if os.path.exists(endpoint):
                with open(endpoint) as f:
                    port = json.load(f)["port"]
                code, body = _post(port, "/profile?source=http")
                if code == 200:
                    armed = True
                    break
            time.sleep(0.02)
        assert armed, "never armed a capture over POST /profile"
    finally:
        thread.join(timeout=300)
        trainer.close()
    assert done.is_set()
    bundles = list_bundles(run_dir)
    assert len(bundles) == 1
    meta = read_bundle_meta(bundles[0]["path"])
    assert meta["trigger"]["source"] == "http"
    # a window armed near the run's end may be truncated; either way it
    # covered at least one step and recorded host samples
    assert meta["window"]["steps"] >= 1 or "note" in meta
    assert meta["sources"]["host"]["samples"] >= 0
