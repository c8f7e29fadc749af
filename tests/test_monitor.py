"""Live fleet monitor (tpu_ddp/monitor): exporter, aggregator, alerts,
watch CLI, and Trainer wiring. All CPU-only and fast (tier-1).

The synthetic-fleet tests write the same per-host file families a real
multihost run leaves in its run dir (``trace-p<i>.jsonl``,
``health-p<i>.jsonl``, ``heartbeat-p<i>.json``) with an injected
straggler / lost host / NaN step, and assert the aggregator + rule
engine flag exactly those hosts and rule ids.
"""

import functools
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from tpu_ddp.monitor import (
    ALERT_RULES,
    AlertEngine,
    FleetAggregator,
    FleetSnapshot,
    HostSnapshot,
    MonitorConfig,
    MonitorExporter,
    host_skew,
    read_fleet_snapshot,
    render_openmetrics,
)
from tpu_ddp.monitor.alerts import ALERT_SCHEMA_VERSION, read_alerts
from tpu_ddp.monitor.watch import WATCH_SCHEMA_VERSION
from tpu_ddp.monitor.watch import main as watch_main
from tpu_ddp.telemetry import reset_default_registry
from tpu_ddp.telemetry.registry import Registry
from tpu_ddp.telemetry.watchdog import (
    HangWatchdog,
    heartbeat_age_seconds,
    read_heartbeat,
)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import fleet_fixture  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_registry():
    """The counters registry is process-wide by design; the Trainer runs
    here must not leak train/steps etc. into later tests' snapshots (the
    telemetry suite asserts exact counts)."""
    reset_default_registry()
    yield
    reset_default_registry()


# -- synthetic fleet files -------------------------------------------------

RUN_META = {
    "run_meta_schema_version": 1,
    "run_id": "cafe0123ab",
    "strategy": "dp",
    "mesh": {"data": 8},
    "process_count": 4,
    "config": {"model": "netresdeep"},
}


write_fleet = functools.partial(fleet_fixture.write_fleet, n_steps=30,
                                run_meta=RUN_META)


# -- OpenMetrics rendering -------------------------------------------------

def _parse_openmetrics(text):
    """{name: (labels_str, value)} for every sample line."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_labels, value = line.rsplit(" ", 1)
        if "{" in name_labels:
            name, labels = name_labels.split("{", 1)
            labels = labels.rstrip("}")
        else:
            name, labels = name_labels, ""
        out[name] = (labels, float(value))
    return out


def test_openmetrics_render_round_trip():
    reg = Registry()
    reg.counter("train/steps").inc(40)
    reg.gauge("train/images_per_sec_per_chip").set(1234.5)
    hist = reg.histogram("phase/compiled_step")
    for v in (0.01, 0.02, 0.03, 0.04):
        hist.record(v)
    labels = {"run_id": "abc123", "strategy": "dp", "mesh": "data=8",
              "host": "0"}
    text = render_openmetrics(reg.snapshot(), labels)

    assert text.endswith("# EOF\n")  # OpenMetrics terminator
    samples = _parse_openmetrics(text)
    # counters carry the mandated _total suffix
    lbl, val = samples["tpu_ddp_train_steps_total"]
    assert val == 40
    for part in ('run_id="abc123"', 'strategy="dp"', 'mesh="data=8"',
                 'host="0"'):
        assert part in lbl
    assert samples["tpu_ddp_train_images_per_sec_per_chip"][1] == 1234.5
    # histograms render as summaries: quantiles + _count + _sum
    assert samples["tpu_ddp_phase_compiled_step_count"][1] == 4
    assert samples["tpu_ddp_phase_compiled_step_sum"][1] == pytest.approx(0.1)
    assert "# TYPE tpu_ddp_phase_compiled_step summary" in text
    assert 'quantile="0.5"' in text
    # TYPE declarations precede their samples
    assert "# TYPE tpu_ddp_train_steps counter" in text


def test_openmetrics_label_escaping_and_empty_registry():
    text = render_openmetrics(
        {"counters": {"x": 1}},
        {"run_id": 'we"ird\\path\nline'},
    )
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    # an empty registry still renders a valid (terminated) exposition
    assert render_openmetrics({}, {}).strip() == "# EOF"


# -- exporter HTTP surface -------------------------------------------------

def _get(port, path):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5
        ) as resp:
            return resp.status, resp.read().decode(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(), dict(e.headers)


def test_exporter_endpoints(tmp_path):
    reg = Registry()
    reg.counter("train/steps").inc(7)
    exporter = MonitorExporter(
        registry=reg, run_meta=RUN_META, port=0, process_index=0,
        run_dir=str(tmp_path),
    ).start()
    try:
        assert exporter.port > 0  # ephemeral bind
        status, body, headers = _get(exporter.port, "/metrics")
        assert status == 200
        assert "openmetrics-text" in headers["Content-Type"]
        assert 'run_id="cafe0123ab"' in body
        assert 'strategy="dp"' in body and 'mesh="data=8"' in body
        assert "tpu_ddp_train_steps_total" in body

        status, body, _ = _get(exporter.port, "/snapshot.json")
        snap = json.loads(body)
        assert status == 200
        assert snap["schema_version"] == 1
        assert snap["run_meta"]["run_id"] == "cafe0123ab"
        assert snap["metrics"]["counters"]["train/steps"] == 7

        # no watchdog configured: alive by virtue of answering
        status, body, _ = _get(exporter.port, "/healthz")
        assert status == 200
        assert json.loads(body)["status"] == "no-watchdog"

        status, _, _ = _get(exporter.port, "/nope")
        assert status == 404

        # scrape-target discovery file
        with open(tmp_path / "exporter-p0.json") as f:
            endpoint = json.load(f)
        assert endpoint["port"] == exporter.port
    finally:
        exporter.close()
    # closed: the socket must actually be gone
    with pytest.raises(OSError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/healthz", timeout=1)


def test_healthz_flips_with_watchdog_staleness():
    """The /healthz contract: 200 while beats are fresh, 503 once the
    watchdog deadline passes, back to 200 on the next beat."""
    wd = HangWatchdog(0.2, poll_interval=0.05).start()
    exporter = MonitorExporter(registry=Registry(), watchdog=wd).start()
    try:
        wd.on_step(5)
        status, body, _ = _get(exporter.port, "/healthz")
        body = json.loads(body)
        assert status == 200 and body["status"] == "ok"
        assert body["last_step"] == 5
        assert body["deadline_s"] == 0.2

        time.sleep(0.35)  # past the deadline without a beat
        status, body, _ = _get(exporter.port, "/healthz")
        assert status == 503 and json.loads(body)["status"] == "stale"
        assert wd.is_stale()

        wd.on_step(6)  # recovery re-arms freshness
        status, body, _ = _get(exporter.port, "/healthz")
        assert status == 200 and json.loads(body)["status"] == "ok"
    finally:
        exporter.close()
        wd.close()


# -- fleet aggregation -----------------------------------------------------

def test_aggregator_flags_straggler_and_lost_host(tmp_path):
    now = write_fleet(tmp_path, straggler_host=2, lost_host=3)
    snap = read_fleet_snapshot(str(tmp_path), now=now)

    assert [h.host for h in snap.hosts] == [0, 1, 2, 3]
    assert snap.stragglers == [2]          # exactly the injected one
    assert snap.lost == [3]                # exactly the stale heartbeat
    assert snap.run_id == "cafe0123ab"
    assert snap.strategy == "dp"

    by_host = {h.host: h for h in snap.hosts}
    assert by_host[2].straggler and "compiled_step" in by_host[2].straggler_phases
    assert not by_host[0].straggler and not by_host[0].lost
    assert by_host[3].heartbeat_age_s == pytest.approx(600, abs=5)
    # derived per-host stats
    h0 = by_host[0]
    assert h0.step == 29
    assert h0.steps_per_sec == pytest.approx(1 / 0.013, rel=0.1)
    assert h0.phase_p50_s["compiled_step"] == pytest.approx(0.010)
    # the queue is full: every wait for input sits behind a busy device
    assert h0.data_wait_share == 0.0
    assert h0.health["nonfinite_steps"] == 0
    # fleet rollup + snapshot schema
    assert snap.fleet["n_hosts"] == 4
    assert snap.fleet["step_max"] == 29
    payload = snap.to_json()
    assert payload["schema_version"] == 2
    json.dumps(payload)  # wire-shape must be serializable


def test_aggregator_clean_fleet_flags_nothing(tmp_path):
    now = write_fleet(tmp_path)
    snap = read_fleet_snapshot(str(tmp_path), now=now)
    assert snap.stragglers == [] and snap.lost == []
    assert all(not h.straggler and not h.lost for h in snap.hosts)


def test_finished_run_is_ended_not_lost(tmp_path):
    """A cleanly finished run's staleness is expected: hosts that
    recorded the run_end marker must never flag FLT001, no matter how
    old the dir is — `watch --once` over finished runs is a CI surface."""
    now = write_fleet(tmp_path)
    for host in range(4):  # every host shut down cleanly...
        with open(tmp_path / f"trace-p{host}.jsonl", "a") as f:
            f.write(json.dumps({
                "schema_version": 1, "type": "instant", "name": "run_end",
                "ts_s": 100.0, "pid": host, "tid": 1,
            }) + "\n")
    # ...and the whole dir is now an hour old
    snap = read_fleet_snapshot(str(tmp_path), now=now + 3600)
    assert all(h.ended for h in snap.hosts)
    assert snap.lost == []
    engine = AlertEngine(MonitorConfig(), once=True)
    assert engine.evaluate(snap) == []


def test_data_wait_share_correct_under_scan_fusion(tmp_path):
    """The share is a wall-time ratio: a fused K-step compiled span must
    weigh its full duration, not the per-step-normalized p50 input."""
    os.makedirs(tmp_path, exist_ok=True)
    with open(tmp_path / "trace-p0.jsonl", "w") as f:
        f.write(json.dumps({"schema_version": 1, "type": "header",
                            "epoch_unix": 0.0, "pid": 0}) + "\n")
        ts = 0.0
        for group in range(10):
            f.write(json.dumps({
                "schema_version": 1, "type": "span", "name": "data_wait",
                "ts_s": ts, "dur_s": 1.0, "pid": 0, "tid": 1,
            }) + "\n")
            ts += 1.0
            f.write(json.dumps({
                "schema_version": 1, "type": "span",
                "name": "compiled_step", "ts_s": ts, "dur_s": 8.0,
                "pid": 0, "tid": 1, "step": group * 8,
                "attrs": {"steps": 8},
            }) + "\n")
            ts += 8.0
    snap = read_fleet_snapshot(str(tmp_path), now=1e12)
    h0 = snap.hosts[0]
    # per-step p50 IS normalized (8s span / 8 steps)...
    assert h0.phase_p50_s["compiled_step"] == pytest.approx(1.0)
    # ...but the share weighs the raw 8s: 1 / (1 + 8), not 1 / (1 + 1)
    assert h0.data_wait_share == pytest.approx(1 / 9)


def test_aggregator_incremental_tail_and_torn_lines(tmp_path):
    now = write_fleet(tmp_path, n_hosts=3, n_steps=10)
    agg = FleetAggregator(str(tmp_path))
    snap = agg.poll(now=now)
    assert snap.fleet["step_max"] == 9
    # append new complete records + one torn line
    path = tmp_path / "trace-p0.jsonl"
    with open(path, "a") as f:
        f.write(json.dumps({
            "schema_version": 1, "type": "span", "name": "compiled_step",
            "ts_s": 9.0, "dur_s": 0.01, "pid": 0, "tid": 1, "depth": 0,
            "step": 42,
        }) + "\n")
        f.write('{"type": "span", "name": "compi')  # crash mid-write
    snap = agg.poll(now=now)
    assert snap.fleet["step_max"] == 42
    # the torn line stays buffered, not dropped: completing it counts
    with open(path, "a") as f:
        f.write('led_step", "ts_s": 9.1, "dur_s": 0.01, "pid": 0, '
                '"step": 43}\n')
    snap = agg.poll(now=now)
    assert snap.fleet["step_max"] == 43


def test_aggregator_nan_host_health(tmp_path):
    now = write_fleet(tmp_path, nan_host=1)
    snap = read_fleet_snapshot(str(tmp_path), now=now)
    by_host = {h.host: h for h in snap.hosts}
    assert by_host[1].health["nonfinite_steps"] == 1
    assert by_host[1].health["last_anomaly"]["reason"] == "nonfinite"
    assert by_host[0].health["nonfinite_steps"] == 0
    assert snap.loss_series  # sparkline input survives aggregation


def test_host_skew_helper():
    assert host_skew({0: 1.0}) is None  # needs a fleet
    skew = host_skew({0: 1.0, 1: 1.0, 2: 1.0, 3: 4.0})
    assert skew["host"] == 3
    assert skew["median"] == 1.0
    assert skew["max_delta"] == pytest.approx(3.0)


# -- alert rules -----------------------------------------------------------

def _snap(hosts, *, fleet=None, wall_time=1000.0):
    return FleetSnapshot(
        wall_time=wall_time, run_dir="/tmp/x", hosts=hosts,
        fleet={"n_hosts": len(hosts), **(fleet or {})},
        stragglers=[h.host for h in hosts if h.straggler],
        lost=[h.host for h in hosts if h.lost],
    )


def _host(i, **kw):
    health = {"nonfinite_steps": 0, "grad_norm_spike": False}
    health.update(kw.pop("health", {}))
    return HostSnapshot(host=i, step=100, health=health, **kw)


def test_alert_rules_quiet_on_clean_snapshot():
    engine = AlertEngine(MonitorConfig())
    edges = engine.evaluate(_snap([_host(0), _host(1)]))
    assert edges == [] and engine.active() == []


def test_host_lost_fires_once_and_resolves():
    engine = AlertEngine(MonitorConfig())
    lost = _snap([_host(0), _host(1, lost=True, heartbeat_age_s=300.0)])
    edges = engine.evaluate(lost)
    assert [(a.rule, a.state, a.host) for a in edges] == [
        ("FLT001", "firing", 1)]
    assert edges[0].severity == "critical"
    # still lost: no duplicate edge, alert stays active
    assert engine.evaluate(lost) == []
    assert [a.rule for a in engine.active()] == ["FLT001"]
    # recovered: one resolved edge, active set drains
    edges = engine.evaluate(_snap([_host(0), _host(1)]))
    assert [(a.rule, a.state) for a in edges] == [("FLT001", "resolved")]
    assert engine.active() == []


def test_straggler_needs_persistence_unless_once():
    config = MonitorConfig(straggler_persist_windows=3)
    engine = AlertEngine(config)
    snap = _snap([_host(0), _host(1), _host(
        2, straggler=True, straggler_phases=["compiled_step"],
        phase_p50_s={"compiled_step": 0.03})])
    assert engine.evaluate(snap) == []      # window 1
    assert engine.evaluate(snap) == []      # window 2
    edges = engine.evaluate(snap)           # window 3: fires
    assert [(a.rule, a.host) for a in edges] == [("STR001", 2)]
    # --once mode: a single observation of a static run dir suffices
    once = AlertEngine(config, once=True)
    assert [a.rule for a in once.evaluate(snap)] == ["STR001"]


def test_numerics_rules():
    engine = AlertEngine(MonitorConfig())
    snap = _snap([
        _host(0, health={"nonfinite_steps": 2}),
        _host(1, health={"grad_norm_spike": True,
                         "last_grad_norm": 250.0}),
    ])
    rules = {(a.rule, a.host) for a in engine.evaluate(snap)}
    assert rules == {("NUM002", 0), ("NUM001", 1)}
    # NUM002 LATCHES: NaNs never un-happen, so it must stay active with
    # no bogus "resolved" record; the grad-spike trend rule does resolve
    snap2 = _snap([_host(0, health={"nonfinite_steps": 2}), _host(1)])
    edges = engine.evaluate(snap2)
    assert {(a.rule, a.state) for a in edges} == {("NUM001", "resolved")}
    assert [a.rule for a in engine.active()] == ["NUM002"]


def test_throughput_collapse_vs_rolling_baseline():
    engine = AlertEngine(MonitorConfig(steps_per_sec_collapse_frac=0.5))
    hosts = [_host(0), _host(1)]
    for _ in range(4):  # build the rolling baseline at 10 steps/s
        assert engine.evaluate(
            _snap(hosts, fleet={"steps_per_sec": 10.0})) == []
    edges = engine.evaluate(_snap(hosts, fleet={"steps_per_sec": 2.0}))
    assert [a.rule for a in edges] == ["THR001"]
    assert edges[0].host is None  # fleet-scoped
    # the baseline FREEZES while collapsed: a persistent collapse must
    # not be absorbed into the median and falsely self-resolve
    for _ in range(8):
        assert engine.evaluate(
            _snap(hosts, fleet={"steps_per_sec": 2.0})) == []
    assert [a.rule for a in engine.active()] == ["THR001"]
    # genuine recovery resolves it
    edges = engine.evaluate(_snap(hosts, fleet={"steps_per_sec": 10.0}))
    assert [(a.rule, a.state) for a in edges] == [("THR001", "resolved")]


def test_data_wait_and_checkpoint_rules(tmp_path):
    config = MonitorConfig(checkpoint_overdue_seconds=300.0)
    engine = AlertEngine(config, run_dir=str(tmp_path))
    snap = _snap(
        [_host(0, data_wait_share=0.8), _host(1)],
        fleet={"checkpoint_age_s": 1000.0, "checkpoint_step": 50},
    )
    rules = {a.rule for a in engine.evaluate(snap)}
    assert rules == {"DWT001", "CKP001"}
    # a run that NEVER checkpointed is the worst case: CKP001 must fire
    # off the run age when no checkpoint span exists at all
    never = AlertEngine(config)
    edges = never.evaluate(
        _snap([_host(0)], fleet={"run_age_s": 1000.0}))
    assert [a.rule for a in edges] == ["CKP001"]
    assert "no checkpoint recorded" in edges[0].message
    # the file action appended schema-versioned records
    records = read_alerts(str(tmp_path))
    assert {r["rule"] for r in records} == {"DWT001", "CKP001"}
    assert all(r["schema_version"] == ALERT_SCHEMA_VERSION
               and r["type"] == "alert" and r["state"] == "firing"
               and r["fix"] for r in records)


def test_alert_registry_shape():
    for rule_id, meta in ALERT_RULES.items():
        assert len(rule_id) == 6  # XXXnnn like the lint registry
        assert meta["severity"] in ("critical", "warning")
        assert meta["kind"] in ("threshold", "trend", "staleness")
        assert meta["title"] and meta["fix"]


# -- watch CLI -------------------------------------------------------------

def test_watch_once_json_schema(tmp_path, capsys):
    now = write_fleet(tmp_path, straggler_host=2, lost_host=3)
    del now
    rc = watch_main([str(tmp_path), "--once", "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1  # alerts firing -> nonzero for scripting
    assert report["schema_version"] == WATCH_SCHEMA_VERSION
    snap = report["snapshot"]
    assert snap["schema_version"] == 2
    assert len(snap["hosts"]) == 4
    assert snap["stragglers"] == [2] and snap["lost"] == [3]
    for h in snap["hosts"]:
        assert {"host", "step", "steps_per_sec", "phase_p50_s",
                "data_wait_share", "straggler", "lost",
                "health"} <= set(h)
    fired = {a["rule"] for a in report["alerts"]}
    assert fired == {"STR001", "FLT001"}
    # alerts.jsonl landed in the run dir (the file action default)
    assert {r["rule"] for r in read_alerts(str(tmp_path))} == fired


def test_watch_once_clean_run_exits_zero(tmp_path, capsys):
    write_fleet(tmp_path)
    rc = watch_main([str(tmp_path), "--once", "--json",
                     "--no-alerts-file"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert report["alerts"] == []
    assert not (tmp_path / "alerts.jsonl").exists()


def test_watch_once_dashboard_text(tmp_path, capsys):
    write_fleet(tmp_path, straggler_host=1)
    watch_main([str(tmp_path), "--once", "--no-alerts-file"])
    out = capsys.readouterr().out
    assert "fleet: 4 host(s)" in out
    assert "STRAGGLER" in out
    assert "STR001" in out
    assert "loss   |" in out  # sparkline from the health record


def test_watch_missing_run_dir(tmp_path, capsys):
    rc = watch_main([str(tmp_path / "nope"), "--once"])
    assert rc == 2


# -- Trainer wiring --------------------------------------------------------

def _short_config(tmp_path, **kw):
    from tpu_ddp.train.trainer import TrainConfig

    kw.setdefault("epochs", 2)
    return TrainConfig(
        synthetic_data=True,
        synthetic_size=512,
        per_shard_batch=8,
        model="netresdeep",
        n_chans1=4,
        n_blocks=1,
        prefetch_depth=0,
        log_every_epochs=1,
        telemetry_dir=str(tmp_path),
        telemetry_sinks="jsonl",
        **kw,
    )


def test_trainer_runs_exporter_during_run(tmp_path):
    """monitor_port=-1: the exporter binds an ephemeral port, serves
    /metrics with the run-meta labels WHILE Trainer.run is in flight,
    and is torn down with the other workers afterwards. Also covers the
    periodic counters_snapshot cadence on the same run."""
    from tpu_ddp.train.trainer import Trainer

    config = _short_config(
        tmp_path, epochs=4, monitor_port=-1, telemetry_snapshot_steps=2,
        watchdog_deadline_seconds=300.0,
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
    endpoint_path = tmp_path / "exporter-p0.json"
    try:
        deadline = time.time() + 60
        while not endpoint_path.exists():
            assert time.time() < deadline, "exporter file never appeared"
            assert not done.is_set() or endpoint_path.exists()
            time.sleep(0.02)
        with open(endpoint_path) as f:
            port = json.load(f)["port"]
        scraped = None
        while not done.is_set():
            try:
                status, body, _ = _get(port, "/metrics")
            except OSError:
                break
            if status == 200 and "tpu_ddp_train_steps_total" in body:
                scraped = body
                status_h, health, _ = _get(port, "/healthz")
                break
            time.sleep(0.02)
        assert scraped is not None, "never scraped a mid-run /metrics"
        assert f'run_id="{trainer.run_meta["run_id"]}"' in scraped
        assert 'strategy="dp"' in scraped and 'host="0"' in scraped
        assert status_h == 200 and json.loads(health)["status"] == "ok"
    finally:
        thread.join(timeout=120)
    assert done.is_set()
    # exporter released with the other workers
    assert trainer._exporter is None
    with pytest.raises(OSError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=1)
    # periodic counters snapshots landed in the JSONL trace
    with open(tmp_path / "trace-p0.jsonl") as f:
        records = [json.loads(line) for line in f if line.strip()]
    periodic = [r for r in records if r.get("type") == "counters"
                and r.get("name") == "counters_snapshot"]
    assert periodic, "no counters_snapshot records in the trace"
    assert periodic[0]["attrs"]["counters"]["train/steps"] >= 2
    trainer.close()


def test_trainer_port_zero_disables_exporter(tmp_path):
    from tpu_ddp.train.trainer import Trainer

    trainer = Trainer(_short_config(tmp_path, epochs=1, monitor_port=0))
    trainer.run()
    assert trainer._exporter is None
    assert not (tmp_path / "exporter-p0.json").exists()
    trainer.close()


def test_monitor_port_validation():
    from tpu_ddp.train.trainer import TrainConfig

    with pytest.raises(ValueError, match="monitor_port"):
        TrainConfig(monitor_port=-2).validate()
    with pytest.raises(ValueError, match="telemetry_snapshot_steps"):
        TrainConfig(telemetry_snapshot_steps=-1).validate()


def test_watch_on_real_trainer_run_dir(tmp_path, capsys):
    """End to end: a real (single-host) run dir aggregates cleanly —
    steps/sec present, no stragglers (no quorum). Which other alerts fire
    is the machine's business: under six test workers the input-wait rule
    (DWT001) has fired here on a loaded machine, and says nothing of
    aggregation."""
    from tpu_ddp.train.trainer import Trainer

    trainer = Trainer(_short_config(
        tmp_path, epochs=1, watchdog_deadline_seconds=300.0,
        telemetry_snapshot_steps=2))
    trainer.run()
    trainer.close()
    capsys.readouterr()  # drain the trainer's own log lines
    rc = watch_main([str(tmp_path), "--once", "--json",
                     "--no-alerts-file", "--stale-seconds", "3600"])
    report = json.loads(capsys.readouterr().out)
    assert rc == (1 if report["alerts"] else 0)  # watch's own contract
    hosts = report["snapshot"]["hosts"]
    assert len(hosts) == 1 and hosts[0]["host"] == 0
    assert hosts[0]["step"] is not None and hosts[0]["step"] > 0
    assert hosts[0]["phase_p50_s"].get("compiled_step") is not None
    assert hosts[0]["ended"] is True  # close() wrote the run_end marker
    assert report["snapshot"]["run_id"] == trainer.run_meta["run_id"]
    assert hosts[0]["steps_per_sec"] > 0
    assert report["snapshot"]["stragglers"] == []
    assert "STR001" not in {a.get("rule") for a in report["alerts"]}


# -- heartbeat read-back helpers ------------------------------------------

def test_read_heartbeat_and_age(tmp_path):
    path = tmp_path / "heartbeat-p0.json"
    assert read_heartbeat(str(path)) is None  # absent = no signal
    path.write_text('{"wall_time": 1000.0, "step": 7}')
    rec = read_heartbeat(str(path))
    assert rec["step"] == 7
    assert heartbeat_age_seconds(rec, now=1060.0) == pytest.approx(60.0)
    assert heartbeat_age_seconds(None) is None
    path.write_text('{"torn')  # mid-replace read
    assert read_heartbeat(str(path)) is None
