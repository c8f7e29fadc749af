"""Telemetry subsystem: spans, registry, sinks, watchdog, summarize CLI.

All CPU-only and fast (tier-1). The end-to-end test drives a real 5-step
Trainer run with the JSONL + Chrome sinks on and asserts the acceptance
contract: every step carries the data-wait / compiled-step / device-sync
phases, the Chrome trace is valid trace_event JSON, and `tpu-ddp trace
summarize` renders per-phase percentiles from the JSONL.
"""

import io
import json
import time

import numpy as np
import pytest

from tpu_ddp.telemetry import (
    ChromeTraceSink,
    HangWatchdog,
    JsonlTraceSink,
    Telemetry,
    TerminalSummarySink,
    build_telemetry,
)
from tpu_ddp.telemetry.events import SPAN, Clock
from tpu_ddp.telemetry.registry import Registry


class CaptureSink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass


def test_span_nesting_and_timing_monotonic():
    cap = CaptureSink()
    tel = Telemetry([cap], registry=Registry())
    with tel.span("outer", step=3):
        with tel.span("inner"):
            time.sleep(0.005)
    inner, outer = cap.events  # spans emit on EXIT: inner closes first
    assert inner.name == "inner" and outer.name == "outer"
    assert inner.depth == 1 and outer.depth == 0
    # containment: the inner span starts no earlier and ends no later
    assert inner.ts_s >= outer.ts_s
    assert inner.ts_s + inner.dur_s <= outer.ts_s + outer.dur_s + 1e-9
    assert inner.dur_s >= 0.005
    assert outer.dur_s >= inner.dur_s
    assert outer.step == 3
    # spans also feed the phase histograms
    assert tel.registry.histogram("phase/inner").count == 1


def test_registry_counter_gauge_histogram_aggregation():
    reg = Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        reg.histogram("h").record(v)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 2.5
    h = snap["histograms"]["h"]
    assert h["count"] == 5 and h["min"] == 1.0 and h["max"] == 100.0
    assert h["p50"] == 3.0
    assert h["p95"] == 100.0
    assert np.isclose(h["mean"], 22.0)


def test_jsonl_sink_schema_versioned_lines(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tel = Telemetry(
        [JsonlTraceSink(path, clock=Clock())], registry=Registry()
    )
    with tel.span("phase_a", step=1):
        pass
    tel.instant("marker", note="x")
    tel.emit_counters()
    tel.close()
    lines = [json.loads(ln) for ln in open(path)]  # every line valid JSON
    assert lines[0]["type"] == "header" and "epoch_unix" in lines[0]
    assert all(rec["schema_version"] == 1 for rec in lines)
    kinds = [rec["type"] for rec in lines[1:]]
    assert kinds.count("span") == 1
    assert "instant" in kinds and "counters" in kinds
    span = next(r for r in lines if r["type"] == "span")
    assert span["name"] == "phase_a" and span["step"] == 1
    assert span["dur_s"] >= 0


def test_chrome_trace_sink_valid_trace_event_json(tmp_path):
    path = str(tmp_path / "trace.trace.json")
    clock = Clock()
    tel = Telemetry(
        [ChromeTraceSink(path, process_index=2)],
        registry=Registry(), process_index=2, clock=clock,
    )
    with tel.span("compiled_step", step=7):
        time.sleep(0.002)
    tel.counter("train/steps").inc()
    tel.emit_counters()
    tel.close()
    doc = json.loads(open(path).read())  # loadable == Perfetto-loadable
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 1
    (x,) = xs
    assert x["name"] == "compiled_step"
    assert x["pid"] == 2
    assert isinstance(x["ts"], (int, float)) and x["ts"] >= 0
    assert x["dur"] >= 2000  # microseconds
    assert x["args"]["step"] == 7
    counters = [e for e in events if e["ph"] == "C"]
    assert any(c["name"] == "train/steps" for c in counters)


def test_terminal_summary_sink_table():
    out = io.StringIO()
    tel = Telemetry([TerminalSummarySink(stream=out)], registry=Registry())
    for _ in range(3):
        with tel.span("data_wait"):
            pass
    tel.close()
    table = out.getvalue()
    assert "data_wait" in table
    assert "p50_ms" in table and "p95_ms" in table


def test_null_telemetry_is_inert(tmp_path):
    tel = build_telemetry(None)
    assert not tel.enabled
    with tel.span("anything"):
        pass
    tel.instant("x")
    tel.close()  # no files, no errors


def test_build_telemetry_rejects_unknown_sink(tmp_path):
    with pytest.raises(ValueError, match="unknown telemetry sink"):
        build_telemetry(str(tmp_path), sinks="jsonl,bogus")


def test_watchdog_fires_on_stalled_step(tmp_path):
    dumps = []
    cap = CaptureSink()
    tel = Telemetry([cap], registry=Registry())
    wd = HangWatchdog(
        0.15,
        heartbeat_dir=str(tmp_path),
        telemetry=tel,
        on_hang=dumps.append,
        poll_interval=0.02,
    ).start()
    try:
        wd.on_step(step=12)
        time.sleep(0.5)  # the "stalled step"
    finally:
        wd.close()
    assert wd.fired and wd.fire_count == 1  # one dump per stall episode
    assert "thread" in dumps[0] and "tpu_ddp watchdog" in dumps[0]
    # heartbeat file records the last completed step
    hb = json.loads(open(tmp_path / "heartbeat-p0.json").read())
    assert hb["step"] == 12
    # hang forensics on disk + the telemetry instant
    assert (tmp_path / "hang-p0.log").exists()
    assert any(e.name == "watchdog_hang" for e in cap.events)
    assert tel.registry.counter("watchdog/hangs").value == 1


def test_watchdog_silent_on_healthy_run(tmp_path):
    wd = HangWatchdog(0.3, poll_interval=0.02).start()
    try:
        for step in range(10):
            wd.on_step(step)
            time.sleep(0.03)  # healthy cadence well inside the deadline
    finally:
        wd.close()
    assert not wd.fired


def _write_trace(path, spans):
    with open(path, "w") as f:
        f.write(json.dumps({"schema_version": 1, "type": "header",
                            "epoch_unix": 0.0, "pid": 0}) + "\n")
        for name, dur in spans:
            f.write(json.dumps({
                "schema_version": 1, "type": SPAN, "name": name,
                "ts_s": 0.0, "dur_s": dur, "pid": 0, "tid": 1, "depth": 0,
            }) + "\n")


def test_trace_summarize_cli(tmp_path, capsys):
    from tpu_ddp.cli.main import main as cli_main

    _write_trace(
        tmp_path / "trace-p0.jsonl",
        [("compiled_step", 0.010)] * 10 + [("compiled_step", 1.0)] * 10
        + [("data_wait", 0.002)] * 20,
    )
    rc = cli_main(["trace", "summarize", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "compiled_step" in out and "data_wait" in out
    assert "p50_ms" in out and "p95_ms" in out
    # p50 of compiled_step is the 10ms mode; p95 catches the 1s outlier
    row = next(ln for ln in out.splitlines()
               if ln.startswith("compiled_step"))
    cols = row.split()
    assert float(cols[4]) == pytest.approx(10.0)    # p50_ms
    assert float(cols[5]) == pytest.approx(1000.0)  # p95_ms


def test_trace_summarize_cli_missing_dir(tmp_path, capsys):
    from tpu_ddp.cli.main import main as cli_main

    rc = cli_main(["trace", "summarize", str(tmp_path / "nope")])
    assert rc == 2
    assert "trace summarize" in capsys.readouterr().err


def test_summarize_tolerates_torn_final_line(tmp_path):
    from tpu_ddp.telemetry.summarize import summarize

    path = tmp_path / "trace-p0.jsonl"
    _write_trace(path, [("step", 0.5)])
    with open(path, "a") as f:
        f.write('{"schema_version": 1, "type": "span", "na')  # crash torn
    out = summarize(str(tmp_path))
    assert "step" in out


def test_metric_logger_jsonl_schema_version(tmp_path, capsys):
    from tpu_ddp.metrics.logging import MetricLogger

    path = str(tmp_path / "metrics.jsonl")
    logger = MetricLogger(jsonl_path=path)
    logger.log(3, train_loss=1.25)
    # crash-safety contract: the record is on disk BEFORE close
    rec = json.loads(open(path).read().splitlines()[0])
    logger.close()
    assert rec["schema_version"] == 1
    assert rec["step"] == 3 and rec["train_loss"] == 1.25
    # the text format is unchanged by the schema field
    assert "[step 3] train_loss=1.25" in capsys.readouterr().out


@pytest.fixture(scope="module")
def telemetry_run(tmp_path_factory):
    """One 5-step CPU training run with JSONL+Chrome sinks + watchdog on
    (shared across the end-to-end assertions below)."""
    from tpu_ddp.telemetry.registry import reset_default_registry
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    # the counters registry is process-wide: whatever an earlier file of
    # this worker left in it is not this run's
    reset_default_registry()
    run_dir = tmp_path_factory.mktemp("telemetry_run")
    cfg = TrainConfig(
        synthetic_data=True,
        synthetic_size=320,   # 8 devices * per_shard 8 * 5 steps
        per_shard_batch=8,
        epochs=1,
        n_chans1=4,
        n_blocks=1,
        log_every_epochs=1,
        telemetry_dir=str(run_dir),
        telemetry_sinks="jsonl,chrome",
        watchdog_deadline_seconds=300.0,  # must stay silent
    )
    trainer = Trainer(cfg)
    trainer.run()
    return run_dir


def test_trainer_emits_phase_spans_per_step(devices, telemetry_run):
    records = [json.loads(ln)
               for ln in open(telemetry_run / "trace-p0.jsonl")]
    spans = [r for r in records if r["type"] == "span"]
    by_step = {}
    for s in spans:
        if s["name"] in ("data_wait", "compiled_step", "device_step"):
            by_step.setdefault(s["step"], set()).add(s["name"])
    # acceptance: every one of the 5 steps carries all three phases
    full = {s for s, names in by_step.items()
            if names >= {"data_wait", "compiled_step", "device_step"}}
    assert len(full) == 5, by_step
    # the counters snapshot saw all 5 steps and the recompile counter moved
    counters = [r for r in records if r["type"] == "counters"][-1]
    assert counters["attrs"]["counters"]["train/steps"] == 5
    assert counters["attrs"]["counters"].get("jax/compilations", 0) > 0
    # watchdog stayed silent on the healthy run
    assert not any(r["name"] == "watchdog_hang" for r in records
                   if r["type"] == "instant")
    hb = json.loads(open(telemetry_run / "heartbeat-p0.json").read())
    assert hb["step"] == 5


def test_trainer_chrome_trace_perfetto_loadable(devices, telemetry_run):
    doc = json.loads(open(telemetry_run / "trace-p0.trace.json").read())
    events = doc["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert {"data_wait", "compiled_step", "device_step"} <= {
        e["name"] for e in xs
    }
    for e in xs:
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0


def test_trainer_run_dir_summarizes(devices, telemetry_run, capsys):
    from tpu_ddp.cli.main import main as cli_main

    assert cli_main(["trace", "summarize", str(telemetry_run)]) == 0
    out = capsys.readouterr().out
    for phase in ("data_wait", "compiled_step", "device_step"):
        assert phase in out


def test_checkpoint_completion_side_telemetry(tmp_path, devices):
    """PR-3 satellite: the ``checkpoint`` span only ever covered save
    INITIATION (orbax saves are async) — completion must be accounted too:
    ``checkpoint/io_seconds`` + ``checkpoint/completed`` land when the
    wait barrier observes the background IO finishing, and the barrier
    itself is traced as a ``checkpoint_wait`` span."""
    from tpu_ddp.checkpoint import Checkpointer
    from tpu_ddp.telemetry.registry import reset_default_registry

    reset_default_registry()
    tel = build_telemetry(str(tmp_path / "run"), sinks="jsonl")
    ck = Checkpointer(str(tmp_path / "ck"), telemetry=tel)
    state = {"w": np.arange(8.0, dtype=np.float32)}
    ck.save(1, state)            # async: completion not yet observed
    assert len(ck._pending) == 1
    ck.wait_until_finished()
    assert ck._pending == []
    assert tel.registry.counter("checkpoint/saves").value == 1
    assert tel.registry.counter("checkpoint/completed").value == 1
    assert tel.registry.counter("checkpoint/io_seconds").value > 0
    ck.save(2, state, wait=True)  # sync saves self-account
    assert tel.registry.counter("checkpoint/completed").value == 2
    ck.close()
    tel.close()
    records = [json.loads(ln)
               for ln in open(tmp_path / "run" / "trace-p0.jsonl")]
    spans = {r["name"] for r in records if r["type"] == "span"}
    assert "checkpoint" in spans and "checkpoint_wait" in spans


def test_compilation_cache_counters(compile_cache, devices):
    """PR-3 satellite: with the persistent compilation cache enabled
    (``enable_compile_cache``, every entry point's first call), cache
    traffic surfaces as jax/cache/* counters in the default registry —
    what `trace summarize` prints in its counters snapshot — so warm
    starts are measurable, not vibes."""
    import jax

    from tpu_ddp.parallel.runtime import enable_compile_cache
    from tpu_ddp.telemetry.jax_hooks import install_jax_hooks
    from tpu_ddp.telemetry.registry import (
        default_registry,
        reset_default_registry,
    )

    try:
        assert enable_compile_cache() == compile_cache
        # jax only caches compiles of 1 s and more; CPU test compiles are
        # sub-ms, so drop the floor to force cache traffic here
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        reset_default_registry()
        assert install_jax_hooks()
        f = jax.jit(lambda x: x * 3 + 1)
        f(np.ones((16,), np.float32))          # cold: cache_misses
        g = jax.jit(lambda y: y * 3 + 1)       # identical HLO: cache_hits
        g(np.ones((16,), np.float32))
        snap = default_registry().snapshot()["counters"]
        assert snap.get("jax/cache/cache_misses", 0) >= 1
        assert snap.get("jax/cache/cache_hits", 0) >= 1
    finally:
        reset_default_registry()


def test_watchdog_heartbeat_freshness_contract(tmp_path):
    """The heartbeat file contract the fleet monitor builds on: every
    beat refreshes the file (modulo the 1-write/sec rate limit), the
    record carries the last completed step + wall time, and the
    staleness predicates flip exactly at the configured deadline."""
    from tpu_ddp.telemetry.watchdog import heartbeat_age_seconds, read_heartbeat

    wd = HangWatchdog(0.3, heartbeat_dir=str(tmp_path), poll_interval=10.0)
    path = tmp_path / "heartbeat-p0.json"

    wd.on_step(step=1)
    rec1 = read_heartbeat(str(path))
    assert rec1["step"] == 1 and rec1["pid"] > 0
    assert heartbeat_age_seconds(rec1) < 5.0

    # within the rate limit the file does NOT advance (atomic writes are
    # throttled to 1/sec so a hot step loop can't thrash the filesystem)
    wd.on_step(step=2)
    assert read_heartbeat(str(path))["step"] == 1
    # past the limiter it must advance (simulate >1s elapsing)
    wd._last_file_write -= 2.0
    wd.on_step(step=3)
    assert read_heartbeat(str(path))["step"] == 3

    # freshness predicates: fresh now, stale exactly past the deadline
    assert wd.seconds_since_beat() < 0.3 and not wd.is_stale()
    wd._last_beat -= 0.5  # no beat for 0.5s > 0.3s deadline
    assert wd.is_stale()
    wd.on_step(step=4)  # a beat re-arms freshness
    assert not wd.is_stale()

    # stop() force-flushes the FINAL step past the rate limiter
    wd.on_step(step=5)
    wd.close()
    assert read_heartbeat(str(path))["step"] == 5


def test_watchdog_staleness_fires_at_deadline_not_before(tmp_path):
    wd = HangWatchdog(0.25, poll_interval=0.02).start()
    try:
        wd.on_step(0)
        time.sleep(0.15)  # inside the deadline: silent and fresh
        assert not wd.fired and not wd.is_stale()
        time.sleep(0.25)  # now past it: predicate and dump agree
        assert wd.is_stale()
        assert wd.fired
    finally:
        wd.close()


def _write_multihost_traces(tmp_path, p50s_ms):
    for host, ms in enumerate(p50s_ms):
        with open(tmp_path / f"trace-p{host}.jsonl", "w") as f:
            f.write(json.dumps({"schema_version": 1, "type": "header",
                                "epoch_unix": 0.0, "pid": host}) + "\n")
            for step in range(10):
                f.write(json.dumps({
                    "schema_version": 1, "type": SPAN,
                    "name": "compiled_step", "ts_s": step * 0.1,
                    "dur_s": ms / 1e3, "pid": host, "tid": 1, "depth": 0,
                    "step": step,
                }) + "\n")


def test_trace_summarize_multihost_skew_line(tmp_path):
    """Satellite: a multihost run dir summarizes every trace-p<i>.jsonl
    AND names the skewed host (max p50 delta vs the fleet median)."""
    from tpu_ddp.telemetry.summarize import summarize

    _write_multihost_traces(tmp_path, [10.0, 10.0, 10.0, 31.0])
    out = summarize(str(tmp_path))
    assert "per-host skew: compiled_step" in out
    assert "host 3" in out
    assert "21.00ms" in out  # 31ms vs the 10ms fleet median

    # single-host dirs stay skew-line-free (nothing to compare)
    solo = tmp_path / "solo"
    solo.mkdir()
    _write_multihost_traces(solo, [10.0])
    assert "per-host skew" not in summarize(str(solo))


def test_summarize_prefers_last_periodic_snapshot(tmp_path):
    """Satellite: a killed run's newest counters record is a periodic
    ``counters_snapshot`` — the summary shows it (with its step) instead
    of pretending there was a clean final snapshot."""
    from tpu_ddp.telemetry.summarize import summarize

    with open(tmp_path / "trace-p0.jsonl", "w") as f:
        f.write(json.dumps({"schema_version": 1, "type": "header",
                            "epoch_unix": 0.0, "pid": 0}) + "\n")
        f.write(json.dumps({
            "schema_version": 1, "type": SPAN, "name": "compiled_step",
            "ts_s": 0.0, "dur_s": 0.01, "pid": 0, "tid": 1, "depth": 0,
        }) + "\n")
        for step, steps_total in ((50, 50), (100, 100)):
            f.write(json.dumps({
                "schema_version": 1, "type": "counters",
                "name": "counters_snapshot", "ts_s": float(step),
                "pid": 0, "tid": 1, "step": step,
                "attrs": {"counters": {"train/steps": steps_total},
                          "gauges": {}, "histograms": {}},
            }) + "\n")
        # no final "counters" record: the run was SIGKILLed here
    out = summarize(str(tmp_path))
    assert "last periodic snapshot @ step 100" in out
    assert "did not shut down cleanly" in out
    assert "train/steps = 100" in out


def test_telemetry_periodic_snapshot_event_name():
    """Telemetry.emit_counters(name=...) labels the record so readers
    can tell periodic tails from clean-shutdown snapshots."""
    cap = CaptureSink()
    tel = Telemetry([cap], registry=Registry())
    tel.count("train/steps", 2)
    tel.emit_counters(name="counters_snapshot")
    tel.emit_counters()
    assert [e.name for e in cap.events] == ["counters_snapshot", "counters"]
    assert all(e.kind == "counters" for e in cap.events)
