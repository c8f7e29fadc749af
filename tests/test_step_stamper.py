"""The step stamper and what reads it (PR 39): a traced run is the run.

- the stamper alone, on fake arrays that become ready on command and a
  clock the test sets: spans in dispatch order, no two overlapping,
  ``ahead`` as dispatched, the starved counter equal to the gaps, ``close`` draining before it joins, a step that never
  completes not holding ``close`` past its stated timeout;
- a ``Trainer`` run with telemetry on in which ``jax.block_until_ready``
  and ``jax.device_get`` are counted by caller: none a step from
  ``_run_loop``;
- the live goodput gauge and ``ledger/stitch.py`` on a hand-written JSONL
  of a loop that runs ahead: a drain inside ``epoch_metrics_fetch`` reads
  as productive;
- the live monitor's data-wait share on such loops: a loader the device
  hides does not alert, one that starves the device does.

CPU only.
"""

import json
import sys
import threading
import time
import types

import pytest

from tpu_ddp.telemetry import Telemetry
from tpu_ddp.telemetry.registry import Registry
from tpu_ddp.telemetry.stamper import SPAN, STARVED, StepStamper


class CaptureSink:
    def __init__(self):
        self.events = []

    def emit(self, event):
        self.events.append(event)

    def close(self):
        pass


class SetClock:
    """A clock that reads what the test last set."""

    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t


class FakeArray:
    """Ready when the test says so."""

    def __init__(self, ready=False):
        self.ready = threading.Event()
        if ready:
            self.ready.set()


def wait_for(array):
    array.ready.wait()


def until(condition, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while not condition():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.001)


@pytest.fixture
def rig():
    sink, clock = CaptureSink(), SetClock()
    tel = Telemetry([sink], registry=Registry(), clock=clock)
    stamper = StepStamper(tel, wait_for, close_timeout_s=0.3)
    yield types.SimpleNamespace(sink=sink, clock=clock, tel=tel,
                                stamper=stamper)
    stamper.close()


def complete(rig, array, at):
    """The device finishes ``array`` at ``at`` on the rig's clock."""
    n = len(rig.sink.events)
    rig.clock.t = at
    array.ready.set()
    until(lambda: len(rig.sink.events) == n + 1)


def test_spans_tile_in_dispatch_order_with_ahead(rig):
    arrays = [FakeArray() for _ in range(4)]
    # three dispatches return before the first completes: the host leads
    for i, returned in enumerate((1.0, 1.1, 1.2)):
        rig.stamper.dispatched(10 + i, 1, returned, arrays[i])
    complete(rig, arrays[0], 2.0)
    complete(rig, arrays[1], 3.0)
    complete(rig, arrays[2], 3.5)
    # the fourth returns half a second after the device ran dry
    rig.stamper.dispatched(13, 1, 4.0, arrays[3])
    complete(rig, arrays[3], 5.0)

    spans = rig.sink.events
    assert [e.name for e in spans] == [SPAN] * 4
    assert [e.step for e in spans] == [10, 11, 12, 13]
    assert [(e.ts_s, e.ts_s + e.dur_s) for e in spans] == [
        (1.0, 2.0), (2.0, 3.0), (3.0, 3.5), (4.0, 5.0)]
    assert [e.attrs for e in spans] == [
        {"ahead": 1}, {"ahead": 2}, {"ahead": 3}, {"ahead": 1}]
    for a, b in zip(spans, spans[1:]):  # no two overlap
        assert a.ts_s + a.dur_s <= b.ts_s
    gaps = sum(b.ts_s - (a.ts_s + a.dur_s) for a, b in zip(spans, spans[1:]))
    assert rig.tel.counter(STARVED).value == pytest.approx(gaps)
    assert gaps == pytest.approx(0.5)
    hist = rig.tel.histogram("phase/" + SPAN)
    assert hist.count == 4 and hist.sum == pytest.approx(3.5)
    # written off the caller's thread
    assert {e.thread_id for e in spans} != {threading.get_ident() & 0xFFFF}


def test_a_fused_dispatch_carries_its_steps(rig):
    array = FakeArray()
    rig.stamper.dispatched(0, 8, 1.0, array)
    complete(rig, array, 2.0)
    assert rig.sink.events[0].attrs["steps"] == 8


def test_span_listeners_hear_device_step(rig):
    heard = []
    rig.tel.add_span_listener(lambda name, dur: heard.append((name, dur)))
    array = FakeArray()
    rig.stamper.dispatched(0, 1, 1.0, array)
    complete(rig, array, 1.25)
    assert heard == [(SPAN, 0.25)]


def test_close_drains_before_it_joins(rig):
    for i in range(50):
        rig.stamper.dispatched(i, 1, float(i), FakeArray(ready=True))
    rig.stamper.close()
    assert [e.step for e in rig.sink.events] == list(range(50))
    rig.stamper.close()  # idempotent


def test_a_step_that_never_completes_does_not_hang_close(rig):
    done, stuck = FakeArray(ready=True), FakeArray()
    rig.stamper.dispatched(0, 1, 1.0, done)
    rig.stamper.dispatched(1, 1, 1.1, stuck)
    t0 = time.monotonic()
    rig.stamper.close()  # the rig's timeout: 0.3 s
    assert 0.3 <= time.monotonic() - t0 < 3.0
    assert [e.step for e in rig.sink.events] == [0]
    stuck.ready.set()  # let the thread go


def test_a_step_that_fails_is_skipped_and_the_next_is_stamped():
    sink, clock = CaptureSink(), SetClock()
    tel = Telemetry([sink], registry=Registry(), clock=clock)

    def wait(array):
        if array == "bad":
            raise RuntimeError("the device said no")

    stamper = StepStamper(tel, wait)
    stamper.dispatched(0, 1, 1.0, "bad")
    stamper.dispatched(1, 1, 1.1, "good")
    stamper.close()
    assert [e.step for e in sink.events] == [1]
    # the failed step left the books: the next one leads by itself alone
    stamper2 = StepStamper(tel, wait)
    stamper2.dispatched(2, 1, 1.2, "bad")
    until(lambda: stamper2._completed == 1)
    stamper2.dispatched(3, 1, 1.3, "good")
    stamper2.close()
    assert sink.events[-1].step == 3 and sink.events[-1].attrs["ahead"] == 1


def test_a_fast_loop_loses_no_step_under_a_short_switch_interval():
    """The loop's thread and the stamper's share two counters and a
    queue: with the interpreter switching threads every 10 us, every
    dispatch is still stamped once, in order, and the books balance."""
    sink = CaptureSink()
    tel = Telemetry([sink], registry=Registry())
    stamper = StepStamper(tel, lambda array: None)
    n = 5000
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for step in range(n):
            stamper.dispatched(step, 1, tel.clock.now(), step)
        stamper.close()
    finally:
        sys.setswitchinterval(interval)
    assert not stamper._thread.is_alive()
    assert [e.step for e in sink.events] == list(range(n))
    assert all(1 <= e.attrs["ahead"] <= n for e in sink.events)
    for a, b in zip(sink.events, sink.events[1:]):
        assert a.ts_s + a.dur_s <= b.ts_s
    assert tel.histogram("phase/" + SPAN).count == n
    gaps = sum(b.ts_s - (a.ts_s + a.dur_s)
               for a, b in zip(sink.events, sink.events[1:]))
    assert tel.counter(STARVED).value == pytest.approx(gaps)


def test_emit_span_is_what_span_writes_on_exit():
    sink, clock = CaptureSink(), SetClock()
    tel = Telemetry([sink], registry=Registry(), clock=clock)
    tel.current_step = 7
    clock.t = 1.0
    with tel.span("compiled_step", steps=2) as nothing:
        assert nothing is None
        clock.t = 1.5
    tel.emit_span("compiled_step", 1.0, 1.5, attrs={"steps": 2})
    by_span, by_hand = sink.events
    assert by_span == by_hand
    assert (by_hand.ts_s, by_hand.dur_s, by_hand.step) == (1.0, 0.5, 7)
    assert tel.histogram("phase/compiled_step").count == 2
    from tpu_ddp.telemetry import NULL

    NULL.emit_span("compiled_step", 1.0, 1.5)  # disabled: nothing, no raise


def test_header_puts_the_trace_on_the_monotonic_clock(tmp_path):
    from tpu_ddp.telemetry import build_telemetry

    before = time.monotonic()
    tel = build_telemetry(str(tmp_path), "jsonl", jax_hooks=False)
    with tel.span("anything"):
        pass
    after = time.monotonic()
    tel.close()
    with open(tmp_path / "trace-p0.jsonl") as f:
        lines = [json.loads(ln) for ln in f]
    header = lines[0]
    assert before <= header["epoch_monotonic"] <= after
    span = next(r for r in lines if r["type"] == "span")
    assert before <= header["epoch_monotonic"] + span["ts_s"] <= after
    assert "epoch_unix" in header


# -- the Trainer's loop with telemetry on ----------------------------------

def test_run_loop_fences_no_step_with_telemetry_on(tmp_path, monkeypatch):
    """Every ``jax.block_until_ready`` and ``jax.device_get`` of a two-epoch
    run, by calling function and thread: the loop waits for no step, the
    stamper's thread for every one, and the epoch's losses come over in
    one fetch."""
    import jax

    from tpu_ddp.telemetry.registry import reset_default_registry
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    reset_default_registry()
    calls = []
    main = threading.get_ident()

    def counted(name, real):
        def wrapper(*args, **kwargs):
            calls.append((name, sys._getframe(1).f_code.co_name,
                          threading.get_ident() == main))
            return real(*args, **kwargs)
        return wrapper

    monkeypatch.setattr(jax, "block_until_ready",
                        counted("block_until_ready", jax.block_until_ready))
    monkeypatch.setattr(jax, "device_get",
                        counted("device_get", jax.device_get))
    trainer = Trainer(TrainConfig(
        synthetic_data=True, synthetic_size=320, per_shard_batch=8,
        epochs=2, n_chans1=4, n_blocks=1, log_every_epochs=1,
        telemetry_dir=str(tmp_path), telemetry_sinks="jsonl"))
    trainer.run()

    from_loop = [c for c in calls if c[1] == "_run_loop"]
    assert [c[0] for c in from_loop] == ["device_get", "device_get"]
    # off the main thread: the stamper, once a dispatch
    assert [c for c in calls if not c[2]] == [
        ("block_until_ready", "_run", False)] * 10
    with open(tmp_path / "trace-p0.jsonl") as f:
        records = [json.loads(ln) for ln in f]
    spans = [r for r in records if r["type"] == "span"]
    steps = [r for r in spans if r["name"] == SPAN]
    assert [r["step"] for r in steps] == list(range(10))
    loop_tid = {r["tid"] for r in spans if r["name"] == "compiled_step"}
    assert len(loop_tid) == 1 and {r["tid"] for r in steps}.isdisjoint(
        loop_tid)
    for a, b in zip(steps, steps[1:]):
        assert a["ts_s"] + a["dur_s"] <= b["ts_s"] + 1e-9
    dispatch_end = {r["step"]: r["ts_s"] + r["dur_s"] for r in spans
                    if r["name"] == "compiled_step"}
    for r in steps:  # a step starts no earlier than its dispatch returned
        assert r["ts_s"] >= dispatch_end[r["step"]] - 1e-9
        assert r["attrs"]["ahead"] >= 1
    final = [r for r in records if r["type"] == "counters"][-1]["attrs"]
    assert final["histograms"]["phase/" + SPAN]["count"] == 10
    assert STARVED in final["counters"]
    assert 0 < final["gauges"]["goodput/fraction"] <= 1
    assert not any(r["name"] == "device_sync" for r in spans)


# -- a loop that runs ahead, written by hand -------------------------------

LOOP, STAMPER, LOADER = 1, 2, 3


def ahead_trace():
    """One epoch of four steps on a device-bound loop: the host dispatches
    all four in 20 ms (the first call compiles for 1 s first), then sits
    in ``epoch_metrics_fetch`` while the device works through them, 0.5 s
    each. ``data/gather`` is a loader thread's span."""
    def span(name, ts, dur, tid, step=None, **attrs):
        r = {"schema_version": 1, "type": "span", "name": name,
             "ts_s": ts, "dur_s": dur, "pid": 0, "tid": tid, "depth": 0}
        if step is not None:
            r["step"] = step
        if attrs:
            r["attrs"] = attrs
        return r

    def counters(name, ts, compile_s):
        return {"schema_version": 1, "type": "counters", "name": name,
                "ts_s": ts, "pid": 0, "tid": LOOP, "attrs": {
                    "counters": {"train/images": 0.0},
                    "histograms": {"jax/compile_seconds": {
                        "count": 1, "sum": compile_s}}}}

    records = [
        {"schema_version": 1, "type": "header", "epoch_unix": 1000.0,
         "epoch_monotonic": 50.0, "pid": 0},
        counters("counters_baseline", 0.0, 0.0),
        span("data/gather", 0.0, 3.0, LOADER),
        # step 0: the compile is inside the dispatch, the device idle
        span("data_wait", 0.000, 0.002, LOOP, 0),
        span("h2d", 0.002, 0.001, LOOP, 0),
        span("compiled_step", 0.003, 1.002, LOOP, 0),
        span(SPAN, 1.005, 0.5, STAMPER, 0, ahead=1),
    ]
    t = 1.005
    for step in (1, 2, 3):  # dispatched while step 0 runs
        records += [
            span("data_wait", t, 0.002, LOOP, step),
            span("h2d", t + 0.002, 0.001, LOOP, step),
            span("compiled_step", t + 0.003, 0.002, LOOP, step),
            span(SPAN, 1.005 + 0.5 * step, 0.5, STAMPER, step,
                 ahead=step + 1),
        ]
        t += 0.005
    records += [
        # the drain: 1.02 -> 3.005, every second of it the device's
        span("epoch_metrics_fetch", t, 3.005 - t, LOOP, 4, epoch=1),
        {"schema_version": 1, "type": "instant", "name": "run_end",
         "ts_s": 3.01, "pid": 0, "tid": LOOP},
        counters("counters", 3.01, 1.0),
    ]
    return records


def test_the_ledger_reads_a_drain_as_productive(tmp_path):
    from tpu_ddp.ledger.stitch import stitch_run
    from tpu_ddp.ledger.taxonomy import build_ledger

    with open(tmp_path / "trace-p0.jsonl", "w") as f:
        for record in ahead_trace():
            f.write(json.dumps(record) + "\n")
    run = stitch_run(str(tmp_path))
    inc = run.incarnations[0]
    assert inc.steps == 4 and inc.exit == "clean"
    # the pool: four device steps and the one dispatch no step covers
    assert inc.buckets["step"] == pytest.approx(2.0 + 1.002)
    # hidden host work costs nothing: what is left is step 0's input
    assert inc.buckets["data_wait"] == pytest.approx(0.002)
    assert inc.buckets["host_overhead"] == pytest.approx(0.001)
    ledger = build_ledger(run)
    cats = ledger.categories
    assert cats["compile"] == pytest.approx(1.0)
    assert cats["productive"] == pytest.approx(2.002)
    assert cats["data_wait"] == pytest.approx(0.002)
    # h2d of step 0 and the 5 ms after the fetch: no 2 s of drain in it,
    # and none of the loader thread's 3 s
    assert cats["host_overhead"] == pytest.approx(0.006)
    assert sum(cats.values()) == pytest.approx(ledger.elapsed_s)
    assert ledger.goodput_fraction == pytest.approx(2.002 / 3.01)


def test_the_live_goodput_gauge_is_the_device_steps(monkeypatch):
    from tpu_ddp.train.trainer import Trainer

    tel = Telemetry([CaptureSink()], registry=Registry(), clock=SetClock())
    tel.histogram("phase/" + SPAN).record(7.0)  # an earlier Trainer's
    fake = types.SimpleNamespace(_goodput_baseline={
        "wall": 1000.0, "device": tel.histogram("phase/" + SPAN).sum})
    for r in ahead_trace():
        if r.get("name") == SPAN:
            tel.emit_span(SPAN, r["ts_s"], r["ts_s"] + r["dur_s"])
    monkeypatch.setattr(time, "time", lambda: 1003.01)
    Trainer._update_goodput_gauges(fake, tel)
    assert tel.gauge("goodput/productive_seconds").value == pytest.approx(2.0)
    assert tel.gauge("goodput/elapsed_seconds").value == pytest.approx(3.01)
    assert tel.gauge("goodput/fraction").value == pytest.approx(2.0 / 3.01)


def test_the_aggregator_windows_device_step_beside_the_loop(tmp_path):
    from tpu_ddp.monitor.aggregate import (
        DEVICE_PHASE,
        LOOP_PHASES,
        SNAPSHOT_SCHEMA_VERSION,
        FleetAggregator,
        MonitorConfig,
    )

    assert DEVICE_PHASE == SPAN and DEVICE_PHASE not in LOOP_PHASES
    with open(tmp_path / "trace-p0.jsonl", "w") as f:
        for record in ahead_trace():
            f.write(json.dumps(record) + "\n")
    snap = FleetAggregator(str(tmp_path), MonitorConfig()).poll(now=1004.0)
    host = snap.hosts[0]
    assert host.phase_p50_s[DEVICE_PHASE] == pytest.approx(0.5)
    # steps 1-3 waited for their input behind a busy device, and step 0's
    # wait came before the first device_step: nothing the device lacked
    assert host.data_wait_share == 0.0
    assert snap.to_json()["schema_version"] == SNAPSHOT_SCHEMA_VERSION == 2


def loop_trace(*, epochs, steps, data_wait_s, device_s, h2d_s=0.006,
               dispatch_s=0.011):
    """A loop with no queue cap but the epoch's: every step's input wait,
    transfer and dispatch back to back on the loop's thread, each step on
    the device as soon as it is dispatched and the device is free, and
    the epoch's fetch waiting for the last of them."""
    def span(name, ts, dur, tid, step):
        return {"schema_version": 1, "type": "span", "name": name,
                "ts_s": round(ts, 9), "dur_s": round(dur, 9), "pid": 0,
                "tid": tid, "depth": 0, "step": step}

    records = [{"schema_version": 1, "type": "header",
                "epoch_unix": 1000.0, "pid": 0}]
    t = device_free = 0.0
    for step in range(epochs * steps):
        for name, dur in (("data_wait", data_wait_s), ("h2d", h2d_s),
                          ("compiled_step", dispatch_s)):
            records.append(span(name, t, dur, LOOP, step))
            t += dur
        start = max(t, device_free)
        device_free = start + device_s
        records.append(span(SPAN, start, device_s, STAMPER, step))
        if (step + 1) % steps == 0:
            fetch = max(device_free - t, 0.0) + 0.001
            records.append(span("epoch_metrics_fetch", t, fetch, LOOP,
                                step + 1))
            t += fetch
    return records


def fleet_of(tmp_path, records):
    from tpu_ddp.monitor.aggregate import FleetAggregator, MonitorConfig
    from tpu_ddp.monitor.alerts import AlertEngine

    with open(tmp_path / "trace-p0.jsonl", "w") as f:
        for record in records:
            f.write(json.dumps(record) + "\n")
    snap = FleetAggregator(str(tmp_path), MonitorConfig()).poll(now=1100.0)
    fired = AlertEngine(MonitorConfig(), once=True).evaluate(snap)
    return snap.hosts[0], [a.rule for a in fired]


def test_a_loader_hidden_behind_the_device_does_not_alert(tmp_path):
    """dp4's shape: 30 ms of input wait a step behind 60 ms of device
    work, in epochs too short to fill the queue, so the dispatch holds no
    backpressure and the epoch's fetch takes the wait. Of the loop's own
    phases the wait is 64%; of the run's time it is the one wait an epoch
    that finds the device idle."""
    records = loop_trace(epochs=4, steps=25, data_wait_s=0.030,
                         device_s=0.060)
    host, fired = fleet_of(tmp_path, records)
    assert 0.030 / (0.030 + 0.006 + 0.011) > 0.5  # what the loop's sum read
    # three boundaries inside the device's reach, 30 ms each, over 6 s
    assert host.data_wait_share == pytest.approx(3 * 0.030 / 6.1, rel=0.05)
    assert "DWT001" not in fired


def test_a_loader_that_starves_the_device_alerts(tmp_path):
    records = loop_trace(epochs=2, steps=25, data_wait_s=0.100,
                         device_s=0.020)
    host, fired = fleet_of(tmp_path, records)
    # the device works through the first 20 ms of each 100 ms wait and
    # idles for the rest: 80 of each step's 117 ms
    assert host.data_wait_share == pytest.approx(0.080 / 0.117, rel=0.03)
    assert "DWT001" in fired


def test_a_trace_without_device_step_keeps_the_loops_own_share(tmp_path):
    records = [r for r in loop_trace(epochs=1, steps=10, data_wait_s=0.030,
                                     device_s=0.060)
               if r.get("name") != SPAN]
    host, _ = fleet_of(tmp_path, records)
    assert host.data_wait_share == pytest.approx(0.030 / 0.047)


def test_covered_and_uncovered():
    from tpu_ddp.telemetry.stamper import covered_s, uncovered_share

    starts, ends = [1.0, 2.0, 4.0], [2.0, 3.0, 5.0]
    assert covered_s(0.0, 1.0, starts, ends) == 0.0
    assert covered_s(1.5, 4.5, starts, ends) == pytest.approx(2.0)
    assert covered_s(3.0, 4.0, starts, ends) == 0.0
    assert covered_s(0.0, 9.0, starts, ends) == pytest.approx(3.0)
    device = list(zip(starts, ends))
    # [0.5, 1.5] starts before the first step and [4.5, 5.5] ends after
    # the last: neither is judged; [2.5, 3.5] lacks the device for 0.5 s
    waits = [(0.5, 1.5), (2.5, 3.5), (4.5, 5.5)]
    assert uncovered_share(waits, device) == pytest.approx(0.5 / 4.0)
    assert uncovered_share(waits, []) is None
    assert uncovered_share([], device) == 0.0


def test_the_ledger_says_so_of_a_trace_without_device_step(tmp_path):
    from tpu_ddp.ledger.stitch import stitch_run

    with open(tmp_path / "trace-p0.jsonl", "w") as f:
        for record in ahead_trace():
            if record.get("name") == SPAN:  # a trace from before PR 39
                record = dict(record, name="device_sync", tid=LOOP)
            f.write(json.dumps(record) + "\n")
    inc = stitch_run(str(tmp_path)).incarnations[0]
    assert any("no device_step span" in note for note in inc.notes)
    with open(tmp_path / "trace-p0.jsonl", "w") as f:
        for record in ahead_trace():
            f.write(json.dumps(record) + "\n")
    assert not stitch_run(str(tmp_path)).incarnations[0].notes


def test_analyze_counts_the_wait_the_device_did_not_hide(tmp_path):
    from tpu_ddp.analysis.explain import data_wait_share, measured_phases

    hidden, fenced = tmp_path / "hidden", tmp_path / "fenced"
    records = loop_trace(epochs=4, steps=25, data_wait_s=0.030,
                         device_s=0.060)
    for run_dir, keep in ((hidden, lambda r: True),
                          (fenced, lambda r: r.get("name") != SPAN)):
        run_dir.mkdir()
        with open(run_dir / "trace-p0.jsonl", "w") as f:
            for record in filter(keep, records):
                f.write(json.dumps(record) + "\n")
    assert data_wait_share(str(hidden), measured_phases(str(hidden))) == (
        pytest.approx(3 * 0.030 / 6.1, rel=0.05))
    assert data_wait_share(str(fenced), measured_phases(str(fenced))) == (
        pytest.approx(0.030 / 0.047))
