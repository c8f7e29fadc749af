"""``chipbench/step_spans.py`` and the three readers of the program's
``device_step`` spans (PR 39): ``device_starved_ms``, ``host_lead_steps``,
``step_complete_ms_p95``. On a hand-written trace JSONL (two epochs, one
boundary gap, and the dispatch that closes the window, whose call holds the
probe's fence), on the rule that the slice is the last ``dispatches`` spans
when set-up's come first, on everything a record may lack (None, nothing
raised), through the harness with the shipped ``BENCHMARK.json``, and on a
real ``Trainer``'s own JSONL: the tiny cell's traced run through the shipped
adapter on the CPU."""

import json
import os
import sys
import time
import types

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny  # noqa: E402
from chipbench import run as harness  # noqa: E402
from chipbench import scopes, step_spans  # noqa: E402

NAMES = ("device_starved_ms", "host_lead_steps", "step_complete_ms_p95")
EPOCH, STEP_S, BOUNDARY_S = 12, 0.050, 0.030


def reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "step_spans_test_" + name)


def span(name, ts, dur, tid=2, step=None, **attrs):
    record = {"schema_version": 1, "type": "span", "name": name,
              "ts_s": round(ts, 9), "dur_s": round(dur, 9), "pid": 0,
              "tid": tid, "depth": 0, "attrs": attrs}
    if step is not None:
        record["step"] = step
    return record


def slice_spans(t0=100.0, first_step=98):
    """Two epochs of twelve 50 ms steps: within an epoch the host is in
    front and the spans tile; the second epoch's first dispatch returns
    30 ms after the first epoch's last step completed."""
    out, t, step = [], t0, first_step
    for epoch in range(2):
        for i in range(EPOCH):
            ahead = 1 if i == 0 else min(i + 1, 8)
            out.append(span("device_step", t, STEP_S, step=step,
                            ahead=ahead))
            t += STEP_S
            step += 1
        t += BOUNDARY_S
    return out


def closing(spans, *, called_after_s, held_s=12.0):
    """The dispatch that closes the window, as a traced run leaves it: its
    call starts ``called_after_s`` after the last completion (before it,
    where negative: the queue was full) and returns ``held_s`` later, after
    the probe's fence and the profiler's stop, so its ``device_step`` starts
    then, with nothing else in flight."""
    last = spans[-1]
    end = last["ts_s"] + last["dur_s"]
    step = last["step"] + 1
    called = end + called_after_s
    return [span("compiled_step", called, held_s, tid=1, step=step),
            span("device_step", called + held_s, 0.001, step=step, ahead=1)]


def write_run(tmp_path, spans, *, dispatches=None, name="trace-p0.jsonl",
              called_after_s=-1.5):
    """A traced run's record and files: ``spans`` and, unless ``dispatches``
    says how many of them are the slice's, the closing dispatch after
    them."""
    if dispatches is None:
        dispatches = 2 * EPOCH + 1
        spans = spans + closing(
            [r for r in spans if r["name"] == "device_step"],
            called_after_s=called_after_s)
    tel = tmp_path / "cell" / "telemetry"
    tel.mkdir(parents=True, exist_ok=True)
    header = {"schema_version": 1, "type": "header", "epoch_unix": 1.0,
              "epoch_monotonic": 2.0, "pid": 0}
    with open(tel / name, "w") as f:
        for record in [header] + spans:
            f.write(json.dumps(record) + "\n")
    record = {"trace_dir": str(tel.parent / "profile"),
              "dispatches": dispatches, "steps": dispatches,
              "steps_per_call": 1}
    return types.SimpleNamespace(record=record, trace=None)


def read_all(run):
    return {name: reader(name).read(run) for name in NAMES}


def test_two_epochs_and_one_boundary_gap(tmp_path, capsys):
    """25 dispatches: two epochs and the one that closes the window, whose
    call started while the queue was full."""
    out = read_all(write_run(tmp_path, slice_spans()))
    # one gap of 30 ms over 25 optimizer steps
    assert out["device_starved_ms"] == pytest.approx(30.0 / 25)
    # ahead over an epoch: 1, 2, ..., 8, 8, 8, 8, 8, twice; not the
    # closing dispatch's 1
    assert out["host_lead_steps"] == 6.5
    # 23 intervals, 22 of 50 ms and the boundary's 80 (not the 12 s to the
    # closing stamp): the 95th percentile lies under the one outlier
    assert out["step_complete_ms_p95"] == pytest.approx(50.0)
    printed = capsys.readouterr().out
    assert "the last 25 are the slice's" in printed
    assert "device starved: " in printed and "in 24 gaps" in printed


def test_the_gap_before_the_closing_dispatch_counts_up_to_its_call(tmp_path):
    """A decoder's slice: the epoch's fetch outlasts the three seconds and
    the next epoch's first dispatch closes the window. Its call starts 20 ms
    after the last completion and returns 12 s later: 20 ms count."""
    run = write_run(tmp_path, slice_spans(), called_after_s=0.020)
    piece = step_spans.of_run(run)
    assert len(piece.steps) == 24
    assert piece.closing_gap_s == pytest.approx(0.020)
    out = read_all(run)
    assert out["device_starved_ms"] == pytest.approx((30.0 + 20.0) / 25)
    assert out["host_lead_steps"] == 6.5
    assert out["step_complete_ms_p95"] == pytest.approx(50.0)
    # a program whose closing call cannot be found: nothing is made up
    spans = [r for r in slice_spans() + closing(slice_spans(),
                                                called_after_s=0.020)
             if r["name"] == "device_step"]
    bare = write_run(tmp_path / "bare", spans, dispatches=25)
    assert step_spans.of_run(bare).closing_gap_s == 0.0


def test_the_tail_sees_a_boundary_in_every_fifth_interval(tmp_path):
    """Six epochs of four steps: 23 intervals, five of them 80 ms."""
    spans, t = [], 5.0
    for epoch in range(6):
        for i in range(4):
            spans.append(span("device_step", t, STEP_S, step=len(spans),
                              ahead=i + 1))
            t += STEP_S
        t += BOUNDARY_S
    out = read_all(write_run(tmp_path, spans))
    assert out["device_starved_ms"] == pytest.approx(5 * 30.0 / 25)
    assert out["host_lead_steps"] == 2.5
    assert out["step_complete_ms_p95"] == pytest.approx(80.0)


def test_set_up_comes_first_and_is_left_out(tmp_path):
    """Epoch 1 and the check's steps are stamped too, long before the
    window and at another cadence: the slice is the last ``dispatches``."""
    setup = [span("device_step", 1.0 + 2.0 * i, 1.5, step=i, ahead=1)
             for i in range(15)]
    other = [span("compiled_step", 100.0 + STEP_S * i, 0.002, tid=1,
                  step=98 + i) for i in range(24)]
    run = write_run(tmp_path, setup + other + slice_spans())
    piece = step_spans.of_run(run)
    assert len(piece.steps) == 24 and piece.steps[0].start == 100.0
    assert [s.id for s in piece.steps] == list(range(98, 122))
    assert read_all(run)["device_starved_ms"] == pytest.approx(30.0 / 25)
    # written out of order: sorted by start
    shuffled = (closing(slice_spans(), called_after_s=-1.5)
                + slice_spans()[::-1] + setup)
    again = write_run(tmp_path / "again", shuffled, dispatches=25)
    assert step_spans.of_run(again) == piece


def test_fused_dispatches_count_their_steps(tmp_path):
    spans = [span("device_step", 1.0 + 0.4 * i, 0.4, step=8 * i, ahead=2,
                  steps=8) for i in range(24)]
    run = write_run(tmp_path, spans)
    run.record.update(steps=25 * 8, steps_per_call=8)
    out = read_all(run)
    assert out["host_lead_steps"] == 16.0
    assert out["step_complete_ms_p95"] == pytest.approx(50.0)
    assert out["device_starved_ms"] == 0.0  # rounding is no gap


def test_nothing_to_read_is_none_and_raises_nothing(tmp_path):
    none = dict.fromkeys(NAMES)
    # the contract test's bare record, an untraced run's
    bare = types.SimpleNamespace(record={"steps": 7, "examples": 56},
                                 trace=None)
    assert read_all(bare) == none
    # a trace directory with no telemetry beside it
    lost = types.SimpleNamespace(
        record={"trace_dir": str(tmp_path / "nowhere" / "profile"),
                "dispatches": 24, "steps": 24}, trace=None)
    assert read_all(lost) == none
    # the parent's program: every span but ``device_step``
    parent = [span(name, 1.0 + i, 0.5, tid=1, step=i) for i in range(25)
              for name in ("data_wait", "h2d", "compiled_step",
                           "device_sync")]
    assert read_all(write_run(tmp_path / "parent", parent,
                              dispatches=25)) == none
    # fewer stamped steps than dispatches: another run's file
    short = write_run(tmp_path / "short", slice_spans()[:20], dispatches=25)
    assert read_all(short) == none
    # one dispatch: no two completions
    one = write_run(tmp_path / "one", slice_spans(), dispatches=1)
    assert read_all(one) == none
    # a short slice has no tail, and says so by leaving the metric out
    few = read_all(write_run(tmp_path / "few", slice_spans(), dispatches=17))
    assert few["step_complete_ms_p95"] is None
    assert few["device_starved_ms"] is not None
    assert few["host_lead_steps"] is not None


def test_the_newest_incarnation_is_the_runs(tmp_path):
    write_run(tmp_path, [span("device_step", 1.0 + i, 1.0, step=i, ahead=1)
                         for i in range(24)])
    run = write_run(tmp_path, slice_spans(), name="trace-p0.i1.jsonl")
    assert read_all(run)["host_lead_steps"] == 6.5


def test_the_harness_reports_them_where_the_benchmark_lists_them(tmp_path):
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES:
        assert by_name[name]["layer"] == "run loop"
        assert by_name[name]["source"] == "program_span"
        assert by_name[name]["moves"] == "images_per_s_per_chip"
    assert "workloads" not in by_name["device_starved_ms"]
    # a canary with the traffic's ceiling: only where the epoch outlasts
    # the device's queue does it say anything before the host is behind
    assert by_name["host_lead_steps"]["workloads"] == ["resnet50-cifar.b512"]
    assert by_name["step_complete_ms_p95"]["workloads"] == [
        "resnet50-cifar.b512", "resnet50-cifar.dp4"]
    record = write_run(tmp_path, slice_spans()).record
    for cell in (c["name"] for c in bench["workloads"]):
        out = harness.per_layer(bench, cell, [harness.HERE], record, None)
        listed = cell.startswith("resnet50-cifar")
        assert ("step_complete_ms_p95" in out) == listed, cell
        assert out["device_starved_ms"] == {
            "value": pytest.approx(1.2), "unit": "ms"}
        assert out.get("host_lead_steps") == (
            {"value": 6.5, "unit": "steps"}
            if cell == "resnet50-cifar.b512" else None), cell


def test_the_helper_imports_nothing_of_the_program():
    with open(os.path.join(harness.HERE, "step_spans.py")) as f:
        text = f.read()
    assert "tpu_ddp" not in text.replace("``tpu_ddp/telemetry/stamper.py``",
                                         "")


@pytest.mark.parametrize("n", [20, 21, 83])
def test_the_tail_is_the_end_to_end_tails_percentile(tmp_path, n):
    """``step_complete_ms_p95`` takes ``statistics.quantiles``' 19th of 20
    inclusive cuts: the harness's own ``percentile(..., 95)``."""
    t, spans = 10.0, []
    for i in range(n + 2):  # the last is the closing dispatch's
        dur = 0.040 + 0.001 * ((i * 7) % 13)
        spans.append(span("device_step", t, dur, step=i, ahead=2))
        t += dur
    run = write_run(tmp_path, spans, dispatches=n + 2)
    intervals = [s["dur_s"] * 1e3 for s in spans[1:-1]]
    assert reader("step_complete_ms_p95").read(run) == pytest.approx(
        harness.percentile(intervals, 95), rel=1e-9)


# -- a real Trainer's own spans --------------------------------------------

_CONFIG = ("jax_compilation_cache_dir",
           "jax_persistent_cache_min_compile_time_secs",
           "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def keep_jax_config():
    """The harness points jax's cache at the checkout; put it back."""
    saved = {k: getattr(jax.config, k) for k in _CONFIG}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_the_tiny_cells_traced_run_stamps_every_dispatch(tmp_path, capsys,
                                                         keep_jax_config):
    bench_path, roots = chipbench_tiny.write(str(tmp_path))
    bench = harness.load_json(bench_path)
    loaded = harness.load_cell(bench, chipbench_tiny.CELL,
                               roots + [harness.HERE])
    ctx = types.SimpleNamespace(
        cell=loaded["cell"], config=loaded["config"],
        traffic=loaded["traffic"], reference=loaded["reference"],
        dataset=loaded["dataset"], seed=2147483659, seconds=0.3, trace=True,
        counters=harness.Counters().install(),
        scratch_dir=str(tmp_path / "runs"), t_start=time.perf_counter(),
        say=harness.say)
    record = loaded["adapter"].run(ctx)
    run = types.SimpleNamespace(record=record, trace=None)
    piece = step_spans.of_run(run)
    assert len(piece.steps) == record["dispatches"] - 1 > 0
    for a, b in zip(piece.steps, piece.steps[1:]):  # no two overlap
        assert a.end <= b.start + 1e-9
    # every dispatch of the run is stamped, set-up's before the slice's
    path = scopes.newest(scopes.telemetry_dir(record))["trace"]
    records = scopes.read_jsonl(path)
    stamped = [r for r in records if r.get("name") == "device_step"]
    dispatched = [r for r in records if r.get("name") == "compiled_step"]
    assert len(stamped) == len(dispatched) > record["dispatches"]
    assert [r["step"] for r in stamped] == [r["step"] for r in dispatched]
    assert piece.steps[-1].id == stamped[-2]["step"]
    assert "epoch_monotonic" in records[0]
    # the closing call holds the probe's fence and the profiler's stop: its
    # step is stamped when it returns, after everything else
    assert stamped[-1]["ts_s"] >= dispatched[-1]["ts_s"] + dispatched[-1][
        "dur_s"] - 1e-9
    # the fence is gone from the loop: no span of it, none in the record
    assert not any(r.get("name") == "device_sync" for r in records)
    assert {name for name, _, _ in record["host_spans"]} <= {
        "data_wait", "h2d", "compiled_step", "epoch_metrics_fetch"}
    out = read_all(run)
    assert out["device_starved_ms"] >= 0.0
    assert out["host_lead_steps"] >= 1.0
    assert out["device_starved_ms"] == pytest.approx(
        sum(step_spans.gaps_s(piece)) * 1e3 / record["steps"])
