"""The trace reduction: interval arithmetic on synthetic planes, and the
recorded v5e slice under ``chipbench/testdata/`` against numbers checked by
hand when it was recorded (PR 23)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import xplane  # noqa: E402

TESTDATA = os.path.join(REPO, "chipbench", "testdata")


def test_union_length_and_subtract():
    spans = [(0, 10), (5, 15), (20, 30), (30, 35), (40, 40), (50, 45)]
    assert xplane.union(spans) == [(0, 15), (20, 35)]
    assert xplane.length(spans) == 30
    assert xplane.subtract([(0, 100)], spans) == [(15, 20), (35, 100)]
    assert xplane.subtract([(0, 10), (20, 30)], [(5, 25)]) == [
        (0, 5), (25, 30)]
    assert xplane.subtract([(0, 10)], []) == [(0, 10)]
    assert xplane.subtract([(0, 10)], [(0, 10)]) == []


def test_names():
    text = "%all-reduce-start.3 = (f32[64]{0}) all-reduce-start(f32[64] %x)"
    assert xplane.short_name(text) == "all-reduce-start.3"
    assert xplane.is_collective(xplane.short_name(text))
    assert xplane.is_collective("reduce-scatter.1")
    assert not xplane.is_collective("fusion.12")
    assert not xplane.is_collective("reduce.4")


def synthetic(chips=1):
    """Two steps a chip. Per step: compute [0,40), an all-reduce in flight
    [30,60) on the async line, of which [40,60) nothing else covers, then
    compute [60,70). Steps start at 0 and 100 (ns)."""
    devices = {}
    for chip in range(chips):
        ops, asyncs, modules = [], [], []
        for base in (0, 100):
            ops += [("fusion.1", base, base + 25),
                    ("fusion.2", base + 20, base + 40),   # overlaps fusion.1
                    ("fusion.3", base + 60, base + 70)]
            asyncs += [("all-reduce-start.1", base + 30, base + 60)]
            modules += [("jit_step(1)", base, base + 70)]
        devices[chip] = {xplane.OPS_LINE: ops, xplane.ASYNC_LINE: asyncs,
                         xplane.MODULES_LINE: modules}
    return {"devices": devices}


def test_busy_is_a_union_and_exposed_collective_is_what_compute_does_not_hide():
    chip = xplane.reduce_chip(synthetic()["devices"][0])
    assert chip["busy_ns"] == 2 * (40 + 10)       # not 25+20+10 summed
    assert chip["collective_ns"] == 2 * 30
    assert chip["collective_exposed_ns"] == 2 * 20
    assert chip["op_totals_ns"] == {"fusion.1": 50, "fusion.2": 40,
                                    "fusion.3": 20}
    assert chip["program_ends"] == [70, 170]


@pytest.mark.parametrize("chips", [1, 4])
def test_reduce_per_step_numbers_and_labelled_gaps(chips):
    # host clock in seconds; the device clock runs 1000 ns ahead of it
    spans = [("device_sync", (0 - 1000) / 1e9, (70 - 1000) / 1e9),
             ("data_wait", (72 - 1000) / 1e9, (90 - 1000) / 1e9),
             ("compiled_step", (90 - 1000) / 1e9, (101 - 1000) / 1e9),
             ("device_sync", (101 - 1000) / 1e9, (170 - 1000) / 1e9)]
    out = xplane.reduce(synthetic(chips), window_s=200e-9, dispatches=2,
                        t_open=-1000 / 1e9, host_spans=spans)
    assert out["chips"] == chips and out["steps"] == 2
    assert out["clock_aligned"] is True
    assert out["busy_s"] == pytest.approx(100e-9)
    assert out["device_step_ms"] == pytest.approx(50e-6)
    assert out["collective_ms"] == pytest.approx(30e-6)
    assert out["collective_exposed_ms"] == pytest.approx(20e-6)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(50e-9)]
    gaps = dict(out["idle_gaps"])
    # inside a step the device waits on the collective while the host sits
    # in its fence; between the steps the host is fetching the next batch
    assert gaps["device_sync"] == pytest.approx(40e-9)
    assert gaps["data_wait"] == pytest.approx(30e-9)
    # the window's tail after the last op: nothing of the host's covers it
    assert gaps["host_other"] == pytest.approx(30e-9)
    assert sum(gaps.values()) == pytest.approx(200e-9 - 100e-9)


def test_without_fences_that_pair_up_the_gaps_stay_unlabelled():
    out = xplane.reduce(synthetic(), window_s=200e-9, dispatches=2)
    assert out["clock_aligned"] is False
    assert [name for name, _ in out["idle_gaps"]] == ["host_other"]


#: the host's spans of ``synthetic()``'s two steps, the device clock 1000 ns
#: ahead of the host's: a loop that fences every step, and the same loop
#: without its fences (a step's completion is stamped off the main thread)
FENCED = [("device_sync", (0 - 1000) / 1e9, (70 - 1000) / 1e9),
          ("data_wait", (72 - 1000) / 1e9, (90 - 1000) / 1e9),
          ("compiled_step", (90 - 1000) / 1e9, (101 - 1000) / 1e9),
          ("device_sync", (101 - 1000) / 1e9, (170 - 1000) / 1e9)]
UNFENCED = [span for span in FENCED if span[0] != "device_sync"]


def test_fences_that_pair_give_the_median_to_the_nanosecond():
    """A fence a step: the offset is the median over the pairs, as before
    the window's closing fence could stand in, whatever that fence says."""
    ends = [70, 170]
    for t_close in (None, (170 - 1000) / 1e9, 5.0):
        assert xplane.clock_offset_ns(ends, FENCED, t_close) == 1000.0
    # an odd one out moves a median of three by nothing
    three = FENCED + [("device_sync", 0.0, (275 - 1000) / 1e9)]
    assert xplane.clock_offset_ns([70, 170, 270], three, 9.0) == 1000.0
    assert xplane.clock_offset_ns(ends, FENCED) == 1000.0


def test_without_a_fence_a_step_the_closing_fence_ties_the_clocks():
    """No ``device_sync`` spans, or another count of them than of programs:
    the last program's end on the device's clock is the window's closing
    fence on the host's."""
    t_close = (170 - 1000) / 1e9
    assert xplane.clock_offset_ns([70, 170], UNFENCED, t_close) == (
        pytest.approx(1000.0))
    assert xplane.clock_offset_ns([70, 170], FENCED[:1], t_close) == (
        pytest.approx(1000.0))
    assert xplane.clock_offset_ns([70, 170], UNFENCED) is None
    assert xplane.clock_offset_ns([], UNFENCED, t_close) is None
    # a window of 170 ns that closes as the last program ends
    out = xplane.reduce(synthetic(), window_s=170e-9, dispatches=2,
                        t_open=-1000 / 1e9, host_spans=UNFENCED)
    assert out["clock_aligned"] is True
    gaps = dict(out["idle_gaps"])
    # the gaps go to the main thread's spans: between the steps the host is
    # fetching the next batch; inside a step nothing of the host's covers
    # the wait on the collective now that no fence does
    assert gaps["data_wait"] == pytest.approx(30e-9)
    assert gaps["host_other"] == pytest.approx(40e-9)
    assert sum(gaps.values()) == pytest.approx(170e-9 - 100e-9)
    fenced = xplane.reduce(synthetic(), window_s=170e-9, dispatches=2,
                           t_open=-1000 / 1e9, host_spans=FENCED)
    assert dict(fenced["idle_gaps"]) == {
        "device_sync": pytest.approx(40e-9),
        "data_wait": pytest.approx(30e-9)}
    for key in ("busy_s", "device_step_ms", "device_ops", "collective_ms"):
        assert out[key] == fenced[key]


def test_a_span_on_a_second_thread_named_outside_host_spans_takes_no_gap():
    """The probe keeps the spans the harness names (``HOST_SPANS``): one that
    a second thread writes under another name, as long as a whole step, is
    not kept, so no idle gap can go to it."""
    import threading
    import time

    from chipbench.adapters import trainer as adapter

    probe = adapter.StepProbe(
        None, None, open_at=0, seconds=1.0, trace_dir=None,
        real_per_step=[1], names={}, counters=None)
    probe.t_open = time.perf_counter()
    assert "step_done" not in adapter.HOST_SPANS
    stamper = threading.Thread(target=probe.on_span,
                               args=("step_done", 70e-9))
    stamper.start()
    stamper.join(timeout=10)
    assert not stamper.is_alive()
    probe.on_span("data_wait", 18e-9)
    assert [name for name, _, _ in probe.spans] == ["data_wait"]
    # and the reduction labels by what it is given: the main thread's spans
    out = xplane.reduce(synthetic(), window_s=170e-9, dispatches=2,
                        t_open=-1000 / 1e9, host_spans=UNFENCED)
    assert {name for name, _ in out["idle_gaps"]} <= {
        name for name, _, _ in UNFENCED} | {"host_other"}


def test_a_trace_in_which_nothing_ran_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce({"devices": {}}, window_s=1.0, dispatches=1)
    empty = {"devices": {0: {xplane.OPS_LINE: []}}}
    with pytest.raises(ValueError):
        xplane.reduce(empty, window_s=1.0, dispatches=1)


def test_a_trace_with_another_count_of_programs_is_refused():
    # busy time is divided by the harness's dispatches: the trace's own
    # count of program executions has to be the same number
    with pytest.raises(ValueError, match="2 program executions"):
        xplane.reduce(synthetic(), window_s=200e-9, dispatches=3)


def test_no_collective_reads_nothing():
    planes = synthetic()
    planes["devices"][0][xplane.ASYNC_LINE] = []
    out = xplane.reduce(planes, window_s=200e-9, dispatches=2)
    assert out["collective_exposed_ms"] is None


def test_the_recorded_slice_gives_the_hand_checked_numbers():
    with open(os.path.join(TESTDATA, "recorded.json")) as f:
        expected = json.load(f)
    planes = xplane.load(os.path.join(TESTDATA, expected["file"]))
    assert sorted(planes["devices"]) == expected["chips"]
    lines = planes["devices"][0]
    assert len(lines[xplane.MODULES_LINE]) == expected["programs"]
    assert len(lines[xplane.OPS_LINE]) == expected["ops"]
    out = xplane.reduce(planes, window_s=expected["window_s"],
                        dispatches=expected["programs"])
    assert out["steps"] == expected["programs"]
    assert out["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert out["device_step_ms"] == pytest.approx(
        expected["device_step_ms"], rel=1e-9)
    assert out["device_ops"][0][0] == expected["top_op"]
    assert out["device_ops"][0][1] == pytest.approx(
        expected["top_op_s"], rel=1e-9)
    share = out["busy_s"] / expected["window_s"]
    assert share == pytest.approx(expected["busy_share"], rel=1e-6)
    assert 0 < share < 1
