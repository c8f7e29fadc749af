"""The traced slice's ``device_step`` spans, as the program stamped them.

With telemetry on the program stamps each dispatched step's completion off
its main thread and writes it into its trace JSONL as the span
``device_step`` (``tpu_ddp/telemetry/stamper.py``): start = the later of
the dispatch's return and the previous completion, end = this completion,
attrs ``ahead`` (dispatches made and not yet complete when this one
returned, itself included) and ``steps`` (optimizer steps in the dispatch,
where more than one). The spans of a run never overlap: the gaps between
them are the time the device had nothing to run, as the host sees it.

The file is found as ``scopes.of_run`` finds it: the newest incarnation of
host 0 under ``<dirname(record["trace_dir"])>/telemetry``. The slice is the
last ``record["dispatches"]`` such spans: the probe ends the run at the
dispatch that closes the window, so no later one exists, and set-up's come
first. Times stay on the telemetry's own clock: only differences are read,
so no clock has to be mapped.

**The closing dispatch is read apart.** The probe stands where the step
callable stood, and the fence that closes the window and the profiler's
stop happen inside its call, after the dispatch: that call returns seconds
after its step has run (12 s in ``resnet50-cifar.b512``), so the span of
the closing dispatch starts and ends then, with ``ahead`` 1. ``Slice.steps``
therefore holds every dispatch but that one, and ``closing_gap_s`` what is
known of the gap before it: from the last completion to the *start* of the
closing call (its ``compiled_step`` span carries the same ``step``), which
leaves out that one dispatch's own enqueue; nothing, where the call started
before the device ran dry.

A program that stamps no step (the parent of the PR that added this file),
an untraced run and a record without a trace directory give ``None``, and
nothing is raised. Plain files and the stdlib: nothing here imports the
program.
"""

from __future__ import annotations

import collections

from chipbench import scopes

SPAN, CALL = "device_step", "compiled_step"
#: the JSONL rounds a span's start and its length to a nanosecond each: a
#: gap under two of them is none
ROUNDING_S = 2e-9

Step = collections.namedtuple("Step", "start end ahead steps id")
Slice = collections.namedtuple("Slice", "steps closing_gap_s")


def of_run(run):
    """The ``Slice`` of one traced run, its dispatches oldest first, read
    once and kept on ``run``; None where there are fewer stamped steps than
    dispatches, or one dispatch only."""
    if hasattr(run, "_step_spans"):
        return run._step_spans
    run._step_spans = None
    tel_dir = scopes.telemetry_dir(run.record)
    dispatches = run.record.get("dispatches")
    if tel_dir is None or not dispatches or dispatches < 2:
        return None
    path = scopes.newest(tel_dir)["trace"]
    if not path:
        return None
    spans = [r for r in scopes.read_jsonl(path) if r.get("type") == "span"]
    stamped = []
    for r in spans:
        if r.get("name") == SPAN:
            attrs = r.get("attrs") or {}
            stamped.append(Step(r["ts_s"], r["ts_s"] + r["dur_s"],
                                attrs.get("ahead"),
                                int(attrs.get("steps", 1)), r.get("step")))
    stamped.sort(key=lambda step: step.start)
    if len(stamped) < dispatches:
        return None
    *steps, closing = stamped[-dispatches:]
    called = [r["ts_s"] for r in spans
              if r.get("name") == CALL and r.get("step") == closing.id]
    gap = max(0.0, called[-1] - steps[-1].end) if called else 0.0
    run._step_spans = Slice(steps, gap)
    scopes.say(f"step spans: {len(stamped)} {SPAN} spans in {path}, the last "
               f"{dispatches} are the slice's; the closing call started "
               f"{gap!r} s after the completion before it")
    return run._step_spans


def gaps_s(piece: Slice) -> list:
    """Seconds between each completion and the next step's start, the gap
    before the closing dispatch last."""
    steps = piece.steps
    gaps = [b.start - a.end for a, b in zip(steps, steps[1:])] + [
        piece.closing_gap_s]
    return [gap if gap > ROUNDING_S else 0.0 for gap in gaps]

