"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

What a v5e trace holds (looked at by hand, PR 23; a recorded slice is under
``chipbench/testdata/``): one plane per chip named ``/device:TPU:<n>`` with
the lines ``Steps`` and ``XLA Modules`` (one event per program execution),
``XLA Ops`` (every operation the core ran, by its HLO name) and ``Async XLA
Ops`` (copies and collectives in flight). An op's name is its HLO text
(``%fusion.27 = (f32[32]...) fusion(...)``); the reduction keeps the part
before `` = ``. Start times are nanoseconds from the start of the trace.

The harness traces with the host tracer off (with it on, the runtime logs an
event per row it re-tiles for the device and the host path slows 3x to 25x),
so the trace has no host spans. The device's clock is tied to the host's by
the traced run's own fences: the telemetry ends its ``device_sync`` span of a
step when the device has finished it, and the step's ``XLA Modules`` event
ends at that moment on the device's clock. The median difference is the
offset; idle gaps are then labelled by the host span that covers them. A
loop that fences no step still has the fence that closes the window
(``adapters/trainer.py::StepProbe._close``): the last program's end is that
moment, and one pair gives the offset (``clock_offset_ns``).

``busy`` is the union of the ``XLA Ops`` intervals of a chip: time in which an
operation ran on its core. A collective is an event on either ops line whose
name starts with one of ``COLLECTIVES``; its exposed part is the time a
collective is in flight while no other operation runs on that chip.

Only ``load`` touches jax; the arithmetic works on plain lists, so the tests
check it on synthetic planes.
"""

from __future__ import annotations

import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_SPAN = "device_sync"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")


# -- interval arithmetic (half-open [start, end), any unit) -------------------

def union(intervals):
    """Sorted disjoint intervals covering the same points."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def length(intervals) -> float:
    return sum(end - start for start, end in union(intervals))


def subtract(a, b):
    """The part of ``a`` (as a union) that ``b`` does not cover."""
    out = []
    b = union(b)
    j = 0
    for start, end in union(a):
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        i = j
        while i < len(b) and b[i][0] < end:
            if b[i][0] > cur:
                out.append((cur, b[i][0]))
            cur = max(cur, b[i][1])
            i += 1
        if cur < end:
            out.append((cur, end))
    return out


def short_name(hlo_text: str) -> str:
    """``%fusion.27 = (f32[32]...) fusion(...)`` -> ``fusion.27``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")[:64]


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


# -- reading the file -----------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """{"devices": {chip: {line: [(name, start_ns, end_ns)]}}}"""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):  # the recorded slice under testdata/
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices = {}
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if not match:
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                lines[line.name] = [
                    (short_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events]
        devices[int(match.group(1))] = lines
    return {"devices": devices}


# -- the reduction ------------------------------------------------------------------

def reduce_chip(lines: dict) -> dict:
    """Busy union, collective time and its exposed part, per-op totals, for
    one chip's lines (times in ns)."""
    ops = lines.get(OPS_LINE, [])
    asyncs = lines.get(ASYNC_LINE, [])
    busy = union((s, e) for _, s, e in ops)
    compute = union((s, e) for n, s, e in ops if not is_collective(n))
    collective = union((s, e) for n, s, e in list(ops) + list(asyncs)
                       if is_collective(n))
    totals = {}
    for name, start, end in ops:
        totals[name] = totals.get(name, 0) + (end - start)
    return {
        "busy": busy,
        "busy_ns": length(busy),
        "collective_ns": length(collective),
        "collective_exposed_ns": length(subtract(collective, compute)),
        "op_totals_ns": totals,
        "program_ends": sorted(e for _, _, e in lines.get(MODULES_LINE, [])),
    }


def label_gaps(gaps_ns, host_spans_ns, other="host_other"):
    """Total seconds of device idle gaps by what the host was doing: each gap
    goes to the host span that overlaps most of it."""
    totals = {}
    spans = sorted(host_spans_ns, key=lambda s: s[1])
    for start, end in gaps_ns:
        best, best_overlap = other, 0
        for name, s, e in spans:
            if s >= end:
                break
            overlap = min(e, end) - max(s, start)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        totals[best] = totals.get(best, 0) + (end - start)
    return sorted(([k, v / 1e9] for k, v in totals.items()),
                  key=lambda kv: -kv[1])


def clock_offset_ns(program_ends_ns, host_spans, t_close=None):
    """Device clock minus host clock, from the fences (module docstring).
    Where the ``device_sync`` spans pair one to one with the program
    executions (a fence a step), the median over the pairs; where they do
    not (a loop that fences no step), the one fence every traced slice has:
    ``t_close``, the host's clock straight after the ``block_until_ready``
    that closed the window, is when the last program ended on the device's.
    None without either."""
    sync_ends = sorted(e for name, _, e in host_spans if name == SYNC_SPAN)
    if sync_ends and len(sync_ends) == len(program_ends_ns):
        return statistics.median(
            d - h * 1e9 for d, h in zip(program_ends_ns, sync_ends))
    if t_close is None or not program_ends_ns:
        return None
    return program_ends_ns[-1] - t_close * 1e9


def reduce(planes: dict, *, window_s: float, dispatches: int,
           steps_per_call: int = 1, t_open=None, host_spans=()) -> dict:
    """``planes`` as ``load`` gives them; ``window_s`` the traced slice's
    length on the host clock (fence to fence) and ``t_open`` its start,
    ``dispatches`` the step dispatches the harness made in it and
    ``host_spans`` ``(name, start, end)`` on the same host clock (seconds)."""
    if not planes["devices"]:
        raise ValueError("the trace has no /device:TPU plane")
    chips = {n: reduce_chip(lines) for n, lines in planes["devices"].items()}
    if not any(c["busy_ns"] for c in chips.values()):
        raise ValueError("no operation ran on the device in the traced slice")
    n = len(chips)
    steps = dispatches * steps_per_call
    first = chips[min(chips)]
    if len(first["program_ends"]) != dispatches:
        # busy time is divided by the harness's count of dispatches: it has
        # to be the trace's own count of programs run
        raise ValueError(
            f"the trace holds {len(first['program_ends'])} program "
            f"executions, the harness made {dispatches} dispatches")
    busy_s = sum(c["busy_ns"] for c in chips.values()) / n / 1e9

    offset_ns = clock_offset_ns(
        first["program_ends"], host_spans,
        None if t_open is None else t_open + window_s)
    lo, hi = first["busy"][0][0], first["busy"][-1][1]
    if offset_ns is not None and t_open is not None:
        lo = min(lo, t_open * 1e9 + offset_ns)
        hi = max(hi, (t_open + window_s) * 1e9 + offset_ns)
    gaps = subtract([(lo, hi)], first["busy"])
    spans_ns = [] if offset_ns is None else [
        (name, s * 1e9 + offset_ns, e * 1e9 + offset_ns)
        for name, s, e in host_spans]
    ops = sorted(first["op_totals_ns"].items(), key=lambda kv: -kv[1])
    collective_ns = sum(c["collective_ns"] for c in chips.values()) / n
    exposed_ns = sum(c["collective_exposed_ns"] for c in chips.values()) / n
    return {
        "chips": n,
        "steps": steps,
        "programs": len(first["program_ends"]),
        "busy_s": busy_s,
        "window_s": window_s,
        "device_step_ms": first["busy_ns"] / 1e6 / steps,
        "collective_ms": collective_ns / 1e6 / steps,
        "collective_exposed_ms": (exposed_ns / 1e6 / steps
                                  if collective_ns else None),
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": label_gaps(gaps, spans_ns),
        "clock_aligned": offset_ns is not None,
    }


def reduce_run(record: dict, say=print) -> dict:
    """The reduction of one traced run of the harness."""
    path = find_xplane(record["trace_dir"])
    planes = load(path)
    out = reduce(
        planes, window_s=record["window_s"],
        dispatches=record["dispatches"],
        steps_per_call=record["steps_per_call"], t_open=record["t_open"],
        host_spans=record["host_spans"])
    out["examples_per_s_per_chip"] = (
        record["examples"] / record["window_s"] / record["chips"])
    say(f"trace: {os.path.getsize(path)} bytes, chips={out['chips']} "
        f"programs={out['programs']} steps={out['steps']} "
        f"clock_aligned={out['clock_aligned']} "
        f"collective_ms_per_step={out['collective_ms']!r}")
    return out
