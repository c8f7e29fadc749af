"""The traced slice by phase and module, and the set-up by function.

A device trace names an operation by its HLO instruction (``fusion.11``).
With telemetry on, the program writes beside its trace JSONL a program map
(``programs-p0[.i<k>].jsonl``): for every instruction of the step program
its ``op_name``, phase (``forward``, ``backward``, ``optimizer``,
``grad_sync``, ``input``, ``other``) and module, from the scopes the step
builders wrap their work in. This file joins the reduced trace's
``device_ops`` (every operation of chip 0 by its short name, seconds over
the slice) with that map, and reads the per-function trace, lowering and
compile seconds from the run-end counters record of the trace JSONL.

Both files are found in ``<dirname(record["trace_dir"])>/telemetry``, the
newest incarnation of host 0. A program that writes no map (the parent of
the PR that added this file) gives ``None`` everywhere: the metrics are left
out of the line and nothing is raised. So does an untraced run, and a map
that lacks the names of more than 1% of the slice's busy time: it is the map
of another program.

Plain files and the stdlib: nothing here imports the program.
"""

from __future__ import annotations

import json
import os
import re

PHASES = ("forward", "backward", "optimizer", "grad_sync", "input", "other")
#: the map's word for a ``conditional``, a ``while`` or a ``call``: no phase.
#: A trace shows such an instruction as long as the branch or body it runs
#: and shows that branch's operations beside it, so its time is theirs
CONTROL = "control"
#: a map that lacks the names of more than this share of busy time is the
#: map of another program
UNMAPPED_CEILING = 0.01
ROWS = 12       # (module, phase) rows printed
FUNCTIONS = 10  # functions printed
SINK = re.compile(r"^(programs|trace)-p0(?:\.i(\d+))?\.jsonl$")
NAME_LENGTH = 64  # ``xplane.short_name`` keeps this much of a name


def say(*parts):
    print("chipbench:", *parts, flush=True)


# -- files ---------------------------------------------------------------------

def telemetry_dir(record: dict):
    trace_dir = record.get("trace_dir")
    if not trace_dir:
        return None
    return os.path.join(os.path.dirname(trace_dir), "telemetry")


def newest(run_dir: str) -> dict:
    """{"trace": path, "programs": path or None} of host 0's newest
    incarnation, by the trace file: a map of an older incarnation is not
    this run's."""
    found = {}
    for name in os.listdir(run_dir) if os.path.isdir(run_dir) else ():
        m = SINK.match(name)
        if m:
            found[(m.group(1), int(m.group(2) or 0))] = os.path.join(
                run_dir, name)
    lives = [k for family, k in found if family == "trace"]
    if not lives:
        return {"trace": None, "programs": None}
    k = max(lives)
    return {"trace": found[("trace", k)],
            "programs": found.get(("programs", k))}


def read_jsonl(path: str) -> list:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except ValueError:  # a line cut short by a kill
                    continue
    return out


def load_map(path) -> dict:
    """{"instructions": {short name: row}, "programs": [...]} from a map
    file; None where there is none or it holds no program."""
    if not path or not os.path.exists(path):
        return None
    records = [r for r in read_jsonl(path) if r.get("type") == "program_map"]
    if not records:
        return None
    instructions = {}
    for record in records:
        for name, row in record.get("instructions", {}).items():
            instructions.setdefault(name[:NAME_LENGTH], row)
    return {
        "instructions": instructions,
        "programs": [
            {key: r.get(key) for key in (
                "program", "module", "dispatch", "export_seconds",
                "mixed_fusions", "phases")} for r in records],
        "bytes": os.path.getsize(path),
    }


def final_counters(path) -> dict:
    """The ``attrs`` of the newest counters record of a trace JSONL that
    carries the per-function table (the run-end record); None without."""
    if not path or not os.path.exists(path):
        return None
    for record in reversed(read_jsonl(path)):
        if record.get("type") == "counters" and "tables" in record.get(
                "attrs", {}):
            return record["attrs"]
    return None


# -- the join --------------------------------------------------------------------

def join(device_ops, instructions: dict, steps: int) -> dict:
    """``device_ops``: ``[[short name, seconds over the slice], ...]``;
    ``instructions``: the map's rows by short name. Milliseconds per
    optimizer step by phase and by (module, phase), and the shares of busy
    time that the map lacks, that sit in mixed fusions, and that took their
    phase from a neighbour. Busy time is time counted once: an instruction
    the map calls ``control`` is in none of the sums (``control_ms`` alone
    says how long the trace showed it)."""
    busy = control = 0.0
    by_phase = {phase: 0.0 for phase in PHASES}
    by_row = {}
    unmapped = mixed = inherited = 0.0
    for name, seconds in device_ops:
        row = instructions.get(name)
        if row is not None and row["phase"] == CONTROL:
            control += seconds
            continue
        busy += seconds
        if row is None:
            unmapped += seconds
            continue
        by_phase[row["phase"]] = by_phase.get(row["phase"], 0.0) + seconds
        key = (row.get("module") or "-", row["phase"])
        by_row[key] = by_row.get(key, 0.0) + seconds
        if row.get("mixed"):
            mixed += seconds
        if row.get("inherited"):
            inherited += seconds
    to_ms = 1e3 / steps

    def share(seconds):
        return seconds / busy if busy else 0.0

    return {
        "busy_s": busy,
        "phase_ms": {p: s * to_ms for p, s in by_phase.items()},
        "unmapped_ms": unmapped * to_ms,
        "control_ms": control * to_ms,
        "unmapped_share": share(unmapped),
        "mixed_share": share(mixed),
        "inherited_share": share(inherited),
        "rows": sorted(([module, phase, s * to_ms] for (module, phase), s
                        in by_row.items()), key=lambda r: -r[2]),
    }


def function_table(counters: dict) -> list:
    """Rows of the per-function table, most trace and lowering seconds
    first: [function, trace_s (nested traces included), trace_self_s
    (without them), traces, lower_s, lowerings, compile_s, compilations,
    load_s, loads]."""
    rows = []
    table = (counters or {}).get("tables", {}).get("jax/functions", {})
    for function, cells in table.items():
        get = cells.get
        rows.append([function, get("trace_seconds", 0.0),
                     get("trace_self_seconds", 0.0),
                     int(get("traces", 0)), get("lower_seconds", 0.0),
                     int(get("lowerings", 0)), get("compile_seconds", 0.0),
                     int(get("compilations", 0)),
                     get("cache_load_seconds", 0.0),
                     int(get("cache_loads", 0))])
    rows.sort(key=lambda r: -(r[1] + r[4]))
    return rows


def trace_lower_s(counters: dict):
    hist = (counters or {}).get("histograms", {})
    if "jax/trace_seconds" not in hist or "jax/lower_seconds" not in hist:
        return None
    return (hist["jax/trace_seconds"].get("sum", 0.0)
            + hist["jax/lower_seconds"].get("sum", 0.0))


# -- one traced run ------------------------------------------------------------------

def of_run(run, out=say) -> dict:
    """{"split": join(...) or None, "counters": ...} of one run, computed
    and printed once and kept on ``run``."""
    cached = getattr(run, "_scopes", None)
    if cached is not None:
        return cached
    result = {"split": None, "counters": None}
    run._scopes = result
    tel_dir = telemetry_dir(run.record)
    if run.trace is None or tel_dir is None:
        return result  # an untraced run
    files = newest(tel_dir)
    result["counters"] = final_counters(files["trace"])
    report_functions(result["counters"], out)
    program_map = load_map(files["programs"])
    if program_map is None:
        out("scopes: the program wrote no program map: no split by phase")
        return result
    out(f"scopes: program map {files['programs']} {program_map['bytes']} "
        f"bytes, programs {program_map['programs']}")
    split = join(run.trace["device_ops"], program_map["instructions"],
                 run.trace["steps"])
    report_split(split, run.trace.get("device_step_ms"), out)
    if split["unmapped_share"] > UNMAPPED_CEILING:
        out(f"scopes: {100 * split['unmapped_share']!r}% of busy time has "
            "names the map lacks: it is the map of another program, no "
            "split is reported")
        return result
    result["split"] = split
    return result


def report_split(split: dict, device_step_ms, out=say):
    total = sum(split["phase_ms"].values()) + split["unmapped_ms"]
    out(f"scopes: ms per step by phase {split['phase_ms']} unmapped "
        f"{split['unmapped_ms']!r} sum {total!r} device_step_ms "
        f"{device_step_ms!r} (control flow, counted in what it runs: "
        f"{split['control_ms']!r})")
    out(f"scopes: share of busy time unmapped "
        f"{100 * split['unmapped_share']!r}% in mixed fusions "
        f"{100 * split['mixed_share']!r}% phase inherited from a neighbour "
        f"{100 * split['inherited_share']!r}%")
    for module, phase, ms in split["rows"][:ROWS]:
        out(f"scopes: {ms:9.4f} ms  {phase:9s} {module}")


def report_functions(counters: dict, out=say):
    rows = function_table(counters)
    if not rows:
        out("scopes: the run-end counters carry no per-function table")
        return
    hist = counters.get("histograms", {})
    count = counters.get("counters", {})
    out("scopes: set-up seconds by stage: trace "
        f"{hist.get('jax/trace_seconds', {}).get('sum')!r} lower "
        f"{hist.get('jax/lower_seconds', {}).get('sum')!r} compile+load "
        f"{hist.get('jax/compile_seconds', {}).get('sum')!r} of which loads "
        f"{hist.get('jax/cache_load_seconds', {}).get('sum')!r}; "
        f"compilations {count.get('jax/compilations', 0)} cache loads "
        f"{count.get('jax/cache_loads', 0)}")
    out("scopes: function trace_s trace_self_s traces lower_s lowerings "
        "compile_s compilations load_s loads")
    for row in rows[:FUNCTIONS]:
        out("scopes:", *[f"{v:.4f}" if isinstance(v, float) else v
                         for v in row])


# -- what the metric files read ---------------------------------------------------------

def phase_ms(run, *phases, unmapped=False):
    """Device milliseconds per step of the given phases (with ``unmapped``,
    of operations the map lacks too); None where there is no split."""
    split = of_run(run)["split"]
    if split is None:
        return None
    total = sum(split["phase_ms"].get(p, 0.0) for p in phases)
    return total + (split["unmapped_ms"] if unmapped else 0.0)
