"""OOM forensics: turn a ``RESOURCE_EXHAUSTED`` death into evidence.

Today an OOM is the worst-documented failure in the fleet: the XLA
runtime raises, the process dies, and the run dir holds nothing that
says *memory* — the goodput ledger books it as a generic ``killed``.
This module gives the death a paper trail:

- :func:`is_resource_exhausted` recognizes XLA allocation failures
  (``RESOURCE_EXHAUSTED`` status, allocator out-of-memory messages)
  without importing jax — classification by evidence, not by type.
- :func:`write_postmortem` writes the one-shot bundle the Trainer emits
  at the step boundary BEFORE re-raising:

    <run_dir>/oom/step_<n>-p<i>/
      meta.json       # schema version, step, incarnation, error, sources
      samples.jsonl   # the sampler's last memory samples (the curve
                      # that walked into the wall)
      config.json     # TrainConfig snapshot
      run_meta.json   # the run-metadata header (what lets the plan be
                      # rebuilt at report time)

  The dying process writes only what it already holds — compiling the
  static plan inside an OOM handler would be asking a drowning process
  to swim. The plan side (:func:`attach_plan`: memplan-convention peak +
  the top-k largest buffers of the recorded program's compiled HLO) is
  attached at REPORT time by ``tpu-ddp mem``, the same
  rebuild-at-read-time contract as the profiler's per-op table.
- the Trainer also emits an ``oom_abort`` trace instant, which
  ``ledger/stitch.py`` classifies as the new ``oom`` exit class
  (docs/goodput.md).
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import List, Optional

#: bump on any breaking change to the bundle meta.json shape
OOM_SCHEMA_VERSION = 1

OOM_DIRNAME = "oom"

#: allocation-failure signatures across jax/XLA versions and backends
#: (TPU runtime, TFRT CPU/GPU allocators, BFC allocator)
_OOM_PATTERNS = re.compile(
    r"RESOURCE[ _]?EXHAUSTED|out of memory|OOM when allocating"
    r"|[Aa]llocation .*failed|failed to allocate|memory exhausted",
)


def is_resource_exhausted(exc: BaseException) -> bool:
    """Does this exception look like an XLA/runtime allocation failure?
    Matched on the rendered message (and the exception-type name for
    ``XlaRuntimeError`` carrying a status prefix) so the check works on
    any jax version and in tests with synthetic exceptions."""
    text = f"{type(exc).__name__}: {exc}"
    return bool(_OOM_PATTERNS.search(text))


def bundle_dir_name(step: int, process_index: int) -> str:
    return f"step_{step}-p{process_index}"


def write_postmortem(
    run_dir: str,
    *,
    step: int,
    process_index: int = 0,
    incarnation: int = 0,
    error: Optional[BaseException] = None,
    samples: Optional[List[dict]] = None,
    config_snapshot: Optional[dict] = None,
    run_meta: Optional[dict] = None,
) -> Optional[str]:
    """Write the one-shot postmortem bundle; returns its path, or the
    existing path when this (step, host) already has one (one-shot: a
    retry loop must not spam bundles), or None when nothing could be
    written (forensics never mask the original failure)."""
    try:
        path = os.path.join(run_dir, OOM_DIRNAME,
                            bundle_dir_name(step, process_index))
        if os.path.isdir(path) and os.path.isfile(
                os.path.join(path, "meta.json")):
            return path
        os.makedirs(path, exist_ok=True)
        samples = samples or []
        with open(os.path.join(path, "samples.jsonl"), "w") as f:
            for rec in samples:
                f.write(json.dumps(rec) + "\n")
        if config_snapshot is not None:
            with open(os.path.join(path, "config.json"), "w") as f:
                json.dump(config_snapshot, f, indent=1)
        if run_meta is not None:
            with open(os.path.join(path, "run_meta.json"), "w") as f:
                json.dump(run_meta, f, indent=1)
        meta = {
            "oom_schema_version": OOM_SCHEMA_VERSION,
            "type": "oom_postmortem",
            "step": step,
            "process_index": process_index,
            "incarnation": incarnation,
            "wall_time": time.time(),
            "error_type": type(error).__name__ if error else None,
            "error": (str(error)[:2000] if error is not None else None),
            "n_samples": len(samples),
            "sources": sorted(os.listdir(path)) + ["meta.json"],
        }
        # meta.json last and atomically: its presence IS the bundle's
        # completeness marker (mirrors the profiler bundle contract)
        tmp = os.path.join(path, f"meta.json.tmp.{os.getpid()}")
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=1)
        os.replace(tmp, os.path.join(path, "meta.json"))
        return path
    except Exception:
        return None


def read_postmortem(bundle_dir: str) -> Optional[dict]:
    """One bundle's meta.json (+ parsed samples), None when absent/torn;
    raises ValueError on a future schema (refusing beats misreading)."""
    try:
        with open(os.path.join(bundle_dir, "meta.json")) as f:
            meta = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    version = meta.get("oom_schema_version", 0)
    if isinstance(version, int) and version > OOM_SCHEMA_VERSION:
        raise ValueError(
            f"{bundle_dir}: oom_schema_version {version} is newer than "
            f"this tool understands ({OOM_SCHEMA_VERSION})")
    meta["path"] = bundle_dir
    samples: List[dict] = []
    try:
        with open(os.path.join(bundle_dir, "samples.jsonl")) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    samples.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    except OSError:
        pass
    meta["samples"] = samples
    for name in ("config", "run_meta", "plan"):
        try:
            with open(os.path.join(bundle_dir, f"{name}.json")) as f:
                meta[name] = json.load(f)
        except (OSError, json.JSONDecodeError):
            pass
    return meta


def list_postmortems(run_dir: str) -> List[dict]:
    """Every complete OOM bundle under ``<run_dir>/oom/``, step order."""
    root = os.path.join(run_dir, OOM_DIRNAME)
    if not os.path.isdir(root):
        return []
    out: List[dict] = []
    for entry in sorted(os.listdir(root)):
        meta = read_postmortem(os.path.join(root, entry))
        if meta is not None:
            out.append(meta)
    out.sort(key=lambda m: (m.get("step") or 0,
                            m.get("process_index") or 0))
    return out


# -- plan attachment (report-time, jax-backed) ----------------------------

def largest_buffers(compiled, k: int = 10) -> List[dict]:
    """Top-k largest tensors of a compiled program, parsed from its
    optimized HLO text — the report's 'what was the plan going to put in
    HBM' table. Byte sizes come from each instruction's result shape
    (the compiler's buffer assignment allocates exactly these), ranked
    descending; tuple-shaped results are skipped (their elements appear
    as their own defining instructions)."""
    dtype_bytes = {
        "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
        "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
        "f64": 8, "c64": 8, "c128": 16,
    }
    pattern = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]"
        r"(?:\{[^}]*\})?\s+(\w[\w\-]*)\(")
    rows: List[dict] = []
    for line in compiled.as_text().splitlines():
        m = pattern.match(line)
        if not m:
            continue
        name, dtype, dims, op = m.groups()
        itemsize = dtype_bytes.get(dtype)
        if itemsize is None:
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        rows.append({
            "name": name,
            "op": op,
            "dtype": dtype,
            "shape": [int(d) for d in filter(None, dims.split(","))],
            "bytes": n * itemsize,
        })
    rows.sort(key=lambda r: -r["bytes"])
    return rows[:k]


def plan_for_run_meta(meta: dict, k: int = 10) -> dict:
    """The static memory plan of a recorded run: memplan-convention peak
    (args + temp per device) plus the top-k largest buffers, from the
    run's RECORDED program rebuilt via the analyze path. Needs jax and
    enough local devices; raises with the analyze refusal messages for
    programs the abstract builder can't reproduce."""
    import jax

    from tpu_ddp.analysis.explain import compiled_for_run_meta

    n_needed = 1
    for s in (meta.get("mesh") or {}).values():
        n_needed *= s
    local = jax.devices()
    if n_needed > len(local):
        raise ValueError(
            f"run used {n_needed} devices, local backend has "
            f"{len(local)} — plan rebuild skipped")
    compiled = compiled_for_run_meta(meta, local[:n_needed])
    ma = compiled.memory_analysis()
    arg = int(ma.argument_size_in_bytes)
    temp = int(ma.temp_size_in_bytes)
    return {
        "argument_bytes": arg,
        "temp_bytes": temp,
        "output_bytes": int(ma.output_size_in_bytes),
        "peak_bytes": arg + temp,   # memplan's steady-state convention
        "top_buffers": largest_buffers(compiled, k),
    }


def attach_plan(bundle_dir: str, k: int = 10) -> Optional[dict]:
    """Compute the bundle's static plan from its recorded ``run_meta``
    and write it as ``plan.json`` (idempotent: an existing plan is
    returned, not recomputed). Returns None — with the reason left in
    the bundle untouched — when the rebuild isn't possible here."""
    plan_path = os.path.join(bundle_dir, "plan.json")
    if os.path.isfile(plan_path):
        try:
            with open(plan_path) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            pass
    try:
        with open(os.path.join(bundle_dir, "run_meta.json")) as f:
            meta = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    try:
        plan = plan_for_run_meta(meta, k)
    except Exception:
        return None
    tmp = f"{plan_path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(plan, f, indent=1)
    os.replace(tmp, plan_path)
    return plan
