"""Device-side capture: ``jax.profiler`` arming + per-op attribution.

Two independent halves:

- **Device trace arming** — ``start_device_trace``/``stop_device_trace``
  wrap ``jax.profiler.start_trace`` for the capture window. Where the
  backend (or the jax build) has no profiler support the arm degrades to
  a *note* recorded in the bundle manifest — never an error: the host
  sampler and the attribution below still capture.

- **Per-op attribution** — the roofline (PR 5) predicts where a step's
  time *should* go from the compiled program's cost model; a capture
  window measures where the ``compiled_step`` span time *did* go, but
  only as one opaque number. :func:`per_op_attribution` joins the two at
  op granularity: it models a time term for every row of the
  :class:`~tpu_ddp.analysis.hlo.StepAnatomy` inventory — fused math
  (cost-model FLOPs / MXU peak), HBM traffic (bytes-accessed / HBM BW),
  and each collective bucket (ring-model wire bytes / ICI link BW) — and
  distributes the window's measured per-step span time across the rows
  in proportion. The result reads "of the measured 12.1 ms step, ~1.8 ms
  sits in ``all-gather/f32/data/g8``, 2.3× what the roofline predicts".
  Deviceless-safe: the math needs only the anatomy (which compiles on
  the CPU CI mesh) and a chip spec. A device with no published peak (the
  CPU mesh) is refused with a note unless ``--chip`` names the chip to
  attribute against — no chip is assumed.

``per_op_attribution`` is pure stdlib over an anatomy record;
``attribution_for_bundle`` is the jax-backed convenience that rebuilds
the recorded program from the bundle's run metadata (the same
``anatomy_for_run_meta`` path ``watch --roofline`` uses) and degrades to
a note on any failure.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional

log = logging.getLogger(__name__)

#: bump on any breaking change to the attribution record shape
ATTRIBUTION_SCHEMA_VERSION = 1


# -- device trace arming ---------------------------------------------------

def start_device_trace(out_dir: str) -> Optional[str]:
    """Arm ``jax.profiler.trace`` into ``out_dir``. Returns None on
    success, else a one-line note for the bundle manifest (no jax, no
    backend profiler support, a trace already running — all degrade)."""
    try:
        import jax

        jax.profiler.start_trace(out_dir)
        return None
    except Exception as e:  # degrade to a note by contract
        return f"jax.profiler trace unavailable: {e}"


def stop_device_trace() -> Optional[str]:
    """Stop a successfully armed trace. Returns None on success, else a
    note (a failed stop must not lose the rest of the bundle)."""
    try:
        import jax

        jax.profiler.stop_trace()
        return None
    except Exception as e:
        return f"jax.profiler trace did not finalize: {e}"


# -- per-op attribution ----------------------------------------------------

def _anatomy_fields(anatomy) -> dict:
    """Accept a StepAnatomy or its ``to_json()`` dict (bundles and
    baseline artifacts carry the dict form)."""
    if isinstance(anatomy, dict):
        return anatomy
    return anatomy.to_json()


def per_op_attribution(anatomy, measured_step_s: Optional[float],
                       chip: Optional[str] = None) -> dict:
    """Distribute a measured per-step time over the anatomy's op rows.

    Every row gets ``model_s`` (its roofline time term), ``share`` (of
    the summed model time), and — when a measurement is given —
    ``attributed_s = measured_step_s * share`` plus ``vs_model`` (the
    measured-over-predicted ratio, the "this collective runs 2.3× the
    ring model" verdict). Attributed times sum to the measured span by
    construction. Stdlib + the chip-spec table only. A device kind with
    no published peak (and no ``chip`` to stand for it) returns
    ``{"note": ...}`` — the shape ``attribution_for_bundle`` degrades to.
    """
    from tpu_ddp.analysis.roofline import chip_spec

    rec = _anatomy_fields(anatomy)
    notes: List[str] = []
    kind = chip or rec.get("device_kind")
    spec = chip_spec(kind)
    if spec is None or spec.peak_bf16_flops is None:
        return {"note": f"no published peak for {kind!r}: pass --chip to "
                        "name the chip to attribute against"}

    rows: List[Dict[str, object]] = []
    flops = rec.get("flops")
    if flops:
        rows.append({
            "op": "compute (fused math)",
            "model_s": float(flops) / spec.peak_bf16_flops,
            "detail": f"{float(flops):.3e} flops @ bf16 peak",
        })
    accessed = rec.get("bytes_accessed")
    if accessed:
        rows.append({
            "op": "hbm traffic",
            "model_s": float(accessed) / spec.hbm_bw,
            "detail": f"{float(accessed):.3e} bytes @ hbm bw",
        })
    for c in rec.get("collectives") or ():
        c = c if isinstance(c, dict) else c.__dict__
        key = (f"{c['kind']}/{c['dtype']}/{c['axis']}"
               f"/g{c['group_size']}")
        wire = float(c.get("wire_bytes") or 0)
        rows.append({
            "op": key,
            "model_s": wire / spec.ici_bw if spec.ici_bw else 0.0,
            "detail": (f"{c.get('count')}x, {int(wire)} wire bytes "
                       "@ ici link bw"),
        })

    model_total = sum(r["model_s"] for r in rows)
    if not rows or model_total <= 0:
        notes.append("anatomy carries no cost-model figures to "
                     "distribute over (backend exposed no cost analysis)")
    for r in rows:
        share = r["model_s"] / model_total if model_total > 0 else 0.0
        r["share"] = share
        if measured_step_s:
            r["attributed_s"] = measured_step_s * share
    rows.sort(key=lambda r: (-r["model_s"], r["op"]))
    # the measured-over-model ratio is a WHOLE-STEP property (the
    # distribution is proportional, so a per-row ratio would just repeat
    # it); >1 means the step runs slower than the serial roofline sum —
    # host gaps, launch overhead, or a chip mismatch
    vs_model = (measured_step_s / model_total
                if measured_step_s and model_total > 0 else None)
    return {
        "schema_version": ATTRIBUTION_SCHEMA_VERSION,
        "chip": spec.key,
        "measured_step_s": measured_step_s,
        "model_step_s": model_total if rows else None,
        "measured_vs_model": vs_model,
        "strategy": rec.get("strategy"),
        "model": rec.get("model"),
        "ops": rows,
        "notes": notes,
    }


def measured_step_from_meta(meta: dict) -> Optional[float]:
    """The window's measured per-STEP compiled span time from a bundle's
    ``measured_phases`` (total compiled time / optimizer steps covered —
    correct under ``--steps-per-call`` fusion, where spans cover K
    steps)."""
    phases = meta.get("measured_phases") or {}
    compiled = phases.get("compiled_step") or {}
    total = compiled.get("total_s")
    steps = (meta.get("window") or {}).get("steps")
    if not isinstance(total, (int, float)) or not steps:
        return None
    return total / steps


def attribution_for_bundle(meta: dict,
                           chip: Optional[str] = None) -> dict:
    """Rebuild the recorded program from the bundle's run metadata (the
    ``anatomy_for_run_meta`` path) and attribute the window's measured
    step time per op. Any failure — no jax, not enough local devices, a
    program the abstract builder can't reproduce — returns ``{"note":
    ...}``: the report must keep rendering."""
    run_meta = meta.get("run_meta") or {}
    measured = measured_step_from_meta(meta)
    try:
        import jax

        from tpu_ddp.analysis.explain import anatomy_for_run_meta

        n_needed = 1
        for s in (run_meta.get("mesh") or {}).values():
            n_needed *= s
        local = jax.devices()
        if n_needed > len(local):
            return {"note": f"run used {n_needed} devices, local backend "
                            f"has {len(local)} — per-op join skipped"}
        anatomy = anatomy_for_run_meta(run_meta, local[:n_needed])
        return per_op_attribution(anatomy, measured, chip)
    except Exception as e:  # degrade, never take the report down
        return {"note": f"per-op attribution unavailable: {e}"}
