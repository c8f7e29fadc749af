"""Calibration: scale roofline predictions toward measured reality.

The roofline is a lower bound — real steps carry launch gaps, imperfect
overlap, and compiler scheduling the cost model can't see. The PR 8
profiler measures exactly that gap (a capture window's measured
per-step span time), and the PR 5 analyzer's run-dir
join records it as ``roofline_fraction`` (= predicted/measured). This
module turns that evidence into one number per CHIP KIND — the median
measured-over-predicted ratio — which ``price.py`` multiplies into
every prediction:

- **profile bundles** (``<run_dir>/profiles/*/meta.json``): the
  window's measured per-step time over the roofline prediction of the
  bundle's own recorded program (rebuilt via ``anatomy_for_run_meta``).
  The ratio is against the OVERLAPPED roofline;
- **analyze --json run-dir artifacts**: ``1 / measured.roofline_fraction``;
- **registry entries**: archived ``tune --json`` artifacts whose
  ``--validate-top`` trials recorded ``measured_vs_model`` ratios.

Evidence only calibrates the chip kind it was measured on (a CPU
trial's ratio says nothing about a v5e), keyed through
``roofline.chip_spec`` so ``"TPU v5 lite"`` and ``"v5e"`` match. With
no applicable evidence the ratio is 1.0 (source ``"none"``) — the
tuner's ordering is what matters devicelessly; calibration sharpens the
absolute numbers where measurement exists.
"""

from __future__ import annotations

import dataclasses
import json
import os
import statistics
from typing import List, Optional, Sequence


@dataclasses.dataclass
class Calibration:
    """The per-chip measured-over-predicted ratio and where it came
    from. ``ratio`` multiplies every roofline step prediction."""

    ratio: float = 1.0
    source: str = "none"
    samples: int = 0


def _chip_key(device_kind: Optional[str]) -> Optional[str]:
    from tpu_ddp.analysis.roofline import chip_spec

    spec = chip_spec(device_kind)
    return spec.key if spec else None


def _ratio_from_bundle_meta(meta: dict, chip_key: str) -> Optional[float]:
    """measured/predicted for one profile bundle, or None when it does
    not apply (different chip kind, no measurement, a program the
    abstract builder can't rebuild locally)."""
    run_meta = meta.get("run_meta") or {}
    if _chip_key(run_meta.get("device_kind")) != chip_key:
        return None
    try:
        import jax

        from tpu_ddp.analysis.explain import anatomy_for_run_meta
        from tpu_ddp.analysis.roofline import roofline
        from tpu_ddp.profiler.device import measured_step_from_meta

        measured = measured_step_from_meta(meta)
        if not measured:
            return None
        n_needed = 1
        for s in (run_meta.get("mesh") or {}).values():
            n_needed *= s
        local = jax.devices()
        if n_needed > len(local):
            return None
        anatomy = anatomy_for_run_meta(run_meta, local[:n_needed])
        rl = roofline(anatomy, chip_key)
        if not rl.predicted_step_s:
            return None
        return measured / rl.predicted_step_s
    except Exception:
        return None  # evidence that can't be joined is skipped, never fatal


def _ratios_from_run_dir(run_dir: str, chip_key: str) -> List[float]:
    profiles = os.path.join(run_dir, "profiles")
    if not os.path.isdir(profiles):
        return []
    out: List[float] = []
    for entry in sorted(os.listdir(profiles)):
        meta_path = os.path.join(profiles, entry, "meta.json")
        if not os.path.isfile(meta_path):
            continue
        try:
            with open(meta_path) as f:
                meta = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        ratio = _ratio_from_bundle_meta(meta, chip_key)
        if ratio and ratio > 0:
            out.append(ratio)
    return out


def _ratio_from_analyze_artifact(path: str,
                                 chip_key: str) -> Optional[float]:
    """``tpu-ddp analyze <run_dir> --json``: the measured join's
    ``roofline_fraction`` is predicted/measured on the run's own chip."""
    try:
        with open(path) as f:
            art = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    anatomy = art.get("anatomy")
    measured = art.get("measured")
    if not isinstance(anatomy, dict) or not isinstance(measured, dict):
        return None
    kind = (art.get("run_meta") or {}).get("device_kind") \
        or anatomy.get("device_kind")
    if _chip_key(kind) != chip_key:
        return None
    fraction = measured.get("roofline_fraction")
    if isinstance(fraction, (int, float)) and fraction > 0:
        return 1.0 / fraction
    return None


def _ratios_from_registry(registry_dir: str, chip_key: str) -> List[float]:
    """Archived validated tune entries: each ``--validate-top`` trial
    recorded its own measured_vs_model on the trial's device kind."""
    from tpu_ddp.registry.store import read_entries

    out: List[float] = []
    try:
        entries = read_entries(registry_dir)
    except (OSError, ValueError):
        return []
    for entry in entries:
        if entry.artifact_kind != "tune":
            continue
        rec = (entry.programs or {}).get("tune") or {}
        for row in rec.get("validated") or ():
            if not isinstance(row, dict):
                continue
            if _chip_key(row.get("device_kind")) != chip_key:
                continue
            ratio = row.get("measured_vs_model")
            if isinstance(ratio, (int, float)) and ratio > 0:
                out.append(float(ratio))
    return out


def calibration_for_chip(
    chip: str,
    *,
    sources: Sequence[str] = (),
    registry_dir: Optional[str] = None,
) -> Calibration:
    """Gather every applicable measured-over-predicted sample for
    ``chip`` and reduce to the median. ``sources`` entries are run dirs
    (profile bundles inside) or ``analyze --json`` artifact files; a
    registry dir contributes validated tune entries."""
    chip_key = _chip_key(chip)
    if chip_key is None:
        raise ValueError(f"unknown chip {chip!r}")
    ratios: List[float] = []
    used: List[str] = []
    for src in sources:
        if os.path.isdir(src):
            found = _ratios_from_run_dir(src, chip_key)
        else:
            one = _ratio_from_analyze_artifact(src, chip_key)
            found = [one] if one else []
        if found:
            ratios.extend(found)
            used.append(os.path.basename(src.rstrip("/")) or src)
    if registry_dir:
        found = _ratios_from_registry(registry_dir, chip_key)
        if found:
            ratios.extend(found)
            used.append(f"registry:{registry_dir}")
    if not ratios:
        return Calibration()
    return Calibration(ratio=round(statistics.median(ratios), 4),
                       source="+".join(used), samples=len(ratios))


# -- HBM-cap calibration (the memory truth loop's food) -------------------

def _hbm_ratio_from_mem_record(rec: dict, chip_key: str) -> Optional[float]:
    """One ``tpu-ddp mem`` record's measured-over-planned HBM ratio, or
    None when it does not apply: wrong chip kind, no join, or NOT
    ``calibratable`` — live-array-accounted (CPU) measurements
    under-count the plan by the whole XLA workspace and must never
    shrink a real chip's cap (docs/memory.md)."""
    if not isinstance(rec, dict) or not rec.get("calibratable"):
        return None
    if _chip_key(rec.get("device_kind")) != chip_key:
        return None
    ratio = rec.get("measured_over_planned")
    if isinstance(ratio, (int, float)) and ratio > 0:
        return float(ratio)
    return None


def _hbm_ratio_from_artifact(path: str, chip_key: str) -> Optional[float]:
    try:
        with open(path) as f:
            art = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    mem = art.get("mem")
    if not isinstance(mem, dict):
        return None
    return _hbm_ratio_from_mem_record(mem, chip_key)


def hbm_calibration_for_chip(
    chip: str,
    *,
    sources: Sequence[str] = (),
    registry_dir: Optional[str] = None,
) -> Calibration:
    """The per-chip measured-over-planned HBM ratio the capacity gate
    multiplies into every candidate's compiled peak — the memory
    analogue of :func:`calibration_for_chip`'s time ratio. Evidence:
    ``tpu-ddp mem --json`` artifact files in ``sources`` and mem-kind
    registry entries; the median wins, 1.0 with no evidence."""
    chip_key = _chip_key(chip)
    if chip_key is None:
        raise ValueError(f"unknown chip {chip!r}")
    ratios: List[float] = []
    used: List[str] = []
    for src in sources:
        if os.path.isdir(src):
            continue  # run dirs carry time evidence, not mem artifacts
        one = _hbm_ratio_from_artifact(src, chip_key)
        if one:
            ratios.append(one)
            used.append(os.path.basename(src) or src)
    if registry_dir:
        from tpu_ddp.registry.store import read_entries

        try:
            entries = read_entries(registry_dir)
        except (OSError, ValueError):
            entries = []
        found = []
        for entry in entries:
            if entry.artifact_kind != "mem":
                continue
            one = _hbm_ratio_from_mem_record(
                (entry.programs or {}).get("mem") or {}, chip_key)
            if one:
                found.append(one)
        if found:
            ratios.extend(found)
            used.append(f"registry:{registry_dir}")
    if not ratios:
        return Calibration()
    return Calibration(ratio=round(statistics.median(ratios), 4),
                       source="+".join(used), samples=len(ratios))
