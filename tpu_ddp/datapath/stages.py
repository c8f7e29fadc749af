"""Staged input-pipeline attribution: the stage vocabulary and the live
per-host ``data-health-p<i>.json`` writer.

The loader decomposes into six named stages (the order they run per
batch); the first five are host work inside ``ShardedBatchLoader``,
the sixth is the Trainer's existing host→device transfer:

==========  =============================================================
stage       what it times
==========  =============================================================
``index``   drawing the next (indices, mask) pair from the epoch
            permutation (shuffle/wrap-pad/multihost row-slice math)
``gather``  ``gather_rows`` of images + labels out of the pinned arrays
``augment`` the optional host-side ``host_augment`` hook (the default
            pipeline augments on-device inside the jitted step, so this
            is a passthrough unless a hook is installed — but it is
            still a named, benchable, chaos-targetable stage)
``collate`` batch-dict assembly + mask materialization
``shard``   device-layout prep (``ascontiguousarray`` copies)
``h2d``     host→device transfer (the Trainer's existing ``h2d`` span)
==========  =============================================================

Each stage emits a ``data/<stage>`` telemetry span (nested inside the
Trainer's ``data_wait`` on the synchronous path) and reports to an
optional observer — :class:`StageMonitor` here — which maintains a
sliding per-stage throughput window and atomically rewrites
``data-health-p<i>.json`` so the fleet aggregator (and the DAT001
stage-throughput-collapse alert) can see live per-stage rates, and so
a wedged stage is named **on disk** while it is stuck: the in-flight
marker is written at stage *entry*, before the chaos stall hook runs,
exactly like the comms HopMonitor leaves its suspect collective behind.

Stdlib-only; safe to call from the background prefetcher thread.
"""

from __future__ import annotations

import glob
import json
import logging
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from tpu_ddp.comms.forensics import _atomic_write

log = logging.getLogger("tpu_ddp.datapath")

#: every stage of the input pipeline, in per-batch execution order
STAGES: Tuple[str, ...] = ("index", "gather", "augment", "collate", "shard", "h2d")

#: the stages that run on the host inside the loader (benchable standalone)
HOST_STAGES: Tuple[str, ...] = STAGES[:-1]

#: bump on any breaking change to the data-health record shape
DATA_HEALTH_SCHEMA_VERSION = 1

HEALTH_PREFIX = "data-health"


def data_health_file(run_dir: str, process_index: int = 0) -> str:
    return os.path.join(run_dir, f"{HEALTH_PREFIX}-p{process_index}.json")


class StageMonitor:
    """Per-host live data-path health: sliding-window per-stage rates,
    an in-flight marker, and a chaos stall seam.

    Implements the loader's observer protocol (``stage_enter`` /
    ``stage_exit``) plus the Trainer-facing ``on_step``/``close``.
    The health file is rewritten atomically and throttled to
    ``min_write_interval_s``, except that entering a *different* stage
    than last written forces a write — a stall anywhere leaves the
    suspect stage on disk for :func:`suspect_stage_from_files`.
    """

    def __init__(
        self,
        run_dir: str,
        *,
        process_index: int = 0,
        stall_hook: Optional[Callable[[str], None]] = None,
        telemetry: Any = None,
        window_s: float = 5.0,
        min_write_interval_s: float = 0.2,
    ) -> None:
        self.path = data_health_file(run_dir, process_index)
        self.process_index = int(process_index)
        self._stall_hook = stall_hook
        self._telemetry = telemetry
        self.window_s = float(window_s)
        self.min_write_interval_s = float(min_write_interval_s)
        self._lock = threading.Lock()
        # stage -> list of (t_end, seconds, nbytes), pruned to window_s
        self._windows: Dict[str, List[Tuple[float, float, int]]] = {s: [] for s in STAGES}
        self._in_flight: Optional[Dict[str, Any]] = None
        self._last_written_stage: Optional[str] = None
        self._step: Optional[int] = None
        self._last_write = 0.0
        self._write({}, time.monotonic(), force=True)

    def on_step(self, step: int) -> None:
        with self._lock:
            self._step = int(step)

    # -- loader observer protocol ------------------------------------

    def stage_enter(self, stage: str) -> None:
        now = time.monotonic()
        with self._lock:
            self._in_flight = {
                "stage": stage,
                "since_unix": time.time(),
                "step": self._step,
            }
            force = stage != self._last_written_stage
            rec = self._snapshot(now)
        self._write(rec, now, force=force)
        if force:
            self._last_written_stage = stage
        # the stall hook runs AFTER the health write: a fault that
        # sleeps here leaves the wedged stage named on disk while the
        # watchdog counts down
        if self._stall_hook is not None:
            self._stall_hook(stage)

    def stage_exit(self, stage: str, seconds: float, nbytes: int) -> None:
        now = time.monotonic()
        with self._lock:
            win = self._windows.setdefault(stage, [])
            win.append((now, float(seconds), int(nbytes)))
            cutoff = now - self.window_s
            while win and win[0][0] < cutoff:
                win.pop(0)
            if self._in_flight is not None and self._in_flight.get("stage") == stage:
                self._in_flight = None
            rec = self._snapshot(now)
        self._write(rec, now)
        tel = self._telemetry
        if tel is not None and win:
            span = max(now - win[0][0], 1e-9)
            tel.gauge(f"datapath/{stage}_batches_per_s").set(len(win) / span)
            tel.gauge(f"datapath/{stage}_s").set(float(seconds))

    # -- health record ------------------------------------------------

    def _snapshot(self, now: float) -> Dict[str, Any]:
        stages: Dict[str, Any] = {}
        for stage, win in self._windows.items():
            if not win:
                continue
            span = max(now - win[0][0], 1e-9)
            stages[stage] = {
                "batches_window": len(win),
                "bytes_window": int(sum(w[2] for w in win)),
                "busy_s_window": round(sum(w[1] for w in win), 6),
                "window_span_s": round(span, 3),
            }
        return {
            "data_health_schema_version": DATA_HEALTH_SCHEMA_VERSION,
            "updated_unix": time.time(),
            "process_index": self.process_index,
            "step": self._step,
            "stages": stages,
            "in_flight": dict(self._in_flight) if self._in_flight else None,
        }

    def _write(self, rec: Dict[str, Any], now: float, *, force: bool = False) -> None:
        if not force and now - self._last_write < self.min_write_interval_s:
            return
        if not rec:
            rec = self._snapshot(now)
        try:
            _atomic_write(self.path, rec)
            self._last_write = now
        except OSError as e:  # pragma: no cover - disk trouble must not kill training
            log.debug("data-health write failed: %s", e)

    def close(self) -> None:
        now = time.monotonic()
        with self._lock:
            rec = self._snapshot(now)
        self._write(rec, now, force=True)


# -- readers (forensics / aggregator side; no monitor required) --------


def read_data_health(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path, "r", encoding="utf-8") as f:
            rec = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    return rec if isinstance(rec, dict) else None


def data_health_files(run_dir: str) -> List[str]:
    pat = os.path.join(run_dir, f"{HEALTH_PREFIX}-p*.json")
    rx = re.compile(rf"{HEALTH_PREFIX}-p(\d+)\.json$")
    return sorted(p for p in glob.glob(pat) if rx.search(os.path.basename(p)))


def suspect_stage_from_files(run_dir: str) -> Optional[Dict[str, Any]]:
    """Name the stage most likely wedged, from the on-disk health files.

    Preference order: any host's in-flight stage (stalls leave it
    behind — see :meth:`StageMonitor.stage_enter`), else the slowest
    recently-seen stage by busy share. Returns ``None`` when no health
    files exist (data-path monitoring wasn't on).
    """
    best: Optional[Dict[str, Any]] = None
    for path in data_health_files(run_dir):
        rec = read_data_health(path)
        if rec is None:
            continue
        inf = rec.get("in_flight")
        if isinstance(inf, dict) and inf.get("stage"):
            return {
                "stage": inf["stage"],
                "process_index": rec.get("process_index"),
                "since_unix": inf.get("since_unix"),
                "source": "in_flight",
            }
        stages = rec.get("stages")
        if isinstance(stages, dict):
            for stage, view in stages.items():
                busy = float(view.get("busy_s_window", 0.0) or 0.0)
                if best is None or busy > best["_busy"]:
                    best = {
                        "stage": stage,
                        "process_index": rec.get("process_index"),
                        "since_unix": None,
                        "source": "slowest_window",
                        "_busy": busy,
                    }
    if best is not None:
        best.pop("_busy", None)
    return best
