"""Anomaly-triggered profiler: capture windows, host stacks, device traces.

The monitor (``tpu_ddp/monitor/``) can say *that* a run is slow — a host
straggles (STR001), throughput collapsed (THR001), the loop is
input-bound (DWT001) — and the analysis layer (``tpu_ddp/analysis/``)
predicts what a step *should* cost. This package closes the loop with
evidence for *why* a live run is slow:

- ``capture``  — a :class:`CaptureManager` in each training process arms
  a window of N steps three ways (``--profile-steps A:B``, ``POST
  /profile`` on the monitor exporter, or the ``capture_profile`` alert
  action auto-firing off STR001/THR001/DWT001) and writes a
  schema-versioned bundle to ``<run_dir>/profiles/step_<n>-p<i>/``.
- ``host``     — a stdlib-only sampling profiler over every thread
  (``sys._current_frames`` at a fixed Hz): flamegraph-compatible folded
  stacks plus a self-time top-frames table — the thing that turns a
  DWT001 data-wait alert into the actual Python frame burning the time,
  on any backend.
- ``device``   — ``jax.profiler.trace`` arming for the window (degrading
  to a note where unsupported). The trace's operations are named by the
  run's program map (``telemetry/program_map.py``), not modelled here.
- ``report``   — ``tpu-ddp profile <run_dir>``: renders bundles (trigger
  provenance, top stacks, where the device trace and the program map are)
  and, across >= 2 hosts, the straggler diff — the frames the flagged host
  shows that the fleet median doesn't.

Module-level stdlib-only (jax imports are lazy), so the watch/report
side runs wherever the run dir lands. See ``docs/profiling.md``.
"""

from tpu_ddp.profiler.capture import (
    PROFILE_SCHEMA_VERSION,
    CaptureManager,
    list_bundles,
    parse_profile_steps,
    post_profile_trigger,
    read_bundle_meta,
)
from tpu_ddp.profiler.host import HostSampler, frame_shares, top_frames
from tpu_ddp.profiler.report import straggler_diff

__all__ = [
    "PROFILE_SCHEMA_VERSION",
    "CaptureManager",
    "HostSampler",
    "frame_shares",
    "list_bundles",
    "parse_profile_steps",
    "post_profile_trigger",
    "read_bundle_meta",
    "straggler_diff",
    "top_frames",
]
