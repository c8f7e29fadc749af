"""Device-side capture: ``jax.profiler`` arming for a capture window.

``start_device_trace``/``stop_device_trace`` wrap
``jax.profiler.start_trace`` for the capture window. Where the backend (or
the jax build) has no profiler support the arm degrades to a *note*
recorded in the bundle manifest — never an error: the host sampler still
captures.

What each device operation of the trace belongs to is not worked out here:
with telemetry on the ``Trainer`` writes the program map
(``telemetry/program_map.py``, ``programs-p<i>.jsonl`` in the run
directory), which names the phase and module of every instruction, and a
capture's ``.xplane.pb`` is read beside it (``docs/profiling.md``). The
per-op table this module once made spread the window's measured span over
a cost model's rows in proportion: it looked measured and was modelled,
and is gone.

``measured_step_from_meta`` stays: the window's measured per-step span
time, which the tuner's calibration reads.
"""

from __future__ import annotations

import logging
from typing import Optional

log = logging.getLogger(__name__)


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


# -- the window's own measurement ------------------------------------------

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
