"""The step stamper: when each dispatched step *finished*, seen off the
main thread.

The run loop dispatches a step and goes on: the device works through a
queue of them while the host prepares the next (``Trainer._run_loop``).
To know when a step completed the loop would have to wait for it, and a
loop that waits every step is another loop than the one that runs with
telemetry off. So the waiting is done here, on one daemon thread: after
each dispatch the loop hands over ``(step, steps, returned_s, array)``
through a queue and makes no device call of its own; the thread takes
them in order, waits for the array (``wait`` is ``jax.block_until_ready``:
it releases the GIL while it waits, and a replicated array waits for the
slowest chip), reads the telemetry's clock, and emits

- span ``device_step``: start = the later of the dispatch's return and
  the previous completion, end = this completion. The spans of a run tile
  the time in which the host knows the device had work: no two overlap,
  their sum is the device's busy time as the host sees it, the gaps
  between them are the time the device starved. ``step`` is the id the
  ``data_wait`` / ``h2d`` / ``compiled_step`` spans of the same iteration
  carry; attrs ``steps`` (optimizer steps in the dispatch, where > 1) and
  ``ahead`` (dispatches made and not yet complete when this one returned,
  itself included). Recorded into ``phase/device_step`` like every span.
- counter ``train/device_starved_seconds``: the gaps, summed as they close.

The readers of a trace take the span's name from here, and the rule that
goes with it (``covered_s``, ``uncovered_share``): a span of the loop's
thread costs the run only the part of it that no ``device_step`` covers.

Stdlib-only like the rest of the package: the caller brings ``wait``.
"""

from __future__ import annotations

import bisect
import logging
import queue
import threading
from typing import Any, Callable, Iterable, List, Optional, Sequence, Tuple

log = logging.getLogger(__name__)

SPAN = "device_step"
STARVED = "train/device_starved_seconds"
#: how long ``close`` waits for the steps still in flight before it gives
#: the thread up (a daemon: it cannot keep the process alive)
CLOSE_TIMEOUT_S = 30.0


class StepStamper:
    """One per ``Trainer.run`` with telemetry on; ``dispatched`` is the
    main thread's, everything else happens on the stamper's own thread."""

    def __init__(self, telemetry, wait: Callable[[Any], Any], *,
                 close_timeout_s: float = CLOSE_TIMEOUT_S):
        self._tel = telemetry
        self._wait = wait
        self._close_timeout_s = close_timeout_s
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._dispatched = 0   # written by the main thread only
        self._completed = 0    # written by the stamper thread only
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="tpu_ddp-step-stamper", daemon=True)
        self._thread.start()

    def dispatched(self, step, steps: int, returned_s: float, array) -> None:
        """The loop's side: dispatch ``step`` (``steps`` optimizer steps)
        returned at ``returned_s`` on the telemetry's clock and ``array``
        is ready when it has run. No device call, no waiting."""
        self._dispatched += 1
        ahead = self._dispatched - self._completed
        self._queue.put((step, steps, returned_s, ahead, array))

    def _run(self) -> None:
        tel = self._tel
        clock = tel.clock
        starved = tel.counter(STARVED)
        last_end = None
        while True:
            item = self._queue.get()
            if item is None:
                return
            step, steps, returned_s, ahead, array = item
            try:
                self._wait(array)
            except Exception as e:  # the loop meets the same error itself
                log.warning("step stamper: step %s did not complete: %s",
                            step, e)
                self._completed += 1
                continue
            end = clock.now()
            self._completed += 1
            start = returned_s
            if last_end is not None:
                if returned_s > last_end:
                    starved.inc(returned_s - last_end)
                else:
                    start = last_end
            # a step that was over before its dispatch was seen to return
            # (the loop read the clock late) has no length, not a negative
            end = max(end, start)
            last_end = end
            attrs = {"ahead": ahead}
            if steps > 1:
                attrs["steps"] = steps
            tel.emit_span(SPAN, start, end, step=step, attrs=attrs)

    def close(self) -> None:
        """Drain, then join: every step handed over is stamped first. A
        step that never completes holds ``close`` for ``close_timeout_s``
        at most; the thread is then left behind. Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._queue.put(None)
        self._thread.join(self._close_timeout_s)
        if self._thread.is_alive():
            log.warning(
                "step stamper: %d step(s) still in flight after %.0f s; "
                "their device_step spans are lost",
                self._dispatched - self._completed, self._close_timeout_s)


def covered_s(start: float, end: float, starts: Sequence[float],
              ends: Sequence[float]) -> float:
    """Seconds of ``[start, end]`` inside the sorted, disjoint intervals
    ``zip(starts, ends)``: the ``device_step`` spans of one trace."""
    total = 0.0
    for i in range(bisect.bisect_right(ends, start), len(starts)):
        if starts[i] >= end:
            break
        total += min(end, ends[i]) - max(start, starts[i])
    return total


def uncovered_share(spans: Iterable[Tuple[float, float]],
                    device: List[Tuple[float, float]]) -> Optional[float]:
    """What ``spans`` (``(start, end)`` each, of the loop's thread) cost
    the run, as a share of the wall time that ``device`` (the
    ``device_step`` spans, oldest first) reaches over: only the part of a
    span that no ``device_step`` covers is counted, so host work hidden
    behind a busy device reads 0. A span that starts before the first
    ``device_step`` or ends after the last is not judged: what covers it
    is not known (yet). None without two ends to measure between."""
    if not device or device[-1][1] <= device[0][0]:
        return None
    first, horizon = device[0][0], device[-1][1]
    starts = [a for a, _ in device]
    ends = [b for _, b in device]
    uncovered = sum(
        (end - start) - covered_s(start, end, starts, ends)
        for start, end in spans if start >= first and end <= horizon)
    return uncovered / (horizon - first)
