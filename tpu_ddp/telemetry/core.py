"""The Telemetry object: spans + counters wired to pluggable sinks.

One ``Telemetry`` instance per run (the Trainer owns it); the disabled
``NULL`` singleton makes every call a cheap no-op so instrumented code
never branches on "is telemetry on". Stdlib-only — the launcher and the
summarize CLI import this without pulling in jax.
"""

from __future__ import annotations

import threading
from typing import Iterator, Optional, Sequence

import contextlib

from tpu_ddp.telemetry.events import (
    COUNTERS,
    INSTANT,
    SPAN,
    Clock,
    Event,
    pop_span,
    push_span,
)
from tpu_ddp.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    default_registry,
)


class Telemetry:
    """Event emitter + registry facade.

    Spans also record into the registry histogram ``phase/<name>`` so the
    end-of-run counters snapshot carries the same per-phase distribution
    the sinks saw.
    """

    def __init__(
        self,
        sinks: Sequence = (),
        *,
        registry: Optional[Registry] = None,
        process_index: int = 0,
        enabled: bool = True,
        clock: Optional[Clock] = None,
    ):
        self.enabled = enabled and bool(sinks)
        self.sinks = list(sinks)
        self.registry = registry if registry is not None else default_registry()
        self.process_index = process_index
        self.clock = clock or Clock()
        self.current_step: Optional[int] = None
        self._closed = False
        # high-rate window taps (the anomaly profiler's capture manager):
        # each listener sees every span's (name, dur_s) as it closes —
        # how a capture window measures its own per-phase times without
        # re-reading the JSONL it is being written into
        self._span_listeners: list = []

    # -- spans / events ---------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, step: Optional[int] = None,
             **attrs) -> Iterator[None]:
        """Time a phase; emits one SPAN event on exit. Nesting is tracked
        per thread and recorded as ``depth`` (Chrome viewers stack slices
        on the same tid by time containment; depth makes nesting explicit
        for the JSONL consumers)."""
        if not self.enabled:
            yield
            return
        depth = push_span()
        t0 = self.clock.now()
        try:
            yield
        finally:
            end = self.clock.now()
            pop_span()
            self.emit_span(name, t0, end, step=step, depth=depth,
                           attrs=attrs)

    def emit_span(self, name: str, start_s: float, end_s: float, *,
                  step: Optional[int] = None, depth: int = 0,
                  attrs: Optional[dict] = None) -> None:
        """One SPAN event between two readings of ``self.clock``, on the
        calling thread's row: what ``span`` does on exit, and the way in
        for a span whose ends were stamped elsewhere (the step stamper's
        ``device_step``). Recorded into ``phase/<name>`` and shown to
        the span listeners like every span."""
        if not self.enabled:
            return
        dur = end_s - start_s
        self._emit(Event(
            name=name,
            kind=SPAN,
            ts_s=start_s,
            dur_s=dur,
            step=self.current_step if step is None else step,
            process_index=self.process_index,
            thread_id=threading.get_ident() & 0xFFFF,
            depth=depth,
            attrs=attrs or {},
        ))
        self.registry.histogram(f"phase/{name}").record(dur)
        for listener in list(self._span_listeners):
            try:
                listener(name, dur)
            except Exception:  # a broken tap must never kill training
                pass

    def instant(self, name: str, step: Optional[int] = None,
                **attrs) -> None:
        """Point event (e.g. "profiler_trace_written", "watchdog_hang")."""
        if not self.enabled:
            return
        self._emit(Event(
            name=name,
            kind=INSTANT,
            ts_s=self.clock.now(),
            step=self.current_step if step is None else step,
            process_index=self.process_index,
            thread_id=threading.get_ident() & 0xFFFF,
            attrs=attrs,
        ))

    def emit_counters(self, step: Optional[int] = None, *,
                      name: str = "counters", tables: bool = False) -> None:
        """Snapshot the registry into the sinks (JSONL record + Chrome "C"
        series). Call at natural boundaries (epoch end, run end); the
        Trainer's mid-epoch cadence passes ``name="counters_snapshot"``
        so readers can tell a periodic tail from a clean-shutdown
        snapshot. ``tables`` adds the per-row tables (per-function compile
        accounting); only the run-end record carries them."""
        if not self.enabled:
            return
        snap = self.registry.snapshot(tables=tables)
        self._emit(Event(
            name=name,
            kind=COUNTERS,
            ts_s=self.clock.now(),
            step=self.current_step if step is None else step,
            process_index=self.process_index,
            thread_id=threading.get_ident() & 0xFFFF,
            attrs=snap,
        ))

    def _emit(self, event: Event) -> None:
        for sink in self.sinks:
            try:
                sink.emit(event)
            except Exception:  # a broken sink must never kill training
                pass

    # -- registry facade --------------------------------------------------

    def counter(self, name: str) -> Counter:
        return self.registry.counter(name)

    def gauge(self, name: str) -> Gauge:
        return self.registry.gauge(name)

    def histogram(self, name: str) -> Histogram:
        return self.registry.histogram(name)

    def count(self, name: str, n: float = 1) -> None:
        if self.enabled:
            self.registry.counter(name).inc(n)

    def record_model_counters(self, per_step) -> dict:
        """What the model counted in some steps, reduced per name on the
        host. ``per_step`` holds one ``{name: array}`` a step: what the
        layers sowed into ``counters`` and the step handed out with its
        metrics (``train/steps.py::sum_counters``), already fetched. For
        every name:

        ``model/<name>_sum``    gauge: the array's sum in a step
        ``model/<name>_mean``   gauge: its mean element in a step
        ``model/<name>_max``    gauge: its largest element in a step
        ``model/<name>_total``  counter: the sum over all these steps

        each gauge the mean over the steps. Returns the gauges (also where
        telemetry is off: ``Trainer.run``'s result carries them)."""
        import numpy as np

        out = {}
        for name in sorted({k for counted in per_step for k in counted}):
            steps = [np.asarray(c[name]) for c in per_step if name in c]
            stats = {"sum": [x.sum() for x in steps],
                     "mean": [x.mean() for x in steps],
                     "max": [x.max() for x in steps]}
            for stat, values in stats.items():
                out[f"model/{name}_{stat}"] = float(np.mean(values))
            self.count(f"model/{name}_total", float(np.sum(stats["sum"])))
        if self.enabled:
            for key, value in out.items():
                self.registry.gauge(key).set(value)
        return out

    # -- span listeners (capture windows) ---------------------------------

    def add_span_listener(self, listener) -> None:
        """Register a ``(name, dur_s)`` callback fired as each span
        closes — the profiler's capture window taps the live stream for
        its measured-phase record. No-op stream when disabled (spans
        never fire)."""
        self._span_listeners.append(listener)

    def remove_span_listener(self, listener) -> None:
        try:
            self._span_listeners.remove(listener)
        except ValueError:
            pass

    # -- lifecycle --------------------------------------------------------

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.enabled:
            # clean-shutdown marker: the fleet aggregator uses it to tell
            # an ENDED host (trace goes quiet because the run finished)
            # from a LOST one (trace goes quiet because the host died)
            self.instant("run_end")
            self.emit_counters(tables=True)
        for sink in self.sinks:
            try:
                sink.close()
            except Exception:
                pass


#: Shared disabled instance: every method is a no-op.
NULL = Telemetry(sinks=(), enabled=False)
