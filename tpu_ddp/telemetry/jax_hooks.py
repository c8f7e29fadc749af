"""jax.monitoring -> registry bridge: where the set-up seconds went.

jax reports the three stages a jitted function goes through before it runs,
each with the function's name (``fun_name``): tracing to a jaxpr, lowering
to an MLIR module, and the backend's compile, which is a load where the
persistent cache holds the program. This module counts them, in total and
per function, into the process-wide registry:

  - ``jax/trace_seconds``, ``jax/lower_seconds``, ``jax/compile_seconds``
    (histograms: ``sum`` is the seconds, ``count`` the events). Traces nest
    (``shard_step``'s trace holds the traces of every jitted helper the
    model calls), so the trace histogram records each trace's *own*
    seconds, without the traces nested inside it: its ``sum`` is time that
    passed, not time counted twice. The compile histogram holds every
    ``backend_compile_duration``, loads included: it is the time the stage
    took, which is what the goodput ledger charges.
  - ``jax/compilations``: programs the backend really compiled;
    ``jax/cache_loads`` and ``jax/cache_load_seconds``: programs it loaded
    from the persistent cache instead. A ``backend_compile_duration`` that
    follows a ``/jax/compilation_cache/cache_hits`` event on the same
    thread is a load.
  - ``jax/cache/<event>``: persistent-cache traffic, one counter per event
    name (``tpu_ddp/diagnose/rules.py`` reads these for its churn verdict).
  - the table ``jax/functions``: per function ``trace_seconds`` (as jax
    reports it, nested traces included), ``trace_self_seconds`` (without
    them), ``traces``, ``lower_seconds``, ``lowerings``,
    ``compile_seconds``, ``compilations``, ``cache_load_seconds``,
    ``cache_loads``. Tracing names a function
    ``shard_step``, lowering and compiling ``jit(shard_step)``: one row,
    under the bare name. Only the run-end counters record carries the table.

A slow step is often a *recompiling* step (a shape leaked into a jit
boundary, a donated buffer changed layout), and a slow start a function
traced more than once: the table says which.

Event names verified on jax 0.9.0 (``jax/_src/dispatch.py``,
``jax/_src/compiler.py``):
  - ``/jax/core/compile/jaxpr_trace_duration``,
    ``/jax/core/compile/jaxpr_to_mlir_module_duration``,
    ``/jax/core/compile/backend_compile_duration`` (duration listeners);
  - ``/jax/compilation_cache/...`` (event listener).

Kept separate from telemetry.core so everything else in the package stays
importable without jax (launcher, summarize CLI).
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time

from tpu_ddp.telemetry.registry import default_registry

log = logging.getLogger(__name__)

FUNCTIONS_TABLE = "jax/functions"
#: traces that ended lately on a thread, kept to find the nested ones
_RECENT_TRACES = 64
_CACHE_PREFIX = "/jax/compilation_cache/"
_CACHE_HIT = _CACHE_PREFIX + "cache_hits"

_installed = False
#: .hit: a cache hit awaits its compile event; .traces: (end, seconds) of
#: the traces that ended lately on this thread, innermost last
_local = threading.local()
_paused = False


def function_name(fun_name) -> str:
    """``jit(shard_step)`` -> ``shard_step``: one row per function."""
    name = str(fun_name or "?")
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    return name


@contextlib.contextmanager
def paused():
    """Stop counting for a while: the program-map export lowers the step a
    second time to read its text, which is not the run's own work."""
    global _paused
    was, _paused = _paused, True
    try:
        yield
    finally:
        _paused = was


def _own_seconds(duration: float) -> float:
    """The part of a trace that was not spent in the traces nested inside
    it. A listener hears a trace when it ends, the nested ones first: every
    trace that ended on this thread after this one began is inside it."""
    now = time.perf_counter()
    recent = getattr(_local, "traces", None)
    if recent is None:
        recent = _local.traces = []
    nested = 0.0
    while recent and recent[-1][0] > now - duration:
        nested += recent.pop()[1]
    recent.append((now, duration))
    del recent[:-_RECENT_TRACES]
    return max(duration - nested, 0.0)


def _on_duration(name: str, duration: float, **kw) -> None:
    if _paused:
        return
    stage = name.rsplit("/", 1)[-1]
    reg = default_registry()
    fun = function_name(kw.get("fun_name"))
    table = reg.table(FUNCTIONS_TABLE)
    if stage == "jaxpr_trace_duration":
        own = _own_seconds(duration)
        reg.histogram("jax/trace_seconds").record(own)
        table.add(fun, "trace_seconds", duration)
        table.add(fun, "trace_self_seconds", own)
        table.add(fun, "traces")
    elif stage == "jaxpr_to_mlir_module_duration":
        reg.histogram("jax/lower_seconds").record(duration)
        table.add(fun, "lower_seconds", duration)
        table.add(fun, "lowerings")
    elif stage == "backend_compile_duration":
        reg.histogram("jax/compile_seconds").record(duration)
        loaded = getattr(_local, "hit", False)
        _local.hit = False
        if loaded:
            reg.counter("jax/cache_loads").inc()
            reg.histogram("jax/cache_load_seconds").record(duration)
            table.add(fun, "cache_load_seconds", duration)
            table.add(fun, "cache_loads")
        else:
            reg.counter("jax/compilations").inc()
            table.add(fun, "compile_seconds", duration)
            table.add(fun, "compilations")


def _on_event(name: str, **kw) -> None:
    if _paused or not name.startswith(_CACHE_PREFIX):
        return
    if name == _CACHE_HIT:
        _local.hit = True
    short = name[len(_CACHE_PREFIX):]
    default_registry().counter(f"jax/cache/{short}").inc()


def install_jax_hooks() -> bool:
    """Register jax.monitoring listeners feeding the default registry.

    Idempotent (listeners are process-global and cannot be unregistered,
    so they are installed once and always write to ``default_registry()``
    — which tests may swap via ``reset_default_registry``). Returns True
    when the hooks are (already) installed, False when this jax has no
    monitoring API.
    """
    global _installed
    if _installed:
        return True
    try:
        from jax import monitoring
    except ImportError:
        return False
    if not hasattr(monitoring, "register_event_duration_secs_listener"):
        return False
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _installed = True
    return True
