"""``dispatch_ms``: host time to enqueue one step: the telemetry span
``compiled_step`` around the call of the compiled step, summed over the
traced slice and divided by its optimizer steps. In a traced run the
telemetry fences every step, so the enqueue never waits for a full queue."""

NAME, UNIT, SOURCE = "dispatch_ms", "ms", "program_span"
LAYER = "run loop"
MOVES = "images_per_s_per_chip"


def read(run):
    spans = [s for s in run.record.get("host_spans", ())
             if s[0] == "compiled_step"]
    steps = run.record.get("steps")
    if not spans or not steps:
        return None
    return sum(end - start for _, start, end in spans) * 1e3 / steps
