"""``input_ms``: host time a step spends getting its batch: the telemetry
spans ``data_wait`` (waiting for the loader) and ``h2d`` (the ``device_put``),
summed over the traced slice and divided by its optimizer steps."""

NAME, UNIT, SOURCE = "input_ms", "ms", "program_span"
LAYER = "run loop"
MOVES = "images_per_s_per_chip"


def read(run):
    spans = [s for s in run.record.get("host_spans", ())
             if s[0] in ("data_wait", "h2d")]
    steps = run.record.get("steps")
    if not spans or not steps:
        return None
    return sum(end - start for _, start, end in spans) * 1e3 / steps
