"""``device_step_ms``: time the core is busy per optimizer step: the union of
the ``XLA Ops`` intervals on the first chip's plane of the traced slice,
divided by the slice's optimizer steps (``chipbench/xplane.py``)."""

NAME, UNIT, SOURCE = "device_step_ms", "ms", "device_trace"
LAYER = "step builders"
MOVES = "images_per_s_per_chip"


def read(run):
    return None if run.trace is None else run.trace.get("device_step_ms")
