"""``collective_exposed_ms``: time per optimizer step in which a collective
is in flight on a chip and no other operation runs there, averaged over the
chips (``chipbench/xplane.py``). A trace without a collective reads nothing;
the total collective time per step goes on an earlier line of the run."""

NAME, UNIT, SOURCE = "collective_exposed_ms", "ms", "device_trace"
LAYER = "step builders"
MOVES = "images_per_s_per_chip"


def read(run):
    if run.trace is None:
        return None
    return run.trace.get("collective_exposed_ms")
