"""``compile_s``: seconds of backend compilation during set-up, summed from
``jax.monitoring``'s ``backend_compile_duration`` events (a program loaded
from the persistent cache does not fire it). The count of compilations and
the cache traffic go on an earlier line of the run."""

NAME, UNIT, SOURCE = "compile_s", "s", "program_counter"
LAYER = "compiler and device"
MOVES = "setup_s"


def read(run):
    return run.record.get("compile_s")
