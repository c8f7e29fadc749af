"""``trace_lower_s``: seconds the run spent tracing functions to jaxprs and
lowering them to MLIR modules, from the program's own counters
(``jax/trace_seconds`` + ``jax/lower_seconds`` of the run-end counters record
in the traced run's telemetry): the part of set-up that ``compile_s`` does not
count. The per-function table goes on earlier lines of the run
(``chipbench/scopes.py``). None where the program keeps no such counters."""

from chipbench import scopes

NAME, UNIT, SOURCE = "trace_lower_s", "s", "program_counter"
LAYER = "compiler and device"
MOVES = "setup_s"


def read(run):
    return scopes.trace_lower_s(scopes.of_run(run)["counters"])
