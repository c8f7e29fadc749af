"""``device_optimizer_ms``: device time per optimizer step of the operations
that the program's map (``chipbench/scopes.py``) gives the phase
``optimizer``: the update scopes of the step builders and the kernels
inside them. None without a map of the traced program."""

from chipbench import scopes

NAME, UNIT, SOURCE = "device_optimizer_ms", "ms", "device_trace"
LAYER = "step builders"
MOVES = "images_per_s_per_chip"


def read(run):
    return scopes.phase_ms(run, "optimizer")
