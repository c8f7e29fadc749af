"""``device_forward_ms``: device time per optimizer step of the operations
that the program's map (``chipbench/scopes.py``) gives the phase ``forward``:
built inside the step builder's forward scope and not by the transpose of
its linearization. None without a map of the traced program."""

from chipbench import scopes

NAME, UNIT, SOURCE = "device_forward_ms", "ms", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"


def read(run):
    return scopes.phase_ms(run, "forward")
