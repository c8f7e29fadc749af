"""``device_backward_ms``: device time per optimizer step of the operations
that the program's map (``chipbench/scopes.py``) gives the phase
``backward``: built by the transpose of the forward's linearization
(``transpose(jvp(`` in the ``op_name``), gradient accumulation included.
None without a map of the traced program."""

from chipbench import scopes

NAME, UNIT, SOURCE = "device_backward_ms", "ms", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"


def read(run):
    return scopes.phase_ms(run, "backward")
