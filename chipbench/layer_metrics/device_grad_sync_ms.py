"""``device_grad_sync_ms``: device time per optimizer step of the operations
that the program's map (``chipbench/scopes.py``) gives the phase
``grad_sync``: every collective whatever scope it sits in, the BatchNorm
statistics' mean across replicas, the compression ring and its codec. Time
on the core's own line, so a collective that another operation hides is
counted once, under the collective. None without a map of the traced
program; reported by the cells on several chips."""

from chipbench import scopes

NAME, UNIT, SOURCE = "device_grad_sync_ms", "ms", "device_trace"
LAYER = "step builders"
MOVES = "images_per_s_per_chip"


def read(run):
    return scopes.phase_ms(run, "grad_sync")
