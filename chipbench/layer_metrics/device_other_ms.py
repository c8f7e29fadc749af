"""``device_other_ms``: device time per optimizer step that the scopes do not
yet explain: the phases ``input`` (in-graph augment and mixup) and ``other``
(metrics, health, operations the compiler made and no neighbour names) of
the program's map (``chipbench/scopes.py``), and operations whose names the
map lacks. With the four phase metrics it sums to ``device_step_ms``. None
without a map of the traced program."""

from chipbench import scopes

NAME, UNIT, SOURCE = "device_other_ms", "ms", "device_trace"
LAYER = "step builders"
MOVES = "images_per_s_per_chip"


def read(run):
    return scopes.phase_ms(run, "input", "other", unmapped=True)
