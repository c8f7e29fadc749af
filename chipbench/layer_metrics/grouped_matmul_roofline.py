"""``grouped_matmul_roofline``: the share of its roofline that the grouped
expert product reaches over a step's calls. ``models/moe.py::grouped_matmul``
is ``jax.lax.ragged_dot``, for which XLA:TPU emits a Mosaic kernel of its
own; that custom call keeps none of the program's scopes, only the
compiler's name for it (``ragged-dot-none.<n>``), which is how its calls
are found here: eight a routed layer body with the layer recomputed (an
expert's input product and its output product, each forward, forward again,
and backward by rows and by weights). The small custom calls that lay out
the groups for them (``ragged-dot-metadata.<n>``) add their time and no
work. Least time from shapes (``chipbench/kernel_costs.py``: the two
products as the cell's configuration states them, gated or plain, of the
hidden or of the latent width) at the rows the program's counters say really
landed on the held experts of a body. None where the traced program calls no
such kernel or keeps no counters, or the cell's files name no routed
experts."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "grouped_matmul_roofline", "%", "device_trace"
LAYER = "kernels"
MOVES = "images_per_s_per_chip"
COMPILER_NAME = "ragged-dot-"
LAYOUT_CALLS = "ragged-dot-metadata"


def read(run):
    return kernel_costs.grouped_roofline(run, COMPILER_NAME, LAYOUT_CALLS)
