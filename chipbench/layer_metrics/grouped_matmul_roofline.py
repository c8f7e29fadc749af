"""``grouped_matmul_roofline``: the share of its roofline that the grouped
expert product reaches over a step's calls. ``models/moe.py::grouped_matmul``
is ``jax.lax.ragged_dot``, for which XLA:TPU emits a Mosaic kernel of its
own; that custom call keeps none of the program's scopes, only the
compiler's name for it (``ragged-dot-none.<n>``), which is how its calls
are found here: eight a sparse layer with the layer recomputed (the input
product of hidden x 2 expert widths and the output product, each forward,
forward again, and backward by rows and by weights). The small custom calls
that lay out the groups for them (``ragged-dot-metadata.<n>``) add their
time and no work. Least time from shapes (``chipbench/kernel_costs.py``) at
the rows the program's counters say really landed on the held experts; all
four calls of a product move the same operands and do the same operations.
None where the traced program calls no such kernel or keeps no counters."""

from chipbench import kernel_costs, scopes

NAME, UNIT, SOURCE = "grouped_matmul_roofline", "%", "device_trace"
LAYER = "kernels"
MOVES = "images_per_s_per_chip"
COMPILER_NAME = "ragged-dot-"
LAYOUT_CALLS = "ragged-dot-metadata"


def read(run):
    found = kernel_costs.compiler_kernel_calls(run, COMPILER_NAME)
    shapes = kernel_costs.cell_shapes(run.record)
    peaks = kernel_costs.peaks_of(run.record)
    if found is None or shapes is None or peaks is None:
        return None
    rows = kernel_costs.landed_rows_per_layer(run, shapes)
    if rows is None:
        return None
    layout = kernel_costs.compiler_kernel_calls(run, LAYOUT_CALLS) or {}
    arch = shapes["arch"]
    c, f = arch["hidden_size"], arch["moe_intermediate_size"]
    kinds = [dict(contraction=c, columns=2 * f), dict(contraction=f, columns=c)]
    per_call = sum(kernel_costs.least_seconds(*kernel_costs.grouped_call(
        rows=rows, held=arch["num_experts"], **kind), peaks)
        for kind in kinds) / len(kinds)
    calls = (sum(n for n, _ in found.values())
             - sum(n for n, _ in layout.values()))
    spent = sum(s for _, s in found.values())
    scopes.say(f"kernel grouped_matmul: {calls} calls a step in "
               f"{sorted(found)}, {spent * 1e3!r} ms a step, {rows!r} real "
               f"rows a layer, least {per_call * 1e3!r} ms a call")
    return 100.0 * calls * per_call / spent if spent and calls else None
