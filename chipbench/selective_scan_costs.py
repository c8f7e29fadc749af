"""What the selective scan of a Mamba-1 mixer (``tpu_ddp/ops/selective_scan.py``,
two Pallas kernels) has to do, from shapes: the operations and bytes of one
call, forward and backward, for its share of its roofline. What every
kernel's costs share (the lookup of a cell's own files, peaks, the least
time, the calls of a kernel in a traced run) is ``kernel_costs.py``'s and
taken from there.

Operations are the recurrence's own, a (position, channel, state): forward
six (the decay's product; the state's two products and sum; the output's
product and sum; the exponential is the transcendental unit's and not
counted), backward nineteen (the block's states again, four; the decay's
product; the state's gradient, two; ``dC`` and ``dB``, ``dx``, ``ddt`` and
``dA``, a product and a sum each; the two products that carry the gradient
to the position before). Bytes are each operand read once and each result
written once, as the program hands them over: ``x``, ``y``, ``dy`` and
``dx`` in bfloat16, ``dt`` and ``ddt`` in float32, ``B`` and ``C`` and
their gradients in bfloat16, ``A`` and ``dA`` in float32, and the state at
each time block's start, float32, a result of the forward call and an
operand of the backward one. What the kernels move beyond that (float32
copies of ``x`` and ``y``, ``B`` and ``C`` replicated over lanes) is their
waste and shows as a lower share.

Neither published peak bounds this work: it runs on the vector unit, whose
rate ``peaks.json`` does not state, so the bound is the memory's and the
share reads low. Plain files and the stdlib; nothing here imports the
program.
"""

from __future__ import annotations

from chipbench import kernel_costs, scopes

#: positions between two kept states (``ops/selective_scan.py::BLOCK_T``)
CHECKPOINT_EVERY = 128
#: operations a (position, channel, state), forward kernel first
FLOPS = {"selective_scan_fwd": 6, "selective_scan_bwd": 19}
SCAN_KERNELS = tuple(FLOPS)


def scan_call(kernel: str, *, batch, tokens, channels, state) -> tuple:
    """(operations, bytes) of one call of a scan kernel."""
    if kernel not in FLOPS:
        raise ValueError(f"no scan kernel {kernel!r}")
    cells = batch * tokens * channels
    checkpoints = (batch * -(-tokens // CHECKPOINT_EVERY) * channels * state
                   * 4)
    bc_like = batch * tokens * state * kernel_costs.BYTES
    a_like = channels * state * 4
    if kernel == "selective_scan_fwd":   # x dt A B C -> y, checkpoints
        moved = (cells * (2 * kernel_costs.BYTES + 4) + a_like + 2 * bc_like
                 + checkpoints)
    else:         # x dt A B C dy checkpoints -> dx ddt dA dB dC
        moved = (cells * (3 * kernel_costs.BYTES + 2 * 4) + 2 * a_like
                 + 4 * bc_like + checkpoints)
    return float(FLOPS[kernel] * cells * state), float(moved)


def scan_shapes(record):
    """``batch``, ``tokens``, ``channels`` and ``state`` of a call in the
    cell the run was of; None where its configuration names no Mamba-1
    sizes (``mamba_expand``, ``mamba_d_state``): never a guess."""
    cell = kernel_costs.cell_files(record)
    if cell is None or not cell["tokens"]:
        return None
    arch = cell["arch"]
    if not {"mamba_expand", "mamba_d_state", "hidden_size"} <= set(arch):
        return None
    return dict(batch=cell["batch"], tokens=cell["tokens"],
                channels=arch["mamba_expand"] * arch["hidden_size"],
                state=arch["mamba_d_state"])


def scan_calls(run, kernel: str):
    """(calls a step, device seconds a step) of ``kernel`` in the traced
    slice, every module together; None where none ran or the run has no
    map."""
    found = kernel_costs.kernel_calls(run, kernel)
    if not found:
        return None
    return (sum(calls for calls, _ in found.values()),
            sum(seconds for _, seconds in found.values()))


def scan_roofline(run, kernel: str):
    """Percent: least seconds of a step's calls of ``kernel`` over their
    device seconds."""
    found = scan_calls(run, kernel)
    peaks = kernel_costs.peaks_of(run.record)
    shapes = scan_shapes(run.record)
    if found is None or peaks is None or shapes is None:
        return None
    flops, moved = scan_call(kernel, **shapes)
    calls, spent = found
    least = kernel_costs.least_seconds(flops, moved, peaks)
    scopes.say(f"kernel {kernel}: {calls} calls a step, {spent * 1e3!r} ms "
               f"a step, a call {flops!r} FLOP {moved!r} bytes, least "
               f"{least * 1e3!r} ms")
    return 100.0 * calls * least / spent if spent else None
