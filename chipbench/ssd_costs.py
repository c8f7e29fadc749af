"""What the chunked state-space scan of the ``nemotron3-super`` cells
(``tpu_ddp/ops/ssd_scan.py``) has to do, from shapes: the operations and
bytes of one call, forward and backward, for its share of its roofline, and
the join of a traced run's device operations with the program's map that
says how long its calls took. What every kernel's costs share (the lookup
of a cell's own files, peaks, the least time, the map's files) is
``kernel_costs.py``'s and taken from there.

The scan is ``jax.numpy``, so a call is many instructions of the step
program, each with ``tpu_ddp.kernel.ssd_scan_fwd`` (or ``_bwd``) in its
``op_name``. Calls are counted by what the ``op_name`` and the map say: one
for each (block, phase) that has such an instruction; a block recomputed in
the backward pass calls the forward scan twice, once in each phase.

Operations are those of the chunked algorithm's own matrix products on the
(position, position) pairs that are visible inside a chunk, counted exactly;
bytes are each operand read once and each result written once (the states
at the chunk boundaries are a result of the forward call and an operand of
the backward one, in float32). Plain files and the stdlib; nothing here
imports the program.
"""

from __future__ import annotations

import re

from chipbench import kernel_costs, scopes

BLOCK = re.compile(r"/block_(\d+)/")
SCAN_KERNELS = ("ssd_scan_fwd", "ssd_scan_bwd")


def _chunks(tokens: int, chunk: int):
    """(chunks, positions a chunk, visible pairs a chunk)."""
    chunk = min(chunk, tokens)
    return -(-tokens // chunk), chunk, chunk * (chunk + 1) // 2


def scan_forward_macs(*, tokens, heads, head_dim, groups, state,
                      chunk) -> float:
    """Multiply-accumulates of the forward scan over one sequence: a
    chunk's scores ``C B^T`` a group, its masked product against ``x`` a
    head, the state each chunk adds and the output each carried state gives
    (positions x head_dim x state each), and the carry between chunks."""
    chunks, length, pairs = _chunks(tokens, chunk)
    cell = head_dim * state
    return float(chunks * (
        groups * pairs * state
        + heads * (pairs * head_dim + 2 * length * cell + cell)))


def scan_backward_macs(*, tokens, heads, head_dim, groups, state,
                       chunk) -> float:
    """The backward scan: the scores again, three masked products a head
    (``dy x^T``, the weights against ``dy``, the outputs again), two a group
    (``dB``, ``dC``), six products of positions x head_dim x state a head
    (the states' worth, their part of ``dx``, ``dB`` and ``dC``, the added
    and the carried again) and the carry back between chunks."""
    chunks, length, pairs = _chunks(tokens, chunk)
    cell = head_dim * state
    return float(chunks * (
        groups * 3 * pairs * state
        + heads * (3 * pairs * head_dim + 6 * length * cell + cell)))


def scan_call(kernel: str, *, batch, tokens, heads, head_dim, groups, state,
              chunk) -> tuple:
    """(operations, bytes) of one call of a scan kernel."""
    sizes = dict(tokens=tokens, heads=heads, head_dim=head_dim,
                 groups=groups, state=state, chunk=chunk)
    x_like = batch * tokens * heads * head_dim * kernel_costs.BYTES
    bc_like = batch * tokens * groups * state * kernel_costs.BYTES
    dt_like = batch * tokens * heads * 4
    states = batch * _chunks(tokens, chunk)[0] * heads * head_dim * state * 4
    if kernel == "ssd_scan_fwd":     # x dt B C -> y, states
        return (2.0 * batch * scan_forward_macs(**sizes),
                float(2 * x_like + 2 * bc_like + dt_like + states))
    if kernel == "ssd_scan_bwd":     # x dt B C dy states -> dx ddt dB dC
        return (2.0 * batch * scan_backward_macs(**sizes),
                float(3 * x_like + 4 * bc_like + 2 * dt_like + states))
    raise ValueError(f"no scan kernel {kernel!r}")


def scan_calls(run, kernel: str):
    """(calls a step, device seconds a step) of the instructions whose
    ``op_name`` holds ``tpu_ddp.kernel.<kernel>`` and that ran in the traced
    slice; None where the run has no map or no such instruction ran."""
    if run.trace is None or scopes.of_run(run)["split"] is None:
        return None
    files = scopes.newest(scopes.telemetry_dir(run.record))
    program_map = scopes.load_map(files["programs"])
    seconds = dict(map(tuple, run.trace["device_ops"]))
    scope = kernel_costs.KERNEL_SCOPE + kernel + "/"
    calls, spent = set(), 0.0
    for name, row in program_map["instructions"].items():
        op_name = (row.get("op_name") or "") + "/"
        if name not in seconds or scope not in op_name:
            continue
        block = BLOCK.search(op_name)
        calls.add((block.group(1) if block else None, row.get("phase")))
        spent += seconds[name] / run.trace["steps"]
    return (len(calls), spent) if calls else None


def scan_roofline(run, kernel: str):
    """Percent: least seconds of a step's calls of ``kernel`` over their
    device seconds. None where the cell's configuration names no Mamba heads
    (``mamba_num_heads``): never a guess."""
    found = scan_calls(run, kernel)
    peaks = kernel_costs.peaks_of(run.record)
    cell = kernel_costs.cell_files(run.record)
    if found is None or peaks is None or cell is None:
        return None
    arch = cell["arch"]
    if "mamba_num_heads" not in arch:
        return None
    flops, moved = scan_call(
        kernel, batch=cell["batch"], tokens=cell["tokens"],
        heads=arch["mamba_num_heads"], head_dim=arch["mamba_head_dim"],
        groups=arch["n_groups"], state=arch["ssm_state_size"],
        chunk=arch["chunk_size"])
    calls, spent = found
    least = kernel_costs.least_seconds(flops, moved, peaks)
    scopes.say(f"kernel {kernel}: {calls} calls a step, {spent * 1e3!r} ms "
               f"a step, a call {flops!r} FLOP {moved!r} bytes, least "
               f"{least * 1e3!r} ms")
    return 100.0 * calls * least / spent if spent else None
