"""What the flash kernels have to do under the block-diffusion visibility,
from shapes: the operations and bytes of one call of ``flash_fwd`` and of
``flash_bwd`` over ``[clean ‖ noisy]``, ``2 L`` positions in blocks of
``B`` (``tpu_ddp/ops/flash_attention.py``, ``diffusion``), for their shares
of their rooflines in a cell whose attention runs under the module scope
``attention_block``.

The (query, key) pairs a head computes are the visible ones, counted
exactly: with ``n = L / B`` blocks, clean on clean ``n (n + 1) / 2`` block
pairs, noisy on clean ``n (n - 1) / 2``, noisy on its own ``n``, each of
``B ** 2``: ``B ** 2 * n * (n + 1)``. A tile an edge crosses costs the
kernel a whole tile and counts here as its visible pairs, so a share below
100% holds that waste too. Products and arrays moved are the band's
(``kernel_costs.FLASH_PRODUCTS`` / ``FLASH_MOVED`` for the forward kernel,
``layer_metrics/flash_bwd_roofline.py``'s for the backward one), at ``2 L``
positions a row. Plain files and the stdlib; nothing here imports the
program."""

from __future__ import annotations

import importlib.util
import os

from chipbench import kernel_costs, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
MODULE = "attention_block"


def visible_pairs(length: int, block: int) -> int:
    """Pairs one head computes over ``[x ‖ x~]`` of ``length`` tokens."""
    n = length // block
    return block * block * n * (n + 1)


def _products_and_moved(kernel: str):
    if kernel in kernel_costs.FLASH_PRODUCTS:
        return (kernel_costs.FLASH_PRODUCTS[kernel],
                kernel_costs.FLASH_MOVED[kernel])
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + kernel + "_roofline", os.path.join(
            HERE, "layer_metrics", kernel + "_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader.PRODUCTS, reader.MOVED


def flash_call(kernel: str, *, batch, length, block, heads, kv_heads,
               head_dim) -> tuple:
    """(operations, bytes) of one call of ``kernel`` (``flash_fwd`` or
    ``flash_bwd``) on ``batch`` rows of ``2 * length`` positions."""
    (of_qk, of_v), (q_qk, q_v, kv_qk, kv_v, stats) = _products_and_moved(
        kernel)
    pairs = batch * heads * visible_pairs(length, block)
    flops = 2.0 * pairs * (of_qk + of_v) * head_dim
    q_rows = batch * 2 * length * heads
    kv_rows = batch * 2 * length * kv_heads
    moved = (kernel_costs.BYTES * head_dim * (
        q_rows * (q_qk + q_v) + kv_rows * (kv_qk + kv_v))
        + 4 * stats * q_rows)
    return flops, float(moved)


def shapes(arch) -> dict:
    """What a configuration says of its block-diffusion attention, or None
    for one that has none."""
    if "block_length" not in arch:
        return None
    return dict(block=arch["block_length"],
                heads=arch["num_attention_heads"],
                kv_heads=arch["num_key_value_heads"],
                head_dim=arch["head_dim"])


def roofline(run, kernel: str):
    """Percent: least seconds of a step's calls of ``kernel`` under
    ``attention_block`` over their device seconds. None where the traced
    program makes no such call (a program before the scope, a cell of
    another mask) or the cell's files name no block length."""
    found = kernel_costs.kernel_calls(run, kernel)
    peaks = kernel_costs.peaks_of(run.record)
    cell = kernel_costs.cell_files(run.record)
    if found is None or MODULE not in found or peaks is None or cell is None:
        return None
    kind = shapes(cell["arch"])
    if kind is None:
        return None
    calls, seconds = found[MODULE]
    flops, moved = flash_call(kernel, batch=cell["batch"],
                              length=cell["tokens"], **kind)
    a_call = kernel_costs.least_seconds(flops, moved, peaks)
    scopes.say(f"kernel {kernel} in {MODULE}: {calls} calls a step, "
               f"{seconds * 1e3!r} ms a step, a call {flops!r} FLOP "
               f"{moved!r} bytes, least {a_call * 1e3!r} ms")
    return 100.0 * calls * a_call / seconds if seconds else None
