"""What the flash attention kernels have to do in a latent-attention layer
of the ``joyai-llm-flash`` cells (``tpu_ddp/ops/flash_attention.py`` at two
widths: queries and keys of ``qk_nope_head_dim + qk_rope_head_dim``, values
of ``v_head_dim``), from shapes: the operations and bytes of one call, for
its share of its roofline. The shipped ``kernel_costs.py`` counts one head
width and reads the ``laguna-xs2`` family's keys; what the two share (the
join of trace and program map, peaks, the least time) is taken from there.

Products of the key width and of the value width are counted apart, on the
(query, key) pairs under the diagonal, exactly:

    flash_fwd   S = Q K^T (qk)            O = P V (v)
    flash_dq    S (qk)   dP = dO V^T (v)  dQ = dS K (qk)
    flash_dkv   S (qk)   dV = P^T dO (v)  dP (v)   dK = dS^T Q (qk)

Bytes are each operand read once and each result written once, as the
program hands them over: ``q``, ``k``, ``dq``, ``dk`` at the key width
(``k`` with the shared rotary key already broadcast over the heads) and
``v``, ``o``, ``dO``, ``dv`` at the value width, a float32 a row for the
logsumexp and for ``rowsum(dO * O)``. The kernel pads 192 to 256 lanes; that
is its waste and not counted as work, so it shows as a lower share. A call
is found by the kernel's own scope in its ``op_name``, whatever module it
sits in: the prediction module's layer calls the same kernels. Plain files
and the stdlib; nothing here imports the program.
"""

from __future__ import annotations

import json
import os

from chipbench import kernel_costs, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
#: (products of the key width, products of the value width) in a call
PRODUCTS = {"flash_fwd": (1, 1), "flash_dq": (2, 1), "flash_dkv": (2, 2)}
#: (arrays of the key width, of the value width, float32 rows) moved
MOVED = {"flash_fwd": (2, 2, 1),    # q k v -> o lse
         "flash_dq": (3, 2, 2),     # q k v dO lse di -> dq
         "flash_dkv": (3, 3, 2)}    # q k v dO lse di -> dk dv


def cell_shapes(record) -> dict:
    """Sizes of one step on one chip of the cell the run was of, for a
    configuration with latent attention (``kv_lora_rank`` and
    ``layers_here``); None for a run of no cell of the benchmark or of
    another family."""
    trace_dir = record.get("trace_dir")
    if not trace_dir:
        return None
    name = os.path.basename(os.path.dirname(os.path.abspath(trace_dir)))
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        return None
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(os.path.dirname(HERE), entry["file"])) as f:
        arch = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if "kv_lora_rank" not in arch or "layers_here" not in arch:
        return None
    return {
        "arch": arch,
        "batch": int(traffic["per_shard_batch"]),
        "tokens": int(traffic["dataset"]["seq_len"]),
        # the stack's layers and the prediction modules', one body each
        "layer_bodies": arch["layers_here"] + arch.get(
            "num_nextn_predict_layers", 0),
    }


def flash_call(kernel: str, *, batch, tokens, heads, qk_dim, v_dim) -> tuple:
    """(operations, bytes) of one call of a flash kernel over causal latent
    attention of ``heads`` heads."""
    pairs = batch * heads * kernel_costs.visible_pairs(tokens, 0)
    of_qk, of_v = PRODUCTS[kernel]
    flops = 2.0 * pairs * (of_qk * qk_dim + of_v * v_dim)
    rows = batch * tokens * heads
    qk_like, v_like, stats = MOVED[kernel]
    moved = rows * (kernel_costs.BYTES * (qk_like * qk_dim + v_like * v_dim)
                    + 4 * stats)
    return flops, float(moved)


def flash_roofline(run, kernel: str):
    """Percent: least seconds of a step's calls of ``kernel`` over their
    device seconds, over every module the calls sit in."""
    found = kernel_costs.kernel_calls(run, kernel)
    peaks = kernel_costs.peaks_of(run.record)
    shapes = cell_shapes(run.record)
    if found is None or peaks is None or shapes is None:
        return None
    arch = shapes["arch"]
    flops, moved = flash_call(
        kernel, batch=shapes["batch"], tokens=shapes["tokens"],
        heads=arch["num_attention_heads"],
        qk_dim=arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"],
        v_dim=arch["v_head_dim"])
    least = kernel_costs.least_seconds(flops, moved, peaks)
    calls = spent = 0
    for module, (n, seconds) in found.items():
        scopes.say(f"kernel {kernel} in {module}: {n} calls a step, "
                   f"{seconds * 1e3!r} ms a step")
        calls += n
        spent += seconds
    scopes.say(f"kernel {kernel}: {calls} calls a step over "
               f"{shapes['layer_bodies']} layer bodies, {spent * 1e3!r} ms a "
               f"step, a call {flops!r} FLOP {moved!r} bytes, least "
               f"{least * 1e3!r} ms")
    return 100.0 * calls * least / spent if spent else None
