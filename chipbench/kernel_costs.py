"""What a Pallas kernel of the ``laguna-xs2`` cells has to do, from shapes:
the operations and bytes of one call, for its share of its roofline, and the
join of a traced run's device operations with the program's map that says
how long the kernel took.

A roofline share is the least time the chip could take for the calls of one
kernel in a step, the larger of operations over the chip's bf16 peak and
bytes over its memory bandwidth (``peaks.json``), over the device time of
those calls in the traced slice. The kernel's operations are those of its
own algorithm on the (query, key) pairs that are visible, counted exactly,
not by tiles: a tile the diagonal crosses costs the kernel a whole tile and
counts here as the pairs under the diagonal, so a share below 100% holds
that waste too. Bytes are each operand read once and each result written
once. Plain files and the stdlib; nothing here imports the program.

A call of one of the program's own kernels is an instruction of the step
program whose ``op_name`` holds the kernel's scope
(``tpu_ddp.kernel.<name>``); a kernel the compiler emits itself keeps no
such name and is found by the compiler's (``compiler_kernel_calls``). A
layer recomputed in the backward pass calls its forward kernel twice and has
two such instructions.
"""

from __future__ import annotations

import json
import os

from chipbench import scopes

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL_SCOPE = "tpu_ddp.kernel."
MODULE_SCOPE = "tpu_ddp.module."
BYTES = 2  # bfloat16 operands and results
#: products of (pairs x head_dim) size in a call of each flash kernel: the
#: forward computes scores and output; dQ recomputes scores and computes dP
#: and dQ; dK/dV recomputes scores and computes dV, dP and dK
FLASH_PRODUCTS = {"flash_fwd": 2, "flash_dq": 3, "flash_dkv": 4}


def cell_shapes(record) -> dict:
    """Sizes of one step on one chip of the cell the run was of, from that
    cell's two data files. ``run.py`` keeps a cell's runs under
    ``.chipbench_runs/<cell>/``, which is where the record's ``trace_dir``
    lies, and ``BENCHMARK.json`` names the cell's configuration and mix.
    None for a run of no cell of the benchmark, or of a configuration that
    is not a decoder of this family."""
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
    if "layers_here" not in arch or "layer_types" not in arch:
        return None
    n = arch["layers_here"]
    return {
        "arch": arch,
        "batch": int(traffic["per_shard_batch"]),
        "tokens": int(traffic["dataset"]["seq_len"]),
        "layers": list(zip(arch["layer_types"][:n],
                           arch["num_attention_heads_per_layer"][:n],
                           arch["mlp_layer_types"][:n])),
    }


def visible_pairs(t: int, window: int) -> int:
    if not window or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def flash_call(kernel: str, *, batch, tokens, heads, kv_heads, head_dim,
               window) -> tuple:
    """(operations, bytes) of one call of a flash kernel."""
    pairs = batch * heads * visible_pairs(tokens, window)
    flops = 2.0 * FLASH_PRODUCTS[kernel] * pairs * head_dim
    q_like = batch * tokens * heads * head_dim * BYTES
    kv_like = batch * tokens * kv_heads * head_dim * BYTES
    stats = batch * tokens * heads * 4  # one float32 a row (logsumexp, delta)
    moved = {
        "flash_fwd": 2 * q_like + 2 * kv_like + stats,       # q k v -> o lse
        "flash_dq": 3 * q_like + 2 * kv_like + 2 * stats,    # q k v do -> dq
        "flash_dkv": 2 * q_like + 4 * kv_like + 2 * stats,   # ... -> dk dv
    }[kernel]
    return flops, float(moved)


def grouped_call(*, rows, held, contraction, columns) -> tuple:
    """(operations, bytes) of one grouped product: ``rows`` real rows against
    their experts' (contraction, columns) matrices, or its transpose."""
    flops = 2.0 * rows * contraction * columns
    moved = BYTES * (rows * (contraction + columns)
                     + held * contraction * columns)
    return flops, float(moved)


def least_seconds(flops, moved, peaks) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def peaks_of(record) -> dict:
    """The chip's peaks as ``run.py`` read them; the bandwidth from the
    table (the record carries the FLOP/s only)."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    flops = record.get("peak_flops_per_s")
    for row in table.values():
        if isinstance(row, dict) and row.get("bf16_flops_per_s") == flops:
            return row
    return None


def _calls(run, is_call) -> dict:
    """{module: (calls a step, device seconds a step)} of the step program's
    instructions that ``is_call(name, row)`` picks and that ran in the
    traced slice, by the module the program's map gives them; None where
    the run has no map or no such instruction ran."""
    if run.trace is None or scopes.of_run(run)["split"] is None:
        return None
    files = scopes.newest(scopes.telemetry_dir(run.record))
    program_map = scopes.load_map(files["programs"])
    seconds = dict(map(tuple, run.trace["device_ops"]))
    found = {}
    for name, row in program_map["instructions"].items():
        if name not in seconds or not is_call(name, row):
            continue
        module = row.get("module") or "-"
        calls, total = found.get(module, (0, 0.0))
        found[module] = (calls + 1,
                         total + seconds[name] / run.trace["steps"])
    return found or None


def kernel_calls(run, kernel: str) -> dict:
    """The calls of one of the program's own Pallas kernels: instructions
    whose ``op_name`` holds ``tpu_ddp.kernel.<kernel>``, by the module scope
    around them (``attention_window``, ``attention_full``)."""
    scope = KERNEL_SCOPE + kernel + "/"
    return _calls(run, lambda name, row: scope in (
        row.get("op_name") or "") + "/")


def compiler_kernel_calls(run, prefix: str) -> dict:
    """The calls of a kernel the compiler emits itself for one primitive
    (XLA:TPU's grouped product for ``lax.ragged_dot``): a custom call that
    keeps no ``op_name`` of the program's, only the compiler's own name for
    it (``ragged-dot-none.7``). The program's map gives such a call the
    phase and module of the instruction that uses its result."""
    return _calls(run, lambda name, row: (
        row.get("opcode") == "custom-call" and name.startswith(prefix)))


def modules_ms(run, modules):
    """{module: device ms a step} of the map's modules named in ``modules``
    (scopes inside the model, ``tpu_ddp.module.<name>``), every phase
    together, each printed on a line; None without a split or without any
    of them in it (a program that has no such scopes)."""
    split = scopes.of_run(run)["split"]
    if split is None:
        return None
    found = {}
    for module, _, ms in split["rows"]:
        if module in modules:
            found[module] = found.get(module, 0.0) + ms
    for module, ms in found.items():
        scopes.say(f"module {module}: {ms!r} ms a step")
    return found or None


def flash_roofline(run, kernel: str):
    """Percent: least seconds of a step's calls of ``kernel`` over their
    device seconds."""
    found = kernel_calls(run, kernel)
    peaks = peaks_of(run.record)
    shapes = cell_shapes(run.record)
    if found is None or peaks is None or shapes is None:
        return None
    arch = shapes["arch"]
    # module scope -> (query heads, window) of that kind of layer
    kinds = {("attention_window" if kind == "sliding_attention"
              else "attention_full"): (
                  heads, arch["sliding_window"]
                  if kind == "sliding_attention" else 0)
             for kind, heads, _ in shapes["layers"]}
    least = spent = 0.0
    for module, (calls, seconds) in found.items():
        if module not in kinds:
            return None  # a kernel call the cell's files do not describe
        heads, window = kinds[module]
        flops, moved = flash_call(
            kernel, batch=shapes["batch"], tokens=shapes["tokens"],
            heads=heads, kv_heads=arch["num_key_value_heads"],
            head_dim=arch["head_dim"], window=window)
        scopes.say(f"kernel {kernel} in {module}: {calls} calls a step, "
                   f"{seconds * 1e3!r} ms a step, a call {flops!r} FLOP "
                   f"{moved!r} bytes, least "
                   f"{least_seconds(flops, moved, peaks) * 1e3!r} ms")
        least += calls * least_seconds(flops, moved, peaks)
        spent += seconds
    return 100.0 * least / spent if spent else None


def landed_rows_per_layer(run, shapes):
    """Mean (token, choice) pairs a sparse layer's held experts got in a
    step, from the program's counters; None without them."""
    gauges = (scopes.of_run(run)["counters"] or {}).get("gauges", {})
    landed = gauges.get("model/expert_load_sum")
    if landed is None:
        return None
    sparse = sum(1 for _, _, ffn in shapes["layers"] if ffn == "sparse")
    return landed / sparse
