"""What a kernel of the decoder cells has to do, from shapes: the operations
and bytes of one call, for its share of its roofline, the join of a traced
run's device operations with the program's map that says how long the kernel
took, and the one place that finds a cell's own files and reads what a
configuration calls its layers, heads and experts.

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

The flash kernels (``tpu_ddp/ops/flash_attention.py``) run at a key width
and a value width, which latent attention makes differ (queries and keys of
``qk_nope_head_dim + qk_rope_head_dim``, values of ``v_head_dim``). Products
of each width are counted apart:

    flash_fwd   S = Q K^T (qk)            O = P V (v)
    flash_dq    S (qk)   dP = dO V^T (v)  dQ = dS K (qk)
    flash_dkv   S (qk)   dV = P^T dO (v)  dP (v)   dK = dS^T Q (qk)

and so are the arrays moved, as the program hands them over: ``q`` and
``dq`` of the query heads and ``k`` and ``dk`` of the key-value heads at the
key width (latent attention's ``k`` with the shared rotary key already
broadcast over the heads), ``o`` and ``dO`` of the query heads and ``v`` and
``dv`` of the key-value heads at the value width, a float32 a row of a query
head for the logsumexp and for ``rowsum(dO * O)``. Lanes a kernel pads a
width to (192 to 256) are its waste and not counted as work, so they show as
a lower share.
"""

from __future__ import annotations

import json
import os

from chipbench import scopes

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL_SCOPE = "tpu_ddp.kernel."
MODULE_SCOPE = "tpu_ddp.module."
BYTES = 2  # bfloat16 operands and results
#: (products of the key width, products of the value width) on the visible
#: pairs in a call of each flash kernel
FLASH_PRODUCTS = {"flash_fwd": (1, 1), "flash_dq": (2, 1),
                  "flash_dkv": (2, 2)}
#: arrays moved in a call: (of the query heads at the key width, at the value
#: width, of the key-value heads at the key width, at the value width,
#: float32 rows of the query heads)
FLASH_MOVED = {"flash_fwd": (1, 1, 1, 1, 1),   # q k v -> o lse
               "flash_dq": (2, 1, 1, 1, 2),    # q k v dO lse di -> dq
               "flash_dkv": (1, 1, 2, 2, 2)}   # q k v dO lse di -> dk dv


# -- a cell's own files, and what its configuration calls its parts ------------

def cell_files(record) -> dict:
    """The configuration, as it is, and the sizes of one step on one chip of
    the cell the run was of, from that cell's two data files. ``run.py``
    keeps a cell's runs under ``.chipbench_runs/<cell>/``, which is where
    the record's ``trace_dir`` lies, and ``BENCHMARK.json`` names the cell's
    configuration and mix. None for a run of no cell of the benchmark; what
    a configuration has of layers, heads and experts is for the functions
    below to say, each by the configuration's own keys."""
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
    return {
        "arch": arch,
        "batch": int(traffic["per_shard_batch"]),
        "tokens": traffic["dataset"].get("seq_len"),
    }


def layer_bodies(arch) -> list:
    """``[(module scope of its attention or None, query heads, has routed
    experts)]``, one for each layer body of the share held here, read by the
    keys the configuration lays its layers out with: per-layer lists
    (``layer_types``, ``num_attention_heads_per_layer``, ``mlp_layer_types``:
    window and full layers), a pattern string (``hybrid_override_pattern``:
    ``*`` attention, ``E`` experts, anything else neither), or latent
    attention (``kv_lora_rank``) over ``first_k_dense_replace`` dense layers
    with ``num_nextn_predict_layers`` prediction modules after the stack,
    each one more body whose outermost scope is ``mtp``, or a period
    (``decoder_sparse_step`` with ``mlp_only_layers``: layer ``i`` has the
    experts where it is not named dense and ``i + 1`` is a multiple of the
    step). A layer that attends under block diffusion's mask (the
    configuration has a ``block_length``) has no scope here: that mask is
    not a band, and its calls are counted by ``block_mask_costs.py`` under
    names of their own, once. Empty for a configuration laid out in none of
    these ways."""
    here = arch.get("layers_here")
    if here is None:
        return []
    if "layer_types" in arch:
        return [("attention_window" if kind == "sliding_attention"
                 else "attention_full", heads, ffn == "sparse")
                for kind, heads, ffn in zip(
                    arch["layer_types"][:here],
                    arch["num_attention_heads_per_layer"][:here],
                    arch["mlp_layer_types"][:here])]
    if "hybrid_override_pattern" in arch:
        return [("attention_full" if kind == "*" else None,
                 arch["num_attention_heads"] if kind == "*" else 0,
                 kind == "E")
                for kind in arch["hybrid_override_pattern"][:here]]
    if "kv_lora_rank" in arch:
        heads = arch["num_attention_heads"]
        dense = arch.get("first_k_dense_replace", 0)
        return ([("attention_latent", heads, i >= dense)
                 for i in range(here)]
                + [("mtp", heads, True)] * arch.get(
                    "num_nextn_predict_layers", 0))
    if "decoder_sparse_step" in arch:
        scope = None if "block_length" in arch else "attention_full"
        step, dense = arch["decoder_sparse_step"], arch["mlp_only_layers"]
        return [(scope, arch["num_attention_heads"] if scope else 0,
                 i not in dense and (i + 1) % step == 0)
                for i in range(here)]
    return []


def attention_shapes(arch) -> dict:
    """{module scope a flash kernel's call is found under: the ``heads``,
    ``kv_heads``, ``qk_dim``, ``v_dim`` and ``window`` of that call}."""
    latent = "kv_lora_rank" in arch
    found = {}
    for scope, heads, _ in layer_bodies(arch):
        if scope is None:
            continue
        found[scope] = dict(
            heads=heads, kv_heads=arch["num_key_value_heads"],
            qk_dim=(arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
                    if latent else arch["head_dim"]),
            v_dim=arch["v_head_dim"] if latent else arch["head_dim"],
            window=(arch["sliding_window"] if scope == "attention_window"
                    else 0))
    return found


def routed_experts(arch) -> dict:
    """The routed experts of a layer body as the grouped products see them:
    ``bodies`` that have them, experts ``held`` here, and the (contraction,
    columns) of an expert's two products: gated, ``hidden -> 2 x
    moe_intermediate -> hidden``, or, where the configuration names a plain
    activation (``mlp_hidden_act`` ``relu2``), ``-> moe_intermediate ->``;
    from and to ``moe_latent_size`` where the experts work in a latent
    space. None for a configuration without routed experts."""
    bodies = sum(1 for _, _, routed in layer_bodies(arch) if routed)
    if not bodies:
        return None
    outer = arch.get("moe_latent_size", arch["hidden_size"])
    inner = arch["moe_intermediate_size"]
    gate = 1 if arch.get("mlp_hidden_act") == "relu2" else 2
    return {
        "bodies": bodies,
        "held": arch.get("num_experts", arch.get("n_routed_experts")),
        "products": [dict(contraction=outer, columns=gate * inner),
                     dict(contraction=inner, columns=outer)],
    }


# -- operations and bytes of one call ----------------------------------------

def visible_pairs(t: int, window: int) -> int:
    if not window or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def flash_call(kernel: str, *, batch, tokens, heads, kv_heads, qk_dim, v_dim,
               window) -> tuple:
    """(operations, bytes) of one call of a flash kernel (module
    docstring)."""
    pairs = batch * heads * visible_pairs(tokens, window)
    of_qk, of_v = FLASH_PRODUCTS[kernel]
    flops = 2.0 * pairs * (of_qk * qk_dim + of_v * v_dim)
    q_rows, kv_rows = batch * tokens * heads, batch * tokens * kv_heads
    q_qk, q_v, kv_qk, kv_v, stats = FLASH_MOVED[kernel]
    moved = (BYTES * (q_rows * (q_qk * qk_dim + q_v * v_dim)
                      + kv_rows * (kv_qk * qk_dim + kv_v * v_dim))
             + 4 * stats * q_rows)
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
    device seconds, every module the calls sit in together (each on an
    earlier line). None where a call sits in a module the cell's files do
    not describe: never a guess."""
    found = kernel_calls(run, kernel)
    peaks = peaks_of(run.record)
    cell = cell_files(run.record)
    if found is None or peaks is None or cell is None:
        return None
    kinds = attention_shapes(cell["arch"])
    least = spent = 0.0
    for module, (calls, seconds) in found.items():
        if module not in kinds:
            return None  # a kernel call the cell's files do not describe
        flops, moved = flash_call(kernel, batch=cell["batch"],
                                  tokens=cell["tokens"], **kinds[module])
        scopes.say(f"kernel {kernel} in {module}: {calls} calls a step, "
                   f"{seconds * 1e3!r} ms a step, a call {flops!r} FLOP "
                   f"{moved!r} bytes, least "
                   f"{least_seconds(flops, moved, peaks) * 1e3!r} ms")
        least += calls * least_seconds(flops, moved, peaks)
        spent += seconds
    return 100.0 * least / spent if spent else None


def grouped_roofline(run, compiler_name: str, layout_calls: str):
    """Percent: least seconds of a step's calls of the compiler's grouped
    product (custom calls named ``compiler_name...``; those named
    ``layout_calls...`` lay the groups out for them and add their time and
    no work) over their device seconds, at the rows the program's counters
    say really landed on the held experts of a layer body: the mean of an
    expert's two products, since all four calls of a product (forward,
    forward again, backward by rows and by weights) move the same operands
    and do the same operations. The calls are counted from the trace, never
    assumed from the layer count. None where the traced program calls no
    such kernel, keeps no counters, or the cell's files name no routed
    experts."""
    found = compiler_kernel_calls(run, compiler_name)
    cell = cell_files(run.record)
    peaks = peaks_of(run.record)
    if found is None or cell is None or peaks is None:
        return None
    experts = routed_experts(cell["arch"])
    landed = (scopes.of_run(run)["counters"] or {}).get("gauges", {}).get(
        "model/expert_load_sum")
    if experts is None or landed is None:
        return None
    rows = landed / experts["bodies"]
    layout = compiler_kernel_calls(run, layout_calls) or {}
    per_call = sum(least_seconds(*grouped_call(
        rows=rows, held=experts["held"], **product), peaks)
        for product in experts["products"]) / len(experts["products"])
    calls = (sum(n for n, _ in found.values())
             - sum(n for n, _ in layout.values()))
    spent = sum(s for _, s in found.values())
    scopes.say(f"kernel grouped_matmul: {calls} calls a step in "
               f"{sorted(found)}, {spent * 1e3!r} ms a step, {rows!r} real "
               f"rows a layer body of {experts['bodies']}, least "
               f"{per_call * 1e3!r} ms a call")
    return 100.0 * calls * per_call / spent if spent and calls else None
