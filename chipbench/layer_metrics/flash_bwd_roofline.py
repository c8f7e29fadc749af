"""``flash_bwd_roofline``: the share of its roofline that the one-kernel
flash attention backward pass (``tpu_ddp.kernel.flash_bwd``,
``ops/flash_attention.py``: a score tile, its exponentials and ``dP`` made
once, and from them ``dQ``, ``dK`` and ``dV``) reaches over a step's calls,
every module scope they are found under together (``attention_window``,
``attention_full``, ``attention_latent``, a prediction module's ``mtp``;
each on an earlier line, whose count of calls a step says how often that
path was taken): the larger of its operations over the chip's bf16 peak and
its bytes over the memory bandwidth, over the kernel's device time in the
traced slice. Operations and bytes are this algorithm's own, from shapes at
the key width and the value width of that scope's layers in the cell's
configuration, on the visible pairs, padding not work:

    S = Q K^T (qk)   dP = dO V^T (v)   dV = P^T dO (v)
    dK = dS^T Q (qk)   dQ = dS K (qk)

and, moved once each, ``q``, ``dq`` (key width) and ``dO`` (value width) of
the query heads, ``k``, ``dk`` (key width) and ``v``, ``dv`` (value width)
of the key-value heads, two float32 a row of a query head (the logsumexp
and ``rowsum(dO * O)``). None where the traced program calls no such kernel
(a program before it, or a shape whose backward pass runs ``flash_dq`` and
``flash_dkv``), or calls it under a scope the cell's files do not
describe."""

from chipbench import kernel_costs, scopes

NAME, UNIT, SOURCE = "flash_bwd_roofline", "%", "device_trace"
LAYER = "kernels"
MOVES = "images_per_s_per_chip"
KERNEL = "flash_bwd"
#: products of the key width and of the value width on the visible pairs
PRODUCTS = (3, 2)
#: arrays moved: of the query heads at the key width (q dq) and at the value
#: width (dO), of the key-value heads at the key width (k dk) and at the
#: value width (v dv), float32 rows of the query heads (lse di)
MOVED = (2, 1, 2, 2, 2)


def call(*, batch, tokens, heads, kv_heads, qk_dim, v_dim, window) -> tuple:
    """(operations, bytes) of one call."""
    pairs = batch * heads * kernel_costs.visible_pairs(tokens, window)
    of_qk, of_v = PRODUCTS
    flops = 2.0 * pairs * (of_qk * qk_dim + of_v * v_dim)
    q_rows, kv_rows = batch * tokens * heads, batch * tokens * kv_heads
    q_qk, q_v, kv_qk, kv_v, stats = MOVED
    moved = (kernel_costs.BYTES * (q_rows * (q_qk * qk_dim + q_v * v_dim)
                                   + kv_rows * (kv_qk * qk_dim + kv_v * v_dim))
             + 4 * stats * q_rows)
    return flops, float(moved)


def read(run):
    found = kernel_costs.kernel_calls(run, KERNEL)
    peaks = kernel_costs.peaks_of(run.record)
    cell = kernel_costs.cell_files(run.record)
    if found is None or peaks is None or cell is None:
        return None
    kinds = kernel_costs.attention_shapes(cell["arch"])
    least = spent = 0.0
    for module, (calls, seconds) in found.items():
        if module not in kinds:
            return None  # a kernel call the cell's files do not describe
        flops, moved = call(batch=cell["batch"], tokens=cell["tokens"],
                            **kinds[module])
        a_call = kernel_costs.least_seconds(flops, moved, peaks)
        scopes.say(f"kernel {KERNEL} in {module}: {calls} calls a step, "
                   f"{seconds * 1e3!r} ms a step, a call {flops!r} FLOP "
                   f"{moved!r} bytes, least {a_call * 1e3!r} ms")
        least += calls * a_call
        spent += seconds
    return 100.0 * least / spent if spent else None
