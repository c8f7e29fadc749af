"""``flash_bwd_roofline`` (PR 40): its own count of the one-kernel backward
pass's operations and bytes at the three decoder cells' shapes, its join of
a traced slice with the program's map through the shipped
``kernel_costs`` functions, and what it reads in a program that has no call
under ``tpu_ddp.kernel.flash_bwd`` (the parent's, or a shape whose backward
pass runs the two kernels): nothing."""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from chipbench import kernel_costs  # noqa: E402
from chipbench import run as harness  # noqa: E402

NAME = "flash_bwd_roofline"
DECODERS = ["laguna-xs2.seq8k", "nemotron3-super.seq8k-v16384",
            "joyai-llm-flash.seq8k-v16160"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
FULL = 8192 * 8193 // 2
WINDOW = 512 * 513 // 2 + (8192 - 512) * 512

#: cell: {module scope: (query heads, key-value heads, key width, value
#: width, visible pairs a head and sequence)}, the configurations' own
SHAPES = {
    "laguna-xs2.seq8k": {
        "attention_full": (48, 8, 128, 128, FULL),
        "attention_window": (64, 8, 128, 128, WINDOW)},
    "nemotron3-super.seq8k-v16384": {
        "attention_full": (4, 1, 128, 128, FULL)},
    "joyai-llm-flash.seq8k-v16160": {
        "attention_latent": (32, 32, 192, 128, FULL),
        "mtp": (32, 32, 192, 128, FULL)},
}
CALLS = [(cell, module) for cell in SHAPES for module in SHAPES[cell]]
#: the two-kernel backward pass's kernels. No cell runs them since PR 40 and
#: their readers went in PR 44 (a cell whose sequence does not fit the one
#: kernel's carry brings them back); ``kernel_costs`` keeps their counts, and
#: the cases that read the two readers read ``flash_roofline`` in their place
PAIR = ("flash_dq", "flash_dkv")


def read_of(name):
    """``read`` of the per-layer metric ``name``, or of a kernel of ``PAIR``
    as its reader made it: ``kernel_costs.flash_roofline(run, kernel)``."""
    if name in PAIR:
        return lambda run: kernel_costs.flash_roofline(run, name)
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "chipbench_metric_" + name).read


@pytest.fixture(scope="module")
def reader():
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", NAME + ".py"),
        "chipbench_metric_" + NAME)


def _record(tmp_path, cell):
    """A run record kept where ``run.py`` keeps that cell's runs."""
    return {"trace_dir": str(tmp_path / cell / "profile"),
            "peak_flops_per_s": 197e12}


def test_the_entry_lists_the_decoder_cells(reader):
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "higher",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": DECODERS}
    assert (reader.UNIT, reader.SOURCE) == ("%", "device_trace")
    assert (reader.LAYER, reader.MOVES) == ("kernels",
                                            "images_per_s_per_chip")
    # it moves the rate, which the benchmark's share of the whole step's
    # peak moves too, in every cell
    mfu = next(m for m in bench["per_layer"] if m["name"] == "step_mfu")
    assert mfu["moves"] == entry["moves"] and "workloads" not in mfu


@pytest.mark.parametrize("cell,module", CALLS)
def test_a_call_costs_five_products_and_nine_arrays(reader, tmp_path, cell,
                                                    module):
    """Three products of the key width and two of the value width on the
    visible pairs; ``q``, ``dq``, ``dO`` of the query heads, ``k``, ``dk``,
    ``v``, ``dv`` of the key-value heads, two float32 a query row; at the
    shapes the cell's own files give the scope."""
    heads, kv_heads, qk, v, pairs = SHAPES[cell][module]
    files = kernel_costs.cell_files(_record(tmp_path, cell))
    shape = kernel_costs.attention_shapes(files["arch"])[module]
    assert (files["batch"], files["tokens"]) == (2, 8192)
    assert (shape["heads"], shape["kv_heads"], shape["qk_dim"],
            shape["v_dim"]) == (heads, kv_heads, qk, v)
    flops, moved = reader.call(batch=2, tokens=8192, **shape)
    assert flops == 2.0 * (2 * heads * pairs) * (3 * qk + 2 * v)
    rows = 2 * 8192
    assert moved == (2 * rows * heads * (2 * qk + v)
                     + 2 * rows * kv_heads * (2 * qk + 2 * v)
                     + 4 * 2 * rows * heads)
    # fewer operations than the two kernels it stands for: S and dP once
    pair = (kernel_costs.flash_call("flash_dq", batch=2, tokens=8192,
                                    **shape)[0]
            + kernel_costs.flash_call("flash_dkv", batch=2, tokens=8192,
                                      **shape)[0])
    assert flops == pair - 2.0 * (2 * heads * pairs) * (qk + v)
    # and every one of these calls is bound by the matrix unit
    assert flops / 197e12 > moved / 819e9


def test_the_least_milliseconds_the_issue_quotes(reader):
    least = {name: 1e3 * kernel_costs.least_seconds(
        *reader.call(batch=2, tokens=8192, heads=h, kv_heads=kv, qk_dim=qk,
                     v_dim=v, window=w), PEAKS)
        for name, (h, kv, qk, v, w) in {
            "latent": (32, 32, 192, 128, 0), "full": (48, 8, 128, 128, 0),
            "window": (64, 8, 128, 128, 512),
            "four_heads": (4, 1, 128, 128, 0)}.items()}
    assert least == pytest.approx({"latent": 18.14, "full": 20.93,
                                   "window": 3.38, "four_heads": 1.744},
                                  rel=2e-3)


def _traced(tmp_path, cell, rows, seconds, steps=5):
    """A traced run of ``cell`` whose map holds ``rows`` ({instruction:
    (op_name, phase, module)}) and whose slice spent ``seconds`` in each."""
    folder = tmp_path / cell / "telemetry"
    os.makedirs(folder, exist_ok=True)
    (folder / "programs-p0.jsonl").write_text(json.dumps({
        "type": "program_map", "program": "train_step",
        "instructions": {name: {"op_name": op, "opcode": "custom-call",
                                "phase": phase, "module": module}
                         for name, (op, phase, module) in rows.items()}})
        + "\n")
    (folder / "trace-p0.jsonl").write_text(json.dumps(
        {"type": "counters", "attrs": {"tables": {}, "gauges": {}}}) + "\n")
    return types.SimpleNamespace(
        record=_record(tmp_path, cell),
        trace={"device_ops": [[name, s] for name, s in seconds.items()],
               "steps": steps, "device_step_ms": 100.0})


STEP = "jit(shard_step)/tpu_ddp.forward_backward/"


def _call(body, module, kernel):
    return (STEP + f"transpose(jvp(Model))/{body}/attn/tpu_ddp.module."
            f"{module}/tpu_ddp.kernel.{kernel}/pallas_call", "backward",
            module)


@pytest.mark.parametrize("cell", DECODERS)
def test_the_share_is_least_over_spent_every_scope_together(reader, capsys,
                                                            tmp_path, cell):
    """One call a scope the cell has, 40 ms a step each; a forward kernel's
    call beside them is not this reader's."""
    rows, seconds = {}, {}
    for i, module in enumerate(SHAPES[cell]):
        body = "mtp" if module == "mtp" else f"layer_{i}"
        scope = "attention_latent" if module == "mtp" else module
        op, phase, _ = _call(body, scope, "flash_bwd")
        rows[f"flash_bwd.{i}"] = (op, phase, module)
        seconds[f"flash_bwd.{i}"] = 5 * 0.040
    rows["flash_fwd.9"] = _call("layer_0", next(iter(SHAPES[cell])),
                                "flash_fwd")
    seconds["flash_fwd.9"] = 5 * 0.015
    run = _traced(tmp_path, cell, rows, seconds)
    files = kernel_costs.cell_files(run.record)
    kinds = kernel_costs.attention_shapes(files["arch"])
    least = sum(kernel_costs.least_seconds(
        *reader.call(batch=2, tokens=8192, **kinds[module]), PEAKS)
        for module in SHAPES[cell])
    got = reader.read(run)
    assert got == pytest.approx(100 * least / (0.040 * len(SHAPES[cell])))
    assert 0 < got < 100
    said = capsys.readouterr().out
    for module in SHAPES[cell]:
        assert f"kernel flash_bwd in {module}: 1 calls a step" in said
    # the two kernels' shares find no call of theirs here
    for kernel in ("flash_dq", "flash_dkv"):
        assert kernel_costs.flash_roofline(run, kernel) is None


@pytest.mark.parametrize("cell", DECODERS)
def test_a_program_with_the_two_kernels_reads_nothing(reader, tmp_path,
                                                      cell):
    """The parent's program, or a shape the carry's budget refuses: calls
    under ``flash_dq`` and ``flash_dkv`` and none under ``flash_bwd``. The
    line leaves the metric out; nothing raises. No cell runs the two since
    PR 40 and their readers went in PR 44; ``kernel_costs.flash_roofline``
    still reads each by the pair's own products, as those readers did."""
    module = next(iter(SHAPES[cell]))
    rows = {"flash_dq.1": _call("layer_0", module, "flash_dq"),
            "flash_dkv.1": _call("layer_0", module, "flash_dkv")}
    spent = {"flash_dq.1": 0.1, "flash_dkv.1": 0.12}
    run = _traced(tmp_path, cell, rows, spent)
    assert reader.read(run) is None
    shape = kernel_costs.attention_shapes(
        kernel_costs.cell_files(run.record)["arch"])[module]
    for kernel in ("flash_dq", "flash_dkv"):
        least = kernel_costs.least_seconds(*kernel_costs.flash_call(
            kernel, batch=2, tokens=8192, **shape), PEAKS)
        assert kernel_costs.flash_roofline(run, kernel) == pytest.approx(
            100 * least / (spent[kernel + ".1"] / 5), rel=1e-12)
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    flash = [m for m in bench["per_layer"] if m["name"].startswith("flash_")]
    assert len(flash) == 3
    out = harness.per_layer(dict(bench, per_layer=flash), cell,
                            [harness.HERE], run.record, run.trace)
    assert out == {}


def test_an_untraced_run_a_run_of_no_cell_and_a_strange_scope_read_nothing(
        reader, tmp_path):
    assert reader.read(types.SimpleNamespace(
        record={"trace_dir": None}, trace=None)) is None
    rows = {"flash_bwd.1": _call("layer_0", "attention_full", "flash_bwd")}
    seconds = {"flash_bwd.1": 0.2}
    # an image cell's files describe no attention: never a guess
    assert reader.read(_traced(tmp_path, "resnet50-cifar.b512", rows,
                               seconds)) is None
    assert reader.read(_traced(tmp_path, "no-such.cell", rows,
                               seconds)) is None
    # a scope the cell's files do not describe (laguna has no latent one)
    rows = {"flash_bwd.1": _call("layer_0", "attention_latent",
                                 "flash_bwd")}
    assert reader.read(_traced(tmp_path, "laguna-xs2.seq8k", rows,
                               seconds)) is None
    # a chip that is not in the table
    run = _traced(tmp_path, "laguna-xs2.seq8k", {
        "flash_bwd.1": _call("layer_0", "attention_full", "flash_bwd")},
        seconds)
    run.record["peak_flops_per_s"] = 1.0
    assert reader.read(run) is None
