"""``flash_fwd_calls_per_bwd_call`` (PR 42): a step's calls of the flash
forward kernel over its backward passes, from a traced slice and the
program's map through the shipped ``kernel_costs.kernel_calls``: 2.0 where a
recomputed layer runs the forward kernel in both passes (the parent's
program), 1.0 where it keeps the output and the row statistics, nothing
where no flash kernel runs (an image cell, an untraced run)."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from chipbench import run as harness  # noqa: E402
from test_chipbench_flash_bwd import DECODERS as BAND  # noqa: E402
from test_chipbench_flash_bwd import STEP  # noqa: E402
from test_chipbench_flash_bwd import _traced as _traced_seconds  # noqa: E402

NAME = "flash_fwd_calls_per_bwd_call"
#: what the entry lists: the cells of the band's flash readers, and since PR
#: 44 the block mask's cell, which runs the same kernels under the same
#: recomputed layer
DECODERS = BAND + ["sdar-30b-a3b.seq4k-v18992"]
#: cell: (module scope, layer bodies with attention) of the traced programs
BODIES = {
    "laguna-xs2.seq8k": ("attention_window", 5),
    "nemotron3-super.seq8k-v16384": ("attention_full", 1),
    "joyai-llm-flash.seq8k-v16160": ("attention_latent", 6),
    "sdar-30b-a3b.seq4k-v18992": ("attention_block", 6),
}


@pytest.fixture(scope="module")
def reader():
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", NAME + ".py"),
        "chipbench_metric_" + NAME)


def _op(body, module, kernel, passes="jvp(Model)"):
    return (STEP + f"{passes}/{body}/attn/tpu_ddp.module.{module}/"
            f"tpu_ddp.kernel.{kernel}/pallas_call")


def _traced(tmp_path, cell, rows):
    """A traced run of ``cell`` whose map holds ``rows`` ({instruction:
    (op_name, phase, module)}), each of which ran in the slice."""
    return _traced_seconds(tmp_path, cell, rows, dict.fromkeys(rows, 0.05))


def _program(cell, *, again: bool, split: bool = False):
    """The flash calls of ``cell``'s step: a forward call a body, a second
    one in the backward pass where the layer makes its residuals ``again``,
    and a backward pass a body: the one kernel, or the dQ and the dK/dV
    kernels where the carry's budget ``split`` it."""
    module, bodies = BODIES[cell]
    back = "transpose(jvp(Model))"
    rows = {}
    for i in range(bodies):
        body = f"layer_{i}"
        rows[f"flash_fwd.{i}"] = (_op(body, module, "flash_fwd"), "forward",
                                  module)
        if again:
            rows[f"flash_fwd.{bodies + i}"] = (
                _op(body, module, "flash_fwd", back + "/checkpoint"),
                "backward", module)
        for kernel in (("flash_dq", "flash_dkv") if split
                       else ("flash_bwd",)):
            rows[f"{kernel}.{i}"] = (_op(body, module, kernel, back),
                                     "backward", module)
    return rows


def test_the_entry_lists_the_cells_of_the_shared_flash_readers(reader):
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "device_trace", "layer": "models",
        "moves": "images_per_s_per_chip", "workloads": DECODERS}
    assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
        entry["unit"], entry["source"], entry["layer"], entry["moves"])
    # one name for the mechanism in every cell that runs the flash kernels:
    # the band's readers' cells, and the cell whose mask is not a band
    flash = next(m for m in bench["per_layer"]
                 if m["name"] == "flash_fwd_roofline")
    block = next(m for m in bench["per_layer"]
                 if m["name"] == "block_flash_fwd_roofline")
    assert entry["workloads"] == flash["workloads"] + block["workloads"]
    assert bench["per_layer"][-1] == entry  # appended, and nothing after it


@pytest.mark.parametrize("cell", DECODERS)
@pytest.mark.parametrize("again,split,ratio", [
    (True, False, 2.0), (False, False, 1.0), (True, True, 2.0),
    (False, True, 1.0)],
    ids=["parent", "change", "parent_two_kernels", "change_two_kernels"])
def test_forward_calls_over_backward_passes(reader, tmp_path, cell, again,
                                            split, ratio):
    """The parent's recomputed layer calls the forward kernel in each pass;
    the change's once. A backward pass split in two kernels is one pass:
    the dQ kernel is not counted."""
    run = _traced(tmp_path, cell, _program(cell, again=again, split=split))
    assert reader.read(run) == ratio


def test_every_scope_counts_together(reader, tmp_path):
    """``joyai-llm-flash``'s prediction module is a scope of its own in the
    map: five bodies under ``attention_latent`` and one under ``mtp``."""
    cell = "joyai-llm-flash.seq8k-v16160"
    rows = _program(cell, again=True)
    for name in ("flash_fwd.5", "flash_fwd.11", "flash_bwd.5"):
        op, phase, _ = rows[name]
        rows[name] = (op, phase, "mtp")
    assert reader.read(_traced(tmp_path, cell, rows)) == 2.0
    del rows["flash_fwd.11"]  # the module alone keeps its residuals
    assert reader.read(_traced(tmp_path, cell, rows)) == 11 / 6


def test_a_call_that_did_not_run_in_the_slice_is_not_counted(reader,
                                                             tmp_path):
    cell = "laguna-xs2.seq8k"
    run = _traced(tmp_path, cell, _program(cell, again=True))
    run.trace["device_ops"] = [row for row in run.trace["device_ops"]
                               if row[0] not in ("flash_fwd.7",
                                                 "flash_fwd.8")]
    assert reader.read(run) == 8 / 5


@pytest.mark.parametrize("cell", ["resnet50-cifar.b512",
                                  "resnet50-cifar.dp4"])
def test_an_image_cell_reads_nothing(reader, tmp_path, cell):
    """No flash kernel in the map: the line leaves the metric out."""
    rows = {"fusion.1": (STEP + "jvp(ResNet)/conv", "forward", "-"),
            "fusion.2": (STEP + "transpose(jvp(ResNet))/conv", "backward",
                         "-")}
    assert reader.read(_traced(tmp_path, cell, rows)) is None


def test_an_untraced_run_and_one_pass_alone_read_nothing(reader, tmp_path):
    assert reader.read(types.SimpleNamespace(
        record={"trace_dir": None}, trace=None)) is None
    cell = "laguna-xs2.seq8k"
    module = BODIES[cell][0]
    forward = {"flash_fwd.0": (_op("layer_0", module, "flash_fwd"),
                               "forward", module)}
    assert reader.read(_traced(tmp_path, cell, forward)) is None  # eval
    backward = {"flash_bwd.0": (_op("layer_0", module, "flash_bwd"),
                                "backward", module)}
    assert reader.read(_traced(tmp_path, cell, backward)) is None


@pytest.mark.parametrize("cell", DECODERS)
def test_the_harness_reports_it_in_the_cells_it_lists(tmp_path, cell):
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = [m for m in bench["per_layer"] if m["name"] == NAME]
    run = _traced(tmp_path, cell, _program(cell, again=False))
    out = harness.per_layer(dict(bench, per_layer=entry), cell,
                            [harness.HERE], run.record, run.trace)
    assert out == {NAME: {"value": 1.0, "unit": "ratio"}}
    image = _traced(tmp_path, "resnet50-cifar.b512",
                    _program(cell, again=False))
    assert harness.per_layer(dict(bench, per_layer=entry),
                             "resnet50-cifar.b512", [harness.HERE],
                             image.record, image.trace) == {}
