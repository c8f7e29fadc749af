"""The ``phi4-mini-flash`` configuration's own files (its plain reference,
the shipped ``zipf_tokens`` generator and ``trainer`` adapter, so the
product's ``Trainer.run``) through the shipped harness at a size a CPU
holds, on a copy of the shipped BENCHMARK.json with the tiny cell appended
(``chipbench_tiny_phi4.py``); the same run held against a reference with the
second softmax or the memory left out; the entries this configuration has
in the shipped file; its configuration file against the catalog's numbers;
the scan's costs from shapes and the join of a traced run with the program's
map that its share of the roofline is read by
(``chipbench/selective_scan_costs.py``); and what the shipped readers make
of the new cell. The cell brings no entry under ``per_layer``: a shipped
test holds that list's last entry
(``test_chipbench_flash_calls.py``), so the seven readers of what only this
cell runs are the next ``benchmark`` PR's (``PERF.md`` section 7)."""

import json
import os
import sys
import types

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import chipbench_tiny_phi4 as tiny_cell  # noqa: E402
import sambay_tiny as tiny  # noqa: E402
from chipbench import kernel_costs, selective_scan_costs  # noqa: E402
from chipbench import run as harness  # noqa: E402
from test_chipbench_contract import appended_only  # noqa: E402

CELL = "phi4-mini-flash.seq16k-v25008"
#: the module scopes of what only this cell runs, by the reader a
#: ``benchmark`` PR is asked to give each (``PERF.md`` section 7)
MODULES = {
    "device_mamba1_ms": ("mamba1_in", "mamba1_conv", "mamba1_dt",
                         "selective_scan", "mamba1_out"),
    "device_gmu_ms": ("gmu",),
    "device_diff_attention_ms": ("attention_diff",),
    "device_cross_attention_ms": ("attention_cross",),
}
#: the catalog's ``config`` of Phi-4-mini-flash-reasoning, every number
CATALOG = dict(embd_pdrop=0, hidden_size=2560, intermediate_size=10240,
               layer_norm_eps=1e-05, max_position_embeddings=262144,
               mb_per_layer=2, num_attention_heads=40, num_hidden_layers=32,
               num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
               vocab_size=200064)
_CONFIG = ("jax_compilation_cache_dir",
           "jax_persistent_cache_min_compile_time_secs",
           "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def keep_jax_config():
    """The harness points jax's cache at the checkout; put it back."""
    saved = {k: getattr(jax.config, k) for k in _CONFIG}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_the_new_configuration_is_correct_through_trainer_run(tmp_path,
                                                              capsys):
    """Three AdamW steps of the tiny decoder-hybrid-decoder through
    ``Trainer.run`` against the stepwise float32 reference: losses, first
    gradient, update."""
    tiny.register()
    result = tiny_cell.run(tmp_path)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 20
    assert set(result["metrics"]) == {"images_per_s_per_chip", "setup_s"}
    assert set(result["compared"]) == {
        "repeated_rows", "loss_gap", "grad_gap", "update_gap", "grad_diff",
        "out_grad_diff"}
    assert "chipbench: tokens_per_s_per_chip=" in capsys.readouterr().out


@pytest.mark.parametrize("fault", ["second_softmax", "memory"])
def test_a_reference_with_a_piece_left_out_reads_not_correct(
        tmp_path, monkeypatch, fault):
    """The same run held against the reference without the second softmax
    (``lambda`` 0) or with ones in the memory's place: not correct, by a
    limit of the comparison."""
    load = harness.load_module

    def load_and_leave_out(path, name):
        module = load(path, name)
        if name.startswith("chipbench_reference_"):
            module.LEFT_OUT = frozenset({fault})
        return module

    monkeypatch.setattr(harness, "load_module", load_and_leave_out)
    tiny.register()
    result = tiny_cell.run(tmp_path)
    assert result["correct"] is False
    assert tiny_cell.failed(result), result["compared"]


# -- what was appended to the shipped file --------------------------------------

def test_the_shipped_file_has_its_configuration_cell_and_readers(bench):
    """Found by name, on the shipped file and on a copy with entries after
    the end of every list: cut where this configuration's entries start, the
    file is one of which the whole is ``appended_only``."""
    names = lambda group: [e["name"] for e in bench[group]]  # noqa: E731
    at = {"configs": names("configs").index("phi4-mini-flash"),
          "workloads": names("workloads").index(CELL)}
    before = dict(bench, **{group: bench[group][:i]
                            for group, i in at.items()})
    assert appended_only(before, bench)
    # no per-layer metric lists the cell: the shipped readers keep their
    # lists, and the new mechanisms' readers are a ``benchmark`` PR's
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", ())]
    assert {"step_mfu", "device_step_ms"} <= {
        m["name"] for m in bench["per_layer"] if "workloads" not in m}
    cell = bench["workloads"][at["workloads"]]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    for said in ("16,384", "2:1:1:1:1", "9:8:1:7:7", "6 of 32 layers"):
        assert said in cell["why"]
    entry = bench["configs"][at["configs"]]
    assert entry["reduced"] == ["layers_here", "vocab_size"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_configuration_file_holds_every_published_number():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "phi4-mini-flash.json")) as f:
        arch = json.load(f)
    changed = {"vocab_size": 25008}
    assert {k: arch[k] for k in CATALOG} == dict(CATALOG, **changed)
    assert arch["reduced"] == ["layers_here", "vocab_size"]
    assert arch["published"] == dict(num_hidden_layers=32, vocab_size=200064)
    assert (arch["first_layer"], arch["layers_here"]) == (14, 6)
    for key in ("source", "deployment", "parameters_here", "reduced_why",
                "precision_note"):
        assert arch[key]
    assert set(arch["reduced_why"]) == set(arch["reduced"])
    assert set(arch["assumed"]) == {
        "mamba_sizes", "layer_kinds", "differential_attention",
        "window_and_positions", "norm", "initialisation", "optimizer"}
    assert "stages of six" in arch["deployment"]
    assert arch["train_config"]["model_overrides"] == dict(
        first_layer=14, num_layers=6, vocab_rows=25008)
    assert arch["train_config"]["remat"] is True
    with open(os.path.join(REPO, "chipbench", "traffic",
                           "seq16k-v25008.json")) as f:
        mix = json.load(f)
    assert mix["per_shard_batch"] == 1 and "fixed_work" not in mix
    assert mix["dataset"] == dict(
        kind="zipf_tokens", size=24, seq_len=16384, vocab_size=25008,
        exponent=1.0, example_holds={"tokens": 16384})


# -- the scan's costs from shapes -------------------------------------------------

CELL_SCAN = dict(batch=1, tokens=16384, channels=5120, state=16)


def test_the_scans_operations_and_bytes_are_counted_from_shapes():
    cells = 16384 * 5120
    checkpoints = 128 * 5120 * 16 * 4
    flops, moved = selective_scan_costs.scan_call(
        "selective_scan_fwd", **CELL_SCAN)
    assert flops == 6 * cells * 16
    assert moved == (cells * 8 + 5120 * 16 * 4 + 2 * 16384 * 16 * 2
                     + checkpoints)
    # about a millisecond a forward call, by the memory's bound
    assert 0.8e-3 < moved / 819e9 < 1.0e-3 and flops / 197e12 < 0.1e-3
    back_flops, back_moved = selective_scan_costs.scan_call(
        "selective_scan_bwd", **CELL_SCAN)
    assert back_flops == 19 * cells * 16
    assert back_moved == (cells * 14 + 2 * 5120 * 16 * 4
                          + 4 * 16384 * 16 * 2 + checkpoints)
    with pytest.raises(ValueError):
        selective_scan_costs.scan_call("ssd_scan_fwd", **CELL_SCAN)
    # the kernel keeps a state every ``CHECKPOINT_EVERY`` positions
    from tpu_ddp.ops import selective_scan

    assert selective_scan_costs.CHECKPOINT_EVERY == selective_scan.BLOCK_T


# -- a traced run read by the map --------------------------------------------------

def _views(tmp_path):
    """An untraced run; a record of steps and examples; a traced run of this
    cell of a program without the scopes and kernels (the parent)."""
    root = tmp_path / CELL
    os.makedirs(root / "telemetry")
    (root / "telemetry" / "trace-p0.jsonl").write_text(json.dumps(
        {"type": "counters", "attrs": {"tables": {}, "gauges": {}}}) + "\n")
    (root / "telemetry" / "programs-p0.jsonl").write_text(json.dumps({
        "type": "program_map", "program": "train_step", "instructions": {
            "fusion.1": {"op_name": "jit(shard_step)/dot_general",
                         "phase": "forward", "module": "-",
                         "opcode": "fusion"}}}) + "\n")
    return [
        types.SimpleNamespace(record={"trace_dir": None}, trace=None),
        types.SimpleNamespace(record={"steps": 7, "examples": 56},
                              trace=None),
        types.SimpleNamespace(
            record={"trace_dir": str(root / "profile"),
                    "peak_flops_per_s": 197e12},
            trace={"device_ops": [["fusion.1", 0.5]], "steps": 5,
                   "device_step_ms": 100.0})]


@pytest.mark.parametrize("kernel", selective_scan_costs.SCAN_KERNELS)
def test_the_scans_share_is_none_where_there_is_nothing_to_read(kernel,
                                                                tmp_path):
    for view in _views(tmp_path):
        assert selective_scan_costs.scan_calls(view, kernel) is None
        assert selective_scan_costs.scan_roofline(view, kernel) is None


def _traced_run(root, cell=CELL):
    """A traced run of the program with the scopes, kept where ``run.py``
    keeps a cell's runs: the map's instructions, the trace's seconds over a
    slice of five steps."""
    root = root / cell
    step = "jit(shard_step)/tpu_ddp.forward_backward/"
    fwd = step + "jvp(SambaYDecoder)/checkpoint/"
    bwd = step + "transpose(jvp(SambaYDecoder))/checkpoint/"
    again = bwd + "rematted_computation/"
    scan = "mixer/tpu_ddp.module.selective_scan/"
    kernel = scan + "tpu_ddp.kernel.selective_scan_"
    rows = {
        # two Mamba layers: forward, forward again, backward
        "scan_fwd.1": (fwd + "layer_0/" + kernel + "fwd/pallas_call",
                       "forward", "selective_scan"),
        "scan_fwd.2": (fwd + "layer_2/" + kernel + "fwd/pallas_call",
                       "forward", "selective_scan"),
        "scan_fwd.3": (again + "layer_0/" + kernel + "fwd/pallas_call",
                       "backward", "selective_scan"),
        "scan_fwd.4": (again + "layer_2/" + kernel + "fwd/pallas_call",
                       "backward", "selective_scan"),
        "scan_bwd.1": (bwd + "layer_0/" + kernel + "bwd/pallas_call",
                       "backward", "selective_scan"),
        "scan_bwd.2": (bwd + "layer_2/" + kernel + "bwd/pallas_call",
                       "backward", "selective_scan"),
        "fusion.1": (fwd + "layer_0/" + scan + "mul", "forward",
                     "selective_scan"),
        "fusion.2": (fwd + "layer_0/mixer/tpu_ddp.module.mamba1_in/"
                     "dot_general", "forward", "mamba1_in"),
        "fusion.3": (fwd + "layer_0/mixer/tpu_ddp.module.mamba1_dt/"
                     "dot_general", "forward", "mamba1_dt"),
        "fusion.4": (fwd + "layer_4/mixer/tpu_ddp.module.gmu/dot_general",
                     "forward", "gmu"),
        "fusion.5": (bwd + "layer_4/mixer/tpu_ddp.module.gmu/dot_general",
                     "backward", "gmu"),
        "flash_fwd.1": (fwd + "layer_1/mixer/tpu_ddp.module.attention_diff/"
                        "tpu_ddp.kernel.flash_fwd/pallas_call", "forward",
                        "attention_diff"),
        "fusion.6": (fwd + "layer_1/mixer/tpu_ddp.module.attention_diff/"
                     "sub", "forward", "attention_diff"),
        "flash_fwd.2": (fwd + "layer_5/mixer/tpu_ddp.module.attention_cross/"
                        "tpu_ddp.kernel.flash_fwd/pallas_call", "forward",
                        "attention_cross"),
        "fusion.7": (fwd + "layer_5/mlp/dot_general", "forward", "-"),
    }
    os.makedirs(root / "telemetry")
    (root / "telemetry" / "programs-p0.jsonl").write_text(json.dumps({
        "type": "program_map", "program": "train_step",
        "instructions": {name: {
            "op_name": op, "phase": phase, "module": module,
            "opcode": "custom-call" if "pallas" in op else "fusion"}
            for name, (op, phase, module) in rows.items()}}) + "\n")
    (root / "telemetry" / "trace-p0.jsonl").write_text(json.dumps({
        "type": "counters", "attrs": {"tables": {}, "gauges": {
            "model/memory_readers_sum": 1.0,
            "model/kv_readers_sum": 1.0}}}) + "\n")
    seconds = [0.020, 0.020, 0.021, 0.019, 0.070, 0.080, 0.005, 0.010,
               0.004, 0.006, 0.012, 0.030, 0.003, 0.045, 0.100]
    return types.SimpleNamespace(
        record={"trace_dir": str(root / "profile"),
                "peak_flops_per_s": 197e12},
        trace={"device_ops": [[name, s] for name, s in zip(rows, seconds)],
               "steps": 5, "device_step_ms": 90.0})


def test_the_scans_costs_join_the_map_and_the_trace(tmp_path):
    run = _traced_run(tmp_path)
    per_step = lambda *seconds: sum(seconds) / 5 * 1e3  # noqa: E731
    read = {name: sum(kernel_costs.modules_ms(run, modules).values())
            for name, modules in MODULES.items()}
    assert read == {
        "device_mamba1_ms": pytest.approx(per_step(
            0.020, 0.020, 0.021, 0.019, 0.070, 0.080, 0.005, 0.010, 0.004)),
        "device_gmu_ms": pytest.approx(per_step(0.006, 0.012)),
        "device_diff_attention_ms": pytest.approx(per_step(0.030, 0.003)),
        "device_cross_attention_ms": pytest.approx(per_step(0.045))}
    # four forward calls a step (two layers, each again in the backward
    # pass) over two backward calls
    assert selective_scan_costs.scan_calls(run, "selective_scan_fwd") == (
        4, pytest.approx(0.016))
    assert selective_scan_costs.scan_calls(run, "selective_scan_bwd") == (
        2, pytest.approx(0.030))
    for kernel, calls, spent in (("selective_scan_fwd", 4, 0.016),
                                 ("selective_scan_bwd", 2, 0.030)):
        flops, moved = selective_scan_costs.scan_call(kernel, **CELL_SCAN)
        least = max(flops / 197e12, moved / 819e9)
        assert least == moved / 819e9   # the memory's bound is the larger
        assert selective_scan_costs.scan_roofline(
            run, kernel) == pytest.approx(100 * calls * least / spent)
    # the same run kept under a cell whose configuration names no Mamba-1
    # sizes: the cell's files are found, and describe no such scan
    elsewhere = _traced_run(tmp_path / "elsewhere",
                            "nemotron3-super.seq8k-v16384")
    assert "mamba_expand" not in kernel_costs.cell_files(
        elsewhere.record)["arch"]
    assert selective_scan_costs.scan_roofline(
        elsewhere, "selective_scan_fwd") is None
    # the shipped flash readers' one count reads this program's calls too
    assert kernel_costs.kernel_calls(run, "flash_fwd") == {
        "attention_diff": (1, pytest.approx(0.006)),
        "attention_cross": (1, pytest.approx(0.009))}


def test_the_new_cell_reports_every_listless_metric(case, bench):
    """What ``run.py::per_layer`` asks of the new cell, from whichever
    benchmark file the fixture hands over: every metric without a list; the
    shipped readers that list their cells, not asked."""
    names = [m["name"] for m in bench["per_layer"]
             if harness.metric_reports_in(m, CELL, bench)]
    assert not {"device_attention_ms", "flash_fwd_roofline",
                "flash_bwd_roofline", "device_mamba_ms"} & set(names)
    assert {"device_step_ms", "step_mfu", "device_forward_ms",
            "device_backward_ms", "device_optimizer_ms", "compile_s",
            "trainer_init_s", "dispatch_ms", "input_ms"} <= set(names)
