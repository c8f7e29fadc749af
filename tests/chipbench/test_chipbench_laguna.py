"""The ``laguna-xs2`` configuration's own files (its plain reference, the
``zipf_tokens`` generator, the shipped ``trainer`` adapter, so the product's
``Trainer.run``) through the shipped harness at a size a CPU holds: a decoder
of the program's ``models/decoder.py`` with every kind of layer, float32. The
sound program is ``correct``; one whose window, shared expert or routed
scaling factor is left out is not, each by a limit of the comparison."""

import json
import os
import sys

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import decoder_tiny as tiny  # noqa: E402
from chipbench import run as harness  # noqa: E402
from test_chipbench_flash_bwd import PAIR, read_of  # noqa: E402

CELL = "laguna-tiny.t24"
LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-3, "update_gap": 0.02,
          "grad_diff": 1e-3, "out_grad_diff": 1e-3}
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


def write(root):
    """A benchmark of one cell in ``root``: the shipped configuration file
    at the tiny decoder's sizes, ``"reference": "laguna-xs2"`` (the shipped
    reference file), the shipped generator at 24 tokens."""
    config = tiny.arch()
    config.update(name="laguna-tiny", reference="laguna-xs2",
                  precision="float32", train_config={
                      "model": "tiny_decoder", "compute_dtype": "float32",
                      "optimizer": "adamw", "lr": 1e-3, "weight_decay": 0.1,
                      "remat": True, "prefetch_depth": 0})
    files = {
        "configs/laguna-tiny.json": config,
        "traffic/t24.json": {
            "name": "t24", "chips": 1, "mesh": {"data": 1},
            "per_shard_batch": 2, "steps_per_call": 1, "overlays": {},
            "dataset": {"kind": "zipf_tokens", "size": 16, "seq_len": tiny.T,
                        "vocab_size": tiny.VOCAB, "exponent": 1.0,
                        "example_holds": {"tokens": tiny.T}}},
        "limits/" + CELL + ".json": {"cell": CELL, "limits": LIMITS},
        "benchmark.json": {
            "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
            "run_seconds": 1,
            "configs": [{"name": "laguna-tiny", "source": "a test",
                         "file": "configs/laguna-tiny.json", "reduced": [],
                         "why": "a test"}],
            "workloads": [{"name": CELL, "config": "laguna-tiny",
                           "traffic": "t24", "chips": 1, "why": "a test"}],
            "end_to_end": [
                {"name": "images_per_s_per_chip", "unit": "images/s/chip",
                 "better": "higher", "bound": 0.1, "source": "host_clock"},
                {"name": "step_ms_p95", "unit": "ms", "better": "lower",
                 "bound": 0.1, "source": "host_clock",
                 "workloads": ["another.cell"]},
                {"name": "setup_s", "unit": "s", "better": "lower",
                 "bound": 0.1, "source": "host_clock"}],
            "per_layer": [
                {"name": "step_mfu", "unit": "%", "better": "higher",
                 "source": "device_trace", "layer": "models",
                 "moves": "images_per_s_per_chip"}]},
    }
    for name, content in files.items():
        path = os.path.join(root, name)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(content, f)
    return os.path.join(root, "benchmark.json"), [root]


#: ``--seconds`` of the tiny cell's runs. The harness refuses a window of
#: fewer than 20 dispatch intervals (``run.py::end_to_end``), so of fewer
#: than 21 steps; a window is whole epochs, here of 16 / 2 = 8 steps, so it
#: needs three of them, and it gets the third only if the second ends, 16
#: steps in, before ``--seconds`` have passed: a step has to take less than
#: ``SECONDS / 16``. At 0.2 s that was 12.5 ms, where this cell's CPU step
#: reads 5.2-6.5 ms alone and read 14.7 ms once beside five other test
#: workers (PERF.md section 6, PR 32). 1.0 s holds up to 62.5 ms, over four
#: times the slowest read. A longer window and not another epoch: the
#: data, and so every number ``correct`` compares below, stay what they were
SECONDS = 1.0


def run(tmp_path, seed=2**31 + 29):
    bench, roots = write(str(tmp_path))
    return harness.run_cell(CELL, seed, SECONDS, False, bench_path=bench,
                            roots=roots, device_check=False)


def test_the_new_configuration_is_correct_through_trainer_run(tmp_path,
                                                              capsys):
    tiny.register()
    result = run(tmp_path)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 20
    assert set(result["metrics"]) == {"images_per_s_per_chip", "setup_s"}
    printed = capsys.readouterr().out
    assert "chipbench: tokens_per_s_per_chip=" in printed


def _broken(fault):
    """Registers ``tiny_decoder`` with one piece of the model left out."""
    import dataclasses

    from tpu_ddp.models import decoder as D
    from tpu_ddp.models.zoo import MODEL_REGISTRY

    spec = tiny.spec()
    if fault == "window":
        spec = dataclasses.replace(spec, layers=tuple(
            dataclasses.replace(layer, window=0) for layer in spec.layers))
    elif fault == "scaling":
        spec = dataclasses.replace(spec, routed_scaling=1.0)
    MODEL_REGISTRY["tiny_decoder"] = (
        lambda num_classes=10, bn_cross_replica_axis=None, dtype=None:
        D.SparseDecoder(spec, dtype=dtype))
    if fault == "shared_expert":
        import flax.linen as nn

        class Silent(nn.Module):  # SwiGLU's leaves, and no output
            width: int
            dtype: object = None

            @nn.compact
            def __call__(self, x):
                dense = lambda n, name: nn.Dense(  # noqa: E731
                    n, use_bias=False, dtype=self.dtype, name=name)
                h = nn.silu(dense(self.width, "gate")(x)) * dense(
                    self.width, "up")(x)
                return 0.0 * dense(x.shape[-1], "down")(h)

        return Silent
    return None


@pytest.mark.parametrize("fault", ["window", "shared_expert", "scaling"])
def test_a_model_with_a_piece_left_out_is_not_correct(tmp_path, monkeypatch,
                                                      fault):
    from tpu_ddp.models import moe

    silent = _broken(fault)
    if silent is not None:
        monkeypatch.setattr(moe, "SwiGLU", silent)
    try:
        result = run(tmp_path)
    finally:
        tiny.register()
    assert result["correct"] is False
    failed = [name for name, number in result["compared"].items()
              if name != "repeated_rows" and not (
                  number["value"] <= number["limit"])]
    assert failed, result["compared"]


# -- the per-layer readers this configuration brings ---------------------------

NEW_METRICS = ("device_moe_ms", "device_attention_ms",
               "expert_load_max_over_mean", "flash_fwd_roofline",
               "grouped_matmul_roofline")


def _reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "chipbench_metric_" + name)


@pytest.mark.parametrize("name", NEW_METRICS + PAIR)
def test_a_reader_finds_nothing_in_a_program_without_its_scopes(name,
                                                                 tmp_path):
    """An untraced run, and a traced run of a program that writes no map and
    keeps no such counters (the parent): None, nothing raised."""
    import types

    read = read_of(name)
    assert read(types.SimpleNamespace(
        record={"trace_dir": None}, trace=None)) is None
    os.makedirs(tmp_path / "telemetry")
    (tmp_path / "telemetry" / "trace-p0.jsonl").write_text(json.dumps(
        {"type": "counters", "attrs": {"tables": {}, "gauges": {}}}) + "\n")
    traced = types.SimpleNamespace(
        record={"trace_dir": str(tmp_path / "profile"),
                "peak_flops_per_s": 197e12},
        trace={"device_ops": [["fusion.1", 0.5]], "steps": 5,
               "device_step_ms": 100.0})
    assert read(traced) is None


def test_the_readers_join_the_map_the_trace_and_the_counters(tmp_path):
    """A traced run of a program with the scopes: module milliseconds by
    scope, the counters' ratio, and each kernel's least time from shapes over
    the device time of the instructions its scope names."""
    import types

    from chipbench import kernel_costs

    # where run.py keeps this cell's runs: the readers take the cell's
    # shapes from the files of the cell the record's directory names
    tmp_path = tmp_path / "laguna-xs2.seq8k"
    step = "jit(shard_step)/tpu_ddp.forward_backward/"
    fwd = step + "jvp(SparseDecoder)/layer_1/"
    bwd = step + "transpose(jvp(SparseDecoder))/layer_1/"
    rows = {
        "custom-call.1": (fwd + "attn/tpu_ddp.module.attention_window/"
                          "tpu_ddp.kernel.flash_fwd/pallas_call",
                          "forward", "attention_window"),
        "custom-call.2": (bwd + "attn/tpu_ddp.module.attention_window/"
                          "tpu_ddp.kernel.flash_dq/pallas_call",
                          "backward", "attention_window"),
        # the compiler's own kernel: its name, the module its user has
        "ragged-dot-none.3": ("ragged-dot-none", "forward", "moe_experts"),
        "ragged-dot-metadata.5": ("ragged-dot-metadata", "forward",
                                  "moe_dispatch"),
        "fusion.4": (fwd + "moe/tpu_ddp.module.moe_route/dot_general",
                     "forward", "moe_route"),
    }
    os.makedirs(tmp_path / "telemetry")
    (tmp_path / "telemetry" / "programs-p0.jsonl").write_text(json.dumps({
        "type": "program_map", "program": "train_step",
        "instructions": {name: {"op_name": op, "opcode": "custom-call",
                                "phase": phase, "module": module}
                         for name, (op, phase, module) in rows.items()}})
        + "\n")
    (tmp_path / "telemetry" / "trace-p0.jsonl").write_text(json.dumps({
        "type": "counters", "attrs": {"tables": {}, "gauges": {
            "model/expert_load_max": 900.0, "model/expert_load_mean": 300.0,
            "model/expert_load_sum": 4 * 8000.0}}}) + "\n")
    run = types.SimpleNamespace(
        record={"trace_dir": str(tmp_path / "profile"),
                "peak_flops_per_s": 197e12},
        trace={"device_ops": [["custom-call.1", 0.050],
                              ["custom-call.2", 0.030],
                              ["ragged-dot-none.3", 0.002],
                              ["ragged-dot-metadata.5", 0.0005],
                              ["fusion.4", 0.010]],
               "steps": 5, "device_step_ms": 18.4})
    assert _reader("device_attention_ms").read(run) == pytest.approx(16.0)
    assert _reader("device_moe_ms").read(run) == pytest.approx(2.5)
    assert _reader("expert_load_max_over_mean").read(run) == 3.0
    # one forward call of a sliding layer's attention in 10 ms a step
    flops, moved = kernel_costs.flash_call(
        "flash_fwd", batch=2, tokens=8192, heads=64, kv_heads=8,
        qk_dim=128, v_dim=128, window=512)
    pairs = 512 * 513 // 2 + (8192 - 512) * 512
    assert flops == 2 * 2 * 2 * 64 * pairs * 128
    least = max(flops / 197e12, moved / 819e9)
    assert _reader("flash_fwd_roofline").read(run) == pytest.approx(
        100 * least / 0.010)
    # the sliding layer's dQ call in 6 ms a step, by the pair's own count
    flops, moved = kernel_costs.flash_call(
        "flash_dq", batch=2, tokens=8192, heads=64, kv_heads=8,
        qk_dim=128, v_dim=128, window=512)
    assert read_of("flash_dq")(run) == pytest.approx(
        100 * max(flops / 197e12, moved / 819e9) / 0.006)
    assert read_of("flash_dkv")(run) is None  # no such call
    # one call of the grouped kernel at 8,000 real rows, its layout call's
    # time counted with it; the two products' least times averaged
    with open(os.path.join(harness.HERE, "configs", "laguna-xs2.json")) as f:
        held = json.load(f)["num_experts"]
    per_call = sum(max(f / 197e12, b / 819e9) for f, b in (
        kernel_costs.grouped_call(rows=8000.0, held=held, contraction=2048,
                                  columns=1024),
        kernel_costs.grouped_call(rows=8000.0, held=held, contraction=512,
                                  columns=2048))) / 2
    assert _reader("grouped_matmul_roofline").read(run) == pytest.approx(
        100 * per_call / 0.0005)
    # the same record kept under another cell's name: that cell's files are
    # found as they are, and describe no layer, head or expert of a decoder
    elsewhere = dict(run.record, trace_dir=str(
        tmp_path.parent / "resnet50-cifar.b512" / "profile"))
    found = kernel_costs.cell_files(elsewhere)
    assert found["arch"]["name"] == "resnet50-cifar"
    assert (found["batch"], found["tokens"]) == (512, None)
    assert kernel_costs.layer_bodies(found["arch"]) == []
    assert kernel_costs.attention_shapes(found["arch"]) == {}
    assert kernel_costs.routed_experts(found["arch"]) is None
    # and a run of no cell of the benchmark has no files
    assert kernel_costs.cell_files(dict(run.record, trace_dir=str(
        tmp_path.parent / "no-such.cell" / "profile"))) is None
    assert kernel_costs.cell_files({"trace_dir": None}) is None


#: (operations, bytes) of ``laguna-xs2.seq8k``'s six calls as the function of
#: one head width counted them before it took a key width and a value width
#: (commit 6e2e4eb, ``flash_call(..., head_dim=128, ...)``)
RECORDED_CALLS = {
    ("flash_fwd", 64, 512): (266304749568.0, 608174080.0),
    ("flash_fwd", 48, 0): (1649468768256.0, 472907776.0),
    ("flash_dq", 64, 512): (399457124352.0, 880803840.0),
    ("flash_dq", 48, 0): (2474203152384.0, 677380096.0),
    ("flash_dkv", 64, 512): (532609499136.0, 679477248.0),
    ("flash_dkv", 48, 0): (3298937536512.0, 543162368.0),
}


@pytest.mark.parametrize("kernel,heads,window", list(RECORDED_CALLS))
def test_at_equal_widths_a_flash_call_costs_what_it_did(kernel, heads,
                                                        window):
    from chipbench import kernel_costs

    assert kernel_costs.flash_call(
        kernel, batch=2, tokens=8192, heads=heads, kv_heads=8, qk_dim=128,
        v_dim=128, window=window) == RECORDED_CALLS[kernel, heads, window]


def test_the_window_and_full_layers_are_read_by_the_files_keys():
    """What ``kernel_costs`` makes of ``laguna-xs2.json``: layers 0-4 by the
    per-layer lists, a scope for each kind of attention with that kind's
    heads, 32 gated experts of 512 held in the four sparse layers."""
    from chipbench import kernel_costs

    arch = harness.load_json(os.path.join(
        harness.HERE, "configs", "laguna-xs2.json"))
    assert kernel_costs.layer_bodies(arch) == [
        ("attention_full", 48, False), ("attention_window", 64, True),
        ("attention_window", 64, True), ("attention_window", 64, True),
        ("attention_full", 48, True)]
    shared = dict(kv_heads=8, qk_dim=128, v_dim=128)
    assert kernel_costs.attention_shapes(arch) == {
        "attention_full": dict(shared, heads=48, window=0),
        "attention_window": dict(shared, heads=64, window=512)}
    assert kernel_costs.routed_experts(arch) == {
        "bodies": 4, "held": 32, "products": [
            dict(contraction=2048, columns=1024),
            dict(contraction=512, columns=2048)]}
