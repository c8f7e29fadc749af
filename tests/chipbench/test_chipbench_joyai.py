"""The ``joyai-llm-flash`` configuration's own files (its plain reference,
the shipped ``zipf_tokens`` generator and ``trainer`` adapter, so the
product's ``Trainer.run``) through the shipped harness at a size a CPU
holds, on a copy of the shipped BENCHMARK.json with the tiny cell appended
(``chipbench_tiny_joyai.py``); the entries this configuration appended to
the shipped file; the five per-layer readers of what only it runs, which
wait for a ``benchmark`` PR to list them (``READERS``), and the flash
kernels' costs at two widths (``chipbench/mla_costs.py``). The left-out
tests are in ``test_chipbench_joyai_left_out.py``."""

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

import chipbench_tiny_joyai as tiny_cell  # noqa: E402
import joyai_tiny as tiny  # noqa: E402
from chipbench import kernel_costs, mla_costs  # noqa: E402
from chipbench import run as harness  # noqa: E402
from test_chipbench_contract import appended_only  # noqa: E402

CELL = "joyai-llm-flash.seq8k-v16160"
#: readers under ``chipbench/layer_metrics`` that ``per_layer`` does not list
#: yet: ``test_chipbench_nemotron.py`` holds its six to the end of that list
#: (PERF.md section 7, for a ``benchmark`` PR), so the cell reports the
#: shipped metrics that carry no ``workloads`` list and these are read here
READERS = ("device_mla_ms", "device_mtp_ms", "mla_flash_fwd_roofline",
           "mla_flash_dq_roofline", "mla_flash_dkv_roofline")
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
    """Three AdamW steps of the tiny decoder on the two-term loss through
    ``Trainer.run`` against the float32 reference: losses, first gradient,
    update."""
    tiny.register()
    result = tiny_cell.run(tmp_path)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 20
    assert set(result["metrics"]) == {"images_per_s_per_chip", "setup_s"}
    assert set(result["compared"]) == {
        "repeated_rows", "loss_gap", "grad_gap", "update_gap", "grad_diff",
        "out_grad_diff"}
    assert "chipbench: tokens_per_s_per_chip=" in capsys.readouterr().out


# -- what was appended to the shipped file --------------------------------------

def test_the_shipped_file_got_one_configuration_and_one_cell():
    """BENCHMARK.json is the parent's with one configuration and one cell
    appended and nothing else: cut where this configuration's entries
    start, it is a file of which the shipped one is ``appended_only``. The
    cell reports the metrics that list no cells (a later PR may append
    metrics that list it: nothing here holds the end of a list)."""
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = lambda group: [e["name"] for e in bench[group]]  # noqa: E731
    at = {"configs": names("configs").index("joyai-llm-flash"),
          "workloads": names("workloads").index(CELL)}
    before = dict(bench, **{group: bench[group][:i]
                            for group, i in at.items()})
    assert appended_only(before, bench)
    assert {"step_mfu", "device_step_ms"} <= {
        m["name"] for m in bench["per_layer"] if "workloads" not in m}
    cell = bench["workloads"][at["workloads"]]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "~512 pairs a step" in cell["why"]


def test_the_configuration_file_holds_every_published_width():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "joyai-llm-flash.json")) as f:
        arch = json.load(f)
    widths = dict(hidden_size=2048, num_attention_heads=32,
                  num_key_value_heads=32, q_lora_rank=1536, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, qk_head_dim=192,
                  v_head_dim=128, head_dim=64, intermediate_size=7168,
                  moe_intermediate_size=768, n_shared_experts=1,
                  num_experts_per_tok=8, routed_scaling_factor=2.5,
                  rope_theta=32000000, first_k_dense_replace=1,
                  num_hidden_layers=40, num_nextn_predict_layers=1,
                  n_group=1, topk_group=1, rms_norm_eps=1e-06)
    assert {k: arch[k] for k in widths} == widths
    assert arch["scoring_func"] == "sigmoid"
    assert arch["topk_method"] == "noaux_tc" and arch["norm_topk_prob"]
    assert arch["published"] == dict(
        num_hidden_layers=40, n_routed_experts=256, vocab_size=129280)
    assert arch["reduced"] == ["layers_here", "n_routed_experts",
                               "vocab_size"]
    assert (arch["layers_here"], arch["n_routed_experts"],
            arch["vocab_size"]) == (5, 16, 16160)
    assert arch["vocab_size"] * 8 == arch["published"]["vocab_size"]
    assert arch["mtp_loss_weight"] == 0.3
    for key in ("deployment", "parameters_here", "assumed", "reduced_why"):
        assert arch[key]
    assert set(arch["reduced_why"]) == set(arch["reduced"])
    assert {"mtp_module", "mtp_loss_weight", "mtp_hidden", "rotary_pairs",
            "router_bias", "optimizer", "init"} <= set(arch["assumed"])
    # one chip's share of sixteen: what the program is told
    assert arch["train_config"]["model_overrides"] == dict(
        num_layers=5, experts_held=16, expert_offset=0, vocab_rows=16160)
    assert arch["train_config"]["attention"] == "flash"
    assert arch["train_config"]["remat"] is True


def test_the_mix_draws_from_the_slice():
    traffic = harness.load_json(os.path.join(
        REPO, "chipbench", "traffic", "seq8k-v16160.json"))
    assert traffic["dataset"] == {
        "kind": "zipf_tokens", "size": 32, "seq_len": 8192,
        "vocab_size": 16160, "exponent": 1.0,
        "example_holds": {"tokens": 8192}}
    assert (traffic["per_shard_batch"], traffic["chips"],
            traffic["steps_per_call"]) == (2, 1, 1)


# -- the kernels' costs at two widths ----------------------------------------------

CALL = dict(batch=2, tokens=8192, heads=32, qk_dim=192, v_dim=128)


def test_products_of_each_width_are_counted_apart():
    pairs = 2 * 32 * (8192 * 8193 // 2)
    rows = 2 * 8192 * 32
    want = {
        "flash_fwd": (192 + 128, 2 * (2 * 192 + 2 * 128) + 4),
        "flash_dq": (2 * 192 + 128, 2 * (3 * 192 + 2 * 128) + 8),
        "flash_dkv": (2 * 192 + 2 * 128, 2 * (3 * 192 + 3 * 128) + 8),
    }
    for kernel, (width, row_bytes) in want.items():
        flops, moved = mla_costs.flash_call(kernel, **CALL)
        assert flops == 2.0 * pairs * width
        assert moved == rows * row_bytes
        # the matrix unit bounds all three at these shapes, not the memory
        assert flops / 197e12 > moved / 819e9
    # with one width they are the shipped costs of as many heads on each side
    for kernel in want:
        assert mla_costs.flash_call(
            kernel, **dict(CALL, qk_dim=128)) == kernel_costs.flash_call(
                kernel, batch=2, tokens=8192, heads=32, kv_heads=32,
                head_dim=128, window=0)
    # the padding of 192 to 256 lanes is not work: least 6.98 ms a forward
    flops, moved = mla_costs.flash_call("flash_fwd", **CALL)
    assert 6.9e-3 < flops / 197e12 < 7.0e-3


def test_the_references_count_of_a_step_uses_the_same_pairs():
    ref = tiny.reference()
    with open(os.path.join(REPO, "chipbench", "configs",
                           "joyai-llm-flash.json")) as f:
        arch = json.load(f)
    parts = ref.forward_macs_by_part(arch, 8192)
    flops, _ = mla_costs.flash_call("flash_fwd", **dict(CALL, batch=1))
    assert 2.0 * parts["attention"] == 6 * flops  # six layer bodies


# -- the per-layer readers ----------------------------------------------------------

def _reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "chipbench_metric_" + name)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_in_a_program_without_its_scopes(name,
                                                                 tmp_path):
    """An untraced run, and a traced run of a program that writes no map
    (the parent): None, nothing raised."""
    reader = _reader(name)
    assert reader.read(types.SimpleNamespace(
        record={"trace_dir": None}, trace=None)) is None
    assert reader.read(types.SimpleNamespace(
        record={"steps": 7, "examples": 56}, trace=None)) is None
    os.makedirs(tmp_path / "telemetry")
    (tmp_path / "telemetry" / "trace-p0.jsonl").write_text(json.dumps(
        {"type": "counters", "attrs": {"tables": {}, "gauges": {}}}) + "\n")
    traced = types.SimpleNamespace(
        record={"trace_dir": str(tmp_path / "profile"),
                "peak_flops_per_s": 197e12},
        trace={"device_ops": [["fusion.1", 0.5]], "steps": 5,
               "device_step_ms": 100.0})
    assert reader.read(traced) is None


def _traced_run(root, cell=CELL):
    """A traced run of a program with the scopes, kept where ``run.py`` keeps
    a cell's runs: a map of nine instructions and the trace's seconds over
    a slice of five steps."""
    root = root / cell
    step = "jit(shard_step)/tpu_ddp.forward_backward/"
    fwd = step + "jvp(SparseDecoder)/checkpoint/"
    bwd = step + "transpose(jvp(SparseDecoder))/checkpoint/"
    attn = "attn/tpu_ddp.module.attention_latent/tpu_ddp.kernel."
    mtp = "tpu_ddp.module.mtp/mtp_layer/"
    rows = {
        "flash_fwd.1": (fwd + "layer_0/" + attn + "flash_fwd/pallas_call",
                        "forward", "attention_latent"),
        # the same layer recomputed in the backward pass: a second call
        "flash_fwd.2": (bwd + "rematted_computation/layer_0/" + attn
                        + "flash_fwd/pallas_call", "backward",
                        "attention_latent"),
        # the module's layer calls the same kernel under the module's name
        "flash_fwd.3": (fwd + mtp + attn + "flash_fwd/pallas_call",
                        "forward", "mtp"),
        "flash_dq.1": (bwd + "layer_0/" + attn + "flash_dq/pallas_call",
                       "backward", "attention_latent"),
        "flash_dkv.1": (bwd + "layer_0/" + attn + "flash_dkv/pallas_call",
                        "backward", "attention_latent"),
        "fusion.1": (fwd + "layer_0/attn/tpu_ddp.module.mla_q/dot_general",
                     "forward", "mla_q"),
        "fusion.2": (fwd + "layer_0/attn/tpu_ddp.module.mla_kv/dot_general",
                     "forward", "mla_kv"),
        "fusion.3": (fwd + "layer_1/moe/tpu_ddp.module.moe_route/"
                     "dot_general", "forward", "moe_route"),
        "fusion.4": (fwd + mtp + "moe/tpu_ddp.module.moe_route/dot_general",
                     "forward", "mtp"),
    }
    os.makedirs(root / "telemetry")
    (root / "telemetry" / "programs-p0.jsonl").write_text(json.dumps({
        "type": "program_map", "program": "train_step",
        "instructions": {name: {"op_name": op, "opcode": "fusion",
                                "phase": phase, "module": module}
                         for name, (op, phase, module) in rows.items()}})
        + "\n")
    (root / "telemetry" / "trace-p0.jsonl").write_text(json.dumps(
        {"type": "counters", "attrs": {"tables": {}, "gauges": {}}}) + "\n")
    seconds = [0.080, 0.085, 0.075, 0.125, 0.150, 0.020, 0.010, 0.004,
               0.006]
    return types.SimpleNamespace(
        record={"trace_dir": str(root / "profile"),
                "peak_flops_per_s": 197e12},
        trace={"device_ops": [[name, s] for name, s in zip(rows, seconds)],
               "steps": 5, "device_step_ms": 120.0})


def test_the_readers_join_the_map_and_the_trace(tmp_path):
    run = _traced_run(tmp_path)
    # module milliseconds a step, every phase together; the module's layer
    # is the module's, whatever scopes nest inside
    assert _reader("device_mla_ms").read(run) == pytest.approx(
        (0.080 + 0.085 + 0.125 + 0.150 + 0.020 + 0.010) / 5 * 1e3)
    assert _reader("device_mtp_ms").read(run) == pytest.approx(
        (0.075 + 0.006) / 5 * 1e3)
    # three forward calls, the module's among them, in 48 ms a step
    for kernel, calls, spent in (("flash_fwd", 3, 0.048),
                                 ("flash_dq", 1, 0.025),
                                 ("flash_dkv", 1, 0.030)):
        flops, moved = mla_costs.flash_call(kernel, **CALL)
        least = max(flops / 197e12, moved / 819e9)
        assert _reader("mla_" + kernel + "_roofline").read(
            run) == pytest.approx(100 * calls * least / spent)
    # the same run kept under a cell of another family: not its shapes
    elsewhere = _traced_run(tmp_path / "elsewhere", "laguna-xs2.seq8k")
    assert mla_costs.cell_shapes(elsewhere.record) is None
    assert _reader("mla_flash_fwd_roofline").read(elsewhere) is None
