"""The ``joyai-llm-flash`` configuration's own files (its plain reference,
the shipped ``zipf_tokens`` generator and ``trainer`` adapter, so the
product's ``Trainer.run``) through the shipped harness at a size a CPU
holds, on a copy of the shipped BENCHMARK.json with the tiny cell appended
(``chipbench_tiny_joyai.py``); the entries this configuration has in the
shipped file; the two per-layer readers of what only it runs (``READERS``),
the eight it shares with the other decoder cells (one name a mechanism),
and the flash kernels' costs at two widths (``chipbench/kernel_costs.py``).
The left-out tests are in ``test_chipbench_joyai_left_out.py``."""

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
from chipbench import kernel_costs  # noqa: E402
from chipbench import run as harness  # noqa: E402
from test_chipbench_contract import appended_only  # noqa: E402
from test_chipbench_flash_bwd import PAIR, read_of  # noqa: E402

CELL = "joyai-llm-flash.seq8k-v16160"
#: the readers of what only this cell runs
READERS = ("device_mla_ms", "device_mtp_ms")
#: the readers it shares with the other decoder cells: one name a mechanism
SHARED_METRICS = ("device_moe_ms", "device_attention_ms",
                  "expert_load_max_over_mean", "moe_rows_walked_over_landed",
                  "flash_fwd_roofline", "grouped_matmul_roofline")
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

def test_the_shipped_file_has_its_configuration_cell_and_readers(bench):
    """Found by name, on the shipped file and on a copy with entries after
    the end of every list: cut where this configuration's entries start, the
    file is one of which the whole is ``appended_only``; its own readers
    list its cell alone, the shared ones list it among the decoder cells.
    Nothing is held about what follows an entry."""
    names = lambda group: [e["name"] for e in bench[group]]  # noqa: E731
    at = {"configs": names("configs").index("joyai-llm-flash"),
          "workloads": names("workloads").index(CELL)}
    before = dict(bench, **{group: bench[group][:i]
                            for group, i in at.items()})
    assert appended_only(before, bench)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert by_name[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "models",
            "moves": "images_per_s_per_chip", "workloads": [CELL]}
    for name in SHARED_METRICS:
        assert CELL in by_name[name]["workloads"]
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

CALL = dict(batch=2, tokens=8192, heads=32, kv_heads=32, qk_dim=192,
            v_dim=128, window=0)


def test_products_of_each_width_are_counted_apart():
    pairs = 2 * 32 * (8192 * 8193 // 2)
    rows = 2 * 8192 * 32
    want = {
        "flash_fwd": (192 + 128, 2 * (2 * 192 + 2 * 128) + 4),
        "flash_dq": (2 * 192 + 128, 2 * (3 * 192 + 2 * 128) + 8),
        "flash_dkv": (2 * 192 + 2 * 128, 2 * (3 * 192 + 3 * 128) + 8),
    }
    for kernel, (width, row_bytes) in want.items():
        flops, moved = kernel_costs.flash_call(kernel, **CALL)
        assert flops == 2.0 * pairs * width
        assert moved == rows * row_bytes
        # the matrix unit bounds all three at these shapes, not the memory
        assert flops / 197e12 > moved / 819e9
    # what ``chipbench/mla_costs.py`` counted for these calls (PR 36), to
    # the last digit: its tables went into the one ``flash_call``
    assert {k: kernel_costs.flash_call(k, **CALL) for k in want} == {
        "flash_fwd": (1374557306880.0, 673185792.0),
        "flash_dq": (2199291691008.0, 876609536.0),
        "flash_dkv": (2749114613760.0, 1010827264.0)}
    # the padding of 192 to 256 lanes is not work: least 6.98 ms a forward
    flops, moved = kernel_costs.flash_call("flash_fwd", **CALL)
    assert 6.9e-3 < flops / 197e12 < 7.0e-3


def test_the_references_count_of_a_step_uses_the_same_pairs():
    ref = tiny.reference()
    with open(os.path.join(REPO, "chipbench", "configs",
                           "joyai-llm-flash.json")) as f:
        arch = json.load(f)
    parts = ref.forward_macs_by_part(arch, 8192)
    flops, _ = kernel_costs.flash_call("flash_fwd", **dict(CALL, batch=1))
    assert 2.0 * parts["attention"] == 6 * flops  # six layer bodies


def test_the_layers_and_the_experts_are_read_by_the_files_keys():
    """What ``kernel_costs`` makes of ``joyai-llm-flash.json``: five layers
    of latent attention, the first dense, and the prediction module's one
    body more under its own scope; 16 gated experts of 768 held."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           "joyai-llm-flash.json")) as f:
        arch = json.load(f)
    assert kernel_costs.layer_bodies(arch) == [
        ("attention_latent", 32, False)] + [
        ("attention_latent", 32, True)] * 4 + [("mtp", 32, True)]
    call = {k: v for k, v in CALL.items() if k not in ("batch", "tokens")}
    assert kernel_costs.attention_shapes(arch) == {
        "attention_latent": call, "mtp": call}
    assert kernel_costs.routed_experts(arch) == {
        "bodies": 5, "held": 16, "products": [
            dict(contraction=2048, columns=2 * 768),
            dict(contraction=768, columns=2048)]}


# -- the per-layer readers ----------------------------------------------------------

def _reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "chipbench_metric_" + name)


@pytest.mark.parametrize("name", READERS + SHARED_METRICS + PAIR)
def test_a_reader_finds_nothing_in_a_program_without_its_scopes(name,
                                                                 tmp_path):
    """An untraced run, and a traced run of this cell of a program that
    writes no map and keeps no counters (the parent): None, nothing
    raised."""
    read = read_of(name)
    assert read(types.SimpleNamespace(
        record={"trace_dir": None}, trace=None)) is None
    assert read(types.SimpleNamespace(
        record={"steps": 7, "examples": 56}, trace=None)) is None
    root = tmp_path / CELL
    os.makedirs(root / "telemetry")
    (root / "telemetry" / "trace-p0.jsonl").write_text(json.dumps(
        {"type": "counters", "attrs": {"tables": {}, "gauges": {}}}) + "\n")
    traced = types.SimpleNamespace(
        record={"trace_dir": str(root / "profile"),
                "peak_flops_per_s": 197e12},
        trace={"device_ops": [["fusion.1", 0.5]], "steps": 5,
               "device_step_ms": 100.0})
    assert read(traced) is None


def _traced_run(root, cell=CELL):
    """A traced run of a program with the scopes, kept where ``run.py`` keeps
    a cell's runs: a map of twelve instructions, the trace's seconds over a
    slice of five steps, the counters."""
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
        # the compiler's own kernel: its name, the module its user has, a
        # layer of the stack's and the module's
        "ragged-dot-none.3": ("ragged-dot-none", "forward", "moe_experts"),
        "ragged-dot-none.4": ("ragged-dot-none", "forward", "mtp"),
        "ragged-dot-metadata.5": ("ragged-dot-metadata", "forward",
                                  "moe_dispatch"),
    }
    os.makedirs(root / "telemetry")
    (root / "telemetry" / "programs-p0.jsonl").write_text(json.dumps({
        "type": "program_map", "program": "train_step",
        "instructions": {name: {
            "op_name": op, "phase": phase, "module": module,
            "opcode": "custom-call" if name.startswith("ragged")
            else "fusion"} for name, (op, phase, module) in rows.items()}})
        + "\n")
    (root / "telemetry" / "trace-p0.jsonl").write_text(json.dumps({
        "type": "counters", "attrs": {"tables": {}, "gauges": {
            "model/expert_load_max": 1871.0, "model/expert_load_mean": 403.0,
            "model/expert_load_sum": 5 * 6450.0,
            "model/expert_rows_walked_sum": 5 * 16384.0,
            "model/expert_rows_walked_max": 16384.0}}}) + "\n")
    seconds = [0.080, 0.085, 0.075, 0.125, 0.150, 0.020, 0.010, 0.004,
               0.006, 0.003, 0.0035, 0.0005]
    return types.SimpleNamespace(
        record={"trace_dir": str(root / "profile"),
                "peak_flops_per_s": 197e12},
        trace={"device_ops": [[name, s] for name, s in zip(rows, seconds)],
               "steps": 5, "device_step_ms": 120.0})


def test_the_cells_own_readers_join_the_map_and_the_trace(tmp_path):
    run = _traced_run(tmp_path)
    # module milliseconds a step, every phase together; the module's layer
    # is the module's, whatever scopes nest inside
    assert _reader("device_mla_ms").read(run) == pytest.approx(
        (0.080 + 0.085 + 0.125 + 0.150 + 0.020 + 0.010) / 5 * 1e3)
    assert _reader("device_mtp_ms").read(run) == pytest.approx(
        (0.075 + 0.006 + 0.0035) / 5 * 1e3)


def _flash_share(kernel, calls, spent):
    flops, moved = kernel_costs.flash_call(kernel, **CALL)
    return 100 * calls * max(flops / 197e12, moved / 819e9) / spent


def _grouped_share(rows, held, products, calls, spent):
    per_call = sum(max(f / 197e12, b / 819e9) for f, b in (
        kernel_costs.grouped_call(rows=rows, held=held, **product)
        for product in products)) / 2
    return 100 * calls * per_call / spent


#: what each shared reader reads of ``_traced_run``: the recorded-run tests
#: of the three ``mla_flash_*_roofline`` went on under the names that took
#: their place
SHARED_READINGS = {
    # three forward calls, the module's among them, in 48 ms a step
    "flash_fwd_roofline": _flash_share("flash_fwd", 3, 0.048),
    "flash_dq": _flash_share("flash_dq", 1, 0.025),
    "flash_dkv": _flash_share("flash_dkv", 1, 0.030),
    # the stack's layers: the module's layer is the module's
    "device_attention_ms": (0.080 + 0.085 + 0.125 + 0.150) / 5 * 1e3,
    "device_moe_ms": (0.004 + 0.003 + 0.0005) / 5 * 1e3,
    "expert_load_max_over_mean": 1871.0 / 403.0,
    "moe_rows_walked_over_landed": 16384 / 6450,
    # two calls, one of them the module's, at 6,450 real rows a body of
    # five: 16 held gated experts, hidden 2048, width 768
    "grouped_matmul_roofline": _grouped_share(
        6450.0, 16, (dict(contraction=2048, columns=1536),
                     dict(contraction=768, columns=2048)),
        2, (0.003 + 0.0035 + 0.0005) / 5),
}


@pytest.mark.parametrize("name", SHARED_METRICS + PAIR)
def test_a_shared_reader_reads_this_cell_by_its_own_files(name, tmp_path):
    assert read_of(name)(_traced_run(tmp_path)) == pytest.approx(
        SHARED_READINGS[name], rel=1e-12)


def test_a_call_under_a_scope_the_cells_files_do_not_describe_is_no_guess(
        tmp_path):
    """The same run kept under a cell of another family: the cell's files
    are found, they describe no ``attention_latent`` and no ``mtp``, and the
    share is left out, not guessed at that family's widths."""
    elsewhere = _traced_run(tmp_path, "laguna-xs2.seq8k")
    found = kernel_costs.cell_files(elsewhere.record)
    assert found["arch"]["name"] == "laguna-xs2"
    assert set(kernel_costs.attention_shapes(found["arch"])) == {
        "attention_window", "attention_full"}
    assert _reader("flash_fwd_roofline").read(elsewhere) is None
    for kernel in PAIR:
        assert read_of(kernel)(elsewhere) is None
