"""The ``nemotron3-super`` configuration's own files (its plain reference,
the shipped ``zipf_tokens`` generator and ``trainer`` adapter, so the
product's ``Trainer.run``) through the shipped harness at a size a CPU
holds, on a copy of the shipped BENCHMARK.json with the tiny cell appended
(``chipbench_tiny_hybrid.py``); the entries this configuration has in the
shipped file; the three per-layer readers that are its cell's own, the eight
it shares with the other decoder cells (one name a mechanism), and the
scan's costs from shapes (``chipbench/ssd_costs.py``). The left-out tests
are in ``test_chipbench_nemotron_left_out.py``."""

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

import chipbench_tiny_hybrid as tiny_cell  # noqa: E402
import hybrid_tiny as tiny  # noqa: E402
from chipbench import kernel_costs, ssd_costs  # noqa: E402
from chipbench import run as harness  # noqa: E402
from test_chipbench_contract import appended_only  # noqa: E402
from test_chipbench_flash_bwd import PAIR, read_of  # noqa: E402

CELL = "nemotron3-super.seq8k-v16384"
#: the readers of what only this cell runs
NEW_METRICS = ("device_mamba_ms", "scan_fwd_roofline", "scan_bwd_roofline")
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
    """Three AdamW steps of the tiny hybrid decoder through ``Trainer.run``
    against the stepwise float32 reference: losses, first gradient, update."""
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
    at = {"configs": names("configs").index("nemotron3-super"),
          "workloads": names("workloads").index(CELL)}
    before = dict(bench, **{group: bench[group][:i]
                            for group, i in at.items()})
    assert appended_only(before, bench)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
    for name in SHARED_METRICS:
        assert CELL in by_name[name]["workloads"]
    assert {"step_mfu", "device_step_ms"} <= {
        m["name"] for m in bench["per_layer"] if "workloads" not in m}
    cell = bench["workloads"][at["workloads"]]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "1.6% of its deployed load" in cell["why"]


def test_the_configuration_file_holds_every_published_width():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron3-super.json")) as f:
        arch = json.load(f)
    widths = dict(hidden_size=4096, head_dim=128, mamba_head_dim=64,
                  ssm_state_size=128, conv_kernel=4, chunk_size=128,
                  moe_latent_size=1024, moe_intermediate_size=2688,
                  moe_shared_expert_intermediate_size=5376,
                  num_experts_per_tok=22, routed_scaling_factor=5,
                  num_hidden_layers=88, expand=2)
    assert {k: arch[k] for k in widths} == widths
    assert arch["published"] == dict(
        n_routed_experts=512, vocab_size=131072, mamba_num_heads=128,
        n_groups=8, num_attention_heads=32, num_key_value_heads=2,
        num_nextn_predict_layers=1, num_hidden_layers=88)
    assert set(arch["reduced"]) == (set(arch["published"])
                                    - {"num_hidden_layers"}) | {"layers_here"}
    assert arch["hybrid_override_pattern"][:arch["layers_here"]] == (
        "MEMEMEM*EME")
    for key in ("deployment", "parameters_here", "assumed", "reduced_why"):
        assert arch[key]
    assert set(arch["reduced_why"]) == set(arch["reduced"])
    # one chip's share of a group of eight: what the program is told
    assert arch["train_config"]["model_overrides"] == dict(
        num_layers=11, experts_held=8, expert_offset=0, vocab_rows=16384,
        head_positions=8, head_position=0)


# -- the scan's costs from shapes -------------------------------------------------

CELL_SCAN = dict(batch=2, tokens=8192, heads=16, head_dim=64, groups=1,
                 state=128, chunk=128)


def test_the_scans_operations_and_bytes_are_counted_from_shapes():
    pairs = 128 * 129 // 2
    macs = 64 * (pairs * 128 + 16 * (pairs * 64 + 2 * 128 * 64 * 128
                                     + 64 * 128))
    assert ssd_costs.scan_forward_macs(
        **{k: v for k, v in CELL_SCAN.items() if k != "batch"}) == macs
    flops, moved = ssd_costs.scan_call("ssd_scan_fwd", **CELL_SCAN)
    assert flops == 2 * 2 * macs
    x_like, bc_like = 2 * 8192 * 16 * 64 * 2, 2 * 8192 * 128 * 2
    states = 2 * 64 * 16 * 64 * 128 * 4
    assert moved == 2 * x_like + 2 * bc_like + 2 * 8192 * 16 * 4 + states
    back_flops, back_moved = ssd_costs.scan_call("ssd_scan_bwd", **CELL_SCAN)
    assert back_flops > flops and back_moved > moved
    # both are bound by the memory, not by the matrix unit, at these shapes
    for f, b in ((flops, moved), (back_flops, back_moved)):
        assert b / 819e9 > f / 197e12
    # a ragged length pays for whole chunks; one shorter than a chunk for
    # its own positions
    short = dict(CELL_SCAN, tokens=100)
    assert ssd_costs.scan_call("ssd_scan_fwd", **short)[0] == 2 * 2 * (
        100 * 101 // 2 * 128 + 16 * (100 * 101 // 2 * 64
                                     + 2 * 100 * 64 * 128 + 64 * 128))
    with pytest.raises(ValueError):
        ssd_costs.scan_call("ssd_scan", **CELL_SCAN)


def test_the_references_count_of_a_step_uses_the_same_scan_costs():
    ref = tiny.reference()
    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron3-super.json")) as f:
        arch = json.load(f)
    parts = ref.forward_macs_by_part(arch, 8192)
    sizes = {k: v for k, v in CELL_SCAN.items() if k != "batch"}
    assert parts["scan"] == 5 * ssd_costs.scan_forward_macs(**sizes)
    assert parts["scan"] < 0.01 * sum(parts.values())


# -- the per-layer readers ----------------------------------------------------------

def _reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "chipbench_metric_" + name)


@pytest.mark.parametrize("name", NEW_METRICS + SHARED_METRICS + PAIR)
def test_a_reader_finds_nothing_in_a_program_without_its_scopes(name,
                                                                 tmp_path):
    """An untraced run, and a traced run of this cell of a program that
    writes no map and keeps no such counters (the parent): None, nothing
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
    a cell's runs: a map of fourteen instructions, the trace's seconds over
    a slice of five steps, the counters."""
    root = root / cell
    step = "jit(shard_step)/tpu_ddp.forward_backward/"
    fwd = step + "jvp(HybridDecoder)/checkpoint/"
    bwd = step + "transpose(jvp(HybridDecoder))/checkpoint/"
    scan = "mixer/tpu_ddp.module.ssm_scan/tpu_ddp.kernel."
    attn = "block_7/mixer/tpu_ddp.module.attention_full/"
    rows = {
        "fusion.1": (fwd + "block_0/" + scan + "ssd_scan_fwd/dot_general",
                     "forward", "ssm_scan"),
        "fusion.2": (fwd + "block_0/" + scan + "ssd_scan_fwd/exp",
                     "forward", "ssm_scan"),
        # the same block recomputed in the backward pass: a second call
        "fusion.3": (bwd + "rematted_computation/block_0/" + scan
                     + "ssd_scan_fwd/dot_general", "backward", "ssm_scan"),
        "fusion.4": (bwd + "block_2/" + scan + "ssd_scan_bwd/dot_general",
                     "backward", "ssm_scan"),
        "fusion.5": (fwd + "block_0/mixer/tpu_ddp.module.mamba_in/"
                     "dot_general", "forward", "mamba_in"),
        "fusion.6": (fwd + "block_1/mixer/tpu_ddp.module.moe_latent/"
                     "dot_general", "forward", "moe_latent"),
        "fusion.7": (fwd + "block_1/mixer/tpu_ddp.module.moe_route/"
                     "dot_general", "forward", "moe_route"),
        # the one attention block: the three flash kernels and what feeds them
        "flash_fwd.1": (fwd + attn + "tpu_ddp.kernel.flash_fwd/pallas_call",
                        "forward", "attention_full"),
        "flash_dq.1": (bwd + attn + "tpu_ddp.kernel.flash_dq/pallas_call",
                       "backward", "attention_full"),
        "flash_dkv.1": (bwd + attn + "tpu_ddp.kernel.flash_dkv/pallas_call",
                        "backward", "attention_full"),
        "fusion.8": (bwd + attn + "mul", "backward", "attention_full"),
        # the compiler's own kernel: its name, the module its user has
        "ragged-dot-none.3": ("ragged-dot-none", "forward", "moe_experts"),
        "ragged-dot-none.4": ("ragged-dot-none", "backward", "moe_experts"),
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
            "model/expert_load_max": 1800.0, "model/expert_load_mean": 720.0,
            "model/expert_load_sum": 5 * 5760.0,
            "model/expert_rows_walked_sum": 5 * 11264.0,
            "model/expert_rows_walked_max": 11264.0}}}) + "\n")
    seconds = [0.010, 0.005, 0.015, 0.040, 0.020, 0.008, 0.002,
               0.0075, 0.011, 0.014, 0.0025, 0.003, 0.0035, 0.0005]
    return types.SimpleNamespace(
        record={"trace_dir": str(root / "profile"),
                "peak_flops_per_s": 197e12},
        trace={"device_ops": [[name, s] for name, s in zip(rows, seconds)],
               "steps": 5, "device_step_ms": 30.0})


def test_the_cells_own_readers_join_the_map_and_the_trace(tmp_path):
    run = _traced_run(tmp_path)
    # module milliseconds a step, every phase together
    assert _reader("device_mamba_ms").read(run) == pytest.approx(
        (0.010 + 0.005 + 0.015 + 0.040 + 0.020) / 5 * 1e3)
    # two forward calls (block 0, and block 0 again in the backward phase)
    # in 6 ms a step; one backward call in 8 ms
    assert ssd_costs.scan_calls(run, "ssd_scan_fwd") == (
        2, pytest.approx(0.006))
    for kernel, calls, spent in (("ssd_scan_fwd", 2, 0.006),
                                 ("ssd_scan_bwd", 1, 0.008)):
        flops, moved = ssd_costs.scan_call(kernel, **CELL_SCAN)
        least = max(flops / 197e12, moved / 819e9)
        assert _reader(kernel.replace("ssd_", "") + "_roofline").read(
            run) == pytest.approx(100 * calls * least / spent)
    # the same run kept under a cell whose configuration names no Mamba
    # heads: the cell's files are found, and describe no scan
    elsewhere = _traced_run(tmp_path / "elsewhere", "laguna-xs2.seq8k")
    found = kernel_costs.cell_files(elsewhere.record)
    assert found["arch"]["name"] == "laguna-xs2"
    assert "mamba_num_heads" not in found["arch"]
    assert _reader("scan_fwd_roofline").read(elsewhere) is None


#: a call of each flash kernel in the one attention block: 4 query heads over
#: 1 key-value head of 128, no window, no positions
ATTENTION = dict(batch=2, tokens=8192, heads=4, kv_heads=1, qk_dim=128,
                 v_dim=128, window=0)
#: an expert's two products in the latent space: plain relu2, no gate
PRODUCTS = (dict(contraction=1024, columns=2688),
            dict(contraction=2688, columns=1024))


def _flash_share(kernel, spent):
    flops, moved = kernel_costs.flash_call(kernel, **ATTENTION)
    return 100 * max(flops / 197e12, moved / 819e9) / spent


def _grouped_share(rows, held, products, calls, spent):
    per_call = sum(max(f / 197e12, b / 819e9) for f, b in (
        kernel_costs.grouped_call(rows=rows, held=held, **product)
        for product in products)) / 2
    return 100 * calls * per_call / spent


#: what each shared reader reads of ``_traced_run``: the recorded-run tests
#: of ``device_latent_moe_ms`` and the two ``latent_moe_*`` ratios went on
#: under the names that took their place (the first three)
SHARED_READINGS = {
    # moe_latent, moe_route, the two grouped products and their layout call
    "device_moe_ms": (0.008 + 0.002 + 0.003 + 0.0035 + 0.0005) / 5 * 1e3,
    "expert_load_max_over_mean": 2.5,
    "moe_rows_walked_over_landed": 11264 / 5760,
    # the block's three kernels and the product beside them
    "device_attention_ms": (0.0075 + 0.011 + 0.014 + 0.0025) / 5 * 1e3,
    "flash_fwd_roofline": _flash_share("flash_fwd", 0.0015),
    "flash_dq": _flash_share("flash_dq", 0.0022),
    "flash_dkv": _flash_share("flash_dkv", 0.0028),
    # two calls at 5,760 real rows a block of five, the layout call's time
    # counted with them: 8 held experts, latent 1024, width 2688
    "grouped_matmul_roofline": _grouped_share(
        5760.0, 8, PRODUCTS, 2, (0.003 + 0.0035 + 0.0005) / 5),
}


@pytest.mark.parametrize("name", SHARED_METRICS + PAIR)
def test_a_shared_reader_reads_this_cell_by_its_own_files(name, tmp_path):
    assert read_of(name)(_traced_run(tmp_path)) == pytest.approx(
        SHARED_READINGS[name], rel=1e-12)


def test_the_attention_block_and_the_experts_are_read_by_the_files_keys():
    """What ``kernel_costs`` makes of ``nemotron3-super.json``: the pattern
    string's one ``*`` of eleven is an ``attention_full`` of the cell's 4
    query heads over 1 key-value head, its five ``E`` hold 8 plain experts
    of 2688 in a latent space of 1024."""
    arch = harness.load_json(os.path.join(
        harness.HERE, "configs", "nemotron3-super.json"))
    bodies = kernel_costs.layer_bodies(arch)
    assert len(bodies) == 11
    assert [scope for scope, _, _ in bodies if scope] == ["attention_full"]
    assert kernel_costs.attention_shapes(arch) == {"attention_full": {
        k: v for k, v in ATTENTION.items() if k not in ("batch", "tokens")}}
    assert kernel_costs.routed_experts(arch) == {
        "bodies": 5, "held": 8, "products": list(PRODUCTS)}
    # a forward call is 137 GFLOP, 0.70 ms at the peak
    flops, _ = kernel_costs.flash_call("flash_fwd", **ATTENTION)
    assert flops == 2.0 * 2 * 4 * (8192 * 8193 // 2) * 2 * 128
    assert 0.69e-3 < flops / 197e12 < 0.71e-3


def test_a_program_without_a_latent_space_reports_its_five_scopes(tmp_path):
    """``laguna-xs2``'s scopes are five of the six: ``device_moe_ms`` reads
    there what it read before ``moe_latent`` joined its tuple."""
    run = _traced_run(tmp_path)
    path = os.path.join(os.path.dirname(run.record["trace_dir"]),
                        "telemetry", "programs-p0.jsonl")
    with open(path) as f:
        text = f.read()
    with open(path, "w") as f:
        f.write(text.replace("moe_latent", "moe_shared"))
    assert _reader("device_moe_ms").read(run) == pytest.approx(
        SHARED_READINGS["device_moe_ms"])
