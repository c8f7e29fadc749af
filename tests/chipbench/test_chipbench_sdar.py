"""The ``sdar-30b-a3b`` configuration's own files (its plain reference, the
shipped ``zipf_tokens`` generator and ``trainer`` adapter, so the product's
``Trainer.run``) through the shipped harness at a size a CPU holds, on a
copy of the shipped BENCHMARK.json with the tiny cell appended
(``chipbench_tiny_sdar.py``): ``correct`` true, and false with a piece of
the model or of the objective left out of the program; the entries this
configuration has in the shipped file; its four per-layer readers, and the
five it shares with the other decoder cells since PR 44, on a synthetic
trace; and ``chipbench/block_mask_costs.py``'s pairs against a brute-force
count."""

import csv
import json
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import chipbench_tiny_sdar as tiny_cell  # noqa: E402
import sdar_tiny as tiny  # noqa: E402
from chipbench import block_mask_costs, kernel_costs  # noqa: E402
from chipbench import run as harness  # noqa: E402
from test_chipbench_contract import appended_only  # noqa: E402
from test_chipbench_flash_bwd import STEP, _traced  # noqa: E402

CELL = "sdar-30b-a3b.seq4k-v18992"
READERS = {"device_block_attention_ms": ("ms", "lower", "models"),
           "device_block_noise_ms": ("ms", "lower", "step builders"),
           "block_flash_fwd_roofline": ("%", "higher", "kernels"),
           "block_flash_bwd_roofline": ("%", "higher", "kernels")}
#: the readers of the mechanisms it runs as the other decoder cells do (PR
#: 44): the routed experts, their grouped products, and how often the flash
#: forward kernel runs a backward pass. Not the band's three
#: (``device_attention_ms``, ``flash_fwd_roofline``, ``flash_bwd_roofline``):
#: its mask is counted under its own four names, and one call is read once
SHARED = ("device_moe_ms", "expert_load_max_over_mean",
          "moe_rows_walked_over_landed", "grouped_matmul_roofline",
          "flash_fwd_calls_per_bwd_call")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
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


# -- the tiny cell through the harness ----------------------------------------

def test_the_new_configuration_is_correct_through_trainer_run(tmp_path,
                                                              capsys):
    """Three AdamW steps of the tiny decoder on the block-diffusion loss
    through ``Trainer.run``, its noise drawn in the step, against the
    float32 reference that draws the same noise by its own lines from the
    seed ``init_params`` was given: losses, first gradient, update."""
    tiny.register()
    result = tiny_cell.run(tmp_path)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 20
    assert set(result["metrics"]) == {"images_per_s_per_chip", "setup_s"}
    assert set(result["compared"]) == {
        "repeated_rows", "loss_gap", "grad_gap", "update_gap", "grad_diff",
        "out_grad_diff"}
    assert "chipbench: tokens_per_s_per_chip=" in capsys.readouterr().out


#: the accepted limit ``loss_gap`` may not fall under, and what the two
#: left-out faults of this file that touch the loss (the weight 1 / t, the
#: clean half) read of it on the tiny cell: the smaller is the clean half's
LOSS_GAP_FLOOR, TINY_FAULTS = 0.00045, ("weight", "clean_half")


def _readings():
    folder = os.path.join(REPO, "chipbench", "limits")
    with open(os.path.join(folder, CELL + ".readings.csv")) as f:
        rows = list(csv.DictReader(f))
    by_read = {}
    for row in rows:
        by_read.setdefault(row["read"], []).append(row)
    return by_read, harness.load_json(os.path.join(folder, CELL + ".json"))


def _break(fault, monkeypatch):
    import flax.linen as nn

    from tpu_ddp.models import decoder
    from tpu_ddp.train import tasks

    changes = {}
    attend, noise = decoder.reference_attention, tasks.block_noise
    if fault == "mask":          # causal over the 2L positions in its place
        monkeypatch.setattr(
            decoder, "reference_attention",
            lambda q, k, v, **how: attend(q, k, v, causal=True))
    elif fault == "weight":      # every masked position weighs 1, not 1 / t
        def unweighted(settings, key, batch):
            out = noise(settings, key, batch)
            return dict(out, block_t=jnp.ones_like(out["block_t"]))
        monkeypatch.setattr(tasks, "block_noise", unweighted)
    elif fault == "clean_half":  # the noised copy stands where the clean was
        def twice_noisy(settings, key, batch):
            out = noise(settings, key, batch)
            half = out["tokens"].shape[1] // 2
            return dict(out, tokens=jnp.concatenate(
                [out["tokens"][:, half:]] * 2, axis=1))
        monkeypatch.setattr(tasks, "block_noise", twice_noisy)
    elif fault == "qk_norm":     # the two scales are there and do nothing
        norm = nn.RMSNorm

        class Passing(norm):
            def __call__(self, x, *args, **kwargs):
                y = norm.__call__(self, x, *args, **kwargs)
                return x if self.name in ("q_norm", "k_norm") else y

        Passing.__name__ = norm.__name__
        monkeypatch.setattr(nn, "RMSNorm", Passing)
    elif fault == "softmax":     # a sigmoid in its place
        changes["router_score"] = "sigmoid"
    else:
        raise ValueError(fault)
    tiny.register(**changes)


@pytest.mark.parametrize("fault", ["mask", "weight", "clean_half", "qk_norm",
                                   "softmax"])
def test_a_model_with_a_piece_left_out_is_not_correct(tmp_path, monkeypatch,
                                                      fault):
    _break(fault, monkeypatch)
    try:
        result = tiny_cell.run(tmp_path)
    finally:
        tiny.register()
    assert result["correct"] is False
    assert tiny_cell.failed(result), result["compared"]
    if fault in TINY_FAULTS:
        # the shipped ``loss_gap``'s ``fault_min`` is the smaller of what
        # these two read on this tiny cell, and says so: a hundred times the
        # shipped limit and more
        _, file = _readings()
        entry = file["readings"]["loss_gap"]
        read = result["compared"]["loss_gap"]["value"]
        assert "tiny cell" in entry["fault"]
        assert read >= 0.999 * entry["fault_min"] > 100 * file["limits"][
            "loss_gap"]
        if fault == "clean_half":
            assert read == pytest.approx(entry["fault_min"], rel=1e-3)


def test_follow_asks_for_the_seed_it_was_not_given():
    ref = tiny.reference()  # a fresh module: init_params was never called
    with pytest.raises(RuntimeError, match="init_params"):
        ref.follow(tiny.arch(), {"batches": [], "params0": {}}, shards=1,
                   optimizer={"name": "adamw", "lr": 1e-3,
                              "weight_decay": 0.1},
                   precision="float32_highest")


# -- what was appended to the shipped file ------------------------------------

def test_the_shipped_file_has_its_configuration_cell_and_readers(bench):
    """Found by name, on the shipped file and on a copy with entries after
    the end of every list: cut where this configuration's entries start, the
    file is one of which the whole is ``appended_only``; its four own
    readers list its cell alone, the five shared ones list it after the
    three decoder cells, and nothing else names it."""
    names = lambda group: [e["name"] for e in bench[group]]  # noqa: E731
    at = {"configs": names("configs").index("sdar-30b-a3b"),
          "workloads": names("workloads").index(CELL),
          "per_layer": names("per_layer").index("device_block_attention_ms")}
    # as a program PR appended it (PR 41); the five lists one name longer
    # are a benchmark PR's edit (PR 44), which ``appended_only`` calls one
    whole = dict(bench, per_layer=[
        dict(m, workloads=[w for w in m["workloads"] if w != CELL])
        if m["name"] in SHARED else m for m in bench["per_layer"]])
    before = dict(whole, **{group: whole[group][:i]
                            for group, i in at.items()})
    assert appended_only(before, whole)
    assert not appended_only(before, bench)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better, layer) in READERS.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer,
            "moves": "images_per_s_per_chip", "workloads": [CELL]}
    for name in SHARED:
        assert by_name[name]["workloads"][-1] == CELL
        assert len(by_name[name]["workloads"]) == 4
    for name, metric in by_name.items():
        assert (CELL in metric.get("workloads", [])) == (
            name in READERS or name in SHARED), name
    assert {"step_mfu", "device_step_ms", "device_starved_ms"} <= {
        m["name"] for m in bench["per_layer"] if "workloads" not in m}
    entry = bench["configs"][at["configs"]]
    assert entry["reduced"] == ["layers_here", "num_experts", "vocab_size"]
    cell = bench["workloads"][at["workloads"]]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "~1,024 pairs" in cell["why"] and "share" in cell["why"]
    # the rate followed the seed's router until the mix fixed its work
    assert "the mix's seed" in cell["why"]


def test_the_configuration_file_holds_every_published_width():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "sdar-30b-a3b.json")) as f:
        arch = json.load(f)
    published = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128,
        hidden_act="silu", hidden_size=2048, intermediate_size=6144,
        max_position_embeddings=32768, max_window_layers=48,
        mlp_only_layers=[], model_type="sdar_moe",
        moe_intermediate_size=768, norm_topk_prob=True,
        num_attention_heads=32, num_experts_per_tok=8, num_hidden_layers=48,
        num_key_value_heads=4, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False)
    assert {k: arch[k] for k in published} == published
    assert arch["published"] == dict(
        num_hidden_layers=48, num_experts=128, vocab_size=151936)
    assert arch["reduced"] == ["layers_here", "num_experts", "vocab_size"]
    assert (arch["layers_here"], arch["num_experts"],
            arch["vocab_size"]) == (6, 16, 18992)
    assert arch["vocab_size"] * 8 == arch["published"]["vocab_size"]
    assert arch["mask_token_id"] == arch["vocab_size"] - 1
    for key in ("deployment", "reduced_why", "assumed", "parameters_here"):
        assert arch[key]
    with open(os.path.join(REPO, "chipbench", "traffic",
                           "seq4k-v18992.json")) as f:
        mix = json.load(f)
    # the data never holds the mask token
    assert mix["dataset"]["vocab_size"] == arch["mask_token_id"]
    assert mix["dataset"]["seq_len"] % arch["block_length"] == 0


# -- the limits, by the written rule over every reading -----------------------

def test_the_limits_are_the_written_rule_over_every_reading():
    """``limits/<cell>.readings.csv`` holds every reading of the cell's five
    numbers by seed: the program's (``sound``), the float8 control's, the
    reference's at the stated bfloat16. The limits file's three keys a
    number are what is in it, and each limit is what the rule of
    ``chipbench/README.md`` makes of them: the geometric mean of the largest
    sound reading and the smallest control where they separate, of the
    largest sound reading and 1 for the two gaps of norms, three times the
    largest sound reading and no lower than the accepted one for the loss.
    Every sound reading is at least two times under its limit."""
    by_read, file = _readings()
    sound, control = by_read["sound"], by_read["control_float8"]
    seeds = [row["seed"] for row in sound]
    assert len(set(seeds)) == len(seeds) >= 23 and "2147485021" in seeds
    assert len(control) >= 4
    limits, entries = file["limits"], file["readings"]
    for name in limits:
        entry = entries[name]
        assert entry["seeds"] == len(sound)
        assert entry["sound_max"] == max(float(r[name]) for r in sound)
        assert 2 * entry["sound_max"] <= limits[name], name
    for name in ("out_grad_diff", "grad_diff"):
        entry = entries[name]
        assert entry["fault_min"] == min(float(r[name]) for r in control)
        assert entry["fault_min"] >= 3 * entry["sound_max"]
        assert limits[name] == pytest.approx(math.sqrt(
            entry["sound_max"] * entry["fault_min"]), rel=5e-3)
        # every control fails it
        assert all(float(r[name]) > limits[name] for r in control)
    for name in ("grad_gap", "update_gap"):
        assert entries[name]["fault_min"] == 1.0
        assert limits[name] == pytest.approx(
            math.sqrt(entries[name]["sound_max"]), rel=2e-2)
    assert limits["loss_gap"] == pytest.approx(max(
        LOSS_GAP_FLOOR, 3 * entries["loss_gap"]["sound_max"]), rel=2e-2)
    assert limits["loss_gap"] >= LOSS_GAP_FLOOR
    # what the stated precision alone makes of the seed that read 8%
    stated = {r["seed"]: r for r in by_read["control_bfloat16"]}
    program = {r["seed"]: r for r in sound}
    assert stated["2147485021"]["grad_gap_at"] == "layer_0.moe.router" == (
        program["2147485021"]["grad_gap_at"])
    assert 0.05 < float(stated["2147485021"]["grad_gap"]) < 0.1
    assert 0.05 < float(program["2147485021"]["grad_gap"]) < 0.1


# -- the costs, from shapes ---------------------------------------------------

@pytest.mark.parametrize("length,block", [(24, 4), (64, 16), (12, 1),
                                          (48, 12)])
def test_the_pairs_are_a_brute_force_count_of_the_mask(length, block):
    i = np.arange(2 * length)
    noisy, b = i >= length, i % length // block
    rn, cn, rb, cb = noisy[:, None], noisy[None, :], b[:, None], b[None, :]
    visible = ((~rn & ~cn & (cb <= rb)) | (rn & ~cn & (cb < rb))
               | (rn & cn & (cb == rb)))
    assert block_mask_costs.visible_pairs(length, block) == int(
        visible.sum())


def test_a_call_at_the_cells_shapes():
    """80 of 256 tiles' worth of pairs and a little more: 16.8 M pairs a
    head where the causal band of 8,192 has 33.6 M; the band's products and
    arrays at 8,192 positions a row; bound by the matrix unit."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           "sdar-30b-a3b.json")) as f:
        shape = block_mask_costs.shapes(json.load(f))
    assert shape == dict(block=4, heads=32, kv_heads=4, head_dim=128)
    pairs = block_mask_costs.visible_pairs(4096, 4)
    assert pairs == 16 * 1024 * 1025 == 16_793_600
    assert 0.5 < pairs / kernel_costs.visible_pairs(8192, 0) < 0.501
    rows = 2 * 8192
    fwd, fwd_moved = block_mask_costs.flash_call(
        "flash_fwd", batch=2, length=4096, **shape)
    assert fwd == 2.0 * (2 * 32 * pairs) * (128 + 128)
    assert fwd_moved == (2 * rows * 32 * 2 * 128 + 2 * rows * 4 * 2 * 128
                         + 4 * rows * 32)
    bwd, bwd_moved = block_mask_costs.flash_call(
        "flash_bwd", batch=2, length=4096, **shape)
    assert bwd == 2.0 * (2 * 32 * pairs) * 5 * 128
    assert bwd_moved == (2 * rows * 32 * 3 * 128 + 2 * rows * 4 * 4 * 128
                         + 4 * 2 * rows * 32)
    # the band's own count at the same widths, pairs apart
    band, band_moved = kernel_costs.flash_call(
        "flash_fwd", batch=2, tokens=8192, heads=32, kv_heads=4, qk_dim=128,
        v_dim=128, window=0)
    assert fwd_moved == band_moved
    assert fwd / band == pairs / kernel_costs.visible_pairs(8192, 0)
    for flops, moved in ((fwd, fwd_moved), (bwd, bwd_moved)):
        assert flops / 197e12 > moved / 819e9
    assert 1e3 * kernel_costs.least_seconds(fwd, fwd_moved, PEAKS) == (
        pytest.approx(2.793, rel=1e-3))
    assert block_mask_costs.shapes({"head_dim": 128}) is None


# -- the readers on a synthetic trace -----------------------------------------

def _rows(module="attention_block"):
    attn = (f"transpose(jvp(SparseDecoder))/layer_0/attn/tpu_ddp.module."
            f"{module}/")
    return {
        "flash_fwd.1": (STEP + attn.replace("transpose(jvp(", "jvp(", 1)
                        .replace("))", ")", 1)
                        + "tpu_ddp.kernel.flash_fwd/pallas_call", "forward",
                        module),
        "flash_fwd.2": (STEP + attn + "tpu_ddp.kernel.flash_fwd/pallas_call",
                        "backward", module),
        "flash_bwd.1": (STEP + attn + "tpu_ddp.kernel.flash_bwd/pallas_call",
                        "backward", module),
        "fusion.7": (STEP + attn + "mul", "backward", module),
        "fusion.8": ("jit(shard_step)/tpu_ddp.input/tpu_ddp.module."
                     "block_noise/concatenate", "input", "block_noise"),
        "fusion.9": ("jit(shard_step)/tpu_ddp.input/tpu_ddp.module."
                     "block_noise/threefry2x32", "input", "block_noise"),
    }


SECONDS = {"flash_fwd.1": 5 * 0.006, "flash_fwd.2": 5 * 0.006,
           "flash_bwd.1": 5 * 0.014, "fusion.7": 5 * 0.002,
           "fusion.8": 5 * 0.0004, "fusion.9": 5 * 0.0006}


def _reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "chipbench_metric_" + name)


def test_the_four_readers_read_their_scopes_and_kernels(tmp_path, capsys):
    run = _traced(tmp_path, CELL, _rows(), SECONDS)
    read = {name: _reader(name).read(run) for name in READERS}
    assert read["device_block_attention_ms"] == pytest.approx(28.0)
    assert read["device_block_noise_ms"] == pytest.approx(1.0)
    shape = dict(block=4, heads=32, kv_heads=4, head_dim=128)
    for kernel, calls, spent in (("flash_fwd", 2, 0.012),
                                 ("flash_bwd", 1, 0.014)):
        least = kernel_costs.least_seconds(*block_mask_costs.flash_call(
            kernel, batch=2, length=4096, **shape), PEAKS)
        got = read[f"block_{kernel}_roofline"]
        assert got == pytest.approx(100 * calls * least / spent)
        assert 0 < got < 100
    said = capsys.readouterr().out
    assert "kernel flash_fwd in attention_block: 2 calls a step" in said
    assert "kernel flash_bwd in attention_block: 1 calls a step" in said
    # the whole line, through the harness: the four, and of the shared five
    # the one that finds something in a trace of attention alone
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    listed = [m for m in bench["per_layer"] if "workloads" in m]
    out = harness.per_layer(dict(bench, per_layer=listed), CELL,
                            [harness.HERE], run.record, run.trace)
    assert sorted(out) == sorted(
        list(READERS) + ["flash_fwd_calls_per_bwd_call"])
    assert out["flash_fwd_calls_per_bwd_call"]["value"] == 2.0


#: a routed layer's rows beside ``_rows``' attention: three module scopes,
#: the compiler's grouped product twice and the call that lays its groups out
ROUTED_SECONDS = {"fusion.20": 5 * 0.003, "fusion.21": 5 * 0.005,
                  "fusion.22": 5 * 0.004, "ragged-dot-none.3": 5 * 0.0010,
                  "ragged-dot-none.4": 5 * 0.0012,
                  "ragged-dot-metadata.5": 5 * 0.0001}
GAUGES = {"model/expert_load_max": 6200.0, "model/expert_load_mean": 1024.0,
          "model/expert_load_sum": 6 * 16384.0,
          "model/expert_rows_walked_sum": 6 * 32768.0,
          "model/expert_rows_walked_max": 32768.0}


def _routed_run(tmp_path, kept=False):
    moe = STEP + "jvp(SparseDecoder)/layer_0/moe/tpu_ddp.module."
    rows = dict(_rows(), **{
        "fusion.20": (moe + "moe_route/dot_general", "forward", "moe_route"),
        "fusion.21": (moe + "moe_dispatch/gather", "forward",
                      "moe_dispatch"),
        "fusion.22": (moe + "moe_combine/scatter-add", "forward",
                      "moe_combine"),
        "ragged-dot-none.3": ("ragged-dot-none", "forward", "moe_experts"),
        "ragged-dot-none.4": ("ragged-dot-none", "backward", "moe_experts"),
        "ragged-dot-metadata.5": ("ragged-dot-metadata", "forward",
                                  "moe_dispatch")})
    if kept:  # the layer keeps its attention's output: no second forward call
        del rows["flash_fwd.2"]
    run = _traced(tmp_path, CELL, rows, dict(
        {k: v for k, v in SECONDS.items() if k in rows}, **ROUTED_SECONDS))
    with open(os.path.join(os.path.dirname(run.record["trace_dir"]),
                           "telemetry", "trace-p0.jsonl"), "w") as f:
        f.write(json.dumps({"type": "counters", "attrs": {
            "tables": {}, "gauges": GAUGES}}) + "\n")
    return run


def test_the_five_shared_readers_read_this_cell_by_its_own_files(tmp_path):
    """The routed experts as ``sdar-30b-a3b.json`` states them: every one
    of the six layers sparse (``decoder_sparse_step`` 1, no dense layer
    named), 16 gated experts of 768 held; the rows that landed a body from
    the counters; the flash forward kernel once a backward call where the
    layer keeps its output."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           "sdar-30b-a3b.json")) as f:
        arch = json.load(f)
    assert kernel_costs.layer_bodies(arch) == [(None, 0, True)] * 6
    assert kernel_costs.routed_experts(arch) == {
        "bodies": 6, "held": 16, "products": [
            dict(contraction=2048, columns=2 * 768),
            dict(contraction=768, columns=2048)]}
    # the mask is not a band: no shape for the band's readers to count by
    assert kernel_costs.attention_shapes(arch) == {}
    run = _routed_run(tmp_path, kept=True)
    read = {name: _reader(name).read(run) for name in SHARED}
    assert read["device_moe_ms"] == pytest.approx(
        1e3 * sum(ROUTED_SECONDS.values()) / 5)
    assert read["expert_load_max_over_mean"] == 6200.0 / 1024.0
    assert read["moe_rows_walked_over_landed"] == 2.0
    assert read["flash_fwd_calls_per_bwd_call"] == 1.0
    per_call = sum(kernel_costs.least_seconds(*kernel_costs.grouped_call(
        rows=16384.0, held=16, **product), PEAKS) for product in (
        dict(contraction=2048, columns=1536),
        dict(contraction=768, columns=2048))) / 2
    assert read["grouped_matmul_roofline"] == pytest.approx(
        100 * 2 * per_call / (0.0010 + 0.0012 + 0.0001))
    assert 0 < read["grouped_matmul_roofline"] < 100
    # the whole line: the cell's own four and the five, nothing else listed
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    listed = [m for m in bench["per_layer"] if "workloads" in m]
    out = harness.per_layer(dict(bench, per_layer=listed), CELL,
                            [harness.HERE], run.record, run.trace)
    assert sorted(out) == sorted(list(READERS) + list(SHARED))
    # a layer recomputed whole runs the forward kernel in both passes
    assert _reader("flash_fwd_calls_per_bwd_call").read(
        _routed_run(tmp_path / "recomputed")) == 2.0
    # and the band's readers find no shape of theirs under this mask
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "device_attention_ms"):
        assert _reader(name).read(run) is None


def test_a_program_without_the_scopes_reads_nothing(tmp_path):
    """The parent's program has neither scope; a band cell's kernels sit
    under another; an untraced run has no trace: nothing raises, the line
    leaves the metric out."""
    untraced = types.SimpleNamespace(record={"trace_dir": None}, trace=None)
    band = _traced(tmp_path, CELL, _rows("attention_full"), SECONDS)
    other_cell = _traced(tmp_path, "laguna-xs2.seq8k", _rows(), SECONDS)
    for name in READERS:
        reader = _reader(name)
        assert reader.read(untraced) is None
        if name != "device_block_noise_ms":
            assert reader.read(band) is None
    for name in ("block_flash_fwd_roofline", "block_flash_bwd_roofline"):
        # a cell whose files name no block length: never a guess
        assert _reader(name).read(other_cell) is None
        run = _traced(tmp_path, CELL, _rows(), SECONDS)
        run.record["peak_flops_per_s"] = 1.0  # a chip not in the table
        assert _reader(name).read(run) is None
