"""The harness end to end at a size a CPU test can hold: a throw-away cell,
configuration, traffic mix and per-layer metric that live in a temporary
directory run through the shipped harness with no shipped file touched; the
comparison that decides ``correct`` passes the sound program, fails the
program computed one notch lower and fails a timed path broken underneath;
the command itself refuses to measure without a TPU."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny  # noqa: E402
from chipbench import control, datagen  # noqa: E402
from chipbench import run as harness  # noqa: E402

CELL = chipbench_tiny.CELL
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


def run_tiny(tmp_path, seed=5, **kwargs):
    bench, roots = chipbench_tiny.write(str(tmp_path), **kwargs)
    # 1.0 s: the harness wants 20 dispatch intervals, so three of these
    # 8-step epochs, so 16 steps inside ``--seconds``: a CPU step of up to
    # 62.5 ms (0.5 s held up to 31.25 ms; test_chipbench_laguna.py::SECONDS)
    return harness.run_cell(CELL, seed, 1.0, False, bench_path=bench,
                            roots=roots, device_check=False)


def test_a_cell_made_of_new_files_only_runs_and_is_correct(tmp_path):
    result = run_tiny(tmp_path, seed=2**31 + 11)  # the driver's seeds are large
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 20
    assert set(result["metrics"]) == {
        "images_per_s_per_chip", "step_ms_p95", "setup_s"}
    for value in result["metrics"].values():
        assert value["value"] > 0 and value["unit"]
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    json.dumps(result)


def test_the_cell_runs_from_a_copy_of_the_shipped_benchmark(tmp_path):
    """What a later PR does: its entries at the end of the shipped lists,
    its files beside the shipped ones, and the shipped metrics' own
    ``workloads`` lists decide what the new cell reports."""
    bench, roots = chipbench_tiny.append(
        str(tmp_path), os.path.join(REPO, "BENCHMARK.json"))
    result = harness.run_cell(CELL, 2**31 + 12, 1.0, False, bench_path=bench,
                              roots=roots, device_check=False)
    assert result["correct"] is True and result["attempted"] > 20
    # ``step_ms_p95`` lists the one shipped cell that reports it
    assert set(result["metrics"]) == {"images_per_s_per_chip", "setup_s"}


def test_four_shards_agree_with_the_per_shard_reference(tmp_path):
    result = run_tiny(tmp_path, seed=9, chips=4, size=256)
    assert result["correct"] is True
    assert result["device"]["count"] == 4


def test_the_program_one_notch_lower_is_not_correct(tmp_path, capsys):
    result = run_tiny(tmp_path, compute_dtype="bfloat16")
    assert result["correct"] is False
    assert "grad_gap" in capsys.readouterr().err


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    import tpu_ddp.train.trainer as trainer_module

    real = trainer_module.make_train_step

    def broken(*args, **kwargs):
        step = real(*args, **dict(kwargs, donate=False))

        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        def half(state, batch):
            n = batch["mask"].shape[0] // 2
            mask = batch["mask"].at[n:].set(False)
            return step(state, dict(batch, mask=mask))

        return unchanged if fault == "state_unchanged" else half

    monkeypatch.setattr(trainer_module, "make_train_step", broken)
    result = run_tiny(tmp_path)
    assert result["correct"] is False


def test_the_control_reads_far_above_the_sound_program(tmp_path, capsys):
    bench, roots = chipbench_tiny.write(str(tmp_path))
    control.main(["--workload", CELL, "--seeds", "1,2,3"], roots=roots,
                 bench_path=bench, device_check=False)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("control summary:")][-1]
    summary = json.loads(line.split(":", 1)[1])
    assert summary["control_precision"] == "bfloat16"
    assert summary["grad_gap.control_min"] > 3 * summary["grad_gap.sound_max"]
    assert summary["out_grad_diff.control_min"] > 3 * summary[
        "out_grad_diff.sound_max"]


def test_the_control_alone_needs_no_program(tmp_path, capsys, monkeypatch):
    bench, roots = chipbench_tiny.write(str(tmp_path), chips=4, size=256)
    loaded = harness.load_cell(harness.load_json(bench), CELL,
                               roots + [harness.HERE])
    monkeypatch.setattr(loaded["adapter"], "run", None)  # never called
    control.main(["--workload", CELL, "--seeds", "4,5", "--read", "control"],
                 roots=roots, bench_path=bench, device_check=False)
    rows = [json.loads(ln.split(":", 1)[1])
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("control:")]
    assert [r["seed"] for r in rows] == [4, 5]
    for row in rows:
        assert "sound" not in row
        assert row["control"]["out_grad_diff"] > 1e-3
        assert set(row["control_grad_diff_by_group"]) == {
            "conv1", "resblock", "fc1", "fc2"}


def test_a_new_layer_metric_is_read_from_its_own_file(tmp_path):
    bench_path, roots = chipbench_tiny.write(str(tmp_path))
    bench = harness.load_json(bench_path)
    record = {"steps": 7, "host_spans": [("compiled_step", 1.0, 1.014)]}
    out = harness.per_layer(bench, CELL, roots + [harness.HERE], record,
                            None)
    assert out["window_steps"] == {"value": 7, "unit": "steps"}
    assert abs(out["dispatch_ms"]["value"] - 2.0) < 1e-9
    # a reader that finds nothing to read returns nothing: left out
    out = harness.per_layer(bench, CELL, roots + [harness.HERE],
                            {"steps": 7}, None)
    assert set(out) == {"window_steps"}


def test_a_share_above_the_ceiling_is_refused(tmp_path):
    bench_path, roots = chipbench_tiny.write(str(tmp_path))
    bench = harness.load_json(bench_path)
    bench["per_layer"] = [dict(bench["per_layer"][1], unit="%")]
    with pytest.raises(harness.Refused):
        harness.per_layer(bench, CELL, roots, {"steps": 106}, None)


def test_the_command_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chipbench", "run.py"),
         "--workload", "resnet50-cifar.b512", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True, cwd=REPO,
        timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_same_seed_same_data_and_the_sampler_arithmetic():
    spec = {"kind": "class_gaussians", "size": 100, "image_size": 32,
            "channels": 3, "num_classes": 10}
    make = harness.load_module(os.path.join(
        harness.HERE, "datasets", "class_gaussians.py"), "gaussians").make
    a, la = make(spec, 2**31 + 5)
    b, lb = make(spec, 2**31 + 5)
    c, _ = make(spec, 2**31 + 6)
    assert np.array_equal(a, b) and np.array_equal(la, lb)
    assert not np.array_equal(a, c)
    assert a.dtype == np.float32 and a.shape == (100, 32, 32, 3)
    from tpu_ddp.data.loader import ShardedBatchLoader

    for size, shards, batch in ((100, 4, 8), (50000, 1, 512), (50000, 4, 512),
                                (50000, 1, 6250), (64, 1, 8)):
        loader = ShardedBatchLoader(
            np.zeros((size, 1), np.float32), np.zeros(size, np.int32),
            world_size=shards, per_shard_batch=batch)
        masks = [int(m.sum()) for _, m in loader.epoch_index_batches(1)]
        assert datagen.real_examples_per_step(size, shards, batch) == masks


def test_percentile_is_numpys():
    values = list(np.random.default_rng(0).normal(size=101))
    for q in (5, 50, 95):
        assert abs(harness.percentile(values, q)
                   - np.percentile(values, q)) < 1e-12
