"""A mix that states ``fixed_work`` (``chipbench/datagen.py::work_seed``):
the training set and the weights are drawn from the mix's own seed in every
run, and ``--seed`` draws the order the set is fed in and the step's noise.
Through the shipped harness, adapter and ``sdar-30b-a3b`` reference at the
tiny block-diffusion cell's size: two seeds start from the same weights,
feed other batches and are both ``correct``; a reference that is not told the
run's seed draws another noise than the step and reads ``correct`` false."""

import json
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import chipbench_tiny_sdar as tiny_cell  # noqa: E402
import sdar_tiny as tiny  # noqa: E402
from chipbench import datagen  # noqa: E402
from chipbench import run as harness  # noqa: E402

FIXED = 2**31 + 977
_DECIDE = harness.decide_correct
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


def test_the_seed_a_set_and_its_weights_are_drawn_from():
    assert datagen.work_seed({}, 2**32 + 5) == 5
    assert datagen.work_seed({"fixed_work": {"seed": 2**32 + 9}}, 5) == 9
    with pytest.raises(ValueError):
        datagen.work_seed({"fixed_work": {"seed": -1}}, 5)


def test_the_shipped_mixes_that_fix_their_work():
    """The two cells whose rate followed their seeded routers (PERF.md
    section 2) state a seed of their own; the others draw from ``--seed``."""
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    fixed = set()
    for cell in bench["workloads"]:
        traffic = harness.load_json(os.path.join(
            REPO, "chipbench", "traffic", cell["traffic"] + ".json"))
        if "fixed_work" in traffic:
            assert set(traffic["fixed_work"]) == {"seed", "why"}
            assert 0 <= traffic["fixed_work"]["seed"] < 2**32
            fixed.add(cell["name"])
    assert fixed == {"nemotron3-super.seq8k-v16384",
                     "sdar-30b-a3b.seq4k-v18992"}


def _run(tmp_path, seed, monkeypatch):
    """The tiny cell with ``fixed_work`` in its mix; returns the result and
    what the probe read of the first three steps."""
    tiny.register()
    bench, roots = tiny_cell.append(
        str(tmp_path), os.path.join(REPO, "BENCHMARK.json"))
    path = os.path.join(str(tmp_path), "traffic", "t24.json")
    traffic = harness.load_json(path)
    traffic["fixed_work"] = {"seed": FIXED, "why": "a test"}
    with open(path, "w") as f:
        json.dump(traffic, f)
    seen = {}

    def keep(loaded, record):
        seen.update(record["check"])
        return _DECIDE(loaded, record)

    monkeypatch.setattr(harness, "decide_correct", keep)
    result = harness.run_cell(tiny_cell.CELL, seed, tiny_cell.SECONDS, False,
                              bench_path=bench, roots=roots,
                              device_check=False)
    return result, seen


def test_two_seeds_share_weights_and_set_and_feed_them_in_another_order(
        tmp_path, monkeypatch):
    (first, a), (second, b) = (
        _run(tmp_path / str(seed), seed, monkeypatch)
        for seed in (2**31 + 51, 2**31 + 52))
    assert first["correct"] is True, first["compared"]
    assert second["correct"] is True, second["compared"]
    assert set(a["params0"]) == set(b["params0"])
    for leaf in a["params0"]:
        np.testing.assert_array_equal(a["params0"][leaf], b["params0"][leaf])
    rows = [{row.tobytes() for batch in seen["batches"]
             for row in batch["tokens"]} for seen in (a, b)]
    assert rows[0] != rows[1]          # other batches first
    spec = harness.load_json(os.path.join(
        str(tmp_path), str(2**31 + 51), "traffic", "t24.json"))["dataset"]
    from chipbench.datasets import zipf_tokens
    whole = {row.tobytes() for row in zipf_tokens.make(
        spec, datagen.fold_seed(FIXED))[0]}
    assert rows[0] <= whole and rows[1] <= whole   # of the one set
    assert first["compared"]["loss_gap"] != second["compared"]["loss_gap"]


def test_a_reference_not_told_the_runs_seed_draws_another_noise(
        tmp_path, monkeypatch):
    monkeypatch.setattr(datagen, "tell_run_seed", lambda *_: None)
    result, _ = _run(tmp_path, 2**31 + 53, monkeypatch)
    assert result["correct"] is False
    assert "loss_gap" in tiny_cell.failed(result)
