"""BENCHMARK.json against the contract, and every name in it against a file."""

import importlib.util
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "chipbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state_size", "proj",
               "width", "filters", "chans", "expansion", "head_dim")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load(path):
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert isinstance(bench["run_seconds"], int)
    assert 10 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    # a full check at the full 24 cells must fit the driver's 43200 s
    runs = 2 + 14 * 24
    assert (runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200) <= 43200


def test_every_name_and_unit_is_legal(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in bench["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for metric in bench["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in e2e
        assert set(metric.get("workloads", [])) <= cells
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    for cell in bench["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["traffic"]) and cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_configuration_states_its_cut(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for entry in bench["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert entry["name"] in used
        assert entry["file"].startswith("chipbench/")
        assert 1 <= len(entry["source"]) <= 200
        with open(os.path.join(REPO, entry["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == entry["reduced"]
        assert config["source"] == entry["source"]
        for key in entry["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(word in key for word in WIDTH_WORDS), key
        for key in ("precision", "train_config", "adapter"):
            assert key in config
        # the sizes are stated once, at the top level where ``reduced``
        # names them
        assert "architecture" not in config
        assert config["train_config"]["compute_dtype"] == config["precision"]


def test_every_cell_is_found_by_name(bench):
    import sys
    sys.path.insert(0, REPO)
    from chipbench import run as harness

    for cell in bench["workloads"]:
        loaded = harness.load_cell(bench, cell["name"], [BENCH])
        assert loaded["traffic"]["chips"] == cell["chips"]
        assert loaded["traffic"]["mesh"]["data"] == cell["chips"]
        assert set(loaded["limits"]["limits"]) == {
            "loss_gap", "grad_gap", "update_gap", "grad_diff",
            "out_grad_diff"}
        for attr in ("init_params", "forward", "param_shapes",
                     "program_names", "OUTPUT_LEAVES"):
            assert hasattr(loaded["reference"], attr)
        assert hasattr(loaded["adapter"], "run")
        # the lower precision is a parameter of the same reference
        from chipbench.reference.common import ONE_NOTCH_LOWER, PRECISIONS
        assert ONE_NOTCH_LOWER[loaded["config"]["precision"]] in PRECISIONS


def test_every_per_layer_metric_has_its_reader(bench):
    for metric in bench["per_layer"]:
        reader = load(os.path.join(
            BENCH, "layer_metrics", metric["name"] + ".py"))
        assert reader.NAME == metric["name"]
        assert reader.UNIT == metric["unit"]
        assert reader.LAYER == metric["layer"]
        assert reader.MOVES == metric["moves"]
        assert reader.SOURCE == metric["source"]
        assert callable(reader.read)


def test_peaks_table_has_the_chip_and_its_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert v5e["source"]


def test_the_harness_imports_nothing_it_should_not():
    banned = ("bench.py", "import bench", "chip_smoke", "metrics.mfu",
              "metrics/mfu", "analysis.roofline", "analysis/roofline")
    for root, _, files in os.walk(BENCH):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(root, name)) as f:
                text = f.read()
            for word in banned:
                assert word not in text, (name, word)
            # only the adapter touches the program
            if os.path.basename(root) != "adapters":
                assert "from tpu_ddp" not in text, name
                assert "import tpu_ddp" not in text, name
