"""BENCHMARK.json against the contract, and every name in it against a file:
the shipped one, and a copy to which a configuration, a cell and two
per-layer metrics were appended the way a PR that changes the program may
append them (``chipbench_tiny.append``): the ``case`` / ``bench`` fixtures of
``conftest.py``, which every test that reads the shipped file's lists
takes."""

import copy
import glob
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny  # noqa: E402
from chipbench import run as harness  # noqa: E402

BENCH = os.path.join(REPO, "chipbench")
SHIPPED = os.path.join(REPO, "BENCHMARK.json")
LISTS = ("configs", "workloads", "end_to_end", "per_layer")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state_size", "proj",
               "width", "filters", "chans", "expansion", "head_dim")


def appended_only(shipped: dict, proposed: dict) -> bool:
    """Is ``proposed`` what a PR that changes the program may make of the
    ``shipped`` BENCHMARK.json: every shipped entry where it was and as it
    was (its ``workloads`` list too), new entries after the last shipped one
    of a list, nothing else changed? The driver asks the same question and
    calls anything else an edit of the benchmark, which only a PR of kind
    ``benchmark`` may make."""
    if set(proposed) != set(shipped):
        return False
    return all(
        proposed[key][:len(shipped[key])] == shipped[key] if key in LISTS
        else proposed[key] == shipped[key] for key in shipped)


def test_top_level_keys_and_sizes(case, bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert isinstance(bench["run_seconds"], int)
    assert 10 <= bench["run_seconds"] <= 51
    assert os.path.getsize(case.path) < 64 * 1024
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


def test_every_configuration_states_its_cut(case, bench):
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for entry in bench["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert entry["name"] in used
        assert entry["file"].startswith("chipbench/")
        assert 1 <= len(entry["source"]) <= 200
        path = os.path.join(REPO, entry["file"])
        if case.appended and not os.path.exists(path):
            # the copy's own configuration, found as ``load_cell`` finds it
            path = harness.find(
                case.roots, "configs", os.path.basename(entry["file"]))
        config = harness.load_json(path)
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


def test_every_cell_is_found_by_name(case, bench):
    for cell in bench["workloads"]:
        loaded = harness.load_cell(bench, cell["name"], case.roots)
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


NUMBERS = ("loss_gap", "grad_gap", "update_gap", "grad_diff",
           "out_grad_diff")


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    BENCH, "limits", "*.json"))), ids=os.path.basename)
def test_a_limit_stands_between_its_two_readings(path):
    """Where a number's entry under ``readings`` states the largest sound
    reading (``sound_max``, over ``seeds`` seeds) and the smallest reading
    of what the number is held against (``fault_min``), the limit lies
    between them; ``grad_gap`` and ``update_gap``, whose sound readings are
    heavy-tailed and whose fault reads 1, keep a factor of two on both
    sides (``chipbench/README.md``, "The rule a limit is set by"). The
    block-diffusion cell's file states them for all five numbers."""
    file = harness.load_json(path)
    limits = file["limits"]
    assert file["cell"] + ".json" == os.path.basename(path)
    stated = []
    for name in NUMBERS:
        entry = file.get("readings", {}).get(name)
        if not (isinstance(entry, dict)
                and {"sound_max", "fault_min"} <= set(entry)):
            continue
        stated.append(name)
        assert isinstance(entry["seeds"], int) and entry["seeds"] >= 1
        assert entry["read"], "the prose stays beside the keys"
        assert entry["sound_max"] < limits[name] < entry["fault_min"], name
        if name in ("grad_gap", "update_gap"):
            assert limits[name] >= 2 * entry["sound_max"], name
            assert 2 * limits[name] <= entry["fault_min"], name
    if file["cell"] == "sdar-30b-a3b.seq4k-v18992":
        assert stated == list(NUMBERS)


def test_every_per_layer_metric_has_its_reader(case, bench):
    for metric in bench["per_layer"]:
        reader = harness.load_module(harness.find(
            case.roots, "layer_metrics", metric["name"] + ".py"), "reader")
        assert reader.NAME == metric["name"]
        assert reader.UNIT == metric["unit"]
        assert reader.LAYER == metric["layer"]
        assert reader.MOVES == metric["moves"]
        assert reader.SOURCE == metric["source"]
        assert callable(reader.read)


def test_every_reader_is_listed_and_every_entry_has_its_file(case, bench):
    """No reader waits unlisted under ``layer_metrics/`` (PR 36 shipped five
    that nothing listed), and no entry lacks its file: the ``*.py`` of the
    roots' ``layer_metrics`` directories are the names of ``per_layer``,
    each once."""
    files = []
    for root in case.roots:
        folder = os.path.join(root, "layer_metrics")
        files += [name[:-3] for name in os.listdir(folder)
                  if name.endswith(".py")]
    names = [m["name"] for m in bench["per_layer"]]
    assert sorted(files) == sorted(names)
    assert len(names) == len(set(names))


def test_one_mechanism_has_one_name_in_every_cell_that_runs_it(bench):
    """A reader is named for a mechanism, not for a configuration: the
    decoder cells share the readers of attention, the flash kernels, the
    routed experts and their grouped products. The block-diffusion cell
    runs the routed experts and the flash kernels as the others do, and is
    on those lists (PR 44); its mask is not a band, so its attention and the
    two kernels' shares are counted by ``block_mask_costs.py`` under names
    of their own and the band's readers do not list it: one call, one
    count. The two-kernel backward pass's readers went when no cell ran it
    any more."""
    decoders = ["laguna-xs2.seq8k", "nemotron3-super.seq8k-v16384",
                "joyai-llm-flash.seq8k-v16160"]
    routed = decoders + ["sdar-30b-a3b.seq4k-v18992"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("device_moe_ms", "expert_load_max_over_mean",
                 "moe_rows_walked_over_landed", "grouped_matmul_roofline",
                 "flash_fwd_calls_per_bwd_call"):
        assert by_name[name]["workloads"] == routed, name
    for name in ("device_attention_ms", "flash_fwd_roofline",
                 "flash_bwd_roofline"):
        assert by_name[name]["workloads"] == decoders, name
    for name in by_name:
        assert not name.startswith(("latent_moe", "mla_flash",
                                    "device_latent", "flash_dq",
                                    "flash_dkv")), name


def test_an_entry_appended_after_device_mtp_ms_is_appended_only(bench):
    """``device_mtp_ms`` is found by its name, and nothing is held about
    what follows it: what follows was appended, and so is one entry more."""
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("device_mtp_ms")
    cut = dict(bench, per_layer=bench["per_layer"][:at + 1])
    assert appended_only(cut, bench)
    more = copy.deepcopy(bench)
    more["per_layer"].append(dict(
        bench["per_layer"][at], name="device_step_done_ms"))
    assert appended_only(bench, more) and appended_only(cut, more)
    moved = copy.deepcopy(more)
    moved["per_layer"].insert(at, moved["per_layer"].pop())
    assert not appended_only(bench, moved)


def test_a_cell_reports_the_metrics_that_list_it_or_list_nobody(case, bench):
    """The harness reads each cell's per-layer metrics from whichever file
    it is given, each in the cells its ``workloads`` list names, or in every
    cell that reports what it ``moves`` where it has no list. The record
    here is an untraced run's, a count of steps and of examples: the copy's
    two readers read those, and what else a later PR's reader finds in it is
    not this test's to judge."""
    read = {cell["name"]: harness.per_layer(
        bench, cell["name"], case.roots, {"steps": 7, "examples": 56}, None)
        for cell in bench["workloads"]}
    for metric in bench["per_layer"]:
        for cell, out in read.items():
            if not harness.metric_reports_in(metric, cell, bench):
                assert metric["name"] not in out
    steps = {"value": 7, "unit": "steps"}
    examples = {"value": 56, "unit": "examples"}
    for cell, out in read.items():
        assert (out.get("window_steps") == steps) == case.appended
        assert (out.get("window_examples") == examples) == (
            case.appended and cell == chipbench_tiny.CELL)


def test_what_is_not_appended_at_the_end_is_an_edit(tmp_path):
    """``appended_only`` says yes to the copy the other tests read, and no
    to the same additions made any other way."""
    shipped = harness.load_json(SHIPPED)
    path, _ = chipbench_tiny.append(str(tmp_path), SHIPPED)
    appended = harness.load_json(path)
    assert appended_only(shipped, shipped)
    assert appended_only(shipped, appended)
    assert [len(appended[key]) - len(shipped[key]) for key in LISTS] == [
        1, 1, 0, 2]

    def edited(change):
        proposed = copy.deepcopy(appended)
        change(proposed)
        return proposed

    def named(bench, group, name):
        return next(m for m in bench[group] if m["name"] == name)

    last = len(shipped["per_layer"]) - 1
    cell = chipbench_tiny.CELL
    edits = {
        # what PR 31 did: the new entry before the last shipped one
        "placed_before_the_last": lambda b: b["per_layer"].insert(
            last, b["per_layer"].pop()),
        # a shipped metric made to report in the new cell
        "workloads_lengthened": lambda b: named(
            b, "per_layer", "device_moe_ms")["workloads"].append(cell),
        "workloads_given": lambda b: named(
            b, "per_layer", "device_step_ms").update(workloads=[cell]),
        "cell_placed_first": lambda b: b["workloads"].insert(
            0, b["workloads"].pop()),
        "bound_loosened": lambda b: named(
            b, "end_to_end", "images_per_s_per_chip").update(bound=0.02),
        "entry_taken_away": lambda b: b["per_layer"].remove(
            named(b, "per_layer", "compile_s")),
        "run_seconds_changed": lambda b: b.update(run_seconds=10),
        "key_added": lambda b: b.update(notes="x"),
    }
    for name, change in edits.items():
        assert not appended_only(shipped, edited(change)), name


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
