"""``chipbench/scopes.py``: the join of a reduced trace's operations with the
program's map on synthetic data (sums, the 1% refusal, newest incarnation,
nothing without a trace or a map), and the tiny cell's traced run through
the shipped adapter on the CPU up to the point a device trace is needed:
the program writes its map and its counters, and the new readers read them."""

import json
import os
import sys
import time
import types

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny  # noqa: E402
from chipbench import run as harness  # noqa: E402
from chipbench import scopes  # noqa: E402

CELL = "tiny-netresdeep.t8"
METRICS = ("device_forward_ms", "device_backward_ms", "device_optimizer_ms",
           "device_grad_sync_ms", "device_other_ms")
_CONFIG = ("jax_compilation_cache_dir",
           "jax_persistent_cache_min_compile_time_secs",
           "jax_persistent_cache_min_entry_size_bytes")


def reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "scopes_test_" + name)


def row(phase, module="", **extra):
    return dict({"op_name": "", "opcode": "fusion", "phase": phase,
                 "module": module}, **extra)


INSTRUCTIONS = {
    "fusion.1": row("forward", "stem_conv"),
    "fusion.2": row("backward", "stem_conv"),
    "fusion.3": row("backward", "head", mixed=True),
    "multiply_add_fusion.4": row("optimizer", "optimizer_update"),
    "all-reduce": row("grad_sync", "collective"),
    "copy-start.5": row("forward", "stem_conv", inherited=True),
    "fusion.6": row("input", "input"),
    "fusion.7": row("other", "metrics"),
}
#: seconds over a slice of 10 steps
DEVICE_OPS = [["fusion.2", 0.30], ["fusion.1", 0.20], ["fusion.3", 0.10],
              ["multiply_add_fusion.4", 0.02], ["all-reduce", 0.01],
              ["copy-start.5", 0.004], ["fusion.6", 0.003],
              ["fusion.7", 0.002], ["fusion.99", 0.001]]


def write_run(tmp_path, instructions=INSTRUCTIONS, incarnation=0,
              counters=True):
    """A traced run's telemetry directory, as the program leaves it."""
    tel = tmp_path / "telemetry"
    tel.mkdir(exist_ok=True)
    suffix = f".i{incarnation}" if incarnation else ""
    if instructions is not None:
        record = {"type": "program_map", "schema_version": 1,
                  "program": "train_step", "module": "jit_shard_step",
                  "dispatch": 3, "export_seconds": 0.5, "mixed_fusions": 1,
                  "phases": {}, "instructions": instructions}
        (tel / f"programs-p0{suffix}.jsonl").write_text(
            json.dumps(record) + "\n")
    lines = [{"type": "header"}]
    if counters:
        lines.append({"type": "counters", "name": "counters", "attrs": {
            "counters": {"jax/compilations": 0, "jax/cache_loads": 221},
            "histograms": {"jax/trace_seconds": {"sum": 12.5, "count": 900},
                           "jax/lower_seconds": {"sum": 7.25, "count": 230},
                           "jax/compile_seconds": {"sum": 5.0, "count": 221}},
            "tables": {"jax/functions": {
                "shard_step": {"trace_seconds": 9.0,
                               "trace_self_seconds": 7.5, "traces": 2,
                               "lower_seconds": 5.0, "lowerings": 2,
                               "cache_load_seconds": 2.0, "cache_loads": 2},
                "_normal": {"trace_seconds": 0.5, "traces": 30}}}}})
    (tel / f"trace-p0{suffix}.jsonl").write_text(
        "".join(json.dumps(line) + "\n" for line in lines))
    record = {"trace_dir": str(tmp_path / "profile")}
    trace = {"device_ops": DEVICE_OPS, "steps": 10,
             "device_step_ms": 1e3 * sum(s for _, s in DEVICE_OPS) / 10}
    return types.SimpleNamespace(record=record, trace=trace)


def test_the_split_sums_to_the_device_step(tmp_path, capsys):
    run = write_run(tmp_path)
    values = {name: reader(name).read(run) for name in METRICS}
    assert values["device_forward_ms"] == pytest.approx(20.4)
    assert values["device_backward_ms"] == pytest.approx(40.0)
    assert values["device_optimizer_ms"] == pytest.approx(2.0)
    assert values["device_grad_sync_ms"] == pytest.approx(1.0)
    # input, other and the operation the map lacks, together
    assert values["device_other_ms"] == pytest.approx(0.3 + 0.2 + 0.1)
    assert sum(values.values()) == pytest.approx(run.trace["device_step_ms"])
    split = scopes.of_run(run)["split"]
    assert split["unmapped_share"] == pytest.approx(0.001 / 0.64)
    assert split["mixed_share"] == pytest.approx(0.10 / 0.64)
    assert split["inherited_share"] == pytest.approx(0.004 / 0.64)
    assert split["rows"][0] == ["stem_conv", "backward", pytest.approx(30.0)]
    out = capsys.readouterr().out.splitlines()
    assert all(line.startswith("chipbench:") for line in out)
    # printed once, however many readers ask
    assert len([ln for ln in out if "ms per step by phase" in ln]) == 1
    assert any("shard_step 9.0000 7.5000 2 5.0000 2" in ln for ln in out)
    assert reader("trace_lower_s").read(run) == pytest.approx(19.75)


def test_a_map_of_another_program_is_refused(tmp_path, capsys):
    fewer = {k: v for k, v in INSTRUCTIONS.items() if k != "fusion.3"}
    run = write_run(tmp_path, instructions=fewer)
    assert all(reader(name).read(run) is None for name in METRICS)
    assert "map of another program" in capsys.readouterr().out
    # the counters are the run's own whatever the map is
    assert reader("trace_lower_s").read(run) == pytest.approx(19.75)


def test_the_newest_incarnation_wins(tmp_path):
    write_run(tmp_path, instructions={"fusion.1": row("other")})
    swapped = dict(INSTRUCTIONS, **{"fusion.1": row("backward", "head"),
                                    "fusion.2": row("forward", "head")})
    run = write_run(tmp_path, instructions=swapped, incarnation=2)
    assert reader("device_forward_ms").read(run) == pytest.approx(30.4)
    files = scopes.newest(str(tmp_path / "telemetry"))
    assert files["programs"].endswith("programs-p0.i2.jsonl")
    # a newer life that wrote no map does not borrow an older life's
    (tmp_path / "telemetry" / "trace-p0.i3.jsonl").write_text("{}\n")
    assert scopes.newest(str(tmp_path / "telemetry"))["programs"] is None


def test_nothing_to_read_is_none_and_raises_nothing(tmp_path, capsys):
    untraced = types.SimpleNamespace(record={"trace_dir": None}, trace=None)
    no_map = write_run(tmp_path, instructions=None, counters=False)
    empty = types.SimpleNamespace(
        record={"trace_dir": str(tmp_path / "none" / "profile")},
        trace={"device_ops": DEVICE_OPS, "steps": 10})
    for run in (untraced, no_map, empty):
        for name in METRICS + ("trace_lower_s",):
            assert reader(name).read(run) is None
    assert "wrote no program map" in capsys.readouterr().out


# -- the tiny cell's traced run, up to the device trace ----------------------------

@pytest.fixture
def keep_jax_config():
    """The harness points jax's cache at the checkout; put it back."""
    saved = {k: getattr(jax.config, k) for k in _CONFIG}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_the_tiny_cell_writes_what_the_readers_read(tmp_path, capsys,
                                                    keep_jax_config):
    bench_path, roots = chipbench_tiny.write(str(tmp_path))
    bench = harness.load_json(bench_path)
    loaded = harness.load_cell(bench, CELL, roots + [harness.HERE])
    ctx = types.SimpleNamespace(
        cell=loaded["cell"], config=loaded["config"],
        traffic=loaded["traffic"], reference=loaded["reference"],
        dataset=loaded["dataset"], seed=7, seconds=0.3, trace=True, counters=harness.Counters().install(),
        scratch_dir=str(tmp_path / "runs"), t_start=time.perf_counter(),
        say=harness.say)
    record = loaded["adapter"].run(ctx)
    # the export is over before the window opens
    assert record["compiles_in_window"] == 0
    files = scopes.newest(scopes.telemetry_dir(record))
    assert files["programs"] and files["trace"]
    program_map = scopes.load_map(files["programs"])
    assert program_map["programs"][0]["module"] == "jit_shard_step"
    assert program_map["programs"][0]["program"] == "train_step"
    # no device on the CPU: stand each instruction that runs in for 1 ms
    ops = [[name, 0.001] for name, r in program_map["instructions"].items()
           if r["opcode"] not in ("parameter", "constant", "tuple",
                                  "get-tuple-element", "bitcast")]
    steps = record["steps"]
    trace = {"device_ops": ops, "steps": steps,
             "device_step_ms": len(ops) / steps}
    # the shipped BENCHMARK.json's new entries, read by the harness itself
    shipped = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    bench["per_layer"] = [m for m in shipped["per_layer"]
                          if m["name"] in METRICS + ("trace_lower_s",)]
    for metric in bench["per_layer"]:
        metric.pop("workloads", None)
    out = harness.per_layer(bench, CELL, roots + [harness.HERE], record,
                            trace)
    assert set(out) == set(METRICS + ("trace_lower_s",))
    assert sum(out[name]["value"] for name in METRICS) == pytest.approx(
        trace["device_step_ms"])
    for name in ("device_forward_ms", "device_backward_ms",
                 "device_optimizer_ms"):
        assert out[name]["value"] > 0
    assert out["trace_lower_s"]["value"] > 0
    printed = capsys.readouterr().out
    assert "scopes: shard_step" in printed  # the per-function table
