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

CELL = chipbench_tiny.CELL
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
                                                    keep_jax_config, case):
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
    shipped = harness.load_json(case.path)
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


# -- the recorded v5e slice under a map, against the parent's readings -------

TESTDATA = os.path.join(harness.HERE, "testdata")
#: real operations of the slice, renamed to what the decoder cell's and the
#: four-chip cell's readers look for by name
RENAMED = {"fusion.4": "ragged-dot-none.3", "fusion.13": "ragged-dot-metadata.5",
           "fusion.17": "all-reduce.7"}
#: the two longest, which a window layer's kernels could be (2.8 and 2.6 ms
#: a step against 1.35 and 2.03 at the least); no operation is long enough
#: for any other call of a flash kernel
KERNELS = {"select_and_scatter.19": ("attention_window", "flash_fwd"),
           "broadcast_maximum_fusion": ("attention_window", "flash_dq")}
MODULES = ("layer_0", "moe_route", "moe_dispatch", "moe_experts",
           "moe_combine", "moe_shared", "attention_window", "attention_full",
           "optimizer_update", "")


def recorded_run(tmp_path):
    """``(record, reduced trace)`` of the recorded v5e slice, dressed as a
    traced run of ``laguna-xs2.seq8k`` leaves one. In each of its 39 program
    executions a ``conditional.1`` lies over operations 100-199 and a
    ``while.2`` over operations 250-259, as a trace shows a switch: as long
    as what it runs, and that beside it. The map calls the two ``control``
    and gives every other name a phase and a module by its checksum; two
    operations are calls of the flash kernels, three are renamed."""
    import zlib

    from chipbench import xplane

    with open(os.path.join(TESTDATA, "recorded.json")) as f:
        recorded = json.load(f)
    planes = xplane.load(os.path.join(TESTDATA, recorded["file"]))
    lines = planes["devices"][0]
    ops = sorted(((RENAMED.get(n, n), s, e)
                  for n, s, e in lines[xplane.OPS_LINE]), key=lambda o: o[1])
    for _, start, end in lines[xplane.MODULES_LINE]:
        inside = [o for o in ops if start <= o[1] < end]
        ops.append(("conditional.1", inside[100][1], inside[199][2]))
        ops.append(("while.2", inside[250][1], inside[259][2]))
    lines[xplane.OPS_LINE] = ops
    spans = [(name, 0.07 * i + a, 0.07 * i + b) for i in range(39)
             for name, a, b in (("data_wait", 0.0, 0.004), ("h2d", 0.004, 0.009),
                                ("compiled_step", 0.009, 0.013))]
    reduced = xplane.reduce(planes, window_s=recorded["window_s"],
                            dispatches=recorded["programs"])

    instructions = {}
    for name, _ in reduced["device_ops"]:
        n = zlib.crc32(name.encode())
        module, kernel = KERNELS.get(name, (MODULES[n % len(MODULES)], None))
        instructions[name] = {
            "opcode": "custom-call" if name.startswith("ragged") else "fusion",
            "op_name": "jit(shard_step)/layer_1/tpu_ddp.module." + module + (
                f"/tpu_ddp.kernel.{kernel}/pallas_call" if kernel else "/op"),
            "phase": scopes.PHASES[n // 16 % len(scopes.PHASES)],
            "module": module, "mixed": n % 7 == 0, "inherited": n % 11 == 0}
    for name in ("conditional.1", "while.2"):
        instructions[name] = {"opcode": name.split(".")[0], "op_name": "",
                              "phase": "control", "module": ""}
    tel = tmp_path / "laguna-xs2.seq8k" / "telemetry"
    tel.mkdir(parents=True)
    (tel / "programs-p0.jsonl").write_text(json.dumps({
        "type": "program_map", "program": "train_step",
        "instructions": instructions}) + "\n")
    (tel / "trace-p0.jsonl").write_text(json.dumps({
        "type": "counters", "attrs": {
            "tables": {"jax/functions": {}},
            "histograms": {"jax/trace_seconds": {"sum": 12.5},
                           "jax/lower_seconds": {"sum": 7.25}},
            "gauges": {"model/expert_load_max": 2260.0,
                       "model/expert_load_mean": 565.5,
                       "model/expert_load_sum": 72388.0,
                       "model/expert_rows_walked_sum": 131072.0}}}) + "\n")
    record = {"trace_dir": str(tel.parent / "profile"), "steps": 39,
              "chips": 1, "examples": 78, "host_spans": spans,
              "peak_flops_per_s": 197e12, "trainer_init_s": 6.25,
              "compile_s": 5.5, "train_flops_per_example": 1.97e11}
    return record, reduced


def read_recorded(record, reduced, bench_path, roots) -> dict:
    """cell -> {metric: value}: every per-layer metric of the benchmark file
    at ``bench_path`` that finds something to read in ``recorded_run``, in
    the decoder's cell and in the four-chip cell."""
    bench = harness.load_json(bench_path)
    return {cell: {name: m["value"] for name, m in harness.per_layer(
        bench, cell, roots, record, reduced).items()}
        for cell in ("laguna-xs2.seq8k", "resnet50-cifar.dp4")}


def test_no_metric_reads_the_busy_sum_or_the_breakdown(tmp_path, capsys,
                                                       case):
    """``testdata/recorded_metrics.json`` holds what the parent of the PR
    that took ``control`` out of ``busy_s`` and put the ``breakdown`` in
    shares read here, metric by metric: each of those is that, to the last
    digit. What a later PR appends to ``per_layer`` is read too (the copy's
    ``window_steps`` is) and is not this test's to judge."""
    with open(os.path.join(TESTDATA, "recorded_metrics.json")) as f:
        parent = json.load(f)["metrics"]
    record, reduced = recorded_run(tmp_path)
    got = read_recorded(record, reduced, case.path, case.roots)
    for cell, metrics in parent.items():
        assert {name: got[cell][name] for name in metrics} == metrics
        assert ("window_steps" in got[cell]) == case.appended
    # the dQ kernel's share that file held until its reader went (PR 44: no
    # cell runs the two-kernel backward pass), from the function that made it
    from chipbench import kernel_costs
    assert kernel_costs.flash_roofline(types.SimpleNamespace(
        record=record, trace=reduced), "flash_dq") == 78.58576334155758
    with open(os.path.join(TESTDATA, "recorded.json")) as f:
        recorded = json.load(f)  # the slice as recorded, without a switch
    by_phase = sum(got["resnet50-cifar.dp4"][name] for name in METRICS)
    assert by_phase == pytest.approx(recorded["device_step_ms"], rel=1e-12)
    # the union is a little longer: a switch covers the gaps between the
    # operations it runs
    step = got["laguna-xs2.seq8k"]["device_step_ms"]
    assert by_phase < step < 1.0001 * by_phase
    # what did move: the shares are of time counted once ...
    seconds = dict(map(tuple, reduced["device_ops"]))
    instructions = scopes.load_map(scopes.newest(scopes.telemetry_dir(
        record))["programs"])["instructions"]
    split = scopes.join(reduced["device_ops"], instructions, reduced["steps"])
    assert split["busy_s"] == pytest.approx(recorded["busy_s"], rel=1e-12)
    assert split["control_ms"] * 39 / 1e3 == pytest.approx(
        seconds["conditional.1"] + seconds["while.2"])
    assert f"sum {by_phase!r} device_step_ms {step!r}" in (
        capsys.readouterr().out)
    # ... and so are the breakdown's: every instruction once, the switch
    # (the longest "operation" of the slice) not among them
    assert reduced["device_ops"][0][0] == "conditional.1"
    ops = harness.breakdown(reduced)["device_ops"]
    assert len(ops) == 10 and ops[0] == [
        "select_and_scatter.19",
        pytest.approx(seconds["select_and_scatter.19"] / (39 * step / 1e3))]
    assert ops[0][1] * step == pytest.approx(
        1e3 * seconds["select_and_scatter.19"] / 39)  # its ms a step
    assert sum(share for _, share in ops) < 1
    # the names it leaves out are the rows the map leaves out of ``busy_s``
    assert {name for name in seconds
            if name.split(".")[0] in harness.CONTROL_STEMS} == {
        name for name, row in instructions.items()
        if row["phase"] == scopes.CONTROL} == {"conditional.1", "while.2"}


def test_the_breakdown_leaves_control_flow_out_by_its_name():
    """An instruction's name is its opcode and a number unless someone named
    it: ``run.CONTROL_STEMS`` are the opcodes to which the program's map
    gives the phase ``control`` (a ``conditional``, a ``while``, a ``call``),
    and a fusion that only starts like one stays in the list."""
    reduced = {"steps": 4, "device_step_ms": 250.0, "idle_gaps": [],
               "device_ops": [["call.3", 0.9], ["while", 0.5],
                              ["fusion.1", 0.4], ["conditional.12", 0.3],
                              ["while_fusion.2", 0.2], ["custom-call.5", 0.1]]}
    assert harness.breakdown(reduced) == {"idle_gaps": [], "device_ops": [
        ["fusion.1", 0.4], ["while_fusion.2", 0.2], ["custom-call.5", 0.1]]}


def test_the_unmapped_ceiling_is_of_time_counted_once(tmp_path, capsys):
    """``busy_s`` feeds one decision: a map that lacks the names of more
    than ``UNMAPPED_CEILING`` of it is another program's, and no phase
    metric is reported. Without the ``control`` rows the sum is smaller and
    the check stricter by as much: unmapped time just under 1% of the sum
    that counted a switch twice is refused where the switch is long."""
    mapped = dict(INSTRUCTIONS, **{"fusion.99": row("other")})
    mapped["conditional.1"] = row(scopes.CONTROL)
    mapped["conditional.1"]["opcode"] = "conditional"
    once = sum(s for _, s in DEVICE_OPS)                      # 0.64 s
    switch = ["conditional.1", 0.60]  # over fusion.2, fusion.1 and fusion.3

    def split_with(unmapped_s, name):
        (tmp_path / name).mkdir()
        run = write_run(tmp_path / name, instructions=mapped)
        run.trace = dict(run.trace, device_ops=DEVICE_OPS + [
            switch, ["fusion.100", unmapped_s]])
        return scopes.of_run(run)["split"]

    under = 0.0099 * once / (1 - 0.0099)    # 0.99% of time counted once
    split = split_with(under, "under")
    assert split["busy_s"] == pytest.approx(once + under)
    assert split["unmapped_share"] == pytest.approx(0.0099)
    # 0.99% of the doubled sum is 1.9% of the time: not this program's map
    doubled = 0.0099 * (once + switch[1]) / (1 - 0.0099)
    assert doubled / (once + switch[1] + doubled) < scopes.UNMAPPED_CEILING
    assert split_with(doubled, "doubled") is None
    assert "map of another program" in capsys.readouterr().out
