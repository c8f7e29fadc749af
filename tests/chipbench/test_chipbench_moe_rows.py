"""``moe_rows_walked_over_landed``: the reader against recorded gauges, a
program without the counter (the parent), and the gauges that the program's
own reduction makes of what ``DroplessMoE`` sows."""

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny  # noqa: E402
from chipbench import run as harness  # noqa: E402

NAME = "moe_rows_walked_over_landed"


@pytest.fixture(scope="module")
def reader():
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", NAME + ".py"),
        "chipbench_metric_" + NAME)


def _traced(tmp_path, gauges):
    os.makedirs(tmp_path / "telemetry")
    (tmp_path / "telemetry" / "trace-p0.jsonl").write_text(json.dumps(
        {"type": "counters", "attrs": {"tables": {}, "gauges": gauges}})
        + "\n")
    return types.SimpleNamespace(
        record={"trace_dir": str(tmp_path / "profile")},
        trace={"device_ops": [["fusion.1", 0.5]], "steps": 5,
               "device_step_ms": 100.0})


@pytest.mark.parametrize("appended", [False, True],
                         ids=["shipped", "appended"])
def test_the_entry_names_the_cells_that_hold_a_share(tmp_path, appended):
    """Found by its name: a later PR's entries go after it, at the end of
    the list (``chipbench_tiny.append`` makes such a copy)."""
    path = os.path.join(REPO, "BENCHMARK.json")
    if appended:
        path, _ = chipbench_tiny.append(str(tmp_path), path)
    bench = harness.load_json(path)
    names = [m["name"] for m in bench["per_layer"]]
    assert not appended or names.index(NAME) < len(names) - 1
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "models",
        "moves": "images_per_s_per_chip", "workloads": [
            "laguna-xs2.seq8k", "nemotron3-super.seq8k-v16384",
            "joyai-llm-flash.seq8k-v16160", "sdar-30b-a3b.seq4k-v18992"]}


@pytest.mark.parametrize("gauges,want", [
    # four sparse layers on the 32,768 rung, 72,388 pairs landed a step
    ({"model/expert_rows_walked_sum": 131072.0,
      "model/expert_rows_walked_max": 32768.0,
      "model/expert_load_sum": 72388.0}, 131072.0 / 72388.0),
    # one layer climbed a rung
    ({"model/expert_rows_walked_sum": 163840.0,
      "model/expert_load_sum": 81920.0}, 2.0),
    # the parent: it counts what landed and walks a row for every pair
    ({"model/expert_load_sum": 72388.0, "model/expert_load_max": 2260.0,
      "model/expert_load_mean": 565.5}, None),
    # a program that keeps no counters at all
    ({}, None),
    # nothing landed: no ratio, nothing raised
    ({"model/expert_rows_walked_sum": 131072.0,
      "model/expert_load_sum": 0.0}, None),
], ids=["one_rung", "climbed", "parent", "no_counters", "nothing_landed"])
def test_the_reader_divides_rows_walked_by_rows_landed(reader, tmp_path,
                                                       gauges, want):
    got = reader.read(_traced(tmp_path, gauges))
    assert got == want if want is None else got == pytest.approx(want)


def test_an_untraced_run_has_nothing_to_read(reader):
    assert reader.read(types.SimpleNamespace(
        record={"trace_dir": None}, trace=None)) is None


def test_the_programs_counters_become_the_gauges_the_reader_reads(
        reader, tmp_path):
    """What two sparse layers sow in two steps, stacked by name as the step
    hands it out, through ``Telemetry.record_model_counters``."""
    import jax

    from tpu_ddp.models.moe import DroplessMoE, buffer_rungs
    from tpu_ddp.parallel.expert_parallel import ExpertShare
    from tpu_ddp.telemetry import Telemetry

    tokens, top_k = 256, 8
    rungs = buffer_rungs(tokens * top_k, 8, 32)
    layer = DroplessMoE(ExpertShare(32, 8, 8), top_k=top_k, expert_width=8)
    steps = []
    for step in range(2):
        sown = []
        for depth in range(2):
            x = jax.random.normal(jax.random.key(10 * step + depth),
                                  (1, tokens, 16))
            params = layer.init(jax.random.key(depth), x)
            sown.append(layer.apply(params, x, mutable=["counters"])[1][
                "counters"])
        steps.append({name: np.stack([np.asarray(c[name][0]) for c in sown])
                      for name in sown[0]})
    gauges = Telemetry(enabled=False).record_model_counters(steps)
    landed = np.mean([s["expert_load"].sum() for s in steps])
    assert gauges["model/expert_rows_walked_sum"] == 2 * rungs[0]
    assert gauges["model/expert_rows_walked_max"] == rungs[0]
    assert reader.read(_traced(tmp_path, gauges)) == pytest.approx(
        2 * rungs[0] / landed)


def test_a_switch_is_not_summed_with_the_branch_it_runs():
    """The layer's switch is a ``conditional`` in the trace, as long as the
    branch's operations beside it; the program's map calls it ``control``
    and the split by phase and by module sums the six phases it knows."""
    from chipbench import scopes

    instructions = {
        "conditional.1": {"opcode": "conditional", "phase": "control",
                          "module": ""},
        "fusion.2": {"opcode": "fusion", "phase": "backward",
                     "module": "moe_combine"},
        "ragged-dot-none.3": {"opcode": "custom-call", "phase": "backward",
                              "module": "moe_experts", "inherited": True},
    }
    split = scopes.join([["conditional.1", 0.030], ["fusion.2", 0.020],
                         ["ragged-dot-none.3", 0.010]], instructions, steps=2)
    assert split["phase_ms"]["backward"] == pytest.approx(15.0)
    assert sum(split["phase_ms"][p] for p in scopes.PHASES) == pytest.approx(
        15.0)
    assert split["unmapped_share"] == 0.0
    # nor is it busy time: the shares are of time counted once
    assert split["busy_s"] == pytest.approx(0.030)
    assert split["inherited_share"] == pytest.approx(1 / 3)
    assert split["control_ms"] == pytest.approx(15.0)
    by_module = {module: ms for module, phase, ms in split["rows"]
                 if phase in scopes.PHASES}
    assert by_module == {"moe_combine": pytest.approx(10.0),
                         "moe_experts": pytest.approx(5.0)}
