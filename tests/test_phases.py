"""The phase vocabulary end to end on the CPU: the ``op_name`` classifier, the
scopes of the step builders as the compiled text shows them, the program map
through its file, the per-function compile accounting, and that telemetry
off costs nothing."""

import json
import os

import jax
import numpy as np
import pytest

from tpu_ddp.telemetry import phases
from tpu_ddp.telemetry.program_map import (
    ProgramMapExporter,
    build_record,
    newest_program_map_file,
    read_program_maps,
)

STEP = "jit(shard_step)/shard_map/"


@pytest.mark.parametrize("op_name,opcode,expected", [
    # forward and backward are told apart by the AD marker
    (STEP + "tpu_ddp.forward_backward/jvp(ResNet)/stem_conv/"
     "conv_general_dilated", "fusion", ("forward", "stem_conv")),
    (STEP + "tpu_ddp.forward_backward/transpose(jvp(ResNet))/_Bottleneck_3/"
     "Conv_0/conv_general_dilated", "fusion", ("backward", "_Bottleneck_3")),
    (STEP + "tpu_ddp.optimizer_update/add", "fusion",
     ("optimizer", "optimizer_update")),
    # a collective is grad_sync whatever scope it sits in
    (STEP + "tpu_ddp.forward_backward/transpose(jvp(ResNet))/head/"
     "psum_invariant", "all-reduce", ("grad_sync", "collective")),
    (STEP + "tpu_ddp.optimizer_update/tpu_ddp.zero1_allgather_params/"
     "all_gather", "all-gather", ("grad_sync", "zero1_allgather_params")),
    # a fusion that joins several paths takes the first
    (STEP + "tpu_ddp.forward_backward/jvp(ResNet)/head/dot_general;"
     + STEP + "tpu_ddp.optimizer_update/add", "fusion", ("forward", "head")),
    # a kernel scope names the module and keeps the enclosing phase
    (STEP + "tpu_ddp.optimizer_update/tpu_ddp.zero1_shard_update/"
     "tpu_ddp.kernel.fused_update/pallas_call", "custom-call",
     ("optimizer", "kernel.fused_update")),
    (STEP + "tpu_ddp.forward_backward/transpose(jvp(ViT))/block_2/attn/"
     "tpu_ddp.kernel.flash_dq/pallas_call", "custom-call",
     ("backward", "kernel.flash_dq")),
    # a scope inside the model names the module, of a kernel inside it too
    (STEP + "tpu_ddp.forward_backward/jvp(SparseDecoder)/layer_2/moe/"
     "tpu_ddp.module.moe_route/router/dot_general", "fusion",
     ("forward", "moe_route")),
    (STEP + "tpu_ddp.forward_backward/transpose(jvp(SparseDecoder))/layer_1/"
     "checkpoint/rematted_computation/attn/tpu_ddp.module.attention_window/"
     "tpu_ddp.kernel.flash_dkv/pallas_call", "custom-call",
     ("backward", "attention_window")),
    (STEP + "tpu_ddp.forward_backward/jvp(SparseDecoder)/layer_1/moe/"
     "tpu_ddp.module.moe_experts/tpu_ddp.kernel.grouped_matmul/pallas_call",
     "custom-call", ("forward", "moe_experts")),
    # the loss sits in the forward scope but under no model
    (STEP + "tpu_ddp.forward_backward/jvp(tpu_ddp.loss)/jit(log_softmax)/"
     "reduce_max", "fusion", ("forward", "loss")),
    # the model's own work, and work under control flow
    (STEP + "tpu_ddp.forward_backward/jvp(ResNet)/reduce_sum", "fusion",
     ("forward", "ResNet")),
    ("jit(shard_multi)/shard_map/while/body/tpu_ddp.forward_backward/"
     "jvp(checkpoint)/NetResDeep/resblock/conv_general_dilated", "fusion",
     ("forward", "resblock")),
    # inside the branch of a switch that a custom_vjp's backward rule made
    (STEP + "tpu_ddp.forward_backward/transpose(jvp(SparseDecoder))/layer_1/"
     "moe/cond/branch_0_fun/jvp(tpu_ddp.module.moe_combine)/jit(_take)/"
     "gather", "fusion", ("backward", "moe_combine")),
    (STEP + "tpu_ddp.input/jit(_uniform)/mul", "fusion", ("input", "input")),
    (STEP + "tpu_ddp.bn_stats_sync/div", "fusion",
     ("grad_sync", "bn_stats_sync")),
    (STEP + "tpu_ddp.metrics/reduce_sum", "fusion", ("other", "metrics")),
    (STEP + "tpu_ddp.zero3_prefetch/b2/all_gather", "all-gather",
     ("grad_sync", "zero3_prefetch")),
    # unscoped, and nothing at all
    (STEP + "add", "fusion", ("other", "")),
    ("", "copy", ("other", "")),
    ("", "all-reduce", ("grad_sync", "collective")),
])
def test_classify(op_name, opcode, expected):
    assert phases.classify(op_name, opcode) == expected
    assert expected[0] in phases.PHASES


@pytest.mark.parametrize("opcode", ["conditional", "while", "call"])
def test_an_instruction_that_only_runs_others_has_no_phase(opcode):
    """Whatever scope it sits in: a trace shows it as long as what it runs,
    and what it runs beside it."""
    path = (STEP + "tpu_ddp.forward_backward/transpose(jvp(SparseDecoder))/"
            "layer_1/moe/cond")
    assert phases.classify(path, opcode) == (phases.CONTROL, "")
    assert phases.CONTROL not in phases.PHASES


def test_a_switch_is_mapped_as_control_and_its_branches_by_their_scopes():
    moe = ("jit(shard_step)/tpu_ddp.forward_backward/jvp(SparseDecoder)/"
           "layer_1/moe/cond")
    inside = moe + "/branch_{}_fun/tpu_ddp.module.moe_experts/mul"
    text = f'''HloModule jit_shard_step, is_scheduled=true

%short (p.2: f32[8]) -> f32[8] {{
  %p.2 = f32[8]{{0}} parameter(0)
  ROOT %fusion.3 = f32[8]{{0}} fusion(%p.2), kind=kLoop, calls=%fused.1, metadata={{op_name="{inside.format(0)}"}}
}}

%long (p.4: f32[8]) -> f32[8] {{
  %p.4 = f32[8]{{0}} parameter(0)
  ROOT %fusion.5 = f32[8]{{0}} fusion(%p.4), kind=kLoop, calls=%fused.2, metadata={{op_name="{inside.format(1)}"}}
}}

ENTRY %main.9 (p.1: f32[8], i.1: s32[]) -> f32[8] {{
  %p.1 = f32[8]{{0}} parameter(0), metadata={{op_name="batch"}}
  %i.1 = s32[] parameter(1), metadata={{op_name="index"}}
  %copy.6 = f32[8]{{0}} copy(%p.1)
  ROOT %conditional.7 = f32[8]{{0}} conditional(%i.1, %copy.6, %copy.6), branch_computations={{%short, %long}}, metadata={{op_name="{moe}"}}
}}
'''
    record = build_record(text, program="train_step")
    rows = record["instructions"]
    assert rows["conditional.7"]["phase"] == phases.CONTROL
    assert rows["conditional.7"]["module"] == ""
    # the copy the compiler made for the switch inherits no ``control``
    assert rows["copy.6"]["phase"] == "other"
    for name in ("fusion.3", "fusion.5"):
        assert (rows[name]["phase"], rows[name]["module"]) == (
            "forward", "moe_experts")
    assert record["phases"][phases.CONTROL] == 1
    assert sum(record["phases"].values()) == len(rows)
    # a sum over the phases of the step never meets the switch's own time
    assert sum(record["phases"][p] for p in phases.PHASES) == len(rows) - 1


def test_a_fusion_that_holds_work_of_two_scopes_is_mixed():
    fwd = STEP + "tpu_ddp.forward_backward/jvp(M)/head/dot_general"
    bwd = STEP + "tpu_ddp.forward_backward/transpose(jvp(M))/head/dot_general"
    update = STEP + "tpu_ddp.optimizer_update/add"
    assert phases.is_mixed(bwd + ";" + update)
    assert phases.is_mixed(fwd, bwd, update)
    # the linearization's residuals run where the backward consumes them
    assert not phases.is_mixed(bwd + ";" + fwd)
    assert not phases.is_mixed(fwd + ";" + fwd) and not phases.is_mixed(fwd)
    assert not phases.is_mixed(bwd + ";" + STEP + "add")  # unscoped
    # a weight-gradient convolution with the update as its epilogue: the
    # fusion keeps the op_name the compiler left on it and says mixed
    text = (
        "HloModule jit_f\n\n%fused (p0: f32[4]) -> f32[4] {\n"
        "  %p0 = f32[4]{0} parameter(0)\n"
        f'  %conv = f32[4]{{0}} convolution(%p0, %p0), '
        f'metadata={{op_name="{bwd}"}}\n'
        f'  ROOT %add = f32[4]{{0}} add(%conv, %p0), '
        f'metadata={{op_name="{update}"}}\n}}\n\n'
        "ENTRY %main (p: f32[4]) -> f32[4] {\n"
        "  %p = f32[4]{0} parameter(0)\n"
        f'  ROOT %multiply_add_fusion.1 = f32[4]{{0}} fusion(%p), '
        f'kind=kOutput, calls=%fused, metadata={{op_name="{bwd}"}}\n}}\n')
    record = build_record(text, program="train_step")
    assert set(record["instructions"]) == {"p", "multiply_add_fusion.1"}
    row = record["instructions"]["multiply_add_fusion.1"]
    assert row["phase"] == "backward" and row["mixed"] is True
    assert record["mixed_fusions"] == 1


# -- the step builders, as the compiled text shows them ------------------------

COUNTED = ("fusion", "convolution", "dot")


def _compiled_text(devices, **layout):
    from tpu_ddp.analysis.explain import abstract_batch
    from tpu_ddp.models import NetResDeep
    from tpu_ddp.parallel import MeshSpec, create_mesh
    from tpu_ddp.train import make_optimizer
    from tpu_ddp.train.strategy import build_abstract_step

    mesh = create_mesh(MeshSpec(data=-1), devices[:4])
    model = NetResDeep(n_chans1=8, n_blocks=6, tied=False)
    sharded = layout.get("zero1") or layout.get("zero3")
    tx = make_optimizer(lr=1e-2, momentum=0.9,
                        zero1_axis="data" if sharded else None)
    step, state = build_abstract_step("dp", model, tx, mesh, **layout)
    batch = abstract_batch(mesh, 8, 32)
    return step.lower(state, batch).compile().as_text()


@pytest.mark.parametrize("layout,scopes", [
    ({}, ("tpu_ddp.forward_backward", "tpu_ddp.optimizer_update",
          "tpu_ddp.bn_stats_sync", "tpu_ddp.metrics", "tpu_ddp.loss")),
    ({"zero1": True}, ("tpu_ddp.zero1_shard_update",
                       "tpu_ddp.zero1_allgather_params",
                       "tpu_ddp.grad_sync")),
    ({"zero3": True}, ("tpu_ddp.zero3_shard_update",
                       "tpu_ddp.zero3_prefetch", "tpu_ddp.grad_sync")),
    ({"grad_compress": {"mode": "int8", "block": 64,
                        "error_feedback": True}},
     ("tpu_ddp.grad_compress_ring", "tpu_ddp.optimizer_update")),
], ids=["dp", "zero1", "zero3", "grad_compress"])
def test_a_dp_builder_names_every_phase_it_has(devices, layout, scopes):
    text = _compiled_text(devices, **layout)
    for scope in scopes:
        assert scope in text, scope
    record = build_record(text, program="train_step")
    assert record["module"] == "jit_shard_step"
    rows = [r for r in record["instructions"].values()
            if r["opcode"] in COUNTED
            or r["opcode"].startswith(phases.COLLECTIVE_OPCODES)]
    by_phase = {p: sum(r["phase"] == p for r in rows) for p in phases.PHASES}
    for phase in ("forward", "backward", "optimizer", "grad_sync"):
        assert by_phase[phase] > 0, (phase, by_phase)
    assert by_phase["other"] < 0.10 * len(rows), by_phase
    collectives = [r for r in rows
                   if r["opcode"].startswith(phases.COLLECTIVE_OPCODES)]
    assert collectives and all(r["phase"] == "grad_sync"
                               for r in collectives)


def test_a_kernel_the_compiler_emits_takes_its_users_phase_and_module():
    """XLA:TPU's grouped product for ``lax.ragged_dot`` is a custom call
    that keeps the compiler's name for it and none of the program's scopes:
    it is mapped by where its result goes. A parameter or a reducer's ``eq``
    with a bare name is no custom call and stays as it was."""
    moe = ("jit(shard_step)/tpu_ddp.forward_backward/transpose(jvp("
           "SparseDecoder))/layer_1/moe/tpu_ddp.module.moe_experts/select_n")
    text = f'''HloModule jit_shard_step, is_scheduled=true

ENTRY %main.9 (p.1: f32[8]) -> f32[8] {{
  %p.1 = f32[8]{{0}} parameter(0), metadata={{op_name="state.params"}}
  %compare.4 = f32[8]{{0}} negate(%p.1), metadata={{op_name="eq"}}
  %ragged-dot-none.1 = f32[8]{{0}} custom-call(%compare.4), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
  ROOT %fusion.2 = f32[8]{{0}} fusion(%ragged-dot-none.1), kind=kLoop, calls=%fused, metadata={{op_name="{moe}"}}
}}
'''
    rows = build_record(text, program="train_step")["instructions"]
    assert rows["ragged-dot-none.1"] == {
        "op_name": "ragged-dot-none", "opcode": "custom-call",
        "phase": "backward", "module": "moe_experts", "inherited": True}
    # inside a jitted function that the compiler inlined, the call's path
    # stands before the compiler's name: where it was called, not its scope
    called = moe.rsplit("/tpu_ddp.module", 1)[0] + "/jit(_routed)/"
    rows = build_record(
        text.replace('op_name="ragged-dot-none"',
                     f'op_name="{called}ragged-dot-none"'),
        program="train_step")["instructions"]
    assert rows["ragged-dot-none.1"] == {
        "op_name": called + "ragged-dot-none", "opcode": "custom-call",
        "phase": "backward", "module": "moe_experts", "inherited": True}
    assert rows["compare.4"]["phase"] == "other"
    assert "inherited" not in rows["compare.4"]


def test_kernel_calls_carry_their_scope():
    from tpu_ddp.ops.fused_quant import fused_dequant, fused_quant

    def both(x):
        payload = fused_quant(x, 128, interpret=True)
        return fused_dequant(payload, 128, x.shape[0], interpret=True)

    text = jax.jit(both).lower(np.ones((512,), np.float32)).as_text(
        debug_info=True)
    assert "tpu_ddp.kernel.fused_quant" in text
    assert "tpu_ddp.kernel.fused_dequant" in text


# -- the map through its file ----------------------------------------------------

def _tiny_config(run_dir, **extra):
    from tpu_ddp.train.trainer import TrainConfig

    return TrainConfig(
        synthetic_data=True, synthetic_size=64, per_shard_batch=4, epochs=1,
        model="netresdeep", n_chans1=8, n_blocks=2, n_devices=2,
        prefetch_depth=0, telemetry_dir=run_dir, **extra)


def test_the_map_round_trips_and_a_second_run_is_a_second_incarnation(
        tmp_path):
    from tpu_ddp.train.trainer import Trainer

    run_dir = str(tmp_path)
    Trainer(_tiny_config(run_dir, telemetry_sinks="jsonl")).run()
    first = newest_program_map_file(run_dir)
    assert os.path.basename(first) == "programs-p0.jsonl"
    (record,) = read_program_maps(first)
    assert record["program"] == "train_step"
    assert record["module"] == "jit_shard_step"
    # exported once the jit cache stopped growing, well inside epoch 1
    assert 2 <= record["dispatch"] <= 4
    names = set(record["instructions"])
    assert any("fusion" in n for n in names)
    for row in record["instructions"].values():
        assert row["phase"] in phases.PHASES
        assert set(row) <= {"op_name", "opcode", "phase", "module", "mixed",
                            "inherited"}
    assert sum(record["phases"].values()) == len(names)
    # the file is what was built: the same text gives the same record
    with open(first) as f:
        assert json.loads(f.readline())["instructions"] == record[
            "instructions"]

    Trainer(_tiny_config(run_dir, telemetry_sinks="jsonl")).run()
    second = newest_program_map_file(run_dir)
    assert os.path.basename(second) == "programs-p0.i1.jsonl"
    assert os.path.exists(first)  # the first life's map is kept
    (again,) = read_program_maps(second)
    assert set(again["instructions"]) == names

    future = dict(record, schema_version=99)
    with open(second, "w") as f:
        f.write(json.dumps(future) + "\n")
    with pytest.raises(ValueError, match="newer"):
        read_program_maps(second)


def test_the_export_waits_for_the_jit_cache_to_settle(tmp_path):
    class Step:
        """Stands for a jitted callable compiled anew on its second call."""

        sizes = iter([1, 2, 2, 2])
        lowered = 0

        def _cache_size(self):
            return next(self.sizes)

        def lower(self, *args):
            Step.lowered += 1
            return self

        def compile(self):
            return self

        def as_text(self):
            return ("HloModule jit_step\n\nENTRY %main (p: f32[2]) -> f32[2] "
                    "{\n  %p = f32[2]{0} parameter(0)\n  ROOT %add.1 = "
                    "f32[2]{0} add(%p, %p), metadata={op_name=\"jit(step)/"
                    "tpu_ddp.optimizer_update/add\"}\n}\n")

    exporter = ProgramMapExporter(str(tmp_path), {"single": ("train_step",
                                                             Step())})
    for dispatch in (1, 2):
        exporter.after_dispatch("single", "state", "batch")
        assert not exporter.done and Step.lowered == 0, dispatch
    exporter.after_dispatch("single", "state", "batch")
    assert exporter.done and Step.lowered == 1
    exporter.after_dispatch("single", "state", "batch")  # nothing more
    assert Step.lowered == 1
    (record,) = read_program_maps(exporter.path)
    assert record["dispatch"] == 3
    assert record["instructions"]["add.1"]["phase"] == "optimizer"


# -- per-function compile accounting -----------------------------------------------

def test_seconds_go_to_the_function_by_name(fresh_registry):
    from tpu_ddp.telemetry.jax_hooks import FUNCTIONS_TABLE, paused

    def named_for_the_table(x):
        return x * 2 + 1

    step = jax.jit(named_for_the_table)
    step(np.ones((7,), np.float32))
    step(np.ones((9,), np.float32))   # a new shape: traced and built again
    step(np.ones((9,), np.float32))   # the jit cache answers
    with paused():
        step(np.ones((11,), np.float32))  # not the run's own work
    snap = fresh_registry().snapshot(tables=True)
    row = snap["tables"][FUNCTIONS_TABLE]["named_for_the_table"]
    assert row["traces"] == 2 and row["lowerings"] == 2
    assert row["compilations"] == 2 and "cache_loads" not in row
    for column in ("trace_seconds", "lower_seconds", "compile_seconds"):
        assert row[column] > 0
    assert 0 < row["trace_self_seconds"] <= row["trace_seconds"]
    hist = snap["histograms"]
    assert hist["jax/trace_seconds"]["sum"] >= row["trace_seconds"]
    assert hist["jax/lower_seconds"]["sum"] >= row["lower_seconds"]
    assert hist["jax/compile_seconds"]["sum"] >= row["compile_seconds"]
    assert snap["counters"]["jax/compilations"] >= 2
    # only the run-end snapshot carries the table
    assert "tables" not in fresh_registry().snapshot()


def test_a_nested_trace_is_counted_once(fresh_registry):
    from tpu_ddp.telemetry.jax_hooks import FUNCTIONS_TABLE

    @jax.jit
    def inner_of_the_pair(x):
        for _ in range(40):  # long enough to show in the outer's seconds
            x = x * 1.5 + 1
        return x

    @jax.jit
    def outer_of_the_pair(x):
        return inner_of_the_pair(x) + inner_of_the_pair(x * 2)[::-1]

    outer_of_the_pair(np.ones((5,), np.float32))
    snap = fresh_registry().snapshot(tables=True)
    table = snap["tables"][FUNCTIONS_TABLE]
    outer, inner = table["outer_of_the_pair"], table["inner_of_the_pair"]
    assert outer["traces"] == 1 and inner["traces"] >= 1
    # jax's own figure for the outer holds the inner; its own part does not
    assert outer["trace_seconds"] >= inner["trace_seconds"]
    assert outer["trace_self_seconds"] <= (
        outer["trace_seconds"] - inner["trace_seconds"] + 1e-6)
    # so the histogram's sum is time that passed, not time counted twice
    total = sum(r.get("trace_self_seconds", 0.0) for r in table.values())
    assert snap["histograms"]["jax/trace_seconds"]["sum"] == pytest.approx(
        total)
    assert total < sum(r.get("trace_seconds", 0.0) for r in table.values())


def test_a_cache_load_is_not_a_compilation(compile_cache, fresh_registry):
    from tpu_ddp.parallel.runtime import enable_compile_cache
    from tpu_ddp.telemetry.jax_hooks import FUNCTIONS_TABLE

    assert enable_compile_cache() == compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    def make():  # two functions, one name, one program
        def built_then_loaded(x):
            return x * 5 - 2
        return built_then_loaded

    jax.jit(make())(np.ones((13,), np.float32))
    row = fresh_registry().snapshot(tables=True)["tables"][
        FUNCTIONS_TABLE]["built_then_loaded"]
    assert row["compilations"] == 1 and "cache_loads" not in row
    before = fresh_registry().snapshot()["counters"]
    jax.jit(make())(np.ones((13,), np.float32))
    snap = fresh_registry().snapshot(tables=True)
    row = snap["tables"][FUNCTIONS_TABLE]["built_then_loaded"]
    assert row["compilations"] == 1 and row["cache_loads"] == 1
    assert row["traces"] == 2 and row["lowerings"] == 2
    assert row["cache_load_seconds"] > 0
    counters = snap["counters"]
    assert counters["jax/cache_loads"] >= 1
    assert counters["jax/compilations"] == before["jax/compilations"]
    assert counters["jax/cache/cache_hits"] >= 1  # the names diagnose reads


# -- telemetry off --------------------------------------------------------------------

def test_telemetry_off_lowers_nothing_and_writes_nothing(tmp_path,
                                                         monkeypatch):
    from tpu_ddp.telemetry import jax_hooks
    from tpu_ddp.train.trainer import Trainer

    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(jax_hooks, "_installed", False)
    installs = []
    monkeypatch.setattr(jax_hooks, "install_jax_hooks",
                        lambda: installs.append(1) or True)
    trainer = Trainer(_tiny_config(None))
    assert trainer._program_map is None
    lowered = []
    real = trainer.train_step

    class Watched:
        def __call__(self, *args):
            return real(*args)

        def lower(self, *args):
            lowered.append(1)
            return real.lower(*args)

    trainer.train_step = Watched()
    trainer.run()
    assert not lowered and not installs
    assert not [name for _, _, files in os.walk(tmp_path) for name in files
                if name.startswith(("programs-", "trace-"))]
