"""Step-time anatomy: extraction, roofline, fingerprints, compare gate.

The analysis subsystem (``tpu_ddp/analysis/``) makes the compiler the
primary observability source: these tests pin (a) the per-strategy
collective fingerprints on the 8-virtual-device CPU mesh — the
parallelism-correctness regression net (an extra all-gather in dp, or
the int8 ring degrading to f32, fails HERE, devicelessly) — (b) the
roofline arithmetic on a hand-computable toy anatomy, (c) the ``bench
compare`` gate in both directions, (d) the run-metadata header round
trip, and (e) the measured-telemetry join on a synthetic trace.
"""

import json

import pytest

import jax

from tpu_ddp.analysis.explain import (
    STRATEGIES,
    anatomy_for_strategy,
    check_fingerprint,
    read_run_meta,
)
from tpu_ddp.analysis.hlo import (
    Collective,
    StepAnatomy,
    compile_cache_stats,
    extract_collectives,
)
from tpu_ddp.analysis.roofline import CHIP_SPECS, chip_spec, roofline


@pytest.fixture(scope="module")
def anatomies(devices):
    """One compiled anatomy per strategy, shared module-wide (the
    process compile cache makes re-use free)."""
    return {s: anatomy_for_strategy(s) for s in STRATEGIES}


# -- collective fingerprints: the parallelism-correctness net -------------

#: EXACT collective kind -> count-must-be-positive sets on the CPU
#: partitioner, 8 devices. A new kind appearing (or one vanishing) in any
#: strategy's compiled step is a layout change that must be reviewed.
CPU_KIND_SETS = {
    "dp": {"all-reduce"},
    "zero1": {"all-reduce", "all-gather", "reduce-scatter"},
    "grad_compress": {"all-reduce", "all-gather", "collective-permute"},
    "sp": {"all-reduce", "collective-permute"},
    "fsdp": {"all-reduce", "all-gather"},
    "pp": {"all-reduce", "collective-permute"},
    "ep": {"all-reduce", "all-gather"},  # CPU partitioner: dispatch via
    #                                      gathers (TPU emits all-to-all,
    #                                      see benchmarks/aot_v5e.json)
}


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_strategy_fingerprint(anatomies, strategy):
    fp = check_fingerprint(anatomies[strategy])
    assert fp["ok"], (
        f"{strategy}: missing={fp['missing']} "
        f"unexpected={fp['unexpected']}"
    )


@pytest.mark.parametrize("strategy", sorted(CPU_KIND_SETS))
def test_exact_collective_kinds(anatomies, strategy):
    kinds = set(anatomies[strategy].collective_kinds())
    assert kinds == CPU_KIND_SETS[strategy], (
        f"{strategy}: compiled collective set changed: {sorted(kinds)} "
        f"(pinned: {sorted(CPU_KIND_SETS[strategy])}) — a parallelism "
        "layout change; re-pin deliberately if intended"
    )


def test_tp_family_superset(anatomies):
    # GSPMD keeps partitioner freedom here (resharding permutes /
    # all-to-alls may come and go): assert the load-bearing core only
    assert {"all-reduce"} <= set(anatomies["tp"].collective_kinds())
    assert {"all-reduce", "all-gather"} <= set(
        anatomies["fsdp_tp"].collective_kinds())


def test_dp_all_reduce_only(anatomies):
    a = anatomies["dp"]
    assert set(a.collective_kinds()) == {"all-reduce"}
    (c,) = [c for c in a.collectives if c.kind == "all-reduce"]
    assert c.dtype == "f32" and c.axis == "data" and c.count >= 1
    assert c.group_size == 8


def test_zero1_reduce_scatter_plus_gather(anatomies):
    a = anatomies["zero1"]
    by_kind = {c.kind: c for c in a.collectives if c.dtype == "f32"}
    rs, ag = by_kind["reduce-scatter"], by_kind["all-gather"]
    assert rs.axis == "data" and ag.axis == "data"
    # the grads scatter down and the params gather back: same update
    # space, so the full payloads match
    assert rs.payload_bytes == ag.payload_bytes > 0


def test_int8_compress_s8_permutes(anatomies):
    a = anatomies["grad_compress"]
    s8 = [c for c in a.collectives
          if c.kind == "collective-permute" and c.dtype == "s8"]
    assert s8, "int8 ring lost its s8 collective-permutes"
    (s8,) = s8
    assert s8.axis == "data"
    # n-1 hops per ring position, 8 devices -> multiples of 7
    assert s8.count % 7 == 0
    # the f32 permutes are the block scales: ~1/block the payload
    f32 = [c for c in a.collectives
           if c.kind == "collective-permute" and c.dtype == "f32"]
    assert f32 and f32[0].payload_bytes < s8.payload_bytes


def test_grad_compress_bf16_fingerprint():
    """bf16 is a supported compress mode: a bf16 run must NOT fail the
    net for lacking s8 payloads — it gets the ring-schedule fingerprint
    (XLA:CPU legalizes bf16 arrays to f32, so the wire dtype itself is
    not portably pinnable; on TPU bench compare pins it)."""
    a = anatomy_for_strategy("grad_compress", compress_mode="bf16")
    fp = check_fingerprint(a, "grad_compress_bf16")
    assert fp["ok"], fp
    assert any(c.kind == "collective-permute" for c in a.collectives)


def test_run_strategy_label_bf16_mode():
    from tpu_ddp.analysis.explain import run_strategy_label

    assert run_strategy_label(
        _meta({"grad_compress": "bf16"})) == "grad_compress_bf16"


def test_sp_rotates_sequence_axis(anatomies):
    a = anatomies["sp"]
    perms = [c for c in a.collectives if c.kind == "collective-permute"]
    assert perms and all(c.axis == "sequence" for c in perms)
    ar_axes = {c.axis for c in a.collectives if c.kind == "all-reduce"}
    assert "data" in ar_axes and "sequence" in ar_axes


def test_anatomy_figures_populated(anatomies):
    for strategy, a in anatomies.items():
        assert a.flops and a.flops > 0, strategy
        assert a.bytes_accessed and a.bytes_accessed > 0, strategy
        assert a.argument_bytes and a.argument_bytes > 0, strategy
        assert a.fusion_count > 0, strategy
        from tpu_ddp.analysis.hlo import ANATOMY_SCHEMA_VERSION

        assert a.schema_version == ANATOMY_SCHEMA_VERSION


def test_anatomy_json_round_trip(anatomies):
    a = anatomies["zero1"]
    rec = json.loads(json.dumps(a.to_json()))
    back = StepAnatomy.from_json(rec)
    assert back.flops == a.flops
    assert back.inventory() == a.inventory()
    assert back.program_order == a.program_order
    # a v1 record (pre-program_order) still loads, order defaults empty
    v1 = {k: v for k, v in rec.items() if k != "program_order"}
    assert StepAnatomy.from_json({**v1, "schema_version": 1}
                                 ).program_order == []
    with pytest.raises(ValueError, match="newer"):
        StepAnatomy.from_json({**rec, "schema_version": 99})


def test_compile_cache_hits(anatomies):
    before = compile_cache_stats()
    again = anatomy_for_strategy("dp")  # same key as the fixture's
    after = compile_cache_stats()
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]
    assert again.inventory() == anatomies["dp"].inventory()


# -- extraction unit tests ------------------------------------------------

def test_extract_collectives_parses_forms():
    hlo = "\n".join([
        "%ar = f32[128,64]{1,0} all-reduce(f32[128,64]{1,0} %p), "
        "channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, "
        "use_global_device_ids=true, to_apply=%add",
        "%ag = f32[128,64]{1,0} all-gather(f32[16,64]{1,0} %rs), "
        "channel_id=2, replica_groups=[1,8]<=[8], dimensions={0}",
        "%cp = s8[64]{0} collective-permute(s8[64]{0} %q), channel_id=3, "
        "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}",
        "%done = f32[8]{0} all-reduce-done(f32[8]{0} %start)",  # skipped
    ])
    mesh = {"data": 8}
    got = {c.kind: c for c in extract_collectives(hlo, mesh)}
    assert set(got) == {"all-reduce", "all-gather", "collective-permute"}
    ar = got["all-reduce"]
    assert (ar.dtype, ar.axis, ar.payload_bytes) == ("f32", "data",
                                                     128 * 64 * 4)
    # ring model: 2(g-1)/g for all-reduce
    assert ar.wire_bytes == int(2 * 7 / 8 * 128 * 64 * 4)
    ag = got["all-gather"]
    # operand is the shard; payload is the gathered tensor (x8)
    assert ag.payload_bytes == 16 * 64 * 4 * 8
    assert ag.group_size == 8  # iota replica_groups form
    cp = got["collective-permute"]
    assert cp.dtype == "s8" and cp.payload_bytes == 64
    assert cp.wire_bytes == 64  # permute moves its payload once


def test_extract_collectives_axis_attribution_2d():
    # data=2 x model=4, row-major ids: model groups are consecutive,
    # data groups strided
    hlo = "\n".join([
        "%a = f32[8]{0} all-reduce(f32[8]{0} %p), "
        "replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add",
        "%b = f32[8]{0} all-reduce(f32[8]{0} %q), "
        "replica_groups={{0,4},{1,5},{2,6},{3,7}}, to_apply=%add",
        "%c = f32[8]{0} all-reduce(f32[8]{0} %r), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add",
    ])
    mesh = {"data": 2, "model": 4}
    axes = sorted((c.axis, c.count) for c in extract_collectives(hlo, mesh))
    assert axes == [("all", 1), ("data", 1), ("model", 1)]


# -- roofline arithmetic on a hand-computable toy -------------------------

def _toy_anatomy(**overrides):
    base = dict(
        strategy="dp", model="toy", device_kind="TPU v5 lite",
        mesh={"data": 8}, n_devices=8, per_shard_batch=8,
        compute_dtype="bfloat16",
        flops=197e12 * 1e-3,          # exactly 1 ms of v5e MXU
        bytes_accessed=8.1e11 * 5e-4,  # exactly 0.5 ms of v5e HBM
        argument_bytes=1 << 20, output_bytes=1 << 20, temp_bytes=2 << 20,
        generated_code_bytes=None, fusion_count=3, hlo_ops={},
        collectives=[Collective(
            kind="all-reduce", dtype="f32", axis="data", count=1,
            group_size=8,
            payload_bytes=45_000_000,
            # ring wire: 2 * 7/8 * payload; at 4.5e10 B/s -> 1.75 ms
            wire_bytes=int(2 * 7 / 8 * 45_000_000),
        )],
    )
    base.update(overrides)
    return StepAnatomy(**base)


def test_roofline_toy_arithmetic():
    a = _toy_anatomy()
    rl = roofline(a)  # spec resolved from device_kind "TPU v5 lite"
    assert rl.chip == "v5e"
    assert rl.compute_s == pytest.approx(1e-3)
    assert rl.hbm_s == pytest.approx(0.5e-3)
    assert rl.ici_s == pytest.approx(
        2 * 7 / 8 * 45_000_000 / 4.5e10, rel=1e-6)
    assert rl.bound == "ici"
    assert rl.predicted_step_s == pytest.approx(rl.ici_s)
    serial = roofline(a, overlap="serial")
    assert serial.predicted_step_s == pytest.approx(
        rl.compute_s + rl.hbm_s + rl.ici_s)
    fr = rl.fractions()
    assert sum(fr.values()) == pytest.approx(1.0)


def test_roofline_compute_bound_and_override():
    a = _toy_anatomy(collectives=[], bytes_accessed=8.1e11 * 1e-5)
    rl = roofline(a)
    assert rl.bound == "compute" and rl.ici_s == 0.0
    # chip override: same program attributed on v5p halves compute time
    rl_p = roofline(a, "v5p")
    assert rl_p.compute_s == pytest.approx(197e12 * 1e-3 / 459e12)


def test_roofline_cpu_has_no_peak():
    a = _toy_anatomy(device_kind="cpu")
    rl = roofline(a)
    assert rl.bound == "unknown" and rl.predicted_step_s is None
    assert any("no published peak" in n for n in rl.notes)
    # ... but an explicit chip classifies
    assert roofline(a, "v5e").bound == "ici"


def test_chip_spec_patterns():
    assert chip_spec("TPU v5 lite").key == "v5e"
    assert chip_spec("TPU v5p").key == "v5p"
    # the regression the merge fixed: bare "TPU v5" is v5p, and must NOT
    # fall through to None (the old mfu table had no pattern for it)
    assert chip_spec("TPU v5").key == "v5p"
    assert chip_spec("TPU v4").key == "v4"
    assert chip_spec("cpu").key == "cpu"
    assert chip_spec("TPU v6 lite").key == "v6e"
    assert chip_spec("warp drive") is None
    assert CHIP_SPECS["v5e"].peak_bf16_flops == 197e12


def test_mfu_reexports_shared_peaks():
    from tpu_ddp.metrics.mfu import peak_flops_per_chip as mfu_peak

    from tpu_ddp.analysis.roofline import peak_flops_per_chip

    assert mfu_peak is peak_flops_per_chip


# -- bench compare gate, both directions ----------------------------------

def _program(**overrides):
    rec = {
        "ok": True, "compile_wall_s": 10.0,
        "argument_size_in_bytes": 1000_000,
        "temp_size_in_bytes": 2_000_000,
        "hlo_ops": {"all-reduce": 2, "fusion": 100},
        "inventory": {
            "all-reduce/f32/data": {"count": 2, "payload_bytes": 500_000,
                                    "wire_bytes": 875_000, "group_size": 8},
        },
    }
    rec.update(overrides)
    return rec


def test_compare_clean_pass(tmp_path):
    from tpu_ddp.analysis.regress import compare

    old = {"prog": _program()}
    result = compare(old, {"prog": _program()})
    assert not result["regressions"]


def test_compare_flags_extra_collective():
    from tpu_ddp.analysis.regress import compare

    new = _program()
    new["hlo_ops"] = {"all-reduce": 2, "fusion": 100, "all-gather": 1}
    new["inventory"] = dict(
        _program()["inventory"],
        **{"all-gather/f32/data": {"count": 1, "payload_bytes": 1,
                                   "wire_bytes": 1, "group_size": 8}},
    )
    result = compare({"prog": _program()}, {"prog": new})
    assert any("all-gather" in r for r in result["regressions"])


def test_compare_flags_widened_dtype():
    from tpu_ddp.analysis.regress import compare

    # the int8 ring degrading to f32: s8 entry gone, f32 entry appears
    old = {"prog": _program(inventory={
        "collective-permute/s8/data": {"count": 7, "payload_bytes": 7000,
                                       "wire_bytes": 7000, "group_size": 8},
    })}
    new = {"prog": _program(inventory={
        "collective-permute/f32/data": {"count": 7, "payload_bytes": 28000,
                                        "wire_bytes": 28000,
                                        "group_size": 8},
    })}
    result = compare(old, new)
    assert any("collective-permute/f32" in r for r in result["regressions"])


def test_compare_tolerance_both_ways():
    from tpu_ddp.analysis.regress import compare

    grown = {"prog": _program(temp_size_in_bytes=2_060_000)}   # +3%
    blown = {"prog": _program(temp_size_in_bytes=2_400_000)}   # +20%
    base = {"prog": _program()}
    assert not compare(base, grown, tolerance=0.05)["regressions"]
    bad = compare(base, blown, tolerance=0.05)["regressions"]
    assert any("temp_size_in_bytes" in r for r in bad)
    # shrink is an improvement, not a regression
    result = compare(blown, base, tolerance=0.05)
    assert not result["regressions"]
    assert any("temp_size_in_bytes" in s for s in result["improvements"])


def test_compare_lost_inventory_fails_closed():
    """A fresh capture whose inventory VANISHED (extraction broke) must
    fail the gate — not read every baseline entry as an improvement."""
    from tpu_ddp.analysis.regress import compare

    new = _program()
    del new["inventory"]
    result = compare({"prog": _program()}, {"prog": new})
    assert any("inventory missing" in r for r in result["regressions"])
    assert not any("gone" in s for s in result["improvements"])


def test_analyze_all_json_is_multi_program(tmp_path, anatomies):
    """--strategy all --json must write ONE programs-table artifact
    covering every strategy (not overwrite per strategy), and it must
    self-compare clean."""
    from tpu_ddp.analysis.explain import main as analyze_main
    from tpu_ddp.analysis.regress import compare, load_artifact

    out = tmp_path / "all.json"
    rc = analyze_main(["--strategy", "all", "--json", str(out)])
    assert rc == 0
    art = load_artifact(str(out))
    assert set(art) == set(STRATEGIES)
    assert all("inventory" in rec for rec in art.values())
    assert not compare(art, art)["regressions"]


def test_compare_zero_baseline_size_no_crash():
    """A zero-valued sized baseline (e.g. wire_bytes 0 from unparsed
    groups) must report, not ZeroDivisionError."""
    from tpu_ddp.analysis.regress import compare

    old = {"prog": _program(inventory={
        "all-reduce/f32/data/g8": {"count": 2, "wire_bytes": 0},
    })}
    new = {"prog": _program(inventory={
        "all-reduce/f32/data/g8": {"count": 2, "wire_bytes": 1 << 20},
    })}
    result = compare(old, new)
    assert any("from 0" in r for r in result["regressions"])


def test_compare_fusion_count_tolerated_not_exact():
    """Fusion/conv/custom-call counts are compiler decisions: small
    jitter passes at tolerance, big growth still gates."""
    from tpu_ddp.analysis.regress import compare

    base = {"prog": _program(fusion_count=166)}
    jitter = {"prog": _program(fusion_count=170)}        # +2.4%
    blown = {"prog": _program(fusion_count=300)}         # +81%
    assert not compare(base, jitter, tolerance=0.1)["regressions"]
    assert any("fusion_count" in r
               for r in compare(base, blown, tolerance=0.1)["regressions"])
    # ... but collective opcode counts stay exact even at high tolerance
    extra = _program()
    extra["hlo_ops"] = dict(extra["hlo_ops"], **{"all-reduce": 3})
    assert compare(base, {"prog": extra}, tolerance=0.5)["regressions"]


def test_compare_missing_program_and_break():
    from tpu_ddp.analysis.regress import compare

    base = {"a": _program(), "b": _program()}
    gone = compare(base, {"a": _program()})
    assert any("missing" in r for r in gone["regressions"])
    broke = compare(base, {"a": _program(ok=False, error="boom"),
                           "b": _program()})
    assert any("compile broke" in r for r in broke["regressions"])
    # a NEW program whose compile is broken must gate too, not slide in
    # as an informational "no baseline" note
    fresh_broken = compare(base, {**base, "c": _program(ok=False,
                                                       error="boom")})
    assert any("compile is broken" in r
               for r in fresh_broken["regressions"])
    fresh_ok = compare(base, {**base, "c": _program()})
    assert not fresh_ok["regressions"]


def test_anatomy_cache_distinguishes_custom_models(devices):
    """Two different explicitly-passed models must not share a cached
    anatomy (the key includes the model's repr, not just its name)."""
    import jax.numpy as jnp

    from tpu_ddp.models import NetResDeep

    a = anatomy_for_strategy("dp", model=NetResDeep(
        n_chans1=8, n_blocks=2, num_classes=10, dtype=jnp.float32))
    b = anatomy_for_strategy("dp", model=NetResDeep(
        n_chans1=16, n_blocks=4, num_classes=10, dtype=jnp.float32))
    assert b.flops > a.flops


def test_compare_cli_exit_codes(tmp_path):
    from tpu_ddp.analysis.regress import main as compare_main

    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps({"programs": {"p": _program()}}))
    new.write_text(json.dumps({"programs": {"p": _program()}}))
    assert compare_main([str(old), str(new)]) == 0
    poisoned = _program()
    poisoned["hlo_ops"] = dict(poisoned["hlo_ops"], **{"all-gather": 3})
    new.write_text(json.dumps({"programs": {"p": poisoned}}))
    assert compare_main([str(old), str(new)]) == 1
    assert compare_main([str(old), str(tmp_path / "nope.json")]) == 2


def test_inventory_key_includes_group_size():
    """Two buckets differing only in group size (fsdp_tp all-gathers over
    model AND data with no mesh attribution) must not shadow each other
    in the inventory dict the compare gate diffs."""
    hlo = "\n".join([
        "%a = f32[128]{0} all-gather(f32[32]{0} %p), "
        "replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}",
        "%b = f32[64]{0} all-gather(f32[32]{0} %q), "
        "replica_groups={{0,4},{1,5},{2,6},{3,7}}, dimensions={0}",
    ])
    cs = extract_collectives(hlo)  # no mesh: both axes read "unknown"
    keys = {c.key() for c in cs}
    assert keys == {"all-gather/f32/unknown/g4", "all-gather/f32/unknown/g2"}


def test_compare_pre_inventory_baseline_not_gated():
    """A baseline without inventories (the committed pre-inventory
    aot_v5e.json) must not read a fresh capture's inventory as 0 -> N
    regressions — noted, then gated from the first inventoried artifact."""
    from tpu_ddp.analysis.regress import compare

    old = _program()
    del old["inventory"]
    result = compare({"prog": old}, {"prog": _program()})
    assert not result["regressions"]
    assert any("pre-inventory" in n for n in result["notes"])


def test_compare_reads_committed_aot_artifact():
    """The committed AOT artifact (pre-inventory schema) must normalize
    and self-compare clean — the CI gate's baseline format."""
    import os

    from tpu_ddp.analysis.regress import load_artifact, compare

    art = load_artifact(os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "aot_v5e.json"))
    assert "dp_netresdeep_b32x8" in art
    assert not compare(art, art)["regressions"]


# -- run-metadata header + telemetry join ---------------------------------

def _write_trace(tmp_path, run_meta, spans):
    trace = tmp_path / "trace-p0.jsonl"
    header = {"schema_version": 1, "type": "header", "epoch_unix": 0.0,
              "pid": 0}
    if run_meta is not None:
        header["run_meta"] = run_meta
    records = [header]
    t = 0.0
    for name, dur, attrs in spans:
        records.append({
            "schema_version": 1, "type": "span", "name": name,
            "ts_s": t, "dur_s": dur, "pid": 0, "tid": 1, "depth": 0,
            **({"attrs": attrs} if attrs else {}),
        })
        t += dur
    trace.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    return tmp_path


def test_run_meta_header_round_trip(tmp_path):
    from tpu_ddp.telemetry import build_telemetry

    meta = {"run_meta_schema_version": 1, "strategy": "dp",
            "config": {"model": "netresdeep"}, "device_kind": "cpu",
            "mesh": {"data": 8}, "n_devices": 8, "jax_version": "0.0"}
    tel = build_telemetry(str(tmp_path), "jsonl,chrome", run_meta=meta)
    with tel.span("compiled_step"):
        pass
    tel.close()
    assert read_run_meta(str(tmp_path)) == meta
    # the chrome trace carries it as a metadata record too
    chrome = json.loads((tmp_path / "trace-p0.trace.json").read_text())
    metas = [e for e in chrome["traceEvents"] if e.get("name") == "run_meta"]
    assert metas and metas[0]["args"]["strategy"] == "dp"
    # and trace summarize labels the run
    from tpu_ddp.telemetry.summarize import summarize

    out = summarize(str(tmp_path))
    assert "strategy=dp" in out and "model=netresdeep" in out


def test_run_meta_refusals(tmp_path):
    _write_trace(tmp_path, None, [("compiled_step", 0.1, None)])
    with pytest.raises(ValueError, match="no run-metadata header"):
        read_run_meta(str(tmp_path))


def test_run_meta_future_schema_refused(tmp_path):
    _write_trace(tmp_path, {"run_meta_schema_version": 99},
                 [("compiled_step", 0.1, None)])
    with pytest.raises(ValueError, match="newer"):
        read_run_meta(str(tmp_path))


def test_trainer_writes_run_meta(tmp_path, devices):
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    config = TrainConfig(
        synthetic_data=True, synthetic_size=64, epochs=1,
        per_shard_batch=8, model="netresdeep", n_chans1=8, n_blocks=2,
        prefetch_depth=0, log_every_epochs=1,
        telemetry_dir=str(tmp_path),
    )
    trainer = Trainer(config)
    trainer.run()
    meta = read_run_meta(str(tmp_path))
    assert meta["strategy"] == "dp"
    assert meta["config"]["model"] == "netresdeep"
    assert meta["mesh"]["data"] == 8
    assert meta["device_kind"] == jax.devices()[0].device_kind
    assert meta["run_meta_schema_version"] == 1


def test_join_with_synthetic_telemetry(tmp_path, anatomies):
    from tpu_ddp.analysis.explain import join_measurements

    a = anatomies["dp"]
    rl = roofline(a, "v5e")
    # 10 steady steps of 2 ms each (one scan-fused span of 4 steps among
    # them exercises the per-step normalization), plus host phases
    spans = [("data_wait", 0.001, None), ("h2d", 0.0005, None)]
    spans += [("compiled_step", 0.002, None)] * 8
    spans += [("compiled_step", 0.008, {"steps": 4})]
    _write_trace(tmp_path, {"run_meta_schema_version": 1}, spans)
    joined = join_measurements(a, rl, str(tmp_path), chip="v5e")
    assert joined["step_p50_s"] == pytest.approx(0.002)
    assert joined["roofline_fraction"] == pytest.approx(
        rl.predicted_step_s / 0.002)
    assert 0 < joined["mfu"] < 1
    assert joined["mfu"] == pytest.approx(a.flops / 0.002 / 197e12)
    assert 0 < joined["data_wait_share"] < 0.1


def _meta(config_overrides=None, strategy="dp", mesh=None):
    config = {"model": "netresdeep", "n_chans1": 8, "n_blocks": 2,
              "per_shard_batch": 8}
    config.update(config_overrides or {})
    return {"run_meta_schema_version": 1, "strategy": strategy,
            "config": config, "mesh": mesh or {"data": 8}, "n_devices": 8}


def test_run_meta_rebuild_honors_config(anatomies, devices):
    """Run-dir rebuild must compile the run's ACTUAL model/optimizer from
    the config snapshot — not a default-shaped stand-in (the default
    NetResDeep is ~10x the demo's 8-chan/2-block one)."""
    from tpu_ddp.analysis.explain import anatomy_for_run_meta

    big = anatomy_for_run_meta(
        _meta({"n_chans1": 16, "n_blocks": 4}), jax.devices())
    # the dp fixture compiled the same tiny 8-chan/2-block NetResDeep:
    # a recorded 16-chan/4-block run must rebuild strictly larger
    assert big.flops > anatomies["dp"].flops
    assert big.strategy == "dp" and big.model == "netresdeep"


def test_run_meta_rebuild_composed_zero1_compress(devices):
    """--zero1 --grad-compress runs compose BOTH layouts in the rebuild
    (the s8 ring inside zero1's scatter/gather), under the grad_compress
    label/fingerprint."""
    from tpu_ddp.analysis.explain import (
        anatomy_for_run_meta,
        run_strategy_label,
    )

    meta = _meta({"zero1": True, "grad_compress": "int8"})
    assert run_strategy_label(meta) == "grad_compress"
    a = anatomy_for_run_meta(meta, jax.devices())
    kinds = set(a.collective_kinds())
    s8 = [c for c in a.collectives
          if c.kind == "collective-permute" and c.dtype == "s8"]
    assert s8, "composed rebuild lost the int8 ring"
    assert "all-gather" in kinds, "composed rebuild lost zero1's gather"
    assert check_fingerprint(a)["ok"]


def test_run_meta_rebuild_refuses_composed_sp(devices):
    from tpu_ddp.analysis.explain import anatomy_for_run_meta

    meta = _meta({"zero1": True}, strategy="sp",
                 mesh={"data": 4, "sequence": 2})
    with pytest.raises(ValueError, match="sp"):
        anatomy_for_run_meta(meta, jax.devices())


def test_run_meta_rebuild_mirrors_schedule_and_optimizer(devices):
    """--schedule/--warmup-steps/--optimizer change the opt_state tree:
    the rebuild must carry them without falling over."""
    from tpu_ddp.analysis.explain import anatomy_for_run_meta

    a = anatomy_for_run_meta(
        _meta({"schedule": "cosine", "warmup_steps": 5,
               "optimizer": "adamw"}), jax.devices())
    assert a.flops and a.flops > 0
    assert check_fingerprint(a)["ok"]


def test_run_meta_rebuild_refuses_scan_fused(devices):
    from tpu_ddp.analysis.explain import anatomy_for_run_meta

    with pytest.raises(ValueError, match="steps_per_call"):
        anatomy_for_run_meta(_meta({"steps_per_call": 4}), jax.devices())
    # ... but scan fusion is dp-only: the Trainer ignores the flag for
    # other families, so an fsdp run with it set rebuilds fine
    a = anatomy_for_run_meta(
        _meta({"steps_per_call": 4}, strategy="fsdp"), jax.devices())
    assert a.strategy == "fsdp" and a.flops > 0


def test_run_meta_rebuild_honors_health(devices):
    """--health on compiles the numerics flight recorder into the step:
    the rebuild must carry it, or every health-enabled run mis-attributes.

    Pinned on the rebuilt program's OUTPUTS — ``metrics["health"]`` with
    the recorder's norms and sentinels, absent when off — not on a
    collective count: plain DP's grads are already synchronized when the
    stats read them, and XLA folds what psums there are into the gradient
    all-reduce (one op on jax 0.9.0, health on or off)."""
    from tpu_ddp.analysis.explain import (
        anatomy_for_run_meta,
        compiled_for_run_meta,
    )
    from tpu_ddp.analysis.hlo import compile_cache_stats

    def metrics_of(meta):
        tree = compiled_for_run_meta(meta, jax.devices()).out_tree
        _, metrics = tree.unflatten(list(range(tree.num_leaves)))
        return metrics

    on_meta = _meta({"health": "on"})
    assert "health" not in metrics_of(_meta())
    assert {"grad_norm", "param_norm", "update_norm", "update_ratio",
            "grads_finite", "updates_finite", "all_finite"} <= set(
                metrics_of(on_meta)["health"])
    # the anatomy a health-enabled run is attributed against is of that
    # same compiled program: a cache hit, not a second (health-less) build
    misses = compile_cache_stats()["misses"]
    assert anatomy_for_run_meta(on_meta, jax.devices()).flops > 0
    assert compile_cache_stats()["misses"] == misses


def test_run_strategy_label():
    from tpu_ddp.analysis.explain import run_strategy_label

    assert run_strategy_label(_meta()) == "dp"
    assert run_strategy_label(_meta({"zero1": True})) == "zero1"
    assert run_strategy_label(
        _meta({"zero1": True, "grad_compress": "int8"})) == "grad_compress"
    # non-dp families keep their own label; composition is a build error
    assert run_strategy_label(_meta({"zero1": True}, strategy="sp")) == "sp"


def test_analyze_refuses_mismatched_strategy(tmp_path):
    """run-dir mode must refuse when --strategy contradicts the header."""
    from tpu_ddp.analysis.explain import main as analyze_main

    meta = {"run_meta_schema_version": 1, "strategy": "dp",
            "config": {"model": "netresdeep", "per_shard_batch": 8},
            "mesh": {"data": 8}, "n_devices": 8}
    _write_trace(tmp_path, meta, [("compiled_step", 0.002, None)])
    rc = analyze_main([str(tmp_path), "--strategy", "fsdp"])
    assert rc == 2
