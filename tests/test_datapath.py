"""Data-path observatory: staged-pipeline attribution, the
batch-provenance determinism audit, loader microbenchmarks, DAT001, and
the tuner's input-bound floor (docs/data.md).

All CPU-only; the fast tier runs no Trainer compile (the end-to-end
staged run lives in the slow tier).
"""

import json
import os

import numpy as np
import pytest

from tpu_ddp.data.loader import ShardedBatchLoader
from tpu_ddp.datapath.audit import (
    DataDigestWriter,
    audit_digests,
    batch_digest,
    format_audit,
    read_digest_files,
    xor_hex,
)
from tpu_ddp.datapath.model import (
    DataModel,
    data_model_from_sources,
    stage_baselines,
)
from tpu_ddp.datapath.prefetch import BackgroundPrefetcher
from tpu_ddp.datapath.stages import (
    HOST_STAGES,
    STAGES,
    StageMonitor,
    data_health_file,
    read_data_health,
    suspect_stage_from_files,
)


class _Gauges:
    """Duck-typed telemetry stub: records every gauge set."""

    def __init__(self):
        self.values = {}

    def gauge(self, name):
        values = self.values

        class _G:
            def set(self, v, _n=name):
                values[_n] = v

        return _G()


def _samples(n, *, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.random((n, 4, 4, 3), dtype=np.float32)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    return images, labels


# -- stage vocabulary ------------------------------------------------------


def test_stage_vocabulary_order():
    assert STAGES == ("index", "gather", "augment", "collate", "shard",
                      "h2d")
    assert HOST_STAGES == STAGES[:-1]


# -- batch digest: order + partition invariance ----------------------------


def test_batch_digest_is_order_and_partition_invariant():
    images, labels = _samples(8)
    mask = np.ones(8, dtype=bool)
    whole, n = batch_digest(images, labels, mask)
    assert n == 8
    # order within the step must not matter (XOR is commutative)
    perm = np.random.default_rng(1).permutation(8)
    shuffled, _ = batch_digest(images[perm], labels[perm], mask)
    assert shuffled == whole
    # any host split of the same global sample set XORs back to the
    # global digest — the 8->4 re-mesh invariance the audit rests on
    a, _ = batch_digest(images[:3], labels[:3], mask[:3])
    b, _ = batch_digest(images[3:], labels[3:], mask[3:])
    assert xor_hex(a, b) == whole
    # mask-false rows (wrap pad) are not part of the content
    masked = mask.copy()
    masked[5] = False
    d1, n1 = batch_digest(images, labels, masked)
    other = images.copy()
    other[5] += 1.0  # only the padded row differs
    d2, n2 = batch_digest(other, labels, masked)
    assert d1 == d2 and n1 == n2 == 7
    # the digest is keyed: a different seed is a different family
    keyed, _ = batch_digest(images, labels, mask, seed=7)
    assert keyed != whole


# -- digest sinks + audit --------------------------------------------------


def _write_digests(run_dir, incarnation, steps, *, process_index=0,
                   seed=0, mutate=None):
    """One incarnation's sink: the loader's deterministic batches for
    the given global steps, optionally mutated at one step."""
    images, labels = _samples(64)
    loader = ShardedBatchLoader(images, labels, world_size=1,
                                per_shard_batch=8, shuffle=True, seed=3)
    w = DataDigestWriter(run_dir, process_index=process_index,
                         incarnation=incarnation, seed=seed)
    batches = list(loader.epoch_batches(0))
    for step in steps:
        batch = batches[step % len(batches)]
        if mutate is not None and step == mutate:
            batch = dict(batch)
            batch["image"] = batch["image"] + 1.0
        w.record(step, batch)
    w.close()


def test_digest_writer_names_and_reader(tmp_path):
    run = str(tmp_path)
    _write_digests(run, 0, range(4))
    _write_digests(run, 1, range(2, 6))
    assert os.path.exists(os.path.join(run, "data-p0.jsonl"))
    assert os.path.exists(os.path.join(run, "data-p0.i1.jsonl"))
    files = read_digest_files(run)
    assert sorted((f["incarnation"], sorted(f["steps"]))
                  for f in files) == [
        (0, [0, 1, 2, 3]), (1, [2, 3, 4, 5])]
    header = files[-1]["header"]
    assert header["seed"] == 0 and header["process_index"] == 0


def test_audit_passes_kill_resume_replay(tmp_path):
    # elastic-style fixture: incarnation 0 dies after step 3, the
    # resume replays steps 2..5 — the overlap must digest identically
    run = str(tmp_path)
    _write_digests(run, 0, range(4))
    _write_digests(run, 1, range(2, 6))
    verdict = audit_digests(run)
    assert verdict["ok"] is True
    (pair,) = verdict["pairs"]
    assert pair["incarnations"] == (0, 1) and pair["overlap"] == 2
    assert "PASS" in format_audit(verdict)


def test_audit_names_first_diverging_step(tmp_path):
    run = str(tmp_path)
    _write_digests(run, 0, range(6))
    _write_digests(run, 1, range(2, 8), mutate=4)
    verdict = audit_digests(run)
    assert verdict["ok"] is False
    (pair,) = verdict["pairs"]
    assert pair["first_diverging_step"] == 4
    text = format_audit(verdict)
    assert "FAIL at step 4" in text and "same batches" in text


def test_audit_remesh_partition_invariance(tmp_path):
    # held global batch, 4 hosts -> 2 hosts: per-host digests XOR-merge
    # to the same per-step global digest in both incarnations
    run = str(tmp_path)
    images, labels = _samples(32)
    mask = np.ones(8, dtype=bool)
    for inc, n_hosts in ((0, 4), (1, 2)):
        per_host = 8 // n_hosts
        for pid in range(n_hosts):
            w = DataDigestWriter(run, process_index=pid,
                                 incarnation=inc)
            for step in range(4):
                rows = slice(step * 8 + pid * per_host,
                             step * 8 + (pid + 1) * per_host)
                d, n = batch_digest(images[rows], labels[rows],
                                    mask[:per_host])
                w.record_digest(step, d, n)
            w.close()
    verdict = audit_digests(run)
    assert verdict["ok"] is True and verdict["steps_compared"] == 4


def test_audit_refuses_seed_mismatch_and_empty_dir(tmp_path):
    from tpu_ddp.datapath.cli import main as data_main

    assert audit_digests(str(tmp_path))["ok"] is None
    assert data_main(["audit", str(tmp_path)]) == 2
    _write_digests(str(tmp_path), 0, range(3), seed=0)
    _write_digests(str(tmp_path), 1, range(3), seed=1)
    verdict = audit_digests(str(tmp_path))
    assert verdict["ok"] is False and "seed" in verdict["error"]
    assert data_main(["audit", str(tmp_path)]) == 1


# -- background prefetcher: parity + queue counters ------------------------


def test_prefetcher_bit_parity_across_epoch_reshuffles():
    images, labels = _samples(64)

    def loader():
        return ShardedBatchLoader(images, labels, world_size=1,
                                  per_shard_batch=8, shuffle=True,
                                  seed=5)

    def digests_sync():
        ld = loader()
        out = []
        for epoch in (0, 1):  # set_epoch reshuffle between epochs
            ld.set_epoch(epoch)
            for batch in ld.epoch_batches(epoch):
                out.append(batch_digest(batch["image"], batch["label"],
                                        batch["mask"])[0])
        return out

    def digests_prefetched():
        ld = loader()
        out = []
        for epoch in (0, 1):
            ld.set_epoch(epoch)
            pf = BackgroundPrefetcher(
                lambda e=epoch: ld.epoch_batches(e), depth=3)
            try:
                for batch in pf:
                    out.append(batch_digest(
                        batch["image"], batch["label"],
                        batch["mask"])[0])
            finally:
                pf.close()
        return out

    sync = digests_sync()
    assert len(sync) == 16
    # the prefetcher moves WHEN batches materialize, never WHAT they
    # contain: digest-for-digest equal, including across reshuffles
    assert digests_prefetched() == sync
    # and the reshuffle actually reshuffles (epoch 0 != epoch 1)
    assert sync[:8] != sync[8:]


def test_prefetcher_gauges_and_exception_forwarding():
    tel = _Gauges()
    pf = BackgroundPrefetcher(lambda: iter(range(5)), depth=2,
                              telemetry=tel)
    assert list(pf) == [0, 1, 2, 3, 4]
    pf.close()
    assert set(tel.values) == {
        "datapath/prefetch_occupancy",
        "datapath/prefetch_put_wait_total_s",
        "datapath/prefetch_get_wait_total_s",
    }

    def boom():
        yield 1
        raise RuntimeError("loader died")

    pf = BackgroundPrefetcher(boom, depth=2)
    it = iter(pf)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="loader died"):
        next(it)
    pf.close()
    with pytest.raises(ValueError, match="depth"):
        BackgroundPrefetcher(lambda: iter(()), depth=0)


# -- StageMonitor health file ----------------------------------------------


def test_stage_monitor_health_and_stall_hook_order(tmp_path):
    seen = []

    def hook(stage):
        # the in-flight marker must ALREADY be on disk when chaos runs,
        # so a stall that wedges here is named while it is stuck
        rec = read_data_health(data_health_file(str(tmp_path)))
        seen.append((stage, (rec.get("in_flight") or {}).get("stage")))

    mon = StageMonitor(str(tmp_path), stall_hook=hook,
                       min_write_interval_s=0.0)
    mon.on_step(7)
    mon.stage_enter("gather")
    mon.stage_exit("gather", 0.01, 1024)
    mon.stage_enter("augment")  # never exits: left wedged
    assert seen == [("gather", "gather"), ("augment", "augment")]
    rec = read_data_health(data_health_file(str(tmp_path)))
    assert rec["data_health_schema_version"] == 1
    assert rec["step"] == 7
    assert rec["stages"]["gather"]["batches_window"] == 1
    assert rec["stages"]["gather"]["bytes_window"] == 1024
    suspect = suspect_stage_from_files(str(tmp_path))
    assert suspect["stage"] == "augment"
    assert suspect["source"] == "in_flight"
    mon.stage_exit("augment", 0.5, 10)
    mon.stage_exit("gather", 0.01, 1024)
    mon.close()
    # nothing in flight: fall back to the slowest windowed stage
    suspect = suspect_stage_from_files(str(tmp_path))
    assert suspect["stage"] == "augment"
    assert suspect["source"] == "slowest_window"
    # a dir with no health files is an honest None
    assert suspect_stage_from_files(str(tmp_path / "nope")) is None


def test_stage_monitor_gauges():
    tel = _Gauges()
    mon = StageMonitor(os.devnull + "-unused-dir", telemetry=tel,
                       min_write_interval_s=10.0)
    mon.stage_enter("shard")
    mon.stage_exit("shard", 0.002, 4096)
    assert tel.values["datapath/shard_s"] == pytest.approx(0.002)
    assert tel.values["datapath/shard_batches_per_s"] > 0


# -- microbench -> artifact -> model -> registry/regress -------------------


@pytest.fixture(scope="module")
def bench_art(tmp_path_factory):
    from tpu_ddp.datapath.microbench import bench_artifact, run_stage_bench

    stages, skipped, headline = run_stage_bench(
        n=64, per_shard_batch=16, reps=1, h2d=False)
    art = bench_artifact(stages, skipped, headline, n=64,
                         per_shard_batch=16, reps=1)
    path = tmp_path_factory.mktemp("data") / "data-bench.json"
    path.write_text(json.dumps(art))
    return art, str(path)


def test_microbench_measures_every_host_stage(bench_art):
    from tpu_ddp.datapath.microbench import format_bench

    art, _ = bench_art
    data = art["data"]
    assert art["type"] == "data" and art["data_schema_version"] == 1
    assert set(data["stages"]) == set(HOST_STAGES)
    for view in data["stages"].values():
        assert view["seconds_per_batch"] > 0
        assert view["batches_per_s"] > 0
    assert data["per_image_s"] > 0
    assert data["batch_time_s"] > 0
    assert data["dominant_stage"] in HOST_STAGES
    assert set(data["rows"]) == {f"stage/{s}" for s in HOST_STAGES}
    # h2d was disabled, not silently dropped
    assert any(s["stage"] == "h2d" for s in data["skipped"])
    text = format_bench(art)
    assert "dominant stage" in text and "gather" in text


def test_data_model_assembles_and_prices_floor(bench_art):
    art, path = bench_art
    model = data_model_from_sources([path])
    assert model  # truthy: evidence present
    assert model.per_image_s == pytest.approx(art["data"]["per_image_s"])
    assert model.dominant_stage == art["data"]["dominant_stage"]
    assert model.source == os.path.basename(path)
    # the floor is linear in images and discounted by overlap
    assert model.input_floor_s(100) == pytest.approx(
        model.per_image_s * 100)
    assert model.input_floor_s(100, overlap=4.0) == pytest.approx(
        model.per_image_s * 25)
    baselines = stage_baselines(art)
    assert set(baselines) == set(HOST_STAGES)
    # no evidence -> falsy model, no floor priced
    assert not data_model_from_sources([])
    assert not DataModel()


def test_registry_classifies_kind_data(bench_art):
    from tpu_ddp.registry.store import _artifact_kind

    art, _ = bench_art
    assert _artifact_kind(art) == "data"


def test_regress_normalizes_and_gates_stage_throughput(bench_art):
    from tpu_ddp.analysis.regress import compare, normalize_artifact

    art, _ = bench_art
    old = normalize_artifact(art)
    assert "data" in old
    assert "sweeps" not in old["data"] and "stages" not in old["data"]
    for stage in HOST_STAGES:
        assert f"data/{stage}" in old
    # self-compare is clean
    assert compare(old, normalize_artifact(art))["regressions"] == []
    # a collapsed stage rate is a regression (batches_per_s: quality)
    worse = json.loads(json.dumps(art))
    worse["data"]["stages"]["gather"]["batches_per_s"] /= 10
    res = compare(old, normalize_artifact(worse))
    assert any("data/gather" in r and "batches_per_s" in r
               for r in res["regressions"])


# -- report: the data_wait decomposition -----------------------------------


def _trace(tmp_path, spans=(), gauges=None):
    recs = [{"schema_version": 1, "type": "header", "epoch_unix": 1000.0,
             "pid": 0}]
    for name, dur in spans:
        recs.append({"schema_version": 1, "type": "span", "name": name,
                     "ts_s": 1.0, "dur_s": dur, "pid": 0})
    if gauges:
        recs.append({"schema_version": 1, "type": "counters",
                     "ts_s": 2.0, "pid": 0,
                     "attrs": {"counters": {}, "gauges": gauges}})
    (tmp_path / "trace-p0.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in recs))
    return str(tmp_path)


def test_report_sync_path_sums_to_data_wait(tmp_path):
    from tpu_ddp.datapath.cli import main as data_main
    from tpu_ddp.datapath.report import datapath_measured

    spans = []
    for _ in range(8):
        spans += [("data/index", 0.001), ("data/gather", 0.004),
                  ("data/augment", 0.002), ("data/collate", 0.001),
                  ("data/shard", 0.002), ("data_wait", 0.010),
                  ("h2d", 0.003)]
    run = _trace(tmp_path, spans)
    d = datapath_measured(run)
    assert set(d["stages"]) == set(STAGES)
    assert d["dominant_stage"] == "gather"
    # acceptance: per-stage p50s sum to the measured wait in tolerance
    assert d["stage_sum_p50_s"] == pytest.approx(0.010)
    assert d["coverage"] == pytest.approx(1.0)
    assert "gather" in d["verdict"]
    assert data_main(["report", run]) == 0
    # a run with no staged evidence is a named refusal, exit 2
    empty = tmp_path / "empty"
    empty.mkdir()
    _trace(empty, [("data_wait", 0.010)])
    assert data_main(["report", str(empty)]) == 2


def test_report_prefetch_verdicts(tmp_path):
    from tpu_ddp.datapath.report import datapath_measured

    bound = _trace(tmp_path, [("data/gather", 0.004)], gauges={
        "datapath/prefetch_occupancy": 0.1,
        "datapath/prefetch_put_wait_total_s": 0.2,
        "datapath/prefetch_get_wait_total_s": 9.0})
    d = datapath_measured(bound)
    assert d["coverage"] is None  # meaningless under the prefetcher
    assert d["verdict"].startswith("input-bound")
    assert "gather" in d["verdict"]
    fed = tmp_path / "fed"
    fed.mkdir()
    _trace(fed, gauges={
        "datapath/prefetch_occupancy": 2.9,
        "datapath/prefetch_put_wait_total_s": 9.0,
        "datapath/prefetch_get_wait_total_s": 0.1})
    assert datapath_measured(str(fed))["verdict"].startswith(
        "device-bound")


def test_trace_summarize_carries_datapath_block(tmp_path):
    from tpu_ddp.telemetry.summarize import summarize, summarize_json

    run = _trace(tmp_path, [("data/gather", 0.004),
                            ("data_wait", 0.004)])
    assert "data path (measured)" in summarize(run)
    assert summarize_json(run)["datapath"]["dominant_stage"] == "gather"


def test_ledger_data_wait_row_names_dominant_stage(tmp_path):
    from tpu_ddp.ledger.report import _data_wait_note

    run = _trace(tmp_path, [("data/augment", 0.01),
                            ("data_wait", 0.01)])
    note = _data_wait_note(run)
    assert "augment" in note and "tpu-ddp data report" in note
    assert _data_wait_note(str(tmp_path / "missing")) == ""


# -- DAT001: stage-throughput collapse vs benched baseline -----------------


def _fleet(datapath, run_dir="/tmp/x"):
    from tpu_ddp.monitor.aggregate import FleetSnapshot, HostSnapshot

    host = HostSnapshot(host=0, step=7, datapath=datapath)
    return FleetSnapshot(wall_time=1000.0, run_dir=run_dir,
                         hosts=[host], fleet={"n_hosts": 1})


def test_dat001_fires_on_collapse_and_stays_quiet_otherwise(
        bench_art, tmp_path):
    from tpu_ddp.monitor.aggregate import MonitorConfig
    from tpu_ddp.monitor.alerts import AlertEngine

    art, baseline = bench_art
    base_rate = art["data"]["stages"]["gather"]["batches_per_s"]
    cfg = MonitorConfig(data_baseline=baseline).validate()
    engine = AlertEngine(cfg, once=True)
    flight = {"stage": "gather", "step": 7, "since_unix": 990.0}
    # collapsed AND material: 2 batches/s is 0.5 s/batch of busy cost
    collapsed = min(base_rate * 0.01, 2.0)
    edges = engine.evaluate(_fleet({
        "stage_batches_per_s": {"gather": collapsed},
        "in_flight": flight}))
    assert [(a.rule, a.host, a.state) for a in edges] == [
        ("DAT001", 0, "firing")]
    assert "gather" in edges[0].message
    assert "benched" in edges[0].message
    assert "in flight: gather" in edges[0].message
    # recovery resolves the edge
    resolved = engine.evaluate(_fleet({
        "stage_batches_per_s": {"gather": base_rate}}))
    assert [(a.rule, a.state) for a in resolved] == [
        ("DAT001", "resolved")]
    # healthy rates never fire
    quiet = AlertEngine(cfg, once=True)
    assert quiet.evaluate(_fleet({
        "stage_batches_per_s": {"gather": base_rate * 0.9}})) == []
    # materiality floor: a micro-stage whose ratio collapsed on observer
    # overhead alone (live 1.3 ms/batch < data_min_stage_s) stays quiet
    # even at a 1e-4 ratio...
    micro = AlertEngine(cfg, once=True)
    assert micro.evaluate(_fleet({
        "stage_batches_per_s": {"gather": 750.0}})) == []
    # ...unless the floor is explicitly disabled
    floorless = AlertEngine(MonitorConfig(
        data_baseline=baseline, data_min_stage_s=0.0).validate(),
        once=True)
    assert [(a.rule, a.state) for a in floorless.evaluate(_fleet({
        "stage_batches_per_s": {"gather": 750.0}}))] == [
        ("DAT001", "firing")]
    with pytest.raises(ValueError, match="data_min_stage_s"):
        MonitorConfig(data_min_stage_s=-0.1).validate()
    # unreadable baseline -> the rule is disabled (named warning), not
    # crashing
    dark = AlertEngine(MonitorConfig(
        data_baseline=str(tmp_path / "missing.json")).validate(),
        once=True)
    assert dark.evaluate(_fleet({
        "stage_batches_per_s": {"gather": 0.001}})) == []
    with pytest.raises(ValueError, match="data_collapse_frac"):
        MonitorConfig(data_collapse_frac=0.0).validate()


def test_datapath_host_view_uses_busy_rate():
    from tpu_ddp.monitor.aggregate import datapath_host_view

    now = 1000.0
    # a demand-driven loader idles between batches: 10 batches over a
    # 5s wall-clock window but only 50ms of stage run time. The view
    # must report the BUSY rate (200/s — comparable to the standalone
    # bench), not the wall-clock 2/s that would false-fire DAT001 on
    # every healthy run
    rec = {"updated_unix": now - 1.0, "step": 7,
           "stages": {"gather": {"batches_window": 10,
                                 "busy_s_window": 0.05,
                                 "window_span_s": 5.0}},
           "in_flight": {"stage": "gather", "step": 7}}
    view = datapath_host_view(rec, now)
    assert view["stage_batches_per_s"]["gather"] == pytest.approx(200.0)
    assert view["in_flight"]["stage"] == "gather"
    assert view["age_s"] == pytest.approx(1.0)
    # a slow stage balloons busy: 10 batches in 8s of run time
    slow = {"updated_unix": now, "step": 7, "in_flight": None,
            "stages": {"augment": {"batches_window": 10,
                                   "busy_s_window": 8.0,
                                   "window_span_s": 5.0}}}
    assert datapath_host_view(slow, now)["stage_batches_per_s"][
        "augment"] == pytest.approx(1.25)
    assert datapath_host_view(None, now) == {}


# -- chaos: stage-targeted data_stall --------------------------------------


def _spec(tmp_path, faults):
    path = tmp_path / "chaos.json"
    path.write_text(json.dumps({
        "chaos_schema_version": 1, "seed": 0, "faults": faults}))
    return str(path)


def test_data_stall_stage_spec_validation(tmp_path):
    from tpu_ddp.chaos.inject import load_spec

    with pytest.raises(ValueError, match="'stage' must be one of"):
        load_spec(_spec(tmp_path, [{"kind": "data_stall", "step": 1,
                                    "stage": "decode"}]))
    with pytest.raises(ValueError, match="'batches' must be an int"):
        load_spec(_spec(tmp_path, [{"kind": "data_stall", "step": 1,
                                    "stage": "gather", "batches": 0}]))
    load_spec(_spec(tmp_path, [{"kind": "data_stall", "step": 1,
                                "stage": "gather", "batches": 2}]))


def test_data_stall_hook_wedges_named_stage_once(tmp_path):
    from tpu_ddp.chaos.inject import ChaosInjector

    run = str(tmp_path / "run")
    os.makedirs(run)
    path = _spec(tmp_path, [{"kind": "data_stall", "step": 2,
                             "stage": "augment", "stall_s": 0.0,
                             "batches": 2}])
    inj = ChaosInjector(path, run)
    assert inj.wants_data_stall_stage()
    inj.data_stall_hook("augment")  # before the trigger window: no-op
    assert inj._load_state()["stall_remaining"] == {}
    inj.on_step(1)  # step 2 is now in flight
    inj.data_stall_hook("gather")  # wrong stage: no-op
    inj.data_stall_hook("augment")
    inj.data_stall_hook("augment")
    state = inj._load_state()
    assert state["stall_remaining"]["0"] == 0 and state["fired"] == [0]
    # a resumed incarnation must not stall again
    inj2 = ChaosInjector(path, run)
    inj2.on_step(5)
    inj2.data_stall_hook("augment")
    assert inj2._load_state()["stall_remaining"]["0"] == 0
    # a step-scoped (stage-less) data_stall never wants the seam
    plain = ChaosInjector(
        _spec(tmp_path, [{"kind": "data_stall", "step": 2}]), run)
    assert not plain.wants_data_stall_stage()


def test_trainconfig_refuses_stage_stall_without_staged_pipeline(
        tmp_path):
    from tpu_ddp.train.trainer import TrainConfig

    path = _spec(tmp_path, [{"kind": "data_stall", "step": 1,
                             "stage": "gather"}])
    with pytest.raises(ValueError, match="staged loader pipeline"):
        TrainConfig(synthetic_data=True, chaos_spec=path,
                    telemetry_dir=str(tmp_path)).validate()
    # either staged path satisfies the seam
    TrainConfig(synthetic_data=True, chaos_spec=path,
                telemetry_dir=str(tmp_path),
                prefetch_depth=0).validate()
    TrainConfig(synthetic_data=True, chaos_spec=path,
                telemetry_dir=str(tmp_path),
                prefetch_batches=2).validate()
    with pytest.raises(ValueError, match="prefetch_batches"):
        TrainConfig(synthetic_data=True,
                    prefetch_batches=-1).validate()


def test_hang_bundle_names_suspect_stage(tmp_path):
    from tpu_ddp.comms.forensics import write_hang_bundle

    mon = StageMonitor(str(tmp_path), min_write_interval_s=0.0)
    mon.on_step(5)
    mon.stage_enter("collate")  # wedged
    rec = write_hang_bundle(str(tmp_path))
    assert rec["suspect_stage"]["stage"] == "collate"
    # no staged evidence is an honest None, not a crash
    bare = tmp_path / "bare"
    bare.mkdir()
    assert write_hang_bundle(str(bare))["suspect_stage"] is None


# -- tuner: the input-bound floor ------------------------------------------


def _anatomy(**kw):
    from tpu_ddp.analysis.explain import StepAnatomy

    defaults = dict(
        strategy="dp", model="m", device_kind="cpu", mesh={"data": 8},
        n_devices=8, per_shard_batch=32, compute_dtype="float32",
        flops=1e9, bytes_accessed=1e8, argument_bytes=10_000_000,
        output_bytes=10_000_000, temp_bytes=5_000_000,
        generated_code_bytes=None, fusion_count=0, hlo_ops={},
        collectives=[],
    )
    defaults.update(kw)
    return StepAnatomy(**defaults)


def test_price_anatomy_excludes_input_bound_candidates():
    from tpu_ddp.tuner.grid import Candidate
    from tpu_ddp.tuner.price import price_anatomy

    cand = Candidate("dp", None, False, None, 32, 8)
    slow_loader = DataModel(per_image_s=1e-3, dominant_stage="augment",
                            source="bench.json")
    p = price_anatomy(cand, _anatomy(), chip="v5e", n_devices=8,
                      data_model=slow_loader)
    assert p.status == "input_bound"
    # 256 global images x 1ms each: the floor the reason must name
    assert p.input_floor_s == pytest.approx(0.256)
    assert "256 images" in p.reason
    assert "dominant stage: augment" in p.reason
    assert "cannot feed" in p.reason
    row = p.row_json(8)
    assert row["status"] == "input_bound"
    assert row["input_floor_us"] == 256_000
    # a fast loader prices the same candidate ok, floor recorded
    fast = DataModel(per_image_s=1e-9, source="bench.json")
    ok = price_anatomy(cand, _anatomy(), chip="v5e", n_devices=8,
                       data_model=fast)
    assert ok.status == "ok"
    assert ok.input_floor_s == pytest.approx(256e-9)
    # no evidence -> no floor priced at all
    bare = price_anatomy(cand, _anatomy(), chip="v5e", n_devices=8)
    assert bare.status == "ok" and bare.input_floor_s is None
    assert "input_floor_us" not in bare.row_json(8)


# -- slow tier: the staged pipeline on a real Trainer ----------------------


@pytest.mark.slow
def test_trainer_staged_prefetch_records_digests_and_spans(tmp_path):
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    run = str(tmp_path)
    config = TrainConfig(
        synthetic_data=True, synthetic_size=128, epochs=1,
        per_shard_batch=4, model="netresdeep", n_chans1=4, n_blocks=1,
        n_devices=8, prefetch_batches=2, telemetry_dir=run,
        log_every_epochs=99,
    ).validate()
    Trainer(config).run()
    # digest sink: one record per step of the epoch
    files = read_digest_files(run)
    assert files and len(files[0]["steps"]) == 128 // 32
    # single incarnation: the audit trivially passes (evidence exists)
    assert audit_digests(run)["ok"] is True
    # staged spans + queue counters landed; the report decomposes them
    from tpu_ddp.datapath.report import datapath_measured

    d = datapath_measured(run)
    assert d and set(HOST_STAGES) <= set(d["stages"])
    assert d["prefetch"] is not None
    # live health file was written and closed
    assert read_data_health(data_health_file(run)) is not None
