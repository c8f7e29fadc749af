"""``make profile-demo`` — end-to-end proof of the anomaly-profiler loop.

The acceptance story the profiler exists for, run as one live circuit on
the 4-virtual-device CPU mesh (exit nonzero on any miss, so CI runs this
beside monitor-demo as a living gate):

1. **Injected slow input pipeline**: a short training run whose loader
   is wrapped to stall in a distinctly named frame
   (``_injected_input_stall``) — the data-wait share climbs past the
   DWT001 threshold.
2. **Alert fires and auto-arms a capture**: a watch-side alert engine
   (aggregator + ``capture_profile`` action) polls the run dir; the
   DWT001 firing edge must POST ``/profile`` at the live exporter and
   arm a capture window — no human in the loop.
3. **The bundle names the frame**: after the run, the capture bundle
   must exist with ``trigger = alert:DWT001`` provenance, and its host
   sampler's top stacks must contain the injected stall frame.
4. **`tpu-ddp profile` renders the verdict**: the report CLI must exit
   0, print the injected frame in the top stacks, and point at the run's
   program map (which names the phase and module of every operation a
   device trace of this run prints), and ``trace summarize`` must surface
   the ``profiler/*`` capture counters.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import threading
import time


def _fail(msg: str) -> None:
    print(f"[profile-demo] FAIL: {msg}", file=sys.stderr)


def _injected_input_stall(seconds: float) -> None:
    """THE frame the demo is about: the host sampler's folded stacks
    must name it, or the loop is broken."""
    time.sleep(seconds)


class _SlowLoader:
    """Wrap the trainer's batch loader with a per-batch stall — the
    injected input-pipeline fault. Delegates everything else, so the
    loader contract (steps_per_epoch, set_epoch, ...) is untouched."""

    def __init__(self, inner, stall_s: float):
        self._inner = inner
        self._stall_s = stall_s

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __iter__(self):
        for batch in self._inner:
            _injected_input_stall(self._stall_s)
            yield batch

    def __len__(self):
        return len(self._inner)


def run_anomaly_loop(run_dir: str) -> bool:
    import jax

    jax.config.update("jax_platforms", "cpu")
    from tpu_ddp.monitor.aggregate import FleetAggregator, MonitorConfig
    from tpu_ddp.monitor.alerts import AlertEngine
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    config = TrainConfig(
        synthetic_data=True,
        synthetic_size=512,
        epochs=3,
        per_shard_batch=8,
        model="netresdeep",
        n_chans1=8,
        n_blocks=2,
        prefetch_depth=0,       # the un-prefetched path wraps next(it)
                                # in the data_wait span the share reads
        log_every_epochs=1,
        telemetry_dir=run_dir,
        telemetry_sinks="jsonl",
        telemetry_snapshot_steps=4,
        monitor_port=-1,        # ephemeral; discovered via exporter-p0.json
        watchdog_deadline_seconds=300.0,
        profile_window_steps=6,
        profile_host_hz=250.0,
    )
    trainer = Trainer(config)
    # the injected fault: every batch stalls in _injected_input_stall,
    # inside the trainer's data_wait span — DWT001's exact condition.
    # 200ms/batch keeps the data-wait share past the threshold on any
    # box, whatever the CPU compiled-step time is
    trainer.train_loader = _SlowLoader(trainer.train_loader, 0.2)
    done = threading.Event()

    def run():
        try:
            trainer.run()
        finally:
            done.set()

    thread = threading.Thread(target=run, daemon=True)
    thread.start()

    # watch side: aggregator + alert engine with the capture_profile
    # action (the default trigger POSTs the run's own exporter). The
    # DWT threshold sits below the injected share with margin on both
    # slow boxes (stall ~ compiled step) and fast ones (stall dominates)
    monitor_config = MonitorConfig(
        data_wait_share_max=0.35, max_auto_profiles=3)
    engine = AlertEngine(
        monitor_config, run_dir=run_dir,
        actions=("log", "file", "capture_profile"), once=True,
    )
    aggregator = FleetAggregator(run_dir, monitor_config)
    fired = False
    deadline = time.time() + 300
    while not done.is_set() and time.time() < deadline:
        edges = engine.evaluate(aggregator.poll())
        if any(e.rule == "DWT001" and e.state == "firing"
               for e in edges):
            fired = True
        if fired and engine.auto_profiles > 0:
            break
        time.sleep(0.25)
    thread.join(timeout=600)
    trainer.close()

    ok = True
    if not done.is_set():
        _fail("training run did not finish")
        return False
    if not fired:
        _fail("DWT001 never fired despite the injected input stall")
        ok = False
    if engine.auto_profiles < 1:
        _fail("the capture_profile action never armed a capture")
        ok = False
    print(f"[profile-demo] DWT001 fired and auto-armed "
          f"{engine.auto_profiles} capture(s)")
    return ok


def check_bundle(run_dir: str) -> bool:
    from tpu_ddp.profiler.capture import list_bundles, read_bundle_meta
    from tpu_ddp.profiler.host import parse_folded, top_frames

    bundles = list_bundles(run_dir)
    if not bundles:
        _fail("no capture bundle was written")
        return False
    ok = True
    bundle = bundles[0]
    meta = read_bundle_meta(bundle["path"])
    trigger = meta.get("trigger") or {}
    if trigger.get("source") != "alert" or trigger.get("rule") != "DWT001":
        _fail(f"bundle trigger provenance is {trigger}, expected "
              "alert:DWT001")
        ok = False
    with open(os.path.join(bundle["path"], "host_stacks.folded")) as f:
        folded = parse_folded(f.read())
    top = top_frames(folded, n=10)
    if not any("_injected_input_stall" in r["frame"] for r in top):
        _fail("host sampler top stacks do not contain the injected "
              f"stall frame; top: {[r['frame'] for r in top[:5]]}")
        ok = False
    else:
        hit = next(r for r in top
                   if "_injected_input_stall" in r["frame"])
        print(f"[profile-demo] bundle {bundle['path']}: injected frame "
              f"at {hit['share']:.0%} self time (alert:DWT001 "
              "provenance ok)")
    return ok


def check_report(run_dir: str) -> bool:
    from tpu_ddp.cli.main import main as cli_main
    from tpu_ddp.telemetry.summarize import summarize

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["profile", run_dir])
    out = buf.getvalue()
    ok = True
    if rc != 0:
        _fail(f"tpu-ddp profile exited {rc}")
        ok = False
    if "_injected_input_stall" not in out:
        _fail("report does not name the injected frame")
        ok = False
    if "program map:" not in out or "train_step (" not in out:
        _fail("the report does not point at the run's program map:\n"
              + out[-2000:])
        ok = False
    summary = summarize(run_dir)
    if "profiler:" not in summary or "capture window(s)" not in summary:
        _fail("trace summarize does not surface the profiler counters")
        ok = False
    if ok:
        table = out[out.index("program map:"):].splitlines()[:4]
        print("[profile-demo] report renders; program map:")
        for line in table:
            print(f"    {line}")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="anomaly-profiler end-to-end demo")
    ap.add_argument("--dir", required=True,
                    help="scratch run dir for the injected-stall run")
    args = ap.parse_args(argv)
    run_dir = os.path.join(args.dir, "live")

    ok = run_anomaly_loop(run_dir)
    ok &= check_bundle(run_dir)
    ok &= check_report(run_dir)
    if ok:
        print("[profile-demo] OK: injected stall -> DWT001 -> "
              "auto-armed capture -> frame named + per-op table; "
              f"inspect with: tpu-ddp profile {run_dir}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
