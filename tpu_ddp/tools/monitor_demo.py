"""``make monitor-demo`` — end-to-end proof of the live fleet monitor.

Four legs, each with observable pass/fail outcomes (exit nonzero on any
miss, so CI runs this as a living acceptance test beside trace-demo /
health-demo / lint-demo):

1. **Live scrape**: a short CPU training run with the monitor exporter
   on an ephemeral port (``monitor_port=-1``) — ``/metrics`` must serve
   OpenMetrics text carrying the run-metadata labels (run id, strategy,
   mesh, host) WHILE the run is in flight, and ``/healthz`` must report
   fresh watchdog heartbeats.
2. **Aggregator over the real run dir**: ``tpu-ddp watch --once
   --json`` must report the host's steps/sec and phase p50s, flag
   nothing, and raise no alerts on the clean run.
3. **Injected faults**: synthetic 4-host streams with (a) one straggler
   host, (b) one lost host, (c) one NaN-spike health record must raise
   EXACTLY their alert rule ids (STR001 / FLT001 / NUM002) — no more,
   no fewer.
4. **Clean fleet**: an identical synthetic fleet with no injected fault
   must raise no alert at all.
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
import urllib.error
import urllib.request


def _get(port: int, path: str):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5
        ) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _fail(msg: str) -> None:
    print(f"[monitor-demo] FAIL: {msg}", file=sys.stderr)


def write_fleet(run_dir: str, *, n_hosts=4, n_steps=40,
                straggler_host=None, lost_host=None, nan_host=None):
    """Synthetic per-host run-dir files: the same trace/health/heartbeat
    families a real multihost run leaves behind, with optional faults."""
    from tpu_ddp.monitor.aggregate import DEVICE_PHASE

    now = time.time()
    os.makedirs(run_dir, exist_ok=True)
    run_meta = {
        "run_meta_schema_version": 1, "run_id": "demo-fleet",
        "strategy": "dp", "mesh": {"data": 8}, "process_count": n_hosts,
    }
    for host in range(n_hosts):
        step_s = 0.030 if host == straggler_host else 0.010
        with open(os.path.join(run_dir, f"trace-p{host}.jsonl"), "w") as f:
            header = {"schema_version": 1, "type": "header",
                      "epoch_unix": now - 120.0, "pid": host}
            if host == 0:
                header["run_meta"] = run_meta
            f.write(json.dumps(header) + "\n")
            ts = 1.0
            for step in range(n_steps):
                # a loop that runs ahead with its queue full: the
                # dispatch holds the backpressure, and the stamper's
                # thread (tid 2) writes the device's steps beside it
                for name, dur, tid in (("data_wait", 0.002, 1),
                                       ("compiled_step", step_s, 1),
                                       (DEVICE_PHASE, step_s, 2)):
                    f.write(json.dumps({
                        "schema_version": 1, "type": "span", "name": name,
                        "ts_s": round(ts, 6), "dur_s": dur, "pid": host,
                        "tid": tid, "depth": 0, "step": step,
                    }) + "\n")
                    if tid == 1:
                        ts += dur
        with open(os.path.join(run_dir, f"health-p{host}.jsonl"), "w") as f:
            f.write(json.dumps({"schema_version": 1, "type": "header",
                                "pid": host, "policy": "warn"}) + "\n")
            for step in range(n_steps):
                nan = host == nan_host and step == n_steps // 2
                rec = {"schema_version": 1, "type": "health",
                       "step": step, "pid": host,
                       "loss": 2.0 - 0.01 * step, "grad_norm": 1.0,
                       "all_finite": not nan}
                if nan:
                    rec["anomaly"] = "nonfinite"
                f.write(json.dumps(rec) + "\n")
        hb_wall = now - (600.0 if host == lost_host else 1.0)
        with open(os.path.join(run_dir, f"heartbeat-p{host}.json"),
                  "w") as f:
            json.dump({"schema_version": 1, "wall_time": hb_wall,
                       "step": n_steps - 1, "pid": os.getpid(),
                       "process_index": host}, f)


def watch_once(run_dir: str, *extra_args: str) -> dict:
    """Run ``tpu-ddp watch --once --json`` in-process, return the report."""
    from tpu_ddp.monitor.watch import main as watch_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = watch_main([run_dir, "--once", "--json",
                         "--no-alerts-file", *extra_args])
    report = json.loads(buf.getvalue())
    report["_rc"] = rc
    return report


def check_injected(run_dir: str, label: str, expect_rules: set) -> bool:
    report = watch_once(run_dir, "--stale-seconds", "60")
    fired = {a["rule"] for a in report["alerts"]}
    if fired != expect_rules:
        _fail(f"{label}: expected exactly {sorted(expect_rules)}, "
              f"got {sorted(fired)}")
        return False
    want_rc = 1 if expect_rules else 0
    if report["_rc"] != want_rc:
        _fail(f"{label}: watch --once exit code {report['_rc']}, "
              f"expected {want_rc}")
        return False
    print(f"[monitor-demo] {label}: alerts "
          f"{sorted(fired) or ['(none)']} as expected")
    return True


def run_live_leg(run_dir: str) -> bool:
    """Leg 1+2: real training run with the exporter up, scraped mid-run,
    then aggregated post-run."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    config = TrainConfig(
        synthetic_data=True,
        synthetic_size=1024,
        epochs=3,
        per_shard_batch=8,
        model="netresdeep",
        n_chans1=8,
        n_blocks=2,
        prefetch_depth=0,
        log_every_epochs=1,
        telemetry_dir=run_dir,
        telemetry_sinks="jsonl",
        telemetry_snapshot_steps=4,
        monitor_port=-1,
        watchdog_deadline_seconds=300.0,
    )
    trainer = Trainer(config)
    done = threading.Event()

    def run():
        try:
            trainer.run()
        finally:
            done.set()

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    ok = True
    endpoint_path = os.path.join(run_dir, "exporter-p0.json")
    deadline = time.time() + 120
    while not os.path.exists(endpoint_path) and time.time() < deadline:
        time.sleep(0.02)
    if not os.path.exists(endpoint_path):
        _fail("exporter endpoint file never appeared")
        thread.join(timeout=300)
        return False
    with open(endpoint_path) as f:
        port = json.load(f)["port"]

    scraped = None
    while not done.is_set():
        try:
            status, body = _get(port, "/metrics")
        except OSError:
            break
        if status == 200 and "tpu_ddp_train_steps_total" in body:
            scraped = body
            break
        time.sleep(0.02)
    if scraped is None:
        _fail("never scraped a mid-run /metrics with train counters")
        ok = False
    else:
        run_id = trainer.run_meta["run_id"]
        for label in (f'run_id="{run_id}"', 'strategy="dp"',
                      'mesh="data=', 'host="0"'):
            if label not in scraped:
                _fail(f"/metrics missing run-meta label {label!r}")
                ok = False
        if not scraped.rstrip().endswith("# EOF"):
            _fail("/metrics is not a terminated OpenMetrics exposition")
            ok = False
        status, body = _get(port, "/healthz")
        if status != 200 or json.loads(body)["status"] != "ok":
            _fail(f"/healthz mid-run: {status} {body}")
            ok = False
        else:
            print(f"[monitor-demo] scraped :{port}/metrics mid-run "
                  f"(labels ok) and /healthz ok")
    thread.join(timeout=600)
    trainer.close()
    if not done.is_set():
        _fail("training run did not finish")
        return False

    # leg 2: aggregate the finished run dir — clean, with real signals
    report = watch_once(run_dir, "--stale-seconds", "3600")
    snap = report["snapshot"]
    host0 = next((h for h in snap["hosts"] if h["host"] == 0), None)
    if host0 is None or not host0.get("step"):
        _fail(f"aggregator saw no host-0 progress: {snap['hosts']}")
        ok = False
    elif host0["phase_p50_s"].get("compiled_step") is None:
        _fail("aggregator derived no compiled_step p50")
        ok = False
    elif report["alerts"]:
        _fail(f"clean run raised alerts: {report['alerts']}")
        ok = False
    else:
        print(
            f"[monitor-demo] aggregator: host 0 at step {host0['step']}, "
            f"compiled_step p50 "
            f"{1e3 * host0['phase_p50_s']['compiled_step']:.1f}ms, "
            f"steps/s {host0['steps_per_sec']}, no alerts"
        )
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="live fleet monitor demo")
    ap.add_argument("--dir", required=True,
                    help="scratch dir for the run + synthetic fleets")
    args = ap.parse_args(argv)

    ok = run_live_leg(os.path.join(args.dir, "live"))

    straggler_dir = os.path.join(args.dir, "straggler")
    write_fleet(straggler_dir, straggler_host=2)
    ok &= check_injected(straggler_dir, "injected straggler", {"STR001"})

    lost_dir = os.path.join(args.dir, "lost")
    write_fleet(lost_dir, lost_host=3)
    ok &= check_injected(lost_dir, "injected lost host", {"FLT001"})

    nan_dir = os.path.join(args.dir, "nan")
    write_fleet(nan_dir, nan_host=1)
    ok &= check_injected(nan_dir, "injected NaN spike", {"NUM002"})

    clean_dir = os.path.join(args.dir, "clean")
    write_fleet(clean_dir)
    ok &= check_injected(clean_dir, "clean fleet", set())

    if ok:
        print(f"[monitor-demo] OK: live scrape + aggregation + alert "
              f"rules all verified; inspect with: tpu-ddp watch "
              f"{os.path.join(args.dir, 'live')}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
