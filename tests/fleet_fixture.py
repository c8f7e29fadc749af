"""A synthetic fleet's run directory: the trace, health and heartbeat files
a real multihost run leaves behind, one set a host, with one optional fault
(a straggler, a lost host, a NaN step). ``tests/test_monitor.py``,
``tests/test_diagnose.py`` and ``tests/test_profiler.py`` read it through
the aggregator."""

import json
import os
import time

RUN_META = {
    "run_meta_schema_version": 1, "run_id": "demo-fleet",
    "strategy": "dp", "mesh": {"data": 8}, "process_count": 4,
}


def write_fleet(run_dir, *, n_hosts=4, n_steps=40, straggler_host=None,
                straggler_factor=3.0, lost_host=None, nan_host=None,
                now=None, run_meta=RUN_META):
    """Write the files under ``run_dir`` and return the wall time they
    are dated from (a lost host's heartbeat is 600 s older)."""
    from tpu_ddp.monitor.aggregate import DEVICE_PHASE

    now = time.time() if now is None else now
    os.makedirs(run_dir, exist_ok=True)
    for host in range(n_hosts):
        step_s = 0.010 * (straggler_factor if host == straggler_host else 1)
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
    return now
