"""Multi-process launcher: the ``torchrun`` / ``mp.spawn`` equivalent.

The reference forks one Python process per GPU from inside the training
script (``/root/reference/main.py:80-85``: ``mp.spawn(main, nprocs=
world_size)``) and rendezvouses them itself (``main.py:21-24``). The JAX
pattern inverts this: the *training script stays single-process* (one
process drives all local chips) and scaling out means one process per
HOST, each calling ``jax.distributed.initialize``. This launcher is the
missing operational piece — the command users of torchrun reach for:

    tpu-ddp-launch --nproc-per-node 2 -- python main.py --device cpu ...
    # multi-node: run on every node with its own --node-rank
    tpu-ddp-launch --nnodes 2 --node-rank 0 --coordinator host0:8476 -- ...

It spawns the requested local processes with the ``TPU_DDP_COORDINATOR`` /
``TPU_DDP_NUM_PROCESSES`` / ``TPU_DDP_PROCESS_ID`` environment set;
``tpu_ddp.parallel.runtime.initialize_distributed`` (called by the train
CLI on startup) reads those and joins the rendezvous. Semantics match
torchrun where it matters:

- any child exiting nonzero terminates the whole job (SIGTERM, grace,
  SIGKILL) and the launcher exits with that child's code;
- SIGTERM/SIGINT to the launcher is forwarded to every child — one
  preemption notice drains ALL ranks through the Trainer's cooperative
  drain (the 2-process drain-agreement behavior tested in
  tests/test_multihost.py);
- ranks are dense and deterministic: process_id = node_rank *
  nproc_per_node + local_rank.

A TPU host takes ONE process, which drives all of its chips: a chip
belongs to one process at a time, the launcher sets ranks and nothing about
devices, so with ``--nproc-per-node N`` > 1 every child would ask for the
whole host's chips and only the first would get them. N > 1 is for CPU
runs and tests (tests/test_launch.py, tests/test_multihost.py).

Deliberately stdlib-only, for the same reason: a launcher that imported
jax would hold the chips itself, and no child it spawns could get them.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional, Sequence, Tuple

COORDINATOR_ENV = "TPU_DDP_COORDINATOR"
NUM_PROCESSES_ENV = "TPU_DDP_NUM_PROCESSES"
PROCESS_ID_ENV = "TPU_DDP_PROCESS_ID"
LOCAL_RANK_ENV = "TPU_DDP_LOCAL_RANK"
NPROC_PER_NODE_ENV = "TPU_DDP_NPROC_PER_NODE"

_TERM_GRACE_SECONDS = 15.0
TERM_GRACE_ENV = "TPU_DDP_TERM_GRACE"


def _term_grace() -> float:
    """Seconds a TERM'd job gets to drain before SIGKILL. Overridable via
    TPU_DDP_TERM_GRACE: preemption notices vary (GCE gives 30s, a pod
    maintenance event may give minutes) and the drain needs the window."""
    raw = os.environ.get(TERM_GRACE_ENV)
    if raw is None:
        return _TERM_GRACE_SECONDS
    try:
        return float(raw)
    except ValueError:
        return _TERM_GRACE_SECONDS


def plan_ranks(nnodes: int, nproc_per_node: int,
               node_rank: int) -> List[Tuple[int, int]]:
    """(process_id, local_rank) for every process THIS node launches.

    Dense global ranks, node-major — the layout jax.distributed expects
    (process_id 0 must live where the coordinator runs, i.e. node 0).
    """
    if nnodes < 1 or nproc_per_node < 1:
        raise ValueError("nnodes and nproc-per-node must be >= 1")
    if not 0 <= node_rank < nnodes:
        raise ValueError(f"node-rank {node_rank} outside [0, {nnodes})")
    base = node_rank * nproc_per_node
    return [(base + local, local) for local in range(nproc_per_node)]


def child_env(base: dict, *, coordinator: str, num_processes: int,
              process_id: int, local_rank: int,
              nproc_per_node: int = 1) -> dict:
    """Environment for one launched process: the rendezvous triple that
    ``initialize_distributed`` auto-joins, plus the local rank and
    node width for user-side per-process knobs (log prefixes, profiler
    dirs, per-node device partitioning)."""
    env = dict(base)
    env[COORDINATOR_ENV] = coordinator
    env[NUM_PROCESSES_ENV] = str(num_processes)
    env[PROCESS_ID_ENV] = str(process_id)
    env[LOCAL_RANK_ENV] = str(local_rank)
    env[NPROC_PER_NODE_ENV] = str(nproc_per_node)
    return env


def pick_free_port(host: str = "127.0.0.1") -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def _terminate_all(procs: Sequence[subprocess.Popen],
                   grace: Optional[float] = None) -> None:
    """TERM every live child, give the group one shared grace window to
    drain (checkpoint-and-exit), then KILL stragglers."""
    for p in procs:
        if p.poll() is None:
            try:
                p.terminate()
            except OSError:
                pass
    deadline = time.monotonic() + (_term_grace() if grace is None else grace)
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def _job_telemetry(telemetry_dir: Optional[str], node_rank: int):
    """Launcher-side telemetry (job lifecycle events into a per-node JSONL
    trace). tpu_ddp.telemetry.core/sinks are stdlib-only by contract, so
    this keeps the launcher's no-jax guarantee; None -> the disabled NULL
    instance."""
    if not telemetry_dir:
        from tpu_ddp.telemetry import NULL

        return NULL
    import os as _os

    from tpu_ddp.telemetry import JsonlTraceSink, Telemetry
    from tpu_ddp.telemetry.events import Clock

    clock = Clock()
    sink = JsonlTraceSink(
        _os.path.join(telemetry_dir, f"launch-n{node_rank}.jsonl"),
        clock=clock, process_index=node_rank,
    )
    return Telemetry([sink], process_index=node_rank, clock=clock)


def run_job(cmd: Sequence[str], *, nnodes: int = 1, nproc_per_node: int = 1,
            node_rank: int = 0, coordinator: Optional[str] = None,
            env: Optional[dict] = None,
            telemetry_dir: Optional[str] = None) -> int:
    """Launch ``cmd`` once per local rank and supervise until all exit.

    Returns the job's exit code: 0 iff every child exited 0, else the
    first failing child's code (with the rest torn down torchrun-style).
    With ``telemetry_dir``, job lifecycle events (spawn/exit per rank,
    forwarded signals, final rc) land in ``launch-n<node>.jsonl`` there —
    the supervisor's side of the story next to the ranks' traces.
    """
    tel = _job_telemetry(telemetry_dir, node_rank)
    if coordinator is None:
        if nnodes > 1:
            raise ValueError("--coordinator host:port is required when "
                             "nnodes > 1 (every node must agree on it)")
        coordinator = f"127.0.0.1:{pick_free_port()}"
    num_processes = nnodes * nproc_per_node
    base_env = dict(os.environ if env is None else env)

    procs: List[subprocess.Popen] = []
    ranks = plan_ranks(nnodes, nproc_per_node, node_rank)

    forwarded = []
    forwarded_logged = 0

    def _forward(signum, frame):
        # async-signal-safe only: no sink IO here (JsonlTraceSink holds a
        # non-reentrant lock the interrupted main thread may own — the
        # same rule as the trainer's _on_signal). The supervise loop
        # emits the telemetry instant after the handler returns.
        forwarded.append(signum)
        for p in procs:
            if p.poll() is None:
                try:
                    p.send_signal(signum)
                except OSError:
                    pass

    prev = {s: signal.signal(s, _forward)
            for s in (signal.SIGTERM, signal.SIGINT)}
    try:
        tel.instant(
            "job_start", nnodes=nnodes, nproc_per_node=nproc_per_node,
            node_rank=node_rank, coordinator=coordinator,
        )
        for process_id, local_rank in ranks:
            procs.append(subprocess.Popen(
                list(cmd),
                env=child_env(base_env, coordinator=coordinator,
                              num_processes=num_processes,
                              process_id=process_id, local_rank=local_rank,
                              nproc_per_node=nproc_per_node),
            ))
            tel.instant(
                "child_spawn", process_id=process_id,
                local_rank=local_rank, os_pid=procs[-1].pid,
            )
        rc = 0
        live = list(procs)
        escalate_at = None
        while live:
            time.sleep(0.1)
            while forwarded_logged < len(forwarded):
                tel.instant(
                    "signal_forwarded",
                    signum=int(forwarded[forwarded_logged]),
                )
                forwarded_logged += 1
            if forwarded and escalate_at is None:
                # a forwarded preemption gets ONE grace window for the
                # cooperative drain; a rank wedged in a collective (peer
                # already gone) must not pin the launcher forever
                escalate_at = time.monotonic() + _term_grace()
            if escalate_at is not None and time.monotonic() >= escalate_at:
                # the ranks already had the full drain window — the
                # escalation pass gets only a token grace before KILL
                _terminate_all(live, grace=1.0)
            for p in list(live):
                code = p.poll()
                if code is None:
                    continue
                live.remove(p)
                tel.instant("child_exit", os_pid=p.pid, code=code)
                if code != 0 and rc == 0:
                    # one failed rank fails the job — INCLUDING during a
                    # forwarded preemption: a rank that crashed instead of
                    # draining means its checkpoint may be stale, and the
                    # job system must not see a clean exit. Peers torn
                    # down here exit via signal; rc keeps the first cause.
                    rc = code
                    _terminate_all(live)
        # signal-style exits surface as the shell convention 128+N so the
        # caller sees e.g. 137 rather than a negative code
        rc = 128 - rc if rc < 0 else rc
        tel.instant("job_end", rc=rc)
        return rc
    finally:
        _terminate_all(procs)
        for s, h in prev.items():
            signal.signal(s, h)
        tel.close()


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tpu-ddp-launch",
        description="Spawn and supervise one training process per local "
                    "rank (torchrun equivalent; see module docstring).",
    )
    ap.add_argument("--nproc-per-node", type=int, default=1,
                    help="processes to launch on THIS node. On a TPU host "
                    "keep the default 1: one process drives all of the "
                    "host's chips, and a second could not get them. "
                    "N > 1 is for CPU runs and tests")
    ap.add_argument("--nnodes", type=int, default=1,
                    help="total nodes in the job")
    ap.add_argument("--node-rank", type=int, default=0,
                    help="this node's rank in [0, nnodes)")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="rendezvous address (node 0's reachable address); "
                    "auto-picked on localhost for single-node jobs")
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="write launcher job-lifecycle events "
                    "(spawn/exit/signals) to launch-n<node>.jsonl here; "
                    "pass the same dir to the train CLI's --telemetry-dir "
                    "for a combined picture")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command to launch, after `--`: python main.py ...")
    args = ap.parse_args(argv)

    cmd = list(args.cmd)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no command given; usage: tpu-ddp-launch [opts] -- "
                 "python main.py ...")
    return run_job(cmd, nnodes=args.nnodes,
                   nproc_per_node=args.nproc_per_node,
                   node_rank=args.node_rank, coordinator=args.coordinator,
                   telemetry_dir=args.telemetry_dir)


if __name__ == "__main__":
    sys.exit(main())
