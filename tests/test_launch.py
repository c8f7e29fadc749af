"""tpu-ddp-launch: rank planning (fast, pure) and job supervision
semantics (subprocess-backed; the jax end-to-end is slow-marked like its
sibling in test_multihost.py)."""

import os
import signal
import subprocess
import sys
import time

import pytest

from tpu_ddp.cli.launch import (
    NPROC_PER_NODE_ENV,
    COORDINATOR_ENV,
    LOCAL_RANK_ENV,
    NUM_PROCESSES_ENV,
    PROCESS_ID_ENV,
    child_env,
    main,
    pick_free_port,
    plan_ranks,
    run_job,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _repo_env(base=None):
    """Env whose PYTHONPATH lets the launcher and path-invoked workers
    import tpu_ddp from the checkout (nothing is pip-installed in CI)."""
    env = dict(os.environ if base is None else base)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


# ------------------------------------------------------------- fast/pure --

def test_plan_ranks_dense_node_major():
    # node 1 of a 3-node x 2-proc job owns global ranks 2 and 3
    assert plan_ranks(3, 2, 1) == [(2, 0), (3, 1)]
    assert plan_ranks(1, 4, 0) == [(0, 0), (1, 1), (2, 2), (3, 3)]


def test_plan_ranks_rejects_bad_shapes():
    with pytest.raises(ValueError):
        plan_ranks(0, 2, 0)
    with pytest.raises(ValueError):
        plan_ranks(2, 2, 2)  # node-rank out of range
    with pytest.raises(ValueError):
        plan_ranks(2, 2, -1)


def test_child_env_sets_rendezvous_triple_and_local_rank():
    env = child_env({"KEEP": "1"}, coordinator="h:1234", num_processes=8,
                    process_id=5, local_rank=1, nproc_per_node=4)
    assert env["KEEP"] == "1"
    assert env[COORDINATOR_ENV] == "h:1234"
    assert env[NUM_PROCESSES_ENV] == "8"
    assert env[PROCESS_ID_ENV] == "5"
    assert env[LOCAL_RANK_ENV] == "1"
    assert env[NPROC_PER_NODE_ENV] == "4"


def test_multinode_requires_explicit_coordinator():
    with pytest.raises(ValueError):
        run_job(["true"], nnodes=2, node_rank=0)


def test_main_requires_a_command():
    with pytest.raises(SystemExit):
        main(["--nproc-per-node", "2"])


def test_launch_module_stays_light():
    """The launcher must not create a jax backend at import or parse time —
    a chip belongs to one process, and a launcher that held the host's
    chips would leave none for its children (module docstring).
    Source-level guard: no jax import."""
    src = open(os.path.join(_REPO, "tpu_ddp", "cli", "launch.py")).read()
    assert "import jax" not in src


# ------------------------------------------------------- job supervision --

def _worker_cmd(body: str):
    return [sys.executable, "-c", body]


def test_run_job_success_and_rank_env():
    """Each rank sees its own dense process id; job exit code 0."""
    body = (
        "import os, sys;"
        f"pid = os.environ['{PROCESS_ID_ENV}'];"
        f"n = os.environ['{NUM_PROCESSES_ENV}'];"
        "sys.exit(0 if (n == '2' and pid in ('0', '1')) else 9)"
    )
    assert run_job(_worker_cmd(body), nproc_per_node=2) == 0


def test_run_job_one_failed_rank_fails_the_job():
    """torchrun semantics: rank 0 exits 3, the launcher tears down the
    still-sleeping rank 1 and reports 3 — promptly, not after rank 1's
    whole sleep."""
    body = (
        "import os, sys, time;"
        f"sys.exit(3) if os.environ['{PROCESS_ID_ENV}'] == '0' "
        "else time.sleep(120)"
    )
    t0 = time.monotonic()
    assert run_job(_worker_cmd(body), nproc_per_node=2) == 3
    assert time.monotonic() - t0 < 60


_READY_PRELUDE = (
    # each rank drops a sentinel AFTER its handler is installed (ready()
    # must be called last in the body), so the test only signals a
    # fully-armed job — touching before installing loses the race under
    # load and the rank dies on the default TERM disposition
    "import os, pathlib, signal, sys, time;"
    "ready = lambda: pathlib.Path(os.environ['READY_DIR'], "
    "os.environ['TPU_DDP_PROCESS_ID']).touch();"
)


def _launch_and_signal(body: str, ready_dir, grace: str):
    env = _repo_env()
    env["TPU_DDP_TERM_GRACE"] = grace
    env["READY_DIR"] = str(ready_dir)
    p = subprocess.Popen(
        [sys.executable, "-m", "tpu_ddp.cli.launch",
         "--nproc-per-node", "2", "--", sys.executable, "-c", body],
        env=env, cwd=_REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    deadline = time.monotonic() + 60
    while len(os.listdir(ready_dir)) < 2:
        assert time.monotonic() < deadline, "ranks never became ready"
        assert p.poll() is None, f"launcher died early: {p.poll()}"
        time.sleep(0.05)
    p.send_signal(signal.SIGTERM)
    return p


def test_forwarded_sigterm_clean_drain_exits_zero(tmp_path):
    """Preemption: both ranks catch the forwarded TERM and exit 0 (the
    Trainer's checkpoint-and-exit contract) -> the job reports success."""
    body = _READY_PRELUDE + (
        "signal.signal(signal.SIGTERM, lambda *a: sys.exit(0));"
        "ready(); time.sleep(60)"
    )
    p = _launch_and_signal(body, tmp_path, grace="5")
    assert p.wait(timeout=30) == 0


def test_forwarded_sigterm_crashed_rank_fails_the_job(tmp_path):
    """Preemption where one rank crashes instead of draining must NOT look
    like a clean exit — its checkpoint may be stale, and a job system that
    sees 0 would happily --resume from it."""
    body = _READY_PRELUDE + (
        "code = 7 if os.environ['TPU_DDP_PROCESS_ID'] == '0' else 0;"
        "signal.signal(signal.SIGTERM, lambda *a: sys.exit(code));"
        "ready(); time.sleep(60)"
    )
    p = _launch_and_signal(body, tmp_path, grace="5")
    assert p.wait(timeout=30) == 7


def test_forwarded_sigterm_wedged_rank_is_escalated_to_kill(tmp_path):
    """A rank that ignores TERM (wedged in a dead collective) must not pin
    the launcher: after the grace window it is SIGKILLed and the job exits
    nonzero with the 128+signal convention."""
    body = _READY_PRELUDE + (
        "signal.signal(signal.SIGTERM, signal.SIG_IGN);"
        "ready(); time.sleep(120)"
    )
    t0 = time.monotonic()
    p = _launch_and_signal(body, tmp_path, grace="2")
    rc = p.wait(timeout=60)
    assert rc == 128 + signal.SIGKILL, rc
    assert time.monotonic() - t0 < 45


# ------------------------------------------------------------- e2e (jax) --

@pytest.mark.slow
def test_launch_two_node_emulation(tmp_path):
    """Multi-node shape: TWO launcher instances (node-rank 0 and 1) share
    an explicit coordinator, each contributing one local process — the
    exact command pattern a 2-host pod uses, emulated on localhost."""
    from tpu_ddp.parallel.runtime import scrubbed_cpu_env

    env = _repo_env(scrubbed_cpu_env())
    env.pop("TPU_DDP_COORDINATOR", None)
    port = pick_free_port()
    outs = [tmp_path / "node0.txt", tmp_path / "node1.txt"]
    nodes = []
    for rank, out in enumerate(outs):
        nodes.append(subprocess.Popen(
            [sys.executable, "-m", "tpu_ddp.cli.launch",
             "--nnodes", "2", "--node-rank", str(rank),
             "--coordinator", f"127.0.0.1:{port}", "--",
             sys.executable,
             os.path.join(_REPO, "tests", "launch_worker.py")],
            env=env, stdout=open(out, "w"), stderr=subprocess.STDOUT,
            cwd=_REPO,
        ))
    for rank, (node, out) in enumerate(zip(nodes, outs)):
        assert node.wait(timeout=300) == 0, out.read_text()[-800:]
    text = "".join(o.read_text() for o in outs)
    assert "LAUNCH_OK pid=0 n=2" in text, text[-800:]
    assert "LAUNCH_OK pid=1 n=2" in text, text[-800:]


@pytest.mark.slow
def test_launch_two_process_rendezvous_end_to_end(tmp_path):
    """The full user path: `python -m tpu_ddp.cli.launch -- python
    launch_worker.py` spawns 2 processes that rendezvous purely from the
    launcher's environment (the train CLI's auto-join path) and pass a
    cross-process barrier."""
    from tpu_ddp.parallel.runtime import scrubbed_cpu_env

    out = tmp_path / "out.txt"
    env = _repo_env(scrubbed_cpu_env())
    env.pop("TPU_DDP_COORDINATOR", None)
    with open(out, "w") as f:
        p = subprocess.run(
            [sys.executable, "-m", "tpu_ddp.cli.launch",
             "--nproc-per-node", "2", "--",
             sys.executable, os.path.join(_REPO, "tests", "launch_worker.py")],
            env=env, stdout=f, stderr=subprocess.STDOUT, timeout=300,
            cwd=_REPO,
        )
    text = out.read_text()
    assert p.returncode == 0, text[-800:]
    assert "LAUNCH_OK pid=0 n=2" in text, text[-800:]
    assert "LAUNCH_OK pid=1 n=2" in text, text[-800:]
