"""bench.py's driver contract: the TERM-then-KILL child protocol, and "a
measurement of the chip or nothing" — without a TPU the bench exits
non-zero with ``"ok": false``, through a parent that never imports jax."""

import json
import os
import signal
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import bench  # noqa: E402  (stdlib-only at module level)

import pytest  # noqa: E402

pytestmark = pytest.mark.slow  # subprocess-heavy: make test-all


def test_terminate_gracefully_prefers_term():
    # A cooperative child dies on TERM and is never KILLed.
    p = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    t0 = time.time()
    bench._terminate_gracefully(p, grace=10)
    assert p.poll() == -signal.SIGTERM
    assert time.time() - t0 < 5  # did not sit out the grace window


def test_terminate_gracefully_kills_term_ignorer():
    # A child stuck ignoring TERM (stand-in for "blocked in a C++ call")
    # eats the KILL after the grace window. Handshake on a sentinel line so
    # the TERM cannot race the handler installation.
    p = subprocess.Popen([
        sys.executable, "-u", "-c",
        "import signal, time; signal.signal(signal.SIGTERM, "
        "signal.SIG_IGN); print('ready', flush=True); time.sleep(60)",
    ], stdout=subprocess.PIPE, text=True)
    assert p.stdout.readline().strip() == "ready"
    bench._terminate_gracefully(p, grace=1)
    assert p.poll() == -signal.SIGKILL


@pytest.mark.parametrize("extra", [[], ["--config", "winner.json"]])
def test_bench_fails_without_a_tpu(extra):
    """On the CPU, bench.py (either mode) exits non-zero; its last line
    parses, says ``"ok": false``, names the device found, and carries no
    number."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"), *extra],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    lines = [l for l in p.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout; stderr tail: {p.stderr[-400:]}"
    rec = json.loads(lines[-1])
    assert rec["ok"] is False and "value" not in rec
    assert rec["device"]["platform"] == "cpu"


def test_bench_parent_never_imports_jax():
    """One process per chip: a parent that has touched jax holds the chip
    and its child cannot get it. The parent path must stay stdlib-only."""
    code = (
        "import sys; sys.argv = ['bench.py']; import bench\n"
        "import subprocess\n"
        "class P:\n"
        "    def __init__(self, *a, **k): pass\n"
        "    def wait(self, timeout=None): return 7\n"
        "    def poll(self): return 7\n"
        "subprocess.Popen = P\n"
        "try:\n"
        "    bench.main()\n"
        "except SystemExit as e:\n"
        "    assert e.code == 7, e.code\n"
        "assert 'jax' not in sys.modules and 'tpu_ddp' not in sys.modules\n"
        "print('parent-clean')\n"
    )
    p = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr[-500:]
    assert "parent-clean" in p.stdout


def test_committed_tpu_evidence_is_valid_json():
    path = os.path.join(_REPO, "benchmarks", "bench_tpu.json")
    with open(path) as f:
        doc = json.load(f)
    assert doc["device_kind"].lower().startswith("tpu")
    flag = doc["flagship"]
    assert flag["images_per_sec_per_chip"] > 0
    assert flag["mfu"] is None or flag["mfu"] > 0
