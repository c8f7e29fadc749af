"""Process/runtime bootstrap.

Replaces ``setup()`` (``/root/reference/main.py:21-24``: MASTER_ADDR/PORT env
rendezvous + ``init_process_group("nccl")``) and the process-per-GPU spawn
(``main.py:80-85``). On TPU, a single process drives all local chips; multi-
host pods launch one process per host, coordinated by
``jax.distributed.initialize`` — there is no per-device rank plumbing and no
torch.multiprocessing equivalent, by design (SURVEY.md §2.6).
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

log = logging.getLogger(__name__)

_initialized = False

#: The environment variable jax itself reads to place its persistent
#: compilation cache.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Where the cache lives when that variable does not place it: one fixed,
#: git-ignored directory in the checkout, derived from this package's
#: location. Fixed because the directory is part of jax's cache key — a
#: path built from /tmp, a pid or a time never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.
    Every entry point calls this before its first trace (the CLI, the
    Trainer, chip_smoke.py, chipbench/run.py through the Trainer).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has read it itself and
    nothing here sets a directory; otherwise the cache goes to
    ``DEFAULT_COMPILE_CACHE_DIR``. jax's own floor stays: a compile is
    written when it took a second or more. Cache traffic lands in the
    ``jax/cache/*`` telemetry counters (telemetry/jax_hooks.py bridges
    jax.monitoring)."""
    from jax.experimental.compilation_cache import compilation_cache

    if not os.environ.get(COMPILE_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
    # jax latches its cache-enabled decision at the FIRST compile of the
    # process: if anything compiled before this call the new config would
    # be silently ignored. Un-latch so the next compile re-evaluates it.
    compilation_cache.reset_cache()
    return jax.config.jax_compilation_cache_dir


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    force: bool = False,
) -> None:
    """Multi-host bootstrap. No-op on a single host (unlike the reference,
    which *requires* its rendezvous even for one machine, main.py:22-24).

    On multi-host TPU pods pass ``force=True`` (args are auto-detected from
    pod metadata) or give explicit coordinator args. Processes spawned by
    ``tpu-ddp-launch`` (the torchrun/mp.spawn equivalent, cli/launch.py)
    carry the rendezvous triple in TPU_DDP_COORDINATOR / _NUM_PROCESSES /
    _PROCESS_ID environment variables and auto-join here. With none of
    these, this is a no-op that does NOT touch any backend — platform
    selection may not have happened yet, and forcing backend creation here
    would pin the wrong one.
    """
    global _initialized
    if _initialized:
        return
    if coordinator_address is None and num_processes is None and not force:
        # launcher-provided rendezvous (lazy import: cli.launch is
        # stdlib-only, so this cannot recurse into backend setup)
        from tpu_ddp.cli.launch import (
            COORDINATOR_ENV,
            NUM_PROCESSES_ENV,
            PROCESS_ID_ENV,
        )

        coordinator_address = os.environ.get(COORDINATOR_ENV)
        if coordinator_address is not None:
            try:
                num_processes = int(os.environ[NUM_PROCESSES_ENV])
                process_id = int(os.environ[PROCESS_ID_ENV])
            except (KeyError, ValueError) as e:
                raise RuntimeError(
                    f"{COORDINATOR_ENV} is set but its companions "
                    f"{NUM_PROCESSES_ENV}/{PROCESS_ID_ENV} are missing or "
                    f"non-integer — a partially scrubbed launcher "
                    f"environment: {e}"
                ) from e
    if coordinator_address is None and num_processes is None and not force:
        _initialized = True
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    log.info(
        "jax.distributed initialized: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )


def scrubbed_cpu_env(n_virtual_devices: int = 1) -> dict:
    """Copy of os.environ for a child that must run on ``n_virtual_devices``
    virtual CPU devices: ``JAX_PLATFORMS=cpu`` plus the device-count flag,
    replacing any existing one (XLA honors the LAST duplicate, so a stale
    value is stripped, not appended after)."""
    import re

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    stripped = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "",
        env.get("XLA_FLAGS", ""),
    )
    env["XLA_FLAGS"] = (
        stripped
        + f" --xla_force_host_platform_device_count={n_virtual_devices}"
    ).strip()
    return env


def is_tpu_device() -> bool:
    """True when the default device is a TPU. Touches the backend — never
    call before platform selection."""
    return jax.devices()[0].platform == "tpu"


def is_primary_process() -> bool:
    """Single-writer predicate (process 0). Fixes the reference's
    all-ranks-write-one-checkpoint race (``main.py:45``) and interleaved
    logging (``main.py:44,49``) — SURVEY.md §5.2."""
    return jax.process_index() == 0


def device_count() -> int:
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()
