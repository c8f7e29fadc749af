"""Test harness: 8 virtual CPU devices (SURVEY.md §4).

This is the "fake backend" the reference never had: the data-parallel step,
mesh construction, collectives, and checkpoint sharding are all exercised on
CPU with XLA's host-platform device-count override — no TPU required.

Must run before the first ``import jax`` anywhere in the test process.
"""

import os

# Force CPU, whatever the session env pins: the env var for subprocesses,
# the live jax config (below) for this process.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The persistent compile cache stays OFF under test (children included): a
# test must not depend on what an earlier run left in the checkout's cache.
# Tests of the cache itself turn it on through the ``compile_cache`` fixture.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402

_CACHE_CONFIG = (
    "jax_enable_compilation_cache",
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
)


@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    """The persistent compile cache ON for one test, placed under its
    tmp_path instead of the checkout; yields the directory
    ``enable_compile_cache()`` will pick."""
    from jax.experimental.compilation_cache import compilation_cache

    from tpu_ddp.parallel import runtime

    saved = {k: getattr(jax.config, k) for k in _CACHE_CONFIG}
    cache_dir = str(tmp_path / "jax_cache")
    monkeypatch.delenv(runtime.COMPILE_CACHE_ENV, raising=False)
    monkeypatch.setattr(runtime, "DEFAULT_COMPILE_CACHE_DIR", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    yield cache_dir
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture
def fresh_registry():
    """The process-wide telemetry registry emptied, with jax's compile events
    counted into it (``jax/functions`` among them) from now on; yields
    ``default_registry``."""
    from tpu_ddp.telemetry.jax_hooks import install_jax_hooks
    from tpu_ddp.telemetry.registry import (
        default_registry,
        reset_default_registry,
    )

    reset_default_registry()
    assert install_jax_hooks()
    yield default_registry
    reset_default_registry()


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def topo():
    """A DESCRIBED (not attached) ``v5e:2x2`` slice: the TPU's compiler is
    installed here, so ``jit(...).lower(shapes).compile()`` against these
    four devices raises what the chip's compiler would raise and returns
    its cost analysis — at no chip time. Nothing can run on them. One
    libtpu process at a time: two collide on its lock file in /tmp."""
    import importlib.util

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu not installed: the v5e topology cannot be "
                    "described")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # e.g. another process holds libtpu's lock file
        pytest.skip(f"the v5e topology cannot be described: {e}")
    assert len(desc.devices) == 4
    return desc
