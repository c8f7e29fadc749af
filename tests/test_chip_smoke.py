"""chip_smoke.py off the chip: it refuses to run without a TPU, every phase
function passes at a tiny size on the CPU (sizes are arguments, steered
from here — the script has no option for it), the compile cache lands where
it should, and the parents that start children never import jax."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402

_TINY = ("--n-chans1", 8, "--n-blocks", 2)


def _run(cmd, **kw):
    return subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                          **kw)


@pytest.mark.parametrize("extra", [[], ["--chips", "4"]])
def test_smoke_fails_without_a_tpu(extra):
    """On the CPU the script exits non-zero and its last line parses with
    ``"ok": false`` and the device jax reported — before any phase ran."""
    p = _run([sys.executable, os.path.join(_REPO, "chip_smoke.py"), *extra],
             env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=_REPO)
    assert p.returncode != 0
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert len(lines) == 1, lines  # no phase line, no result


def test_smoke_alone_in_a_directory_prints_no_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    it fails without printing a result."""
    with open(os.path.join(_REPO, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = _run([sys.executable, "chip_smoke.py"], env=env, cwd=tmp_path)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout


def test_phase_reference_recipe(tmp_path, devices):
    info = chip_smoke.reference_recipe(
        str(tmp_path), device="cpu", synthetic_size=128, epochs=2, batch=32,
        steps_per_call=2, extra=_TINY)
    assert info["resumed_from_step"] == info["steps"] == 8
    assert info["loss_last_epoch"] < info["loss_first_epoch"]
    assert info["scan_loss_last_epoch"] < info["scan_loss_first_epoch"]


def test_phase_full_width_cli(tmp_path, devices):
    info = chip_smoke.full_width_cli(
        str(tmp_path), device="cpu", model="netresdeep", batch=16,
        synthetic_size=64, extra=_TINY)
    assert info["steps"] == 4 and info["loss"] > 0
    assert info["peak_bytes_in_use"] is None  # the CPU keeps no stats


def test_phase_full_width_step(devices):
    from tpu_ddp.models.vit import ViT

    model = ViT(patch_size=8, hidden_dim=32, depth=1, num_heads=2,
                num_classes=10, dtype=jnp.bfloat16)
    info = chip_smoke.full_width_step(
        model, image_size=32, batch=8, num_classes=10, steps=2)
    assert info["steps"] == 3 and info["block_until_ready_honest"]


#: one tiny case for each kind of plan the flash kernel makes: tiles that
#: divide T, one whole-axis block (ViT-B/16's 196 tokens), padded and masked
_TINY_FLASH = (((1, 256, 2, 64), False), ((1, 256, 2, 64), True),
               ((2, 196, 2, 64), False), ((1, 600, 2, 64), True))


def test_phase_kernels_direct(devices):
    """Interpreted on the CPU: the numerics checks run, the custom-call
    assertion (a chip-compile fact) is steered off."""
    from tpu_ddp.ops.flash_attention import _plan

    plans = [_plan(shape, 128, 128, masked=False)
             for shape, _ in chip_smoke.FLASH_CASES + _TINY_FLASH]
    kinds = [("tiled" if p.t_pad == s[1] and p.bq < s[1] else
              "whole" if p.t_pad == s[1] else "padded")
             for p, (s, _) in zip(plans, chip_smoke.FLASH_CASES + _TINY_FLASH)]
    # the chip's cases and the tiny ones walk the same three paths
    assert kinds == ["tiled", "tiled", "whole", "padded"] * 2

    info = chip_smoke.kernels_direct(
        flash_cases=_TINY_FLASH, dtype="float32", quant_elements=4096,
        quant_block=128, update_leaves=((24, 40), (3, 3, 4, 8)),
        require_custom_call=False)
    assert [(tuple(f["shape"]), f["causal"])
            for f in info["flash"]] == list(_TINY_FLASH)
    for f in info["flash"]:
        assert f["fwd_rel_err"] < 1e-5 and f["bwd_rel_err"] < 1e-4, f
    assert info["fused_quant"]["payload_steps_differing"] == 0.0
    with pytest.raises(chip_smoke.SmokeFailure, match="kernel"):
        chip_smoke.kernels_direct(
            flash_cases=_TINY_FLASH[:1], dtype="float32",
            quant_elements=4096, quant_block=128,
            update_leaves=((24, 40),))  # no tpu_custom_call on the CPU


def test_phase_kernels_cli(tmp_path, devices):
    info = chip_smoke.kernels_cli(
        str(tmp_path), device="cpu", batch=8, synthetic_size=16,
        extra=_TINY, require_custom_call=False)
    assert set(info) == {"attention_flash", "kernels"}
    # interpreted on the CPU: no kernel in the compiled step to find
    assert info["kernels"]["custom_calls_in_step"] == 0


def test_phase_data_parallel_on_four_virtual_devices(tmp_path, devices):
    info = chip_smoke.data_parallel(
        str(tmp_path), device="cpu", n_devices=4, per_shard=4,
        synthetic_size=64, epochs=2, extra=_TINY)
    assert info["n_devices"] == 4
    assert info["zero1_vs_dp_max_param_diff"] < chip_smoke.ZERO1_VS_DP_TOL
    assert abs(info["loss_dp"] - info["loss_single_device"]) < 0.6


# ---- the compile cache ------------------------------------------------------

_CACHE_CHILD = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {repo!r})
    import jax, jax.numpy as jnp
    from tpu_ddp.parallel.runtime import enable_compile_cache
    from tpu_ddp.telemetry.jax_hooks import install_jax_hooks
    from tpu_ddp.telemetry.registry import default_registry
    install_jax_hooks()
    cache_dir = enable_compile_cache()
    # jax only caches compiles of 1 s and more; CPU compiles are faster
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: jnp.sin(x) @ x.T + 3)(jnp.ones((64, 64))).block_until_ready()
    c = default_registry().snapshot()["counters"]
    print(json.dumps({{"dir": cache_dir,
                      "config": jax.config.jax_compilation_cache_dir,
                      "hits": c.get("jax/cache/cache_hits", 0),
                      "misses": c.get("jax/cache/cache_misses", 0)}}))
""")


def _cache_child(cwd, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true",
                **env)
    p = _run([sys.executable, "-c", _CACHE_CHILD.format(repo=_REPO)],
             env=base, cwd=cwd)
    assert p.returncode == 0, p.stderr[-800:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cache_env_var_places_the_cache(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set jax reads it itself, the helper
    sets no directory, and the run writes its cache there."""
    placed = tmp_path / "placed"
    first = _cache_child(tmp_path, JAX_COMPILATION_CACHE_DIR=str(placed))
    assert first["dir"] == first["config"] == str(placed)
    assert first["misses"] >= 1 and os.listdir(placed)
    second = _cache_child(_REPO, JAX_COMPILATION_CACHE_DIR=str(placed))
    assert second["hits"] >= 1


def test_cache_default_is_one_in_checkout_path(tmp_path, monkeypatch):
    """Unset, two processes started from two working directories pick the
    same in-checkout directory (none is created here: the cache is off
    under test, and jax only makes the directory on its first write)."""
    from tpu_ddp.parallel import runtime

    want = os.path.join(_REPO, ".jax_cache")
    assert runtime.DEFAULT_COMPILE_CACHE_DIR == want
    code = ("import sys; sys.path.insert(0, {!r}); "
            "from tpu_ddp.parallel.runtime import enable_compile_cache; "
            "print(enable_compile_cache())").format(_REPO)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    for cwd in (str(tmp_path), _REPO):
        p = _run([sys.executable, "-c", code], env=env, cwd=cwd)
        assert p.returncode == 0, p.stderr[-800:]
        assert p.stdout.strip().splitlines()[-1] == want


def test_cache_default_is_shared_across_working_directories(
        compile_cache, tmp_path, monkeypatch):
    """Unset, in this process: the helper sets its default directory (here
    moved under tmp_path by the fixture), a compile misses, the same
    program compiled again after a change of working directory hits."""
    from tpu_ddp.parallel.runtime import enable_compile_cache
    from tpu_ddp.telemetry.jax_hooks import install_jax_hooks
    from tpu_ddp.telemetry.registry import (
        default_registry,
        reset_default_registry,
    )

    assert enable_compile_cache() == compile_cache
    assert jax.config.jax_compilation_cache_dir == compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    reset_default_registry()
    install_jax_hooks()
    try:
        jax.jit(lambda x: jnp.cos(x) * 5 + x)(jnp.ones(32))
        monkeypatch.chdir(tmp_path)
        assert enable_compile_cache() == compile_cache
        jax.jit(lambda y: jnp.cos(y) * 5 + y)(jnp.ones(32))
        counters = default_registry().snapshot()["counters"]
        assert counters.get("jax/cache/cache_misses", 0) >= 1
        assert counters.get("jax/cache/cache_hits", 0) >= 1
    finally:
        reset_default_registry()


def test_only_the_helper_sets_a_cache_directory():
    """No other ``jax_compilation_cache_dir`` value is set in code."""
    hits = []
    for root in ("tpu_ddp", "benchmarks"):
        for dirpath, _, files in os.walk(os.path.join(_REPO, root)):
            hits += [os.path.join(dirpath, f) for f in files
                     if f.endswith(".py")]
    hits += [os.path.join(_REPO, f) for f in os.listdir(_REPO)
             if f.endswith(".py")]
    setters = []
    for path in hits:
        with open(path) as f:
            if 'update("jax_compilation_cache_dir"' in f.read().replace(
                    "\n", "").replace(" ", "").replace("'", '"'):
                setters.append(os.path.relpath(path, _REPO))
    assert setters == [os.path.join("tpu_ddp", "parallel", "runtime.py")]


# ---- one process per chip ---------------------------------------------------

@pytest.mark.parametrize("module,path", [
    ("tpu_ddp.cli.launch", "."),
    ("tpu_ddp.elastic.supervisor", "."),
])
def test_parents_that_start_children_stay_off_jax(module, path):
    """A parent that has touched jax holds the chip, and its child then
    fails or hangs: these modules must import without jax."""
    code = ("import sys; sys.path[:0] = [{!r}, {!r}]; import {}; "
            "assert 'jax' not in sys.modules, 'jax imported'").format(
                _REPO, os.path.join(_REPO, path), module)
    p = _run([sys.executable, "-c", code], cwd=_REPO)
    assert p.returncode == 0, p.stderr[-500:]
