"""Fused Pallas kernel tier (``ops/fused_update.py``,
``ops/fused_quant.py``, docs/kernels.md): bit-parity against the
jnp/optax references (parity is compared jit-vs-jit — eager XLA:CPU
contracts FMAs differently), error-feedback telescoping with kernels
on, the KRN001 fail-closed lint rule, the ops artifact/model
calibration loop, and the tuner's signed-savings kernel axis."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from tpu_ddp.ops.fused_quant import (
    _reference_dequant,
    _reference_quant,
    fused_dequant,
    fused_quant,
    supports_block,
)
from tpu_ddp.parallel import MeshSpec, create_mesh
from tpu_ddp.parallel.collectives import ring_all_reduce
from tpu_ddp.train.optim import make_optimizer


def _tree_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    bad = [i for i, (x, y) in enumerate(zip(la, lb))
           if not np.array_equal(np.asarray(x), np.asarray(y))]
    return bad


# ---- quant -> dequant roundtrip ------------------------------------------


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("tail", [0, 37])
def test_quant_roundtrip_bitwise(block, tail):
    """Fused quantize and dequantize-accumulate must be bit-identical
    to the compression.py references across block sizes and odd tails
    (a chunk whose last block is partial)."""
    assert supports_block(block)
    size = block * 3 + tail
    x = (jnp.sin(jnp.arange(size, dtype=jnp.float32)) * 3.0
         ).at[5].set(0.0)
    acc = jnp.cos(jnp.arange(size, dtype=jnp.float32))

    q_f = jax.jit(lambda v: fused_quant(v, block))(x)
    q_r = jax.jit(lambda v: _reference_quant(v, block))(x)
    assert not _tree_bitwise(q_f, q_r)
    assert q_f["q"].dtype == jnp.int8

    d_f = jax.jit(lambda p: fused_dequant(p, block, size))(q_f)
    d_r = jax.jit(lambda p: _reference_dequant(p, block, size))(q_r)
    assert not _tree_bitwise(d_f, d_r)

    # the ring's accumulate form: dequantize ONTO a running f32 sum
    a_f = jax.jit(lambda p, a: fused_dequant(p, block, size, add_to=a)
                  )(q_f, acc)
    a_r = jax.jit(lambda p, a: _reference_dequant(p, block, size,
                                                  add_to=a))(q_r, acc)
    assert not _tree_bitwise(a_f, a_r)


def test_unsupported_block_raises():
    """A non-lane-aligned block is refused by name — the kernel asked for
    is never quietly swapped for the reference."""
    assert not supports_block(64)
    x = jnp.arange(200, dtype=jnp.float32)
    with pytest.raises(ValueError, match="block=64"):
        fused_quant(x, 64)
    with pytest.raises(ValueError, match="block=64"):
        fused_dequant(_reference_quant(x, 64), 64, 200)


# ---- error feedback with kernels on --------------------------------------


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_error_feedback_telescopes_with_kernels(devices):
    """The EF telescoping identity (test_compression.py) must survive
    the fused wire kernels — and the whole trajectory (every hop's
    output AND the final residual) must be bit-identical to the XLA
    ring, the contract the Trainer's --kernels switch rests on."""
    n, k = 4, 6
    mesh = create_mesh(MeshSpec(data=n), devices[:n])

    def make(kernels):
        def body(x, res):
            outs, r = [], res
            for _ in range(k):
                out, err = ring_all_reduce(
                    x + r, "data", mode="int8", block=128,
                    with_error=True, kernels=kernels)
                outs.append(out)
                r = err
            return jnp.stack(outs), lax.psum(r, "data")

        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P(), P())))

    rng = np.random.default_rng(3)
    xs = rng.standard_normal((n, 512)).astype(np.float32)
    flat = jnp.asarray(xs).reshape(-1)
    zero = jnp.zeros(n * 512, jnp.float32)
    outs_x, res_x = make(False)(flat, zero)
    outs_k, res_k = make(True)(flat, zero)
    assert not _tree_bitwise((outs_k, res_k), (outs_x, res_x))
    outs, res = np.asarray(outs_k), np.asarray(res_k)
    np.testing.assert_allclose(
        outs.sum(0) + res, k * xs.sum(0), rtol=0, atol=1e-4)


# ---- fused optimizer update ----------------------------------------------


def _opt_problem(seed=0):
    rng = np.random.default_rng(seed)

    def arr(shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    # 2-D leaves see the kernels-only decay mask; the 1-D bias and the
    # frozen matrix pin the mask + label plumbing
    params = {"w": arr((16, 128)), "b": arr((128,)),
              "frozen_w": arr((8, 128))}
    grads = {"w": arr((16, 128)), "b": arr((128,)),
             "frozen_w": arr((8, 128))}
    return params, grads


def _freeze(path, leaf):
    return any("frozen" in str(p) for p in path)


@pytest.mark.parametrize("optimizer", ["adamw", "sgd"])
def test_fused_update_matches_reference_bitwise(optimizer):
    """make_optimizer(kernels=True).fused.apply == the reference optax
    chain, bit for bit — params, moments, EMA, and the frozen leaf —
    with clip + weight decay + freeze mask + EMA all engaged."""
    import optax

    kw = dict(lr=1e-2, weight_decay=0.05, grad_clip_norm=1.0,
              optimizer=optimizer, ema_decay=0.99,
              freeze_predicate=_freeze)
    if optimizer == "sgd":
        kw["momentum"] = 0.9
    tx_ref = make_optimizer(**kw)
    fused = make_optimizer(kernels=True, **kw).fused
    assert fused is not None  # the switch must not fail closed here

    params, grads = _opt_problem()
    state = tx_ref.init(params)

    @jax.jit
    def ref(g, s, p):
        u, ns = tx_ref.update(g, s, p)
        return optax.apply_updates(p, u), ns

    @jax.jit
    def krn(g, s, p):
        np_, _u, ns = fused.apply(g, s, p)
        return np_, ns

    p_ref, s_ref = ref(grads, state, params)
    p_krn, s_krn = krn(grads, state, params)
    assert not _tree_bitwise(p_krn, p_ref)
    assert not _tree_bitwise(s_krn, s_ref)
    # the frozen leaf really is frozen on both paths
    assert np.array_equal(np.asarray(p_krn["frozen_w"]),
                          np.asarray(params["frozen_w"]))
    # a second step from the fused state keeps telescoping bitwise
    p2_ref, s2_ref = ref(grads, s_ref, p_ref)
    p2_krn, s2_krn = krn(grads, s_krn, p_krn)
    assert not _tree_bitwise(p2_krn, p2_ref)
    assert not _tree_bitwise(s2_krn, s2_ref)


def test_fused_update_interpret_kernel_close():
    """The true pallas lowering (interpret=True on CPU) agrees with the
    reference to float32 precision — the mosaic path's math is the
    mirror's math (the 1-ulp latitude is XLA:CPU FMA contraction,
    docs/kernels.md)."""
    import optax

    from tpu_ddp.ops.fused_update import FusedUpdate

    kw = dict(lr=1e-2, weight_decay=0.05, grad_clip_norm=1.0,
              optimizer="adamw", ema_decay=0.99)
    tx_ref = make_optimizer(**kw)
    mirror = make_optimizer(kernels=True, **kw).fused
    assert mirror is not None
    pallas = FusedUpdate(mirror.recipe, interpret=True)

    params, grads = _opt_problem(1)
    state = tx_ref.init(params)
    u, ns = jax.jit(lambda g, s, p: tx_ref.update(g, s, p)
                    )(grads, state, params)
    p_ref = optax.apply_updates(params, u)
    p_k, _u, ns_k = jax.jit(lambda g, s, p: pallas.apply(g, s, p)
                            )(grads, state, params)
    for want, got in zip(jax.tree.leaves((p_ref, ns)),
                         jax.tree.leaves((p_k, ns_k))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-6, atol=1e-7)


# ---- KRN001: the kernel switch fails closed by name ----------------------


def test_krn001_fail_closed_names_kernel_and_fallback():
    from tpu_ddp.analysis.lint import RULES, lint_kernels

    assert "KRN001" in RULES
    assert lint_kernels(False) == []
    # a capable backend (cpu interpret / tpu mosaic) audits clean
    assert lint_kernels(True, backend="interpret") == []
    findings = lint_kernels(True, backend=None)
    assert findings and all(f.rule == "KRN001" for f in findings)
    assert all(f.severity == "error" for f in findings)
    text = " ".join(f.message for f in findings)
    for name in ("fused_update", "fused_quant", "fused_dequant"):
        assert name in text  # the dead kernel is named...
    assert "fallback" in text  # ...and so is the path actually taken


def test_ops_bench_parity_gate_fails_closed_by_name(tmp_path, capsys):
    """``tpu-ddp ops bench`` measures a kernel against its jnp reference
    and fits it a cost line only while every point is bit-identical: a
    corrupted output (the hidden ``--corrupt``) exits 1 and names the
    kernel, so a bad lowering cannot ship a cost model."""
    from tpu_ddp.ops.cli import main as ops_main

    out = tmp_path / "ops.json"
    args = ["bench", "--kernels", "fused_quant", "--sizes", "4096,16384",
            "--reps", "1", "--out", str(out)]
    assert ops_main(args) == 0
    ops = json.loads(out.read_text())["ops"]
    assert ops["parity_ok"] and ops["backend"] == "interpret"
    for side in ("fused", "xla"):
        assert ops["kernels"]["fused_quant"][side]["s_per_elem"] > 0
    capsys.readouterr()
    assert ops_main(args + ["--corrupt", "fused_quant"]) == 1
    err = capsys.readouterr().err
    assert "PARITY GATE FAILED" in err and "fused_quant" in err
    assert json.loads(out.read_text())["ops"]["parity_failures"] == [
        "fused_quant"]


# ---- the ops artifact kind and cost model --------------------------------


def _ops_artifact(chip="cpu", parity_ok=True, xla_slope=3e-9):
    return {
        "type": "ops", "ops_schema_version": 1,
        "ops": {
            "chip": chip, "device_kind": chip, "backend": "interpret",
            "parity_ok": parity_ok,
            "kernels": {
                "fused_update": {
                    "fused": {"alpha_s": 1e-5, "s_per_elem": 1e-9,
                              "samples": 2},
                    "xla": {"alpha_s": 2e-5, "s_per_elem": xla_slope,
                            "samples": 2},
                    "parity_ok": parity_ok,
                },
            },
        },
    }


def test_registry_and_regress_classify_ops():
    from tpu_ddp.analysis.regress import normalize_artifact
    from tpu_ddp.registry.store import _artifact_kind

    art = _ops_artifact()
    assert _artifact_kind(art) == "ops"
    norm = normalize_artifact(art)
    assert "ops" in norm
    assert "kernels" not in norm["ops"]  # rows/sweeps trimmed for gating


def test_ops_model_assembly_signed_savings(tmp_path):
    from tpu_ddp.ops.model import fit_cost_line, ops_model_for_chip

    line = fit_cost_line([1000.0, 2000.0], [1e-4, 1.5e-4])
    assert line.alpha_s == pytest.approx(5e-5)
    assert line.s_per_elem == pytest.approx(5e-8)

    path = tmp_path / "ops.json"
    path.write_text(json.dumps(_ops_artifact()))
    m = ops_model_for_chip("cpu", sources=[str(path)])
    assert m and "ops.json" in m.source
    # xla slope 3e-9 vs fused 1e-9: positive saving, scaling with count
    s1 = m.savings_s("fused_update", 1_000_000)
    assert s1 is not None and s1 > 0
    assert m.savings_s("fused_update", 1_000_000, count=3) == \
        pytest.approx(3 * s1)
    # a slower fused line prices NEGATIVE — the model never clamps
    slow = tmp_path / "slow.json"
    slow.write_text(json.dumps(_ops_artifact(xla_slope=5e-10)))
    assert ops_model_for_chip(
        "cpu", sources=[str(slow)]).savings_s("fused_update", 1_000_000) < 0
    # wrong-chip evidence is ignored; parity-failed kernels price None
    assert not ops_model_for_chip("v5e", sources=[str(path)])
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(_ops_artifact(parity_ok=False)))
    mb = ops_model_for_chip("cpu", sources=[str(bad)])
    assert mb and mb.savings_s("fused_update", 1_000_000) is None


# ---- the tuner's kernel axis ---------------------------------------------


def _anatomy(**kw):
    from tpu_ddp.analysis.hlo import StepAnatomy

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


def test_kernel_twin_shares_program_and_prices_signed():
    from tpu_ddp.ops.model import CostLine, KernelCost, OpsModel
    from tpu_ddp.tuner.grid import Candidate
    from tpu_ddp.tuner.price import price_anatomy

    base = Candidate("dp", None, True, "int8", 32, 1)
    twin = dataclasses.replace(base, kernels=True)
    assert twin.program_key() == base.program_key()  # one compile
    assert "+krn" in twin.name(8) and "+krn" not in base.name(8)

    def model(fused_slope):
        kc = KernelCost(
            fused=CostLine(alpha_s=0.0, s_per_elem=fused_slope,
                           samples=2),
            xla=CostLine(alpha_s=0.0, s_per_elem=2e-10, samples=2),
            parity_ok=True)
        return OpsModel(chip="v5e", kernels={"fused_update": kc},
                        source="synthetic", samples=4)

    kw = dict(chip="v5e", n_devices=8, param_elements=1_000_000)
    p_off = price_anatomy(base, _anatomy(), **kw,
                          ops_model=model(1e-10))
    assert p_off.kernel_savings_s is None
    p_fast = price_anatomy(twin, _anatomy(), **kw,
                           ops_model=model(1e-10))
    assert p_fast.kernel_savings_s is not None
    assert p_fast.kernel_savings_s > 0
    assert p_fast.effective_step_s < p_off.effective_step_s
    assert (p_fast.predicted_images_per_sec_per_chip
            > p_off.predicted_images_per_sec_per_chip)
    # the SIGNED branch: a measured-slower fused path must rank BELOW
    p_slow = price_anatomy(twin, _anatomy(), **kw,
                           ops_model=model(5e-10))
    assert p_slow.kernel_savings_s < 0
    assert p_slow.effective_step_s > p_off.effective_step_s
    row = p_slow.row_json(8)
    assert row["kernels"] is True and row["kernel_savings_us"] < 0
    assert p_off.row_json(8)["kernels"] is False


# ---- the whole Trainer, bit for bit --------------------------------------


def _trainer_end_state(kernels):
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    cfg = TrainConfig(
        synthetic_data=True, synthetic_size=32, epochs=1,
        per_shard_batch=4, n_devices=4, lr=1e-3, seed=0,
        optimizer="adamw", weight_decay=0.05, grad_clip_norm=1.0,
        ema_decay=0.99, schedule="cosine", warmup_steps=1,
        prefetch_depth=0, log_every_epochs=99,
        zero1=True, grad_compress="int8", grad_compress_block=128,
        grad_compress_error_feedback=True, kernels=kernels,
        n_chans1=4, n_blocks=1, mem_sample_steps=0,
    ).validate()
    trainer = Trainer(cfg)
    trainer.run()
    return jax.device_get((trainer.state.params, trainer.state.opt_state,
                           trainer.state.grad_residual))


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_trainer_kernels_bitwise_zero1_int8_ef(devices):
    """The acceptance contract: a full zero1 + int8-ring +
    error-feedback training run with --kernels leaves params, moments +
    EMA, and EF residuals bit-identical to the XLA path."""
    ref = _trainer_end_state(False)
    krn = _trainer_end_state(True)
    for name, a, b in zip(("params", "opt_state", "grad_residual"),
                          ref, krn):
        bad = _tree_bitwise(a, b)
        assert not bad, f"{name}: {len(bad)} leaves differ"
