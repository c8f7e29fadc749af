"""Quantized gradient collectives (``parallel/compression.py``,
``--grad-compress``).

Parity discipline: the f32-mode ring is the correctness anchor for the
ring SCHEDULE — bit-identical to ``lax.psum_scatter``/``lax.pmean`` on
exact-arithmetic (integer-valued f32) inputs, where any chunk misrouting
shows up loudly, and within float32 reduction-order ULPs on random
floats (XLA:CPU folds every chunk in rank order; a ring necessarily
folds chunk c starting at device c+1 — IEEE addition is commutative but
not associative). The lossy modes are pinned by their analytic error
bounds and by trajectory closeness to the uncompressed run.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from tpu_ddp.data.cifar10 import synthetic_cifar10
from tpu_ddp.models import NetResDeep
from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
from tpu_ddp.parallel.collectives import (
    ring_all_reduce,
    ring_reduce_scatter,
)
from tpu_ddp.parallel.compression import (
    GradCompression,
    GradCompressor,
    chunk_wire_bytes,
    dequantize_chunk,
    quantize_chunk,
    wire_bytes_table,
)
from tpu_ddp.parallel.mesh import replicated_sharding
from tpu_ddp.parallel.zero import Zero1Partition
from tpu_ddp.train import create_train_state, make_optimizer, make_train_step

_ATOL = 1e-5  # float32 reduction-order drift (same pin as test_zero1)


def _model(**kw):
    # n_chans1=6 / num_classes=7: conv kernels (162, 324 elems), biases
    # (6,), head (7,) — NONE divisible by 4 shards, so every leaf
    # exercises the uneven-padding path through flatten AND the int8
    # tail-block path through quantize.
    cfg = dict(n_chans1=6, n_blocks=2, num_classes=7)
    cfg.update(kw)
    return NetResDeep(**cfg)


def _batch(mesh, n=64, seed=0, num_classes=7):
    imgs, labels = synthetic_cifar10(n, num_classes=num_classes, seed=seed)
    return jax.device_put(
        {"image": imgs.astype(np.float32), "label": labels,
         "mask": np.ones(n, bool)},
        batch_sharding(mesh),
    )


def _trees_close(a, b, atol=_ATOL):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=0, atol=atol)


# ---- quantize/dequantize round trip --------------------------------------


@pytest.mark.parametrize("block", [1, 7, 32, 256])
def test_int8_round_trip_error_bound(block):
    """Block-scaled int8: |x - deq(q(x))| <= max|block| / 127 / 2 + ULP
    per element (half a quantization step at that block's scale), for
    block sizes that tile and that leave a ragged tail."""
    rng = np.random.default_rng(0)
    for size in (block, 3 * block + max(block // 2, 1), 1000):
        x = (rng.standard_normal(size) * rng.uniform(0.1, 10)).astype(
            np.float32)
        payload = quantize_chunk(jnp.asarray(x), "int8", block)
        back = np.asarray(dequantize_chunk(payload, "int8", block, size))
        nb = -(-size // block)
        padded = np.pad(x, (0, nb * block - size)).reshape(nb, block)
        bound = np.repeat(
            np.abs(padded).max(axis=1) / 127.0 / 2.0 * 1.001 + 1e-7, block
        )[:size]
        assert (np.abs(back - x) <= bound).all(), (
            np.abs(back - x) - bound).max()


def test_bf16_round_trip_error_bound():
    """bf16 cast: relative error <= 2^-8 (half of bf16's 7-bit mantissa
    step)."""
    rng = np.random.default_rng(1)
    x = (rng.standard_normal(4096) * 100).astype(np.float32)
    payload = quantize_chunk(jnp.asarray(x), "bf16", 256)
    back = np.asarray(dequantize_chunk(payload, "bf16", 256, 4096))
    assert (np.abs(back - x) <= np.abs(x) * 2.0 ** -8 + 1e-30).all()


def test_quantize_preserves_nonfinite_sentinels():
    """A NaN/Inf input block must dequantize non-finite — the numerics
    flight recorder's sentinels survive the wire (module docstring)."""
    x = jnp.asarray(np.r_[np.ones(10, np.float32), np.nan, np.ones(5,
                    np.float32)])
    back = np.asarray(dequantize_chunk(
        quantize_chunk(x, "int8", 4), "int8", 4, 16))
    assert np.isnan(back[8:12]).any()
    x = x.at[10].set(np.inf)
    back = np.asarray(dequantize_chunk(
        quantize_chunk(x, "int8", 4), "int8", 4, 16))
    assert not np.isfinite(back[8:12]).all()


def test_wire_bytes_accounting():
    """Static accounting: int8 payload ~size + 4/block overhead, and the
    model-level table shows ~4x (int8) / 2x (bf16) vs f32."""
    assert chunk_wire_bytes(1024, "f32", 256) == 4096
    assert chunk_wire_bytes(1024, "bf16", 256) == 2048
    assert chunk_wire_bytes(1024, "int8", 256) == 1024 + 4 * 4
    # NetResDeep's many small leaves pay visible block-pad + scale
    # overhead; a conv trunk at ResNet-50 scale amortizes it to ~4x.
    table = wire_bytes_table(
        jax.eval_shape(
            lambda: create_train_state(
                NetResDeep(), make_optimizer(lr=0.1), jax.random.key(0)
            )
        ).params,
        8,
    )
    assert table["modes"]["bf16"]["dp_ratio_vs_f32"] == pytest.approx(
        2.0, abs=0.1)
    assert table["modes"]["int8"]["dp_ratio_vs_f32"] > 3.2
    from tpu_ddp.models.zoo import MODEL_REGISTRY

    r50 = jax.eval_shape(
        lambda: create_train_state(
            MODEL_REGISTRY["resnet50"](num_classes=10),
            make_optimizer(lr=0.1), jax.random.key(0))
    ).params
    big = wire_bytes_table(r50, 8)
    assert big["modes"]["int8"]["dp_ratio_vs_f32"] == pytest.approx(
        3.9, abs=0.15)
    assert big["modes"]["int8"]["zero1_ratio_vs_f32"] == pytest.approx(
        3.9, abs=0.15)


# ---- ring schedule parity (the f32 anchor) -------------------------------


def test_ring_f32_bit_parity(devices):
    """mode="f32" ring RS/AR vs lax.psum_scatter/lax.pmean on 4 CPU
    devices: bit-identical on exact-arithmetic inputs; ULP-bounded on
    gaussians (module docstring: XLA:CPU's rank-order fold vs the ring's
    rotated fold differ only in association)."""
    n = 4
    mesh = create_mesh(MeshSpec(data=n), devices[:n])

    def body(x):
        rs, _ = ring_reduce_scatter(x, "data", mode="f32")
        ar, _ = ring_all_reduce(x, "data", mode="f32")
        ref_rs = lax.psum_scatter(
            x, "data", scatter_dimension=0, tiled=True)
        return rs, ar / n, ref_rs, lax.pmean(x, "data")

    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P("data"),
        out_specs=(P("data"), P(), P("data"), P()),
    ))
    rng = np.random.default_rng(0)
    ints = rng.integers(-64, 64, (n, 256)).astype(np.float32)
    rs, ar, ref_rs, ref_ar = map(np.asarray, f(jnp.asarray(ints).reshape(-1)))
    # exact arithmetic -> association cannot matter -> bit-identical
    assert np.array_equal(rs, ref_rs)
    assert np.array_equal(ar, ref_ar)
    gauss = rng.standard_normal((n, 256)).astype(np.float32)
    rs, ar, ref_rs, ref_ar = map(
        np.asarray, f(jnp.asarray(gauss).reshape(-1)))
    np.testing.assert_allclose(rs, ref_rs, rtol=0, atol=1e-6)
    np.testing.assert_allclose(ar, ref_ar, rtol=0, atol=1e-6)


def test_ring_all_reduce_replica_identical_int8(devices):
    """The lossy all-reduce returns the SAME bytes on every replica (the
    all-gather phase broadcasts each owner's quantized payload verbatim),
    which is what keeps DDP params replicated — typed replicated by the
    rep checker (out_specs P() would fail otherwise) and checked
    numerically via per-device shards."""
    n = 4
    mesh = create_mesh(MeshSpec(data=n), devices[:n])

    def body(x):
        ar, _ = ring_all_reduce(x, "data", mode="int8", block=16)
        return ar

    out_rep = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P("data"), out_specs=P()))
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(n * 64).astype(np.float32))
    result = out_rep(x)  # P() out_specs: rep check passed
    # sanity: result approximates the true sum
    true = np.asarray(x).reshape(n, 64).sum(0)
    np.testing.assert_allclose(np.asarray(result), true, atol=0.2)


# ---- error feedback ------------------------------------------------------


def test_error_feedback_telescopes_for_constant_gradient(devices):
    """EF accounting is lossless: for a CONSTANT per-device input, the
    sum of the k compressed all-reduce outputs plus the final residual
    equals k times the true sum EXACTLY (up to f32 arithmetic) — the
    errors telescope instead of accumulating, so the long-run applied
    gradient is unbiased."""
    n = 4
    k = 6
    mesh = create_mesh(MeshSpec(data=n), devices[:n])

    def body(x, res):
        outs = []
        r = res
        for _ in range(k):
            out, err = ring_all_reduce(
                x + r, "data", mode="int8", block=16, with_error=True)
            outs.append(out)
            r = err
        # per-device residual enters the global identity via its psum
        return jnp.stack(outs), lax.psum(r, "data")

    f = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(), P()),
    ))
    rng = np.random.default_rng(3)
    xs = rng.standard_normal((n, 64)).astype(np.float32)
    outs, res_sum = f(jnp.asarray(xs).reshape(-1),
                      jnp.zeros(n * 64, jnp.float32))
    outs, res_sum = np.asarray(outs), np.asarray(res_sum)
    true = xs.sum(0)
    # telescoping: sum_t out_t + final residual == k * true sum
    np.testing.assert_allclose(
        outs.sum(0) + res_sum, k * true, rtol=0, atol=1e-4)
    # and the mean applied value converges at rate residual/k
    single_err = np.abs(outs[0] - true).max()
    mean_err = np.abs(outs.mean(0) - true).max()
    assert mean_err < single_err


# ---- step-level composition ----------------------------------------------


def _run_pair(mesh, model, make_tx, build_a, build_b, n_steps=3,
              state_b=None):
    tx = make_tx()
    state = create_train_state(model, tx, jax.random.key(0))
    s_a = jax.device_put(state, replicated_sharding(mesh))
    s_b = state_b if state_b is not None else s_a
    step_a, step_b = build_a(tx), build_b(tx)
    losses = ([], [])
    for i in range(n_steps):
        batch = _batch(mesh, seed=i, num_classes=model.num_classes)
        s_a, m_a = step_a(s_a, batch)
        s_b, m_b = step_b(s_b, batch)
        losses[0].append(float(m_a["loss"]))
        losses[1].append(float(m_b["loss"]))
    return s_a, s_b, losses


def test_f32_mode_step_matches_plain(devices):
    """A train step whose sync runs through the f32-mode ring matches the
    plain pmean step to reduction-order tolerance — the whole compression
    path (flatten/pad/ring/unflatten) is a numerical no-op at f32."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    comp = None

    def build_plain(tx):
        return make_train_step(model, tx, mesh, donate=False)

    def build_ring(tx):
        nonlocal comp
        state = jax.eval_shape(
            lambda: create_train_state(model, tx, jax.random.key(0)))
        comp = GradCompressor(GradCompression(mode="f32"), state.params, 4)
        return make_train_step(model, tx, mesh, donate=False, compress=comp)

    s_a, s_b, losses = _run_pair(
        mesh, model, lambda: make_optimizer(lr=1e-2, momentum=0.9),
        build_plain, build_ring)
    np.testing.assert_allclose(losses[0], losses[1], rtol=0, atol=_ATOL)
    _trees_close(s_a.params, s_b.params)


def test_int8_step_trajectory_close(devices):
    """int8 + error feedback stays close to the uncompressed trajectory
    over a few steps (``tpu-ddp curves diff`` holds 20 steps to 0.05; here
    a tight smoke bound)."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()

    def build_plain(tx):
        return make_train_step(model, tx, mesh, donate=False)

    comp_holder = {}

    def build_int8(tx):
        state = jax.eval_shape(
            lambda: create_train_state(model, tx, jax.random.key(0)))
        comp = GradCompressor(
            GradCompression(mode="int8", block=64, error_feedback=True),
            state.params, 4)
        comp_holder["comp"] = comp
        return make_train_step(model, tx, mesh, donate=False, compress=comp)

    def make_tx():
        return make_optimizer(lr=1e-2, momentum=0.9)

    tx = make_tx()
    state = create_train_state(model, tx, jax.random.key(0))
    step_b = build_int8(tx)
    s_b = jax.device_put(state, replicated_sharding(mesh))
    mesh_ctx = mesh
    s_b = s_b.replace(
        grad_residual=comp_holder["comp"].init_residual(mesh_ctx))
    s_a, s_b, losses = _run_pair(
        mesh, model, make_tx, build_plain, lambda _: step_b, state_b=s_b)
    assert max(abs(a - b) for a, b in zip(*losses)) < 0.05
    # the residual is live state: nonzero after quantized steps
    assert any(
        float(np.abs(np.asarray(leaf)).max()) > 0
        for leaf in jax.tree.leaves(s_b.grad_residual)
    )


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_scan_step_carries_residual(devices):
    """Scan-fused K-step: the residual rides the carry. In f32 mode the
    fused trajectory matches K single steps to reduction-order tolerance
    (residual included — pins the carry STRUCTURE); int8 runs as a smoke
    on the same fused program (exact cross-compile parity is not a valid
    pin for a lossy mode: scan fusion shifts gradients by ULPs, and int8
    rounding amplifies a boundary ULP into one quantization step)."""
    K = 3
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    tx = make_optimizer(lr=1e-2, momentum=0.9)
    state = create_train_state(model, tx, jax.random.key(0))
    batches = [_batch(mesh, seed=i) for i in range(K)]
    stacked = {k: jnp.stack([b[k] for b in batches]) for k in batches[0]}

    comp = GradCompressor(
        GradCompression(mode="f32", error_feedback=True), state.params, 4)
    s0 = jax.device_put(state, replicated_sharding(mesh)).replace(
        grad_residual=comp.init_residual(mesh))
    single = make_train_step(model, tx, mesh, donate=False, compress=comp)
    fused = make_train_step(
        model, tx, mesh, steps_per_call=K, donate=False, compress=comp)
    s_seq = s0
    seq_losses = []
    for b in batches:
        s_seq, m = single(s_seq, b)
        seq_losses.append(float(m["loss"]))
    s_fused, m_fused = fused(s0, stacked)
    assert np.asarray(m_fused["loss"]).shape == (K,)
    np.testing.assert_allclose(
        seq_losses, np.asarray(m_fused["loss"]), rtol=0, atol=_ATOL)
    _trees_close(s_seq.params, s_fused.params)
    # f32 ring introduces zero error; the carried residual stays zero
    assert all(float(np.abs(np.asarray(x)).max()) == 0
               for x in jax.tree.leaves(s_fused.grad_residual))

    comp8 = GradCompressor(
        GradCompression(mode="int8", block=64, error_feedback=True),
        state.params, 4)
    fused8 = make_train_step(
        model, tx, mesh, steps_per_call=K, donate=False, compress=comp8)
    s8, m8 = fused8(
        s0.replace(grad_residual=comp8.init_residual(mesh)), stacked)
    np.testing.assert_allclose(
        np.asarray(m8["loss"]), seq_losses, rtol=0, atol=0.05)
    assert any(float(np.abs(np.asarray(x)).max()) > 0
               for x in jax.tree.leaves(s8.grad_residual))


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_zero1_composition_uneven_padding(devices):
    """--zero1 + --grad-compress: the compressed ring drops into the
    partition's reduce-scatter (uneven-padding leaves — see _model) —
    f32 mode matches plain zero1 exactly; int8+EF trains close and keeps
    the opt state physically scattered."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_train_state(
        model, make_optimizer(lr=1e-2, momentum=0.9), jax.random.key(0))

    def zero1_state(part, comp=None):
        s = part.shard_state(
            state.replace(opt_state=tx.init(state.params)), mesh)
        if comp is not None and comp.config.error_feedback:
            s = s.replace(grad_residual=comp.init_residual(mesh))
        return s

    part_plain = Zero1Partition(tx, state.params, 4)
    step_plain = make_train_step(
        model, tx, mesh, donate=False, zero1=part_plain)

    comp_f32 = GradCompressor(GradCompression(mode="f32"), state.params, 4)
    part_f32 = Zero1Partition(tx, state.params, 4, compress=comp_f32)
    step_f32 = make_train_step(
        model, tx, mesh, donate=False, zero1=part_f32, compress=comp_f32)

    s_a, s_b = zero1_state(part_plain), zero1_state(part_f32)
    for i in range(3):
        batch = _batch(mesh, seed=i)
        s_a, m_a = step_plain(s_a, batch)
        s_b, m_b = step_f32(s_b, batch)
        np.testing.assert_allclose(
            float(m_a["loss"]), float(m_b["loss"]), rtol=0, atol=_ATOL)
    _trees_close(s_a.params, s_b.params)
    _trees_close(part_plain.deshard_opt_state(s_a.opt_state),
                 part_f32.deshard_opt_state(s_b.opt_state))

    comp_i8 = GradCompressor(
        GradCompression(mode="int8", block=64, error_feedback=True),
        state.params, 4)
    part_i8 = Zero1Partition(tx, state.params, 4, compress=comp_i8)
    step_i8 = make_train_step(
        model, tx, mesh, donate=False, zero1=part_i8, compress=comp_i8)
    s_c = zero1_state(part_i8, comp_i8)
    for i in range(3):
        s_c, m_c = step_i8(s_c, _batch(mesh, seed=i))
    # trajectory stays in range and the 1/N physical scatter holds
    for leaf in (x for x in jax.tree.leaves(s_c.opt_state) if x.ndim == 1):
        assert leaf.addressable_shards[0].data.size * 4 == leaf.size
    _trees_close(s_a.params, s_c.params, atol=0.05)


def test_health_reports_compress_error_norm(devices):
    """The flight-recorder schema gains compress_error_norm under
    compression (zero when the mode is lossless-f32, positive for int8),
    and the skip-step guard also reverts the residual on a poisoned
    batch."""
    from tpu_ddp.health.stats import HealthConfig

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    tx = make_optimizer(lr=1e-2, momentum=0.9)
    state = create_train_state(model, tx, jax.random.key(0))
    comp = GradCompressor(
        GradCompression(mode="int8", block=64, error_feedback=True),
        state.params, 4)
    s = jax.device_put(state, replicated_sharding(mesh)).replace(
        grad_residual=comp.init_residual(mesh))
    step = make_train_step(
        model, tx, mesh, donate=False, compress=comp,
        health=HealthConfig(skip_nonfinite=True))
    s, m = step(s, _batch(mesh, seed=0))
    assert float(m["health"]["compress_error_norm"]) > 0
    res_before = jax.device_get(s.grad_residual)
    poisoned = _batch(mesh, seed=0)
    poisoned = dict(poisoned, image=jnp.full_like(
        poisoned["image"], jnp.nan))
    s, m2 = step(s, poisoned)
    # sentinels survive the quantized wire (NaN-poisoned scales)
    assert not bool(np.asarray(m2["health"]["all_finite"]))
    _trees_close(res_before, jax.device_get(s.grad_residual), atol=0)


@pytest.mark.slow  # ~50s: the heaviest compile in the file; the int8/zero1
# composition pins stay fast — make test-all
def test_sp_strategy_composition(devices):
    """build_strategy routes --grad-compress through the SP step (f32
    mode == uncompressed SP trajectory; the compressor + residual ride
    the Strategy for the trainer)."""
    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.train.strategy import build_strategy

    mesh = create_mesh(MeshSpec(data=4, sequence=2), devices)
    model = MODEL_REGISTRY["vit_s4"](num_classes=10)
    results = {}
    for mode in (None, "f32"):
        tx = make_optimizer(lr=1e-2, momentum=0.9)
        strat = build_strategy(
            "sp", mesh, model, tx, jax.random.key(0),
            grad_compress=(
                None if mode is None
                else {"mode": mode, "block": 64, "error_feedback": True}),
        )
        assert (strat.compress is not None) == (mode is not None)
        state = strat.state
        losses = []
        for i in range(2):
            imgs, labels = synthetic_cifar10(32, seed=i)
            batch = jax.device_put(
                {"image": imgs.astype(np.float32), "label": labels,
                 "mask": np.ones(32, bool)},
                strat.batch_shardings,
            )
            state, m = strat.train_step(state, batch)
            losses.append(float(m["loss"]))
        results[mode] = losses
    np.testing.assert_allclose(
        results[None], results["f32"], rtol=0, atol=_ATOL)


def test_strategy_rejects_unsupported_families(devices):
    """--grad-compress with a GSPMD family is a config error, not a
    silent no-op (their grad movement is partitioner-internal)."""
    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.train.strategy import build_strategy

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = MODEL_REGISTRY["vit_s4"](num_classes=10)
    tx = make_optimizer(lr=1e-2)
    with pytest.raises(ValueError, match="grad-compress"):
        build_strategy(
            "fsdp", mesh, model, tx, jax.random.key(0),
            grad_compress={"mode": "int8", "block": 256,
                           "error_feedback": False})


def test_config_validation():
    """validate() rejects unknown modes, bad blocks, unsupported
    families, and error feedback without compression."""
    from tpu_ddp.train.trainer import TrainConfig

    with pytest.raises(ValueError, match="grad-compress mode"):
        TrainConfig(grad_compress="int4").validate()
    with pytest.raises(ValueError, match="grad_compress_block"):
        TrainConfig(grad_compress="int8", grad_compress_block=0).validate()
    for family in ("fsdp", "tp", "pp", "ep"):
        with pytest.raises(ValueError, match="grad-compress"):
            TrainConfig(grad_compress="int8",
                        parallelism=family).validate()
    with pytest.raises(ValueError, match="error-feedback"):
        TrainConfig(grad_compress_error_feedback=True).validate()
    # the supported families pass
    TrainConfig(grad_compress="bf16", parallelism="sp").validate()
    TrainConfig(grad_compress="int8", zero1=True,
                grad_compress_error_feedback=True).validate()
    with pytest.raises(ValueError, match="mode"):
        GradCompression(mode="fp8")


def _trainer_config(tmp_path, epochs, resume=False, **kw):
    from tpu_ddp.train.trainer import TrainConfig

    return TrainConfig(
        synthetic_data=True, synthetic_size=256, epochs=epochs,
        per_shard_batch=8, n_devices=4, momentum=0.9, lr=1e-2, seed=0,
        prefetch_depth=0, log_every_epochs=1,
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every_epochs=1,
        resume=resume, **kw).validate()


@pytest.mark.slow
def test_checkpoint_roundtrip_carries_residual(tmp_path, devices):
    """The error-feedback residual persists through checkpoints: a
    resumed run restores the exact residual; cross-layout resumes
    compose (plain ckpt -> EF run gets a zero residual; EF ckpt -> plain
    run drops it)."""
    from tpu_ddp.train.trainer import Trainer

    EF = dict(grad_compress="int8", grad_compress_block=64,
              grad_compress_error_feedback=True)
    a = Trainer(_trainer_config(tmp_path, 1, **EF))
    a.run()
    res_before = jax.device_get(a.state.grad_residual)
    assert any(float(np.abs(np.asarray(x)).max()) > 0
               for x in jax.tree.leaves(res_before))
    b = Trainer(_trainer_config(tmp_path, 2, resume=True, **EF))
    assert b.resumed_step == 8
    _trees_close(res_before, jax.device_get(b.state.grad_residual), atol=0)
    b.run()
    # plain ckpt -> EF resume: fresh zero residual
    c = Trainer(_trainer_config(tmp_path / "p", 1))
    c.run()
    d = Trainer(_trainer_config(tmp_path / "p", 2, resume=True, **EF))
    assert d.resumed_step == 8
    assert all(float(np.abs(np.asarray(x)).max()) == 0
               for x in jax.tree.leaves(
                   jax.device_get(d.state.grad_residual)))
    # EF ckpt -> plain resume: residual discarded
    e = Trainer(_trainer_config(tmp_path / "q", 1, **EF))
    e.run()
    f = Trainer(_trainer_config(tmp_path / "q", 2, resume=True))
    assert f.resumed_step == 8
    assert f.state.grad_residual is None


@pytest.mark.slow
def test_trainer_telemetry_counts_wire_bytes(tmp_path, devices):
    """comm/grad_bytes_* counters land in the trace and `tpu-ddp trace
    summarize` renders the comms section with the effective ratio."""
    from tpu_ddp.telemetry.summarize import summarize
    from tpu_ddp.train.trainer import Trainer

    run_dir = tmp_path / "run"
    cfg = _trainer_config(
        tmp_path, 1, grad_compress="int8", grad_compress_block=64,
        telemetry_dir=str(run_dir), telemetry_sinks="jsonl",
    )
    t = Trainer(cfg)
    t.run()
    acct = t._compress.accounting()
    text = summarize(str(run_dir))
    assert "comm/grad_bytes_on_wire" in text
    assert "comms (gradient collectives):" in text
    assert "compression ratio" in text
    # the counter itself carries steps x per-step accounting exactly
    steps = 256 // (8 * 4) * 1
    expect = steps * acct["all_reduce_bytes_on_wire_per_device"]
    assert f"comm/grad_bytes_on_wire = {expect}" in text
    ratio = (acct["all_reduce_bytes_f32_per_device"]
             / acct["all_reduce_bytes_on_wire_per_device"])
    assert f"{ratio:.2f}x" in text
