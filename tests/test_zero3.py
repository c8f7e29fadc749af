"""ZeRO-3 parameter streaming (``parallel/zero.py::Zero3Partition``).

Parity discipline mirrors ``tests/test_zero1.py``: the streamed step
(block-prefetch all-gather forward, re-gather-free backward, shard-space
update with NO trailing gather) computes the SAME math as the replicated
DP step — pinned to float32 reduction-order tolerance, not bit equality.
The in-tree ``fsdp`` GSPMD strategy is the second, independent oracle:
XLA's own ZeRO-3 partitioning of the identical initial state must land
on the same trajectory as the hand-scheduled streaming step.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.data.cifar10 import synthetic_cifar10
from tpu_ddp.models import NetResDeep
from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
from tpu_ddp.parallel.compression import GradCompression, GradCompressor
from tpu_ddp.parallel.mesh import replicated_sharding
from tpu_ddp.parallel.zero import Zero3Partition, param_blocks
from tpu_ddp.train import create_train_state, make_optimizer, make_train_step

_STEPS = 4
_ATOL = 1e-5  # float32 reduction-order drift over _STEPS tiny-model steps


def _model(**kw):
    # n_chans1=6 / num_classes=7: conv kernels (162, 324 elems), biases
    # (6,), head (7,) — NONE divisible by 4 shards, so every leaf
    # exercises the uneven-padding path of the flat update space the
    # params now LIVE in.
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


def _zero3_state(part, state, tx, mesh, comp=None):
    """Fresh zero3 training state from a replicated init: params AND opt
    state scattered into the flat update space (the ONE construction the
    Trainer uses — shard_state on an original-layout state)."""
    s = part.shard_state(
        state.replace(opt_state=tx.init(state.params)), mesh)
    if comp is not None and comp.config.error_feedback:
        s = s.replace(grad_residual=comp.init_residual(mesh))
    return s


def _run_pair(mesh, model, make_tx, build_step, n_steps=_STEPS):
    """(replicated final, zero3 final, partition, losses): the same
    batches through the replicated and the streamed step."""
    tx_rep = make_tx(None)
    tx_z = make_tx("data")
    state = create_train_state(model, tx_rep, jax.random.key(0))
    part = Zero3Partition(tx_z, state.params, mesh.shape["data"])

    s_rep = jax.device_put(state, replicated_sharding(mesh))
    s_z = _zero3_state(part, state, tx_z, mesh)

    step_rep = build_step(tx_rep, None)
    step_z = build_step(tx_z, part)
    losses = ([], [])
    for i in range(n_steps):
        batch = _batch(mesh, seed=i, num_classes=model.num_classes)
        s_rep, m_rep = step_rep(s_rep, batch)
        s_z, m_z = step_z(s_z, batch)
        losses[0].append(np.asarray(m_rep["loss"]))
        losses[1].append(np.asarray(m_z["loss"]))
    return s_rep, s_z, part, losses


def test_zero3_plain_parity(devices):
    """Streamed step vs replicated DP: loss trajectory, de-sharded
    params, AND de-sharded optimizer state all match — with uneven
    padding on every leaf (see _model)."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()

    def build(tx, part):
        return make_train_step(model, tx, mesh, donate=False, zero1=part)

    s_rep, s_z, part, losses = _run_pair(
        mesh, model, lambda ax: make_optimizer(
            lr=1e-2, momentum=0.9, zero1_axis=ax), build)
    np.testing.assert_allclose(losses[0], losses[1], rtol=0, atol=_ATOL)
    _trees_close(s_rep.params, part.deshard_params(s_z.params))
    _trees_close(s_rep.opt_state, part.deshard_opt_state(s_z.opt_state))
    assert int(s_z.step) == _STEPS


@pytest.mark.slow  # ~37s (GSPMD fsdp compile) — make test-all
def test_zero3_fsdp_oracle_parity(devices):
    """The independent oracle: XLA's GSPMD ZeRO-3 (the in-tree fsdp
    strategy) from the IDENTICAL initial state lands on the same loss
    trajectory and final params as the hand-scheduled streaming step.
    LayerNorm model on purpose: batchnorm statistics are per-shard under
    the DP shard_map but global under GSPMD, which would diverge the
    two oracles for reasons unrelated to the streaming schedule."""
    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.train.strategy import build_strategy

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = MODEL_REGISTRY["vit_s4"](num_classes=7)
    tx = make_optimizer(lr=1e-2, momentum=0.9)
    tx_z = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_train_state(model, tx, jax.random.key(0))

    # the fsdp step donates its state: hand the strategy its own buffer
    # copy so donation cannot delete arrays the zero3 state aliases
    strat = build_strategy("fsdp", mesh, model, tx, jax.random.key(0),
                           initial_state=jax.tree.map(jnp.array, state))
    part = Zero3Partition(tx_z, state.params, 4)
    s_z = _zero3_state(part, state, tx_z, mesh)
    step_z = make_train_step(model, tx_z, mesh, donate=False, zero1=part)

    s_f = strat.state
    for i in range(3):
        batch = _batch(mesh, seed=i)
        fbatch = jax.device_put(
            jax.device_get(batch), strat.batch_shardings)
        s_f, m_f = strat.train_step(s_f, fbatch)
        s_z, m_z = step_z(s_z, batch)
        np.testing.assert_allclose(
            np.asarray(m_f["loss"]), np.asarray(m_z["loss"]),
            rtol=0, atol=_ATOL)
    _trees_close(jax.device_get(s_f.params),
                 jax.device_get(part.deshard_params(s_z.params)))


def test_zero3_params_physically_scattered(devices):
    """The HBM claim on live buffers: every params leaf is a flat
    (padded,) array holding exactly padded/N elements per device, and the
    accounting reports ~1/N per-device param bytes plus a bounded
    two-block prefetch high-water."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_train_state(model, tx, jax.random.key(0))
    part = Zero3Partition(tx, state.params, 4)
    sharded = part.shard_params(state.params, mesh)
    assert (jax.tree.structure(sharded)
            == jax.tree.structure(state.params)), \
        "flattening must preserve the pytree structure"
    for leaf in jax.tree.leaves(sharded):
        assert leaf.ndim == 1
        assert leaf.addressable_shards[0].data.size * 4 == leaf.size
    acct = part.accounting()
    assert acct["params_bytes_per_device_sharded"] <= (
        acct["params_bytes_replicated"] // 4
        + acct["params_padding_overhead_bytes_total"] + 64
    )
    names, blocks = param_blocks(state.params)
    assert acct["n_blocks"] == len(blocks) >= 2
    assert acct["block_names"] == names
    # the double-buffer bound: at most two adjacent blocks live gathered
    block_bytes = acct["params_bytes_replicated"]
    assert 0 < acct["prefetch_buffer_bytes"] <= (
        block_bytes + acct["params_padding_overhead_bytes_total"])
    # round trip back out of the update space is exact
    _trees_close(state.params, part.deshard_params(sharded), atol=0)


def test_zero3_scan_parity(devices):
    """Scan-fused K-step: params ride the carry AS SHARDS across the K
    inner steps (one prefetch schedule per inner step, never a full
    materialized tree in the carry); losses and final state match."""
    K = 3
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()

    def build(tx, part):
        return make_train_step(
            model, tx, mesh, steps_per_call=K, donate=False, zero1=part)

    tx_rep = make_optimizer(lr=1e-2, momentum=0.9)
    tx_z = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_train_state(model, tx_rep, jax.random.key(0))
    part = Zero3Partition(tx_z, state.params, 4)
    s_rep = jax.device_put(state, replicated_sharding(mesh))
    s_z = _zero3_state(part, state, tx_z, mesh)

    batches = [_batch(mesh, seed=i) for i in range(K)]
    stacked = {
        k: jnp.stack([b[k] for b in batches]) for k in batches[0]
    }
    s_rep, m_rep = build(tx_rep, None)(s_rep, stacked)
    s_z, m_z = build(tx_z, part)(s_z, stacked)
    np.testing.assert_allclose(
        np.asarray(m_rep["loss"]), np.asarray(m_z["loss"]),
        rtol=0, atol=_ATOL)
    assert np.asarray(m_z["loss"]).shape == (K,)
    _trees_close(s_rep.params, part.deshard_params(s_z.params))
    _trees_close(s_rep.opt_state, part.deshard_opt_state(s_z.opt_state))


def test_zero3_grad_accum_parity(devices):
    """Gradient accumulation: the microbatch loop re-streams params once
    per microbatch but reduce-scatters ONCE for the accumulated average;
    trajectory matches the replicated accumulating step."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()

    def build(tx, part):
        return make_train_step(
            model, tx, mesh, accum_steps=2, donate=False, zero1=part)

    s_rep, s_z, part, losses = _run_pair(
        mesh, model, lambda ax: make_optimizer(
            lr=1e-2, momentum=0.9, zero1_axis=ax), build, n_steps=3)
    np.testing.assert_allclose(losses[0], losses[1], rtol=0, atol=_ATOL)
    _trees_close(s_rep.params, part.deshard_params(s_z.params))


@pytest.mark.slow  # ~11s (two compiled ring variants) — make test-all
def test_zero3_compress_composition(devices):
    """--zero3 + --grad-compress: the quantized ring drops into the
    reduce-scatter exactly as under zero1 — f32 mode matches plain zero3
    to reduction tolerance; int8+EF stays in range with params AND opt
    state still physically scattered."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_train_state(
        model, make_optimizer(lr=1e-2, momentum=0.9), jax.random.key(0))

    part_plain = Zero3Partition(tx, state.params, 4)
    step_plain = make_train_step(
        model, tx, mesh, donate=False, zero1=part_plain)

    comp_f32 = GradCompressor(GradCompression(mode="f32"), state.params, 4)
    part_f32 = Zero3Partition(tx, state.params, 4, compress=comp_f32)
    step_f32 = make_train_step(
        model, tx, mesh, donate=False, zero1=part_f32, compress=comp_f32)

    s_a = _zero3_state(part_plain, state, tx, mesh)
    s_b = _zero3_state(part_f32, state, tx, mesh)
    for i in range(3):
        batch = _batch(mesh, seed=i)
        s_a, m_a = step_plain(s_a, batch)
        s_b, m_b = step_f32(s_b, batch)
        np.testing.assert_allclose(
            float(m_a["loss"]), float(m_b["loss"]), rtol=0, atol=_ATOL)
    _trees_close(part_plain.deshard_params(s_a.params),
                 part_f32.deshard_params(s_b.params))

    comp_i8 = GradCompressor(
        GradCompression(mode="int8", block=64, error_feedback=True),
        state.params, 4)
    part_i8 = Zero3Partition(tx, state.params, 4, compress=comp_i8)
    step_i8 = make_train_step(
        model, tx, mesh, donate=False, zero1=part_i8, compress=comp_i8)
    s_c = _zero3_state(part_i8, state, tx, mesh, comp_i8)
    for i in range(3):
        s_c, m_c = step_i8(s_c, _batch(mesh, seed=i))
    for leaf in jax.tree.leaves(s_c.params):
        assert leaf.addressable_shards[0].data.size * 4 == leaf.size
    _trees_close(part_plain.deshard_params(s_a.params),
                 part_i8.deshard_params(s_c.params), atol=0.05)


@pytest.mark.slow  # ~12s (interpret-mode kernel compiles) — make test-all
def test_zero3_kernels_bit_parity(devices):
    """The acceptance pin: --zero3 --grad-compress --kernels is
    bit-identical to the --zero3 --grad-compress XLA path (the fused
    Pallas tail interprets on CPU; its contract is exact, not
    approximate — atol=0 on params AND opt state)."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    state = create_train_state(
        model, make_optimizer(lr=1e-2, momentum=0.9), jax.random.key(0))

    finals = {}
    for kernels in (False, True):
        tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data",
                            kernels=kernels)
        comp = GradCompressor(
            GradCompression(mode="int8", block=64, error_feedback=True),
            state.params, 4)
        part = Zero3Partition(tx, state.params, 4, compress=comp)
        step = make_train_step(
            model, tx, mesh, donate=False, zero1=part, compress=comp)
        s = _zero3_state(part, state, tx, mesh, comp)
        for i in range(3):
            s, _ = step(s, _batch(mesh, seed=i))
        finals[kernels] = jax.device_get(
            (s.params, s.opt_state, s.grad_residual))
    _trees_close(finals[False], finals[True], atol=0)


def test_zero3_config_guards():
    """Fail-fast surface: --zero3 refuses --zero1 (subsumed), lamb (whole
    -leaf trust ratios), and every family that owns its own layout."""
    from tpu_ddp.train.trainer import TrainConfig

    with pytest.raises(ValueError, match="subsumes"):
        TrainConfig(zero3=True, zero1=True).validate()
    with pytest.raises(ValueError, match="lamb"):
        TrainConfig(zero3=True, optimizer="lamb").validate()
    for par in ("fsdp", "tp", "pp", "ep"):
        with pytest.raises(ValueError, match="zero3"):
            TrainConfig(zero3=True, parallelism=par).validate()


def test_zero3_abstract_builder_guards(devices):
    """The compile-only twin enforces the same family rules."""
    from tpu_ddp.train.strategy import build_abstract_step

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    with pytest.raises(ValueError, match="dp family"):
        build_abstract_step("fsdp", model, tx, mesh, zero3=True)
    with pytest.raises(ValueError, match="subsumes"):
        build_abstract_step("dp", model, tx, mesh, zero1=True, zero3=True)


def test_zero3_lint_clean_and_fingerprint(devices):
    """The product's zero3 program carries the full prefetch schedule:
    the strategy lint (COL001 order pin + collective fingerprint) passes
    with zero findings, and the analyzer labels a zero3 run meta
    'zero3' (grad_compress keeps winning the label when composed)."""
    from tpu_ddp.analysis.explain import run_strategy_label
    from tpu_ddp.analysis.lint import lint_strategy

    findings, audit = lint_strategy("zero3", devices=devices[:4])
    assert findings == [], [f.render() for f in findings]
    assert audit.strategy == "zero3"

    assert run_strategy_label(
        {"strategy": "dp", "config": {"zero3": True}}) == "zero3"
    assert run_strategy_label(
        {"strategy": "dp",
         "config": {"zero3": True, "grad_compress": "int8"}},
    ) == "grad_compress"


def test_zero3_lint_serialized_schedule_fails_closed(devices):
    """The injected violation: a zero3 program built with
    ``prefetch=False`` (just-in-time serialized gathers — no prefetch
    scopes, no handoff barriers) trips COL001 by id, fail-closed."""
    from tpu_ddp.analysis.explain import abstract_batch
    from tpu_ddp.analysis.lint import lint_program
    from tpu_ddp.parallel.partitioning import abstract_train_state

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = jax.eval_shape(
        lambda: create_train_state(model, tx, jax.random.key(0)))
    part = Zero3Partition(tx, state.params, 4, prefetch=False)
    state = state.replace(
        params=jax.eval_shape(part.flatten, state.params),
        opt_state=part.opt_template,
    )
    step = make_train_step(model, tx, mesh, donate=False, zero1=part)
    findings, _ = lint_program(
        step, abstract_train_state(state, part.state_shardings(state, mesh)),
        abstract_batch(mesh, 8, 32), mesh,
        strategy="zero3", model_name="injected")
    col = [f for f in findings if f.rule == "COL001"]
    assert col, [f.render() for f in findings]
    assert any("prefetch schedule absent" in f.message for f in col)
    assert all(f.severity == "error" for f in col)


def test_zero3_tuner_overlay_gate(devices):
    """The tuner prices zero3 as an overlay: enumerated alongside its
    replicated twin, it is REFUSED by name (replicated_fits) when the
    twin fits the cap at least as fast — and ranks when swept alone (no
    twin to defer to). The winner artifact round-trips the flag."""
    from tpu_ddp.tuner.cli import winner_config_fields
    from tpu_ddp.tuner.grid import enumerate_grid
    from tpu_ddp.tuner.price import tune
    from tpu_ddp.tuner.validate import train_config_for

    model = _model()
    pair = enumerate_grid(model, 4, batches=[8], steps_per_call=[1],
                          strategies=["dp", "zero3"])
    assert [c.strategy_token for c in pair] == ["dp", "zero3"]
    assert pair[1].zero3 and "+zero3" in pair[1].name(4)
    res = tune(model=model, model_name="netresdeep", devices=devices[:4],
               chip="v5e", candidates=pair)
    assert len(res.ranked) + len(res.excluded) == 2
    z3 = [p for p in (res.ranked + res.excluded) if p.candidate.zero3]
    twin = [p for p in (res.ranked + res.excluded)
            if not p.candidate.zero3]
    assert len(z3) == 1 and len(twin) == 1
    # the gate invariant: zero3 keeps a rank ONLY by beating its
    # replicated twin outright; otherwise it is refused BY NAME with the
    # twin and both step times in the reason (HBM relief earns no rank)
    if z3[0].status == "ok":
        assert z3[0].effective_step_s < twin[0].effective_step_s
    else:
        assert z3[0].status == "replicated_fits"
        assert "replicated twin" in z3[0].reason
        assert twin[0].name in z3[0].reason

    solo = enumerate_grid(model, 4, batches=[8], steps_per_call=[1],
                          strategies=["zero3"])
    res_solo = tune(model=model, model_name="netresdeep",
                    devices=devices[:4], chip="v5e", candidates=solo)
    assert res_solo.excluded == [] and len(res_solo.ranked) == 1
    fields = winner_config_fields(
        res_solo.ranked[0], model_name="netresdeep", n_chans1=6,
        n_blocks=2, num_classes=7, compute_dtype="float32", n_devices=4)
    assert fields["zero3"] is True and fields["zero1"] is False
    cfg = train_config_for(fields)
    assert cfg.zero3 and cfg.validate()


def test_zero3_memplan_guards():
    """tpu-ddp-memplan refuses the combinations the trainer refuses —
    same wording discipline, before any topology work."""
    from tpu_ddp.tools.memplan import plan

    with pytest.raises(ValueError, match="fsdp is the GSPMD ZeRO-3"):
        plan("netresdeep", 32, compute_dtype="float32", remat=False,
             n_devices=None, parallelism="fsdp", zero3=True,
             topology="v5e:2x2")
    with pytest.raises(ValueError, match="subsumes"):
        plan("netresdeep", 32, compute_dtype="float32", remat=False,
             n_devices=None, zero1=True, zero3=True, topology="v5e:2x2")


# -- Trainer integration (slow tier) ---------------------------------------


def _trainer_config(tmp_path, layout, *, resume=False, epochs=2, ckpt=True,
                    n_devices=4, per_shard_batch=8, **overrides):
    """layout: 'replicated' | 'zero1' | 'zero3'."""
    from tpu_ddp.train.trainer import TrainConfig

    base = dict(
        synthetic_data=True, synthetic_size=256, epochs=epochs,
        per_shard_batch=per_shard_batch, n_devices=n_devices,
        momentum=0.9, lr=1e-2,
        zero1=layout == "zero1", zero3=layout == "zero3",
        seed=0, prefetch_depth=0, log_every_epochs=1,
        checkpoint_dir=str(tmp_path / "ckpt") if ckpt else None,
        checkpoint_every_epochs=1, resume=resume,
    )
    base.update(overrides)
    return TrainConfig(**base)


def _original_layout(trainer):
    """(params, opt_state) in the ONE layout checkpoints persist."""
    params, opt = trainer.state.params, trainer.state.opt_state
    if trainer._zero1 is not None:
        opt = trainer._zero1.deshard_opt_state(opt)
        if getattr(trainer._zero1, "scattered_params", False):
            params = trainer._zero1.deshard_params(params)
    return params, opt


@pytest.mark.slow  # ~25s per direction (two Trainers each) — make test-all
@pytest.mark.parametrize("first,second", [
    ("zero3", "replicated"),
    ("replicated", "zero3"),
    ("zero3", "zero1"),
    ("zero1", "zero3"),
])
def test_zero3_checkpoint_roundtrip(tmp_path, devices, first, second):
    """--resume composes zero3 <-> zero1 <-> replicated in EVERY
    direction: checkpoints persist the ONE de-sharded layout, so a run
    trained one way restores into any other and matches an uninterrupted
    replicated run.

    The round trip itself is pinned EXACT (the second run restores bit for
    bit what the first held); the continuation to 1e-4 of the uninterrupted
    run over epochs of 2 steps — longer runs measure the layouts'
    reduction-order drift through the momentum, not the resume (see
    test_zero1_checkpoint_roundtrip for the measured drift)."""
    from tpu_ddp.train.trainer import Trainer

    def cfg(path, layout, **kw):  # 64/(8*4) = 2 steps per epoch
        return _trainer_config(path, layout, synthetic_size=64, **kw)

    ref = Trainer(cfg(tmp_path / "ref", "replicated"))
    ref.run()

    a = Trainer(cfg(tmp_path, first, epochs=1))
    a.run()
    b = Trainer(cfg(tmp_path, second, resume=True))
    assert b.resumed_step == 2
    _trees_close(_original_layout(a), _original_layout(b), atol=0)
    b.run()
    assert int(b.state.step) == int(ref.state.step) == 4
    _trees_close(_original_layout(ref), _original_layout(b), atol=1e-4)


@pytest.mark.slow  # ~30s (three Trainers) — make test-all
def test_zero3_elastic_resume_8_to_4(tmp_path, devices):
    """Device-count independence: a zero3 checkpoint written on 8
    devices resumes on 4 (the de-sharded layout carries no shard count)
    — same global batch, so the math matches an uninterrupted 4-device
    replicated run to reduction tolerance.

    LayerNorm model: netresdeep's batchnorm computes PER-SHARD batch
    statistics, so 8x4 and 4x8 shardings of the same global batch are
    different models — a semantics difference unrelated to zero3."""
    from tpu_ddp.train.trainer import Trainer

    ref = Trainer(_trainer_config(tmp_path / "ref", "replicated",
                                  model="vit_s4"))
    ref.run()

    a = Trainer(_trainer_config(tmp_path, "zero3", epochs=1,
                                n_devices=8, per_shard_batch=4,
                                model="vit_s4"))
    a.run()
    b = Trainer(_trainer_config(tmp_path, "zero3", resume=True,
                                n_devices=4, per_shard_batch=8,
                                model="vit_s4"))
    assert b.resumed_step == 8
    b.run()
    assert int(b.state.step) == int(ref.state.step)
    _trees_close(ref.state.params,
                 b._zero1.deshard_params(b.state.params), atol=1e-4)


@pytest.mark.slow  # ~20s (one telemetry run + plan rebuild) — make test-all
def test_zero3_mem_reconcile(tmp_path, devices):
    """tpu-ddp mem reconciles a --zero3 run: the plan is rebuilt from
    the run meta WITH the streaming layout (flat 1/N param arguments),
    and the join carries the CPU degradation note."""
    from tpu_ddp.memtrack.reconcile import CPU_DEGRADATION_NOTE, reconcile
    from tpu_ddp.telemetry import reset_default_registry
    from tpu_ddp.train.trainer import Trainer

    reset_default_registry()
    run_dir = str(tmp_path / "z3run")
    Trainer(_trainer_config(
        tmp_path, "zero3", epochs=1, ckpt=False,
        telemetry_dir=run_dir, telemetry_sinks="jsonl",
        telemetry_snapshot_steps=3)).run()
    reset_default_registry()
    rec = reconcile(run_dir)
    assert rec["strategy"] == "dp"
    planned = rec["planned"]
    assert planned["peak_bytes"] == (
        planned["argument_bytes"] + planned["temp_bytes"])
    assert rec["calibratable"] is False
    assert CPU_DEGRADATION_NOTE in rec["notes"]


@pytest.mark.slow  # ~60s (four Trainers: 3-seed band + judged run)
def test_zero3_curves_overlay_parity(tmp_path, devices):
    """The convergence gate: a --zero3 run judged against a 3-seed
    REPLICATED band of the same recipe sits inside the envelope (rc 0)
    under the strict quality digest — the streaming layout is a memory
    layout, not a different optimizer."""
    import json
    import os

    from tpu_ddp.curves import curve_artifact, extract_curve
    from tpu_ddp.curves.report import main as curves_main
    from tpu_ddp.registry.store import record_artifact
    from tpu_ddp.telemetry import reset_default_registry
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    def run(name, **overrides):
        reset_default_registry()
        d = str(tmp_path / name)
        cfg = TrainConfig(
            synthetic_data=True, synthetic_size=320, epochs=2,
            per_shard_batch=8, model="netresdeep", n_chans1=8, n_blocks=2,
            n_devices=4, prefetch_depth=0, momentum=0.9, lr=1e-2,
            log_every_epochs=99, eval_each_epoch=True, health="on",
            telemetry_dir=d, telemetry_sinks="jsonl", **overrides,
        ).validate()
        t = Trainer(cfg)
        metrics = t.run(close=False)
        t.record_final_eval(accuracy=metrics.get("test_accuracy"))
        t.close()
        reset_default_registry()
        return d

    curves = [extract_curve(run(f"s{seed}", seed=seed))
              for seed in (0, 1, 2)]
    reg = str(tmp_path / "reg")
    os.makedirs(reg)
    for i, c in enumerate(curves):
        path = os.path.join(reg, f"src{i}.json")
        with open(path, "w") as f:
            json.dump(curve_artifact(dict(c)), f)
        record_artifact(reg, path)

    z3 = run("z3", seed=3, zero3=True)
    assert curves_main([z3, "--against", reg, "--allow-dirty",
                        "--band-quality", curves[0]["quality_digest"]]) == 0


# -- structural pins (no compiles, no mesh: the cheap tier) -----------------


def _np_template():
    """Hand-made params tree: four top-level module keys, every leaf size
    indivisible by 4 shards (uneven padding everywhere)."""
    f32 = np.float32
    return {
        "conv1": {"kernel": np.ones((3, 3, 3, 6), f32),
                  "bias": np.ones((6,), f32)},
        "fc1": {"kernel": np.ones((54, 10), f32),
                "bias": np.ones((10,), f32)},
        "fc2": {"kernel": np.ones((10, 7), f32)},
        "resblock": {"Conv_0": {"kernel": np.ones((3, 3, 6, 6), f32)}},
    }


def _np_partition(n_shards=4, **kw):
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    return Zero3Partition(tx, _np_template(), n_shards, **kw)


def test_param_blocks_partition_every_leaf_exactly_once():
    names, blocks = param_blocks(_np_template())
    n_leaves = len(jax.tree.leaves(_np_template()))
    flat_indices = [i for blk in blocks for i in blk]
    assert sorted(flat_indices) == list(range(n_leaves))
    assert len(flat_indices) == n_leaves  # no leaf in two blocks
    assert len(names) == len(blocks) == len(set(names))
    assert names == ["conv1", "fc1", "fc2", "resblock"]


def test_param_blocks_depend_on_structure_not_shapes():
    """The partitioner is a pure function of tree PATHS — the linter
    recomputes it from abstract (shape-different) states."""
    doubled = jax.tree.map(lambda x: np.ones(x.shape * 2, x.dtype),
                           _np_template())
    assert param_blocks(_np_template()) == param_blocks(doubled)


def test_zero3_scattered_params_probe():
    from tpu_ddp.parallel.zero import Zero1Partition

    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    assert _np_partition().scattered_params is True
    z1 = Zero1Partition(tx, _np_template(), 4)
    assert getattr(z1, "scattered_params", False) is False


def test_zero3_partition_blocks_match_the_one_function():
    part = _np_partition()
    names, blocks = param_blocks(part.param_template)
    assert (part.block_names, part.blocks) == (names, blocks)


def test_zero3_flat_layout_shapes_and_roundtrip():
    part = _np_partition()
    flat = jax.eval_shape(part.flatten, _np_template())
    for got, orig in zip(jax.tree.leaves(flat),
                         jax.tree.leaves(_np_template())):
        assert got.ndim == 1 and got.dtype == orig.dtype
        assert got.size % 4 == 0 and 0 <= got.size - orig.size < 4
    rt = jax.eval_shape(lambda p: part.unflatten(part.flatten(p)),
                        _np_template())
    for got, orig in zip(jax.tree.leaves(rt),
                         jax.tree.leaves(_np_template())):
        assert got.shape == orig.shape and got.dtype == orig.dtype


def test_zero3_param_specs_live_on_the_data_axis():
    from jax.sharding import PartitionSpec as P

    from tpu_ddp.parallel.zero import Zero1Partition

    part = _np_partition()
    specs = jax.tree.leaves(part.param_specs)
    assert specs and all(s == P("data") for s in specs)
    assert all(s == P("data")
               for s in jax.tree.leaves(part.state_specs().params))
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    assert Zero1Partition(tx, _np_template(), 4).state_specs().params == P()


def test_zero3_accounting_invariants():
    part = _np_partition()
    acct = part.accounting()
    sizes = [x.size for x in jax.tree.leaves(_np_template())]
    padded = [x.size for x in jax.tree.leaves(
        jax.eval_shape(part.flatten, _np_template()))]
    assert acct["params_bytes_replicated"] == 4 * sum(sizes)
    assert acct["params_bytes_per_device_sharded"] == sum(padded)  # /4 shards, x4 B
    assert acct["params_padding_overhead_bytes_total"] == 4 * (
        sum(padded) - sum(sizes))
    assert acct["n_blocks"] == len(acct["block_names"]) == 4
    block_bytes = [0] * 4
    for k, blk in enumerate(part.blocks):
        for i in blk:
            block_bytes[k] += 4 * padded[i]
    assert acct["prefetch_buffer_bytes"] == max(
        block_bytes[k] + block_bytes[k + 1] for k in range(3))


def test_zero3_single_block_prefetch_high_water_is_that_block():
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    tmpl = {"only": {"kernel": np.ones((5, 3), np.float32)}}
    acct = Zero3Partition(tx, tmpl, 4).accounting()
    assert acct["n_blocks"] == 1
    assert acct["prefetch_buffer_bytes"] == 4 * 16  # 15 padded to 16


def test_zero3_accounting_opt_side_matches_zero1():
    from tpu_ddp.parallel.zero import Zero1Partition

    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    z1 = Zero1Partition(tx, _np_template(), 4).accounting()
    z3 = _np_partition().accounting()
    for key in z1:
        assert z3[key] == z1[key], key


def test_zero3_prefetch_flag_default_and_injection_override():
    assert _np_partition().prefetch is True
    assert _np_partition(prefetch=False).prefetch is False


def test_zero3_grid_candidate_token_pins():
    from tpu_ddp.tuner.grid import enumerate_grid

    c_plain, c_comp = enumerate_grid(
        _model(), 4, batches=[8], steps_per_call=[1],
        strategies=["zero3", "zero3+grad_compress"])
    assert c_plain.zero3 and not c_plain.zero1
    assert c_plain.strategy_token == "zero3"
    assert "+zero3" in c_plain.name(4)
    assert c_comp.strategy_token == "zero3+grad_compress"
    assert c_comp.zero3 and c_comp.grad_compress == "int8"


def test_zero3_run_label_family_pins():
    from tpu_ddp.analysis.explain import run_strategy_label

    assert run_strategy_label(
        {"strategy": "dp", "config": {}}) == "dp"
    assert run_strategy_label(
        {"strategy": "dp", "config": {"zero1": True}}) == "zero1"


def test_zero3_flat_dtype_preserved_mixed_precision():
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    tmpl = {"a": {"w": np.ones((5,), np.float32)},
            "b": {"w": np.ones((3,), jnp.bfloat16)}}
    part = Zero3Partition(tx, tmpl, 4)
    flat = jax.eval_shape(part.flatten, tmpl)
    assert flat["a"]["w"].dtype == np.float32
    assert flat["b"]["w"].dtype == jnp.bfloat16
