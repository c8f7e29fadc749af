"""ZeRO-1 cross-replica weight-update sharding (``parallel/zero.py``).

Parity discipline: the sharded update (reduce-scatter + 1/N shard update +
all-gather) computes the SAME math as the replicated update (pmean + full
update). On this backend the element order inside XLA's all-reduce vs
reduce-scatter kernels can differ, so trajectories are pinned to float32
reduction-order tolerance (a few ULP per step — ``_ATOL`` per step over
``_STEPS`` steps), not bit equality; small shapes frequently ARE bit-equal
but that is not guaranteed by the spec.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu_ddp.data.cifar10 import synthetic_cifar10
from tpu_ddp.models import NetResDeep
from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
from tpu_ddp.parallel.mesh import replicated_sharding
from tpu_ddp.parallel.zero import Zero1Partition, clip_by_global_norm_sharded
from tpu_ddp.train import create_train_state, make_optimizer, make_train_step
from tpu_ddp.train.optim import _decay_mask

_STEPS = 4
_ATOL = 1e-5  # float32 reduction-order drift over _STEPS tiny-model steps


def _model(**kw):
    # n_chans1=6 / num_classes=7: conv kernels (162, 324 elems), biases
    # (6,), head (7,) — NONE divisible by 4 shards, so every leaf
    # exercises the uneven-padding path.
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


def _run_pair(mesh, model, make_tx, build_step, n_steps=_STEPS):
    """(replicated final state, zero1 final state, losses pair): the same
    batches through both update paths. ``build_step(tx, zero1)`` returns
    the compiled step; ``make_tx(zero1_axis)`` the optimizer."""
    tx_rep = make_tx(None)
    tx_z = make_tx("data")
    state = create_train_state(model, tx_rep, jax.random.key(0))
    part = Zero1Partition(tx_z, state.params, mesh.shape["data"])

    s_rep = jax.device_put(state, replicated_sharding(mesh))
    s_z = part.shard_state(
        state.replace(opt_state=tx_z.init(state.params)), mesh)

    step_rep = build_step(tx_rep, None)
    step_z = build_step(tx_z, part)
    losses = ([], [])
    for i in range(n_steps):
        batch = _batch(mesh, seed=i, num_classes=model.num_classes)
        s_rep, m_rep = step_rep(s_rep, batch)
        s_z, m_z = step_z(s_z, batch)
        losses[0].append(np.asarray(m_rep["loss"]))
        losses[1].append(np.asarray(m_z["loss"]))
    return s_rep, s_z, part, losses, (m_rep, m_z)


def test_zero1_plain_parity(devices):
    """Plain DP step: loss trajectory, params, AND the de-sharded
    optimizer state all match the replicated run — with uneven padding on
    every leaf (see _model)."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()

    def build(tx, part):
        return make_train_step(model, tx, mesh, donate=False, zero1=part)

    s_rep, s_z, part, losses, _ = _run_pair(
        mesh, model, lambda ax: make_optimizer(
            lr=1e-2, momentum=0.9, zero1_axis=ax), build)
    np.testing.assert_allclose(losses[0], losses[1], rtol=0, atol=_ATOL)
    _trees_close(s_rep.params, s_z.params)
    # the scattered opt state de-shards to exactly the replicated layout
    _trees_close(s_rep.opt_state, part.deshard_opt_state(s_z.opt_state))
    assert int(s_z.step) == _STEPS


def test_zero1_opt_state_is_physically_scattered(devices):
    """The HBM claim, checked on live buffers: every update-space leaf
    holds exactly ceil(size/N) elements per device, and the accounting
    reports ~1/N per-device bytes."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_train_state(model, tx, jax.random.key(0))
    part = Zero1Partition(tx, state.params, 4)
    opt = part.init_opt_state(state.params, mesh)
    arrs = [x for x in jax.tree.leaves(opt) if x.ndim == 1]
    assert arrs, "momentum trace expected in the scattered opt state"
    for leaf in arrs:
        assert leaf.addressable_shards[0].data.size * 4 == leaf.size
    acct = part.accounting()
    assert acct["optimizer_state_bytes_per_device_sharded"] <= (
        acct["optimizer_state_bytes_replicated"] // 4
        + acct["padding_overhead_bytes_total"] + 64
    )
    assert acct["sharding_factor"] >= 3.5


def test_zero1_scan_parity(devices):
    """Scan-fused K-step: the scattered opt state rides the carry
    UNGATHERED across the K inner steps; per-inner-step losses and the
    final state match the replicated scan."""
    K = 3
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()

    def build(tx, part):
        return make_train_step(
            model, tx, mesh, steps_per_call=K, donate=False, zero1=part)

    tx_rep = make_optimizer(lr=1e-2, momentum=0.9)
    tx_z = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_train_state(model, tx_rep, jax.random.key(0))
    part = Zero1Partition(tx_z, state.params, 4)
    s_rep = jax.device_put(state, replicated_sharding(mesh))
    s_z = part.shard_state(
        state.replace(opt_state=tx_z.init(state.params)), mesh)

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
    _trees_close(s_rep.params, s_z.params)
    _trees_close(s_rep.opt_state, part.deshard_opt_state(s_z.opt_state))


def test_zero1_grad_accum_parity(devices):
    """Gradient accumulation: ONE reduce-scatter for the accumulated
    average; trajectory matches the replicated accumulating step."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()

    def build(tx, part):
        return make_train_step(
            model, tx, mesh, accum_steps=2, donate=False, zero1=part)

    s_rep, s_z, part, losses, _ = _run_pair(
        mesh, model, lambda ax: make_optimizer(
            lr=1e-2, momentum=0.9, zero1_axis=ax), build, n_steps=3)
    np.testing.assert_allclose(losses[0], losses[1], rtol=0, atol=_ATOL)
    _trees_close(s_rep.params, s_z.params)


def test_zero1_adamw_decay_clip_parity(devices):
    """The full production chain — adamw + masked weight decay (the mask
    PRECOMPUTED on original shapes) + global-norm clip (the psum'd sharded
    variant) — matches the replicated chain."""
    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    mask = None

    def make_tx(ax):
        nonlocal mask
        if ax is not None and mask is None:
            state = jax.eval_shape(
                lambda: create_train_state(
                    model, optax.sgd(0.1), jax.random.key(0)))
            mask = _decay_mask(state.params)
        return make_optimizer(
            lr=1e-3, optimizer="adamw", weight_decay=1e-2,
            grad_clip_norm=0.5,  # small enough to actually trigger
            zero1_axis=ax, decay_mask=mask if ax is not None else None,
        )

    def build(tx, part):
        return make_train_step(model, tx, mesh, donate=False, zero1=part)

    s_rep, s_z, part, losses, _ = _run_pair(mesh, model, make_tx, build)
    np.testing.assert_allclose(losses[0], losses[1], rtol=0, atol=_ATOL)
    _trees_close(s_rep.params, s_z.params)
    _trees_close(s_rep.opt_state, part.deshard_opt_state(s_z.opt_state))


def test_zero1_freeze_parity(devices):
    """Path-keyed freeze labels survive flattening (per-leaf sharding
    keeps the tree paths): frozen params stay EXACTLY fixed, trainable
    ones match the replicated run."""
    from tpu_ddp.train.optim import freeze_all_but

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()

    def make_tx(ax):
        return make_optimizer(
            lr=1e-2, momentum=0.9,
            freeze_predicate=freeze_all_but(("fc",)),
            zero1_axis=ax,
        )

    def build(tx, part):
        return make_train_step(model, tx, mesh, donate=False, zero1=part)

    s_rep, s_z, part, losses, _ = _run_pair(mesh, model, make_tx, build)
    _trees_close(s_rep.params, s_z.params)
    init = create_train_state(
        model, make_tx(None), jax.random.key(0)).params
    frozen_moved = [
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(init)[0],
            jax.tree_util.tree_flatten_with_path(s_z.params)[0],
        )
        if not str(path[0]).startswith("['fc")
    ]
    assert max(frozen_moved) == 0.0, "frozen params must not move"


def test_zero1_health_parity(devices):
    """The flight recorder reports the SAME global stats from shard-local
    psum'd norms as the replicated path computes on full trees."""
    from tpu_ddp.health.stats import HEALTH_SCALAR_KEYS, HealthConfig

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    health = HealthConfig(per_layer=True)

    def build(tx, part):
        return make_train_step(
            model, tx, mesh, donate=False, health=health, zero1=part)

    _, _, _, losses, (m_rep, m_z) = _run_pair(
        mesh, model,
        lambda ax: make_optimizer(lr=1e-2, momentum=0.9, zero1_axis=ax),
        build, n_steps=2)
    h_rep, h_z = m_rep["health"], m_z["health"]
    for key in HEALTH_SCALAR_KEYS:
        np.testing.assert_allclose(
            np.asarray(h_rep[key], np.float32),
            np.asarray(h_z[key], np.float32),
            rtol=1e-5, atol=1e-5, err_msg=key)
    for group in ("grad_norm", "param_norm"):
        assert set(h_rep["per_layer"][group]) == set(h_z["per_layer"][group])
        for k in h_rep["per_layer"][group]:
            np.testing.assert_allclose(
                np.asarray(h_rep["per_layer"][group][k]),
                np.asarray(h_z["per_layer"][group][k]),
                rtol=1e-5, atol=1e-5, err_msg=f"{group}/{k}")


def test_zero1_skip_step_guard(devices):
    """A poisoned (all-NaN) batch under skip_nonfinite discards the update
    on params AND the scattered opt state — nothing desyncs, and the next
    clean step continues from the pre-poison state."""
    from tpu_ddp.health.stats import HealthConfig

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _model()
    tx = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_train_state(model, tx, jax.random.key(0))
    part = Zero1Partition(tx, state.params, 4)
    s = part.shard_state(state.replace(opt_state=tx.init(state.params)), mesh)
    step = make_train_step(
        model, tx, mesh, donate=False,
        health=HealthConfig(skip_nonfinite=True), zero1=part)

    clean = _batch(mesh, seed=0)
    s, _ = step(s, clean)
    before_p = jax.device_get(s.params)
    before_o = jax.device_get(part.deshard_opt_state(s.opt_state))
    poisoned = dict(clean, image=jnp.full_like(clean["image"], jnp.nan))
    s, m = step(s, poisoned)
    assert not bool(np.asarray(m["health"]["all_finite"]))
    _trees_close(before_p, jax.device_get(s.params), atol=0)
    _trees_close(
        before_o, jax.device_get(part.deshard_opt_state(s.opt_state)),
        atol=0)
    s, m2 = step(s, clean)  # recovers on clean data
    assert bool(np.asarray(m2["health"]["all_finite"]))


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_zero1_lm_parity(devices):
    """The causal-LM DP step under zero1 matches the replicated one."""
    from tpu_ddp.models.lm import CausalTransformerLM
    from tpu_ddp.train.lm_steps import (
        create_lm_train_state,
        make_lm_train_step,
    )

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = CausalTransformerLM(vocab_size=17, hidden_dim=32, depth=2,
                                num_heads=2)
    tx_rep = make_optimizer(lr=1e-2, momentum=0.9)
    tx_z = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_lm_train_state(model, tx_rep, jax.random.key(0))
    part = Zero1Partition(tx_z, state.params, 4)
    s_rep = jax.device_put(state, replicated_sharding(mesh))
    s_z = part.shard_state(
        state.replace(opt_state=tx_z.init(state.params)), mesh)
    step_rep = make_lm_train_step(model, tx_rep, mesh, donate=False)
    step_z = make_lm_train_step(model, tx_z, mesh, donate=False, zero1=part)
    rng = np.random.default_rng(0)
    for i in range(_STEPS):
        toks = jax.device_put(
            {"tokens": rng.integers(0, 17, (8, 16)).astype(np.int32)},
            {"tokens": batch_sharding(mesh)},
        )
        s_rep, m_rep = step_rep(s_rep, toks)
        s_z, m_z = step_z(s_z, toks)
        np.testing.assert_allclose(
            np.asarray(m_rep["loss"]), np.asarray(m_z["loss"]),
            rtol=0, atol=_ATOL)
    _trees_close(s_rep.params, s_z.params)
    _trees_close(s_rep.opt_state, part.deshard_opt_state(s_z.opt_state))


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_zero1_sp_lm_parity(devices):
    """Sequence-parallel LM on a (data=4, sequence=2) mesh: the zero1
    update (opt scattered over DATA, replicated over sequence) matches the
    replicated SP step."""
    from tpu_ddp.models.lm import CausalTransformerLM
    from tpu_ddp.train.lm_steps import (
        create_lm_train_state,
        make_sp_lm_train_step,
    )

    mesh = create_mesh(MeshSpec(data=4, sequence=2), devices)
    model = CausalTransformerLM(vocab_size=17, hidden_dim=32, depth=2,
                                num_heads=2, sp_axis="sequence")
    tx_rep = make_optimizer(lr=1e-2, momentum=0.9)
    tx_z = make_optimizer(lr=1e-2, momentum=0.9, zero1_axis="data")
    state = create_lm_train_state(model, tx_rep, jax.random.key(0))
    part = Zero1Partition(tx_z, state.params, 4)
    s_rep = jax.device_put(state, replicated_sharding(mesh))
    s_z = part.shard_state(
        state.replace(opt_state=tx_z.init(state.params)), mesh)
    step_rep = make_sp_lm_train_step(model, tx_rep, mesh, donate=False)
    step_z = make_sp_lm_train_step(
        model, tx_z, mesh, donate=False, zero1=part)
    from jax.sharding import NamedSharding, PartitionSpec as P

    tok_sharding = {"tokens": NamedSharding(mesh, P("data", "sequence"))}
    rng = np.random.default_rng(0)
    for i in range(2):
        toks = jax.device_put(
            {"tokens": rng.integers(0, 17, (8, 16)).astype(np.int32)},
            tok_sharding,
        )
        s_rep, m_rep = step_rep(s_rep, toks)
        s_z, m_z = step_z(s_z, toks)
        np.testing.assert_allclose(
            np.asarray(m_rep["loss"]), np.asarray(m_z["loss"]),
            rtol=0, atol=_ATOL)
    _trees_close(s_rep.params, s_z.params)


@pytest.mark.slow  # ~35s SP compile; zero1+sp LM parity stays fast — make test-all
def test_zero1_sp_strategy_parity(devices):
    """build_strategy routes --zero1 through the SP image step; the
    trajectory matches the replicated SP strategy and the strategy carries
    the partition for the trainer's checkpoint/EMA hooks."""
    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.train.strategy import build_strategy

    mesh = create_mesh(MeshSpec(data=4, sequence=2), devices)
    model = MODEL_REGISTRY["vit_s4"](num_classes=10)
    results = {}
    for zero1 in (False, True):
        tx = make_optimizer(
            lr=1e-2, momentum=0.9, zero1_axis="data" if zero1 else None)
        strat = build_strategy(
            "sp", mesh, model, tx, jax.random.key(0), zero1=zero1)
        assert (strat.zero1 is not None) == zero1
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
        results[zero1] = (state, losses)
    np.testing.assert_allclose(
        results[False][1], results[True][1], rtol=0, atol=_ATOL)
    _trees_close(results[False][0].params, results[True][0].params)


def test_zero1_strategy_rejects_sharded_families(devices):
    """--zero1 with a family that already owns its state layout is a
    config error, not a silent no-op."""
    from tpu_ddp.models.zoo import MODEL_REGISTRY
    from tpu_ddp.train.strategy import build_strategy

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = MODEL_REGISTRY["vit_s4"](num_classes=10)
    tx = make_optimizer(lr=1e-2)
    with pytest.raises(ValueError, match="ZeRO-3 subsumes ZeRO-1"):
        build_strategy("fsdp", mesh, model, tx, jax.random.key(0),
                       zero1=True)


def test_zero1_config_guards():
    """Fail-fast surface: lamb + zero1 and non-dp/sp parallelism are
    rejected at validate(); the optimizer factory demands a precomputed
    decay mask in the sharded update space."""
    from tpu_ddp.train.trainer import TrainConfig

    with pytest.raises(ValueError, match="lamb"):
        TrainConfig(zero1=True, optimizer="lamb").validate()
    with pytest.raises(ValueError, match="zero1"):
        TrainConfig(zero1=True, parallelism="fsdp").validate()
    with pytest.raises(ValueError, match="decay_mask"):
        make_optimizer(lr=1e-2, weight_decay=1e-4, zero1_axis="data")
    with pytest.raises(ValueError, match="lamb"):
        make_optimizer(lr=1e-2, optimizer="lamb", zero1_axis="data")


def _trainer_config(tmp_path, zero1, *, resume=False, epochs=2, ckpt=True,
                    synthetic_size=256):
    from tpu_ddp.train.trainer import TrainConfig

    return TrainConfig(
        synthetic_data=True, synthetic_size=synthetic_size, epochs=epochs,
        per_shard_batch=8, n_devices=4, momentum=0.9, lr=1e-2,
        zero1=zero1, seed=0, prefetch_depth=0, log_every_epochs=1,
        checkpoint_dir=str(tmp_path / "ckpt") if ckpt else None,
        checkpoint_every_epochs=1, resume=resume,
    )


def _original_layout(trainer):
    """(params, opt_state) in the ONE layout checkpoints persist."""
    opt = trainer.state.opt_state
    if trainer._zero1 is not None:
        opt = trainer._zero1.deshard_opt_state(opt)
    return trainer.state.params, opt


@pytest.mark.slow  # ~25s per direction (two Trainers each); the cross-layout
# elastic resume pin covers the scatter/gather math — make test-all
@pytest.mark.parametrize("first,second", [(True, False), (False, True)])
def test_zero1_checkpoint_roundtrip(tmp_path, devices, first, second):
    """--resume composes with --zero1 in EITHER direction: a run trains
    epoch 1 with one layout, a second run resumes epoch 2 with the other,
    and the result matches an uninterrupted replicated run — because
    checkpoints always persist the de-sharded layout.

    Two pins. The round trip itself is EXACT: what the second run restores
    into its own layout is bit for bit what the first run held. The
    continuation is held to 1e-4 of the uninterrupted run over epochs of
    2 steps. It is kept that short because the two layouts sum in different
    orders and this deep tied-block model amplifies the difference
    erratically through its momentum — measured on jax 0.9.0's XLA:CPU with
    NO checkpoint in between, replicated against --zero1 (and --zero3, the
    same figures), max abs difference of the momentum: 5e-7 after 4 steps,
    2.7e-3 after 8 (another 128-image set), 4e-4 after 16 — so a longer
    run would measure the drift and not the resume."""
    from tpu_ddp.train.trainer import Trainer

    def cfg(path, zero1, **kw):  # 64/(8*4) = 2 steps per epoch
        return _trainer_config(path, zero1, synthetic_size=64, **kw)

    ref = Trainer(cfg(tmp_path / "ref", False))
    ref.run()

    a = Trainer(cfg(tmp_path, first, epochs=1))
    a.run()
    b = Trainer(cfg(tmp_path, second, resume=True))
    assert b.resumed_step == 2
    _trees_close(_original_layout(a), _original_layout(b), atol=0)
    b.run()
    assert int(b.state.step) == int(ref.state.step) == 4
    _trees_close(_original_layout(ref), _original_layout(b), atol=1e-4)


@pytest.mark.slow  # ~22s; test_ema covers the trainer EMA path — make test-all
def test_zero1_trainer_ema_eval(devices):
    """--ema-decay composes: the EMA shadow lives as update-space shards
    inside the scattered opt state, and eval de-flattens it back — final
    eval matches the replicated EMA run."""
    import tempfile

    from tpu_ddp.train.trainer import Trainer

    accs = {}
    with tempfile.TemporaryDirectory() as td:
        for zero1 in (False, True):
            cfg = dataclasses.replace(
                _trainer_config(
                    __import__("pathlib").Path(td) / str(zero1), zero1,
                    ckpt=False),
                ema_decay=0.9, eval_each_epoch=False, epochs=1,
            )
            t = Trainer(cfg)
            t.run()
            accs[zero1] = t.evaluate()
            # the eval source really is the (de-flattened) EMA tree
            src = t._eval_source_state()
            from tpu_ddp.train.optim import find_ema

            ema = find_ema(t.state.opt_state)
            if zero1:
                ema = t._zero1.unflatten(ema)
            _trees_close(src.params, ema, atol=0)
    np.testing.assert_allclose(accs[False][1], accs[True][1], atol=1e-4)
    np.testing.assert_allclose(accs[False][0], accs[True][0], atol=1e-6)


def test_zero1_sharded_clip_matches_optax(devices):
    """clip_by_global_norm_sharded on scattered shards == optax's clip on
    the full tree (both trigger and no-trigger regimes)."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    full = {"a": jnp.arange(10, dtype=jnp.float32) / 10.0,
            "b": jnp.ones((6,), jnp.float32)}
    for max_norm in (0.5, 100.0):  # triggering and not
        ref, _ = optax.clip_by_global_norm(max_norm).update(full, None)

        def body(tree):
            idx = lax.axis_index("data")

            def shard(x):
                pad = (-x.size) % 4
                xp = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
                s = xp.size // 4
                return lax.dynamic_slice_in_dim(xp, idx * s, s)

            shards = jax.tree.map(shard, tree)
            clipped, _ = clip_by_global_norm_sharded(
                max_norm, "data").update(shards, None)
            return clipped

        # out_specs P("data") reassembles the shards in axis order
        out = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(),), out_specs=P("data")))(full)
        for k in full:
            np.testing.assert_allclose(
                np.asarray(out[k])[: full[k].size], np.asarray(ref[k]),
                rtol=1e-6, atol=1e-7, err_msg=f"max_norm={max_norm}/{k}")
