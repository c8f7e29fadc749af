"""Parameter EMA (--ema-decay): transform math, sharding inheritance,
trainer eval swap, and checkpoint round-trip."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tpu_ddp.train.optim import find_ema, make_optimizer, params_ema


def test_params_ema_matches_manual_recursion():
    """After k steps, the carried EMA equals the hand-computed recursion
    over the post-update param trajectory."""
    decay = 0.9
    tx = optax.chain(optax.sgd(0.1), params_ema(decay))
    params = {"w": jnp.ones((3,)), "b": jnp.zeros(())}
    state = tx.init(params)

    expect = dict(params)
    for k in range(5):
        grads = {"w": jnp.full((3,), float(k + 1)), "b": jnp.asarray(1.0)}
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        expect = {
            n: decay * expect[n] + (1 - decay) * params[n] for n in expect
        }
    ema = find_ema(state)
    assert ema is not None
    for n in params:
        np.testing.assert_allclose(ema[n], expect[n], rtol=1e-6)
        # the shadow must differ from the live params (it lags them)
        assert not np.allclose(ema[n], params[n])


def test_ema_rejects_degenerate_decay():
    for bad in (0.0, 1.0, -0.1, 1.5):
        with pytest.raises(ValueError):
            params_ema(bad)


def test_find_ema_none_without_ema():
    tx = make_optimizer(lr=0.1)
    state = tx.init({"w": jnp.ones((2,))})
    assert find_ema(state) is None


def test_make_optimizer_ema_composes_with_freeze_and_clip():
    """EMA chained outermost-last: frozen params receive zero updates, so
    their EMA converges toward their (constant) value; trainable params'
    EMA tracks the clipped, lr-scaled trajectory."""
    tx = make_optimizer(
        lr=0.5, grad_clip_norm=1.0, ema_decay=0.5,
        freeze_predicate=lambda path, leaf: path[0].key == "frozen",
    )
    params = {"frozen": jnp.asarray(2.0), "live": jnp.asarray(0.0)}
    state = tx.init(params)
    for _ in range(3):
        grads = {"frozen": jnp.asarray(10.0), "live": jnp.asarray(1.0)}
        updates, state = tx.update(grads, state, params)
        params = optax.apply_updates(params, updates)
    assert float(params["frozen"]) == 2.0
    ema = find_ema(state)
    np.testing.assert_allclose(ema["frozen"], 2.0)  # constant -> EMA exact
    assert float(params["live"]) < 0.0  # descended
    assert float(ema["live"]) != float(params["live"])


def test_ema_state_inherits_param_shardings():
    """opt_state_specs suffix-matches EmaState leaves to the param tree, so
    ZeRO shards the shadow exactly like the params it mirrors."""
    from tpu_ddp.parallel.partitioning import opt_state_specs

    tx = make_optimizer(lr=0.1, momentum=0.9, ema_decay=0.99)
    params = {"conv": {"kernel": jnp.ones((3, 3, 4, 8))},
              "fc": {"kernel": jnp.ones((8, 2))}}
    opt_state = tx.init(params)
    param_specs = {"conv": {"kernel": P("data")}, "fc": {"kernel": P(None)}}
    specs = opt_state_specs(opt_state, param_specs)
    ema_specs = find_ema(specs)
    assert ema_specs is not None
    assert ema_specs["conv"]["kernel"] == P("data")
    assert ema_specs["fc"]["kernel"] == P(None)


def test_ema_updates_inside_scan_fused_step():
    """The flagship config fuses K optimizer steps into one dispatch
    (``make_train_step(steps_per_call=K)``); the EMA shadow must advance once
    per INNER step, not once per dispatch — K fused steps and K unfused steps from
    the same start must produce the same shadow."""
    from tpu_ddp.data import synthetic_cifar10
    from tpu_ddp.models import NetResDeep
    from tpu_ddp.parallel import (
        MeshSpec,
        batch_sharding,
        create_mesh,
        stacked_batch_sharding,
    )
    from tpu_ddp.train import (
        create_train_state,
        make_train_step,
    )

    K, per_shard = 3, 4
    mesh = create_mesh(MeshSpec(data=-1), jax.devices())
    n = len(jax.devices())
    gb = per_shard * n
    model = NetResDeep(n_blocks=2)
    tx = make_optimizer(lr=0.05, ema_decay=0.8)
    imgs, labels = synthetic_cifar10(K * gb, seed=3)
    imgs = imgs.astype(np.float32)

    fused_state = create_train_state(model, tx, jax.random.key(0))
    fused = make_train_step(model, tx, mesh, steps_per_call=K,
                            donate=False)
    batch_k = jax.device_put(
        {"image": imgs.reshape(K, gb, 32, 32, 3),
         "label": labels.reshape(K, gb),
         "mask": np.ones((K, gb), bool)},
        stacked_batch_sharding(mesh))
    fused_state, _ = fused(fused_state, batch_k)

    step_state = create_train_state(model, tx, jax.random.key(0))
    step = make_train_step(model, tx, mesh, donate=False)
    for k in range(K):
        b = jax.device_put(
            {"image": imgs[k * gb:(k + 1) * gb],
             "label": labels[k * gb:(k + 1) * gb],
             "mask": np.ones(gb, bool)},
            batch_sharding(mesh))
        step_state, _ = step(step_state, b)

    ema_fused = find_ema(fused_state.opt_state)
    ema_step = find_ema(step_state.opt_state)
    for a, b in zip(jax.tree.leaves(ema_fused), jax.tree.leaves(ema_step)):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_trainer_ema_eval_and_resume(tmp_path):
    """End-to-end: train with --ema-decay, eval reads the EMA weights, and
    a checkpoint round-trip preserves the shadow exactly."""
    from tpu_ddp.train.trainer import TrainConfig, Trainer

    common = dict(
        synthetic_data=True, synthetic_size=128, per_shard_batch=4,
        lr=0.05, ema_decay=0.9, seed=0, log_every_epochs=1,
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every_epochs=2,
    )
    t = Trainer(TrainConfig(epochs=2, **common))
    t.run()
    ema = find_ema(t.state.opt_state)
    assert ema is not None
    # the shadow lags the live params after real training steps
    diffs = jax.tree.map(
        lambda e, p: float(jnp.max(jnp.abs(e - p))), ema, t.state.params)
    assert max(jax.tree.leaves(diffs)) > 0
    acc, loss = t.evaluate()  # reads the EMA weights (config.ema_decay > 0)
    assert 0.0 <= acc <= 1.0 and np.isfinite(loss)

    t2 = Trainer(TrainConfig(epochs=2, resume=True, **common))
    ema2 = find_ema(t2.state.opt_state)
    same = jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), ema, ema2)
    assert all(jax.tree.leaves(same)), "EMA shadow not preserved by resume"
