"""The state is born placed: when ``Trainer.__init__`` returns, every leaf of
``trainer.state`` is a committed array laid out as a step returns it, so the
step function is traced, lowered and compiled once per ``Trainer`` and not a
second time for its own output.

CPU, a tiny NetResDeep, virtual devices for the data axis.
"""

import jax
import numpy as np
import pytest

from tpu_ddp.parallel.mesh import replicated_sharding


def _config(**extra):
    from tpu_ddp.train.trainer import TrainConfig

    fields = dict(
        synthetic_data=True, synthetic_size=64, per_shard_batch=4, epochs=1,
        model="netresdeep", n_chans1=8, n_blocks=2, n_devices=2,
        prefetch_depth=0)
    fields.update(extra)
    return TrainConfig(**fields)


def _assert_placed(trainer):
    """Every leaf committed, and where the trainer says a step leaves it."""
    wanted = trainer.state_shardings
    if wanted is None:
        rep = replicated_sharding(trainer.mesh)
        wanted = jax.tree.map(lambda _: rep, trainer.state)
    paths = jax.tree_util.tree_leaves_with_path(trainer.state)
    shardings = jax.tree.leaves(wanted)
    assert len(paths) == len(shardings) > 0
    for (path, leaf), sharding in zip(paths, shardings):
        name = jax.tree_util.keystr(path)
        assert isinstance(leaf, jax.Array), name
        assert leaf.committed, f"{name} is not committed"
        assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim), (
            f"{name}: {leaf.sharding} where a step returns {sharding}")


def _assert_one_of_each(registry, trainer):
    """The step function this run dispatched (8 steps an epoch: at least three
    dispatches): one trace, one lowering, one compilation or load."""
    from tpu_ddp.telemetry.jax_hooks import FUNCTIONS_TABLE

    name = (trainer.multi_step or trainer.train_step).__name__
    row = registry().snapshot(tables=True)["tables"][FUNCTIONS_TABLE][name]
    built = row.get("compilations", 0) + row.get("cache_loads", 0)
    assert (row["traces"], row["lowerings"], built) == (1, 1, 1), (name, row)


CASES = {
    "fresh_dp": {},
    "zero1": {"zero1": True},
    "zero3": {"zero3": True},
    "int8_error_feedback": {
        "grad_compress": "int8", "grad_compress_error_feedback": True},
    "steps_per_call_2": {"steps_per_call": 2},
    "grad_accum_2": {"grad_accum_steps": 2},
    "fsdp": {"parallelism": "fsdp"},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_state_is_born_placed_and_the_step_is_built_once(
        devices, fresh_registry, case):
    from tpu_ddp.train.trainer import Trainer

    trainer = Trainer(_config(**CASES[case]))
    _assert_placed(trainer)
    trainer.run()
    assert int(trainer.state.step) >= 3
    _assert_one_of_each(fresh_registry, trainer)


@pytest.mark.parametrize("flags", [{}, {"zero1": True}],
                         ids=["resume_dp", "resume_zero1"])
def test_a_resumed_state_is_placed_too(devices, fresh_registry, tmp_path,
                                       flags):
    from tpu_ddp.telemetry.registry import reset_default_registry
    from tpu_ddp.train.trainer import Trainer

    ckpt = dict(checkpoint_dir=str(tmp_path / "ckpt"),
                checkpoint_every_epochs=1, log_every_epochs=1)
    first = Trainer(_config(**ckpt))
    first.run()
    saved = jax.device_get(first.state.params)
    del first
    reset_default_registry()  # count the second life alone

    trainer = Trainer(_config(resume=True, epochs=2, **ckpt, **flags))
    assert trainer.resumed_step == 8
    _assert_placed(trainer)
    jax.tree.map(np.testing.assert_array_equal,
                 jax.device_get(trainer.state.params), saved)
    trainer.run()
    assert int(trainer.state.step) == 16
    _assert_one_of_each(fresh_registry, trainer)


def test_placement_changes_no_number(devices):
    """The placed state is the unplaced one bit for bit, and three steps from
    either give the same parameters and losses bit for bit."""
    from tpu_ddp.models import NetResDeep
    from tpu_ddp.parallel.mesh import batch_sharding, data_parallel_mesh
    from tpu_ddp.train.optim import make_optimizer
    from tpu_ddp.train.state import create_train_state
    from tpu_ddp.train.steps import make_train_step

    mesh = data_parallel_mesh(4)
    model = NetResDeep(n_chans1=8, n_blocks=2)
    tx = make_optimizer(lr=0.05, momentum=0.9)
    unplaced = create_train_state(model, tx, jax.random.key(3))
    placed = jax.device_put(unplaced, replicated_sharding(mesh))
    assert not any(x.committed for x in jax.tree.leaves(unplaced))
    assert all(x.committed for x in jax.tree.leaves(placed))

    def same(a, b):
        jax.tree.map(np.testing.assert_array_equal,
                     jax.device_get(a), jax.device_get(b))

    same(unplaced, placed)

    rng = np.random.default_rng(0)
    batches = [jax.device_put(
        {"image": rng.normal(size=(16, 32, 32, 3)).astype(np.float32),
         "label": rng.integers(0, 10, size=(16,)).astype(np.int32),
         "mask": np.ones((16,), bool)}, batch_sharding(mesh))
        for _ in range(3)]

    def three_steps(state):
        # a builder each: nothing of one run's compilation serves the other
        step = make_train_step(model, tx, mesh, donate=False)
        losses = []
        for batch in batches:
            state, metrics = step(state, batch)
            losses.append(np.asarray(metrics["loss"]))
        return state, losses

    from_unplaced, losses_unplaced = three_steps(unplaced)
    from_placed, losses_placed = three_steps(placed)
    same(from_unplaced, from_placed)
    np.testing.assert_array_equal(losses_unplaced, losses_placed)
    assert np.all(np.isfinite(losses_placed))
    assert losses_placed[0] != losses_placed[2]  # it did train
