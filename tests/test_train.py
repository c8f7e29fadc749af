"""Trainer orchestration: prefetch parity, fused-step parity, freeze masks.

The reference's only "test" of its loop was eyeballing printed losses
(SURVEY.md §4); here the loop's execution variants must be bit-identical:
however batches are assembled (direct, threaded prefetch, native ring
prefetch) and however steps are dispatched (one-by-one or scan-fused), the
same data must reach the same math.
"""

import jax
import pytest
import numpy as np

from tpu_ddp.train.trainer import TrainConfig, Trainer


def _run(seed=0, **overrides) -> list:
    cfg = TrainConfig(
        synthetic_data=True,
        synthetic_size=200,  # not divisible by global batch: exercises
        epochs=2,            # the masked short-batch + remainder paths
        per_shard_batch=4,
        seed=seed,
        log_every_epochs=1,
        **overrides,
    )
    trainer = Trainer(cfg)
    trainer.run()
    return trainer.history["train_loss"]


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_prefetched_epoch_matches_direct(devices):
    """prefetch_depth>0 must not change a single batch: loss history is
    bit-identical to the unprefetched run."""
    direct = _run(prefetch_depth=0)
    prefetched = _run(prefetch_depth=3)
    np.testing.assert_array_equal(direct, prefetched)


@pytest.mark.slow  # ~30-55s each: make test-all
def test_prefetched_fused_scan_matches_direct(devices):
    """Fused K-step groups assembled as ONE native gather (concatenated
    indices) == K separate gathers stacked on host."""
    direct = _run(steps_per_call=4, prefetch_depth=0)
    prefetched = _run(steps_per_call=4, prefetch_depth=2)
    np.testing.assert_array_equal(direct, prefetched)


@pytest.mark.slow  # ~30-55s each: make test-all
def test_fused_scan_matches_single_steps(devices):
    """steps_per_call must be a pure dispatch optimization."""
    single = _run(prefetch_depth=0)
    fused = _run(steps_per_call=4, prefetch_depth=0)
    np.testing.assert_allclose(single, fused, rtol=1e-6)


@pytest.mark.slow  # ~30-55s each: make test-all
def test_resume_continues_identically(devices, tmp_path):
    """Checkpoint at epoch 2 then resume for epochs 3-4 must reproduce the
    uninterrupted 4-epoch run's loss trajectory exactly (state + data order
    both restored) — the resume capability the reference lacks entirely
    (SURVEY.md §5.4: save-only, no loading code)."""
    common = dict(
        synthetic_data=True,
        synthetic_size=200,
        per_shard_batch=4,
        seed=0,
        log_every_epochs=1,
        checkpoint_every_epochs=2,
    )
    uninterrupted = TrainConfig(epochs=4, **common)
    t_full = Trainer(uninterrupted)
    t_full.run()

    ck = str(tmp_path / "ck")
    t_half = Trainer(TrainConfig(epochs=2, checkpoint_dir=ck, **common))
    t_half.run()
    t_resumed = Trainer(
        TrainConfig(epochs=4, checkpoint_dir=ck, resume=True, **common)
    )
    t_resumed.run()
    assert t_resumed.history["epoch"] == [3, 4]
    np.testing.assert_allclose(
        t_resumed.history["train_loss"],
        t_full.history["train_loss"][2:],
        rtol=1e-6,
    )


def test_multihost_put_path_degenerate_single_process(devices):
    """The multi-host assembly path (make_array_from_process_local_data)
    must agree with device_put when this process owns every device — the
    degenerate case runnable without a pod."""
    cfg = TrainConfig(
        synthetic_data=True, synthetic_size=64, per_shard_batch=4, epochs=1
    )
    t = Trainer(cfg)
    batch = next(iter(t.train_loader))
    direct = t._put(batch)
    t._multihost = True
    assembled = t._put(batch)
    t._multihost = False
    for key in batch:
        np.testing.assert_array_equal(
            np.asarray(direct[key]), np.asarray(assembled[key])
        )
        assert assembled[key].sharding == direct[key].sharding
    t.close()


def test_cli_config_mapping(devices):
    """argparse surface -> TrainConfig (the reference's config story is
    hardcoded constants + a vestigial argparse, SURVEY.md §5.6)."""
    from tpu_ddp.cli.train import build_parser, config_from_args

    args = build_parser().parse_args(
        [
            "--device", "cpu",
            "--synthetic-data",
            "--epochs", "7",
            "--global-batch-size", "64",
            "--lr", "0.5",
            "--momentum", "0.9",
            "--schedule", "cosine",
            "--model", "resnet18",
            "--dataset", "cifar100",
            "--steps-per-call", "8",
            "--prefetch-depth", "0",
            "--freeze", "head", "fc",
            "--faithful-epoch-order",
        ]
    )
    cfg = config_from_args(args)
    assert cfg.epochs == 7
    assert cfg.per_shard_batch == 64 // len(jax.devices())
    assert cfg.lr == 0.5 and cfg.momentum == 0.9
    assert cfg.schedule == "cosine"
    assert cfg.model == "resnet18"
    assert cfg.num_classes == 100  # inferred from --dataset cifar100
    assert cfg.steps_per_call == 8
    assert cfg.prefetch_depth == 0
    assert cfg.freeze_prefixes == ("head", "fc")
    assert cfg.reshuffle_each_epoch is False


def test_grad_accum_matches_full_batch(devices):
    """K-microbatch gradient accumulation must produce the SAME update as
    the full-batch step (exact for a BN-free model with equal microbatch
    counts: the per-microbatch pmean-before-AD sync is preserved and the
    outer mean commutes with AD)."""
    import numpy as np

    from tpu_ddp.data import synthetic_cifar10
    from tpu_ddp.models.vit import ViT
    from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
    from tpu_ddp.train import create_train_state, make_optimizer, make_train_step

    mesh = create_mesh(MeshSpec(data=-1))
    model = ViT(patch_size=8, hidden_dim=32, depth=2, num_heads=2)
    tx = make_optimizer(lr=0.05, momentum=0.9)
    state = create_train_state(model, tx, jax.random.key(0))
    imgs, labels = synthetic_cifar10(8 * 16, seed=11)
    batch = {
        "image": imgs, "label": labels, "mask": np.ones(len(labels), bool)
    }
    sharding = batch_sharding(mesh)
    batch = jax.device_put(batch, sharding)

    full = make_train_step(model, tx, mesh, donate=False)
    accum = make_train_step(mesh=mesh, model=model, tx=tx,
                            accum_steps=4, donate=False)
    s_full, m_full = full(state, batch)
    s_acc, m_acc = accum(state, batch)
    assert set(m_acc) == set(m_full)  # no aux_loss: this model sows none
    np.testing.assert_allclose(
        float(m_full["loss"]), float(m_acc["loss"]), rtol=1e-5
    )
    for a, b in zip(
        jax.tree.leaves(s_full.params), jax.tree.leaves(s_acc.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-6, rtol=1e-5
        )


@pytest.mark.slow  # subprocess CLI e2e; the grad-accum math pin stays fast
def test_grad_accum_cli_and_guards(tmp_path, devices):
    from tpu_ddp.cli.train import main

    result = main([
        "--device", "cpu", "--synthetic-data", "--synthetic-size", "128",
        "--epochs", "1", "--batch-size", "8", "--grad-accum-steps", "2",
        "--log-every-epochs", "1",
    ])
    assert np.isfinite(result["test_accuracy"])

    import pytest

    with pytest.raises(ValueError, match="opposite trades"):
        main([
            "--device", "cpu", "--synthetic-data", "--synthetic-size", "128",
            "--epochs", "1", "--batch-size", "8", "--grad-accum-steps", "2",
            "--steps-per-call", "4",
        ])


def test_weight_decay_excludes_bias_and_bn(devices):
    """--weight-decay must decay kernels ONLY: BN scales/offsets and biases
    are excluded (the standard recipe exclusion; the reference has no wd at
    all, main.py:27)."""
    import numpy as np

    from tpu_ddp.models import NetResDeep
    from tpu_ddp.train import create_train_state, make_optimizer

    model = NetResDeep(n_chans1=8, n_blocks=1)
    tx = make_optimizer(lr=1.0, weight_decay=0.1)
    state = create_train_state(model, tx, jax.random.key(0))
    import jax.numpy as jnp

    zero_grads = jax.tree.map(jnp.zeros_like, state.params)
    updates, _ = tx.update(zero_grads, state.opt_state, state.params)
    flat = jax.tree_util.tree_flatten_with_path(updates)[0]
    for path, u in flat:
        name = jax.tree_util.keystr(path)
        if np.asarray(u).ndim >= 2:
            assert np.abs(np.asarray(u)).max() > 0, f"kernel {name} not decayed"
        else:
            assert np.abs(np.asarray(u)).max() == 0, f"{name} decayed"


def test_grad_clip_norm_scales_update(devices):
    """--grad-clip-norm clips the GLOBAL gradient norm before the update,
    and sees the RAW gradient: the (coupled, pre-lr) weight-decay term is
    added inside (after) the clip, so with decay on, the update's norm
    exceeds the clip cap."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tpu_ddp.models import NetResDeep
    from tpu_ddp.train import create_train_state, make_optimizer

    model = NetResDeep(n_chans1=8, n_blocks=1)
    tx = make_optimizer(lr=1.0, grad_clip_norm=1.0)
    state = create_train_state(model, tx, jax.random.key(0))

    big_grads = jax.tree.map(lambda p: jnp.full_like(p, 100.0), state.params)
    updates, _ = tx.update(big_grads, state.opt_state, state.params)
    gnorm = float(optax.global_norm(updates))
    np.testing.assert_allclose(gnorm, 1.0, rtol=1e-5)  # clipped to the cap

    small_grads = jax.tree.map(lambda p: jnp.full_like(p, 1e-4), state.params)
    # optax transforms are pure: reuse the same tx/state
    updates2, _ = tx.update(small_grads, state.opt_state, state.params)
    # under the cap: untouched (sgd lr=1.0 negates only)
    for a, b in zip(jax.tree.leaves(updates2), jax.tree.leaves(small_grads)):
        np.testing.assert_allclose(np.asarray(a), -np.asarray(b), rtol=1e-6)

    # ordering pin: with weight decay ON, the decay term is added AFTER the
    # clip, so the final update norm exceeds the cap (a flipped chain that
    # clips the decayed gradient would land at exactly 1.0 and fail here)
    tx3 = make_optimizer(lr=1.0, grad_clip_norm=1.0, weight_decay=0.1)
    state3 = create_train_state(model, tx3, jax.random.key(0))
    updates3, _ = tx3.update(big_grads, state3.opt_state, state3.params)
    assert float(optax.global_norm(updates3)) > 1.001


# -- the one step body: accumulation is a stage of it, not a copy ----------

def _routed_like_model():
    """BN-free (so microbatches and the full batch normalise alike), and it
    sows what a routed layer sows: an auxiliary loss and a counter."""
    import flax.linen as nn
    import jax.numpy as jnp

    class Sower(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            h = nn.Dense(12)(x.reshape(x.shape[0], -1))
            self.sow("aux_loss", "balance", jnp.mean(h ** 2))
            self.sow("counters", "rows", jnp.float32(x.shape[0])[None])
            return nn.Dense(10)(nn.relu(h))

    return Sower()


def _layout_state(layout, model, mesh):
    """``(tx, state, builder keywords)`` of a DP state layout, built the
    way the layouts' own tests build them."""
    from tpu_ddp.parallel.compression import GradCompression, GradCompressor
    from tpu_ddp.parallel.mesh import replicated_sharding
    from tpu_ddp.parallel.zero import Zero1Partition, Zero3Partition
    from tpu_ddp.train import create_train_state, make_optimizer

    n = mesh.shape["data"]
    sharded = layout in ("zero1", "zero3")
    tx = make_optimizer(lr=0.05, momentum=0.9,
                        zero1_axis="data" if sharded else None)
    state = create_train_state(model, tx, jax.random.key(0))
    if sharded:
        cls = Zero3Partition if layout == "zero3" else Zero1Partition
        part = cls(tx, state.params, n)
        return tx, part.shard_state(state, mesh), {"zero1": part}
    state = jax.device_put(state, replicated_sharding(mesh))
    if layout == "int8_ef":
        comp = GradCompressor(
            GradCompression(mode="int8", block=64, error_feedback=True),
            state.params, n)
        state = state.replace(grad_residual=comp.init_residual(mesh))
        return tx, state, {"compress": comp}
    return tx, state, {}


@pytest.mark.parametrize("health", [False, True], ids=["plain", "health"])
@pytest.mark.parametrize("layout", ["dp", "zero1", "zero3", "int8_ef"])
def test_accumulation_is_a_stage_of_the_one_step(devices, layout, health):
    """``accum_steps=2`` changes how the gradients are made and nothing
    after: the same state tree, the same metric keys (``aux_loss`` and the
    model's counters among them) and, on equal unmasked microbatches of a
    BN-free model, the ``accum_steps=1`` step's parameters."""
    from tpu_ddp.data import synthetic_cifar10
    from tpu_ddp.health.stats import HealthConfig
    from tpu_ddp.parallel import MeshSpec, batch_sharding, create_mesh
    from tpu_ddp.train import make_train_step

    mesh = create_mesh(MeshSpec(data=4), devices[:4])
    model = _routed_like_model()
    tx, state, keywords = _layout_state(layout, model, mesh)
    if health:
        keywords["health"] = HealthConfig(per_layer=True, skip_nonfinite=True)
    imgs, labels = synthetic_cifar10(32, seed=5)
    batch = jax.device_put(
        {"image": imgs.astype(np.float32), "label": labels,
         "mask": np.ones(32, bool)}, batch_sharding(mesh))

    out = {}
    for accum in (1, 2):
        step = make_train_step(model, tx, mesh, accum_steps=accum,
                               donate=False, **keywords)
        out[accum] = step(state, batch)
    (s1, m1), (s2, m2) = out[1], out[2]
    assert jax.tree.structure(s1) == jax.tree.structure(s2)
    assert jax.tree.structure(m1) == jax.tree.structure(m2)
    assert {"loss", "aux_loss", "counters", "accuracy"} <= set(m2)
    assert ("health" in m2) == health
    np.testing.assert_array_equal(np.asarray(m2["counters"]["rows"]), [[32.]])
    for key in ("loss", "aux_loss", "accuracy"):
        np.testing.assert_allclose(
            float(m1[key]), float(m2[key]), rtol=1e-5)
    # the int8 ring rounds each block of the gradient to 1/127 of its
    # largest entry: two gradients a float32 rounding apart may part by one
    # such notch, times the learning rate
    atol = 2e-4 if layout == "int8_ef" else 2e-6
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=atol, rtol=1e-5)


@pytest.mark.parametrize("g", [1, 2])
def test_the_trainer_and_the_abstract_twin_choose_the_step_alike(devices, g):
    """One builder takes ``accum_steps``: the ``Trainer`` and
    ``build_abstract_step`` (what analyze, lint, tune and memplan compile)
    lower to the same text, and only an accumulating step has a loop."""
    from tpu_ddp.train.strategy import build_abstract_step

    trainer = Trainer(TrainConfig(
        synthetic_data=True, synthetic_size=64, per_shard_batch=4, epochs=1,
        model="netresdeep", n_chans1=8, n_blocks=2, n_devices=2,
        prefetch_depth=0, grad_accum_steps=g))
    try:
        inputs = trainer.abstract_step_inputs()
        live = trainer.train_step.lower(*inputs).as_text()
        twin, _ = build_abstract_step(
            "dp", trainer.model, trainer.tx, trainer.mesh,
            grad_accum_steps=g)
        assert twin.lower(*inputs).as_text() == live
        assert ("stablehlo.while" in live) == (g > 1)
    finally:
        trainer.close()


# -- the run loop's seam: one list of what is told of every step -----------

class _Recorder:
    def __init__(self):
        self.steps, self.closed = [], 0

    def on_step(self, step):
        self.steps.append(step)

    def close(self):
        self.closed += 1


def _seam_config(**extra):
    fields = dict(
        synthetic_data=True, synthetic_size=56, per_shard_batch=4, epochs=1,
        model="netresdeep", n_chans1=8, n_blocks=1, n_devices=2,
        prefetch_depth=0)  # 7 steps an epoch
    fields.update(extra)
    return TrainConfig(**fields)


def test_nothing_configured_means_nobody_to_tell(devices):
    trainer = Trainer(_seam_config())
    try:
        assert trainer._watchers == []
    finally:
        trainer.close()


@pytest.mark.parametrize("k,told", [
    (1, [1, 2, 3, 4, 5, 6, 7]),
    (3, [3, 6, 7]),  # two fused dispatches, the remainder as single steps
])
def test_a_watcher_hears_of_every_dispatch_and_is_closed_once(
        devices, k, told):
    """Adding a watcher is appending it: the loop tells it the host's
    global step once a dispatch, ``run`` + ``close`` close it once."""
    trainer = Trainer(_seam_config(steps_per_call=k))
    recorder = _Recorder()
    trainer._watchers.append(recorder)
    trainer.run(close=False)
    trainer.close()
    assert recorder.steps == told
    assert recorder.closed == 1
    assert trainer._watchers == []


def test_the_watchdog_beats_before_chaos_hears_of_the_step(
        devices, tmp_path, monkeypatch):
    """An injected hang must leave the beat before it as the last one."""
    import json

    from tpu_ddp.chaos.inject import ChaosInjector
    from tpu_ddp.telemetry import HangWatchdog

    spec = tmp_path / "chaos.json"
    spec.write_text(json.dumps({"chaos_schema_version": 1, "faults": [
        {"kind": "data_stall", "step": 10_000, "stall_s": 0.0}]}))
    log = []

    def logged(who, on_step):
        def wrapper(self, step):
            log.append((who, step))
            on_step(self, step)
        return wrapper

    monkeypatch.setattr(
        HangWatchdog, "on_step", logged("beat", HangWatchdog.on_step))
    monkeypatch.setattr(
        ChaosInjector, "on_step", logged("chaos", ChaosInjector.on_step))
    trainer = Trainer(_seam_config(
        chaos_spec=str(spec), telemetry_dir=str(tmp_path / "run"),
        watchdog_deadline_seconds=300.0))
    assert [type(w).__name__ for w in trainer._watchers] == [
        "ChaosInjector", "StageMonitor", "CaptureManager", "MemorySampler"]
    trainer.run()
    assert log == [(who, step) for step in range(1, 8)
                   for who in ("beat", "chaos")]
