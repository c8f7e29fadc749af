"""Sequence-parallel ViT training tests on a 2x4 (data x sequence) virtual
mesh: SP loss must equal the non-SP loss on identical params/data, and a
training step must run and reduce loss."""

import pytest
import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from tpu_ddp.data import synthetic_cifar10
from tpu_ddp.models.vit import ViT
from tpu_ddp.parallel import MeshSpec, create_mesh
from tpu_ddp.parallel.sequence_parallel import make_sp_train_step
from tpu_ddp.train import create_train_state, make_optimizer
from tpu_ddp.train.losses import cross_entropy_loss


def _setup(data=2, seq=4):
    mesh = create_mesh(MeshSpec(data=data, sequence=seq))
    sp_model = ViT(depth=2, hidden_dim=64, num_heads=2, sp_axis="sequence")
    ref_model = ViT(depth=2, hidden_dim=64, num_heads=2)
    tx = make_optimizer(lr=0.05)
    # init via the NON-SP module (no axis bound outside shard_map); the SP
    # module is defined to have identical param shapes
    state = create_train_state(ref_model, tx, jax.random.key(0))
    imgs, labels = synthetic_cifar10(16, seed=5)
    batch = {
        "image": imgs,
        "label": labels,
        "mask": np.ones(16, bool),
    }
    return mesh, sp_model, ref_model, tx, state, batch


@pytest.mark.slow  # heavyweight compile - make test-all (tier-1 870s budget)
def test_sp_loss_matches_non_sp(devices):
    mesh, sp_model, ref_model, tx, state, batch = _setup()
    step = make_sp_train_step(sp_model, tx, mesh, donate=False)
    new_state, metrics = step(state, batch)
    logits = ref_model.apply({"params": state.params}, batch["image"], train=True)
    ref_loss = cross_entropy_loss(logits, batch["label"], batch["mask"])
    assert abs(float(metrics["loss"]) - float(ref_loss)) < 2e-4
    assert int(new_state.step) == 1


def test_sp_step_trains(devices):
    mesh, sp_model, _, tx, state, batch = _setup()
    step = make_sp_train_step(sp_model, tx, mesh, donate=False)
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]  # overfits the fixed batch
    assert np.isfinite(losses).all()


def test_sp_grads_match_non_sp(devices):
    """Gradients through ring attention + pos-embed slice + pmean pooling
    must equal the single-device ViT gradients."""
    mesh, sp_model, ref_model, tx, state, batch = _setup()

    def ref_loss_fn(params):
        logits = ref_model.apply({"params": params}, batch["image"], train=True)
        return cross_entropy_loss(logits, batch["label"], batch["mask"])

    ref_grads = jax.grad(ref_loss_fn)(state.params)

    from jax import lax

    def sp_loss(params, b):
        logits = sp_model.apply({"params": params}, b["image"], train=True)
        loss = cross_entropy_loss(logits, b["label"], b.get("mask"))
        # the library's sync formulation (parallel/sequence_parallel.py):
        # AD of the pmean'd loss
        return lax.pmean(loss, "data")

    def sp_grads_fn(p, b):
        return jax.grad(sp_loss)(p, b)

    specs = {"image": P("data", "sequence"), "label": P("data"), "mask": P("data")}
    sp_grads = jax.jit(
        jax.shard_map(
            sp_grads_fn,
            mesh=mesh,
            in_specs=(P(), specs),
            out_specs=P(),
        )
    )(state.params, batch)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_flatten_with_path(ref_grads)[0],
        jax.tree_util.tree_flatten_with_path(sp_grads)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4,
            err_msg=jax.tree_util.keystr(path),
        )
