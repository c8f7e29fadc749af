"""Adapter ``lm_probe``: the program's decoder (``models/lm.py``) stepped
behind the shipped ``StepProbe``, because the ``Trainer`` cannot drive a
decoder yet. A loop of its own feeds whole epochs of the seeded token set in
order; the probe reads the first steps, opens and closes the window and ends
the loop exactly as it does for ``Trainer.run``.

The step is the program's ``train/lm_steps.py::make_lm_train_step`` where the
mix says so (``"step": "make_lm_train_step"``: it takes no mask, so the mix has
no padding). Otherwise it is a masked next-token step put together here from
the program's model, ``_token_nll``, optimizer factory and
``apply_optimizer``; ``fault`` (a key of the configuration, tests only) breaks
that step underneath.
"""

from __future__ import annotations

import time
import types

import numpy as np

from chipbench import datagen
from chipbench.adapters import trainer as shipped

CHECK_STEPS = shipped.CHECK_STEPS


def first_moment_only(b1=0.9):
    """Adam with the second moment left out of the update."""
    import jax
    import optax

    def init(params):
        zeros = jax.tree.map(lambda p: 0.0 * p, params)
        return optax.ScaleByAdamState(count=0, mu=zeros, nu=zeros)

    def update(grads, state, params=None):
        del params
        count = state.count + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
        return (jax.tree.map(lambda m: m / (1 - b1 ** count), mu),
                optax.ScaleByAdamState(count=count, mu=mu, nu=state.nu))

    return optax.GradientTransformation(init, update)


def masked_step(model, tx, fault):
    import jax
    import jax.numpy as jnp

    from tpu_ddp.train.lm_steps import _token_nll
    from tpu_ddp.train.optim import apply_optimizer

    def loss_fn(params, tokens, mask):
        logits = model.apply({"params": params}, tokens, train=True)
        targets = tokens[:, :-1] if fault == "shift_left_out" else (
            tokens[:, 1:])
        nll = _token_nll(logits[:, :-1], targets)
        w = mask[:, 1:].astype(jnp.float32)
        if fault == "padding_counted":
            w = jnp.ones_like(w)
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)

    @jax.jit
    def step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(
            state.params, batch["tokens"], batch["mask"])
        params, _, opt_state = apply_optimizer(
            tx, grads, state.opt_state, state.params)
        if fault == "leaf_unchanged":
            params = dict(params, ln_f=state.params["ln_f"])
        return state.replace(step=state.step + 1, params=params,
                             opt_state=opt_state), {"loss": loss}

    return step


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from chipbench.reference import common
    from tpu_ddp.models.lm import CausalTransformerLM
    from tpu_ddp.train.lm_steps import (create_lm_train_state,
                                        make_lm_train_step)
    from tpu_ddp.train.optim import make_optimizer

    arch, traffic = ctx.config, ctx.traffic
    fault = arch.get("fault")
    seed = datagen.fold_seed(ctx.seed)
    chips, batch_rows = int(traffic["chips"]), int(traffic["per_shard_batch"])
    marks = [("imports", time.perf_counter())]
    tokens, mask = ctx.dataset.make(traffic["dataset"], seed)
    ref_params = ctx.reference.init_params(arch, seed)
    marks.append(("data_and_weights", time.perf_counter()))

    t0 = time.perf_counter()
    train = dict(arch["train_config"])
    model = CausalTransformerLM(
        vocab_size=arch["vocab_size"], hidden_dim=arch["hidden_dim"],
        depth=arch["depth"], num_heads=arch["num_heads"],
        mlp_ratio=arch["mlp_ratio"],
        dtype=jnp.dtype(train["compute_dtype"]))
    tx = make_optimizer(lr=train["lr"], optimizer=train["optimizer"],
                        weight_decay=train["weight_decay"])
    if fault == "second_moment_left_out":
        tx = optax.chain(
            first_moment_only(),
            optax.add_decayed_weights(
                train["weight_decay"],
                mask=lambda p: jax.tree.map(lambda x: x.ndim >= 2, p)),
            optax.scale_by_learning_rate(train["lr"]))
    state = create_lm_train_state(model, tx, jax.random.key(seed),
                                  seq_len=arch["seq_len"])
    driver = types.SimpleNamespace(state=state, _preempted=False)
    names = ctx.reference.program_names(arch)
    shipped.install_weights(driver, ref_params, names)
    if traffic.get("step") == "make_lm_train_step":
        mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
        product = make_lm_train_step(model, tx, mesh)

        def step(state, batch):
            return product(state, {"tokens": batch["tokens"]})
    else:
        step = masked_step(model, tx, fault)
    trainer_init_s = time.perf_counter() - t0
    marks.append(("model_and_step", time.perf_counter()))

    epoch_steps = len(tokens) // (batch_rows * chips)
    probe = shipped.StepProbe(
        driver, step, seconds=ctx.seconds, open_at=epoch_steps,
        trace_dir=None, real_per_step=[batch_rows * chips] * epoch_steps,
        names=names, counters=ctx.counters,
        state_fields=common.task(ctx.reference).OPTIMIZER_STATE)
    state = driver.state
    while not driver._preempted:
        for i in range(epoch_steps):
            rows = slice(i * batch_rows * chips, (i + 1) * batch_rows * chips)
            state, _ = probe(state, {"tokens": jnp.asarray(tokens[rows]),
                                     "mask": jnp.asarray(mask[rows])})
            if driver._preempted:
                break

    record = probe.record(ctx, chips=chips, shards=chips, marks=marks)
    record.update({
        "trainer_init_s": trainer_init_s, "trainer_result": {},
        "optimizer": common.optimizer_of(train)})
    return record
