"""``sdar_30b_a3b`` (``models/decoder.py``: query and key norms, a softmax
router, block diffusion) against the benchmark's plain reference
(``chipbench/reference/sdar-30b-a3b.py``) on seeded weights at a size a CPU
holds; the task that noises its batch in the step; no leak through the
mask; the share test; the ``Trainer`` and the CLI driving it.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import sdar_tiny as tiny  # noqa: E402

SEED, STEP, SHARD = 2**31 + 41, 2, 1


def step_key(seed=SEED, step=STEP, shard=SHARD):
    """The key ``make_train_step`` hands ``Task.prepare``."""
    key = jax.random.fold_in(jax.random.key(seed), step)
    return jax.random.fold_in(jax.random.fold_in(key, shard), 2)


@pytest.fixture(scope="module")
def ref():
    return tiny.reference()


@pytest.fixture(scope="module")
def seeded(ref):
    arch = tiny.arch()
    tokens, mask = tiny.tokens(2, seed=3)
    return (arch, ref.init_params(arch, 7), jnp.asarray(tokens),
            jnp.asarray(mask))


def _batch(tokens, mask):
    return {"tokens": tokens, "loss_mask": mask,
            "mask": jnp.ones(tokens.shape[0], bool)}


# -- the model and its task against the reference -----------------------------

def test_the_step_draws_the_references_noise(ref, seeded):
    """``block_noise`` from the step's key is the reference's own draw of
    (seed, step, shard), to the bit: the doubled input, the masked positions
    and the levels; a level lies in (t_min, 1] and is one a block."""
    from tpu_ddp.models.decoder import SparseDecoder
    from tpu_ddp.train.tasks import task_of

    arch, _, tokens, mask = seeded
    task = task_of(SparseDecoder(tiny.spec()))
    assert task.name == "block_diffusion" and task.prepare is not None
    got = task.prepare(step_key(), _batch(tokens, mask))
    fed, masked, t = ref.noise(arch, tokens, seed=SEED, step=STEP,
                               shard=SHARD)
    np.testing.assert_array_equal(got["tokens"], fed)
    np.testing.assert_array_equal(got["block_masked"], masked)
    np.testing.assert_array_equal(got["block_t"], t)
    np.testing.assert_array_equal(got["block_targets"], tokens)
    half = tokens.shape[1]
    np.testing.assert_array_equal(fed[:, :half], tokens)
    np.testing.assert_array_equal(
        fed[:, half:], np.where(masked, tiny.VOCAB - 1, tokens))
    levels = np.asarray(t).reshape(2, -1, tiny.BLOCK)
    assert (levels == levels[..., :1]).all()
    assert (levels > 1e-3).all() and (levels <= 1.0).all()
    assert 0 < int(masked.sum()) < masked.size


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("what", ["logits", "loss", "gradient"])
def test_the_program_matches_the_reference(ref, seeded, remat, what):
    from tpu_ddp.models.decoder import SparseDecoder
    from tpu_ddp.train.tasks import task_of

    arch, params, tokens, mask = seeded
    model = SparseDecoder(tiny.spec(), remat=remat)
    tree = tiny.program_tree(ref, arch, params)
    task = task_of(model)
    init = model.init(jax.random.key(0),
                      task.example_input(tokens))["params"]
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, tree)
    batch = task.prepare(step_key(), _batch(tokens, mask))

    def program(tree):
        logits, sown = model.apply({"params": tree}, batch["tokens"],
                                   mutable=["counters"])
        return task.loss(None, logits, batch), (logits, sown)

    with jax.default_matmul_precision("highest"):
        if what == "logits":
            _, (got, sown) = program(tree)
            want = ref.forward(arch, params, batch["tokens"])
            assert got.shape == (2, tiny.T, tiny.VOCAB)  # the noisy half's
            np.testing.assert_allclose(got, want, atol=2e-5)
            assert int(sown["counters"]["block_masked_tokens"][0]) == int(
                batch["block_masked"].sum())
            return
        (loss, terms), grads = jax.value_and_grad(
            lambda t: program(t)[0], has_aux=True)(tree)
        want, want_grads = jax.value_and_grad(
            lambda p: ref.sequence_loss(arch, p, tokens, mask, seed=SEED,
                                        step=STEP, shard=SHARD))(params)
    if what == "loss":
        np.testing.assert_allclose(loss, want, rtol=2e-6)
        assert set(terms) == {"nll_masked", "masked_share"}
        np.testing.assert_allclose(
            terms["masked_share"], batch["block_masked"].mean(), rtol=1e-6)
        return
    want_tree = tiny.program_tree(ref, arch, want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_tree)):
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))) + 1e-7,
            err_msg=jax.tree_util.keystr(path))
    assert np.any(grads["layer_0"]["attn"]["q_norm"]["scale"])
    assert np.any(grads["layer_0"]["attn"]["k_norm"]["scale"])


def test_the_loss_is_the_weighted_sum_written_out(seeded):
    """``sum(m w CE / t) / sum(w)`` by hand: a row the loader padded and a
    position outside ``loss_mask`` count for nothing, above or below."""
    from tpu_ddp.train.tasks import block_diffusion_loss

    _, _, tokens, _ = seeded
    n, length = tokens.shape
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(n, length, tiny.VOCAB)).astype(np.float32)
    masked = rng.random((n, length)) < 0.5
    t = rng.uniform(0.1, 1.0, (n, length)).astype(np.float32)
    loss_mask = rng.random((n, length)) < 0.8
    rows = np.array([True, False])
    loss, terms = block_diffusion_loss(jnp.asarray(logits), {
        "block_targets": tokens, "block_masked": jnp.asarray(masked),
        "block_t": jnp.asarray(t), "loss_mask": jnp.asarray(loss_mask),
        "mask": jnp.asarray(rows)})
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    nll = -np.take_along_axis(logp, np.asarray(tokens)[..., None], -1)[..., 0]
    w = loss_mask * rows[:, None]
    np.testing.assert_allclose(
        loss, (masked * w * nll / t).sum() / w.sum(), rtol=1e-5)
    np.testing.assert_allclose(
        terms["nll_masked"], (masked * w * nll).sum() / (masked * w).sum(),
        rtol=1e-5)


# -- no leak ------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_a_noisy_block_sees_the_clean_past_and_itself_alone(ref, seeded,
                                                           impl):
    """Logits of noisy block ``b`` do not move when a clean token of block
    ``>= b`` or a noisy token of another block changes, and do when a clean
    token of a block before ``b`` does."""
    import functools

    from tpu_ddp.models.decoder import SparseDecoder
    from tpu_ddp.ops.flash_attention import flash_attention

    arch, params, tokens, _ = seeded
    attend = None if impl == "reference" else functools.partial(
        flash_attention, block_q=8, block_k=8, interpret=True)
    model = SparseDecoder(tiny.spec(), attention_impl=attend)
    tree = tiny.program_tree(ref, arch, params)
    fed = np.asarray(ref.noise(arch, tokens, seed=SEED, step=0, shard=0)[0])
    half, b = tiny.T, 3
    own = slice(b * tiny.BLOCK, (b + 1) * tiny.BLOCK)

    def block_logits(fed):
        with jax.default_matmul_precision("highest"):
            return np.asarray(model.apply({"params": tree},
                                          jnp.asarray(fed)))[:, own]

    def changed(position):
        other = fed.copy()
        other[:, position] = (other[:, position] + 1) % (tiny.VOCAB - 1)
        return block_logits(other)

    base = block_logits(fed)
    unseen = ([b * tiny.BLOCK, b * tiny.BLOCK + 3, half - 1]   # clean, >= b
              + [half + 0, half + (b - 1) * tiny.BLOCK + 3,    # noisy, not b
                 half + (b + 1) * tiny.BLOCK, 2 * half - 1])
    for position in unseen:
        np.testing.assert_array_equal(changed(position), base,
                                      err_msg=str(position))
    seen = [0, (b - 1) * tiny.BLOCK + 3,                       # clean, < b
            half + b * tiny.BLOCK, half + b * tiny.BLOCK + 3]  # noisy, b
    for position in seen:
        assert np.abs(changed(position) - base).max() > 1e-4, position


# -- sizes --------------------------------------------------------------------

def _count(**share):
    from tpu_ddp.models.decoder import SparseDecoder, sdar_30b_a3b_spec

    model = SparseDecoder(sdar_30b_a3b_spec(**share))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 16), jnp.int32))["params"])
    return shapes, sum(int(np.prod(leaf.shape))
                       for leaf in jax.tree.leaves(shapes))


def test_published_sizes_count_the_published_parameters():
    _, whole = _count()
    assert whole == 30_532_122_624      # "30B"
    # the benchmark's cut: 10.33 GB at 16 bytes a parameter
    assert _count(num_layers=6, experts_held=16,
                  vocab_rows=18992)[1] == 645_623_296


def test_the_references_shapes_are_the_cut_models(ref):
    with open(os.path.join(tiny.REPO, "chipbench", "configs",
                           "sdar-30b-a3b.json")) as f:
        arch = json.load(f)
    shapes, count = _count(**arch["train_config"]["model_overrides"])
    assert f"{count:,}" in arch["parameters_here"]
    assert f"{_count()[1]:,}" in arch["parameters_here"]
    want = {path: shape for path, (shape, _) in zip(
        ref.program_names(arch).values(), ref.param_shapes(arch).values())}
    got = {tuple(k.key for k in path): leaf.shape for path, leaf in
           jax.tree_util.tree_leaves_with_path(shapes)}
    assert got == want
    # the spec's settings are the file's
    from tpu_ddp.models.decoder import sdar_30b_a3b_spec

    spec = sdar_30b_a3b_spec(**arch["train_config"]["model_overrides"])
    assert (spec.diffusion.block, spec.diffusion.mask_id,
            spec.diffusion.t_min) == (
                arch["block_length"], arch["mask_token_id"],
                arch["noise_t_min"])
    # 4.31 TFLOP a sequence forward, 12.9 trained: the issue's arithmetic
    flops = ref.train_flops_per_example(arch, {"dataset": {"seq_len": 4096}})
    assert 12.9e12 < flops < 12.96e12
    parts = {k: 2 * v / arch["layers_here"] / 1e9
             for k, v in ref.forward_macs_by_part(arch, 4096).items()}
    assert round(parts["projections"]) == 309
    assert round(parts["attention"]) == 275
    assert round(parts["routed"]) == 77 and round(parts["router"]) == 4


@pytest.mark.parametrize("length,block", [(24, 4), (32, 16), (12, 1),
                                          (4096, 4)])
def test_the_references_pairs_are_a_count_of_its_mask(ref, length, block):
    if length > 64:  # the cell's: by the closed form's own parts
        n = length // block
        assert ref.visible_pairs(length, block) == block * block * (
            n * (n + 1) // 2 + n * (n - 1) // 2 + n)
        return
    i = jnp.arange(2 * length)
    assert int(ref.visible(i, i, length, block).sum()) == ref.visible_pairs(
        length, block)


# -- the share test -----------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: over the eight shares of two experts each,
    the routed parts of the program's layer, with the attention (which every
    chip computes alike) counted once, are the uncut reference's layer; the
    reference given a share computes that share's part; every (position,
    choice) pair lands once."""
    from tpu_ddp.models.decoder import DecoderLayer

    whole = tiny.arch(held=tiny.WHOLE, offset=0)
    params = ref.init_params(whole, 11)
    x = jax.random.normal(jax.random.key(5), (2, 2 * tiny.T, tiny.HIDDEN))
    cos, sin = (jnp.concatenate([a, a])
                for a in ref.rotary_tables(whole, tiny.T))
    prefix = "layer_1."
    own = {k[len(prefix):]: v for k, v in params.items()
           if k.startswith(prefix)}

    def cut(leaves, rows):
        return {k: v[rows] if k.startswith("moe.w_") else v
                for k, v in leaves.items()}

    def layer(share, leaves):
        return ref.layer(whole, leaves, x, (cos, sin), share,
                         "float32_highest")

    def tree_of(leaves):
        tree = {}
        for leaf, path in ref.program_names(whole).items():
            if not leaf.startswith(prefix):
                continue
            node = tree
            for part in path[1:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = leaves[leaf[len(prefix):]]
        return tree

    with jax.default_matmul_precision("highest"):
        want = layer((0, tiny.WHOLE), own)
        once = layer((0, 0), cut(own, slice(0, 0)))
        total, landed = once, 0
        for offset in range(0, tiny.WHOLE, 2):
            here = cut(own, slice(offset, offset + 2))
            spec = tiny.spec(held=2, offset=offset)
            y, sown = DecoderLayer(spec.layers[1], spec).apply(
                {"params": tree_of(here)}, x, cos, sin,
                mutable=["counters", "intermediates"])
            np.testing.assert_allclose(y, layer((offset, 2), here), atol=5e-5)
            total = total + (y - once)
            landed += int(sown["counters"]["moe"]["expert_load"][0].sum())
    np.testing.assert_allclose(total, want, atol=2e-4)
    assert landed == 2 * 2 * tiny.T * whole["num_experts_per_tok"]


# -- the step: noise by (seed, step, shard) -----------------------------------

def test_the_same_seed_step_and_shard_draw_the_same_noise(devices, seeded):
    """Through ``make_train_step`` on two shards: the masked positions a
    step counts (``block_masked_tokens``) and its loss are the same from
    the same state, and another step's are another draw; the shards draw
    apart."""
    import optax

    from tpu_ddp.models.decoder import SparseDecoder
    from tpu_ddp.parallel.mesh import batch_sharding, data_parallel_mesh
    from tpu_ddp.train.state import TrainState
    from tpu_ddp.train.steps import make_train_step
    from tpu_ddp.train.tasks import task_of

    model = SparseDecoder(tiny.spec())
    task = task_of(model)
    tokens, mask = tiny.tokens(4, seed=9)
    mesh = data_parallel_mesh(2)
    batch = jax.device_put(_batch(jnp.asarray(tokens), jnp.asarray(mask)),
                           batch_sharding(mesh))
    tx = optax.sgd(1e-3)
    params = model.init(jax.random.key(0),
                        task.example_input(batch["tokens"]))["params"]
    state = TrainState(step=jnp.int32(0), params=params, batch_stats={},
                       opt_state=tx.init(params))
    step = make_train_step(model, tx, mesh, task=task, donate=False,
                           augment_seed=SEED, compute_accuracy=False)

    def read(state):
        _, metrics = step(state, batch)
        return (int(metrics["counters"]["block_masked_tokens"][0]),
                float(metrics["loss"]), float(metrics["masked_share"]))

    assert read(state) == read(state)
    assert read(state) != read(state.replace(step=jnp.int32(1)))
    other = make_train_step(model, tx, mesh, task=task, donate=False,
                            augment_seed=SEED + 1, compute_accuracy=False)
    assert read(state)[0] != int(other(state, batch)[1]["counters"][
        "block_masked_tokens"][0])
    # the count is the two shards' own draws: shard d of step 0
    want = sum(int(task.prepare(step_key(step=0, shard=d), _batch(
        jnp.asarray(tokens[2 * d:2 * d + 2]),
        jnp.asarray(mask[2 * d:2 * d + 2])))["block_masked"].sum())
        for d in range(2))
    assert read(state)[0] == want
    a, b = (task.prepare(step_key(shard=d), batch)["block_masked"]
            for d in range(2))
    assert bool(jnp.any(a != b))


# -- the Trainer --------------------------------------------------------------

def _config(**extra):
    from tpu_ddp.train.trainer import TrainConfig

    tiny.register()
    fields = dict(model="tiny_sdar", per_shard_batch=2, epochs=1,
                  n_devices=2, prefetch_depth=0, optimizer="adamw", lr=1e-3,
                  weight_decay=0.1, remat=True)
    fields.update(extra)
    return TrainConfig(**fields)


@pytest.mark.parametrize("flags", [{}, {"zero1": True},
                                   {"grad_accum_steps": 2}],
                         ids=["dp", "zero1", "accumulated"])
def test_trainer_drives_the_model_and_reports_its_terms(devices, flags):
    from tpu_ddp.train.trainer import Trainer

    trainer = Trainer(_config(**flags), train_data=tiny.tokens(16),
                      test_data=tiny.tokens(8, seed=1))
    assert trainer.task.name == "block_diffusion"
    seen = []
    step = trainer.train_step

    def watched(state, batch):
        state, metrics = step(state, batch)
        seen.append(metrics)
        return state, metrics

    trainer.train_step = watched
    result = trainer.run()
    assert int(trainer.state.step) == 4
    assert np.isfinite(trainer.history["train_loss"]).all()
    for metrics in seen:
        assert {"loss", "nll_masked", "masked_share"} <= set(metrics)
        assert 0.0 < float(metrics["masked_share"]) < 1.0
    # masked positions a step: about half of 4 rows of T
    assert 0 < result["model/block_masked_tokens_sum"] < 4 * tiny.T
    assert 0 < result["model/expert_load_sum"] < 3 * 4 * 2 * tiny.T * 4
    # evaluation noises its batch by one fixed draw: two passes agree
    first, again = trainer.evaluate()[1], trainer.evaluate()[1]
    assert np.isfinite(first) and first == again


def test_the_cli_trains_the_published_model_by_name(devices, capsys):
    """``--model sdar_30b_a3b`` with one chip's share cut far enough for a
    CPU (two layers, two experts, 64 vocabulary rows; every width
    published), under ``--attention flash`` and ``--remat``."""
    from tpu_ddp.cli.train import main

    main([
        "--device", "cpu", "--model", "sdar_30b_a3b", "--model-overrides",
        '{"num_layers": 2, "experts_held": 2, "vocab_rows": 64}',
        "--synthetic-data", "--synthetic-size", "2", "--attention", "flash",
        "--remat", "--batch-size", "2", "--n-devices", "1", "--epochs", "1",
        "--optimizer", "adamw", "--lr", "1e-4", "--prefetch-depth", "0"])
    assert "Training loss" in capsys.readouterr().out
