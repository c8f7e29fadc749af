"""``joyai_llm_flash`` (``models/decoder.py``: latent attention, a selection
bias on the router, the multi-token-prediction module) against the
benchmark's plain reference (``chipbench/reference/joyai-llm-flash.py``) on
seeded weights at a size a CPU holds; the two-term task; the share test;
the ``Trainer`` and the CLI driving it.
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

import joyai_tiny as tiny  # noqa: E402


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


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("what", ["logits", "mtp_logits", "loss",
                                  "gradient"])
def test_the_program_matches_the_reference(ref, seeded, remat, what):
    from tpu_ddp.models.decoder import SparseDecoder
    from tpu_ddp.train.tasks import task_of

    arch, params, tokens, mask = seeded
    model = SparseDecoder(tiny.spec(), remat=remat)
    tree = tiny.program_tree(ref, arch, params)
    init = model.init(jax.random.key(0), tokens[:, :8])["params"]
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, tree)
    task = task_of(model)
    assert task.name == "next_token_mtp"

    def program(tree):
        outputs, _ = model.apply({"params": tree}, tokens,
                                 mutable=["counters"])
        return task.loss(None, outputs, _batch(tokens, mask)), outputs

    with jax.default_matmul_precision("highest"):
        if what in ("logits", "mtp_logits"):
            _, got = program(tree)
            want = ref.forward(arch, params, tokens)
            at = what == "mtp_logits"
            np.testing.assert_allclose(got[at], want[at], atol=2e-5)
            return
        (loss, terms), grads = jax.value_and_grad(
            lambda t: program(t)[0], has_aux=True)(tree)
        want, want_grads = jax.value_and_grad(
            lambda p: ref.sequence_loss(arch, p, tokens, mask))(params)
    if what == "loss":
        l_next, l_mtp = ref.loss_terms(arch, params, tokens, mask)
        np.testing.assert_allclose(loss, want, rtol=2e-6)
        np.testing.assert_allclose(terms["loss_next"], l_next, rtol=2e-6)
        np.testing.assert_allclose(terms["loss_mtp"], l_mtp, rtol=2e-6)
        np.testing.assert_allclose(
            loss, terms["loss_next"] + 0.3 * terms["loss_mtp"], rtol=1e-6)
        return
    want_tree = tiny.program_tree(ref, arch, want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_tree)):
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))) + 1e-7,
            err_msg=jax.tree_util.keystr(path))
    # the selection bias chooses and is not differentiated through
    assert not np.any(grads["layer_1"]["moe"]["router_bias"])
    assert np.any(grads["mtp_proj"]["kernel"])


def test_the_reference_takes_its_heads_in_groups(ref, seeded, monkeypatch):
    """Two heads at a time and four give the same layer."""
    arch, params, tokens, _ = seeded
    with jax.default_matmul_precision("highest"):
        want = ref.forward(arch, params, tokens)
        monkeypatch.setattr(ref, "HEAD_GROUP", 2)
        got = ref.forward(arch, params, tokens)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_the_second_term_is_the_nll_of_the_token_after_next(seeded):
    """``L_mtp`` by the plain formula on the module's logits: position ``i``
    against token ``i + 2``, the loss mask's and the row mask's zeros
    dropped, and no gradient where there is no target."""
    from tpu_ddp.train.tasks import next_token_mtp

    _, _, tokens, _ = seeded
    b, t = tokens.shape
    ks = jax.random.split(jax.random.key(1), 2)
    logits = jax.random.normal(ks[0], (b, t, tiny.VOCAB))
    mtp_logits = jax.random.normal(ks[1], (b, t, tiny.VOCAB))
    loss_mask = jnp.ones((b, t), bool).at[0, 5].set(False)
    batch = {"tokens": tokens, "loss_mask": loss_mask,
             "mask": jnp.array([True, False])}

    def plain(mtp_logits):
        logp = jax.nn.log_softmax(mtp_logits)
        nll = -jnp.take_along_axis(logp[:, :-2], tokens[:, 2:, None],
                                   axis=-1)[..., 0]
        w = loss_mask[:, 2:] * jnp.array([1.0, 0.0])[:, None]
        return jnp.sum(nll * w) / jnp.sum(w)

    def program(mtp_logits):
        loss, terms = next_token_mtp(0.3).loss(
            None, (logits, mtp_logits), batch)
        return terms["loss_mtp"], (loss, terms)

    want, want_grad = jax.value_and_grad(plain)(mtp_logits)
    (got, (loss, terms)), grad = jax.value_and_grad(
        program, has_aux=True)(mtp_logits)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(grad, want_grad, atol=1e-6)
    np.testing.assert_allclose(
        loss, terms["loss_next"] + 0.3 * got, rtol=1e-6)
    assert not np.any(grad[1])          # the padded row
    assert not np.any(grad[0, -2:])     # no token two ahead


# -- sizes -----------------------------------------------------------------------

def _count(**share):
    from tpu_ddp.models.decoder import SparseDecoder, joyai_llm_flash_spec

    model = SparseDecoder(joyai_llm_flash_spec(**share))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    return shapes, sum(int(np.prod(leaf.shape))
                       for leaf in jax.tree.leaves(shapes))


def test_published_sizes_count_the_published_parameters():
    _, whole = _count()
    assert whole == 50_190_489_600      # 48.94 B and the module: "48B"
    # the benchmark's cut: 10.89 GB at 16 bytes a parameter
    assert _count(num_layers=5, experts_held=16,
                  vocab_rows=16160)[1] == 680_439_040


def test_the_references_shapes_are_the_cut_models(ref):
    with open(os.path.join(tiny.REPO, "chipbench", "configs",
                           "joyai-llm-flash.json")) as f:
        arch = json.load(f)
    shapes, count = _count(**arch["train_config"]["model_overrides"])
    assert f"{count:,}" in arch["parameters_here"]
    want = {path: shape for path, (shape, _) in zip(
        ref.program_names(arch).values(), ref.param_shapes(arch).values())}
    got = {tuple(k.key for k in path): leaf.shape for path, leaf in
           jax.tree_util.tree_leaves_with_path(shapes)}
    assert got == want
    # 9.28 TFLOP a sequence forward, 27.84 trained: the issue's arithmetic
    flops = ref.train_flops_per_example(arch, {"dataset": {"seq_len": 8192}})
    assert 27.8e12 < flops < 27.9e12
    parts = ref.forward_macs_by_part(arch, 8192)
    share = {k: v / sum(parts.values()) for k, v in parts.items()}
    assert 0.44 < share["attention"] < 0.45
    assert 0.27 < share["mla_projections"] < 0.285
    assert 0.115 < share["head"] < 0.12


def test_a_model_without_the_new_options_keeps_its_task_and_its_output():
    import decoder_tiny

    from tpu_ddp.models.decoder import SparseDecoder
    from tpu_ddp.train.tasks import NEXT_TOKEN, task_of

    model = SparseDecoder(decoder_tiny.spec())
    assert task_of(model) is NEXT_TOKEN
    tokens = jnp.asarray(decoder_tiny.tokens(1, seed=3)[0])
    tree = model.init(jax.random.key(0), tokens)["params"]
    assert not [k for k in tree if k.startswith("mtp")]
    assert model.apply({"params": tree}, tokens).shape == (
        1, tokens.shape[1], decoder_tiny.spec().vocab_rows)


# -- the share test ----------------------------------------------------------------

def _layer_tree(ref, arch, prefix, leaves):
    """One layer's reference leaves as the program's ``DecoderLayer`` takes
    them."""
    tree = {}
    for leaf, path in ref.program_names(arch).items():
        if not leaf.startswith(prefix):
            continue
        node = tree
        for part in path[1:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaves[leaf[len(prefix):]]
    return tree


@pytest.mark.parametrize("prefix", ["layer_1.", "mtp_layer."],
                         ids=["a_sparse_layer", "the_module"])
def test_sixteen_shares_add_up_to_the_uncut_layer(ref, prefix):
    """The guide's share test: over all sixteen expert offsets of one held
    expert each, the routed parts of the program's layer, with the
    attention and the shared expert (which every chip computes alike)
    counted once, are the uncut reference's layer; the reference given a
    share computes that share's part; every (token, choice) pair lands once.
    The module's layer reads what the module feeds it."""
    from tpu_ddp.models.decoder import DecoderLayer

    whole = tiny.arch(held=tiny.WHOLE, offset=0)
    params = ref.init_params(whole, 11)
    tokens = jnp.asarray(tiny.tokens(2, seed=5)[0])
    x = jax.random.normal(jax.random.key(5), (2, tiny.T, tiny.HIDDEN))
    tables = ref.rotary_tables(whole, tiny.T)
    own = {k[len(prefix):]: v for k, v in params.items()
           if k.startswith(prefix)}

    def cut(leaves, rows):
        return {k: v[rows] if k.startswith("moe.w_") else v
                for k, v in leaves.items()}

    def layer(share, leaves):
        return ref.layer(whole, leaves, x, "sparse", tables, share,
                         "float32_highest")

    with jax.default_matmul_precision("highest"):
        if prefix == "mtp_layer.":
            x = ref._mtp_input(whole, params, x, tokens, "float32_highest")
        want = layer((0, tiny.WHOLE, True), own)
        once = layer((0, 0, True), cut(own, slice(0, 0)))
        total, landed = once, 0
        for offset in range(tiny.WHOLE):
            here = cut(own, slice(offset, offset + 1))
            spec = tiny.spec(held=1, offset=offset)
            tree = _layer_tree(ref, whole, prefix, here)
            y, sown = DecoderLayer(spec.mtp, spec).apply(
                {"params": tree}, x, *spec.mtp.rotary.tables(tiny.T),
                mutable=["counters", "intermediates"])
            np.testing.assert_allclose(y, layer((offset, 1, True), here),
                                       atol=5e-5)
            total = total + (y - once)
            landed += int(sown["counters"]["moe"]["expert_load"][0].sum())
    np.testing.assert_allclose(total, want, atol=2e-4)
    assert landed == 2 * tiny.T * whole["num_experts_per_tok"]


# -- the Trainer -----------------------------------------------------------------

def _config(**extra):
    from tpu_ddp.train.trainer import TrainConfig

    tiny.register()
    fields = dict(model="tiny_joyai", per_shard_batch=2, epochs=1,
                  n_devices=2, prefetch_depth=0, optimizer="adamw", lr=1e-3,
                  weight_decay=0.1, remat=True)
    fields.update(extra)
    return TrainConfig(**fields)


@pytest.mark.parametrize("flags", [{}, {"zero1": True},
                                   {"grad_accum_steps": 2}],
                         ids=["dp", "zero1", "accumulated"])
def test_trainer_drives_the_model_and_reports_both_terms(devices, flags):
    from tpu_ddp.train.trainer import Trainer

    trainer = Trainer(_config(**flags), train_data=tiny.tokens(16),
                      test_data=tiny.tokens(8, seed=1))
    assert trainer.task.name == "next_token_mtp"
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
        assert {"loss", "loss_next", "loss_mtp"} <= set(metrics)
        np.testing.assert_allclose(
            metrics["loss"],
            metrics["loss_next"] + 0.3 * metrics["loss_mtp"], rtol=1e-5)
    # three sparse layers (the module's among them), four of sixteen held
    assert 0 < result["model/expert_load_sum"] < 3 * 2 * 2 * tiny.T * 4


def test_the_cli_trains_the_published_model_by_name(devices, capsys):
    """``--model joyai_llm_flash`` with one chip's share cut far enough for
    a CPU (two layers and the module, two experts, 64 vocabulary rows; every
    width published), under ``--attention flash`` and ``--remat``."""
    from tpu_ddp.cli.train import main

    main([
        "--device", "cpu", "--model", "joyai_llm_flash", "--model-overrides",
        '{"num_layers": 2, "experts_held": 2, "vocab_rows": 64}',
        "--synthetic-data", "--synthetic-size", "2", "--attention", "flash",
        "--remat", "--batch-size", "2", "--n-devices", "1", "--epochs", "1",
        "--optimizer", "adamw", "--lr", "1e-4", "--prefetch-depth", "0"])
    assert "Training loss" in capsys.readouterr().out
