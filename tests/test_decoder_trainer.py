"""The ``Trainer`` drives the sparse decoder (``models/decoder.py``) through
the one step builder: one trace, one lowering and one load of the step under
dp, zero1, accumulation, fused steps and health; checkpoint and resume; the
CLI by the published model's name. CPU, a tiny decoder with every kind of
layer (``decoder_tiny.py``), virtual devices for the data axis."""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import decoder_tiny as tiny  # noqa: E402


def _config(**extra):
    from tpu_ddp.train.trainer import TrainConfig

    tiny.register()
    fields = dict(model="tiny_decoder", per_shard_batch=2, epochs=1,
                  n_devices=2, prefetch_depth=0, optimizer="adamw", lr=1e-3,
                  weight_decay=0.1, remat=True)
    fields.update(extra)
    return TrainConfig(**fields)


def _one_of_each(registry, trainer):
    from tpu_ddp.telemetry.jax_hooks import FUNCTIONS_TABLE

    name = (trainer.multi_step or trainer.train_step).__name__
    row = registry().snapshot(tables=True)["tables"][FUNCTIONS_TABLE][name]
    built = row.get("compilations", 0) + row.get("cache_loads", 0)
    assert (row["traces"], row["lowerings"], built) == (1, 1, 1), (name, row)


@pytest.mark.parametrize("flags", [
    {}, {"zero1": True}, {"grad_accum_steps": 2}, {"steps_per_call": 2},
    {"prefetch_depth": 2, "health": "on"}],
    ids=["dp", "zero1", "grad_accum", "steps_per_call", "prefetch_health"])
def test_trainer_drives_the_decoder_with_one_trace_one_lowering_one_load(
        devices, fresh_registry, flags):
    from tpu_ddp.train.trainer import Trainer

    trainer = Trainer(_config(**flags), train_data=tiny.tokens(16),
                      test_data=tiny.tokens(8, seed=1))
    assert trainer.task.name == "next_token"
    result = trainer.run()
    assert int(trainer.state.step) == 4
    assert np.isfinite(trainer.history["train_loss"]).all()
    _one_of_each(fresh_registry, trainer)
    # what the sparse layers counted comes out with every step's metrics,
    # summed over shards and microbatches: every (token, choice) pair lands
    # on one of the 16 experts; this share holds 4 of them
    assert 0 < result["model/expert_load_sum"] < (
        4 * 2 * 2 * tiny.T * tiny.TOP_K)
    assert result["model/expert_load_max"] >= result["model/expert_load_mean"]
    accuracy, loss = trainer.evaluate()
    assert accuracy == 0.0 and np.isfinite(loss)


def test_a_decoder_resumes_from_its_checkpoint(devices, fresh_registry,
                                               tmp_path):
    from tpu_ddp.telemetry.registry import reset_default_registry
    from tpu_ddp.train.trainer import Trainer

    ckpt = dict(checkpoint_dir=str(tmp_path / "ckpt"),
                checkpoint_every_epochs=1, log_every_epochs=1)
    data = dict(train_data=tiny.tokens(16), test_data=tiny.tokens(8, seed=1))
    first = Trainer(_config(**ckpt), **data)
    first.run()
    saved = jax.device_get(first.state)
    del first
    reset_default_registry()

    trainer = Trainer(_config(resume=True, epochs=2, **ckpt), **data)
    assert trainer.resumed_step == 4
    jax.tree.map(np.testing.assert_array_equal,
                 jax.device_get(trainer.state.params), saved.params)
    jax.tree.map(np.testing.assert_array_equal,
                 jax.device_get(trainer.state.opt_state), saved.opt_state)
    trainer.run()
    assert int(trainer.state.step) == 8
    _one_of_each(fresh_registry, trainer)


def test_synthetic_sequences_are_sized_by_the_built_model(devices):
    """``--synthetic-data`` with a token model: ids over the vocabulary rows
    the built model holds, whatever its factory's keywords are called, at
    the synthetic length; no option of its own."""
    from tpu_ddp.data.tokens import SEQ_LEN
    from tpu_ddp.train.trainer import Trainer, TrainConfig

    assert not hasattr(TrainConfig, "seq_len")
    trainer = Trainer(_config(synthetic_data=True, synthetic_size=6))
    tokens, loss_mask = (trainer.train_loader.images,
                         trainer.train_loader.labels)
    assert tokens.shape == loss_mask.shape == (6, SEQ_LEN)
    assert 0 <= tokens.min() and tokens.max() < tiny.VOCAB
    assert len(np.unique(tokens)) > tiny.VOCAB // 2


def test_model_counters_reduce_by_name_without_knowing_the_model():
    """``Telemetry.record_model_counters``: any name a layer sows comes out
    as sum, mean and max of a step, averaged over the steps, and a total."""
    from tpu_ddp.telemetry import Telemetry

    tel = Telemetry(enabled=False)
    steps = [{"expert_load": np.array([[1, 3], [0, 4]]), "skipped": np.array(2)},
             {"expert_load": np.array([[2, 2], [2, 6]])}]
    out = tel.record_model_counters(steps)
    assert out == {
        "model/expert_load_sum": 10.0, "model/expert_load_mean": 2.5,
        "model/expert_load_max": 5.0, "model/skipped_sum": 2.0,
        "model/skipped_mean": 2.0, "model/skipped_max": 2.0}


def test_a_token_model_is_refused_by_a_strategy_that_reads_images(devices):
    from tpu_ddp.train.trainer import Trainer

    with pytest.raises(ValueError, match="next_token"):
        Trainer(_config(parallelism="fsdp"), train_data=tiny.tokens(16))


def test_the_cli_trains_the_published_model_by_name(devices, capsys):
    """``--model laguna_xs2`` with one chip's share cut far enough for a CPU
    (two layers, two experts, 64 vocabulary rows; every width published)."""
    from tpu_ddp.cli.train import main

    result = main([
        "--device", "cpu", "--model", "laguna_xs2", "--model-overrides",
        '{"num_layers": 2, "experts_held": 2, "vocab_rows": 64}',
        "--synthetic-data", "--synthetic-size", "2",
        "--batch-size", "2", "--n-devices", "1", "--epochs", "1",
        "--optimizer", "adamw", "--lr", "1e-4", "--prefetch-depth", "0"])
    assert np.isfinite(result["mean_step_seconds"]) or True
    assert "Training loss" in capsys.readouterr().out
