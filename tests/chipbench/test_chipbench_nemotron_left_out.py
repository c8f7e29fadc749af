"""The tiny ``nemotron3-super`` cell (``chipbench_tiny_hybrid.py``) with one
piece of the model left out of the program: the convolution, the gated
norm's grouping, ``D``, the selection bias, a latent projection, the ``** 2``
or the scaling of 5. Each reads ``correct`` false by a limit of the
comparison, against the shipped reference through the shipped harness."""

import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import chipbench_tiny_hybrid as tiny_cell  # noqa: E402
import hybrid_tiny as tiny  # noqa: E402

_CONFIG = ("jax_compilation_cache_dir",
           "jax_persistent_cache_min_compile_time_secs",
           "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def keep_jax_config():
    saved = {k: getattr(jax.config, k) for k in _CONFIG}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def _silenced(module, leaf):
    """``module`` with one of its own leaves read as zeros: the leaf is
    there, what it did is left out."""
    class Silenced(module):
        def param(self, name, *args, **kwargs):
            value = super().param(name, *args, **kwargs)
            return jnp.zeros_like(value) if name == leaf else value

    Silenced.__name__ = module.__name__
    return Silenced


def _break(fault, monkeypatch):
    from tpu_ddp.models import hybrid, moe

    changes = {}
    if fault == "convolution":   # the taps' leaves are there; x goes on as is
        monkeypatch.setattr(
            hybrid, "causal_conv", lambda x, kernel, bias: (
                x.astype(jnp.float32) + 0.0 * kernel.sum() + bias))
    elif fault == "norm_grouping":   # one norm over both held groups
        grouped = hybrid.gated_group_norm
        monkeypatch.setattr(
            hybrid, "gated_group_norm",
            lambda y, z, scale, groups, eps: grouped(y, z, scale, 1, eps))
    elif fault == "D":
        monkeypatch.setattr(hybrid, "Mamba2Mixer",
                            _silenced(hybrid.Mamba2Mixer, "D"))
    elif fault == "selection_bias":
        monkeypatch.setattr(hybrid, "DroplessMoE",
                            _silenced(hybrid.DroplessMoE, "router_bias"))
    elif fault == "latent_projection":
        # ``latent_down`` has its leaf and no say: the tokens' first
        # channels go to the experts as they are
        dense = nn.Dense.__call__

        def call(self, x):
            y = dense(self, x)
            if self.name != "latent_down":
                return y
            return 0.0 * y + x[..., :y.shape[-1]].astype(y.dtype)

        monkeypatch.setattr(nn.Dense, "__call__", call)
    elif fault == "square":      # the shared expert's relu is not squared

        class Relu(nn.Module):
            width: int
            dtype: object = None

            @nn.compact
            def __call__(self, x):
                dense = lambda n, name: nn.Dense(  # noqa: E731
                    n, use_bias=False, dtype=self.dtype, name=name)
                return dense(x.shape[-1], "down")(
                    nn.relu(dense(self.width, "up")(x)))

        monkeypatch.setattr(moe, "Relu2MLP", Relu)
    elif fault == "scaling":
        changes["routed_scaling"] = 1.0
    else:
        raise ValueError(fault)
    tiny.register(**changes)


@pytest.mark.parametrize("fault", [
    "convolution", "norm_grouping", "D", "selection_bias",
    "latent_projection", "square", "scaling"])
def test_a_model_with_a_piece_left_out_is_not_correct(tmp_path, monkeypatch,
                                                      fault):
    _break(fault, monkeypatch)
    try:
        result = tiny_cell.run(tmp_path)
    finally:
        tiny.register()
    assert result["correct"] is False
    assert tiny_cell.failed(result), result["compared"]
