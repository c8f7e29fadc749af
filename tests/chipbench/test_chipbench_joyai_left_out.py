"""The tiny ``joyai-llm-flash`` cell (``chipbench_tiny_joyai.py``) with one
piece of the model left out of the program: the shared rotary key, the
scale of ``1 / sqrt(192)`` taken as ``1 / sqrt(128)`` (here 24 and 16), the
second loss term's weight, the selection bias, the shared expert. Each
reads ``correct`` false by a limit of the comparison, against the shipped
reference through the shipped harness."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import chipbench_tiny_joyai as tiny_cell  # noqa: E402
import joyai_tiny as tiny  # noqa: E402

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
    from tpu_ddp.models import decoder, moe

    changes = {}
    nope = tiny.SIZES["qk_nope_head_dim"]
    attend = decoder.reference_attention
    if fault == "rotary_key":    # a key's rotary part reads as zeros
        monkeypatch.setattr(
            decoder, "reference_attention", lambda q, k, v, **how: attend(
                q, k.at[..., nope:].set(0.0), v, **how))
    elif fault == "scale":       # 1 / sqrt(values' width), not of the keys'
        wrong = math.sqrt(tiny.SIZES["qk_head_dim"]
                          / tiny.SIZES["v_head_dim"])
        monkeypatch.setattr(
            decoder, "reference_attention", lambda q, k, v, **how: attend(
                q * wrong, k, v, **how))
    elif fault == "lambda":
        changes["mtp_weight"] = 0.0
    elif fault == "selection_bias":
        monkeypatch.setattr(decoder, "DroplessMoE",
                            _silenced(decoder.DroplessMoE, "router_bias"))
    elif fault == "shared_expert":   # its leaves are there and add nothing
        shared = moe.SwiGLU

        class Silent(shared):
            def __call__(self, x):
                return 0.0 * shared.__call__(self, x)

        Silent.__name__ = shared.__name__
        monkeypatch.setattr(moe, "SwiGLU", Silent)
    else:
        raise ValueError(fault)
    tiny.register(**changes)


@pytest.mark.parametrize("fault", [
    "rotary_key", "scale", "lambda", "selection_bias", "shared_expert"])
def test_a_model_with_a_piece_left_out_is_not_correct(tmp_path, monkeypatch,
                                                      fault):
    _break(fault, monkeypatch)
    try:
        result = tiny_cell.run(tmp_path)
    finally:
        tiny.register()
    assert result["correct"] is False
    assert tiny_cell.failed(result), result["compared"]
