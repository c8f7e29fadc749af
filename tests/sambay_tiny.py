"""A decoder-hybrid-decoder of the program's ``models/sambay.py`` at a size a
CPU test can hold, registered as ``tiny_sambay`` so that the ``Trainer``
builds it by name; and the matching ``arch`` of the benchmark's plain
reference (``chipbench/reference/phi4-mini-flash.py``). ``LAYERS``
published layers, by default all of them: with twelve, layers 0-6 are the
self-decoder (Mamba at 0, 2, 4, 6, window attention at 1, 3, 5), layer 7
the full attention, and the cross-decoder has two Gated Memory Units (8,
10) and two cross-attention layers (9, 11), so the memory and the keys and
values have two readers each."""

import importlib.util
import json
import os

from hybrid_tiny import program_tree  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = 12
VOCAB, HIDDEN, T = 50, 32, 28        # 28 positions: three and a half blocks
SIZES = dict(hidden_size=HIDDEN, intermediate_size=48, head_dim=8,
             num_attention_heads=4, num_key_value_heads=2, sliding_window=5,
             mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
             mamba_dt_rank=2, vocab_size=VOCAB)


def reference():
    spec = importlib.util.spec_from_file_location(
        "phi4_mini_flash_reference",
        os.path.join(REPO, "chipbench", "reference", "phi4-mini-flash.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def arch(*, layers=LAYERS, first_layer=0, num_layers=None):
    with open(os.path.join(REPO, "chipbench", "configs",
                           "phi4-mini-flash.json")) as f:
        a = json.load(f)
    a.update(SIZES, num_hidden_layers=layers, first_layer=first_layer,
             layers_here=layers - first_layer if num_layers is None
             else num_layers)
    a["published"] = dict(num_hidden_layers=layers, vocab_size=VOCAB)
    return a


def spec(*, layers=LAYERS, first_layer=0, num_layers=None, **changes):
    from tpu_ddp.models.sambay import SambaYSpec

    fields = dict(
        layers=layers, first_layer=first_layer,
        num_layers=layers - first_layer if num_layers is None
        else num_layers, vocab_rows=VOCAB, hidden=HIDDEN,
        mlp_width=SIZES["intermediate_size"],
        heads=SIZES["num_attention_heads"],
        kv_heads=SIZES["num_key_value_heads"], head_dim=SIZES["head_dim"],
        window=SIZES["sliding_window"], mamba_every=2,
        inner=SIZES["mamba_expand"] * HIDDEN, state=SIZES["mamba_d_state"],
        conv_kernel=SIZES["mamba_d_conv"], dt_rank=SIZES["mamba_dt_rank"])
    fields.update(changes)
    return SambaYSpec(**fields)


def register(**changes):
    from tpu_ddp.models.sambay import SambaYDecoder
    from tpu_ddp.models.zoo import MODEL_REGISTRY

    def tiny_sambay(num_classes=10, bn_cross_replica_axis=None, dtype=None,
                    **share):
        del num_classes, bn_cross_replica_axis
        return SambaYDecoder(spec(**share, **changes), dtype=dtype)

    MODEL_REGISTRY["tiny_sambay"] = tiny_sambay


def tokens(size, seed=0, length=T):
    from tpu_ddp.data.tokens import synthetic_tokens

    return synthetic_tokens(size, VOCAB, seed, seq_len=length)

