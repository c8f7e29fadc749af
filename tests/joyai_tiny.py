"""``joyai_llm_flash`` of the program's ``models/decoder.py`` at a size a CPU
test can hold, with everything the published one has (latent attention with
keys of 24 over values of 16 and one shared rotary key, a dense layer and two
sparse ones with a selection bias, the prediction module), registered as
``tiny_joyai`` so that the ``Trainer`` builds it by name; and the matching
``arch`` of the benchmark's plain reference
(``chipbench/reference/joyai-llm-flash.py``). ``WHOLE`` experts is the uncut
tiny layer's; ``arch()`` / ``spec()`` by default hold four of its sixteen."""

import dataclasses
import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, HIDDEN, T, LAYERS = 50, 32, 28, 3
WHOLE, HELD, OFFSET = 16, 4, 4
SIZES = dict(hidden_size=HIDDEN, num_attention_heads=4,
             num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
             head_dim=8, v_head_dim=16, intermediate_size=48,
             moe_intermediate_size=12, num_experts_per_tok=4,
             vocab_size=VOCAB, layers_here=LAYERS)


def reference():
    spec = importlib.util.spec_from_file_location(
        "joyai_llm_flash_reference",
        os.path.join(REPO, "chipbench", "reference", "joyai-llm-flash.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def arch(*, held=HELD, offset=OFFSET):
    with open(os.path.join(REPO, "chipbench", "configs",
                           "joyai-llm-flash.json")) as f:
        a = json.load(f)
    a.update(SIZES, n_routed_experts=held, expert_offset=offset)
    a["published"] = dict(a["published"], n_routed_experts=WHOLE,
                          vocab_size=VOCAB)
    return a


def spec(*, held=HELD, offset=OFFSET, **changes):
    from tpu_ddp.models.decoder import (
        DecoderSpec, LatentSpec, LayerSpec, Rotary)

    layer = LayerSpec(
        heads=SIZES["num_attention_heads"], window=0,
        rotary=Rotary(dims=SIZES["qk_rope_head_dim"], theta=32000000.0),
        sparse=True, gate=False,
        latent=LatentSpec(q_rank=SIZES["q_lora_rank"],
                          kv_rank=SIZES["kv_lora_rank"],
                          nope_dim=SIZES["qk_nope_head_dim"],
                          rope_dim=SIZES["qk_rope_head_dim"],
                          v_dim=SIZES["v_head_dim"]))
    fields = dict(
        vocab_rows=VOCAB, hidden=HIDDEN, head_dim=SIZES["qk_head_dim"],
        kv_heads=SIZES["num_key_value_heads"],
        layers=tuple(dataclasses.replace(layer, sparse=i > 0)
                     for i in range(LAYERS)),
        dense_width=SIZES["intermediate_size"], num_experts=WHOLE,
        experts_held=held, expert_offset=offset,
        top_k=SIZES["num_experts_per_tok"],
        expert_width=SIZES["moe_intermediate_size"],
        shared_width=SIZES["moe_intermediate_size"], routed_scaling=2.5,
        selection_bias=True, mtp=layer, mtp_weight=0.3)
    fields.update(changes)
    return DecoderSpec(**fields)


def register(**changes):
    from tpu_ddp.models.decoder import SparseDecoder
    from tpu_ddp.models.zoo import MODEL_REGISTRY

    def tiny_joyai(num_classes=10, bn_cross_replica_axis=None, dtype=None,
                   **share):
        del num_classes, bn_cross_replica_axis
        return SparseDecoder(spec(**share, **changes), dtype=dtype)

    MODEL_REGISTRY["tiny_joyai"] = tiny_joyai


def tokens(size, seed=0, length=T):
    from tpu_ddp.data.tokens import synthetic_tokens

    return synthetic_tokens(size, VOCAB, seed, seq_len=length)


def program_tree(ref, a, params):
    """The reference's flat leaves as the program's nested ``params``."""
    tree = {}
    for leaf, path in ref.program_names(a).items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = params[leaf]
    return tree
