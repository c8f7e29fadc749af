"""A decoder of the program's ``models/decoder.py`` at a size a CPU test can
hold, with every kind of layer the published one has (full + dense, window +
sparse x3, full + sparse), registered as ``tiny_decoder`` so that the
``Trainer`` builds it by name; and the matching ``arch`` of the benchmark's
plain reference (``chipbench/reference/laguna-xs2.py``)."""

import dataclasses
import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, HIDDEN, HEAD, KV, WINDOW, T = 50, 64, 16, 2, 8, 24
EXPERTS, HELD, OFFSET, TOP_K = 16, 4, 4, 3


def reference():
    spec = importlib.util.spec_from_file_location(
        "laguna_xs2_reference",
        os.path.join(REPO, "chipbench", "reference", "laguna-xs2.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def arch(*, layers=5, held=HELD, offset=OFFSET):
    with open(os.path.join(REPO, "chipbench", "configs",
                           "laguna-xs2.json")) as f:
        a = json.load(f)
    a.update(hidden_size=HIDDEN, head_dim=HEAD, num_key_value_heads=KV,
             intermediate_size=96, moe_intermediate_size=24,
             shared_expert_intermediate_size=24, sliding_window=WINDOW,
             layers_here=layers, num_experts=held, vocab_size=VOCAB,
             num_experts_per_tok=TOP_K, expert_offset=offset)
    a["published"] = dict(a["published"], num_experts=EXPERTS)
    a["num_attention_heads_per_layer"] = [4, 6, 6, 6] * 10
    a["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 16
    return a


def spec(*, layers=5, held=HELD, offset=OFFSET):
    from tpu_ddp.models import decoder as D

    full = dataclasses.replace(
        D._LAGUNA_FULL, dims=HEAD // 2,
        yarn=(64.0, 16, 64.0, 1.0, 1.4158883083359672))
    sliding = dataclasses.replace(D._LAGUNA_SLIDING, dims=HEAD)
    kinds = tuple(
        D.LayerSpec(heads=4, window=0, rotary=full, sparse=i > 0)
        if i % 4 == 0 else
        D.LayerSpec(heads=6, window=WINDOW, rotary=sliding, sparse=True)
        for i in range(layers))
    return D.DecoderSpec(
        vocab_rows=VOCAB, hidden=HIDDEN, head_dim=HEAD, kv_heads=KV,
        layers=kinds, dense_width=96, num_experts=EXPERTS, experts_held=held,
        expert_offset=offset, top_k=TOP_K, expert_width=24, shared_width=24,
        routed_scaling=2.5)


def register():
    from tpu_ddp.models import decoder as D
    from tpu_ddp.models.zoo import MODEL_REGISTRY

    def tiny_decoder(num_classes=10, bn_cross_replica_axis=None, dtype=None,
                     **share):
        del num_classes, bn_cross_replica_axis
        return D.SparseDecoder(spec(**share), dtype=dtype)

    MODEL_REGISTRY["tiny_decoder"] = tiny_decoder


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
