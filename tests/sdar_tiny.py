"""``sdar_30b_a3b`` of the program's ``models/decoder.py`` at a size a CPU
test can hold, with everything the published one has (grouped heads with
query and key norms, a softmax router over sixteen experts with four a
token, no shared expert, block diffusion in blocks of four with the mask on
the last vocabulary row), registered as ``tiny_sdar`` so that the
``Trainer`` builds it by name; and the matching ``arch`` of the benchmark's
plain reference (``chipbench/reference/sdar-30b-a3b.py``). ``WHOLE`` experts
is the uncut tiny layer's; ``arch()`` / ``spec()`` by default hold four of
its sixteen."""

import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, HIDDEN, T, LAYERS, BLOCK = 50, 32, 24, 3, 4
WHOLE, HELD, OFFSET = 16, 4, 4
SIZES = dict(hidden_size=HIDDEN, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, moe_intermediate_size=12,
             num_experts_per_tok=4, vocab_size=VOCAB, layers_here=LAYERS,
             block_length=BLOCK, mask_token_id=VOCAB - 1)


def reference():
    spec = importlib.util.spec_from_file_location(
        "sdar_30b_a3b_reference",
        os.path.join(REPO, "chipbench", "reference", "sdar-30b-a3b.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def arch(*, held=HELD, offset=OFFSET, **sizes):
    with open(os.path.join(REPO, "chipbench", "configs",
                           "sdar-30b-a3b.json")) as f:
        a = json.load(f)
    a.update(SIZES, num_experts=held, expert_offset=offset)
    a.update(sizes)
    a["published"] = dict(a["published"], num_experts=WHOLE,
                          vocab_size=VOCAB)
    return a


def spec(*, held=HELD, offset=OFFSET, **changes):
    from tpu_ddp.models.decoder import (
        BlockDiffusion, DecoderSpec, LayerSpec, Rotary)

    layer = LayerSpec(heads=SIZES["num_attention_heads"], window=0,
                      rotary=Rotary(dims=SIZES["head_dim"], theta=1000000.0),
                      sparse=True, gate=False)
    fields = dict(
        vocab_rows=VOCAB, hidden=HIDDEN, head_dim=SIZES["head_dim"],
        kv_heads=SIZES["num_key_value_heads"], layers=(layer,) * LAYERS,
        dense_width=48, num_experts=WHOLE, experts_held=held,
        expert_offset=offset, top_k=SIZES["num_experts_per_tok"],
        expert_width=SIZES["moe_intermediate_size"], shared_width=0,
        routed_scaling=1.0, qk_norm=True, router_score="softmax",
        diffusion=BlockDiffusion(block=BLOCK, mask_id=VOCAB - 1))
    fields.update(changes)
    return DecoderSpec(**fields)


def register(**changes):
    from tpu_ddp.models.decoder import SparseDecoder
    from tpu_ddp.models.zoo import MODEL_REGISTRY

    def tiny_sdar(num_classes=10, bn_cross_replica_axis=None, dtype=None,
                  **share):
        del num_classes, bn_cross_replica_axis
        return SparseDecoder(spec(**share, **changes), dtype=dtype)

    MODEL_REGISTRY["tiny_sdar"] = tiny_sdar


def tokens(size, seed=0, length=T):
    """Data ids under the mask's: 0 .. VOCAB - 2."""
    from tpu_ddp.data.tokens import synthetic_tokens

    return synthetic_tokens(size, VOCAB - 1, seed, seq_len=length)


def program_tree(ref, a, params):
    """The reference's flat leaves as the program's nested ``params``."""
    tree = {}
    for leaf, path in ref.program_names(a).items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = params[leaf]
    return tree
