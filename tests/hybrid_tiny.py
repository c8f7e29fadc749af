"""A hybrid decoder of the program's ``models/hybrid.py`` at a size a CPU
test can hold, with every kind of block the published one has (Mamba-2,
latent experts, attention: ``ME*ME``), registered as ``tiny_hybrid`` so that
the ``Trainer`` builds it by name; and the matching ``arch`` of the
benchmark's plain reference (``chipbench/reference/nemotron3-super.py``).
``WHOLE`` is the uncut tiny model, ``arch()`` / ``spec()`` by default one of
eight head positions' share of it with four of its sixteen experts: five
choices a token, so more than the experts held."""

import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PATTERN = "ME*ME"
VOCAB, HIDDEN, T = 50, 64, 28          # 28 positions: three and a half chunks
WHOLE = dict(mamba_num_heads=16, n_groups=8, num_attention_heads=8,
             num_key_value_heads=2, n_routed_experts=16)
POSITIONS, POSITION, HELD, OFFSET = 8, 5, 4, 4
SIZES = dict(hidden_size=HIDDEN, head_dim=16, mamba_head_dim=8,
             ssm_state_size=16, chunk_size=8, moe_latent_size=32,
             moe_intermediate_size=24,
             moe_shared_expert_intermediate_size=48, num_experts_per_tok=5,
             vocab_size=VOCAB, hybrid_override_pattern=PATTERN,
             layers_here=len(PATTERN))


def reference():
    spec = importlib.util.spec_from_file_location(
        "nemotron3_super_reference",
        os.path.join(REPO, "chipbench", "reference", "nemotron3-super.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def whole_arch():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron3-super.json")) as f:
        a = json.load(f)
    a.update(SIZES, **WHOLE, expert_offset=0, head_position=0,
             head_positions=1)
    a["published"] = dict(a["published"], **WHOLE)
    return a


def arch(*, positions=POSITIONS, position=POSITION, held=HELD, offset=OFFSET):
    a = whole_arch()
    part = lambda n: max(n // positions, 1)  # noqa: E731
    a.update({key: part(WHOLE[key]) for key in (
        "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads")})
    a.update(n_routed_experts=held, expert_offset=offset,
             head_position=position, head_positions=positions)
    return a


def spec(*, positions=POSITIONS, position=POSITION, held=HELD, offset=OFFSET,
         **changes):
    from tpu_ddp.models.hybrid import HybridSpec
    from tpu_ddp.parallel.expert_parallel import HeadShare

    share = HeadShare(positions, position)
    fields = dict(
        pattern=PATTERN, vocab_rows=VOCAB, hidden=HIDDEN,
        heads=share.of(WHOLE["num_attention_heads"])[0],
        kv_heads=share.of(WHOLE["num_key_value_heads"])[0],
        head_dim=SIZES["head_dim"],
        mamba_heads=share.of(WHOLE["mamba_num_heads"])[0],
        mamba_head_dim=SIZES["mamba_head_dim"],
        groups=share.of(WHOLE["n_groups"])[0],
        state=SIZES["ssm_state_size"], conv_kernel=4,
        chunk=SIZES["chunk_size"], num_experts=WHOLE["n_routed_experts"],
        experts_held=held, expert_offset=offset,
        top_k=SIZES["num_experts_per_tok"],
        expert_width=SIZES["moe_intermediate_size"],
        shared_width=SIZES["moe_shared_expert_intermediate_size"],
        latent=SIZES["moe_latent_size"], routed_scaling=5.0)
    fields.update(changes)
    return HybridSpec(**fields)


def register(**changes):
    from tpu_ddp.models.hybrid import HybridDecoder
    from tpu_ddp.models.zoo import MODEL_REGISTRY

    def tiny_hybrid(num_classes=10, bn_cross_replica_axis=None, dtype=None,
                    **share):
        del num_classes, bn_cross_replica_axis
        return HybridDecoder(spec(**share, **changes), dtype=dtype)

    MODEL_REGISTRY["tiny_hybrid"] = tiny_hybrid


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
