"""The hybrid decoder (``models/hybrid.py``: Mamba-2 blocks over
``ops/ssd_scan.py``, latent expert blocks of ``models/moe.py::DroplessMoE``,
attention blocks of ``models/decoder.py::GroupedQueryAttention``) against the
benchmark's plain reference (``chipbench/reference/nemotron3-super.py``) on
seeded weights at a size a CPU holds; the chunked scan against the
recurrence; the share test; the ``Trainer`` driving it; and the sparse
decoder's traced step, which the options all this added must not move.
"""

import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import decoder_tiny  # noqa: E402
import hybrid_tiny as tiny  # noqa: E402


@pytest.fixture(scope="module")
def ref():
    return tiny.reference()


@pytest.fixture(scope="module")
def seeded(ref):
    arch = tiny.arch()
    return (arch, ref.init_params(arch, 7),
            jnp.asarray(tiny.tokens(2, seed=3)[0]))


def _assert_same(got, want, atol=2e-5):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, atol=atol * float(jnp.max(jnp.abs(w))) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_logits_loss_and_gradients_match_the_reference(ref, seeded, remat):
    from tpu_ddp.models.hybrid import HybridDecoder

    arch, params, tokens = seeded
    model = HybridDecoder(tiny.spec(), remat=remat)
    tree = tiny.program_tree(ref, arch, params)
    init = model.init(jax.random.key(0), tokens[:, :8])["params"]
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, tree)
    mask = jnp.ones(tokens.shape, bool)

    def program_loss(tree):
        logits, _ = model.apply({"params": tree}, tokens,
                                mutable=["counters"])
        return ref.next_token_loss(logits, tokens, mask), logits

    def reference_loss(params):
        logits = ref.forward(arch, params, tokens)
        return ref.next_token_loss(logits, tokens, mask), logits

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            program_loss, has_aux=True)(tree)
        (want, want_logits), want_grads = jax.value_and_grad(
            reference_loss, has_aux=True)(params)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    _assert_same(grads, tiny.program_tree(ref, arch, want_grads))
    # the selection bias chooses and does not weigh: no gradient reaches it
    assert not np.any(want_grads["block_1.mixer.router_bias"])
    assert not np.any(grads["block_1"]["mixer"]["router_bias"])


def _bfloat16_loss(remat, **spec_changes):
    """``(loss, tree, tokens)`` of the tiny decoder as the cell runs it:
    bfloat16, blocks recomputed or not."""
    from tpu_ddp.models.hybrid import HybridDecoder

    tokens = jnp.asarray(tiny.tokens(2, seed=3)[0])
    model = HybridDecoder(tiny.spec(**spec_changes), dtype=jnp.bfloat16,
                          remat=remat)
    tree = model.init(jax.random.key(0), tokens[:, :8])["params"]

    def loss(tree):
        logits, _ = model.apply({"params": tree}, tokens,
                                mutable=["counters"])
        return jnp.mean(logits ** 2)

    return loss, tree, tokens


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_an_expert_block_makes_its_routers_choice_once(remat):
    """A recomputed expert block keeps its router's logits, chosen ids and
    their scores (``moe.LOGITS_NAME``, ``IDS_NAME``, ``SCORES_NAME``) beside
    the routed result, so its backward pass makes neither the float32
    product nor the ``top_k`` again: one ``top_k`` an ``E`` block and the
    router's three ``HIGHEST`` products (forward, and the gradients of
    operand and weight), with recomputation as without. The chosen scores
    are read by compares against the expert axis (``moe._chosen``): no call
    of ``take_along_axis``, and no gather or scatter that makes or consumes
    a (tokens, choices) float32, in either pass."""
    loss, tree, _ = _bfloat16_loss(remat)
    text = jax.jit(jax.value_and_grad(loss)).lower(tree).as_text()
    blocks = tiny.PATTERN.count("E")
    n, c, e = 2 * tiny.T, tiny.HIDDEN, tiny.WHOLE["n_routed_experts"]
    k = tiny.SIZES["num_experts_per_tok"]
    assert text.count("chlo.top_k") == blocks
    assert "take_along_axis" not in text
    assert not [line for line in text.splitlines()
                if re.search(r"stablehlo\.(gather|scatter)", line)
                and f"tensor<{n}x{k}xf32>" in line]
    router_shaped = [f"-> tensor<{a}x{b}xf32>"
                     for a, b in ((n, e), (e, c), (n, c))]
    products = [line for line in text.splitlines()
                if "precision = [HIGHEST, HIGHEST]" in line
                and line.rstrip().endswith(tuple(router_shaped))]
    assert len(products) == 3 * blocks, products


def test_a_recomputed_expert_block_keeps_its_routers_choice(capsys):
    """What an expert block hands its backward pass from inside the layer,
    beside the block's input and the weights: the three values
    ``HybridDecoder``'s policy names of the router, (tokens, experts)
    float32 logits, (tokens, choices) ids (which are ``moe._chosen``'s one
    residual too) and float32 scores, and the (tokens, latent) routed
    result: nothing (tokens, hidden) wide, nothing of the sorts, nothing
    (tokens, choices, experts) wide."""
    from jax.ad_checkpoint import print_saved_residuals

    spec = tiny.spec(pattern="E")
    loss, tree, tokens = _bfloat16_loss(True, pattern="E")
    print_saved_residuals(loss, tree)  # "f32[56,16] output of ... (where)"
    kept = sorted(line.split()[0]
                  for line in capsys.readouterr().out.splitlines()
                  if line.endswith("(DroplessMoE.__call__)"))
    n, k = tokens.size, spec.top_k
    assert kept == sorted([f"f32[{n},{spec.num_experts}]", f"i32[{n},{k}]",
                           f"f32[{n},{k}]", f"bf16[{n},{spec.latent}]"])


@pytest.mark.parametrize("wrap", [jax.jit, jax.checkpoint],
                         ids=["jit", "checkpoint"])
@pytest.mark.parametrize("n,k,e", [(56, 4, 16), (64, 8, 256), (32, 22, 512)])
def test_the_chosen_scores_are_take_along_axis_to_the_bit(n, k, e, wrap):
    """``moe._chosen`` against ``jnp.take_along_axis`` in float32 on the
    ids of a real ``top_k`` of ``scored + bias``: the same values, and of
    their weighed sum the same gradient with respect to ``scored`` (the
    scatter-add's values at its places, zeros elsewhere); and no gradient
    reaches the bias, which chooses and does not weigh."""
    from tpu_ddp.models import moe

    keys = jax.random.split(jax.random.key(n + k + e), 3)
    scored = jax.nn.sigmoid(jax.random.normal(keys[0], (n, e)))
    bias = 0.1 * jax.random.normal(keys[1], (e,))
    weigh = jax.random.normal(keys[2], (n, k))

    def weighed(read, scored, bias):
        _, ids = jax.lax.top_k(scored + bias, k)
        scores = read(scored, ids)
        return jnp.sum(scores * weigh), scores

    def both(read):
        run = wrap(lambda scored, bias: weighed(read, scored, bias))
        (_, scores), grads = jax.value_and_grad(
            run, (0, 1), has_aux=True)(scored, bias)
        return scores, grads

    got, (d_scored, d_bias) = both(lambda s, ids: moe._chosen(s, ids, e))
    want, (want_scored, _) = both(
        lambda s, ids: jnp.take_along_axis(s, ids, axis=-1))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(d_scored, want_scored)
    assert np.count_nonzero(d_scored) == n * k
    assert not np.any(d_bias)


def test_the_references_blocked_loss_is_its_loss_of_the_logits(ref, seeded,
                                                                monkeypatch):
    """``follow`` takes the head and the softmax a block of positions at a
    time and the stack a run of blocks at a time, for the chip's memory: the
    same loss and the same gradient as the logits' own, masked rows too."""
    arch, params, tokens = seeded
    monkeypatch.setattr(ref, "LOSS_BLOCK", 7)   # 28 positions: four blocks
    mask = np.ones(tokens.shape, bool)
    mask[0, 10:] = False
    mask = jnp.asarray(mask)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(
            lambda p: ref.sequence_loss(arch, p, tokens, mask))(params)
        want, want_grads = jax.value_and_grad(
            lambda p: ref.next_token_loss(ref.forward(arch, p, tokens),
                                          tokens, mask))(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    _assert_same(grads, want_grads)


# -- the scan --------------------------------------------------------------------

def _scan_case(t, groups, seed, heads=4, p=8, n=16, b=2):
    keys = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(keys[0], (b, t, heads, p)),
            jax.nn.softplus(jax.random.normal(keys[1], (b, t, heads)) - 1),
            -jnp.exp(0.5 * jax.random.normal(keys[2], (heads,))),
            jax.random.normal(keys[3], (b, t, groups, n)),
            jax.random.normal(keys[4], (b, t, groups, n)),
            jax.random.normal(keys[5], (b, t, heads, p)))


@pytest.mark.parametrize("t,chunk,groups", [
    (37, 8, 2),      # four chunks and five positions of a fifth
    (8, 8, 4),       # one chunk
    (64, 16, 1),     # whole chunks, every head on one group
    (5, 128, 2),     # shorter than a chunk
], ids=["ragged", "one_chunk", "whole_chunks", "short"])
def test_the_chunked_scan_is_the_recurrence_in_values_and_gradients(
        t, chunk, groups):
    from tpu_ddp.ops.ssd_scan import ssd_scan, ssd_scan_stepwise

    *operands, weigh = _scan_case(t, groups, seed=t)
    weighed = lambda scan: (lambda *a: jnp.sum(scan(*a) * weigh))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*operands, chunk)
        want = ssd_scan_stepwise(*operands)
        grads = jax.grad(weighed(lambda *a: ssd_scan(*a, chunk)),
                         range(5))(*operands)
        want_grads = jax.grad(weighed(ssd_scan_stepwise), range(5))(*operands)
    np.testing.assert_allclose(got, want, atol=2e-5 * float(
        jnp.max(jnp.abs(want))))
    for name, g, w in zip(("x", "dt", "A", "B", "C"), grads, want_grads):
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))), err_msg=name)


def test_the_scan_keeps_its_operands_and_the_chunk_states_and_no_score_block():
    """The residuals of the hand-written backward pass: the five operands
    and the (chunks, heads, head_dim, state) states; nothing of (chunk,
    chunk) a chunk."""
    from tpu_ddp.ops.ssd_scan import ssd_scan

    *operands, _ = _scan_case(64, 2, seed=1)
    _, pull = jax.vjp(lambda *a: ssd_scan(*a, 16), *operands)
    kept = sorted(leaf.shape for leaf in jax.tree.leaves(pull))
    assert kept == sorted([o.shape for o in operands] + [(2, 4, 2, 2, 8, 16)])


def test_the_scan_names_its_passes_for_the_trace():
    from tpu_ddp.ops.ssd_scan import ssd_scan

    *operands, _ = _scan_case(16, 2, seed=2)
    fn = jax.grad(lambda *a: jnp.sum(ssd_scan(*a, 8)), range(5))
    text = jax.jit(fn).lower(*operands).as_text(debug_info=True)
    assert "tpu_ddp.kernel.ssd_scan_fwd" in text
    assert "tpu_ddp.kernel.ssd_scan_bwd" in text


def _kernel_case(t=24, heads=4, groups=2, p=8, n=16, b=2, rate=1.0,
                 dtype=jnp.float32):
    """Operands in ``dtype`` (``dt`` and ``A`` float32, as the mixer has
    them) and the weights of a loss; ``rate`` scales ``A``."""
    x, dt, A, B, C, weigh = _scan_case(t, groups, seed=t + heads, heads=heads,
                                       p=p, n=n, b=b)
    return (x.astype(dtype), dt, A * rate, B.astype(dtype),
            C.astype(dtype)), weigh


def _values_and_gradients(scan, operands, weigh):
    def loss(*a):
        y = scan(*a)
        return jnp.sum(y.astype(jnp.float32) * weigh), y

    (_, y), grads = jax.value_and_grad(loss, argnums=range(5), has_aux=True)(
        *operands)
    return (y,) + grads


def _under_checkpoint(scan):
    from tpu_ddp.models import decoder

    return jax.checkpoint(scan, policy=jax.checkpoint_policies
                          .save_only_these_names(*decoder.KEPT_NAMES))


def _over_two_shards(scan):
    """``scan`` inside a ``shard_map`` over ``data`` = 2, the sequences
    split and ``A`` the same on both: off the TPU the recurrence stands in
    for the interpreted kernels there, and ``dA`` is the shards' sum."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    rows = P("data")
    return jax.shard_map(scan, mesh=mesh,
                         in_specs=(rows, rows, P(), rows, rows),
                         out_specs=rows)


KERNEL_CASES = {
    # several heads a group and several groups, in tiles of two heads
    "heads_and_groups": (dict(t=32, heads=8, groups=2, p=64, n=16), 16, None),
    "a_head_a_tile": (dict(t=16, heads=2, groups=1, p=128, n=8), 8, None),
    "every_head_one_tile": (dict(t=16, heads=4, groups=1), 8, None),
    # five positions of a fourth chunk: padded with ``dt = 0``
    "ragged": (dict(t=29), 8, None),
    "chunk_over_length": (dict(t=5), 128, None),
    "one_chunk": (dict(t=8), 8, None),
    "many_chunks": (dict(t=48), 4, None),
    # decays that underflow inside a chunk: ``exp(-inf)`` under the
    # triangle, never ``inf * 0``
    "strongly_negative_A": (dict(t=32, rate=120.0), 16, None),
    "under_checkpoint": (dict(t=16), 8, _under_checkpoint),
    "two_shards": (dict(t=16), 8, _over_two_shards),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_scans_kernels_are_the_recurrence(case, dtype):
    """``ssd_scan``'s two kernels, interpreted: ``y`` and all five
    gradients against the recurrence one position at a time on the same
    operands in float32; in bfloat16 to the roundings of the matrix unit's
    operands."""
    from tpu_ddp.ops.ssd_scan import ssd_scan, ssd_scan_stepwise

    sizes, chunk, wrap = KERNEL_CASES[case]
    operands, weigh = _kernel_case(dtype=dtype, **sizes)
    scan = lambda *a: ssd_scan(*a, chunk)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = _values_and_gradients(wrap(scan) if wrap else scan, operands,
                                    weigh)
        want = _values_and_gradients(
            ssd_scan_stepwise, [a.astype(jnp.float32) for a in operands],
            weigh)
    rounded = dtype == jnp.bfloat16
    for name, g, w, like in zip(("y", "x", "dt", "A", "B", "C"), got, want,
                                operands[:1] + operands):
        assert g.shape == w.shape and g.dtype == like.dtype, name
        g = np.asarray(g.astype(jnp.float32))
        assert np.isfinite(g).all(), name
        # ``la``'s gradient is what is left of two sums that cancel: where
        # the decays underflow there is less gradient in ``ddt`` and ``dA``
        # than rounding of those sums (the array form this replaced read
        # the same to four digits), so they are held to be finite
        if case == "strongly_negative_A" and name in ("dt", "A"):
            continue
        loose = (1e-1 if name == "A" else 3e-2) if rounded else 2e-5
        np.testing.assert_allclose(
            g, w, atol=loose * float(jnp.max(jnp.abs(w))) + 1e-30,
            err_msg=name)


def test_a_recomputed_caller_runs_the_forward_kernel_in_both_passes():
    """The scan names nothing for a recomputation policy (PERF.md section
    6, PR 47: keeping ``y`` and the states cost the cell's step more than
    the call they save), so under the decoder stacks' policy a recomputed
    caller has the forward kernel twice and the backward one once, as it
    has under a policy that keeps nothing."""
    from tpu_ddp.models import decoder
    from tpu_ddp.ops.ssd_scan import ssd_scan

    operands, weigh = _kernel_case(t=16)

    def calls(policy):
        scan = jax.checkpoint(lambda *a: ssd_scan(*a, 8), policy=policy)
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(scan(*a) * weigh), range(5)))(*operands))
        return text.count("pallas_call")

    policies = jax.checkpoint_policies
    assert calls(policies.nothing_saveable) == 3
    assert calls(policies.save_only_these_names(*decoder.KEPT_NAMES)) == 3


def test_the_scans_kernels_trace_inside_a_shard_map(monkeypatch):
    """The train steps call the kernels inside a ``shard_map`` over
    ``data``, where activations vary over the mesh and ``A`` does not: the
    kernels' results say so, their loops carry nothing from an operand, and
    ``A``'s gradient is summed over the shards by AD. Traced as the chip
    compiles it, not lowered."""
    from jax.sharding import Mesh, PartitionSpec as P

    from tpu_ddp.ops.ssd_scan import ssd_scan
    from tpu_ddp.parallel import runtime

    monkeypatch.setattr(runtime, "is_tpu_device", lambda: True)
    (x, dt, A, B, C), _ = _kernel_case(t=16)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def shard(x, dt, A, B, C):
        value, dA = jax.value_and_grad(
            lambda A: jnp.sum(ssd_scan(x, dt, A, B, C, 8)))(A)
        return jax.lax.pmean(value, "data"), dA

    rows = P("data")
    text = str(jax.make_jaxpr(jax.shard_map(
        shard, mesh=mesh, in_specs=(rows, rows, P(), rows, rows),
        out_specs=(P(), P())))(x, dt, A, B, C))
    assert text.count("pallas_call") == 2 and "psum" in text


# -- the share test ----------------------------------------------------------------

def _mixer_tree(ref, leaves):
    """A mixer's reference leaves as the program's module takes them."""
    tree = {}
    for leaf, value in leaves.items():
        path = tuple(leaf.split("."))
        path = path if path[-1] in ref.BARE else path + ("kernel",)
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return tree


def _program_mixer(kind, spec):
    from tpu_ddp.models.decoder import GroupedQueryAttention, LayerSpec
    from tpu_ddp.models.hybrid import Mamba2Mixer
    from tpu_ddp.models.moe import DroplessMoE
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    if kind == "M":
        return Mamba2Mixer(spec.mamba_heads, spec.mamba_head_dim,
                           spec.groups, spec.state, spec.conv_kernel,
                           spec.chunk, spec.norm_eps)
    if kind == "*":
        return GroupedQueryAttention(
            LayerSpec(heads=spec.heads, window=0, rotary=None, sparse=False,
                      gate=False), spec.kv_heads, spec.head_dim)
    return DroplessMoE(
        ExpertShare(spec.num_experts, spec.experts_held, spec.expert_offset),
        top_k=spec.top_k, expert_width=spec.expert_width,
        shared_width=spec.shared_width, scaling=spec.routed_scaling,
        gated=False, latent=spec.latent, selection_bias=True)


@pytest.mark.parametrize("kind,block", [("M", 0), ("*", 2), ("E", 1)],
                         ids=["mamba", "attention", "experts"])
def test_the_shares_add_up_to_the_uncut_block(ref, kind, block):
    """The guide's share test: the partial results of a block's mixer over
    the eight head positions (Mamba, attention) or over all expert offsets
    (the expert block; the shared expert, which every chip computes alike,
    counted once) are the uncut reference's mixer, and the reference given
    a share computes that share's part."""
    whole = tiny.whole_arch()
    params = ref.init_params(whole, 11)
    prefix = f"block_{block}.mixer."
    p = {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}
    u = jax.random.normal(jax.random.key(5), (2, tiny.T, tiny.HIDDEN))
    if kind == "E":
        shares = [dict(positions=1, head_position=0, experts_held=tiny.HELD,
                       expert_offset=o)
                  for o in range(0, WHOLE_EXPERTS, tiny.HELD)]
    else:
        shares = [dict(positions=tiny.POSITIONS, head_position=i,
                       experts_held=WHOLE_EXPERTS, expert_offset=0)
                  for i in range(tiny.POSITIONS)]
    with jax.default_matmul_precision("highest"):
        want = ref.mixer(whole, kind, p, u)
        shared = ref.relu2_mlp(u, p["shared.up"], p["shared.down"],
                               "float32_highest") if kind == "E" else 0.0
        total, landed = shared, 0
        for share in shares:
            here, cut = ref.take_share(whole, p, **share)
            spec = tiny.spec(positions=share["positions"],
                             position=share["head_position"],
                             held=share["experts_held"],
                             offset=share["expert_offset"])
            y, sown = _program_mixer(kind, spec).apply(
                {"params": _mixer_tree(ref, cut)}, u, mutable=["counters"])
            np.testing.assert_allclose(
                y, ref.mixer(here, kind, cut, u,
                             expert_offset=share["expert_offset"]),
                atol=5e-5)
            total = total + (y - shared)
            if kind == "E":
                landed += int(sown["counters"]["expert_load"][0].sum())
    np.testing.assert_allclose(total, want, atol=2e-4)
    if kind == "E":  # every (token, choice) pair landed once
        assert landed == 2 * tiny.T * whole["num_experts_per_tok"]


WHOLE_EXPERTS = tiny.WHOLE["n_routed_experts"]


def test_head_shares_are_checked_and_name_the_heads_held():
    from tpu_ddp.parallel.expert_parallel import HeadShare

    at = [HeadShare(8, i) for i in range(8)]
    assert [s.of(128) for s in at[:3]] == [(16, 0), (16, 16), (16, 32)]
    assert [s.of(8) for s in at[6:]] == [(1, 6), (1, 7)]
    assert [s.of(32)[1] for s in at] == [0, 4, 8, 12, 16, 20, 24, 28]
    # two key-value heads over eight chips: each holds the one its query
    # heads read
    assert [s.of(2) for s in at] == [(1, 0)] * 4 + [(1, 1)] * 4
    assert HeadShare().of(128) == (128, 0)
    with pytest.raises(ValueError, match="position"):
        HeadShare(8, 8)
    with pytest.raises(ValueError, match="divide"):
        HeadShare(8, 0).of(12)


# -- the routed path at more choices than experts held ------------------------

def test_the_ladder_ends_at_the_experts_held_a_token():
    from tpu_ddp.models.moe import buffer_rungs

    # the benchmark cell: 16,384 tokens x 22 choices, 8 of 512 experts held;
    # a token's choices are distinct, so 8 rows a token at most
    assert buffer_rungs(360448, 8, 512, 22) == (11264, 131072)
    assert buffer_rungs(131072, 32, 256, 8) == buffer_rungs(131072, 32, 256)
    assert buffer_rungs(2 * 28 * 5, 4, 16, 5) == (2 * 28 * 4,)


def test_a_router_that_sends_every_token_here_drops_nothing(ref):
    """Every token chooses all four held experts and one other: the one rung
    of four rows a token holds them all, and the block is the reference's."""
    arch = tiny.arch()
    params = ref.init_params(arch, 3)
    p = {k[len("block_1.mixer."):]: v for k, v in params.items()
         if k.startswith("block_1.mixer.")}
    bias = np.zeros(WHOLE_EXPERTS, np.float32)
    bias[tiny.OFFSET:tiny.OFFSET + tiny.HELD] = 4.0
    p["router_bias"] = jnp.asarray(bias)
    u = jax.random.normal(jax.random.key(9), (2, tiny.T, tiny.HIDDEN))
    with jax.default_matmul_precision("highest"):
        y, sown = _program_mixer("E", tiny.spec()).apply(
            {"params": _mixer_tree(ref, p)}, u, mutable=["counters"])
        want = ref.mixer(arch, "E", p, u, expert_offset=tiny.OFFSET)
    assert int(sown["counters"]["expert_load"][0].sum()) == (
        2 * tiny.T * tiny.HELD)
    assert int(sown["counters"]["expert_rows_walked"][0]) == (
        2 * tiny.T * tiny.HELD)
    np.testing.assert_allclose(y, want, atol=5e-5)


# a small share of many experts: 64, 2 a token, experts 0 and 1 held; of
# 2 x 2,048 tokens' 8,192 pairs a fair router lands 256, so 1,024 rows and
# eight times that
T_EXPERTS, T_HELD, T_K, T_T, T_C, T_F = 64, 2, 2, 2048, 16, 8
T_RUNGS = (1024, 8192)


def _small_share_case(landed, seed=0):
    """(layer, params, x) whose router lands ``landed`` pairs on the held
    experts: a token's first three channels say which of three rows of the
    router it reads (both choices held here; none; one), the rest is noise
    the router hardly weighs."""
    from tpu_ddp.models.moe import DroplessMoE
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    layer = DroplessMoE(ExpertShare(T_EXPERTS, T_HELD, 0), top_k=T_K,
                        expert_width=T_F, scaling=2.5)
    keys = jax.random.split(jax.random.key(seed), 3)
    x = np.array(jax.random.normal(keys[0], (2, T_T, T_C)))
    tree = jax.tree.map(np.array, layer.init(keys[1], x)["params"])
    router = 0.02 * np.array(jax.random.normal(keys[2], (T_C, T_EXPERTS)))
    router[:3] = -3.0
    router[0, [0, 1]] = 3.0       # two pairs land
    router[1, [2, 3]] = 3.0       # none lands
    router[2, [1, 2]] = 3.0       # one lands
    kinds = np.ones(2 * T_T, int)
    kinds[:landed // 2] = 0
    kinds[landed // 2:landed // 2 + landed % 2] = 2
    kinds = np.random.default_rng(seed).permutation(kinds)
    x[..., :3] = np.eye(3)[kinds].reshape(2, T_T, 3)
    tree["router"]["kernel"] = router
    return layer, jax.tree.map(jnp.asarray, tree), jnp.asarray(x)


def _every_expert_on_every_token(tree, x, scaling=2.5):
    """The same layer with no buffer at all: each held expert over every
    token, weighed zero where the token did not choose it."""
    xf = x.reshape(-1, x.shape[-1])
    scores, ids = jax.lax.top_k(
        jax.nn.sigmoid(xf @ tree["router"]["kernel"]), T_K)
    weights = scores / scores.sum(axis=-1, keepdims=True) * scaling
    y = 0.0
    for e in range(T_HELD):
        w = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)[:, None]
        h = jax.nn.silu(xf @ tree["w_gate"][e]) * (xf @ tree["w_up"][e])
        y = y + w * (h @ tree["w_down"][e])
    return y.reshape(x.shape)


@pytest.mark.parametrize("landed,rung", [
    (T_RUNGS[0] // 2, T_RUNGS[0]), (T_RUNGS[0], T_RUNGS[0]),
    (T_RUNGS[0] + 1, T_RUNGS[1]), (2 * T_RUNGS[0] + 1, T_RUNGS[1])],
    ids=["half_the_short", "short_to_the_brim", "one_over_the_short",
         "twice_the_short"])
def test_a_small_share_takes_the_shortest_rung_that_holds(landed, rung):
    from tpu_ddp.models.moe import buffer_rungs

    assert buffer_rungs(2 * T_T * T_K, T_HELD, T_EXPERTS) == T_RUNGS
    layer, tree, x = _small_share_case(landed)
    w = jax.random.normal(jax.random.key(17), x.shape)

    def loss(tree, x):
        y, sown = layer.apply({"params": tree}, x, mutable=["counters"])
        return jnp.sum(y * w), (y, sown["counters"])

    with jax.default_matmul_precision("highest"):
        (_, (y, counters)), grads = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(tree, x)
        want, want_grads = jax.value_and_grad(
            lambda tree, x: jnp.sum(
                _every_expert_on_every_token(tree, x) * w), (0, 1))(tree, x)
        want_y = _every_expert_on_every_token(tree, x)
    assert int(counters["expert_load"][0].sum()) == landed
    assert int(counters["expert_rows_walked"][0]) == rung
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    _assert_same(grads, want_grads)


# -- sizes -----------------------------------------------------------------------

def test_published_sizes_count_the_published_parameters():
    from tpu_ddp.models.hybrid import HybridDecoder, nemotron3_super_spec

    def count(**share):
        model = HybridDecoder(nemotron3_super_spec(**share))
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))

    assert 120.0e9 < count() < 121.0e9          # the published 120 B
    # the benchmark's cut: 11.21 GB at 16 bytes a parameter
    assert count(num_layers=11, experts_held=8, vocab_rows=16384,
                 head_positions=8) == 700_865_520


def test_the_references_shapes_are_the_cut_models(ref):
    import json

    from tpu_ddp.models.hybrid import HybridDecoder, nemotron3_super_spec

    with open(os.path.join(tiny.REPO, "chipbench", "configs",
                           "nemotron3-super.json")) as f:
        arch = json.load(f)
    model = HybridDecoder(nemotron3_super_spec(
        **arch["train_config"]["model_overrides"]))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    want = {path: shape for path, (shape, _) in zip(
        ref.program_names(arch).values(), ref.param_shapes(arch).values())}
    got = {tuple(k.key for k in path): leaf.shape for path, leaf in
           jax.tree_util.tree_leaves_with_path(shapes)}
    assert got == want
    # 0.86 GFLOP a token forward, 21 TFLOP a sequence trained
    flops = ref.train_flops_per_example(
        arch, {"dataset": {"seq_len": 8192}})
    assert 20.9e12 < flops < 21.2e12
    parts = ref.forward_macs_by_part(arch, 8192)
    assert 0.50 < parts["shared"] / sum(parts.values()) < 0.53


# -- the Trainer -----------------------------------------------------------------

def _config(**extra):
    from tpu_ddp.train.trainer import TrainConfig

    tiny.register()
    fields = dict(model="tiny_hybrid", per_shard_batch=2, epochs=1,
                  n_devices=2, prefetch_depth=0, optimizer="adamw", lr=1e-3,
                  weight_decay=0.1, remat=True)
    fields.update(extra)
    return TrainConfig(**fields)


@pytest.mark.parametrize("flags", [{}, {"zero1": True}], ids=["dp", "zero1"])
def test_trainer_drives_the_hybrid_decoder(devices, fresh_registry, flags):
    from tpu_ddp.train.trainer import Trainer

    trainer = Trainer(_config(**flags), train_data=tiny.tokens(16),
                      test_data=tiny.tokens(8, seed=1))
    assert trainer.task.name == "next_token"
    result = trainer.run()
    assert int(trainer.state.step) == 4
    assert np.isfinite(trainer.history["train_loss"]).all()
    # two expert blocks, four of sixteen experts held, five choices a token
    assert 0 < result["model/expert_load_sum"] < 2 * 2 * 2 * tiny.T * 4
    assert result["model/expert_rows_walked_sum"] == 2 * 2 * 2 * tiny.T * 4


def test_the_cli_trains_the_published_model_by_name(devices, capsys):
    """``--model nemotron3_super`` with one chip's share cut far enough for
    a CPU (blocks ``ME``, two experts, 64 vocabulary rows, one of eight head
    positions; every width published)."""
    from tpu_ddp.cli.train import main

    main([
        "--device", "cpu", "--model", "nemotron3_super", "--model-overrides",
        '{"num_layers": 2, "experts_held": 2, "vocab_rows": 64, '
        '"head_positions": 8, "head_position": 3}',
        "--synthetic-data", "--synthetic-size", "2",
        "--batch-size", "2", "--n-devices", "1", "--epochs", "1",
        "--optimizer", "adamw", "--lr", "1e-4", "--prefetch-depth", "0"])
    assert "Training loss" in capsys.readouterr().out


# -- what the new options cost the sparse decoder: nothing ----------------------

#: sha256 of the lowered text of the functions below at the parent commit
#: (c15b096, ``git archive``, this container's jax): the tiny sparse decoder's
#: loss and gradient, plain and recomputed, and one ``DroplessMoE`` with a
#: two-rung ladder in bfloat16. The text is jax's, so another jax makes
#: another. To read them again: ``git archive c15b096 | tar -x -C <dir>``,
#: copy this file and ``hybrid_tiny.py`` into ``<dir>/tests``, and from
#: ``<dir>`` run ``python -c "import sys; sys.path.insert(0, 'tests');
#: import hashlib, test_hybrid as t; [print(c, hashlib.sha256(
#: t._lowered(c).encode()).hexdigest()) for c in t.PARENT]"``
#: ``decoder_remat`` was read again on PR 42's tree (parent 6673acc), whose
#: ``SparseDecoder`` recomputes a layer under ``decoder.KEPT_NAMES`` and no
#: longer under a bare ``nn.remat``, and is the same: this decoder attends
#: through the fused jnp reference, which names nothing, its router has no
#: selection bias, and no backward rule reads a sparse layer's routed
#: result, so the policy keeps nothing here and the text is the parent's.
PARENT = {
    "decoder_plain":
        "e1a42552cc92487e1f8cbff165bb56d91aa7e1b7998f7a3e1327865fcf0c80e9",
    "decoder_remat":
        "a0e0e62f6326529dd1b96adf02bfc7a516cb8e92a9de82078b64d2f666dadde3",
    "moe_ladder":
        "c8d63ca0b62f2bc9bedf2c51659a48ee4ddc1b6dddb6c1587028deb6de1244d1",
}


def _lowered(case):
    from tpu_ddp.models.decoder import SparseDecoder
    from tpu_ddp.models.moe import DroplessMoE
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    if case == "moe_ladder":
        layer = DroplessMoE(ExpertShare(16, 4, 4), top_k=4, expert_width=16,
                            shared_width=16, scaling=2.5, dtype=jnp.bfloat16)
        x = jnp.zeros((2, 256, 32))
        tree = layer.init(jax.random.key(0), x)["params"]

        def loss(tree, x):
            y, sown = layer.apply({"params": tree}, x, mutable=["counters"])
            return jnp.mean(y.astype(jnp.float32) ** 2), sown

        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True)).lower(
            tree, x).as_text()
    tokens = jnp.asarray(decoder_tiny.tokens(2, seed=3)[0])
    model = SparseDecoder(decoder_tiny.spec(), remat=case == "decoder_remat")
    tree = model.init(jax.random.key(0), tokens)["params"]

    def loss(tree, tokens):
        logits, sown = model.apply({"params": tree}, tokens,
                                   mutable=["counters"])
        return jnp.mean(logits ** 2), sown

    return jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        tree, tokens).as_text()


@pytest.mark.parametrize("case", list(PARENT))
def test_the_sparse_decoders_traced_step_is_the_parents(case):
    """``DroplessMoE`` and ``GroupedQueryAttention`` took options for the
    hybrid stack (plain experts, a latent space, a selection bias, more
    choices than experts held; no rotary, no gate). A model that sets none
    of them lowers to the parent's text, to the byte."""
    text = _lowered(case)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT[case]
