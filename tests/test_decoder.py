"""The sparse decoder (``models/decoder.py``, ``models/moe.py::DroplessMoE``,
the windowed grouped-query flash kernel) against the benchmark's plain
reference (``chipbench/reference/laguna-xs2.py``) on seeded weights, at a
size a CPU holds, for a stack with every kind of layer the published model
has; and the ``Trainer`` driving it through the one step builder.
"""

import dataclasses
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import decoder_tiny as tiny  # noqa: E402


@pytest.fixture(scope="module")
def ref():
    return tiny.reference()


@pytest.fixture(scope="module")
def seeded(ref):
    arch = tiny.arch()
    params = ref.init_params(arch, 7)
    tokens = jnp.asarray(tiny.tokens(2, seed=3)[0])
    return arch, params, tokens


def _program(params, ref, arch, **kwargs):
    from tpu_ddp.models.decoder import SparseDecoder

    return (SparseDecoder(tiny.spec(**kwargs.pop("spec", {})), **kwargs),
            tiny.program_tree(ref, arch, params))


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_logits_loss_and_gradients_match_the_reference(ref, seeded, remat):
    arch, params, tokens = seeded
    model, tree = _program(params, ref, arch, remat=remat)
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
    got = tiny.program_tree(ref, arch, want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(got)):
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.max(jnp.abs(w))) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_the_program_task_loss_is_the_reference_loss(ref, seeded):
    from tpu_ddp.train.tasks import NEXT_TOKEN

    arch, params, tokens = seeded
    logits = ref.forward(arch, params, tokens)
    loss_mask = np.ones(tokens.shape, bool)
    loss_mask[0, 10:] = False
    rows = np.array([True, False])
    batch = {"tokens": tokens, "loss_mask": jnp.asarray(loss_mask),
             "mask": jnp.asarray(rows)}
    want = ref.next_token_loss(logits, tokens, ref.target_mask(
        {k: np.asarray(v) for k, v in batch.items()}))
    loss, terms = NEXT_TOKEN.loss(None, logits, batch)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert terms == {}  # a loss of one term names none


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: the partial results of all the shares, the
    shared expert counted once, are the uncut reference's layer."""
    from tpu_ddp.models.moe import DroplessMoE
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    whole = tiny.arch(layers=2, held=tiny.EXPERTS, offset=0)
    params = ref.init_params(whole, 11)
    p = {k.split(".", 1)[1]: v for k, v in params.items()
         if k.startswith("layer_1.")}
    x = jax.random.normal(jax.random.key(5), (2, tiny.T, tiny.HIDDEN))
    with jax.default_matmul_precision("highest"):
        want = ref._moe(whole, p, x, (0, tiny.EXPERTS), "float32_highest")
        shared = ref.swiglu(x, p["moe.shared.gate"], p["moe.shared.up"],
                            p["moe.shared.down"], "float32_highest")
        total, landed = shared, 0
        shares = 4
        for position in range(shares):
            share = ExpertShare.of_position(tiny.EXPERTS, position, shares)
            rows = slice(share.offset, share.offset + share.held)
            layer = DroplessMoE(
                share, top_k=tiny.TOP_K, expert_width=24, shared_width=24,
                scaling=2.5)
            tree = {"router": {"kernel": p["moe.router"]},
                    "w_gate": p["moe.w_gate"][rows],
                    "w_up": p["moe.w_up"][rows],
                    "w_down": p["moe.w_down"][rows],
                    "shared": {k: {"kernel": p[f"moe.shared.{k}"]}
                               for k in ("gate", "up", "down")}}
            y, mut = layer.apply({"params": tree}, x, mutable=["counters"])
            # the reference given the same share computes the same part
            np.testing.assert_allclose(
                y, ref._moe(whole, dict(p, **{
                    f"moe.{k}": p[f"moe.{k}"][rows]
                    for k in ("w_gate", "w_up", "w_down")}), x,
                    (share.offset, share.held), "float32_highest"),
                atol=2e-5)
            total = total + (y - shared)
            landed += int(mut["counters"]["expert_load"][0].sum())
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert landed == 2 * tiny.T * tiny.TOP_K  # every pair landed once


def test_a_skewed_router_drops_no_token(ref):
    """Most tokens to one expert: no capacity, so every pair that names a
    held expert is computed, and the layer is still the reference's."""
    from tpu_ddp.models.moe import DroplessMoE
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    arch = tiny.arch(layers=2, held=4, offset=0)
    params = ref.init_params(arch, 13)
    p = {k.split(".", 1)[1]: v for k, v in params.items()
         if k.startswith("layer_1.")}
    # a router that scores expert 2 far above the rest, for every token
    router = np.array(p["moe.router"]) * 0.01
    x = jnp.abs(jax.random.normal(jax.random.key(9),
                                  (2, tiny.T, tiny.HIDDEN))) + 0.1
    router[:, 2] += 1.0
    p["moe.router"] = jnp.asarray(router)
    layer = DroplessMoE(ExpertShare(tiny.EXPERTS, 4, 0), top_k=tiny.TOP_K,
                        expert_width=24, shared_width=24, scaling=2.5)
    tree = {"router": {"kernel": p["moe.router"]},
            "w_gate": p["moe.w_gate"], "w_up": p["moe.w_up"],
            "w_down": p["moe.w_down"],
            "shared": {k: {"kernel": p[f"moe.shared.{k}"]}
                       for k in ("gate", "up", "down")}}
    with jax.default_matmul_precision("highest"):
        y, mut = layer.apply({"params": tree}, x, mutable=["counters"])
        want = ref._moe(arch, p, x, (0, 4), "float32_highest")
    load = np.asarray(mut["counters"]["expert_load"][0])
    assert load[2] == 2 * tiny.T, load  # every token chose expert 2
    np.testing.assert_allclose(y, want, atol=2e-5)


# -- the routed layer's ladder of buffer lengths ------------------------------
# 32 experts, 8 a token, this share holds experts 8..15: of 2 x 128 tokens'
# 2,048 (token, choice) pairs a fair router lands 512, so the ladder is
# 1,024 and 2,048 rows (``moe.buffer_rungs``).

L_EXPERTS, L_HELD, L_OFFSET, L_K, L_T, L_C, L_F = 32, 8, 8, 8, 128, 32, 24
L_PAIRS = 2 * L_T * L_K
L_RUNGS = (L_PAIRS // 2, L_PAIRS)


class FullBufferMoE(nn.Module):
    """``DroplessMoE.__call__`` as PR 27 left it, kept here word for word:
    the sorted buffer has a row for every (token, choice), whatever landed.
    What every rung is held to, and the program a whole share still is."""

    share: object
    top_k: int
    expert_width: int
    shared_width: int = 0
    scaling: float = 1.0
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):  # (B, T, C) -> (B, T, C)
        from tpu_ddp.models.moe import (SwiGLU, _collect, _spread,
                                        grouped_matmul)
        from tpu_ddp.telemetry.phases import module_scope

        B, T, C = x.shape
        E, held, offset = (self.share.num_experts, self.share.held,
                           self.share.offset)
        K, F = self.top_k, self.expert_width
        xf = x.reshape(B * T, C).astype(self.dtype)
        stacked = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        w_gate = self.param("w_gate", stacked, (held, C, F), jnp.float32)
        w_up = self.param("w_up", stacked, (held, C, F), jnp.float32)
        w_down = self.param("w_down", stacked, (held, F, C), jnp.float32)

        with jax.named_scope(module_scope("moe_route")):
            logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST,
                              name="router")(xf.astype(jnp.float32))
            scores, ids = jax.lax.top_k(jax.nn.sigmoid(logits), K)  # (N, K)
            weights = (scores / scores.sum(axis=-1, keepdims=True)
                       * self.scaling)
            self.sow("intermediates", "expert_ids", ids)

        with jax.named_scope(module_scope("moe_dispatch")):
            local = ids.reshape(-1) - offset                    # (N*K,)
            group = jnp.where((local >= 0) & (local < held), local, held)
            order = jnp.argsort(group, stable=True).astype(jnp.int32)
            inverse = jnp.argsort(order).astype(jnp.int32)
            load = jnp.sum(group[:, None] == jnp.arange(held)[None, :],
                           axis=0, dtype=jnp.int32)             # (held,)
            self.sow("counters", "expert_load", load)
            real = (jnp.arange(order.shape[0]) < load.sum())[:, None]
            keep = lambda a: jnp.where(real, a, 0)  # noqa: E731
            rows = keep(_spread(xf, order, inverse, K))

        with jax.named_scope(module_scope("moe_experts")):
            w_in = jnp.concatenate([w_gate, w_up], axis=-1).astype(self.dtype)
            h = keep(grouped_matmul(rows, w_in, load))
            h = nn.silu(h[:, :F]) * h[:, F:]
            out = keep(grouped_matmul(h, w_down.astype(self.dtype), load))

        with jax.named_scope(module_scope("moe_combine")):
            w_sorted = jnp.take(weights.reshape(-1), order)[:, None]
            y = _collect(out * w_sorted.astype(out.dtype), order, inverse, K)

        if self.shared_width:
            with jax.named_scope(module_scope("moe_shared")):
                y = y + SwiGLU(self.shared_width, dtype=self.dtype,
                               name="shared")(xf)
        return y.reshape(B, T, C)


def _layers(share=None):
    from tpu_ddp.models.moe import DroplessMoE
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    share = share or ExpertShare(L_EXPERTS, L_HELD, L_OFFSET)
    kwargs = dict(top_k=L_K, expert_width=L_F, shared_width=L_F, scaling=2.5)
    return DroplessMoE(share, **kwargs), FullBufferMoE(share, **kwargs)


#: what each router lands on the held experts, and the rung that holds it
ROUTERS = {
    "fair": (None, L_RUNGS[0]),
    "to_the_brim": (L_RUNGS[0], L_RUNGS[0]),
    "one_over": (L_RUNGS[0] + 1, L_RUNGS[1]),
    "skewed": (L_PAIRS, L_RUNGS[1]),
}


def _routed_case(kind, seed=0):
    """(params, x) whose router lands what ``ROUTERS[kind]`` says. A token's
    first three channels say which of three rows of the router it reads
    (all eight choices held here; none; one), the other channels are noise
    that the router hardly weighs."""
    layer, _ = _layers()
    keys = jax.random.split(jax.random.key(seed), 3)
    x = np.array(jax.random.normal(keys[0], (2, L_T, L_C)))
    tree = jax.tree.map(np.array, layer.init(keys[1], x)["params"])
    landed = ROUTERS[kind][0]
    if landed is None:
        return jax.tree.map(jnp.asarray, tree), jnp.asarray(x)
    here = np.arange(L_OFFSET, L_OFFSET + L_HELD)
    there = np.arange(L_OFFSET + L_HELD, L_OFFSET + L_HELD + L_K)
    router = 0.02 * np.array(jax.random.normal(keys[2], (L_C, L_EXPERTS)))
    router[:3] = -3.0
    router[0, here] = 3.0                      # eight pairs land
    router[1, there] = 3.0                     # none lands
    router[2, np.append(there[:-1], here[0])] = 3.0   # one lands
    kinds = np.ones(2 * L_T, int)
    kinds[:landed // L_K] = 0
    kinds[landed // L_K:landed // L_K + landed % L_K] = 2
    kinds = np.random.default_rng(seed).permutation(kinds)
    x[..., :3] = np.eye(3)[kinds].reshape(2, L_T, 3)
    tree["router"]["kernel"] = router
    return jax.tree.map(jnp.asarray, tree), jnp.asarray(x)


def _value_grads_counters(layer, tree, x, wrap=lambda f: f):
    w = jax.random.normal(jax.random.key(17), x.shape)

    def loss(tree, x):
        y, sown = wrap(lambda tree, x: layer.apply(
            {"params": tree}, x, mutable=["counters"]))(tree, x)
        return jnp.sum(y * w), (y, sown["counters"])

    with jax.default_matmul_precision("highest"):
        (_, (y, counters)), grads = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(tree, x)
    return y, grads, {k: np.asarray(v[0]) for k, v in counters.items()}


def _assert_same(got, want, atol=2e-5):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, atol=atol * float(jnp.max(jnp.abs(w))) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_the_ladder_is_read_off_the_shapes_and_ends_at_every_pair():
    from tpu_ddp.models.moe import buffer_rungs

    # the benchmark cell: 16,384 tokens x 8 choices, 32 of 256 experts held
    assert buffer_rungs(131072, 32, 256) == (32768, 131072)
    assert buffer_rungs(L_PAIRS, L_HELD, L_EXPERTS) == L_RUNGS
    assert buffer_rungs(131072, 256, 256) == (131072,)   # a whole share
    assert buffer_rungs(131072, 128, 256) == (131072,)   # twice fair is all
    # whole tiles of the grouped kernel: 188 fair rows are one tile of 512
    assert buffer_rungs(3000, 1, 16) == (1024, 3000)
    assert buffer_rungs(144, 4, 16) == (144,)            # the tiny decoder's


@pytest.mark.parametrize("kind", list(ROUTERS))
def test_every_rung_is_the_full_buffers_layer(kind):
    """Output and gradients (input, router, experts, shared expert) at each
    rung, with the buffer full to its last row and one row over."""
    layer, full = _layers()
    tree, x = _routed_case(kind)
    y, grads, counters = _value_grads_counters(layer, tree, x)
    want_y, want_grads, want_counters = _value_grads_counters(full, tree, x)
    landed, rung = ROUTERS[kind]
    if landed is not None:
        assert counters["expert_load"].sum() == landed
    assert counters["expert_rows_walked"] == rung
    np.testing.assert_array_equal(counters["expert_load"],
                                  want_counters["expert_load"])
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    _assert_same(grads, want_grads)


def test_rows_walked_is_the_rung_and_the_top_rung_drops_nothing(ref):
    """A fair router walks the shortest buffer; one that sends every pair
    here walks a row for every pair and is still the reference's layer."""
    layer, _ = _layers()
    walked = {}
    for kind in ("fair", "skewed"):
        tree, x = _routed_case(kind, seed=1)
        p = {"moe.router": tree["router"]["kernel"],
             **{f"moe.{k}": tree[k] for k in ("w_gate", "w_up", "w_down")},
             **{f"moe.shared.{k}": tree["shared"][k]["kernel"]
                for k in ("gate", "up", "down")}}
        with jax.default_matmul_precision("highest"):
            y, sown = layer.apply({"params": tree}, x, mutable=["counters"])
            want = ref._moe({"num_experts_per_tok": L_K,
                             "moe_routed_scaling_factor": 2.5}, p, x,
                            (L_OFFSET, L_HELD), "float32_highest")
        np.testing.assert_allclose(y, want, atol=2e-5)
        walked[kind] = (int(sown["counters"]["expert_rows_walked"][0]),
                        int(sown["counters"]["expert_load"][0].sum()))
    assert walked["fair"][0] == L_RUNGS[0] > walked["fair"][1]
    assert walked["skewed"] == (L_PAIRS, L_PAIRS)


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            v = getattr(v, "jaxpr", v)
            if hasattr(v, "eqns"):
                yield v


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _equations(sub)


@pytest.mark.parametrize("passes", ["forward", "backward"])
def test_the_shortest_rung_holds_no_wide_array_of_every_pair(passes):
    """Inside the branch of the shortest rung, forward and backward, nothing
    has a row for every (token, choice) except vectors of indices."""
    layer, _ = _layers()
    tree, x = _routed_case("fair")

    def forward(tree, x):
        return jnp.sum(layer.apply({"params": tree}, x))

    fn = forward if passes == "forward" else jax.grad(forward, (0, 1))
    switches = [e for e in _equations(jax.make_jaxpr(fn)(tree, x).jaxpr)
                if e.primitive.name == "cond"]
    assert len(switches) == (1 if passes == "forward" else 2)

    def wide(branch):
        return [v.aval.shape for e in _equations(branch.jaxpr)
                for v in list(e.invars) + list(e.outvars)
                if getattr(v.aval, "shape", ())[:1] == (L_PAIRS,)
                and np.prod(v.aval.shape[1:]) > 1]

    for switch in switches:
        shortest, longest = switch.params["branches"]
        assert wide(shortest) == []
        assert (L_PAIRS, L_C) in wide(longest)    # the walk does find them


def test_a_whole_share_is_the_parents_program():
    """One rung: no ``cond`` is traced, and the lowered text, forward and
    backward, is the full-buffer layer's to the byte."""
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    layer, full = _layers(ExpertShare(L_EXPERTS, L_EXPERTS, 0))
    x = jax.random.normal(jax.random.key(2), (2, L_T, L_C))
    tree = layer.init(jax.random.key(3), x)["params"]

    def forward(layer):
        return lambda tree, x: jnp.sum(layer.apply({"params": tree}, x))

    for fn in (forward, lambda layer: jax.grad(forward(layer), (0, 1))):
        assert not [e for e in _equations(
            jax.make_jaxpr(fn(layer))(tree, x).jaxpr)
            if e.primitive.name == "cond"]
        assert (jax.jit(fn(layer)).lower(tree, x).as_text()
                == jax.jit(fn(full)).lower(tree, x).as_text())
    _, sown = layer.apply({"params": tree}, x, mutable=["counters"])
    assert int(sown["counters"]["expert_rows_walked"][0]) == L_PAIRS


@pytest.mark.parametrize("kind", list(ROUTERS))
def test_a_recomputed_rung_is_the_full_buffers_layer(kind):
    layer, full = _layers()
    tree, x = _routed_case(kind)
    y, grads, counters = _value_grads_counters(layer, tree, x,
                                               wrap=jax.checkpoint)
    want_y, want_grads, _ = _value_grads_counters(full, tree, x)
    assert counters["expert_rows_walked"] == ROUTERS[kind][1]
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    _assert_same(grads, want_grads)


@pytest.mark.parametrize("kinds", [("fair", "one_over"),
                                   ("to_the_brim", "skewed")])
def test_shards_take_their_own_rungs_under_shard_map(kinds):
    """``data=2`` on the CPU mesh, the parameters replicated: each shard
    switches on what landed on it, and the gradient of the replicated
    parameters is the sum of the shards'."""
    from jax.sharding import Mesh, PartitionSpec as P

    layer, full = _layers()
    cases = [_routed_case(kind) for kind in kinds]
    tree = cases[1][0]   # one set of weights; the router of the second case
    x = jnp.concatenate([x for _, x in cases])
    w = jax.random.normal(jax.random.key(17), x.shape)

    def local(layer, tree, x, w):
        y, sown = layer.apply({"params": tree}, x, mutable=["counters"])
        return jnp.sum(y * w), sown["counters"].get(
            "expert_rows_walked", (None,))[0]

    def shard(tree, x, w):
        def loss(tree, x):
            value, walked = local(layer, tree, x, w)
            return jax.lax.psum(value, "data"), walked
        (_, walked), grads = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(tree, x)
        return grads, walked[None]

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    with jax.default_matmul_precision("highest"):
        (g_tree, g_x), walked = jax.jit(jax.shard_map(
            shard, mesh=mesh, in_specs=(P(), P("data"), P("data")),
            out_specs=((P(), P("data")), P("data"))))(tree, x, w)
        want = [jax.grad(lambda tree, x, w=w: local(full, tree, x, w)[0],
                         (0, 1))(tree, x, w)
                for x, w in zip(jnp.split(x, 2), jnp.split(w, 2))]
    # the first case's tokens read the second case's router: a fair router
    # may become another, so its rung is read off what landed, not named
    assert int(walked[1]) == ROUTERS[kinds[1]][1]
    assert {int(walked[0])} <= set(L_RUNGS)
    _assert_same(g_tree, jax.tree.map(jnp.add, want[0][0], want[1][0]))
    _assert_same(g_x, jnp.concatenate([want[0][1], want[1][1]]))


def test_expert_share_is_checked():
    from tpu_ddp.parallel.expert_parallel import ExpertShare

    assert ExpertShare.of_position(256, 0, 8) == ExpertShare(256, 32, 0)
    with pytest.raises(ValueError):
        ExpertShare(256, 32, 240)
    with pytest.raises(ValueError):
        ExpertShare.of_position(256, 0, 7)


def test_rotary_tables_are_the_references(ref):
    from tpu_ddp.models import decoder as D

    arch = tiny.arch()
    arch["head_dim"] = 128
    arch["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 4096
    for kind, spec in (("full_attention", D._LAGUNA_FULL),
                       ("sliding_attention", D._LAGUNA_SLIDING)):
        cos, sin, dims = ref.rotary_tables(
            arch["rope_parameters"][kind], 128, 300)
        got_cos, got_sin = spec.tables(300)
        assert dims == spec.dims
        np.testing.assert_array_equal(cos, got_cos)
        np.testing.assert_array_equal(sin, got_sin)
    # YaRN leaves the fastest dimension alone and slows the slowest by 64
    full = D._LAGUNA_FULL.tables(2)[1] / 1.4158883083359672
    plain = dataclasses.replace(D._LAGUNA_FULL, yarn=None).tables(2)[1]
    np.testing.assert_allclose(full[1, 0], plain[1, 0], rtol=1e-6)
    np.testing.assert_allclose(full[1, -1] * 64, plain[1, -1], rtol=1e-5)


def test_published_sizes_count_the_published_parameters():
    from tpu_ddp.models import decoder as D

    def count(spec):
        model = D.SparseDecoder(spec)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0),
                               jnp.zeros((1, 8), jnp.int32))["params"])
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))

    assert count(D.laguna_xs2_spec()) == 33_442_596_864
    assert count(D.laguna_xs2_spec(   # the benchmark cell's share
        num_layers=5, experts_held=16, vocab_rows=12544)) == 490_297_344


# -- the windowed, grouped-query flash kernel ----------------------------------

@pytest.mark.parametrize("t,heads,kv,window,bq,bk", [
    (64, 4, 2, 0, 16, 16),     # full causal, groups of two
    (64, 4, 2, 24, 16, 16),    # a window that ends inside a block
    (64, 6, 2, 16, 16, 32),    # groups of three, blocks of unequal sizes
    (64, 4, 4, 40, 32, 16),    # no grouping, a window wider than a block
    (64, 4, 1, 8, 16, 16),     # one key-value head, a window inside a block
    (128, 2, 1, 100, 32, 64),
    # the kernels keep their row statistics (rows, 128): against scores a
    # lane width of columns wide, and two (``_lanes``)
    (256, 4, 2, 70, 32, 128),
    (512, 2, 1, 200, 64, 256),
])
def test_windowed_grouped_flash_matches_the_blocked_reference(
        ref, t, heads, kv, window, bq, bk):
    """Interpret mode against the reference's blocked attention, forward and
    backward, window edge and head grouping included."""
    from tpu_ddp.ops.flash_attention import flash_attention

    key = jax.random.key(t + heads + window)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), shape)
                  for i, shape in enumerate([
                      (2, t, heads, 16), (2, t, kv, 16), (2, t, kv, 16),
                      (2, t, heads, 16)]))

    def kernel(q, k, v):
        return flash_attention(q, k, v, bq, bk, True, causal=True,
                               window=window)

    def blocked(q, k, v):
        return ref.blocked_attention(q, k, v, window, "float32_highest")

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(kernel(q, k, v), blocked(q, k, v),
                                   atol=2e-6)
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(blocked(*a) * w),
                        (0, 1, 2))(q, k, v)
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, atol=5e-6)


@pytest.mark.parametrize("t,heads,kv,window,bq,bk", [
    (256, 4, 2, 48, 64, 128),   # a window, groups of two
    (512, 2, 1, 0, 64, 256),    # keys two lane widths wide
])
def test_a_dead_row_under_a_band_is_exact_zeros(t, heads, kv, window, bq,
                                                bk):
    """``kv_mask`` with the decoder's bands and shared heads: forward and
    the three gradients against the fused reference; rows that see no key
    (batch 1 before ``t // 4``) come out exact zeros, gradients too."""
    from tpu_ddp.ops.flash_attention import _reference, flash_attention

    key = jax.random.key(t + window)
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), shape)
                  for i, shape in enumerate([
                      (2, t, heads, 16), (2, t, kv, 16), (2, t, kv, 16),
                      (2, t, heads, 16)]))
    how = dict(causal=True, window=window,
               kv_mask=jnp.ones((2, t)).at[1, :t // 4].set(0.0))

    def kernel(q, k, v):
        return flash_attention(q, k, v, bq, bk, True, **how)

    def fused(q, k, v):
        return _reference(q, k, v, **how)

    with jax.default_matmul_precision("highest"):
        out = kernel(q, k, v)
        np.testing.assert_allclose(out, fused(q, k, v), atol=2e-6)
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(fused(*a) * w), (0, 1, 2))(q, k, v)
    for g, x in zip(got, want):
        np.testing.assert_allclose(g, x, atol=5e-6)
    assert np.all(np.asarray(out)[1, :t // 4] == 0.0)
    assert np.all(np.asarray(got[0])[1, :t // 4] == 0.0)


def _kernel_primitives(fn, *args) -> dict:
    """How often each primitive stands in the Pallas kernels ``fn`` calls,
    branches included."""
    counts = {}

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside:
                name = eqn.primitive.name
                counts[name] = counts.get(name, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inside or eqn.primitive.name == "pallas_call")

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return counts


def test_the_forward_kernel_computes_a_tile_whole():
    """ViT-B/16's 196 tokens are one whole-axis block and no band: one
    product of scores, one of values, one row maximum and one row sum behind
    three branches (first step, the tile, last step). A decoder's tile has
    two branches, with the mask arithmetic and without, each the same once."""
    from tpu_ddp.ops.flash_attention import flash_attention

    names = ("dot_general", "reduce_max", "reduce_sum", "exp", "cond")
    x = jnp.zeros((2, 196, 4, 64), jnp.bfloat16)
    vit = _kernel_primitives(
        lambda q: flash_attention(q, q, q, 128, 128, False), x)
    assert [vit[p] for p in names] == [2, 1, 1, 2, 3]
    y = jnp.zeros((1, 1024, 4, 128), jnp.bfloat16)
    decoder = _kernel_primitives(
        lambda q: flash_attention(q, q, q, 512, 512, False, causal=True,
                                  window=512), y)
    assert [decoder[p] for p in names] == [4, 2, 2, 4, 4]


def test_the_band_visits_only_tiles_that_hold_a_visible_pair():
    from tpu_ddp.ops.flash_attention import _Band

    band = _Band(True, 512, 512, 512, 16, 16)
    assert (band.kv_width, band.q_width) == (2, 2)      # of 16 blocks
    assert [band.kv_lo(j) for j in (0, 1, 5)] == [0, 0, 4]
    assert [band.q_hi(j) for j in (0, 14, 15)] == [1, 15, 15]
    full = _Band(True, 0, 512, 512, 16, 16)
    assert (full.kv_width, full.kv_hi(3), full.q_lo(3)) == (16, 3, 3)
    every = _Band(False, 0, 128, 128, 4, 4)
    assert (every.kv_width, every.q_width, every.kv_hi(0)) == (4, 4, 3)
    # exhaustively: a tile is inside the band iff it holds a visible pair
    for bq, bk, window in ((16, 32, 24), (32, 16, 40), (16, 16, 8)):
        band = _Band(True, window, bq, bk, 128 // bq, 128 // bk)
        for j in range(band.n_q):
            for kb in range(band.n_k):
                rows = np.arange(j * bq, (j + 1) * bq)[:, None]
                cols = np.arange(kb * bk, (kb + 1) * bk)[None, :]
                any_visible = bool(np.any(
                    (cols <= rows) & (cols > rows - window)))
                assert (band.kv_lo(j) <= kb <= band.kv_hi(j)) == any_visible
                assert (band.q_lo(kb) <= j <= band.q_hi(kb)) == any_visible
