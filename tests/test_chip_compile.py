"""The main path's kernels and the DP step, compiled at REAL widths for a
described (not attached) v5e chip — in tier-1, at no chip time.

Interpret mode accepts tilings Mosaic refuses (ViT-B/16's 196 tokens were
one), so the CPU suite alone cannot say that a kernel will build on the
chip. The TPU's compiler is installed here: ``jax.experimental.topologies``
describes a ``v5e:2x2`` slice, and ``jit(...).lower(shapes).compile()``
raises what the chip's compiler would raise. Each case asserts the
``tpu_custom_call`` count or the collective it expects in the compiled
text. Nothing runs: a compile that passes is not a chip run.

Code that asks jax for its platform still sees the CPU here, so the kernels
are steered with ``interpret=False`` — in the test, not through an option
of the program. One libtpu process at a time: two collide on its lock file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

CUSTOM_CALL = "tpu_custom_call"

# the ``topo`` fixture (the described v5e:2x2 slice) is conftest's


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A deviceless compile is written to the persistent cache but cannot
    be read back without a chip (the next one warns and compiles again):
    keep the cache off around these, as conftest keeps it off everywhere."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


# ---- flash attention ---------------------------------------------------------

@pytest.mark.parametrize("shape,causal", [
    ((4, 2048, 8, 128), False),   # the bench shape
    ((4, 2048, 8, 128), True),
    ((64, 196, 12, 64), False),   # ViT-B/16's own: one whole-axis block
    ((2, 1000, 4, 64), True),     # no tiling: padded to 1024 and masked
])
def test_flash_compiles_at_real_widths(topo, shape, causal):
    from tpu_ddp.ops.flash_attention import flash_attention

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=_one_chip(topo))

    def fwd(q, k, v):
        return flash_attention(q, k, v, 128, 128, False, causal=causal)

    bwd = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                   (0, 1, 2))
    assert _text(fwd, x, x, x).count(CUSTOM_CALL) == 1
    # fwd recompute + the one backward kernel, exactly
    assert _text(bwd, x, x, x).count(CUSTOM_CALL) == 2


@pytest.mark.parametrize("heads,window", [(64, 512), (48, 0)],
                         ids=["sliding_layer", "full_layer"])
def test_windowed_grouped_flash_compiles_at_the_decoder_widths(topo, heads,
                                                               window):
    """A Laguna-XS.2 layer's attention at the benchmark cell's shapes: 8,192
    positions, heads of 128 over 8 key-value heads, blocks of 512."""
    from tpu_ddp.ops.flash_attention import flash_attention

    one = _one_chip(topo)
    q = jax.ShapeDtypeStruct((2, 8192, heads, 128), jnp.bfloat16,
                             sharding=one)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16, sharding=one)

    def fwd(q, k, v):
        return flash_attention(q, k, v, 512, 512, False, causal=True,
                               window=window)

    bwd = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                   (0, 1, 2))
    assert _text(fwd, q, kv, kv).count(CUSTOM_CALL) == 1
    text = _text(bwd, q, kv, kv)
    assert text.count(CUSTOM_CALL) == 2 and "kernel.flash_bwd" in text


def test_latent_flash_compiles_at_the_decoder_widths(topo):
    """A JoyAI-LLM-Flash layer's attention at the benchmark cell's shapes:
    8,192 positions, 32 heads, queries and keys of 192 (padded to 256 lanes
    on their own) over values of 128 (not padded), blocks of 512."""
    from tpu_ddp.ops.flash_attention import flash_attention

    one = _one_chip(topo)
    qk = jax.ShapeDtypeStruct((2, 8192, 32, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=one)

    def fwd(q, k, v):
        return flash_attention(q, k, v, 512, 512, False, causal=True)

    bwd = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                   (0, 1, 2))
    text = _text(fwd, qk, qk, v)
    assert text.count(CUSTOM_CALL) == 1
    # the output is of the values' width: nothing of 256 comes back
    assert "bf16[64,8192,128]" in text and "bf16[64,8192,256]{" in text
    # 25.2 MB of dk / dv carried in VMEM for a head: over Mosaic's default
    text = _text(bwd, qk, qk, v)
    assert text.count(CUSTOM_CALL) == 2 and "kernel.flash_bwd" in text


def test_block_mask_flash_compiles_at_the_decoder_widths(topo):
    """An SDAR-30B-A3B layer's attention at the benchmark cell's shapes:
    ``[clean ‖ noisy]`` of 4,096 tokens in blocks of 4, 8,192 positions, 32
    heads of 128 over 4 key-value heads, tiles of 512: the visit lists'
    index maps and the mask's integer division pass Mosaic, and the backward
    pass is the one kernel."""
    from tpu_ddp.ops.flash_attention import flash_attention

    one = _one_chip(topo)
    q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16, sharding=one)

    def fwd(q, k, v):
        return flash_attention(q, k, v, 512, 512, False, diffusion=(4096, 4))

    bwd = jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
                   (0, 1, 2))
    assert _text(fwd, q, kv, kv).count(CUSTOM_CALL) == 1
    text = _text(bwd, q, kv, kv)
    assert text.count(CUSTOM_CALL) == 2 and "kernel.flash_bwd" in text


def test_a_sequence_over_the_carrys_budget_compiles_the_two_kernels(topo):
    """32,768 positions at latent attention's widths: 100 MB of dk / dv a
    head would not fit, so the backward pass is the dQ kernel and the dK/dV
    kernel, chosen from the shape alone."""
    from tpu_ddp.ops.flash_attention import flash_attention

    one = _one_chip(topo)
    qk = jax.ShapeDtypeStruct((1, 32768, 2, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, 32768, 2, 128), jnp.bfloat16, sharding=one)
    bwd = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, 512, 512, False, causal=True).astype(jnp.float32).sum(),
        (0, 1, 2))
    text = _text(bwd, qk, qk, v)
    assert text.count(CUSTOM_CALL) == 3 and "kernel.flash_bwd" not in text
    assert "kernel.flash_dq" in text and "kernel.flash_dkv" in text


def test_grouped_expert_products_compile_at_the_decoder_widths(topo):
    """``models/moe.py::grouped_matmul`` over 32 held experts of width 512
    on a hidden size of 2,048, a row for each of 16,384 tokens' 8 choices:
    the compiler's own grouped-product kernel, forward and backward."""
    from tpu_ddp.models.moe import grouped_matmul

    one = _one_chip(topo)
    rows = jax.ShapeDtypeStruct((131072, 2048), jnp.bfloat16, sharding=one)
    weights = jax.ShapeDtypeStruct((32, 2048, 1024), jnp.bfloat16,
                                   sharding=one)
    sizes = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one)
    text = _text(grouped_matmul, rows, weights, sizes)
    assert "ragged-dot" in text and CUSTOM_CALL in text
    bwd = jax.grad(
        lambda x, w, n: grouped_matmul(x, w, n).astype(jnp.float32).sum(),
        (0, 1))
    assert _text(bwd, rows, weights, sizes).count("ragged-dot") >= 2


def test_the_routed_ladder_compiles_at_the_decoder_widths(topo):
    """``models/moe.py::_switch`` over the benchmark cell's ladder (32,768
    and 131,072 rows), forward and backward: two conditionals, each
    branch with its grouped kernels, and no more temporary memory than the
    longest rung alone needs (``lax.switch`` left to AD hands out every
    branch's residuals: 7.09 GB against 1.96 GB here)."""
    import functools

    from tpu_ddp.models import moe

    one = _one_chip(topo)
    n, c, f, k, held = 16384, 2048, 512, 8, 32

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    rungs = moe.buffer_rungs(n * k, held, 256)
    assert rungs == (32768, 131072)
    walk = tuple(functools.partial(moe._routed, r, k, jnp.bfloat16)
                 for r in rungs)
    routing = (shape((n * k,), jnp.int32), shape((n * k,), jnp.int32),
               shape((held,), jnp.int32), shape((n,), jnp.int32))
    floats = (shape((n, c), jnp.bfloat16), shape((held, c, f), jnp.float32),
              shape((held, c, f), jnp.float32),
              shape((held, f, c), jnp.float32), shape((n, k), jnp.float32))

    def loss(floats, index, routing):
        return moe._switch(walk, index, routing, floats).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        floats, shape((), jnp.int32), routing).compile()
    text = compiled.as_text()
    assert text.count(" conditional(") == 2
    assert text.count("ragged-dot") >= 2 * len(rungs)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


# ---- the hybrid decoder's own shapes (nemotron3-super.seq8k-v16384) ---------

def test_the_state_space_scan_compiles_at_the_hybrid_decoder_widths(
        topo, monkeypatch):
    """``ops/ssd_scan.py`` at the benchmark cell's shapes: 2 sequences of
    8,192 positions, 16 heads of 64 on one B/C group of state 128, chunks of
    128, bfloat16 operands; the forward kernel and, in the gradient, the
    backward pass's own. A chunk's (128, 128) blocks stay in VMEM (PR 47):
    what the forward call leaves in HBM beside ``y`` is the float32 state
    at each chunk's start, 67 MB, and the gradient holds that and its
    results, where the array form's blocks were 134 MB each and the bounds
    1.5 and 3 GB."""
    from tpu_ddp.ops.ssd_scan import ssd_scan
    from tpu_ddp.parallel import runtime

    # the scan asks the runtime whether to interpret its kernels, and the
    # runtime sees the CPU here: steered in the test
    monkeypatch.setattr(runtime, "is_tpu_device", lambda: True)
    one = _one_chip(topo)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    operands = (shape((2, 8192, 16, 64)), shape((2, 8192, 16), jnp.float32),
                shape((16,), jnp.float32), shape((2, 8192, 1, 128)),
                shape((2, 8192, 1, 128)))
    forward = jax.jit(ssd_scan).lower(*operands).compile()
    assert forward.as_text().count(CUSTOM_CALL) == 1
    assert forward.memory_analysis().temp_size_in_bytes < 1e8
    backward = jax.jit(jax.grad(
        lambda *a: ssd_scan(*a).astype(jnp.float32).sum(),
        range(5))).lower(*operands).compile()
    text = backward.as_text()
    assert text.count(CUSTOM_CALL) == 2 and "kernel.ssd_scan_bwd" in text
    assert backward.memory_analysis().temp_size_in_bytes < 2e8


@pytest.fixture(scope="module")
def mamba2_block_step(topo):
    """Value and gradient of one Mamba-2 block of the hybrid decoder at the
    cell's widths (2 sequences of 8,192 positions, hidden 4,096, 16 heads of
    64 on one group of state 128) in bfloat16, recomputed as
    ``HybridDecoder`` recomputes it, compiled for one described chip."""
    from tpu_ddp.models.decoder import recomputed
    from tpu_ddp.models.hybrid import HybridBlock, nemotron3_super_spec
    from tpu_ddp.parallel import runtime

    one = _one_chip(topo)
    spec = nemotron3_super_spec(num_layers=1, experts_held=8, vocab_rows=512,
                                head_positions=8)
    block = recomputed(HybridBlock)("M", spec, dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: block.init(
        jax.random.key(0), jnp.zeros((1, 256, spec.hidden), jnp.bfloat16)))[
            "params"]
    params = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        l.shape, l.dtype, sharding=one), shapes)
    h = jax.ShapeDtypeStruct((2, 8192, spec.hidden), jnp.bfloat16,
                             sharding=one)

    def loss(p, h):
        return block.apply({"params": p}, h).astype(jnp.float32).sum()

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(runtime, "is_tpu_device", lambda: True)
        return jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
            params, h).compile()


def test_a_recomputed_mamba2_block_calls_the_forward_scan_in_both_passes(
        mamba2_block_step):
    """A recomputed block keeps nothing of its scan: one ``ssd_scan_fwd`` in
    each pass and one ``ssd_scan_bwd``. Kept by name, ``y`` and the states
    at the chunk starts (33.5 MB and 67 MB a block) saved the second call,
    0.46 ms then, and cost the cell's step 5.6 ms: 0.50 GB more took the
    compiled step to the compiler's memory budget, which it answered by
    compressing the head's logits gradient (PERF.md section 6, PR 47)."""
    assert _kernel_calls(
        mamba2_block_step.as_text(), "ssd_scan_fwd", "ssd_scan_bwd") == {
            "ssd_scan_fwd": 2, "ssd_scan_bwd": 1}


def test_the_selective_scan_compiles_at_the_decoder_hybrid_decoder_widths(
        topo):
    """``phi4-mini-flash.seq16k-v25008``'s scan: one sequence of 16,384
    positions, 5120 channels, 16 states, ``x``, ``B`` and ``C`` in bfloat16
    as the model has them, forward and backward: two kernels in the
    gradient (the forward one, and the backward pass's own). The kernels
    read and write the model's arrays: the compiled program holds no
    float32 copy of a (1, 16384, 5120) bfloat16 array beside ``dt``'s
    gradient, and nothing replicated over lanes (PR 46)."""
    from tpu_ddp.ops.selective_scan import selective_scan

    one = _one_chip(topo)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def loss(x, dt, A, B, C, D):
        return selective_scan(x, dt, A, B, C, D, interpret=False).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        shape((1, 16384, 5120)),
        shape((1, 16384, 5120), jnp.float32), shape((5120, 16), jnp.float32),
        shape((1, 16384, 16)), shape((1, 16384, 16)),
        shape((5120,), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) == 2
    assert "[1,16384,16,128]" not in text
    # ``ddt`` and the checkpoints, 336 + 42 MB, and small change: a float32
    # copy of ``x`` or ``y`` would be 336 MB more each
    assert compiled.memory_analysis().temp_size_in_bytes < 200e6
    assert compiled.memory_analysis().output_size_in_bytes < 520e6


@pytest.fixture(scope="module")
def mamba_layer_step(topo):
    """Value and gradient of published layer 16 of ``phi4_mini_flash`` (a
    Mamba-1 layer, the one that hands its scan's output on) over one
    sequence of 16,384 tokens in bfloat16, recomputed as ``SambaYDecoder``
    recomputes it, compiled for one described chip."""
    from tpu_ddp.models.decoder import recomputed
    from tpu_ddp.models.sambay import SambaYLayer, phi4_mini_flash_spec
    from tpu_ddp.parallel import runtime

    one = _one_chip(topo)
    spec = phi4_mini_flash_spec(first_layer=14, num_layers=6, vocab_rows=512)
    layer = recomputed(SambaYLayer)(spec.memory_layer, spec,
                                    dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.key(0), jnp.zeros((1, 16, spec.hidden), jnp.bfloat16)))[
            "params"]
    params = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        l.shape, l.dtype, sharding=one), shapes)
    h = jax.ShapeDtypeStruct((1, 16384, spec.hidden), jnp.bfloat16,
                             sharding=one)

    def loss(p, h):
        out, memory = layer.apply({"params": p}, h)
        return (out.astype(jnp.float32).sum()
                + memory.astype(jnp.float32).sum())

    # the mixer asks the runtime whether to interpret its kernels, and the
    # runtime sees the CPU here: steered in the test, as ``interpret=False``
    # steers the kernels' own cases
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(runtime, "is_tpu_device", lambda: True)
        return jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
            params, h).compile()


def test_a_recomputed_mamba_layer_calls_the_forward_scan_once(
        mamba_layer_step):
    """A recomputed layer keeps everything the forward scan kernel writes,
    its output and the state at each time block's start
    (``selective_scan.Y_NAME``, ``CKPT_NAME`` of ``decoder.KEPT_NAMES``),
    so the kernel's second call, which made them again for the backward
    kernel, is dead code: one ``selective_scan_fwd`` and one
    ``selective_scan_bwd`` where the layer had two and one before PR 46."""
    assert _kernel_calls(
        mamba_layer_step.as_text(), "selective_scan_fwd",
        "selective_scan_bwd") == {"selective_scan_fwd": 1,
                                  "selective_scan_bwd": 1}


def test_differential_attention_compiles_one_backward_kernel_at_16k(topo):
    """``phi4-mini-flash``'s full-attention call: 16,384 positions, 40
    query heads of 64 over 20 key heads of 64 and value heads of 128; the
    carry of the one-kernel backward pass is exactly its budget
    (``_FUSED_CARRY_MAX``), and the compiler takes it."""
    import importlib

    fa = importlib.import_module("tpu_ddp.ops.flash_attention")
    assert fa._fused_carry_bytes(16384, 128, 128, 2) == fa._FUSED_CARRY_MAX
    one = _one_chip(topo)
    shape = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return _flash_impl()(q, k, v, causal=True, window=0).astype(
            jnp.float32).sum()

    text = _text(jax.grad(loss, argnums=(0, 1, 2)), shape(1, 16384, 40, 64),
                 shape(1, 16384, 20, 64), shape(1, 16384, 20, 128))
    assert text.count(CUSTOM_CALL) == 2 and "kernel.flash_bwd" in text
    assert "kernel.flash_dq" not in text and "kernel.flash_dkv" not in text


def test_the_routed_ladder_compiles_at_the_latent_experts_widths(topo):
    """``_switch`` over the hybrid cell's ladder: 16,384 tokens x 22 choices
    with 8 of 512 plain experts held, so 11,264 rows and, a token's choices
    being distinct, 131,072 at most (not 360,448); latent width 1,024,
    expert width 2,688, no gate matrix."""
    import functools

    from tpu_ddp.models import moe

    one = _one_chip(topo)
    n, c, f, k, held = 16384, 1024, 2688, 22, 8

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    rungs = moe.buffer_rungs(n * k, held, 512, k)
    assert rungs == (11264, 131072)
    walk = tuple(functools.partial(moe._routed, r, k, jnp.bfloat16)
                 for r in rungs)
    routing = (shape((n * k,), jnp.int32), None, shape((held,), jnp.int32),
               shape((n,), jnp.int32))
    floats = (shape((n, c), jnp.bfloat16), None,
              shape((held, c, f), jnp.bfloat16),
              shape((held, f, c), jnp.bfloat16), shape((n, k), jnp.float32))

    def loss(floats, index, routing):
        return moe._switch(walk, index, routing, floats).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        floats, shape((), jnp.int32), routing).compile()
    text = compiled.as_text()
    assert text.count(" conditional(") == 2
    assert text.count("ragged-dot") >= 2 * len(rungs)
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


@pytest.fixture(scope="module")
def expert_block_step(topo):
    """The gradient of blocks ``ME`` of the hybrid decoder at the cell's
    widths (16,384 tokens, 22 of 512 experts, 8 held), recomputed, compiled
    for one described chip."""
    from tpu_ddp.models.hybrid import HybridDecoder, nemotron3_super_spec
    from tpu_ddp.parallel import runtime

    one = _one_chip(topo)
    spec = nemotron3_super_spec(num_layers=2, experts_held=8, vocab_rows=512,
                                head_positions=8)
    model = HybridDecoder(spec, dtype=jnp.bfloat16, remat=True)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 256), jnp.int32)))["params"]
    params = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        l.shape, l.dtype, sharding=one), shapes)
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one)

    def loss(p, tokens):
        logits, _ = model.apply({"params": p}, tokens, mutable=["counters"])
        return logits.sum()

    # the Mamba block's scan asks the runtime whether to interpret its
    # kernels: steered in the test
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(runtime, "is_tpu_device", lambda: True)
        return jax.jit(jax.grad(loss)).lower(params, tokens).compile()


def test_a_recomputed_expert_block_walks_its_routed_path_twice(
        expert_block_step):
    """A recomputed block of the hybrid decoder keeps the routed result
    (``moe.ROUTED_NAME``, 33.5 MB a block at the cell's sizes), so its
    backward pass makes the sorts and the projections again but not the
    ladder's branch with its grouped products: one switch forward and one
    backward for the one expert block, where recomputing everything has
    three."""
    assert expert_block_step.as_text().count(" conditional(") == 2


def _flash_impl():
    import functools

    from tpu_ddp.ops.flash_attention import flash_attention

    return functools.partial(flash_attention, block_q=512, block_k=512,
                             interpret=False)


@pytest.fixture(scope="module")
def sparse_layer_step(topo):
    """Value and gradient of one sparse layer of ``joyai_llm_flash`` at the
    cell's widths (16,384 tokens, latent attention through the flash
    kernels, 8 of 256 experts with a selection bias, 16 held), recomputed
    as ``SparseDecoder`` recomputes it (``decoder.recomputed``: all but
    ``KEPT_NAMES``), compiled for one described chip."""
    from tpu_ddp.models.decoder import (DecoderLayer, joyai_llm_flash_spec,
                                        recomputed)

    one = _one_chip(topo)
    spec = joyai_llm_flash_spec(num_layers=2, experts_held=16, vocab_rows=512)
    layer = recomputed(DecoderLayer)(
        spec.mtp, spec, dtype=jnp.bfloat16, attention_impl=_flash_impl())
    cos, sin = spec.mtp.rotary.tables(8192)
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.key(0), jnp.zeros((1, 256, spec.hidden), jnp.bfloat16),
        cos[:256], sin[:256]))["params"]
    params = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        l.shape, l.dtype, sharding=one), shapes)
    x = jax.ShapeDtypeStruct((2, 8192, spec.hidden), jnp.bfloat16,
                             sharding=one)

    def loss(p, x):
        y, _ = layer.apply({"params": p}, x, cos, sin, mutable=["counters"])
        return y.astype(jnp.float32).sum()

    # with the value, or the forward pass itself is dead code here
    return jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        params, x).compile()


@pytest.mark.parametrize("step,temporaries", [
    ("expert_block_step", 2.95e9), ("sparse_layer_step", 3.7e9),
], ids=["nemotron3_super", "joyai_llm_flash"])
def test_a_recomputed_expert_block_makes_its_routers_choice_once(
        step, temporaries, request):
    """Both decoder stacks keep the router's float32 logits, the chosen ids
    and their scores (``moe.LOGITS_NAME``, ``IDS_NAME``, ``SCORES_NAME`` of
    ``decoder.KEPT_NAMES``: 33.5 MB and twice 1.4 MB a block of the hybrid
    stack), so the backward pass makes none of the six-pass product and the
    ``top_k`` again. The TPU compiler writes ``lax.top_k`` of 22 over 512
    (8 over 256) as a whole ``sort`` of the (16,384, experts) scores with
    their places and a slice, and leaves ``top_k`` in its ``op_name``: one,
    the forward pass's, and nothing of the router's product under
    ``rematted_computation`` (``SparseDecoder`` recomputed a layer without
    a policy until PR 42, and its router sorted twice). The chosen scores
    are read and differentiated as compares against the expert axis
    (``moe._chosen``): no gather, no scatter and no further sort under
    ``moe_route`` anywhere in the step, and nothing (tokens, choices,
    experts) wide is written (184 M and 34 M places): the hybrid pair of
    blocks' temporaries are 2.848 GB, 0.8 MB under what they were with
    ``take_along_axis`` (PERF.md section 6, PR 34 and PR 37)."""
    compiled = request.getfixturevalue(step)
    text = compiled.as_text()
    routed = [line for line in text.splitlines()
              if re.search(r'op_name="[^"]*moe_route/', line)]
    assert routed
    sorted_ = [line for line in routed if " sort(" in line]
    assert len(sorted_) == 1
    assert all("moe_route/top_k" in line for line in sorted_)
    assert not re.search(r"rematted_computation[^\"]*moe_route/router", text)
    # neither the instructions nor what the compiler made of their parts
    assert not [line for line in routed if re.search(
        r' (gather|scatter)\(|op_name="[^"]*(gather|scatter)', line)]
    wide = re.compile(r"\[16384,(8|22),(256|512)\]\{[^}]*\} fusion\(")
    assert not wide.search(text)
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries


def _kernel_calls(text: str, *kernels) -> dict:
    """{kernel: custom calls} of the program's ``kernels`` in a compiled
    text, by the scope each call's ``op_name`` keeps."""
    calls = [line for line in text.splitlines()
             if " custom-call(" in line and CUSTOM_CALL in line]
    return {kernel: sum(f"tpu_ddp.kernel.{kernel}/" in line for line in calls)
            for kernel in kernels}


def _flash_calls(text: str) -> dict:
    return _kernel_calls(text, "flash_fwd", "flash_bwd")


def test_a_recomputed_sparse_layer_calls_the_forward_kernel_once(
        sparse_layer_step):
    """A recomputed layer keeps its attention's output and a float32 a row
    of the logsumexp (``flash_attention.OUT_NAME``, ``LSE_NAME``), so the
    forward kernel's second call, which made them again for the backward
    kernel, is dead code: one ``flash_fwd`` and one ``flash_bwd`` where a
    bare ``nn.remat`` has two and one; and the temporaries are under the
    3.97 GB of that (3.62 GB even with the statistics kept lane-broadcast;
    ISSUE 42's table)."""
    assert _flash_calls(sparse_layer_step.as_text()) == {
        "flash_fwd": 1, "flash_bwd": 1}
    assert sparse_layer_step.memory_analysis().temp_size_in_bytes < 3.7e9


def test_a_recomputed_stack_keeps_a_float_a_row_of_its_statistics(topo):
    """``laguna-xs2.seq8k``'s model (5 layers, 32 experts held, 2 sequences
    of 8,192, bfloat16), value and gradient, compiled for one described
    chip: five forward and five backward calls (ten and five without the
    names), and temporaries of 3.64 GB beside the 3.60 GB of a bare
    ``nn.remat``. With the logsumexp kept as the kernels hold it, 128 lanes
    a row, they are 6.05 GB, which the cell's step has no room for: what
    lives from pass to pass is (B*H, T) float32, and this is the test that
    holds it there."""
    from tpu_ddp.models.decoder import SparseDecoder, laguna_xs2_spec

    one = _one_chip(topo)
    model = SparseDecoder(
        laguna_xs2_spec(num_layers=5, experts_held=32, vocab_rows=512),
        dtype=jnp.bfloat16, attention_impl=_flash_impl(), remat=True)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 256), jnp.int32)))["params"]
    params = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        l.shape, l.dtype, sharding=one), shapes)
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one)

    def loss(p, tokens):
        logits, _ = model.apply({"params": p}, tokens, mutable=["counters"])
        return logits.sum()

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        params, tokens).compile()
    assert _flash_calls(compiled.as_text()) == {"flash_fwd": 5,
                                                "flash_bwd": 5}
    assert compiled.memory_analysis().temp_size_in_bytes < 3.9e9


# ---- the int8 ring's quantize / dequantize ----------------------------------

def test_fused_quant_compiles_at_real_size(topo):
    from tpu_ddp.ops.fused_quant import fused_dequant, fused_quant

    n, block = 2_359_296, 256  # a 3x3x512x512 conv kernel
    one = _one_chip(topo)
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one)
    payload = {
        "q": jax.ShapeDtypeStruct((n,), jnp.int8, sharding=one),
        "scale": jax.ShapeDtypeStruct((n // block,), jnp.float32,
                                      sharding=one),
    }
    quant = _text(lambda v: fused_quant(v, block, interpret=False), x)
    assert quant.count(CUSTOM_CALL) == 1
    dequant = _text(
        lambda p, acc: fused_dequant(p, block, n, add_to=acc,
                                     interpret=False), payload, x)
    assert dequant.count(CUSTOM_CALL) == 1


# ---- the fused optimizer update ---------------------------------------------

@pytest.mark.parametrize("name,opt", [
    ("sgd+momentum", dict(lr=1e-2, momentum=0.9)),
    ("adamw", dict(lr=1e-3, optimizer="adamw", weight_decay=0.05)),
])
def test_fused_update_compiles_at_real_leaves(topo, name, opt):
    from tpu_ddp.ops.fused_update import FusedUpdate
    from tpu_ddp.train.optim import make_optimizer

    tx = make_optimizer(**opt, kernels=True)
    fused = FusedUpdate(tx.fused.recipe, interpret=False)  # not the mirror
    one = _one_chip(topo)
    params = {
        "dense": {"kernel": jax.ShapeDtypeStruct((768, 3072), jnp.float32)},
        "conv": {"kernel": jax.ShapeDtypeStruct((3, 3, 512, 512),
                                                jnp.float32)},
    }
    opt_state = jax.eval_shape(tx.init, params)
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), t)
    text = _text(fused.apply, place(params), place(opt_state), place(params))
    # one kernel launch per leaf
    assert text.count(CUSTOM_CALL) == 2, name


# ---- the DP step on four described chips ------------------------------------

@pytest.mark.parametrize("zero1,wanted", [
    (False, ("all-reduce",)),
    # XLA may lower the psum_scatter as an all-reduce and a slice
    (True, ("reduce-scatter|all-reduce", "all-gather")),
])
def test_dp_step_compiles_for_four_chips(topo, zero1, wanted):
    from tpu_ddp.analysis.explain import abstract_batch
    from tpu_ddp.models import NetResDeep
    from tpu_ddp.parallel import MeshSpec, create_mesh
    from tpu_ddp.train import make_optimizer
    from tpu_ddp.train.strategy import build_abstract_step

    mesh = create_mesh(MeshSpec(data=-1), topo.devices)
    assert mesh.shape["data"] == 4
    model = NetResDeep()  # the reference model, full size
    tx = make_optimizer(lr=1e-2, momentum=0.9,
                        zero1_axis="data" if zero1 else None)
    step, state = build_abstract_step("dp", model, tx, mesh, zero1=zero1)
    batch = abstract_batch(mesh, 32, 32)
    assert batch["image"].sharding == NamedSharding(mesh, P("data"))
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    for pattern in wanted:
        assert re.search(rf" ({pattern})(-start)?\(", text), pattern
    # per-device bytes: a quarter of the global batch, the whole params
    assert compiled.memory_analysis().argument_size_in_bytes > 0
