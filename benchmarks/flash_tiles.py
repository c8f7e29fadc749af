"""By hand, on the chip: one call of each flash attention kernel at the
decoder cells' layer shapes (``laguna-xs2.seq8k``: 2 x 8,192 x 48 x 128, no
window, and 2 x 8,192 x 64 x 128, window 512; 8 key-value heads;
``joyai-llm-flash.seq8k-v16160``: 2 x 8,192 x 32, keys of 192 over values
of 128; bfloat16).

    python3 benchmarks/flash_tiles.py [--layers ...] [--arms ...]

An arm is

``fwd``         the forward kernel at ``--blocks``
``bwd``         the backward pass at ``--blocks``: whichever kernels
                ``_flash_backward`` runs for the shape (``flash_bwd``, or
                ``flash_dq`` and ``flash_dkv``), each by its scope
``blocks:QxK``  the forward kernel at plain ``flash_blocks`` of (Q, K): what
                a reader would otherwise ask about

Every arm is jitted alone, run ``--reps`` times under the profiler, and
reported as the device milliseconds a call of each kernel (by the
``tpu_ddp.kernel.<name>`` scope of the custom call) beside the host clock's
around the whole program (kernels, the folds into (B*H, T, D) and, for the
backward arm, ``di``), with the largest difference of its results from the
first arm's of its pass. Run from a copy of another commit (the script
copied into its ``benchmarks/``), ``fwd`` and ``bwd`` time that commit's
kernels: that is the parent's row of PERF.md's table (section 6, PR 30).
The arms that walked a tile in smaller pieces, and the two that did less
per score, were in this script while the kernels could do so; PERF.md keeps
what they read. No benchmark run calls this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: a layer kind's query heads and what it has other than no window,
#: ``--kv-heads`` and ``--head-dim``
LAYERS = {"full": dict(heads=48), "window": dict(heads=64, window=512),
          "latent": dict(heads=32, kv_heads=32, qk_dim=192, v_dim=128)}
DEFAULT_ARMS = ("fwd,bwd,blocks:256x256,blocks:512x128,blocks:512x1024,"
                "blocks:1024x512")
CALL = re.compile(
    r'^\s*%?([\w.-]+) = .*custom_call_target="tpu_custom_call".*'
    r'op_name="[^"]*tpu_ddp\.kernel\.(\w+)', re.M)


def main(argv=None):
    import jax
    import jax.numpy as jnp

    # ``tpu_ddp.ops`` exports a function of the module's name
    fa = importlib.import_module("tpu_ddp.ops.flash_attention")

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--tokens", type=int, default=8192)
    parser.add_argument("--kv-heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=128)
    parser.add_argument("--blocks", default="512x512")
    parser.add_argument("--layers", default="full,window")
    parser.add_argument("--arms", default=DEFAULT_ARMS)
    parser.add_argument("--reps", type=int, default=10)
    args = parser.parse_args(argv)
    device = jax.devices()[0]
    interpret = fa._resolve_interpret(None)
    print(json.dumps({"device": device.platform, "kind": device.device_kind,
                      "interpret": interpret}), flush=True)

    for layer in args.layers.split(","):
        kind = LAYERS[layer]
        heads, window = kind["heads"], kind.get("window", 0)
        kv_heads = kind.get("kv_heads", args.kv_heads)
        qk_dim = kind.get("qk_dim", args.head_dim)
        v_dim = kind.get("v_dim", args.head_dim)
        keys = jax.random.split(jax.random.key(heads), 4)
        q, g, k, v = (
            jax.random.normal(key, (args.batch, args.tokens, n, d),
                              jnp.bfloat16)
            for key, n, d in zip(keys, (heads, heads, kv_heads, kv_heads),
                                 (qk_dim, v_dim, qk_dim, v_dim)))
        first = {}
        for arm in args.arms.split(","):
            what, _, tile = arm.partition(":")
            blocks = tuple(map(int, (tile or args.blocks).split("x")))
            backward = what == "bwd"
            how = dict(block_q=blocks[0], block_k=blocks[1],
                       interpret=interpret, causal=True, window=window)
            forward = jax.jit(lambda q, k, v, how=how: fa._flash_forward(
                q, k, v, **how))
            if backward:
                out, lse = forward(q, k, v)
                program = jax.jit(
                    lambda q, k, v, o, lse, g, how=how: fa._flash_backward(
                        q, k, v, o, lse, g, **how))
                operands = (q, k, v, out, lse, g)
            else:
                program, operands = forward, (q, k, v)
            line = {"layer": layer, "arm": arm}
            try:
                line.update(_time(program, operands, args.reps))
            except Exception as e:  # what the chip's compiler refuses
                line["error"] = repr(e)[:400]
                print(json.dumps(line), flush=True)
                continue
            results = jax.tree.leaves(program(*operands))
            base = first.setdefault(backward, results)
            line["largest_difference_from_first_arm"] = max(
                float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(results, base))
            print(json.dumps(line), flush=True)


def _time(program, operands, reps):
    """Compile, warm, then ``reps`` calls under the profiler: the host
    clock's ms a call and the device's by kernel."""
    import jax

    from chipbench import xplane

    t0 = time.perf_counter()
    compiled = program.lower(*operands).compile()
    compile_s = time.perf_counter() - t0
    kernel_of = dict(CALL.findall(compiled.as_text()))
    jax.block_until_ready(compiled(*operands))
    out = tempfile.mkdtemp(prefix="flash_tiles_")
    jax.profiler.start_trace(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(compiled(*operands))
    host_ms = (time.perf_counter() - t0) / reps * 1e3
    jax.profiler.stop_trace()
    timed = {"compile_s": compile_s, "host_ms_a_call": host_ms}
    try:
        lines = xplane.load(xplane.find_xplane(out))["devices"][0]
    except (FileNotFoundError, KeyError) as e:  # no device plane: a CPU
        timed["trace"] = repr(e)
        return timed
    finally:
        shutil.rmtree(out, ignore_errors=True)
    for name, ns in xplane.reduce_chip(lines)["op_totals_ns"].items():
        kernel = kernel_of.get(name)
        if kernel:
            key = f"{kernel}_ms_a_call"
            timed[key] = timed.get(key, 0.0) + ns / reps / 1e6
    return timed


if __name__ == "__main__":
    main()
