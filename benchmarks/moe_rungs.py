"""By hand, on the chip: one ``DroplessMoE`` layer's routed path at a cell's
shapes, forward and backward, over buffers of different lengths and with the
ways of collecting and of switching that ``models/moe.py`` chose among.

    python3 benchmarks/moe_rungs.py [--landed 18097] [--trace 1]

Variants, each jitted alone and timed by the host clock around ``--reps``
calls that end in ``block_until_ready``:

``full``       the buffer has a row for every (token, choice): the parent's
``rung``       the shortest rung, collected by token order (``_ByToken``)
``rung_fill``  the shortest rung, collected as the full buffer is: ``N * K``
               rows gathered by ``inverse``, zeros where it points past it
``switch``     the layer's own: ``_switch`` over the ladder (residuals are
               the operands, the backward pass switches again)
``switch_ad``  ``lax.switch`` over the ladder left to reverse-mode AD: every
               branch's residuals come out of the forward pass

The router is seeded so that about ``--landed`` pairs land on the held
experts. No benchmark run calls this; PERF.md records what it printed.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_ddp.models import moe

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tokens", type=int, default=16384)
    parser.add_argument("--hidden", type=int, default=2048)
    parser.add_argument("--width", type=int, default=512)
    parser.add_argument("--experts", type=int, default=256)
    parser.add_argument("--held", type=int, default=32)
    parser.add_argument("--top-k", type=int, default=8)
    parser.add_argument("--landed", type=int, default=18097)
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--variants", default="full,rung,rung_fill,switch,"
                                              "switch_ad")
    args = parser.parse_args(argv)
    n, c, f, k = args.tokens, args.hidden, args.width, args.top_k
    held, pairs = args.held, args.tokens * args.top_k
    rungs = moe.buffer_rungs(pairs, held, args.experts)
    dtype = jnp.bfloat16
    device = jax.devices()[0]
    print(json.dumps({"device": device.platform, "kind": device.device_kind,
                      "rungs": rungs}), flush=True)

    # a router that lands ``landed`` pairs: each pair on a held expert with
    # that probability, the fullest expert about four times the mean
    rng = np.random.default_rng(0)
    here = rng.random(pairs) < args.landed / pairs
    skew = 1.0 / np.arange(1, held + 1) ** 0.9
    ids = np.where(here, rng.choice(held, size=pairs, p=skew / skew.sum()),
                   held + rng.integers(0, args.experts - held, size=pairs))
    group = jnp.asarray(np.where(ids < held, ids, held), jnp.int32)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    load = jnp.sum(group[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)
    count = jnp.sum(group.reshape(-1, k) < held, axis=1, dtype=jnp.int32)
    routing = (order, inverse, load, count)

    def rung_of(routing):
        return sum((routing[2].sum() > r).astype(jnp.int32)
                   for r in rungs[:-1])

    keys = jax.random.split(jax.random.key(1), 6)
    floats = (
        jax.random.normal(keys[0], (n, c), dtype),
        jax.random.normal(keys[1], (held, c, f)) * c ** -0.5,
        jax.random.normal(keys[2], (held, c, f)) * c ** -0.5,
        jax.random.normal(keys[3], (held, f, c)) * f ** -0.5,
        jax.nn.softmax(jax.random.normal(keys[4], (n, k))) * 2.5)
    cotangent = jax.random.normal(keys[5], (n, c), dtype)
    print(json.dumps({"landed": int(load.sum()), "fullest": int(load.max()),
                      "rung_index": int(rung_of(routing))}), flush=True)

    walk = [functools.partial(moe._routed, r, k, dtype) for r in rungs]

    # the collect that is the least code: the full buffer's, from a short one
    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def collect_fill(y, order, inverse, k):
        rows = jnp.take(y, inverse, axis=0, mode="fill", fill_value=0)
        return rows.astype(jnp.float32).reshape(
            -1, k, y.shape[-1]).sum(axis=1).astype(y.dtype)

    collect_fill.defvjp(
        lambda y, order, inverse, k: (collect_fill(y, order, inverse, k),
                                      order),
        lambda k, order, g: (moe._spread_rows(g, order, k), None, None))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def spread_fill(x, order, inverse, k):
        return moe._spread_rows(x, order, k)

    spread_fill.defvjp(
        lambda x, order, inverse, k: (moe._spread_rows(x, order, k),
                                      (order, inverse)),
        lambda k, res, g: (collect_fill(g, res[0], res[1], k), None, None))

    def rung_fill(routing, xf, w_gate, w_up, w_down, weights):
        order, inverse, load = routing[:3]
        order = order[:rungs[0]]
        real = (jnp.arange(rungs[0]) < load.sum())[:, None]
        keep = lambda a: jnp.where(real, a, 0)  # noqa: E731
        rows = keep(spread_fill(xf, order, inverse, k))
        w_in = jnp.concatenate([w_gate, w_up], axis=-1).astype(dtype)
        h = keep(moe.grouped_matmul(rows, w_in, load))
        h = jax.nn.silu(h[:, :f]) * h[:, f:]
        out = keep(moe.grouped_matmul(h, w_down.astype(dtype), load))
        w_sorted = jnp.take(weights.reshape(-1), order)[:, None]
        return collect_fill(out * w_sorted.astype(out.dtype), order, inverse,
                            k)

    variants = {
        "full": walk[-1],
        "rung": walk[0],
        "rung_fill": rung_fill,
        "switch": lambda routing, *floats: moe._switch(
            tuple(walk), rung_of(routing), routing, floats),
        "switch_ad": lambda routing, *floats: jax.lax.switch(
            rung_of(routing), walk, routing, *floats),
    }

    def both_passes(fn):
        # the routing is an argument: a switch on a constant is no switch
        def loss(floats, routing):
            return jnp.sum(fn(routing, *floats).astype(jnp.float32)
                           * cotangent.astype(jnp.float32))
        return jax.jit(jax.value_and_grad(loss))

    results = {}
    for name in args.variants.split(","):
        step = both_passes(variants[name])
        t0 = time.perf_counter()
        compiled = step.lower(floats, routing).compile()
        compile_s = time.perf_counter() - t0
        memory = compiled.memory_analysis()
        value, grads = compiled(floats, routing)
        jax.block_until_ready(grads)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            value, grads = compiled(floats, routing)
        jax.block_until_ready(grads)
        ms = (time.perf_counter() - t0) / args.reps * 1e3
        results[name] = (value, grads)
        print(json.dumps({
            "variant": name, "ms_forward_and_backward": ms,
            "compile_s": compile_s, "loss": float(value),
            "temp_bytes": getattr(memory, "temp_size_in_bytes", None)}),
            flush=True)
        if args.trace:
            _trace(name, compiled, (floats, routing))
    base = results.get("full")
    for name, (value, grads) in results.items():
        if base is None or name == "full":
            continue
        gaps = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32)))
                      / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-30))
                for a, b in zip(jax.tree.leaves(grads),
                                jax.tree.leaves(base[1]))]
        print(json.dumps({"variant": name, "against": "full",
                          "loss_gap": float(abs(value - base[0])
                                            / abs(base[0])),
                          "worst_gradient_gap": max(gaps)}), flush=True)


def _trace(name, compiled, args):
    """Three calls under the profiler; the device operations by total time,
    and their union: whether a ``conditional`` holds its branch's
    operations' time once more."""
    import jax

    from chipbench import xplane

    out = os.path.join("chiprun_out", "moe_rungs", name)
    jax.profiler.start_trace(out)
    for _ in range(3):
        jax.block_until_ready(compiled(*args))
    jax.profiler.stop_trace()
    try:
        lines = xplane.load(xplane.find_xplane(out))["devices"][0]
    except (FileNotFoundError, KeyError) as e:  # no device plane: a CPU
        print(json.dumps({"variant": name, "trace": repr(e)}), flush=True)
        return
    chip = xplane.reduce_chip(lines)
    top = sorted(chip["op_totals_ns"].items(), key=lambda kv: -kv[1])[:25]
    print(json.dumps({
        "variant": name, "busy_ms_a_call": chip["busy_ns"] / 3e6,
        "summed_ms_a_call": sum(chip["op_totals_ns"].values()) / 3e6,
        "top_ops_ms_a_call": [[op, ns / 3e6] for op, ns in top]}),
        flush=True)


if __name__ == "__main__":
    main()
