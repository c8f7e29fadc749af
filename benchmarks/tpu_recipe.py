#!/usr/bin/env python
"""On-chip rerun of the committed recipe demo (round-4 verdict item 2).

The committed training-quality artifact (``benchmarks/recipe_demo/``) shows
the framework recipe beating the reference recipe on BOTH time-to-threshold
and final accuracy — but it ran on the virtual CPU mesh, and the verdict
asked for the demo run on a chip. This tool does exactly that: the same
two-arm comparison (same task,
model, knobs — see ``benchmarks/recipe_demo.py``) executed with
``--device tpu``, written to ``benchmarks/recipe_demo_tpu/`` so the CPU
artifact stays untouched for comparison.

One process per chip (shared with bench.py / tpu_curve.py): this parent is
stdlib-only and never imports jax; the demo runs in ONE child, which holds
the chip, is TERMed gracefully on timeout, and — ``--device tpu`` — fails
loudly when there is no TPU. This tool then exits non-zero too.

Usage: ``python benchmarks/tpu_recipe.py [--timeout 2400] [--epochs 32]``
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OUT_DIR = os.path.join(_REPO, "benchmarks", "recipe_demo_tpu")

sys.path.insert(0, _REPO)
import bench  # noqa: E402  (stdlib-only at module level)


def _on_term(signum, frame):
    # the demo child registers in bench._ACTIVE_CHILD via run_child; a
    # TERM mid-demo must not orphan a child that holds the chip
    child = bench._ACTIVE_CHILD
    if child is not None:
        bench._terminate_gracefully(child, grace=20)
    raise SystemExit(124)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=float, default=2400.0)
    ap.add_argument("--epochs", type=int, default=32)
    ap.add_argument("--seeds", default="0 1")
    args = ap.parse_args()
    signal.signal(signal.SIGTERM, _on_term)

    # Same arms/knobs as the committed CPU artifact (recipe_demo.py
    # defaults + the committed invocation: tiny flagship config, hard
    # synthetic task) so the two summaries differ only in device_kind.
    demo_argv = [
        sys.executable, "-u", os.path.join(_REPO, "benchmarks",
                                           "recipe_demo.py"),
        "--device", "tpu",
        "--out-dir", _OUT_DIR,
        "--model", "netresdeep",
        "--common", "--n-chans1 16 --n-blocks 2",
        "--size", "4096",
        "--epochs", str(args.epochs),
        # GLOBAL batch 256 on the single chip = the committed CPU
        # artifact's global batch (32/shard x 8 virtual workers), so both
        # arms' lrs stay in the regime they were tuned/compared at; the
        # demo's --batch-size is per-shard (reference semantics).
        "--batch-size", "256",
        "--seeds", *args.seeds.split(),
    ]
    # A stale summary from an earlier run must not be read back as THIS
    # run's result if the child dies before writing its own.
    stale = os.path.join(_OUT_DIR, "summary.json")
    if os.path.exists(stale):
        os.unlink(stale)
    out, err, wall = bench.run_child(demo_argv, args.timeout)
    try:
        with open(os.path.join(_OUT_DIR, "summary.json")) as f:
            json.load(f)
    except (OSError, json.JSONDecodeError):
        # A TERM'd/crashed child can leave a truncated summary.json
        # (recipe_demo writes it non-atomically); it must not survive to
        # be read as this run's result.
        if os.path.exists(stale):
            os.unlink(stale)
        if err is None:
            err = ("demo exited 0 but wrote no summary.json: "
                   + " | ".join(out.strip().splitlines()[-4:]))
    print(f"tpu_recipe: {'ok' if err is None else err} [{wall:.0f}s]",
          flush=True)
    if err is not None:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
