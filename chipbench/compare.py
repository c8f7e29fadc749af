"""The comparison that decides ``correct``.

The timed path (the compiled step with its state, driven through its first
three steps by the window's own call and feed) is compared with the plain
reference that follows the same three steps from the same seeded weights and
the same rows. Numbers compared, each against a limit of its own from
``chipbench/limits/<cell>.json``:

``loss_gap``
    worst over the steps of ``|loss - reference loss| / |reference loss|``.
    Hardly moved by precision; held against part of the batch left out.
``grad_gap``
    the first step's gradient as the optimizer got it, by the worst leaf:
    ``| ||g|| - ||g_ref|| | / max(||g_ref||, median leaf ||g_ref||)``: the gap
    between the norms, not the norm of the difference, measured against that
    leaf's reference norm or the median leaf's, whichever is larger (some
    gradients are all but zero). Second order in rounding noise, so one
    notch of precision does not move it; held against a leaf without its
    gradient (gap 1).
``update_gap``
    the same by the worst leaf for the parameters' change over the three
    steps. Held against a step that returns its state unchanged (gap 1).
``grad_diff``
    ``||g - g_ref|| / ||g_ref||`` over all leaves as one vector: first order
    in rounding noise, where a gap of norms is second order. The backward
    pass amplifies rounding noise layer by layer, so through a deep stack the
    stated precision already reads high here and one notch lower reads less
    than three times that (PERF.md section 2): held against a gradient that
    points elsewhere (the exchange between chips left out, rows of another
    batch).
``out_grad_diff``
    the same over the output layer's leaves alone (the reference's
    ``OUTPUT_LEAVES``). That gradient is the whole forward pass's activations
    against the loss's own derivative: it sees every layer's forward rounding
    and no backward pass amplifies it. It is the number that tells the stated
    precision from the one below.

Non-finite numbers fail. Every run prints each number beside its limit.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def worst_leaf_gap(leaf_norms: dict, ref_norms: dict):
    """(gap, leaf) of the worst leaf, the gap measured as the module
    docstring says; a NaN anywhere is the worst."""
    if set(leaf_norms) != set(ref_norms):
        raise ValueError(
            f"leaves differ: {sorted(set(leaf_norms) ^ set(ref_norms))[:6]}")
    floor = statistics.median(ref_norms.values())
    worst, where = 0.0, None
    for name, ref in ref_norms.items():
        denom = max(ref, floor)
        gap = abs(leaf_norms[name] - ref) / denom if denom > 0 else (
            0.0 if leaf_norms[name] == 0 else math.inf)
        if math.isnan(gap):
            return gap, name
        if gap > worst:
            worst, where = gap, name
    return worst, where


def norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def relative_difference(tree: dict, ref_tree: dict, leaves=None) -> float:
    """``||x - x_ref|| / ||x_ref||`` over ``leaves`` (all of the reference's
    unless given) taken as one vector."""
    leaves = list(ref_tree) if leaves is None else list(leaves)
    num = sum(float(np.sum(np.square(
        np.asarray(tree[k], np.float64) - np.asarray(ref_tree[k], np.float64))))
        for k in leaves)
    den = sum(float(np.sum(np.square(np.asarray(ref_tree[k], np.float64))))
              for k in leaves)
    return math.sqrt(num / den) if den > 0 else math.inf


def readings(numbers: dict, ref_numbers: dict) -> dict:
    """name -> (value, worst leaf or step). ``numbers`` and ``ref_numbers``
    hold ``losses`` (a list), ``grad`` and ``update`` (leaf -> array);
    ``ref_numbers`` also names the ``output_leaves``."""
    losses, ref_losses = numbers["losses"], ref_numbers["losses"]
    loss_gaps = [abs(a - b) / abs(b) if b else math.inf
                 for a, b in zip(losses, ref_losses)]
    if len(losses) != len(ref_losses) or not loss_gaps:
        loss_gaps = [math.inf]
    worst_step = max(range(len(loss_gaps)),
                     key=lambda i: (math.isnan(loss_gaps[i]), loss_gaps[i]))
    n = len(ref_numbers["grad"])
    out = list(ref_numbers["output_leaves"])
    return {
        "loss_gap": (loss_gaps[worst_step], f"step {worst_step + 1}"),
        "grad_gap": worst_leaf_gap(
            norms(numbers["grad"]), norms(ref_numbers["grad"])),
        "update_gap": worst_leaf_gap(
            norms(numbers["update"]), norms(ref_numbers["update"])),
        "grad_diff": (relative_difference(
            numbers["grad"], ref_numbers["grad"]), f"{n} leaves"),
        "out_grad_diff": (relative_difference(
            numbers["grad"], ref_numbers["grad"], out), " + ".join(out)),
    }


def decide(read: dict, limits: dict, out=print) -> bool:
    """True when every reading is finite and within its limit; prints each
    number beside its limit."""
    ok = True
    for name, (value, where) in read.items():
        limit = limits[name]
        within = math.isfinite(value) and value <= limit
        ok = ok and within
        out(f"correct: {name} = {value!r} (at {where}) limit {limit!r} "
            f"{'ok' if within else 'FAILED'}")
    return ok
