"""The control of ``correct``, on the chip, at a cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 [--read sound|control]
                                 [--precision <a name of reference/common.py::PRECISIONS>]

For each seed, in one process: drive the cell's timed path through its first
three steps (the harness's own adapter and probe, no measured window) and
read its gaps to the plain reference; then put the reference in the program's
place, computed one notch below the precision the configuration states
(``chipbench/reference/common.py::ONE_NOTCH_LOWER``), and read the same gaps.
Prints one line per seed and the two numbers every limit is set from: the
largest sound reading and the smallest control reading. The benchmark's own
runs never run this; PERF.md records what it printed.

``--precision`` puts the reference in the program's place at another
precision than the notch below: at the stated one (``bfloat16`` for a
configuration that states it) it reads what that precision alone makes of a
number, which is how a reading of the program that stands apart from its
other seeds is told from a fault (PERF.md section 2).

``--read control`` leaves the program out: the control is the reference
against itself, so it needs the cell's sizes (rows, shards, seeded weights and
training set) and one chip, not the cell's chips. ``--read sound`` leaves the
control out. Together they read a four-chip cell at the least cost: the
control on one chip, the program alone on four.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run as harness  # noqa: E402
from chipbench.reference import common  # noqa: E402


def seeded_record(loaded, seed) -> dict:
    """What ``reference_numbers`` reads of a run record, without the program:
    the seeded weights and the first ``CHECK_STEPS`` batches of the seeded
    training set in shard-major order, at the cell's batch and shards, as the
    configuration's task lays a batch out (``common.batches`` by default)."""
    import numpy as np

    from chipbench import datagen

    cfg, traffic = loaded["config"], loaded["traffic"]
    folded = datagen.fold_seed(seed)
    drawn_from = datagen.work_seed(traffic, folded)
    data = loaded["dataset"].make(traffic["dataset"], drawn_from)
    shards = int(traffic["mesh"]["data"])
    batches = common.task(loaded["reference"]).batches(
        data, rows=shards * int(traffic["per_shard_batch"]),
        steps=loaded["adapter"].CHECK_STEPS)
    params = {k: np.asarray(v) for k, v in
              loaded["reference"].init_params(cfg, drawn_from).items()}
    datagen.tell_run_seed(loaded["reference"], folded)
    return {"check": {"params0": params, "batches": batches},
            "shards": shards,
            "optimizer": common.optimizer_of(
                {**cfg["train_config"], **traffic.get("overlays", {})})}


def by_group(numbers, reference) -> dict:
    """``grad_diff`` of each group of leaves (a leaf's name up to its first
    dot), for looking at where in the stack a difference sits."""
    from chipbench import compare

    groups = {}
    for leaf in reference["grad"]:
        groups.setdefault(leaf.split(".", 1)[0], []).append(leaf)
    return {group: compare.relative_difference(
        numbers["grad"], reference["grad"], leaves)
        for group, leaves in groups.items()}


def read_seed(loaded, seed, args, precision, counters, scratch) -> dict:
    """One seed's row: the program's gaps to the reference (``sound``), the
    reference's at ``precision`` (``control``), or both. Everything the seed
    made on the host dies with this call."""
    t0 = time.perf_counter()
    ctx = types.SimpleNamespace(
        cell=loaded["cell"], config=loaded["config"],
        traffic=loaded["traffic"], reference=loaded["reference"],
        dataset=loaded["dataset"], seed=seed, seconds=0.0, trace=False,
        counters=counters, scratch_dir=scratch, t_start=t0,
        say=harness.say, open_after_steps=0)
    if args.read == "control":
        record = seeded_record(loaded, seed)
    else:
        record = loaded["adapter"].run(ctx)
    gc.collect()
    reference = harness.reference_numbers(loaded, record)
    row = {"seed": seed}
    if args.read != "sound":
        row["control_precision"] = precision
    readers = []
    if args.read != "control":
        row["losses"] = record["check"]["losses"]
        readers.append(("sound", lambda: harness.program_numbers(
            common.task(loaded["reference"]), record)))
    if args.read != "sound":
        readers.append(("control", lambda: harness.reference_numbers(
            loaded, record, precision)))
    for label, numbers in readers:
        numbers = numbers()
        read = harness.gaps(numbers, reference)
        row[label] = {k: v[0] for k, v in read.items()}
        row[label + "_at"] = {k: v[1] for k, v in read.items()}
        row[label + "_grad_diff_by_group"] = by_group(numbers, reference)
    row["seconds"] = time.perf_counter() - t0
    return row


def main(argv=None, *, roots=None, bench_path=None, device_check=True):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--read", choices=("both", "sound", "control"),
                        default="both")
    parser.add_argument("--precision", choices=sorted(common.PRECISIONS),
                        help="what the control is computed in (default: one "
                             "notch below the configuration's)")
    args = parser.parse_args(argv)
    roots = list(roots or []) + [harness.HERE]
    bench = harness.load_json(
        bench_path or os.path.join(harness.REPO, "BENCHMARK.json"))
    loaded = harness.load_cell(bench, args.workload, roots)
    chips = 1 if args.read == "control" else int(loaded["cell"]["chips"])
    harness.setup_compile_cache()
    counters = harness.Counters().install()
    if device_check:
        harness.check_device(chips, harness.load_json(
            harness.find(roots, "peaks.json")))
    precision = args.precision or common.ONE_NOTCH_LOWER[
        loaded["config"]["precision"]]
    scratch = os.path.join(harness.REPO, ".chipbench_runs", args.workload)
    os.makedirs(scratch, exist_ok=True)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(read_seed(loaded, seed, args, precision, counters,
                              scratch))
        # a seed's record and readings are some 20 GB of host copies at a
        # decoder cell's size: freed before the next seed builds its own
        gc.collect()
        print("control:", json.dumps(rows[-1]), flush=True)
    summary = {"workload": args.workload, "control_precision": precision,
               "seeds": len(rows)}
    for label, pick in (("sound", max), ("control", min)):
        for name in rows[0].get(label, {}):
            summary[f"{name}.{label}_{pick.__name__}"] = pick(
                r[label][name] for r in rows)
    print("control summary:", json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
