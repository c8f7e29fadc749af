"""A throw-away benchmark at a size a CPU test can hold, made of nothing but
new files: a configuration, a traffic mix, limits and two per-layer metrics.
``write`` gives them a BENCHMARK.json of their own in a temporary directory;
``append`` puts their entries at the end of each list of a copy of a shipped
one, which is all that a PR that changes the program may do to it. The
shipped harness runs either without a shipped file being touched."""

import json
import os

CELL = "tiny-netresdeep.t8"
ARCH = {"n_chans1": 8, "n_blocks": 2, "tied_blocks": True, "fc_width": 32,
        "image_size": 32, "channels": 3, "num_classes": 10}
#: the source's own sizes (BaamPark/DistributedDataParallel-Cifar10,
#: model/resnet.py, main.py:27): no cell runs them yet (PERF.md section 7)
NETRESDEEP_PUBLISHED = dict(ARCH, n_chans1=32, n_blocks=10)
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-3,
          "grad_diff": 1e-3, "out_grad_diff": 1e-3}
#: the new per-layer metrics, each a count that the run record holds under
#: the name of its unit: one that every cell reports and one whose
#: ``workloads`` list names the new cell
READERS = {"window_steps": "steps", "window_examples": "examples"}
READER = '''NAME, UNIT, SOURCE = "{name}", "{unit}", "program_counter"
LAYER = "run loop"
MOVES = "images_per_s_per_chip"


def read(run):
    return run.record.get("{unit}")
'''


def entries(chips=1):
    """What the files of ``write_files`` add to a BENCHMARK.json, by list."""
    metric = {"better": "higher", "source": "program_counter",
              "layer": "run loop", "moves": "images_per_s_per_chip"}
    return {
        "configs": [{"name": "tiny-netresdeep", "source": "a test",
                     "file": "chipbench/configs/tiny-netresdeep.json",
                     "reduced": [], "why": "a test"}],
        "workloads": [{"name": CELL, "config": "tiny-netresdeep",
                       "traffic": "t8", "chips": chips, "why": "a test"}],
        "per_layer": [
            dict(metric, name="window_steps", unit="steps"),
            dict(metric, name="window_examples", unit="examples",
                 workloads=[CELL])]}


def write_files(root, *, compute_dtype="float32", limits=None,
                per_shard_batch=8, size=64, chips=1):
    """The cell's own files under ``root``, laid out as ``chipbench/`` is."""
    for sub in ("configs", "traffic", "limits", "layer_metrics"):
        os.makedirs(os.path.join(root, sub), exist_ok=True)
    config = {
        "name": "tiny-netresdeep", "source": "a test", "adapter": "trainer",
        "reference": "netresdeep", **ARCH,
        "precision": compute_dtype, "reduced": [],
        "train_config": {
            "model": "netresdeep", "n_chans1": ARCH["n_chans1"],
            "n_blocks": ARCH["n_blocks"], "tied_blocks": True,
            "compute_dtype": compute_dtype, "optimizer": "sgd", "lr": 0.01,
            "momentum": 0.0}}
    traffic = {
        "name": "t8", "chips": chips, "mesh": {"data": chips},
        "per_shard_batch": per_shard_batch, "steps_per_call": 1,
        "dataset": {"kind": "class_gaussians", "size": size,
                    "image_size": 32, "channels": 3, "num_classes": 10},
        "overlays": {}}
    files = {
        "configs/tiny-netresdeep.json": config, "traffic/t8.json": traffic,
        "limits/" + CELL + ".json": {"limits": limits or LIMITS}}
    for rel, obj in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    for name, unit in READERS.items():
        with open(os.path.join(root, "layer_metrics", name + ".py"),
                  "w") as f:
            f.write(READER.format(name=name, unit=unit))


def _dump(root, bench):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path, [root]


def write(root, *, chips=1, **files):
    """Returns (bench_path, roots). The cell is ``tiny-netresdeep.t8``."""
    write_files(root, chips=chips, **files)
    new = entries(chips)
    return _dump(root, {
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
        "run_seconds": 1,
        "configs": new["configs"], "workloads": new["workloads"],
        "end_to_end": [
            {"name": "images_per_s_per_chip", "unit": "images/s/chip",
             "better": "higher", "bound": 0.1, "source": "host_clock"},
            {"name": "step_ms_p95", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "dispatch_ms", "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "run loop",
             "moves": "images_per_s_per_chip"}] + new["per_layer"]})


def append(root, shipped_path, **files):
    """Returns (bench_path, roots): a copy of the benchmark file at
    ``shipped_path`` with this cell's entries at the end of ``configs``,
    ``workloads`` and ``per_layer``, its files under ``root``, and no shipped
    entry or file touched. ``roots`` goes in front of the shipped
    ``chipbench/`` (``harness.find``)."""
    write_files(root, **files)
    with open(shipped_path) as f:
        bench = json.load(f)
    for group, new in entries().items():
        bench[group] = bench[group] + new
    return _dump(root, bench)
