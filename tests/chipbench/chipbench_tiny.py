"""A throw-away benchmark at a size a CPU test can hold, built in a temporary
directory from nothing but new files: its own BENCHMARK.json, configuration,
traffic mix, limits and one per-layer metric. The shipped harness runs it
without a shipped file being touched, which is how a later PR adds a cell."""

import json
import os

ARCH = {"n_chans1": 8, "n_blocks": 2, "tied_blocks": True, "fc_width": 32,
        "image_size": 32, "channels": 3, "num_classes": 10}
#: the source's own sizes (BaamPark/DistributedDataParallel-Cifar10,
#: model/resnet.py, main.py:27): no cell runs them yet (PERF.md section 7)
NETRESDEEP_PUBLISHED = dict(ARCH, n_chans1=32, n_blocks=10)


def write(root, *, compute_dtype="float32", limits=None, per_shard_batch=8,
          size=64, chips=1):
    """Returns (bench_path, roots). The cell is ``tiny-netresdeep.t8``."""
    for sub in ("configs", "traffic", "limits", "layer_metrics"):
        os.makedirs(os.path.join(root, sub), exist_ok=True)
    config = {
        "name": "tiny-netresdeep", "adapter": "trainer",
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
    limits = {"limits": limits or {
        "loss_gap": 1e-4, "grad_gap": 1e-3, "update_gap": 1e-3,
        "grad_diff": 1e-3, "out_grad_diff": 1e-3}}
    bench = {
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny-netresdeep", "source": "a test",
                     "file": "configs/tiny-netresdeep.json", "reduced": [],
                     "why": "a test"}],
        "workloads": [{"name": "tiny-netresdeep.t8",
                       "config": "tiny-netresdeep", "traffic": "t8",
                       "chips": chips, "why": "a test"}],
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
             "moves": "images_per_s_per_chip"},
            {"name": "window_steps", "unit": "steps", "better": "higher",
             "source": "program_counter", "layer": "run loop",
             "moves": "images_per_s_per_chip"}]}
    files = {
        "configs/tiny-netresdeep.json": config, "traffic/t8.json": traffic,
        "limits/tiny-netresdeep.t8.json": limits, "BENCHMARK.json": bench}
    for rel, obj in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(root, "layer_metrics", "window_steps.py"),
              "w") as f:
        f.write('NAME, UNIT, SOURCE = "window_steps", "steps", '
                '"program_counter"\nLAYER = "run loop"\n'
                'MOVES = "images_per_s_per_chip"\n\n\n'
                'def read(run):\n    return run.record["steps"]\n')
    return os.path.join(root, "BENCHMARK.json"), [root]
