"""The ``phi4-mini-flash`` configuration at a size a CPU test can hold, as a
cell of its own appended to a copy of the shipped BENCHMARK.json the way
``chipbench_tiny.append`` appends: the shipped configuration file at the
tiny decoder-hybrid-decoder's sizes (``tests/sambay_tiny.py``; eight
layers, so one of each kind the cross-decoder has),
``"reference": "phi4-mini-flash"`` (the shipped reference file), the shipped
``zipf_tokens`` generator at 28 tokens, float32."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import sambay_tiny as tiny  # noqa: E402
from chipbench_tiny_hybrid import failed  # noqa: E402,F401

CELL = "phi4-tiny.t28"
LAYERS = 8
LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-3, "update_gap": 0.02,
          "grad_diff": 1e-3, "out_grad_diff": 1e-3}
#: as ``test_chipbench_laguna.py::SECONDS``, and for its reason: 8-step
#: epochs, so a window of 20 dispatch intervals needs a step under 62.5 ms
SECONDS = 1.0


def append(root, shipped_path):
    """Returns (bench_path, roots): a copy of the benchmark file at
    ``shipped_path`` with the tiny cell's entries at the end of ``configs``
    and ``workloads``, its files under ``root``."""
    config = tiny.arch(layers=LAYERS)
    config.update(name="phi4-tiny", reference="phi4-mini-flash",
                  precision="float32", train_config={
                      "model": "tiny_sambay", "model_overrides": {"layers": LAYERS},
                      "compute_dtype": "float32", "optimizer": "adamw",
                      "lr": 1e-3, "weight_decay": 0.1, "remat": True,
                      "prefetch_depth": 0})
    with open(shipped_path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "phi4-tiny", "source": config["source"],
        "file": "chipbench/configs/phi4-tiny.json",
        "reduced": config["reduced"], "why": "a test"})
    bench["workloads"].append({
        "name": CELL, "config": "phi4-tiny", "traffic": "t28", "chips": 1,
        "why": "a test"})
    files = {
        "configs/phi4-tiny.json": config,
        "traffic/t28.json": {
            "name": "t28", "chips": 1, "mesh": {"data": 1},
            "per_shard_batch": 2, "steps_per_call": 1, "overlays": {},
            "dataset": {"kind": "zipf_tokens", "size": 16, "seq_len": tiny.T,
                        "vocab_size": tiny.VOCAB, "exponent": 1.0,
                        "example_holds": {"tokens": tiny.T}}},
        "limits/" + CELL + ".json": {"cell": CELL, "limits": LIMITS},
        "BENCHMARK.json": bench,
    }
    for name, content in files.items():
        path = os.path.join(root, name)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(content, f)
    return os.path.join(root, "BENCHMARK.json"), [root]


def run(tmp_path, seed=2**31 + 45):
    from chipbench import run as harness

    bench, roots = append(str(tmp_path), os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json"))
    return harness.run_cell(CELL, seed, SECONDS, False, bench_path=bench,
                            roots=roots, device_check=False)

