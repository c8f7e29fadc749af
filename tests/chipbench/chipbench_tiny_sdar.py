"""The ``sdar-30b-a3b`` configuration at a size a CPU test can hold, as a
cell of its own appended to a copy of the shipped BENCHMARK.json the way
``chipbench_tiny.append`` appends: the shipped configuration file at the
tiny decoder's sizes (``tests/sdar_tiny.py``: four query heads over two
key-value heads with query and key norms, three sparse layers, four of
sixteen softmax experts at four choices a token, block diffusion in blocks
of four with the mask on the last vocabulary row), ``"reference":
"sdar-30b-a3b"`` (the shipped reference file), the shipped ``zipf_tokens``
generator at 24 tokens over the ids under the mask's, float32."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import sdar_tiny as tiny  # noqa: E402

CELL = "sdar-tiny.t24"
LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-3, "update_gap": 0.02,
          "grad_diff": 1e-3, "out_grad_diff": 1e-3}
#: as ``test_chipbench_laguna.py::SECONDS``, and for its reason: 8-step
#: epochs, so a window of 20 dispatch intervals needs a step under 62.5 ms
SECONDS = 1.0


def share():
    return dict(held=tiny.HELD, offset=tiny.OFFSET)


def append(root, shipped_path):
    """Returns (bench_path, roots): a copy of the benchmark file at
    ``shipped_path`` with the tiny cell's entries at the end of ``configs``
    and ``workloads``, its files under ``root``."""
    config = tiny.arch(**share())
    config.update(name="sdar-tiny", reference="sdar-30b-a3b",
                  precision="float32", train_config={
                      "model": "tiny_sdar", "model_overrides": share(),
                      "compute_dtype": "float32", "optimizer": "adamw",
                      "lr": 1e-3, "weight_decay": 0.1, "remat": True,
                      "prefetch_depth": 0})
    with open(shipped_path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "sdar-tiny", "source": config["source"],
        "file": "chipbench/configs/sdar-tiny.json",
        "reduced": config["reduced"], "why": "a test"})
    bench["workloads"].append({
        "name": CELL, "config": "sdar-tiny", "traffic": "t24", "chips": 1,
        "why": "a test"})
    files = {
        "configs/sdar-tiny.json": config,
        "traffic/t24.json": {
            "name": "t24", "chips": 1, "mesh": {"data": 1},
            "per_shard_batch": 2, "steps_per_call": 1, "overlays": {},
            "dataset": {"kind": "zipf_tokens", "size": 16, "seq_len": tiny.T,
                        "vocab_size": tiny.VOCAB - 1, "exponent": 1.0,
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


def run(tmp_path, seed=2**31 + 41):
    from chipbench import run as harness

    bench, roots = append(str(tmp_path), os.path.join(
        os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json"))
    return harness.run_cell(CELL, seed, SECONDS, False, bench_path=bench,
                            roots=roots, device_check=False)


def failed(result):
    """The numbers of ``result["compared"]`` that are over their limits."""
    return [name for name, number in result["compared"].items()
            if name != "repeated_rows" and not (
                number["value"] <= number["limit"])]
