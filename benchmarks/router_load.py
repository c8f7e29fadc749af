"""By hand, on the chip: what each layer's router lands on the experts held
here in the first step of a sparse decoder cell, and the rung it walks.

    python3 benchmarks/router_load.py --workload <cell> --seeds 1,2

For each seed: the cell's seeded weights and first batch, made ready as the
step makes it ready (a task that prepares its batch draws its noise with the
key of that seed, step 0, shard 0), one forward pass of the program's model
as the cell builds it (``build_model`` of the cell's ``TrainConfig``), and
per layer what ``DroplessMoE`` sowed: the (position, choice) pairs each held
expert got, the pairs that landed in all, the fullest expert over the mean,
and the rows of the rung taken. One compilation for all seeds. A block
diffusion cell feeds its first layer some thousands of identical rows, the
mask token's, which all go where the seeded router sends that one
embedding: this prints how many of those experts are held. No benchmark run
calls this; PERF.md records what it printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import datagen  # noqa: E402
from chipbench import run as harness  # noqa: E402


def main(argv=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import common
    from tpu_ddp.train.tasks import task_of
    from tpu_ddp.train.trainer import TrainConfig, build_model

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    args = parser.parse_args(argv)
    bench = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    loaded = harness.load_cell(bench, args.workload, [harness.HERE])
    arch, traffic, ref = (loaded["config"], loaded["traffic"],
                          loaded["reference"])
    model = build_model(TrainConfig(**arch["train_config"]))
    task = task_of(model)
    device = jax.devices()[0]
    print("router load:", json.dumps({
        "device": device.platform, "kind": device.device_kind,
        "task": task.name}), flush=True)

    @jax.jit
    def first_forward(tree, batch, seed):
        if task.prepare is not None:
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.key(seed), 0), 0)
            batch = task.prepare(jax.random.fold_in(key, 2), batch)
        _, sown = model.apply({"params": tree}, batch[task.input_key],
                              mutable=["counters"])
        return sown["counters"]

    for seed in (datagen.fold_seed(int(s)) for s in args.seeds.split(",")):
        data = loaded["dataset"].make(traffic["dataset"], seed)
        batch = common.task(ref).batches(
            data, rows=int(traffic["per_shard_batch"]), steps=1)[0]
        params = ref.init_params(arch, seed)
        tree = {}
        for leaf, path in ref.program_names(arch).items():
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = params[leaf]
        counters = jax.device_get(first_forward(
            tree, {k: jnp.asarray(v) for k, v in batch.items()},
            jnp.uint32(seed)))
        row = {"seed": seed, "layers": {}}
        if "block_masked_tokens" in counters:
            row["block_masked_tokens"] = int(
                np.sum(counters["block_masked_tokens"]))
        for name, layer in sorted(counters.items()):
            if not isinstance(layer, dict) or "moe" not in layer:
                continue
            load = np.asarray(layer["moe"]["expert_load"][0])
            row["layers"][name] = {
                "landed": int(load.sum()),
                "max_over_mean": float(load.max() / max(load.mean(), 1e-9)),
                "rows_walked": int(np.asarray(
                    layer["moe"]["expert_rows_walked"][0])),
                "load": load.tolist()}
        print("router load:", json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
