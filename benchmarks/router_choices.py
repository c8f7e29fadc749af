"""By hand, on the chip: how many (token, choice) pairs of a sparse decoder
cell's routers fall otherwise in the program than in the plain reference.

    python3 benchmarks/router_choices.py --workload laguna-xs2.seq8k --seeds 1,2

The router's logits are float32 on both sides, but its input is what the
layers below produced, in bfloat16 in the program and in float32 in the
reference, so near-ties among the 256 scores fall otherwise. For each seed:
the cell's seeded weights and first batch, one forward pass of the program's
model as the cell builds it (``build_model`` of the cell's ``TrainConfig``)
and one of the reference, and per sparse layer the share of a token's
choices that the other side did not make, and the share of those that name
an expert held here. No benchmark run calls this; PERF.md records what it
printed. A token whose set of experts differs trains other experts' weights:
it is why ``grad_diff`` of such a cell reads higher than a dense model's.
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
    import numpy as np

    from chipbench.reference import common
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
    held = range(arch.get("expert_offset", 0),
                 arch.get("expert_offset", 0) + arch["num_experts"])
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
        _, sown = jax.jit(lambda p, t: model.apply(
            {"params": p}, t, mutable=["intermediates", "counters"]))(
                tree, batch["tokens"])
        taps = []
        with jax.default_matmul_precision("highest"):
            jax.block_until_ready(ref.forward(
                arch, params, batch["tokens"], taps=taps))
        row = {"seed": seed, "layers": {}}
        for (name, layer), want in zip(
                sorted(sown["intermediates"].items()), taps):
            got = np.asarray(layer["moe"]["expert_ids"][0])
            want = np.asarray(want).reshape(got.shape)
            missing = ~(got[:, :, None] == want[:, None, :]).any(axis=-1)
            row["layers"][name] = {
                "choices_that_differ": float(missing.mean()),
                "of_them_on_held_experts": float(
                    np.isin(got[missing], held).mean()) if missing.any()
                else 0.0}
        print("router choices:", json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
