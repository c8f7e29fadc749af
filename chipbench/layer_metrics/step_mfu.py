"""``step_mfu``: the share of the chip's bf16 peak that a step reaches while
it runs: FLOPs a step requires, from shapes (the configuration's own
``train_flops_per_example`` where its reference file gives one, otherwise
``chipbench/flops.py`` over the plain reference's forward pass, times three
for forward and backward) for the examples one chip trains per step, over
``device_step_ms`` times the peak of ``chipbench/peaks.json``. Slice, step
count and examples are the same ones ``device_step_ms`` and the idle share
are computed from."""

NAME, UNIT, SOURCE = "step_mfu", "%", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"


def read(run):
    rec = run.record
    if run.trace is None or not rec.get("peak_flops_per_s"):
        return None
    examples_per_chip_step = rec["examples"] / rec["steps"] / rec["chips"]
    flops = rec["train_flops_per_example"] * examples_per_chip_step
    seconds = run.trace["device_step_ms"] / 1e3
    return 100.0 * flops / (seconds * rec["peak_flops_per_s"])
