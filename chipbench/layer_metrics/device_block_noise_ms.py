"""``device_block_noise_ms``: device time per optimizer step of the
operations the program's map gives the module ``block_noise`` (what a
block-diffusion task does to its batch inside the step,
``tpu_ddp/train/tasks.py::block_noise``: the draw of the levels and of the
masked positions, and the concatenation of the clean sequence and its noised
copy). None without a map that names it."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "device_block_noise_ms", "ms", "device_trace"
LAYER = "step builders"
MOVES = "images_per_s_per_chip"
MODULES = ("block_noise",)


def read(run):
    found = kernel_costs.modules_ms(run, MODULES)
    return None if found is None else sum(found.values())
