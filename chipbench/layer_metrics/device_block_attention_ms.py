"""``device_block_attention_ms``: device time per optimizer step of the
operations the program's map gives the module ``attention_block`` (the
attention proper of a block-diffusion layer,
``tpu_ddp/models/decoder.py::GroupedQueryAttention``: the flash kernels
under the block mask and what feeds them, without the projections),
forward, recomputation and backward together. None without a map that names
it."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "device_block_attention_ms", "ms", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"
MODULES = ("attention_block",)


def read(run):
    found = kernel_costs.modules_ms(run, MODULES)
    return None if found is None else sum(found.values())
