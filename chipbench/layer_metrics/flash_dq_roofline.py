"""``flash_dq_roofline``: the share of its roofline that the flash attention
dQ kernel (``tpu_ddp.kernel.flash_dq``, ``ops/flash_attention.py``)
reaches over a step's calls, every module scope they are found under
together (``attention_window``, ``attention_full``, ``attention_latent``, a
prediction module's ``mtp``; each on an earlier line): the larger of its
operations over the chip's bf16 peak and its bytes over the memory
bandwidth, both from shapes at the key width and the value width of that
scope's layers in the cell's configuration (``chipbench/kernel_costs.py``),
over the kernel's device time in the traced slice. None where the traced
program calls no such kernel, or calls it under a scope the cell's files do
not describe."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "flash_dq_roofline", "%", "device_trace"
LAYER = "kernels"
MOVES = "images_per_s_per_chip"


def read(run):
    return kernel_costs.flash_roofline(run, "flash_dq")
