"""``block_flash_bwd_roofline``: the share of its roofline that the
one-kernel flash attention backward pass (``tpu_ddp.kernel.flash_bwd``)
reaches over a step's calls under the module scope ``attention_block``, the
block-diffusion visibility over ``[clean ‖ noisy]``: operations and bytes of
``flash_bwd_roofline.py``'s products and arrays on the visible pairs
(``chipbench/block_mask_costs.py``), over the kernel's device time in the
traced slice; the calls and the least time of one go on an earlier line.
None where the traced program makes no such call."""

from chipbench import block_mask_costs

NAME, UNIT, SOURCE = "block_flash_bwd_roofline", "%", "device_trace"
LAYER = "kernels"
MOVES = "images_per_s_per_chip"


def read(run):
    return block_mask_costs.roofline(run, "flash_bwd")
