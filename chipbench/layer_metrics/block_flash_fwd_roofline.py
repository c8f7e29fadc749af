"""``block_flash_fwd_roofline``: the share of its roofline that the flash
attention forward kernel (``tpu_ddp.kernel.flash_fwd``) reaches over a
step's calls under the module scope ``attention_block``, the block-diffusion
visibility over ``[clean ‖ noisy]``: the larger of its operations over the
chip's bf16 peak and its bytes over the memory bandwidth, from shapes on the
visible pairs (``chipbench/block_mask_costs.py``), over the kernel's device
time in the traced slice; the calls and the least time of one go on an
earlier line. None where the traced program makes no such call."""

from chipbench import block_mask_costs

NAME, UNIT, SOURCE = "block_flash_fwd_roofline", "%", "device_trace"
LAYER = "kernels"
MOVES = "images_per_s_per_chip"


def read(run):
    return block_mask_costs.roofline(run, "flash_fwd")
