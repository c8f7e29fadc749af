"""``mla_flash_fwd_roofline``: the share of its roofline that the flash
attention forward kernel (``tpu_ddp.kernel.flash_fwd``,
``ops/flash_attention.py``) reaches over a step's calls in latent
attention, keys of 192 over values of 128, the stack's layers and the
prediction module's together: the larger of its operations over the chip's
bf16 peak and its bytes over the memory bandwidth, both from shapes with
the products of each width counted apart (``chipbench/mla_costs.py``),
over the kernel's device time in the traced slice. The lanes the kernel
pads 192 to are its waste, not work. None where the traced program calls no
such kernel or the cell's configuration has no latent attention."""

from chipbench import mla_costs

NAME, UNIT, SOURCE = "mla_flash_fwd_roofline", "%", "device_trace"
LAYER = "kernels"
MOVES = "images_per_s_per_chip"


def read(run):
    return mla_costs.flash_roofline(run, "flash_fwd")
