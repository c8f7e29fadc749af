"""``device_attention_ms``: device time per optimizer step of the operations
the program's map gives the modules ``attention_window``, ``attention_full``
and ``attention_latent`` (the attention proper of a sliding, of a full and
of a latent-attention layer: the flash kernels and what feeds them, without
the projections), forward, recomputation and backward together; each goes on
an earlier line. A layer inside another module is that module's (a
prediction module's attention is ``device_mtp_ms``'s). None without a map
that names them."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "device_attention_ms", "ms", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"
MODULES = ("attention_window", "attention_full", "attention_latent")


def read(run):
    found = kernel_costs.modules_ms(run, MODULES)
    return None if found is None else sum(found.values())
