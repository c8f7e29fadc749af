"""``device_mla_ms``: device time per optimizer step of the operations the
program's map (``chipbench/scopes.py``) gives one of latent attention's
modules in the stack's layers: ``mla_q`` (both query projections and their
norm), ``mla_kv`` (the joint key-value projection, its norm, the expansion
and the rotary turns), ``attention_latent`` (the flash kernels' calls) and
``mla_out`` (scopes inside the model, ``tpu_ddp.module.<name>``,
``tpu_ddp/models/decoder.py::LatentAttention``), forward, recomputation and
backward together; each module goes on an earlier line. The prediction
module's layer is not in it: the outermost scope names the module, so that
layer's attention is ``device_mtp_ms``'s. None without a map of the traced
program, or where it names none of them (a program without these scopes)."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "device_mla_ms", "ms", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"
MODULES = ("mla_q", "mla_kv", "attention_latent", "mla_out")


def read(run):
    found = kernel_costs.modules_ms(run, MODULES)
    return None if found is None else sum(found.values())
