"""``device_mtp_ms``: device time per optimizer step of the operations the
program's map (``chipbench/scopes.py``) gives the multi-token-prediction
module, ``mtp`` (a scope inside the model, ``tpu_ddp.module.mtp``,
``tpu_ddp/models/decoder.py::SparseDecoder``): ``W_eh``, its two norms, its
one layer whole (that layer's own scopes nest inside, and the outermost
names the module), its use of the final norm and, block by block inside the
loss, of the head; forward, recomputation and backward together. The
softmax of its loss term lies under ``tpu_ddp.loss`` like the first term's.
None without a map of the traced program, or where it names no ``mtp`` (a
program without a prediction module)."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "device_mtp_ms", "ms", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"
MODULES = ("mtp",)


def read(run):
    found = kernel_costs.modules_ms(run, MODULES)
    return None if found is None else sum(found.values())
