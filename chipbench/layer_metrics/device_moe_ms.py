"""``device_moe_ms``: device time per optimizer step of the operations the
program's map (``chipbench/scopes.py``) gives one of a routed layer's
modules, ``moe_route``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``,
``moe_shared`` and, where the experts work in a latent space, ``moe_latent``
(both latent projections; scopes inside the model,
``tpu_ddp.module.<name>``), forward, recomputation and backward together;
each module goes on an earlier line. A routed layer inside another module
is that module's: the outermost scope names the module, so a prediction
module's routed layer is ``device_mtp_ms``'s. None without a map of the
traced program, or where it names none of them (a program without these
scopes)."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "device_moe_ms", "ms", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"
MODULES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
           "moe_shared", "moe_latent")


def read(run):
    found = kernel_costs.modules_ms(run, MODULES)
    return None if found is None else sum(found.values())
