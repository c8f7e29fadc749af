"""``device_latent_moe_ms``: device time per optimizer step of the
operations the program's map (``chipbench/scopes.py``) gives one of the
latent expert block's modules: ``moe_route``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``, ``moe_shared`` and ``moe_latent`` (both
latent projections; scopes inside the model, ``tpu_ddp.module.<name>``),
forward, recomputation and backward together; each module goes on an earlier
line. What the shipped ``device_moe_ms`` reads and the latent projections
beside it: that metric lists its own cells. None without a map of the traced
program, or where it names no ``moe_latent`` (a program whose experts have
no latent space)."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "device_latent_moe_ms", "ms", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"
MODULES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine",
           "moe_shared", "moe_latent")


def read(run):
    found = kernel_costs.modules_ms(run, MODULES)
    if found is None or "moe_latent" not in found:
        return None
    return sum(found.values())
