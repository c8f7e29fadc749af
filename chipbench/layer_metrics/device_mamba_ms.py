"""``device_mamba_ms``: device time per optimizer step of the operations the
program's map (``chipbench/scopes.py``) gives one of the Mamba-2 mixer's
modules, ``mamba_in``, ``mamba_conv``, ``ssm_scan``, ``mamba_norm`` and
``mamba_out`` (scopes inside the model, ``tpu_ddp.module.<name>``,
``tpu_ddp/models/hybrid.py``), forward, recomputation and backward together;
each module goes on an earlier line. None without a map of the traced
program, or where it names none of them (a program without these scopes)."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "device_mamba_ms", "ms", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"
MODULES = ("mamba_in", "mamba_conv", "ssm_scan", "mamba_norm", "mamba_out")


def read(run):
    found = kernel_costs.modules_ms(run, MODULES)
    return None if found is None else sum(found.values())
