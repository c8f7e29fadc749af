"""``flash_fwd_calls_per_bwd_call``: how often the flash attention forward
kernel runs for each backward pass of it, in one step: the calls of
``tpu_ddp.kernel.flash_fwd`` over those of ``tpu_ddp.kernel.flash_bwd`` and
``tpu_ddp.kernel.flash_dkv`` (a backward pass is the one kernel, or the
dK/dV kernel with its dQ kernel beside it: ``ops/flash_attention.py``),
every module scope together, counted from the traced slice and the
program's map (``kernel_costs.kernel_calls``; each kernel's roofline reader
says its count by scope on an earlier line). 2.0 is a recomputed layer that
runs the forward kernel in both passes, the second time only to make again
the output and the row statistics its backward kernel reads; 1.0 is a layer
that kept them, or one that is not recomputed. None where the traced program
runs no flash kernel forward or none backward (an image cell, an untraced
run)."""

from chipbench import kernel_costs

NAME, UNIT, SOURCE = "flash_fwd_calls_per_bwd_call", "ratio", "device_trace"
LAYER = "models"
MOVES = "images_per_s_per_chip"
FORWARD = ("flash_fwd",)
BACKWARD = ("flash_bwd", "flash_dkv")


def _calls(run, kernels) -> int:
    return sum(calls for kernel in kernels for calls, _ in (
        kernel_costs.kernel_calls(run, kernel) or {}).values())


def read(run):
    forward, backward = _calls(run, FORWARD), _calls(run, BACKWARD)
    if not forward or not backward:
        return None
    return forward / backward
