"""``scan_fwd_roofline``: the share of its roofline that the forward pass of
the chunked state-space scan (``tpu_ddp.kernel.ssd_scan_fwd``,
``tpu_ddp/ops/ssd_scan.py``) reaches over a step's calls: the larger of its
operations over the chip's bf16 peak and its bytes over the memory
bandwidth, both from shapes (``chipbench/ssd_costs.py``), over the device
time of the instructions under its scope in the traced slice. None where the
traced program has no such scope."""

from chipbench import ssd_costs

NAME, UNIT, SOURCE = "scan_fwd_roofline", "%", "device_trace"
LAYER = "kernels"
MOVES = "images_per_s_per_chip"


def read(run):
    return ssd_costs.scan_roofline(run, "ssd_scan_fwd")
