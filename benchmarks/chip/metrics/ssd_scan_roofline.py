"""Roofline share of the ``ssd_scan`` kernel: the least time its calls in
the traced window need, each counted from its shapes in the trace
(``kernels/ssd_scan.py``), over their device time."""
from chipbench.kernels import ssd_scan as kernel
from chipbench.work import kernel_share


def read(ctx):
    return kernel_share(kernel, ctx)
