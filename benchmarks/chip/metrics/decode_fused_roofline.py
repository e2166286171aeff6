"""Roofline share of the ``decode_fused`` kernel: the least time its calls in
the traced window need, each counted from its shapes in the trace
(``kernels/decode_fused.py``), over their device time."""
from chipbench.kernels import decode_fused as kernel
from chipbench.work import kernel_share


def read(ctx):
    return kernel_share(kernel, ctx)
