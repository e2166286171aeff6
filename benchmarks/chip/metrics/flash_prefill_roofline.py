"""Roofline share of the flash prefill kernel: the least time its calls in
the traced window need, each counted at its least live prefix from its
shapes and KV rows in the trace (``kernels/flash_prefill.py``), over their
device time."""
from chipbench.kernels import flash_prefill as kernel
from chipbench.work import kernel_share


def read(ctx):
    return kernel_share(kernel, ctx)
