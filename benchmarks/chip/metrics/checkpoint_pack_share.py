"""Per cent of the traced window in which the chip sat idle while the host
was in the engine's ``checkpoint.pack`` span: the per-slot copy, crc32 and
meta record of each due slot in ``serving/cache.py::offload_slots``, as
``checkpoint_transfer_share`` counts it."""
from chipbench.metrics.checkpoint_transfer_share import idle_share

SPAN = "checkpoint.pack"


def read(ctx):
    return idle_share(ctx, SPAN)
