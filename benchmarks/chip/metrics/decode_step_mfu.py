"""Whole decode-burst program (``decode_tokens``: ``decode_block`` steps
for every slot in one scan) against the chip's peaks: the least time the
tokens it decoded need (the engine's ``repro_tokens_total{phase="decode"}``
over the traced window; the larger of FLOPs at peak and bytes at HBM
bandwidth, ``work.decode_work``) over the device time of its runs in the
trace, in per cent."""
from chipbench.trace import device_seconds, runs
from chipbench.work import decode_work, roofline_share

PROGRAM = r"decode_n"


def read(ctx):
    s = ctx["summary"]
    tokens = ctx["counters"]["decode_tokens"]
    if not tokens:
        return None
    steps = sum(runs(s, PROGRAM, key="modules").values()) * \
        ctx["engine"]["decode_block"]
    return roofline_share([decode_work(ctx["model"], tokens, steps)],
                          device_seconds(s, PROGRAM, key="modules"),
                          ctx["peak"])
