"""Whole prefill-chunk program (``lm_prefill_chunk``) against the chip's
peaks: the least time the prompt tokens it prefilled need (the engine's
``repro_tokens_total{phase="prefill"}`` over the traced window; the larger
of FLOPs at peak and bytes at HBM bandwidth, ``work.prefill_work``) over
the device time of its runs in the trace, in per cent."""
from chipbench.trace import device_seconds, runs
from chipbench.work import prefill_work, roofline_share

PROGRAM = r"chunk_step"


def read(ctx):
    s = ctx["summary"]
    tokens = ctx["counters"]["prefill_tokens"]
    if not tokens:
        return None
    work = prefill_work(ctx["model"], tokens,
                        sum(runs(s, PROGRAM, key="modules").values()),
                        ctx["engine"]["chunk"])
    return roofline_share([work], device_seconds(s, PROGRAM, key="modules"),
                          ctx["peak"])
