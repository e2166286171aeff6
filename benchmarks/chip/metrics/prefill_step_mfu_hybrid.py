"""Whole prefill-chunk program (``lm_prefill_chunk``) of a parallel-hybrid
model against the chip's peaks: the least time the prompt tokens it
prefilled need (the engine's ``repro_tokens_total{phase="prefill"}`` over
the traced window; the larger of FLOPs at peak and bytes at HBM bandwidth,
``work_hybrid.prefill_work``, attention at the least live prefix of the
traced flash calls) over the device time of its runs in the trace, in per
cent."""
from chipbench.kernels import flash_prefill
from chipbench.trace import device_seconds, result_shapes, runs
from chipbench.work import roofline_share
from chipbench.work_hybrid import attention_pairs, prefill_work

PROGRAM = r"chunk_step"


def read(ctx):
    s, model = ctx["summary"], ctx["model"]
    tokens = ctx["counters"]["prefill_tokens"]
    calls = [(n, shape[0], shape[2], flash_prefill.kv_rows(key))
             for key, n in runs(s, flash_prefill.PATTERN).items()
             for shape in [result_shapes(key)[0]]]
    if not tokens or not calls:
        return None
    pairs = attention_pairs(calls, tokens, model["n_layers"])
    work = prefill_work(model, tokens,
                        sum(runs(s, PROGRAM, key="modules").values()),
                        ctx["engine"]["chunk"], pairs)
    return roofline_share([work], device_seconds(s, PROGRAM, key="modules"),
                          ctx["peak"])
