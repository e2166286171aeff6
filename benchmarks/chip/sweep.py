#!/usr/bin/env python3
"""The highest rate an open-loop cell's engine sustains, swept once on the
chip to fix the mix's ``rate_per_s``.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --scales 0.25 0.5 1 ...

One set-up, then for each scale of the mix's rate a window of
``--seconds`` and its drain.  Per rate, one JSON line: the offered rate,
requests sent and still open at the window's close, TTFT percentiles of
the first and the last third of the arrivals, and the share of the
window's time the engine had a backlog waiting for a slot.  A rate is
sustained where the last third waits no longer than the first and few
requests are left open at the close; a growing backlog shows as the
reverse.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--scales", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: needs a TPU")
    from repro.launch.serve import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = run.read_json("..", "..", "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf = run.read_json("configs", conf["file"].rsplit("/", 1)[1])
    mix = run.read_json("traffic", f"{cell['traffic']}.json")
    if mix["kind"] != "open":
        raise SystemExit("sweep: an open-loop mix has a rate to sweep")
    _, eng, registry, _ = run.set_up(conf, mix, args.seed)
    gen_mod = run.module("loadgen/open.py")
    tokens = registry.counter("repro_tokens_total").labels(phase="prefill")
    for scale in args.scales:
        gen = gen_mod.Generator(mix, args.seed, args.seconds,
                                conf["model"]["vocab_size"],
                                rate_scale=scale)
        win = run.Window(eng, gen, None, time.perf_counter)
        backlog = []
        tok0 = tokens.value
        win.run(args.seconds,
                lambda now: backlog.append(len(eng.queue) > 0))
        tok1 = tokens.value
        e2e = run.end_to_end(win, tok0, tok1)
        left = win.open
        win.drain(120.0)
        recs = sorted(win.reqs.values(), key=lambda r: r["arrival"])
        third = max(1, len(recs) // 3)

        def ttft(rs, q):
            return run.percentile([(r["first"] - r["arrival"]) * 1e3
                                   for r in rs if r["first"]], q)
        print(json.dumps({
            "rate_per_s": mix["rate_per_s"] * scale, "sent": len(recs),
            "open_at_close": left,
            "ttft_p50_ms_first_third": ttft(recs[:third], 50),
            "ttft_p50_ms_last_third": ttft(recs[-third:], 50),
            "ttft_p90_ms": ttft(recs, 90),
            "itl_p95_ms": e2e["itl_p95_ms"],
            "tokens_per_s": e2e["tokens_per_s"],
            "backlog_share": float(np.mean(backlog)) if backlog else 0.0,
            "failed": sum(r["req"].status != "ok" for r in recs)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
