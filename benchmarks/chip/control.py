#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness check, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...]

For each seed, in this one process: the cell's set-up and a window of
``--seconds`` at the cell's own load, then, over the same sample of served
requests, the widest gap by which a served token's float32 reference logit
lies below the reference's best (the program's reading), and the same gap
of the token that the float8 reference puts first (the control's
reading).  One JSON line per seed.  The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("control: needs a TPU")
    from repro.launch.serve import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = run.read_json("..", "..", "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf = run.read_json("configs", conf["file"].rsplit("/", 1)[1])
    mix = run.read_json("traffic", f"{cell['traffic']}.json")
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = run.run_cell(bench, cell, conf, mix, seed=seed,
                           seconds=args.seconds, trace=False,
                           limits={"max_logit_gap": float("inf"),
                                   "requests_failed": 0},
                           per_layer=[], t_start=t0, control=True)
        print(json.dumps({
            "seed": seed, "attempted": out["attempted"],
            "failed": out["failed"],
            "program_gap": out["checks"]["max_logit_gap"]["value"],
            "control_gap": out["checks"]["control_max_logit_gap"]["value"],
            "metrics": out["metrics"], "device": out["device"],
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
