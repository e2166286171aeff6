#!/usr/bin/env python3
"""Chip benchmark: one cell (configuration x traffic mix) of BENCHMARK.json
served through ``ServingEngine`` on the TPU this process holds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``configs/<config>.json``, its mix in
``traffic/<traffic>.json`` (its ``kind`` names the generator in
``loadgen/<kind>.py``), each per-layer metric's reader in
``metrics/<metric>.py``, the limits of the correctness check in
``limits/<cell>.json`` and the chip's peaks in ``peaks.json``.

Set-up makes the weights on the chip from the seed in one jitted call,
builds the engine with its defaults, and runs every program the window
can reach once, through ``submit``/``step``.  The window then drives
``submit``/``step`` for ``--seconds`` from the mix's generator; after it
closes, the requests sent in it are served to their end and the
end-to-end metrics are taken over them.  ``--trace 1`` profiles a steady
stretch of the window with host spans around the engine's calls
(``spans.json``) and prints the per-layer metrics instead of the
end-to-end ones.  Last, the
plain reference checks a sample of the served tokens (``correct``).

The last line of standard output is the result as one JSON object; the
last lines of standard error are the numbers compared, beside their
limits.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(ROOT, ".chipbench")
if "chipbench" not in sys.modules:      # this directory as a package
    _pkg = types.ModuleType("chipbench")
    _pkg.__path__ = [HERE]
    sys.modules["chipbench"] = _pkg
sys.path.insert(0, os.path.join(ROOT, "src"))

WINDOW_RID = 1_000_000      # request ids of the window; warm-up uses below


def read_json(*parts) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def module(rel: str):
    """A file of this benchmark, by its path under this directory."""
    return importlib.import_module(
        "chipbench." + rel[:-3].replace("/", "."))


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ----------------------------------------------------------- statistics
def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile of all ``values``, interpolated linearly between
    the two closest ranks (numpy's default)."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def seed_key(seed: int):
    """A PRNG key from every bit of a seed of up to 64 bits."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


# ------------------------------------------------------------- program
def program_config(conf: Dict[str, Any]):
    """The program's ModelConfig for ``conf``: its registry entry with
    every field that the file's ``model`` section names set from it (a
    nested group onto the entry's own), so the file states what runs."""
    import dataclasses
    import repro.configs  # noqa: F401  (registers the architectures)
    from repro.core.registry import get
    m = conf["model"]
    cfg = get(conf["arch"])
    kw = {}
    for f in dataclasses.fields(cfg):
        if f.name not in m:
            continue
        v = m[f.name]
        if isinstance(v, dict):
            v = dataclasses.replace(getattr(cfg, f.name), **v)
        elif isinstance(v, list):
            v = tuple(v)
        kw[f.name] = v
    return dataclasses.replace(cfg, **kw)


class Spans:
    """Host spans (``jax.profiler.TraceAnnotation``) around the engine's
    calls that ``spans.json`` names, only while ``on``.  A call that the
    engine does not have is left out, and its span with it: the spans only
    attribute the chip's idle gaps in the breakdown."""

    def __init__(self, eng, names: Dict[str, str]):
        import jax
        self.on = False
        self._ann = jax.profiler.TraceAnnotation
        for span, attr in names.items():
            owner, _, name = attr.rpartition(".")
            obj = eng
            for part in owner.split(".") if owner else []:
                obj = getattr(obj, part, None)
            fn = getattr(obj, name, None)
            if not callable(fn):
                log(f"span {span}: the engine has no {attr}; left out")
                continue
            setattr(obj, name, self._wrap(span, fn))

    def _wrap(self, span, fn):
        def wrapped(*a, **k):
            if not self.on:
                return fn(*a, **k)
            with self._ann(span):
                return fn(*a, **k)
        return wrapped

    def span(self, name):
        return self._ann(name) if self.on else contextlib.nullcontext()


def _quiet_profiler():
    """Device and host spans only: Python's own calls are not traced."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def warm_up(eng, mix, vocab: int) -> None:
    """Run every program the window can reach once, through ``submit`` and
    ``step`` alone, and no other.

    The engine prefills a group of one row or of ``slots`` rows, each a
    program of its own, and decodes all slots in one burst; an engine that
    keeps keys and values has one of each per KV bucket that a request
    climbs.  So short requests go in as a group of ``slots`` and as a group
    of one, and, where the engine has KV buckets, the mix's longest prompt
    with its longest output the same way.  Where arrivals can starve the
    queue (an open loop), one request more than ``slots`` forces a
    preemption and its restore."""
    from repro.serving.engine import Request
    p, o = mix["prompt"], mix["output"]
    lo, hi = p.get("lo", p.get("fixed")), p.get("hi", p.get("fixed"))
    top = o.get("hi", o.get("fixed"))
    short, new = min(lo, eng.chunk_size), eng.decode_block + 1
    rounds = [([short] * eng.slots, new), ([short], new)]
    if eng.kv_buckets:
        rounds += [([hi] * eng.slots, top), ([hi], top)]
    if mix["kind"] == "open":
        rounds.append(([short] * (eng.slots + 1), 6 * eng.decode_block))
    g = np.random.default_rng(0)
    rid = 0
    for lens, max_new in rounds:
        for n in lens:
            eng.submit(Request(rid=rid, prompt=g.integers(
                0, vocab, n).astype(np.int32), max_new=max_new))
            rid += 1
        while eng.step() or eng.queue:
            pass


class Window:
    """Drives the engine from a generator and keeps, per request, its
    arrival, first token and every host delivery of tokens."""

    def __init__(self, eng, gen, spans: Optional[Spans], clock):
        self.eng, self.gen, self.spans, self.clock = eng, gen, spans, clock
        self.reqs: Dict[int, Dict[str, Any]] = {}
        self.pending = sorted(gen.start(), key=lambda r: r[0])
        self.next_rid = WINDOW_RID
        self.open = 0

    def _span(self, name):
        return self.spans.span(name) if self.spans else \
            contextlib.nullcontext()

    def _submit_due(self, now_rel: float) -> None:
        from repro.serving.engine import Request
        with self._span("client.submit"):
            while self.pending and self.pending[0][0] <= now_rel:
                due, client, prompt, max_new = self.pending.pop(0)
                req = Request(rid=self.next_rid, prompt=prompt,
                              max_new=max_new)
                self.next_rid += 1
                self.eng.submit(req)
                self.reqs[req.rid] = {
                    "req": req, "client": client,
                    "arrival": self.t0 + due, "sent": self.clock(),
                    "first": None, "done": None, "deliveries": []}
                self.open += 1

    def _after_step(self, t: float, send: bool) -> None:
        for rec in self.reqs.values():
            if rec["done"] is not None:
                continue
            req = rec["req"]
            have = sum(n for _, n in rec["deliveries"])
            if len(req.out) > have:
                rec["deliveries"].append((t, len(req.out) - have))
                if rec["first"] is None:
                    rec["first"] = t
            if req.done:
                rec["done"] = t
                self.open -= 1
                if send:
                    self.pending += self.gen.done(rec["client"],
                                                  t - self.t0)
                    self.pending.sort(key=lambda r: r[0])

    def run(self, seconds: float, on_tick=None) -> None:
        self.t0 = self.clock()
        while True:
            now = self.clock() - self.t0
            if now >= seconds:
                break
            if on_tick:
                on_tick(now)
            self._submit_due(now)
            if self.open:
                self.eng.step()
                self._after_step(self.clock(), send=True)
            else:
                nxt = self.pending[0][0] if self.pending else seconds
                with self._span("client.wait"):
                    time.sleep(max(0.0, min(nxt, seconds)
                                   - (self.clock() - self.t0)))
        self.t1 = self.clock()
        # requests due inside the window that a step held back are sent
        # now; their wait counts from when they were due
        self._submit_due(seconds - 1e-9)

    def drain(self, limit_s: float) -> None:
        """Serve what the window sent to its end; send nothing new."""
        end = self.clock() + limit_s
        while self.open and self.clock() < end:
            self.eng.step()
            self._after_step(self.clock(), send=False)


def end_to_end(win: Window, tok0: float, tok1: float) -> Dict[str, Any]:
    """Every end-to-end statistic, once the window's requests have been
    served: time to first token over every request the window sent, its
    first token inside the window or after it; the gaps and tokens of
    every delivery inside the window."""
    w = win.t1 - win.t0
    ttft, itl, out_tokens = [], [], 0
    for rec in win.reqs.values():
        if rec["first"] is not None:
            ttft.append((rec["first"] - rec["arrival"]) * 1e3)
        prev = None
        for t, n in rec["deliveries"]:
            if t <= win.t1:
                out_tokens += n
                if prev is not None:
                    itl.append((t - prev) * 1e3 / n)
            prev = t
    return {"ttft_p50_ms": percentile(ttft, 50),
            "itl_p95_ms": percentile(itl, 95),
            "tokens_per_s": (tok1 - tok0 + out_tokens) / w,
            "samples": {"ttft": len(ttft), "itl": len(itl)},
            "window_s": w}


def correctness(w, model, mix, win: Window, seed: int, limits, ref_mod,
                control: bool = False) -> Dict[str, Dict[str, float]]:
    """The reference's gaps over a sample of the requests the window sent
    and the engine finished: the longest, then others drawn from the seed
    until the sample holds the mix's ``check`` count of requests and of
    served tokens, both."""
    ok = [r["req"] for r in win.reqs.values()
          if r["req"].status == "ok" and len(r["req"].out) > 0]
    failed = sum(1 for r in win.reqs.values() if r["req"].status != "ok")
    checks = {"requests_failed": {"value": failed,
                                  "limit": limits["requests_failed"]}}
    if not ok:
        checks["max_logit_gap"] = {"value": float("inf"),
                                   "limit": limits["max_logit_gap"]}
        return checks
    ok.sort(key=lambda r: (len(r.prompt) + len(r.out), r.rid))
    rest = list(np.random.default_rng(seed).permutation(len(ok) - 1))
    pick = [ok[-1]]
    want = mix["check"]
    while rest and (len(pick) < want.get("requests", 0) or sum(
            len(r.out) for r in pick) < want.get("tokens", 0)):
        pick.append(ok[rest.pop()])
    R = ref_mod.Reference(model, block=want["ref_block"])
    gap = ctl = 0.0
    for r in pick:
        got, alt = R.gaps(w, np.asarray(r.prompt), np.asarray(r.out),
                          control=control)
        gap = max(gap, float(np.max(got)))
        if control:
            ctl = max(ctl, float(np.max(alt)))
    checks["max_logit_gap"] = {"value": gap,
                               "limit": limits["max_logit_gap"]}
    if control:
        checks["control_max_logit_gap"] = {"value": ctl,
                                           "limit": limits["max_logit_gap"]}
    log(f"compared: {len(pick)} requests, "
        f"{sum(len(r.out) for r in pick)} served tokens")
    return checks


def set_up(conf, mix, seed: int):
    """The weights, made on the chip from the seed in one jitted call in
    the dtype the engine serves, and the engine, built with its defaults
    and warmed up.  Returns (weights, engine, its metrics registry, the
    reference module)."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import serving_param_dtype
    from repro.models.lm import init_lm_params
    from repro.serving.engine import ServingEngine
    from repro.serving.metrics import MetricsRegistry
    t0 = time.perf_counter()
    model = conf["model"]
    cfg = program_config(conf)
    dtype = serving_param_dtype(cfg, full_size=True)
    if jnp.dtype(model["param_dtype"]) != dtype:
        raise SystemExit(f"{conf['name']}: the engine serves {dtype}, the "
                         f"file states {model['param_dtype']}")
    ref_mod = module(conf["reference"])
    w = jax.jit(functools.partial(ref_mod.init_weights, model,
                                  dtype=dtype))(seed_key(seed))
    want = jax.eval_shape(lambda: init_lm_params(cfg, jax.random.PRNGKey(0),
                                                 dtype=dtype))
    if (jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(w)
            or [a.shape for a in jax.tree_util.tree_leaves(want)]
            != [a.shape for a in jax.tree_util.tree_leaves(w)]):
        raise SystemExit(f"{conf['name']}: the reference's weight layout "
                         "is not the program's")
    jax.block_until_ready(w)
    t1 = time.perf_counter()
    registry = MetricsRegistry()
    eng = ServingEngine(cfg, w, slots=mix["engine"]["slots"],
                        max_seq=mix["engine"]["max_seq"], metrics=registry)
    t2 = time.perf_counter()
    warm_up(eng, mix, model["vocab_size"])
    log(f"set-up: weights {t1 - t0:.3f} s, engine {t2 - t1:.3f} s, "
        f"warm-up {time.perf_counter() - t2:.3f} s")
    return w, eng, registry, ref_mod


def run_cell(bench, cell, conf, mix, *, seed: int, seconds: float,
             trace: bool, limits, per_layer: List[Dict[str, Any]],
             t_start: float = None, control: bool = False) -> Dict[str, Any]:
    import jax

    t_start = T_START if t_start is None else t_start
    compiles: Dict[str, int] = {}
    counting = [False]

    def on_event(event: str, *_, **__):
        if counting[0] and "compile" in event:
            compiles[event] = compiles.get(event, 0) + 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    jax.monitoring.register_event_listener(on_event)

    log(f"start: {time.perf_counter() - t_start:.3f} s to set-up")
    w, eng, registry, ref_mod = set_up(conf, mix, seed)
    model = conf["model"]
    gen_mod = module(f"loadgen/{mix['kind']}.py")
    spans = None
    if trace:
        names = read_json("spans.json")
        spans = Spans(eng, names["engine"])
    tokens = registry.counter("repro_tokens_total")
    prefill, decode = tokens.labels(phase="prefill"), tokens.labels(
        phase="decode")

    def counters():
        """The engine's own counts: what the per-layer metrics divide."""
        return {"ckpt_ms": eng.stats["ckpt_ms"], "prefill_tokens":
                prefill.value, "decode_tokens": decode.value}
    gen = gen_mod.Generator(mix, seed, seconds, model["vocab_size"])
    win = Window(eng, gen, spans, time.perf_counter)
    setup_s = time.perf_counter() - t_start

    # --trace 1: the window ends with a traced stretch of a few steady
    # seconds (stopping the profiler takes seconds of host time, so
    # nothing is measured after it)
    tlo = 0.4 * seconds
    run_s = tlo + min(6.0, 0.3 * seconds) if trace else seconds
    tdir = os.path.join(OUT, "trace")
    traced = {}

    def on_tick(now):
        if trace and not traced and now >= tlo:
            shutil.rmtree(tdir, ignore_errors=True)
            jax.profiler.start_trace(tdir, profiler_options=_quiet_profiler())
            spans.on = True
            traced["window"] = jax.profiler.TraceAnnotation("window")
            traced["window"].__enter__()
            traced["c0"] = counters()

    tok0 = prefill.value
    counting[0] = True
    win.run(run_s, on_tick)
    counting[0] = False
    tok1 = prefill.value
    if traced:
        traced["window"].__exit__(None, None, None)
        spans.on = False
        c1 = counters()
        traced["counts"] = {k: c1[k] - traced["c0"][k] for k in c1}
        jax.profiler.stop_trace()
    log(f"compiles_in_window: {sum(compiles.values())} {json.dumps(compiles)}")
    win.drain(120.0)
    e2e = end_to_end(win, tok0, tok1)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    lag = [rec["sent"] - rec["arrival"] for rec in win.reqs.values()]
    log(f"window: {e2e['window_s']:.3f} s, {len(win.reqs)} requests, "
        f"samples {json.dumps(e2e['samples'])}, generator lag p50/max "
        f"{percentile(lag, 50):.4f}/{max(lag):.4f} s, setup {setup_s:.3f} s")

    result_metrics: Dict[str, Any] = {}
    breakdown = None
    if trace:
        trace_mod = module("trace.py")
        names = read_json("spans.json")
        span_names = list(names["engine"]) + names["harness"]
        summary = trace_mod.reduce(trace_mod.load(tdir), span_names)
        shutil.rmtree(tdir, ignore_errors=True)
        peaks = read_json("peaks.json")
        if dev.device_kind not in peaks:
            raise SystemExit(f"no peaks for device kind {dev.device_kind!r}")
        ctx = {"summary": summary, "model": model,
               "peak": peaks[dev.device_kind], "counters": traced["counts"],
               "engine": {"chunk": eng.chunk_size,
                          "decode_block": eng.decode_block}}
        for m in per_layer:
            v = module(f"metrics/{m['name']}.py").read(ctx)
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = trace_mod.breakdown(summary)
        log(f"trace: {json.dumps(breakdown)}")
    else:
        for m in bench["end_to_end"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            if v is not None:
                result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    attempted = len(win.reqs)
    failed = sum(1 for r in win.reqs.values() if r["req"].status != "ok")
    # the reference runs on what the program leaves: only the weights
    del eng, win.eng, spans
    gc.collect()
    t_ref = time.perf_counter()
    checks = correctness(w, model, mix, win, seed, limits, ref_mod,
                         control)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    for k, v in checks.items():
        log(f"{k}: {v['value']} limit {v['limit']}")
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": result_metrics, "device": device,
           "compiles_in_window": sum(compiles.values())}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    bench = read_json("..", "..", "BENCHMARK.json")
    cell = next((c for c in bench["workloads"] if c["name"] == args.workload),
                None)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        raise SystemExit(f"{args.workload} needs {cell['chips']} TPU chip(s); "
                         f"JAX found {len(devs)} {devs[0].platform} device(s)")
    from repro.launch.serve import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf = read_json(os.path.relpath(os.path.join(ROOT, conf["file"]), HERE))
    mix = read_json("traffic", f"{cell['traffic']}.json")
    limits = read_json("limits", f"{cell['name']}.json")
    per_layer = [m for m in bench["per_layer"]
                 if cell["name"] in m.get("workloads", [cell["name"]])]
    out = run_cell(bench, cell, conf, mix, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   limits={k: v["limit"] for k, v in limits.items()
                           if isinstance(v, dict)},
                   per_layer=per_layer)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
