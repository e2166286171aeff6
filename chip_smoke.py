#!/usr/bin/env python3
"""Chip smoke: serve mamba2-2.7b and zamba2-2.7b at published widths on one
TPU, through the serving entry point, and check the kernels against the
reference on the chip.

    python chip_smoke.py

Every phase runs in this one process (a chip belongs to one process):

  (a) mamba2-2.7b at published widths and full depth (64 layers), weights
      in the compute dtype (``launch/serve.py::serving_param_dtype``,
      bfloat16) drawn from a fixed seed, served by ``ServingEngine.run()``:
      4 slots, max_seq 4096, 8 requests of 320-2000 prompt tokens, 32 new
      tokens each.
  (b) the same for zamba2-2.7b (54 layers, shared attention block every
      6th layer), which adds flash prefill and split-K decode attention.
  (c) per model, at published widths with the depth cut: logits of 4 rows
      of different prompt lengths, prefilled together in the engine's
      256-token chunks at max_seq 4096, then 4 teacher-forced decode steps,
      on the ``pallas`` backend against the ``ref`` backend.

Each phase prints one JSON line (device kind, param dtype, compile and wall
seconds, tokens, and the device's peak bytes in use so far: a high-water
mark over the process, not per phase).  The last line is exactly
``{"ok": true, "device": {...}}``.  Without a TPU, or when any check fails,
the script exits non-zero and prints no ok line.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCHS = ("mamba2-2.7b", "zamba2-2.7b")
SEED = 0
SLOTS, MAX_SEQ, MAX_NEW = 4, 4096, 32
PROMPT_LENS = (320, 2000, 640, 1280, 448, 1536, 896, 1800)
# (c) runs the engine's prefill shapes: SLOTS rows prefilled as one group in
# CHUNK-token chunks (ServingEngine's chunk at max_seq 4096).  Row 0 is the
# shortest, so once it ends the rows' cache offsets differ, and each decode
# step reads a different valid length per row: a kernel that takes a row's
# offset, state or KV length from another row is off in the rows it
# misreads.
CHUNK = 256
CHECK_PROMPTS = (300, 1100, 777, 520)
CHECK_STEPS = 4
# Depth is cut to one Zamba2 unit (5 Mamba-2 layers, then the shared
# attention block) and 4 Mamba-2 layers: every layer runs the same kernels
# at the same widths, and random-weight models amplify float32 rounding
# with depth (CPU, interpret vs ref at d_model 256: 5e-7 at 1 layer, 2.4e-4
# at 4, 6.2e-4 at 8 by the 4th decode step).
CHECK_LAYERS = {"mamba2-2.7b": 4, "zamba2-2.7b": 6}
# pallas and ref differ only in the order they add float32 partial sums, so
# their gap is the model's sensitivity to rounding: (c) also reports ref
# against ref with every weight scaled by (1 + ROUNDING * N(0, 1)), the
# floor no kernel can beat.  On a CPU at d_model 256 that floor and the
# interpreted kernels' error are both 2-7e-4 by the 4th decode step;
# LOGITS_TOL leaves ~8x room above them.  Faults planted in the kernels, in
# the same CPU check, are off by more: 2.0e-2 to 4.8e-2 for a row's flash
# q_offset or decode KV length read from row 0 (one attention block in
# six), 0.39 to 0.72 for row 0's SSM state read by every row or head 0's A
# used for every head.
ROUNDING = 1e-7
LOGITS_TOL = 5e-3

_compile_s = 0.0


def _count_compile(event: str, duration_secs: float, **_) -> None:
    global _compile_s
    if event.startswith("/jax/core/compile/"):
        _compile_s += duration_secs


def _peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def _params(cfg, dtype):
    from repro.models.lm import init_lm_params
    return init_lm_params(cfg, jax.random.PRNGKey(SEED), dtype=dtype)


def _report(phase: str, arch: str, dtype, t0: float, c0: float,
            **extra) -> None:
    print(json.dumps({
        "phase": phase, "arch": arch,
        "device_kind": jax.devices()[0].device_kind,
        "param_dtype": jnp.dtype(dtype).name,
        "compile_s": round(_compile_s - c0, 3),
        "wall_s": round(time.perf_counter() - t0, 3),
        "peak_bytes_in_use": _peak_bytes(), **extra}), flush=True)


def serve_phase(phase: str, arch: str) -> None:
    """Serve PROMPT_LENS through the engine; every request must end ok with
    all MAX_NEW tokens and no sentinel trip."""
    from repro.core.registry import get
    from repro.kernels import dispatch
    from repro.launch.serve import serve, serving_param_dtype
    cfg = get(arch)
    dtype = serving_param_dtype(cfg, full_size=True)
    t0, c0 = time.perf_counter(), _compile_s
    params = _params(cfg, dtype)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS]
    eng, run_s = serve(cfg, params, prompts, slots=SLOTS, max_seq=MAX_SEQ,
                       max_new=MAX_NEW)
    done = eng.finished
    bad = [(r.rid, r.status, len(r.out), str(r.error)) for r in done
           if r.status != "ok" or len(r.out) != MAX_NEW]
    if len(done) != len(prompts) or bad or eng.stats["divergences"]:
        raise SystemExit(f"{phase} {arch}: {len(done)}/{len(prompts)} "
                         f"finished, failures {bad}, sentinel trips "
                         f"{eng.stats['divergences']}")
    _report(phase, arch, dtype, t0, c0,
            backend=dispatch.get_backend(),
            layers=cfg.n_layers, d_model=cfg.d_model, requests=len(done),
            prompt_tokens=int(sum(PROMPT_LENS)),
            tokens=int(sum(len(r.out) for r in done)),
            run_s=round(run_s, 3), prefill_chunks=eng.stats["prefill_chunks"],
            decode_tokens=eng.stats["decode_tokens"])


def _logits(cfg, params, prompts, steps, backend: str) -> np.ndarray:
    """Prefill ``prompts`` as one group in CHUNK-token chunks (the engine's
    ``chunked_prefill`` path: KV buckets, per-row valid lengths), then
    teacher-force ``steps`` [CHECK_STEPS, rows] decode tokens.  Returns
    [1 + CHECK_STEPS, rows, vocab] float32 logits: each row's last prompt
    token, then one per step.  Fresh jitted programs per backend: the
    backend is read at trace time."""
    from repro.kernels import dispatch
    from repro.models.lm import init_lm_cache, lm_decode_step
    from repro.serving.prefill import chunked_prefill
    lens = [len(p) for p in prompts]
    toks = np.zeros((len(prompts), max(lens)), np.int32)
    for row, p in enumerate(prompts):
        toks[row, :len(p)] = p
    with dispatch.use_backend(backend), \
            jax.default_matmul_precision("highest"):
        step = jax.jit(lambda p, t, c: lm_decode_step(cfg, p, t, c))
        cache = init_lm_cache(cfg, len(prompts), MAX_SEQ)
        lg, cache = chunked_prefill(cfg, params, toks, cache,
                                    chunk_size=CHUNK, lengths=lens)
        out = [lg[:, -1]]
        for tok in steps:
            lg, cache = step(params, jnp.asarray(tok, jnp.int32)[:, None],
                             cache)
            out.append(lg[:, -1])
    return np.asarray(jnp.stack(out)[..., :cfg.vocab_size], np.float32)


def _perturbed(params):
    """Every weight times (1 + ROUNDING * N(0, 1)), from a fixed seed."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    key = jax.random.PRNGKey(SEED)
    return jax.tree_util.tree_unflatten(tree, [
        x * (1 + ROUNDING * jax.random.normal(jax.random.fold_in(key, i),
                                              x.shape, x.dtype))
        for i, x in enumerate(leaves)])


def logits_error(cfg, params, backend: str):
    """max |logits - ref| / max |ref| over the vocabulary, per step (the
    prefill, then each decode step) and row, [1 + CHECK_STEPS, rows]: of
    ``backend`` against ``ref``, and of ``ref`` on rounding-perturbed
    weights against ``ref`` (the noise floor)."""
    rng = np.random.default_rng(SEED + 1)
    prompts = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
               for n in CHECK_PROMPTS]
    steps = rng.integers(2, cfg.vocab_size,
                         (CHECK_STEPS, len(CHECK_PROMPTS)))
    want = _logits(cfg, params, prompts, steps, "ref")

    def rel(got):
        if not np.isfinite(got).all():
            return np.full(got.shape[:2], np.inf)
        return np.abs(got - want).max(axis=-1) / np.abs(want).max(axis=-1)
    return (rel(_logits(cfg, params, prompts, steps, backend)),
            rel(_logits(cfg, _perturbed(params), prompts, steps, "ref")))


def check_phase(arch: str) -> None:
    """pallas against ref in float32 (weights and activations)."""
    from repro.core.registry import get
    cfg = dataclasses.replace(get(arch), compute_dtype="float32",
                              n_layers=CHECK_LAYERS[arch])
    t0, c0 = time.perf_counter(), _compile_s
    params = _params(cfg, jnp.float32)
    err, floor = (e.max(axis=1) for e in logits_error(cfg, params, "pallas"))
    if not err.max() <= LOGITS_TOL:
        raise SystemExit(f"(c) {arch}: pallas vs ref logits error per step "
                         f"{err.tolist()} exceeds {LOGITS_TOL}")
    _report("c", arch, jnp.float32, t0, c0, layers=cfg.n_layers,
            prompt_tokens=list(CHECK_PROMPTS), chunk=CHUNK, max_seq=MAX_SEQ,
            decode_steps=CHECK_STEPS,
            rel_err=[float(f"{e:.4g}") for e in err],
            rounding_floor=[float(f"{e:.4g}") for e in floor],
            tol=LOGITS_TOL)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    from repro.kernels import dispatch
    from repro.launch.serve import use_compile_cache
    if dispatch.get_backend() != "pallas":
        raise SystemExit(f"chip_smoke: kernel backend is "
                         f"{dispatch.get_backend()!r}, not 'pallas'")
    use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    import repro.configs  # noqa: F401  (registers the model configs)

    for phase, arch in zip("ab", ARCHS):
        serve_phase(phase, arch)
        gc.collect()             # release the phase's params and caches
    for arch in ARCHS:
        check_phase(arch)
        gc.collect()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
