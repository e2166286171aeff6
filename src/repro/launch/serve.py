"""Serving launcher: slot-based continuous batching through ServingEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b \\
      --requests 8 --slots 4 --max-new 16

serves a reduced (CPU-smoke) config; ``--full-size`` serves the published
widths with weights in the config's compute dtype
(:func:`serving_param_dtype`).  Compiled
programs persist in JAX's compilation cache: the directory
``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads it itself),
otherwise ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced
from repro.core.config import ModelConfig
from repro.core.registry import get, list_archs
from repro.models.lm import init_lm_params
from repro.serving.engine import Request, ServingEngine

CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def serving_param_dtype(cfg: ModelConfig, full_size: bool) -> jnp.dtype:
    """Weight dtype to serve ``cfg`` in.  Serving never updates weights and
    every layer computes in ``cfg.compute_dtype``, so published widths hold
    their weights in that dtype: ``cfg.param_dtype`` (the training master
    copy, float32) would only add the compute-dtype copy XLA hoists out of
    the layer scan.  For mamba2-2.7b the float32 weights (10.8 GB) and that
    copy do not fit a 16 GB TPU v5e beside the cache (AOT compile for v5e:
    11.5 GB of arguments + 5.1 GB temp for one prefill chunk).  Reduced
    CPU configs keep ``cfg.param_dtype``."""
    return jnp.dtype(cfg.compute_dtype if full_size else cfg.param_dtype)


def use_compile_cache() -> None:
    """Keep compiled programs across processes.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it; otherwise the
    cache lives at one fixed path in the checkout, so the next run finds
    it again."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)


def serve(cfg: ModelConfig, params, prompts: Sequence[np.ndarray], *,
          slots: int, max_seq: int, max_new: int
          ) -> Tuple[ServingEngine, float]:
    """Submit one request per prompt and drive ``ServingEngine.run()`` to
    completion.  Returns the engine (its ``finished`` requests and
    ``stats``) and the wall seconds of the run."""
    eng = ServingEngine(cfg, params, slots=slots, max_seq=max_seq)
    for i, prompt in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=prompt, max_new=max_new))
    t0 = time.perf_counter()
    eng.run()
    return eng, time.perf_counter() - t0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--full-size", action="store_true")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get(args.arch)
    if not args.full_size:
        cfg = reduced(cfg)
    if cfg.family in ("encoder", "audio"):
        raise SystemExit(f"{args.arch} is encoder-only: no decode serving")
    dtype = serving_param_dtype(cfg, args.full_size)
    print(f"{cfg.name}: d_model {cfg.d_model}, {cfg.n_layers} layers, "
          f"{dtype.name} params")
    params = init_lm_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size,
                            args.prompt_len).astype(np.int32)
               for _ in range(args.requests)]
    eng, dt = serve(cfg, params, prompts, slots=args.slots,
                    max_seq=args.max_seq, max_new=args.max_new)
    tokens = sum(len(r.out) for r in eng.finished)
    print(f"served {len(eng.finished)} requests / {tokens} tokens in "
          f"{dt:.2f}s ({tokens / dt:.1f} tok/s aggregate)")


if __name__ == "__main__":
    main()
