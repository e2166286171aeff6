"""The serving launcher's entry function (``launch/serve.py::serve``, which
the CLI and ``chip_smoke.py`` both call), its compile-cache rule, and
``chip_smoke.py``'s kernel check run on a CPU through the interpreter."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced
from repro.core.registry import get
from repro.launch import serve as serve_mod
from repro.models.lm import init_lm_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-2.7b"])
def test_serve_finishes_every_request(arch):
    cfg = reduced(get(arch))
    params = init_lm_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 20, 11)]
    eng, seconds = serve_mod.serve(cfg, params, prompts, slots=2,
                                   max_seq=64, max_new=4)
    assert seconds > 0
    assert sorted(r.rid for r in eng.finished) == [0, 1, 2]
    for r in eng.finished:
        assert r.status == "ok" and len(r.out) == 4, (r.rid, r.status)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it; the code
    sets nothing); otherwise the cache is the fixed ``.jax_cache`` at the
    root of the checkout."""
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    serve_mod.use_compile_cache()
    if env_dir is None:
        assert updates == [("jax_compilation_cache_dir", serve_mod.CACHE_DIR)]
        assert serve_mod.CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    else:
        assert updates == []


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "zamba2-2.7b"])
def test_chip_smoke_kernel_check_on_cpu(monkeypatch, arch):
    """Phase (c) of ``chip_smoke.py`` at reduced width and length,
    interpreted kernels against ref: rows of different lengths, prefilled
    in several chunks, then decode steps, stay within its tolerance, and
    the two backends ran different programs (a replayed trace would agree
    exactly)."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(smoke, "CHECK_PROMPTS", (9, 40, 27, 17))
    monkeypatch.setattr(smoke, "CHUNK", 16)
    monkeypatch.setattr(smoke, "MAX_SEQ", 64)
    cfg = dataclasses.replace(reduced(get(arch)), compute_dtype="float32",
                              n_layers=smoke.CHECK_LAYERS[arch])
    params = init_lm_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    err, floor = smoke.logits_error(cfg, params, "interpret")
    assert err.shape == floor.shape == (1 + smoke.CHECK_STEPS, 4)
    assert 0 < err.max() <= smoke.LOGITS_TOL, err
    assert np.isfinite(floor).all()
