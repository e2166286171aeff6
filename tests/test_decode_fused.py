"""Fused decode path: (1) ``decode_tokens`` must emit tokens identical to n
sequential ``lm_decode_step`` calls on every arch family, on both the ref
and interpret (Pallas) backends; (2) the fused decode-step kernels must
match their jnp oracle numerically."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import AttnConfig, ModelConfig, SSMConfig
from repro.kernels import dispatch
from repro.kernels.decode_fused.kernel import (mamba1_decode_fused_pallas,
                                               mamba2_decode_fused_pallas)
from repro.kernels.decode_fused.ref import (conv1d_decode_ref,
                                            mamba1_decode_fused_ref,
                                            mamba2_decode_fused_ref)
from repro.models import (decode_tokens, init_lm_cache, init_lm_params,
                          lm_decode_step, lm_prefill)

KEY = jax.random.PRNGKey(0)


def _cfgs():
    return [
        ModelConfig(name="attn", family="dense", n_layers=3, d_model=64,
                    d_ff=128, vocab_size=97,
                    attn=AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16),
                    layer_pattern=("dense",), vocab_pad_multiple=16),
        ModelConfig(name="mamba2", family="ssm", n_layers=3, d_model=64,
                    d_ff=0, vocab_size=97,
                    ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
                    layer_pattern=("mamba2",), vocab_pad_multiple=16),
        ModelConfig(name="mamba1", family="ssm", n_layers=2, d_model=64,
                    d_ff=0, vocab_size=97,
                    ssm=SSMConfig(d_state=8, variant="mamba1"),
                    layer_pattern=("mamba1",), vocab_pad_multiple=16),
        ModelConfig(name="hybrid", family="hybrid", n_layers=4, d_model=64,
                    d_ff=0, vocab_size=97,
                    ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
                    layer_pattern=("mamba2", "mamba2+shared"),
                    shared_attn=AttnConfig(n_heads=4, n_kv_heads=4,
                                           head_dim=16),
                    shared_attn_d_ff=128, vocab_pad_multiple=16),
    ]


@pytest.mark.parametrize("backend", [
    "ref",
    # interpret sweep: hybrid exercises every fused kernel in one config;
    # the per-family interpret runs are the slow sweep (scripts/verify.sh)
    pytest.param("interpret", marks=pytest.mark.slow),
])
@pytest.mark.parametrize("cfg", _cfgs(), ids=lambda c: c.name)
def test_decode_tokens_matches_sequential(cfg, backend):
    """The fused lax.scan loop must reproduce the per-token python loop
    exactly (same backend => identical op sequence => identical tokens)."""
    batch, plen, n = 2, 8, 6
    params = init_lm_params(cfg, KEY)
    prompt = jax.random.randint(KEY, (batch, plen), 0, cfg.vocab_size,
                                jnp.int32)
    with dispatch.use_backend(backend):
        cache = init_lm_cache(cfg, batch, 32)
        lg, cache = jax.jit(lambda p, t, c: lm_prefill(
            cfg, p, {"tokens": t}, c))(params, prompt, cache)
        first = jnp.argmax(lg[..., :cfg.vocab_size], -1).astype(jnp.int32)

        seq_cache, tok, seq_toks = cache, first, []
        step = jax.jit(lambda p, t, c: lm_decode_step(cfg, p, t, c))
        for _ in range(n):
            lg1, seq_cache = step(params, tok, seq_cache)
            tok = jnp.argmax(lg1[..., :cfg.vocab_size], -1).astype(jnp.int32)
            seq_toks.append(np.asarray(tok[:, 0]))
        seq_toks = np.stack(seq_toks, axis=1)

        fused, fused_cache = jax.jit(
            lambda p, c, f: decode_tokens(cfg, p, c, f, n))(
                params, cache, first)
    np.testing.assert_array_equal(np.asarray(fused), seq_toks)
    np.testing.assert_array_equal(np.asarray(fused_cache["pos"]),
                                  np.asarray(seq_cache["pos"]))
    # states must agree too (bitwise on ref; kernels only reorder float ops)
    for a, b in zip(jax.tree_util.tree_leaves(fused_cache["segments"]),
                    jax.tree_util.tree_leaves(seq_cache["segments"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-5)


def test_decode_tokens_interpret_smoke():
    """Thin tier-1 interpret-parity smoke: the hybrid config alone touches
    every fused decode kernel (conv shift, SSM update, shared attention)."""
    test_decode_tokens_matches_sequential(_cfgs()[3], "interpret")


def test_decode_tokens_sampling_reproducible():
    cfg = _cfgs()[0]
    params = init_lm_params(cfg, KEY)
    prompt = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size, jnp.int32)
    cache = init_lm_cache(cfg, 2, 32)
    lg, cache = lm_prefill(cfg, params, {"tokens": prompt}, cache)
    first = jnp.argmax(lg[..., :cfg.vocab_size], -1).astype(jnp.int32)
    t1, _ = decode_tokens(cfg, params, cache, first, 8, temperature=0.8,
                          rng=jax.random.PRNGKey(7))
    t2, _ = decode_tokens(cfg, params, cache, first, 8, temperature=0.8,
                          rng=jax.random.PRNGKey(7))
    t3, _ = decode_tokens(cfg, params, cache, first, 8, temperature=0.8,
                          rng=jax.random.PRNGKey(8))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert (np.asarray(t1) < cfg.vocab_size).all()
    # a different key must actually change the sampled stream
    assert not np.array_equal(np.asarray(t1), np.asarray(t3))


# ------------------------------------------------------------ fused kernels

@pytest.mark.parametrize("b,h,p,g,n,k", [(2, 4, 16, 2, 16, 4),
                                         (1, 8, 8, 1, 32, 4),
                                         (3, 4, 32, 4, 8, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba2_decode_fused_kernel(b, h, p, g, n, k, dtype):
    di = h * p
    c = di + 2 * g * n
    ks = jax.random.split(KEY, 9)
    conv = jax.random.normal(ks[0], (b, k - 1, c), dtype)
    ssm = jax.random.normal(ks[1], (b, h, p, n), jnp.float32)
    xbc = jax.random.normal(ks[2], (b, c), dtype)
    w = jax.random.normal(ks[3], (c, k))
    bias = jax.random.normal(ks[4], (c,))
    dt_raw = jax.random.normal(ks[5], (b, h), dtype)
    dtb = jax.random.normal(ks[6], (h,))
    al = jax.random.normal(ks[7], (h,))
    D = jax.random.normal(ks[8], (h,))
    ref = mamba2_decode_fused_ref(conv, ssm, xbc, w, bias, dt_raw, dtb, al, D,
                                  n_groups=g, d_state=n, headdim=p)
    ker = mamba2_decode_fused_pallas(conv, ssm, xbc, w, bias, dt_raw, dtb,
                                     al, D, n_groups=g, d_state=n, headdim=p,
                                     interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for r, got, nm in zip(ref, ker, ["y", "conv", "ssm"]):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=tol, atol=tol, err_msg=nm)


@pytest.mark.parametrize("b,di,n,dtr,k,cancels", [
    # in float32 this case's y = sum_n h*C + D*x adds terms up to ~1e4 that
    # cancel to values near 1, and the kernel adds its float32 products in
    # another order than the oracle's batched einsums: y agrees to 1e-5 of
    # the terms' magnitudes (measured: at most 6% of that bound), not of
    # their cancelled sum (one element of 64 misses that by 3.8e-5)
    pytest.param(2, 32, 8, 6, 4, True, id="2-32-8-6-4"),
    pytest.param(1, 64, 16, 4, 2, False, id="1-64-16-4-2")])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba1_decode_fused_kernel(b, di, n, dtr, k, cancels, dtype):
    ks = jax.random.split(KEY, 10)
    conv = jax.random.normal(ks[0], (b, k - 1, di), dtype)
    ssm = jax.random.normal(ks[1], (b, di, n), jnp.float32)
    xi = jax.random.normal(ks[2], (b, di), dtype)
    w = jax.random.normal(ks[3], (di, k))
    bias = jax.random.normal(ks[4], (di,))
    xp = jax.random.normal(ks[5], (di, dtr + 2 * n), dtype)
    dtp = jax.random.normal(ks[6], (dtr, di), dtype)
    dtb = jax.random.normal(ks[7], (di,))
    al = jax.random.normal(ks[8], (di, n))
    D = jax.random.normal(ks[9], (di,))
    ref = mamba1_decode_fused_ref(conv, ssm, xi, w, bias, xp, dtp, dtb, al, D,
                                  d_state=n, dt_rank=dtr)
    ker = mamba1_decode_fused_pallas(conv, ssm, xi, w, bias, xp, dtp, dtb,
                                     al, D, d_state=n, dt_rank=dtr,
                                     interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    for r, got, nm in zip(ref, ker, ["y", "conv", "ssm"]):
        r, got = np.asarray(r, np.float32), np.asarray(got, np.float32)
        if nm == "y" and cancels and dtype == jnp.float32:
            xc, _ = conv1d_decode_ref(conv, xi, w, bias)
            cm = (xc @ xp)[..., dtr + n:]
            terms = jnp.einsum("bdn,bn->bd", jnp.abs(ref[2]), jnp.abs(cm)) \
                + jnp.abs(xc * D)
            np.testing.assert_array_less(
                np.abs(got - r), tol * (np.asarray(terms) + np.abs(r)),
                err_msg=nm)
        else:
            np.testing.assert_allclose(got, r, rtol=tol, atol=tol,
                                       err_msg=nm)
