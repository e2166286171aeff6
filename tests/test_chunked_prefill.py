"""Chunk-parity property: state-carrying chunked prefill must match the
one-shot ``lm_prefill`` — logits, cache positions, and the decode
continuation — for every architecture family, across chunk sizes
(including ragged last chunks), on the ref and Pallas-interpret backends,
and for heterogeneous prompt lengths in one padded batch.

Rolling sliding-window ("local") architectures go through the ring-buffer
chunk path: their parity sweep covers window == chunk, window < chunk
(wrap inside one chunk) and window > chunk, always with prompts longer
than the window so the ring cursor wraps.  Those configs pin
``compute_dtype=float32``: the ring and one-shot paths reduce in
different orders, and fp32 makes the bit-exact decode-continuation gate
deterministic instead of hostage to bf16 argmax near-ties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import AttnConfig, ModelConfig, SSMConfig
from repro.kernels import dispatch
from repro.models.lm import (decode_tokens, init_lm_cache, init_lm_params,
                             lm_prefill, lm_prefill_chunk)
from repro.serving.prefill import (_jitted_chunk_step, chunked_prefill,
                                   supports_chunked_prefill)

KEY = jax.random.PRNGKey(0)


def _cfgs():
    return {
        "dense": ModelConfig(
            name="dense", family="dense", n_layers=3, d_model=64, d_ff=128,
            vocab_size=97,
            attn=AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16),
            layer_pattern=("dense",), vocab_pad_multiple=16),
        "mamba2": ModelConfig(
            name="mamba2", family="ssm", n_layers=3, d_model=64, d_ff=0,
            vocab_size=97, ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
            layer_pattern=("mamba2",), vocab_pad_multiple=16),
        "mamba1": ModelConfig(
            name="mamba1", family="ssm", n_layers=2, d_model=64, d_ff=0,
            vocab_size=97, ssm=SSMConfig(d_state=8, variant="mamba1"),
            layer_pattern=("mamba1",), vocab_pad_multiple=16),
        "hybrid": ModelConfig(
            name="hybrid", family="hybrid", n_layers=4, d_model=64, d_ff=0,
            vocab_size=97, ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
            layer_pattern=("mamba2", "mamba2+shared"),
            shared_attn=AttnConfig(n_heads=4, n_kv_heads=4, head_dim=16),
            shared_attn_d_ff=128, vocab_pad_multiple=16),
        "hybrid_par": ModelConfig(
            name="hybrid_par", family="hybrid", n_layers=2, d_model=64,
            d_ff=128, vocab_size=97,
            attn=AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16),
            ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
            layer_pattern=("hybrid_par",), vocab_pad_multiple=16),
        # rolling sliding-window configs (ring-buffer chunked prefill);
        # fp32 compute — see module docstring
        "local": ModelConfig(
            name="local", family="dense", n_layers=2, d_model=64, d_ff=128,
            vocab_size=97, compute_dtype="float32",
            attn=AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16,
                            sliding_window=8),
            layer_pattern=("local", "dense"), vocab_pad_multiple=16),
        "local_pure": ModelConfig(
            name="local_pure", family="dense", n_layers=2, d_model=64,
            d_ff=128, vocab_size=97, compute_dtype="float32",
            attn=AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16,
                            sliding_window=8),
            layer_pattern=("local",), vocab_pad_multiple=16),
        "local_hybrid": ModelConfig(
            name="local_hybrid", family="hybrid", n_layers=2, d_model=64,
            d_ff=128, vocab_size=97, compute_dtype="float32",
            attn=AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16,
                            sliding_window=8),
            ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
            layer_pattern=("local", "mamba2"), vocab_pad_multiple=16),
    }


def _run_chunked(cfg, params, toks, max_seq, chunk):
    cache = init_lm_cache(cfg, toks.shape[0], max_seq)
    return chunked_prefill(cfg, params, toks, cache, chunk_size=chunk)


@pytest.mark.parametrize("arch", [
    "dense", "mamba2", "hybrid",                       # tier-1 smoke
    pytest.param("mamba1", marks=pytest.mark.slow),
    pytest.param("hybrid_par", marks=pytest.mark.slow),
])
@pytest.mark.parametrize("chunk", [
    7,                                                 # ragged — tier-1 smoke
    pytest.param(8, marks=pytest.mark.slow),           # even chunking
    pytest.param(21, marks=pytest.mark.slow),          # one-shot-sized
])
def test_chunk_parity(arch, chunk):
    """Chunked == one-shot: logits, pos, and an 8-token greedy
    continuation, for even and ragged chunkings (21 = one-shot-sized)."""
    cfg = _cfgs()[arch]
    assert supports_chunked_prefill(cfg)
    params = init_lm_params(cfg, KEY)
    B, L, MS = 2, 21, 40
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0,
                              cfg.vocab_size, jnp.int32)
    ref_logits, ref_cache = lm_prefill(cfg, params, {"tokens": toks},
                                       init_lm_cache(cfg, B, MS))
    logits, cache = _run_chunked(cfg, params, toks, MS, chunk)
    # bf16 logits: tolerance must sit above bf16 ULP (2^-8) — a few-ULP
    # drift from reduction-order changes is expected; the bit-exact greedy
    # continuation below is the strong parity gate
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                  np.asarray(ref_cache["pos"]))
    first = jnp.argmax(ref_logits[..., :cfg.vocab_size], -1).astype(jnp.int32)
    t_ref, _ = decode_tokens(cfg, params, ref_cache, first, 8)
    t_chk, _ = decode_tokens(cfg, params, cache, first, 8)
    np.testing.assert_array_equal(np.asarray(t_chk), np.asarray(t_ref))


@pytest.mark.parametrize("arch,chunk", [
    ("local", 8),                                      # chunk == window
    ("local", 16),                                     # chunk > window: the
                                                       # ring wraps INSIDE one
                                                       # chunk
    ("local_pure", 5),                                 # chunk < window, ragged
    pytest.param("local", 5, marks=pytest.mark.slow),
    pytest.param("local_pure", 8, marks=pytest.mark.slow),
    pytest.param("local_pure", 16, marks=pytest.mark.slow),
    pytest.param("local_hybrid", 8, marks=pytest.mark.slow),
    pytest.param("local_hybrid", 5, marks=pytest.mark.slow),
])
def test_ring_chunk_parity(arch, chunk):
    """Ring-buffer chunked prefill == one-shot rolling prefill for
    window=8 configs with a 21-token prompt (the ring cursor wraps twice):
    logits, pos, the rolling-cache invariant (slot i holds the token with
    pos % window == i), and a bit-exact greedy continuation."""
    cfg = _cfgs()[arch]
    assert supports_chunked_prefill(cfg)
    params = init_lm_params(cfg, KEY)
    B, L, MS = 2, 21, 40
    window = cfg.attn.sliding_window
    assert L > window, "the test must wrap the ring cursor"
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0,
                              cfg.vocab_size, jnp.int32)
    # fp32 caches as well as fp32 compute: the chunked path re-reads
    # earlier chunks' KV from the cache (one-shot never does), so a bf16
    # cache would inject quantization the reference path doesn't see
    ref_logits, ref_cache = lm_prefill(cfg, params, {"tokens": toks},
                                       init_lm_cache(cfg, B, MS,
                                                     dtype=jnp.float32))
    cache = init_lm_cache(cfg, B, MS, dtype=jnp.float32)
    logits, cache = chunked_prefill(cfg, params, toks, cache,
                                    chunk_size=chunk)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                  np.asarray(ref_cache["pos"]))
    # the rolling invariant transfers: one-shot and ring paths must land
    # the same window contents in the same slots (a misaligned slot would
    # show up as an O(1) error, far above fp32 reduction drift)
    checked = 0
    for ref_leaf, leaf in zip(jax.tree_util.tree_leaves(ref_cache),
                              jax.tree_util.tree_leaves(cache)):
        if ref_leaf.ndim == 5 and ref_leaf.shape[2] == window:
            np.testing.assert_allclose(np.asarray(ref_leaf, np.float32),
                                       np.asarray(leaf, np.float32),
                                       rtol=1e-4, atol=1e-4)
            checked += 1
    assert checked >= 1
    first = jnp.argmax(ref_logits[..., :cfg.vocab_size], -1).astype(jnp.int32)
    t_ref, _ = decode_tokens(cfg, params, ref_cache, first, 8, rope_len=MS)
    t_chk, _ = decode_tokens(cfg, params, cache, first, 8, rope_len=MS)
    np.testing.assert_array_equal(np.asarray(t_chk), np.asarray(t_ref))


def test_ring_write_gated_by_lengths():
    """A zero-length (inert) row in a mixed group must leave its ring
    cache untouched even after the cursor has wrapped — an ungated write
    would clobber live window history that decode still attends."""
    cfg = _cfgs()["local_pure"]
    params = init_lm_params(cfg, KEY)
    B, MS, C = 2, 40, 8
    window = cfg.attn.sliding_window
    # row 0: prefill 2*window tokens so its ring is fully wrapped
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, 2 * window), 0,
                              cfg.vocab_size, jnp.int32)
    cache = init_lm_cache(cfg, B, MS)
    logits, cache = chunked_prefill(cfg, params, toks, cache, chunk_size=C)
    ring_before = [np.asarray(leaf)
                   for leaf in jax.tree_util.tree_leaves(cache)
                   if leaf.ndim == 5]
    # another chunk where BOTH rows are zero-length: pure no-op
    extra = jax.random.randint(jax.random.PRNGKey(5), (B, C), 0,
                               cfg.vocab_size, jnp.int32)
    _, cache2 = lm_prefill_chunk(cfg, params, {"tokens": extra}, cache,
                                 lengths=jnp.zeros((B,), jnp.int32))
    ring_after = [np.asarray(leaf)
                  for leaf in jax.tree_util.tree_leaves(cache2)
                  if leaf.ndim == 5]
    assert ring_before and len(ring_before) == len(ring_after)
    for a, b in zip(ring_before, ring_after):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(cache2["pos"]),
                                  np.asarray(cache["pos"]))


@pytest.mark.parametrize("arch", [
    "dense", "mamba2",                                 # tier-1 smoke: flash
                                                       # q_offset + scan/ssd
    "local",                                           # ring kv_wrap kernel
    pytest.param("mamba1", marks=pytest.mark.slow),
    pytest.param("hybrid", marks=pytest.mark.slow),
    pytest.param("local_pure", marks=pytest.mark.slow),
])
def test_chunk_parity_interpret_backend(arch):
    """The same parity through the Pallas kernels (interpret=True on CPU):
    exercises the flash q_offset path and initial-state scan/ssd/conv
    plumbing inside the compiled chunk step."""
    cfg = _cfgs()[arch]
    params = init_lm_params(cfg, KEY)
    B, L, MS = 2, 13, 24
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, L), 0,
                              cfg.vocab_size, jnp.int32)
    with dispatch.use_backend("interpret"):
        ref_logits, ref_cache = lm_prefill(cfg, params, {"tokens": toks},
                                           init_lm_cache(cfg, B, MS))
        logits, cache = _run_chunked(cfg, params, toks, MS, chunk=5)
    # bf16 logits: tolerance must sit above bf16 ULP (2^-8) — a few-ULP
    # drift from reduction-order changes is expected
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(np.asarray(cache["pos"]),
                                  np.asarray(ref_cache["pos"]))


def test_chunk_step_cache_keys_on_backend():
    """The kernel backend is read at trace time, so the jitted chunk step
    is kept per backend: a reference run after a Pallas run in the same
    process traces its own program instead of replaying the Pallas one."""
    cfg = _cfgs()["mamba2"]
    with dispatch.use_backend("interpret"):
        interp = _jitted_chunk_step(cfg, None)
    with dispatch.use_backend("ref"):
        ref = _jitted_chunk_step(cfg, None)
        assert _jitted_chunk_step(cfg, None) is ref
    assert interp is not ref


@pytest.mark.parametrize("arch", [
    "dense",                                           # tier-1 smoke
    pytest.param("hybrid", marks=pytest.mark.slow),
    pytest.param("mamba1", marks=pytest.mark.slow),
    pytest.param("mamba2", marks=pytest.mark.slow),
])
def test_mixed_length_batch_matches_solo(arch):
    """One padded heterogeneous batch (no same-length grouping): every
    row's logits and cache states must equal a batch-1 prefill of just
    that row's prompt."""
    cfg = _cfgs()[arch]
    params = init_lm_params(cfg, KEY)
    MS = 40
    rng = np.random.default_rng(0)
    lens = [5, 17, 9]
    prompts = [rng.integers(2, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    padded = np.zeros((len(lens), max(lens)), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    cache = init_lm_cache(cfg, len(lens), MS)
    logits, cache = chunked_prefill(cfg, params, jnp.asarray(padded), cache,
                                    chunk_size=6, lengths=lens)
    assert np.asarray(cache["pos"]).tolist() == lens
    for i, p in enumerate(prompts):
        solo_logits, solo_cache = lm_prefill(
            cfg, params, {"tokens": jnp.asarray(p[None])},
            init_lm_cache(cfg, 1, MS))
        np.testing.assert_allclose(np.asarray(logits[i], np.float32),
                                   np.asarray(solo_logits[0], np.float32),
                                   rtol=2e-2, atol=2e-2)
        # decode continuation must agree token-for-token with the solo row
        first = jnp.argmax(solo_logits[..., :cfg.vocab_size],
                           -1).astype(jnp.int32)
        t_solo, _ = decode_tokens(cfg, params, solo_cache, first, 6)
        from repro.serving.cache import extract_slot
        row = extract_slot(cache, i)
        t_row, _ = decode_tokens(cfg, params, row, first, 6)
        np.testing.assert_array_equal(np.asarray(t_row), np.asarray(t_solo))


def test_zero_length_rows_are_inert():
    """Rows admitted with length 0 (batch padding in the serving group)
    must leave their carried state untouched: conv/SSM states stay zero
    and pos stays put.  (Their KV rows may receive scratch writes — those
    are hidden by the decode-time valid_len mask and later overwrites.)"""
    cfg = _cfgs()["hybrid"]
    params = init_lm_params(cfg, KEY)
    B, MS, C = 2, 24, 8
    cache = init_lm_cache(cfg, B, MS)
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, C), 0,
                              cfg.vocab_size, jnp.int32)
    lens = jnp.asarray([C, 0], jnp.int32)
    _, new_cache = jax.jit(
        lambda p, t, l, c: lm_prefill_chunk(cfg, p, {"tokens": t}, c,
                                            lengths=l)
    )(params, toks, lens, cache)
    assert np.asarray(new_cache["pos"]).tolist() == [C, 0]
    checked = 0
    for seg in new_cache["segments"]:
        for layer in seg:
            for key in ("conv", "ssm"):
                if key in layer:
                    # leaves are [n_rep, B, ...]; row 1 was inert (dt is
                    # driven through softplus(-30) ~ 1e-13, not exactly 0)
                    row = np.asarray(layer[key][:, 1], np.float32)
                    np.testing.assert_allclose(row, np.zeros_like(row),
                                               atol=1e-9)
                    checked += 1
    assert checked >= 2


def test_supports_chunked_prefill_exclusions():
    """Every decodable architecture chunks — rolling windows included
    (ring-buffer path).  Only encoders (no prefix-extension recurrence)
    and audio frontends (feature inputs, not tokens) are excluded."""
    cfgs = _cfgs()
    assert supports_chunked_prefill(cfgs["dense"])
    assert supports_chunked_prefill(cfgs["local"])
    assert supports_chunked_prefill(cfgs["local_pure"])
    assert supports_chunked_prefill(cfgs["local_hybrid"])
    enc = ModelConfig(
        name="enc", family="encoder", n_layers=2, d_model=64, d_ff=128,
        vocab_size=97,
        attn=AttnConfig(n_heads=4, n_kv_heads=4, head_dim=16, causal=False),
        layer_pattern=("encoder",), vocab_pad_multiple=16)
    assert not supports_chunked_prefill(enc)
    audio = ModelConfig(
        name="aud", family="audio", n_layers=2, d_model=64, d_ff=128,
        vocab_size=97,
        attn=AttnConfig(n_heads=4, n_kv_heads=4, head_dim=16),
        layer_pattern=("dense",), frontend="audio",
        frontend_feature_dim=16, vocab_pad_multiple=16)
    assert not supports_chunked_prefill(audio)
