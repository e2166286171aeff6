"""Unified language model: embedding -> scanned layer segments -> head.

One code path serves every assigned architecture: dense / local:global /
MoE / SSM / hybrid / encoder-only / modality-stub models, selected purely by
``ModelConfig``.  Layers are grouped into repeating units and executed with
``lax.scan`` over stacked params (compact HLO; trip counts recoverable by the
HLO cost analyzer).

Decode API
----------
The cache built by :func:`init_lm_cache` carries a per-row position vector
``pos: [B] int32`` so every batch slot decodes at its own offset (the
serving engine admits requests at different times).  Three entry points:

* :func:`lm_prefill` — process the prompt, fill the cache.
* :func:`lm_prefill_chunk` — one chunk of a state-carrying chunked prefill:
  attention KV lands at the per-row running offset ``cache["pos"]`` (offset
  causal mask), SSM layers continue from their carried conv/SSM states, and
  a per-row ``lengths`` vector makes ragged/heterogeneous chunks inert past
  each row's valid prefix.  The serving layer (``repro.serving.prefill``)
  drives it to prefill arbitrarily long prompts at flat memory.
* :func:`lm_decode_step` — one token for all rows (``token: [B, 1]``).
* :func:`decode_tokens` — the fused multi-token loop: runs ``n`` greedy (or
  temperature-sampled) steps inside a single ``jax.lax.scan`` with on-device
  token selection, so a whole generation burst is one compiled program with
  zero host round-trips per token.  This is the serving fast path.

Mamba decode steps route through the fused conv-shift + state-update
kernels in ``repro.kernels.decode_fused`` (backend selected by
``REPRO_KERNEL_BACKEND`` / ``repro.kernels.dispatch``).

:func:`lm_prefill_chunk` and :func:`decode_tokens` accept a static
``kv_bucket``: the KV caches are sliced to that extent around the compiled
body so attention FLOPs/IO track the live prefix instead of ``max_seq``
(bit-identical outputs; see ``repro.serving.bucketing`` for how callers
pick the bucket from a bounded power-of-two ladder whose top rung is the
model's largest KV extent — the sliding *window* for rolling
architectures).  Rolling ("local") layers chunk-prefill through their
ring-buffer caches (modular scatter + ring-unrolling mask in
``repro.models.attention``); both entry points take a static ``rope_len``
so rope tables cover positions past a window-sized cache."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import blocks
from repro.models.norms import rms_norm, scale_by
from repro.models.params import ParamDef, init_params, param_axes, stack_defs
from repro.models.rope import rope_tables


# --------------------------------------------------------------------------
# parameter / cache construction
# --------------------------------------------------------------------------

def model_param_defs(cfg: ModelConfig) -> Dict[str, Any]:
    D, V = cfg.d_model, cfg.padded_vocab
    defs: Dict[str, Any] = {
        "embed": ParamDef((V, D), ("vocab", "embed"), fan_in=1, scale=0.02),
        "final_norm": ParamDef((D,), ("embed",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((D, V), ("embed", "vocab"), fan_in=D)
    segs = []
    for unit, n_rep in cfg.segments():
        unit_defs = tuple(blocks.layer_param_defs(cfg, kind) for kind in unit)
        segs.append(stack_defs(unit_defs, n_rep))
    defs["segments"] = segs
    if any(k == "mamba2+shared" for k in cfg.layer_kinds):
        defs["shared"] = blocks.shared_block_defs(cfg)
    if cfg.frontend != "none":
        defs["frontend_proj"] = ParamDef((cfg.frontend_feature_dim, D),
                                         (None, "embed"),
                                         fan_in=cfg.frontend_feature_dim)
    return defs


def init_lm_params(cfg: ModelConfig, key: jax.Array, dtype=None):
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    return init_params(model_param_defs(cfg), key, dtype)


def lm_param_axes(cfg: ModelConfig):
    return param_axes(model_param_defs(cfg))


def init_lm_cache(cfg: ModelConfig, batch: int, max_seq: int, *,
                  kv_repeat: int = 1, shared_kv_repeat: int = 1,
                  dtype=jnp.bfloat16):
    segs = []
    for unit, n_rep in cfg.segments():
        unit_cache = tuple(
            blocks.init_layer_cache(cfg, kind, batch, max_seq,
                                    kv_repeat=kv_repeat,
                                    shared_kv_repeat=shared_kv_repeat,
                                    dtype=dtype)
            for kind in unit)
        segs.append(jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_rep,) + x.shape), unit_cache))
    return {"segments": segs, "pos": jnp.zeros((batch,), jnp.int32)}


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params, inputs: Dict[str, jax.Array]) -> jax.Array:
    with jax.named_scope("embed"):
        if cfg.frontend == "audio":
            # input is precomputed frame features [B, S, feat]
            x = jnp.einsum("bsf,fd->bsd",
                           inputs["features"].astype(jnp.dtype(cfg.compute_dtype)),
                           params["frontend_proj"].astype(
                               jnp.dtype(cfg.compute_dtype)))
        else:
            emb = params["embed"]
            x = jnp.take(emb, inputs["tokens"], axis=0)
            x = x.astype(jnp.dtype(cfg.compute_dtype))
            if cfg.mup is not None:
                x = scale_by(x, cfg.mup.embedding)
            if cfg.frontend == "vision" and "features" in inputs:
                feats = jnp.einsum(
                    "bnf,fd->bnd",
                    inputs["features"].astype(jnp.dtype(cfg.compute_dtype)),
                    params["frontend_proj"].astype(jnp.dtype(cfg.compute_dtype)))
                x = jnp.concatenate([feats, x], axis=1)
    return constrain(x, ("batch", "seq", "embed"))


def _head(cfg: ModelConfig, params, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x,
                                params["embed"].astype(x.dtype))
        else:
            logits = jnp.einsum("bsd,dv->bsv", x,
                                params["lm_head"].astype(x.dtype))
        if cfg.mup is not None:
            logits = scale_by(logits, cfg.mup.lm_head)
        if cfg.padded_vocab != cfg.vocab_size:
            mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
            logits = jnp.where(mask[None, None, :], logits, -1e30)
    return constrain(logits, ("batch", "seq", "vocab"))


def _rope_for(cfg: ModelConfig, max_seq: int):
    if cfg.attn is None and cfg.shared_attn is None:
        return None, None
    a = cfg.attn or cfg.shared_attn
    rope = rope_tables(max_seq, a.head_dim, a.rope_theta)
    rope_local = None
    if cfg.attn is not None and cfg.attn.sliding_window is not None:
        rope_local = rope_tables(max_seq, a.head_dim, 10_000.0)
    return rope, rope_local


def _run_segments(cfg: ModelConfig, params, x: jax.Array, *, cache=None,
                  pos=None, kv_repeat=1, shared_kv_repeat=1, moe_groups=1,
                  rope=None, rope_local=None, train: bool = False,
                  chunk_mask=None):
    shared = params.get("shared")
    new_cache_segs = []
    for si, (unit, n_rep) in enumerate(cfg.segments()):
        seg_params = params["segments"][si]
        seg_cache = cache["segments"][si] if cache is not None else None

        def unit_body(x, xs, unit=unit):
            layer_p, layer_c = xs
            new_cs = []
            for li, kind in enumerate(unit):
                c = layer_c[li] if layer_c is not None else None
                x, nc = blocks.apply_layer(
                    cfg, kind, layer_p[li], x, rope=rope,
                    rope_local=rope_local, cache=c, pos=pos,
                    kv_repeat=kv_repeat, shared=shared,
                    shared_kv_repeat=shared_kv_repeat, moe_groups=moe_groups,
                    chunk_mask=chunk_mask)
                new_cs.append(nc if nc is not None else
                              (dict() if c is None else c))
            return x, tuple(new_cs)

        body = unit_body
        if train and cfg.remat == "block":
            body = jax.checkpoint(unit_body)

        def scan_body(x, xs):
            return body(x, xs)

        if cfg.scan_layers and n_rep > 1:
            x, new_seg_cache = jax.lax.scan(
                scan_body, x, (seg_params, seg_cache))
        else:
            ncs = []
            for r in range(n_rep):
                p_r = jax.tree_util.tree_map(lambda t: t[r], seg_params)
                c_r = (jax.tree_util.tree_map(lambda t: t[r], seg_cache)
                       if seg_cache is not None else None)
                x, nc = body(x, (p_r, c_r))
                ncs.append(nc)
            new_seg_cache = (jax.tree_util.tree_map(
                lambda *ts: jnp.stack(ts), *ncs) if cache is not None else None)
        new_cache_segs.append(new_seg_cache)
    return x, new_cache_segs


def lm_forward(cfg: ModelConfig, params, inputs: Dict[str, jax.Array], *,
               kv_repeat: int = 1, shared_kv_repeat: int = 1,
               moe_groups: int = 1, train: bool = True) -> jax.Array:
    """Full-sequence forward (training / encoder inference). Returns logits."""
    x = _embed(cfg, params, inputs)
    rope, rope_local = _rope_for(cfg, x.shape[1])
    x, _ = _run_segments(cfg, params, x, kv_repeat=kv_repeat,
                         shared_kv_repeat=shared_kv_repeat,
                         moe_groups=moe_groups, rope=rope,
                         rope_local=rope_local, train=train)
    return _head(cfg, params, x)


def lm_prefill(cfg: ModelConfig, params, inputs: Dict[str, jax.Array], cache,
               *, kv_repeat: int = 1, shared_kv_repeat: int = 1,
               moe_groups: int = 1) -> Tuple[jax.Array, Any]:
    """Process the prompt, fill the cache. Returns (last-token logits, cache)."""
    x = _embed(cfg, params, inputs)
    seq = x.shape[1]
    max_seq = _cache_max_seq(cfg, cache) or seq
    rope, rope_local = _rope_for(cfg, max(seq, max_seq))
    x, new_segs = _run_segments(cfg, params, x, cache=cache, pos=None,
                                kv_repeat=kv_repeat,
                                shared_kv_repeat=shared_kv_repeat,
                                moe_groups=moe_groups, rope=rope,
                                rope_local=rope_local, train=False)
    logits = _head(cfg, params, x[:, -1:])
    return logits, {"segments": new_segs,
                    "pos": jnp.full((x.shape[0],), seq, jnp.int32)}


def lm_prefill_chunk(cfg: ModelConfig, params, inputs: Dict[str, jax.Array],
                     cache, *, lengths: Optional[jax.Array] = None,
                     kv_repeat: int = 1, shared_kv_repeat: int = 1,
                     moe_groups: int = 1,
                     kv_bucket: Optional[int] = None,
                     rope_len: Optional[int] = None,
                     with_sentinel: bool = False):
    """One state-carrying prefill chunk: process ``S`` prompt tokens
    starting at each row's running offset ``cache["pos"]``.

    Attention layers scatter the chunk's KV at that offset and attend with
    the offset causal mask over the whole cache; SSM layers continue from
    their carried conv/SSM states.  ``lengths`` ([B] int32, default all-S)
    marks how many leading tokens of the chunk are valid per row — ragged
    last chunks and already-finished rows (length 0) are inert: they update
    no SSM state, and their stale KV is either overwritten by later writes
    or hidden by the decode-time ``valid_len`` mask.  Running the chunks of
    a prompt in order therefore reproduces :func:`lm_prefill` exactly (up
    to fp tolerance) with peak activation memory O(chunk), not O(prompt).

    ``kv_bucket`` (static int, or None for the full cache) bounds attention
    to the live prefix: KV-cache leaves larger than the bucket are sliced
    to their first ``kv_bucket`` rows before the flash kernels run and
    written back after, so the chunk's attention FLOPs/IO scale with the
    true prefix rather than ``max_seq``.  The caller must pick
    ``kv_bucket >= max(pos) + chunk`` capped at the model's KV extent (see
    ``repro.serving.bucketing``: the ladder tops out at the largest leaf,
    which for rolling architectures is the window); outputs are
    bit-identical to the unbucketed program.  Rolling ring-buffer leaves
    are only ever sliced while the live prefix hasn't wrapped (the caller
    contract above guarantees it); ``REPRO_RING_BUCKETS=0`` keeps them at
    the full window regardless.

    ``rope_len`` (static int, or None) sizes the rope tables.  Rolling
    architectures need it: their largest cache leaf is the *window*, but
    chunk positions run up to the full prompt length — the serving layer
    passes its ``max_seq``.  Values at a given position are identical for
    any sufficient table size.

    ``with_sentinel`` (static bool) appends a per-row divergence sentinel
    to the return: ``ok [B] bool`` is True iff every hidden state of the
    row's *valid* chunk tokens (and its emitted logits) is finite.  The
    reduction is fused into the chunk program — no extra dispatch or
    host sync — and costs O(B*S*D) compares next to the chunk's matmuls.

    Returns ``(logits of each row's last valid chunk token [B,1,V],
    updated cache)`` — plus ``ok`` when ``with_sentinel`` — with ``pos``
    advanced by ``lengths``."""
    _check_kv_bucket(cfg, kv_bucket)
    full_cache = cache
    if kv_bucket is not None:
        cache = _slice_kv_cache(cache, kv_bucket,
                                keep_extent=_ring_slice_exempt(cfg))
    x = _embed(cfg, params, inputs)
    b, s = x.shape[0], x.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32), (b,))
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    else:
        lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))
    chunk_mask = jnp.arange(s)[None, :] < lengths[:, None]
    max_seq = _cache_max_seq(cfg, cache) or s
    rope, rope_local = _rope_for(cfg, max(s, max_seq, rope_len or 0))
    x, new_segs = _run_segments(cfg, params, x, cache=cache, pos=pos,
                                kv_repeat=kv_repeat,
                                shared_kv_repeat=shared_kv_repeat,
                                moe_groups=moe_groups, rope=rope,
                                rope_local=rope_local, train=False,
                                chunk_mask=chunk_mask)
    last = jnp.clip(lengths - 1, 0, s - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = _head(cfg, params, x_last)
    new_cache = {"segments": new_segs, "pos": pos + lengths}
    if kv_bucket is not None:
        new_cache = _unslice_kv_cache(full_cache, new_cache)
    if not with_sentinel:
        return logits, new_cache
    # divergence sentinel: a row is ok iff all its VALID chunk tokens'
    # hidden states and its emitted logits are finite (padding rows and
    # zero-length rows pass vacuously — their garbage is inert by design)
    ok = jnp.all(jnp.where(chunk_mask[:, :, None], jnp.isfinite(x), True),
                 axis=(1, 2))
    ok &= jnp.all(jnp.isfinite(logits[:, 0, :cfg.vocab_size]), axis=-1)
    ok |= lengths == 0
    return logits, new_cache, ok


def lm_decode_step(cfg: ModelConfig, params, token: jax.Array, cache, *,
                   kv_repeat: int = 1, shared_kv_repeat: int = 1,
                   moe_groups: int = 1,
                   rope_len: Optional[int] = None) -> Tuple[jax.Array, Any]:
    """One token step. token: [B, 1] int32 (or features [B,1,feat]).
    ``cache["pos"]`` is a [B] vector: rows may sit at different offsets.
    ``rope_len`` (static) sizes the rope tables past the cache extent —
    required for rolling-window architectures whose positions outgrow
    their window-sized caches (the serving layer passes ``max_seq``)."""
    pos = cache["pos"]
    inputs = {"tokens": token} if token.ndim == 2 else {"features": token}
    x = _embed(cfg, params, inputs)
    max_seq = _cache_max_seq(cfg, cache) or 1
    rope, rope_local = _rope_for(cfg, max(max_seq, rope_len or 0))
    x, new_segs = _run_segments(cfg, params, x, cache=cache, pos=pos,
                                kv_repeat=kv_repeat,
                                shared_kv_repeat=shared_kv_repeat,
                                moe_groups=moe_groups, rope=rope,
                                rope_local=rope_local, train=False)
    logits = _head(cfg, params, x)
    return logits, {"segments": new_segs, "pos": pos + 1}


def decode_tokens(cfg: ModelConfig, params, cache, first_token: jax.Array,
                  n: int, *, kv_repeat: int = 1, shared_kv_repeat: int = 1,
                  moe_groups: int = 1, temperature: float = 0.0,
                  rng: Optional[jax.Array] = None,
                  kv_bucket: Optional[int] = None,
                  rope_len: Optional[int] = None,
                  with_sentinel: bool = False):
    """Fused multi-token decode: run ``n`` generation steps inside one
    ``jax.lax.scan``.

    ``first_token`` ([B, 1] int32) is fed to the first step; every
    subsequent input token is selected on device (greedy argmax, or
    categorical sampling when ``temperature > 0`` with ``rng``), so the
    whole burst compiles to a single program with no host synchronisation
    per token.  Returns ``(tokens [B, n] int32, cache)`` — token ``[:, i]``
    is the model's output after consuming the (i-1)-th emitted token,
    exactly matching ``n`` sequential :func:`lm_decode_step` calls.

    ``kv_bucket`` (static int >= ``max(live pos) + n``, or None) slices the
    KV caches to the live prefix ONCE outside the scan, runs the whole
    burst against the slice, and writes it back once at the end — decode
    attention reads ``kv_bucket`` rows per token instead of ``max_seq``,
    bit-identically (rows of retired slots whose ``pos`` exceeds the bucket
    write nothing and produce finite garbage, as on the full-cache path).

    ``with_sentinel`` (static bool) appends a per-row divergence sentinel:
    ``ok [B] bool``, True iff every step's logits for that row were finite
    across the whole burst.  The ``isfinite`` reduction rides inside the
    existing scan carry — zero extra dispatches and zero per-token host
    syncs; the caller reads it with the same device->host transfer that
    fetches the tokens.  Returns ``(tokens, cache, ok)`` instead of
    ``(tokens, cache)``.
    """
    sample = temperature > 0.0
    if sample and rng is None:
        raise ValueError("temperature sampling requires an rng key")
    _check_kv_bucket(cfg, kv_bucket)
    full_cache = cache
    if kv_bucket is not None:
        cache = _slice_kv_cache(cache, kv_bucket,
                                keep_extent=_ring_slice_exempt(cfg))

    def select(logits: jax.Array, key) -> jax.Array:
        lg = logits[:, 0, :cfg.vocab_size]
        if sample:
            nxt = jax.random.categorical(key, lg / temperature, axis=-1)
        else:
            nxt = jnp.argmax(lg, axis=-1)
        return nxt.astype(jnp.int32)[:, None]              # [B, 1]

    def step(carry, key):
        tok, c, ok = carry
        logits, c = lm_decode_step(cfg, params, tok, c, kv_repeat=kv_repeat,
                                   shared_kv_repeat=shared_kv_repeat,
                                   moe_groups=moe_groups, rope_len=rope_len)
        if with_sentinel:
            # fold the finiteness reduction into the scan carry: one AND
            # per step on device, surfaced with the tokens' transfer
            ok &= jnp.all(jnp.isfinite(logits[:, 0, :cfg.vocab_size]), -1)
        nxt = select(logits, key)
        return (nxt, c, ok), nxt[:, 0]

    # keys are presplit outside the scan; greedy mode carries none at all
    keys = jax.random.split(rng, n) if sample else None
    ok0 = jnp.ones((first_token.shape[0],), bool)
    (_, cache, ok), toks = jax.lax.scan(
        step, (first_token.astype(jnp.int32), cache, ok0), keys, length=n)
    if kv_bucket is not None:
        cache = _unslice_kv_cache(full_cache, cache)
    if with_sentinel:
        return toks.T, cache, ok                           # [B, n], ..., [B]
    return toks.T, cache                                   # [B, n]


def _is_kv_leaf(path) -> bool:
    """Attention-cache leaves are the dict entries keyed "k"/"v" (possibly
    nested under "attn" for shared blocks); mamba conv/ssm states and "pos"
    never carry those keys."""
    last = path[-1]
    return getattr(last, "key", None) in ("k", "v")


def _check_kv_bucket(cfg: ModelConfig, kv_bucket: Optional[int]) -> None:
    if kv_bucket is None:
        return
    if kv_bucket < 1:
        raise ValueError(f"kv_bucket must be >= 1, got {kv_bucket}")
    if "encoder" in cfg.layer_kinds:
        raise ValueError(
            "kv_bucket requires causal KV caches; encoder (bidirectional) "
            "layers cannot be prefix-sliced")


def _ring_slice_exempt(cfg: ModelConfig) -> Optional[int]:
    """Extent of rolling ring-buffer KV leaves to EXEMPT from bucket
    slicing, or None when they may slice.  With ``REPRO_RING_BUCKETS=1``
    (the default) ring leaves slice like append-only ones — valid because
    callers select ``bucket >= min(max(pos) + chunk, extent)`` from the
    extent-topped ladder, so a ring is only ever sliced before its prefix
    wraps.  The env kill-switch keeps rings at the full window instead."""
    from repro.kernels import dispatch as kdispatch
    if kdispatch.ring_buckets():
        return None
    if "local" in cfg.layer_kinds and cfg.attn is not None:
        return cfg.attn.sliding_window
    return None


def _slice_kv_cache(cache, bucket: int, keep_extent: Optional[int] = None):
    """Slice every KV-cache leaf to its first ``bucket`` rows (axis 2 of the
    stacked [n_rep, B, Skv, KV, hd] leaves).  Callers guarantee every read
    and write of the upcoming program lands below ``bucket``; the masked
    tail contributes exact zeros, so outputs are bit-identical to the
    full-cache program while attention FLOPs/IO track the live prefix.
    For rolling ring-buffer leaves "lands below bucket" additionally means
    the prefix has not wrapped yet — guaranteed by selecting the bucket
    from a ladder that tops out at the model's largest KV extent (window
    for rolling architectures).  ``keep_extent`` exempts leaves of exactly
    that extent (the ``REPRO_RING_BUCKETS=0`` escape hatch) — extent
    matching is deliberately conservative: an append-only leaf that
    coincidentally equals the window is also kept whole, which only costs
    FLOPs in that already-degraded debug mode, never correctness."""
    def f(path, leaf):
        if (_is_kv_leaf(path) and leaf.shape[2] > bucket
                and leaf.shape[2] != keep_extent):
            return jax.lax.slice_in_dim(leaf, 0, bucket, axis=2)
        return leaf
    return jax.tree_util.tree_map_with_path(f, cache)


def _unslice_kv_cache(full, sliced):
    """Write bucket-sliced KV leaves back into the full-extent cache (rows
    past the bucket were untouched by construction)."""
    def f(path, f_leaf, s_leaf):
        if _is_kv_leaf(path) and s_leaf.shape[2] < f_leaf.shape[2]:
            return jax.lax.dynamic_update_slice_in_dim(
                f_leaf, s_leaf.astype(f_leaf.dtype), 0, axis=2)
        return s_leaf
    return jax.tree_util.tree_map_with_path(f, full, sliced)


def _cache_max_seq(cfg: ModelConfig, cache) -> Optional[int]:
    """KV caches are [n_rep, B, S, KV, hd]; mamba caches have no usable seq
    dim, so look for a 5-D leaf (present whenever any layer has attention)."""
    if cache is None:
        return None
    best = None
    for seg in cache["segments"]:
        for leaf in jax.tree_util.tree_leaves(seg):
            if leaf.ndim == 5:
                best = max(best or 0, int(leaf.shape[2]))
    return best
