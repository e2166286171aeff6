"""GQA attention: dense / chunked(flash-style) / banded-local, plus decode w/ cache.

All variants are written with *global* array semantics; GSPMD partitions them
according to the activation sharding constraints installed by the step
builder (see distributed/sharding.py).  The chunked path mirrors the Pallas
flash kernel (kernels/flash) and is the lowering used for the CPU dry-run.

Rolling sliding-window ("local") caches are ring buffers of exactly
``window`` slots — slot i holds the newest token with ``pos % window ==
i``.  Decode writes modularly; chunked prefill attends ``[ring | chunk]``
with a per-row ``kv_wrap`` cursor that lets the kernels unroll the ring
in-mask (no rolled copy), then folds the chunk back into the ring with a
deterministic gather.  Every architecture therefore prefills through the
same chunked serving path.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import AttnConfig
from repro.distributed.sharding import constrain
from repro.kernels import dispatch as kdispatch
from repro.kernels.flash.ref import ring_kv_positions
from repro.models.params import ParamDef
from repro.models.norms import head_rms_norm, scale_by
from repro.models.rope import apply_rope

NEG_INF = -1e30


def _full_seq_attn(q, k, v, a: AttnConfig, *, causal: bool,
                   window: Optional[int],
                   q_offset: Optional[jax.Array] = None,
                   kv_wrap: Optional[jax.Array] = None,
                   ring_len: Optional[int] = None) -> jax.Array:
    """Dispatch the full-sequence core. q: [B,Sq,KV,G,hd]; k,v: [B,Skv,KV,hd].

    ``q_offset`` ([B] int32, or None) shifts the causal mask for chunked
    prefill: query i of row b sits at absolute position q_offset[b] + i
    while keys cover absolute positions [0, Skv).

    ``kv_wrap`` ([B] int32) + static ``ring_len`` switch the first
    ``ring_len`` key slots into a ring buffer with modulus ``window`` and
    per-row write cursor ``kv_wrap`` (slots past ``ring_len`` are the
    in-flight chunk at ``kv_wrap + j - ring_len``) — the layout of a
    chunked prefill over a rolling sliding-window cache."""
    if kdispatch.get_backend() != "ref":
        from repro.kernels.flash.ops import flash_attention
        b, sq, nkv, g, hd = q.shape
        qh = q.reshape(b, sq, nkv * g, hd).transpose(0, 2, 1, 3)
        kh = k.transpose(0, 2, 1, 3)
        vh = v.transpose(0, 2, 1, 3)
        o = flash_attention(qh, kh, vh, causal=causal, window=window,
                            q_offset=q_offset, kv_wrap=kv_wrap,
                            ring_len=ring_len)
        return o.transpose(0, 2, 1, 3).reshape(b, sq, nkv, g, hd)
    kv_pos = None
    if kv_wrap is not None:
        kv_pos = ring_kv_positions(kv_wrap, window, ring_len, k.shape[1])
    if (q_offset is None and kv_pos is None and window is not None and causal
            and k.shape[1] > 2 * window):
        return _local_banded_attention(q, k, v, window=window)
    off = 0 if q_offset is None else q_offset
    if k.shape[1] <= a.dense_cutoff or a.impl == "dense":
        return _dense_attention(q, k, v, causal=causal, window=window,
                                q_offset=off, kv_pos=kv_pos)
    return _chunked_attention(q, k, v, causal=causal, window=window,
                              q_offset=off, kv_pos=kv_pos)


def attn_param_defs(d_model: int, a: AttnConfig) -> Dict[str, ParamDef]:
    defs = {
        "wq": ParamDef((d_model, a.n_heads, a.head_dim), ("embed", "heads", None),
                       fan_in=d_model),
        "wk": ParamDef((d_model, a.n_kv_heads, a.head_dim), ("embed", "kv_heads", None),
                       fan_in=d_model),
        "wv": ParamDef((d_model, a.n_kv_heads, a.head_dim), ("embed", "kv_heads", None),
                       fan_in=d_model),
        "wo": ParamDef((a.n_heads, a.head_dim, d_model), ("heads", None, "embed"),
                       init="normal_out", fan_in=a.n_heads * a.head_dim),
    }
    if a.qk_norm:
        defs["q_norm"] = ParamDef((a.head_dim,), (None,), init="zeros")
        defs["k_norm"] = ParamDef((a.head_dim,), (None,), init="zeros")
    return defs


def _repeat_kv(k: jax.Array, repeat: int) -> jax.Array:
    if repeat == 1:
        return k
    return jnp.repeat(k, repeat, axis=2)


def _group_q(q: jax.Array, n_kv: int) -> jax.Array:
    """[B,S,H,hd] -> [B,S,KV,G,hd] grouping q heads by their kv head."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, hd)


def _dense_attention(q, k, v, *, causal: bool, window: Optional[int],
                     q_offset=0, kv_pos=None) -> jax.Array:
    """q: [B,Sq,KV,G,hd]; k,v: [B,Skv,KV,hd]. Returns [B,Sq,KV,G,hd].
    ``q_offset``: scalar or [B] per-row query-position offset.
    ``kv_pos`` ([B, Skv] int32, or None for ``arange``): per-slot absolute
    key positions (negative = never written, masked out) — the ring-buffer
    KV layout of a chunked prefill over a rolling window."""
    with jax.named_scope("attn_core"):
        scale = 1.0 / math.sqrt(q.shape[-1])
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                            preferred_element_type=jnp.float32) * scale
        sq, skv = q.shape[1], k.shape[1]
        off = jnp.atleast_1d(jnp.asarray(q_offset))
        qpos = jnp.arange(sq)[None, :] + off[:, None]          # [Bb, Sq]
        if kv_pos is not None:
            kpos = kv_pos[:, None, :]                          # [B, 1, Skv]
            mask = jnp.broadcast_to(kpos >= 0,
                                    (kv_pos.shape[0], sq, skv))
        else:
            kpos = jnp.arange(skv)[None, None, :]
            mask = jnp.ones((off.shape[0], sq, skv), bool)
        if causal:
            mask = mask & (qpos[:, :, None] >= kpos)
        if window is not None:
            mask = mask & ((qpos[:, :, None] - kpos) < window)
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def _chunked_attention(q, k, v, *, causal: bool, window: Optional[int],
                       kv_block: int = 1024, q_offset=0,
                       kv_pos=None) -> jax.Array:
    """Online-softmax over kv blocks (flash-style, numerically exact).
    ``q_offset``: scalar or [B] per-row query-position offset.
    ``kv_pos`` ([B, Skv] int32, or None for ``arange``): per-slot absolute
    key positions (negative = masked), for ring-buffer KV layouts."""
    b, sq, nkv, g, hd = q.shape
    skv = k.shape[1]
    nb = -(-skv // kv_block)
    pad = nb * kv_block - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nb, kv_block, nkv, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nb, kv_block, nkv, hd).transpose(1, 0, 2, 3, 4)
    # key positions per slot (-1 on the padded tail so it masks out); the
    # default arange collapses to the classic in-order layout
    if kv_pos is None:
        kv_pos = jnp.arange(skv, dtype=jnp.int32)[None, :]
    kp = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    kpb = kp.reshape(kp.shape[0], nb, kv_block).transpose(1, 0, 2)
    scale = 1.0 / math.sqrt(hd)
    off = jnp.atleast_1d(jnp.asarray(q_offset))
    qpos = jnp.arange(sq)[None, :] + off[:, None]              # [Bb, Sq]
    nrow = max(off.shape[0], kp.shape[0])

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, kpos = blk
        with jax.named_scope("attn_core"):
            s = jnp.einsum("bqkgd,bskd->bkgqs", q, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.broadcast_to(kpos[:, None, :] >= 0,
                                    (nrow, sq, kv_block))
            if causal:
                mask = mask & (qpos[:, :, None] >= kpos[:, None, :])
            if window is not None:
                mask = mask & ((qpos[:, :, None] - kpos[:, None, :]) < window)
            s = jnp.where(mask[:, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(q.dtype), vblk)
            acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, nkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, nkv, g, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, kpb))
    out = acc / jnp.maximum(l, 1e-37).transpose(0, 3, 1, 2)[..., None]
    return out.astype(q.dtype)


def _local_banded_attention(q, k, v, *, window: int) -> jax.Array:
    """Sliding-window causal attention via the two-block trick (exact for
    window <= block).  FLOPs ~ S * 2w instead of S^2."""
    b, sq, nkv, g, hd = q.shape
    w = window
    nb = -(-sq // w)
    pad = nb * w - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qb = q.reshape(b, nb, w, nkv, g, hd)
    kb = k.reshape(b, nb, w, nkv, hd)
    vb = v.reshape(b, nb, w, nkv, hd)
    kprev = jnp.pad(kb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    vprev = jnp.pad(vb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    k2 = jnp.concatenate([kprev, kb], axis=2)  # [B, nb, 2w, KV, hd]
    v2 = jnp.concatenate([vprev, vb], axis=2)
    with jax.named_scope("attn_core"):
        scale = 1.0 / math.sqrt(hd)
        s = jnp.einsum("bnqkgd,bnskd->bnkgqs", qb, k2,
                       preferred_element_type=jnp.float32) * scale
        qpos = jnp.arange(w)[:, None] + w          # position within [prev, own]
        kpos = jnp.arange(2 * w)[None, :]
        mask = (qpos >= kpos) & ((qpos - kpos) < w)
        # first block has no previous block
        first = (kpos >= w) & mask
        blk = jnp.arange(nb)
        mask_b = jnp.where((blk == 0)[:, None, None], first[None], mask[None])
        s = jnp.where(mask_b[None, :, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        ob = jnp.einsum("bnkgqs,bnskd->bnqkgd", p, v2)
    out = ob.reshape(b, nb * w, nkv, g, hd)
    return out[:, :sq]


def attention(p: Dict, x: jax.Array, a: AttnConfig, *,
              rope: Optional[Tuple[jax.Array, jax.Array]],
              window: Optional[int] = None,
              cache: Optional[Dict] = None,
              pos: Optional[jax.Array] = None,
              kv_repeat: int = 1,
              chunk_mask: Optional[jax.Array] = None,
              eps: float = 1e-6,
              key_mult: Optional[float] = None
              ) -> Tuple[jax.Array, Optional[Dict]]:
    """Full attention sub-block: qkv proj -> rope -> core -> out proj.

    cache=None: full-sequence (train/prefill, no cache returned).
    cache dict with "k","v" [B,Skv,KV*rep,hd]: if x has S>1 it is a prefill
    that fills the cache; if S==1 it is a decode step at position ``pos``.

    ``chunk_mask`` ([B, S] bool, chunked prefill only) marks the valid
    prefix of the chunk per row; rolling (ring-buffer) caches use it to
    gate their writes — an invalid token must never overwrite live ring
    history (append-only caches just let later writes/masks hide it).

    ``key_mult`` (Falcon-H1's muP ``key_multiplier``, or None) scales the
    projected keys before rope, so the cache holds the scaled keys.
    """
    b, s, _ = x.shape
    with jax.named_scope("qkv_proj"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
        if key_mult is not None:
            k = scale_by(k, key_mult)
    if a.qk_norm:
        q = head_rms_norm(q, p["q_norm"], eps)
        k = head_rms_norm(k, p["k_norm"], eps)
    if rope is not None:
        sin, cos = rope
        if cache is not None and s == 1:
            # per-row positions: pos is [B] (scalar broadcasts for old callers)
            posv = jnp.broadcast_to(jnp.atleast_1d(pos), (b,))
            # clip like the dynamic_slice this replaces (an overrun row —
            # e.g. a retired engine slot — must stay finite, not NaN-fill)
            sin = jnp.take(sin, posv, axis=0, mode="clip")[:, None]
            cos = jnp.take(cos, posv, axis=0, mode="clip")[:, None]
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        elif cache is not None and pos is not None:
            # chunked prefill: row b's chunk covers absolute positions
            # pos[b] .. pos[b]+s (clip keeps overrun/inert rows finite)
            posv = jnp.broadcast_to(jnp.atleast_1d(pos), (b,))
            idx = posv[:, None] + jnp.arange(s)                # [B, s]
            sin = jnp.take(sin, idx, axis=0, mode="clip")      # [B, s, half]
            cos = jnp.take(cos, idx, axis=0, mode="clip")
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        else:
            q = apply_rope(q, sin[:s], cos[:s])
            k = apply_rope(k, sin[:s], cos[:s])
    # the cache stores UNREPEATED kv heads (exact GQA); replication to a
    # shardable head count happens at compute time only.
    kr = constrain(_repeat_kv(k, kv_repeat), ("batch", "seq", "kv_heads", None))
    vr = constrain(_repeat_kv(v, kv_repeat), ("batch", "seq", "kv_heads", None))
    n_kv = a.n_kv_heads * kv_repeat
    q = constrain(_group_q(q, n_kv), ("batch", "seq", "kv_heads", None, None))

    new_cache = None
    if cache is None:
        o = _full_seq_attn(q, kr, vr, a, causal=a.causal, window=window)
    elif (s > 1 and pos is not None and window is not None
          and cache["k"].shape[1] <= window):
        # ring-buffer chunked prefill over a rolling sliding-window cache:
        # attend the chunk against [ring | chunk] with the modular mask
        # (the kernels unroll the ring via kv_wrap — no rolled copy), then
        # fold the chunk's last min(len, window) valid tokens back into the
        # ring at slot (pos + i) % window.  ``ring_len`` may be < window
        # when the serving layer bucket-sliced a not-yet-wrapped ring.
        ring_len = cache["k"].shape[1]
        posv = jnp.broadcast_to(jnp.atleast_1d(pos), (b,)).astype(jnp.int32)
        if chunk_mask is not None:
            chunk_len = jnp.sum(chunk_mask, axis=1).astype(jnp.int32)
        else:
            chunk_len = jnp.full((b,), s, jnp.int32)
        kcat = jnp.concatenate([cache["k"].astype(k.dtype), k], axis=1)
        vcat = jnp.concatenate([cache["v"].astype(v.dtype), v], axis=1)
        kcr = constrain(_repeat_kv(kcat, kv_repeat),
                        ("batch", "kv_seq", "kv_heads", None))
        vcr = constrain(_repeat_kv(vcat, kv_repeat),
                        ("batch", "kv_seq", "kv_heads", None))
        o = _full_seq_attn(q, kcr, vcr, a, causal=a.causal, window=window,
                           q_offset=posv, kv_wrap=posv, ring_len=ring_len)
        # ring write as a gather: slot j takes the LAST valid chunk token
        # with (pos + i) % window == j, or keeps its old row.  (A scatter
        # would rely on XLA's unspecified duplicate-index ordering when
        # chunk > window; the gather is deterministic by construction.)
        slot = jnp.arange(ring_len, dtype=jnp.int32)

        def _ring_write(ring, upd, p, ln):
            t = jnp.mod(p + ln - 1 - slot, window)
            i = ln - 1 - t                       # largest valid source idx
            src = jnp.take(upd, jnp.clip(i, 0, s - 1), axis=0)
            return jnp.where((i >= 0)[:, None, None],
                             src.astype(ring.dtype), ring)

        kc = constrain(jax.vmap(_ring_write)(cache["k"], k, posv, chunk_len),
                       ("batch", "kv_seq", "kv_heads", None))
        vc = constrain(jax.vmap(_ring_write)(cache["v"], v, posv, chunk_len),
                       ("batch", "kv_seq", "kv_heads", None))
        new_cache = {"k": kc, "v": vc}
    elif s > 1 and pos is not None:
        # chunked prefill: scatter this chunk's kv at each row's running
        # offset, then attend over the whole cache with the offset causal
        # mask.  Out-of-range positions are dropped; positions past a row's
        # valid length hold garbage that the next chunk overwrites or the
        # decode-time valid_len mask hides.
        skv = cache["k"].shape[1]
        posv = jnp.broadcast_to(jnp.atleast_1d(pos), (b,)).astype(jnp.int32)
        idx = posv[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

        def _scatter_rows(full, upd, ii):
            return full.at[ii].set(upd.astype(full.dtype), mode="drop")

        kc = constrain(jax.vmap(_scatter_rows)(cache["k"], k, idx),
                       ("batch", "kv_seq", "kv_heads", None))
        vc = constrain(jax.vmap(_scatter_rows)(cache["v"], v, idx),
                       ("batch", "kv_seq", "kv_heads", None))
        new_cache = {"k": kc, "v": vc}
        kcr = constrain(_repeat_kv(kc.astype(x.dtype), kv_repeat),
                        ("batch", "kv_seq", "kv_heads", None))
        vcr = constrain(_repeat_kv(vc.astype(x.dtype), kv_repeat),
                        ("batch", "kv_seq", "kv_heads", None))
        o = _full_seq_attn(q, kcr, vcr, a, causal=a.causal, window=window,
                           q_offset=posv)
    elif s > 1:
        # prefill into cache
        o = _full_seq_attn(q, kr, vr, a, causal=a.causal, window=window)
        skv = cache["k"].shape[1]
        if window is not None and skv == window:
            # rolling cache: slot i must hold the token with pos % window == i
            # (decode writes at pos % window), so roll the last-window slice.
            if s >= window:
                kw, vw = k[:, -window:], v[:, -window:]
                shift = (s - window) % window
                kw = jnp.roll(kw, shift, axis=1)
                vw = jnp.roll(vw, shift, axis=1)
            else:
                kw = jnp.pad(k, ((0, 0), (0, window - s), (0, 0), (0, 0)))
                vw = jnp.pad(v, ((0, 0), (0, window - s), (0, 0), (0, 0)))
            new_cache = {"k": kw.astype(cache["k"].dtype),
                         "v": vw.astype(cache["v"].dtype)}
        else:
            # match the cache layout before the write (kv_seq may be
            # sequence-sharded when kv heads don't divide the model axis)
            kw = constrain(k.astype(cache["k"].dtype),
                           ("batch", "kv_seq", "kv_heads", None))
            vw = constrain(v.astype(cache["v"].dtype),
                           ("batch", "kv_seq", "kv_heads", None))
            kfull = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], kw, 0, axis=1)
            vfull = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], vw, 0, axis=1)
            new_cache = {"k": kfull, "v": vfull}
    else:
        # decode step: each batch row writes its new kv at its own position
        # (pos: [B] per-slot counters; scalar pos broadcasts).  Per-row
        # dynamic-slice write so the token touches one cache row, not the
        # whole [Skv] axis; out-of-range rows (retired slots) rewrite their
        # clamped row with its current value, i.e. write nothing.
        skv = cache["k"].shape[1]
        posv = jnp.broadcast_to(jnp.atleast_1d(pos), (b,))
        slot = posv % skv if (window is not None and skv == window) else posv
        ok = (slot >= 0) & (slot < skv)
        slot_c = jnp.clip(slot, 0, skv - 1)

        def _write_row(full, new, start, keep):
            cur = jax.lax.dynamic_slice_in_dim(full, start, 1, axis=0)
            return jax.lax.dynamic_update_slice_in_dim(
                full, jnp.where(keep, new, cur), start, axis=0)

        kc = jax.vmap(_write_row)(cache["k"], k.astype(cache["k"].dtype),
                                  slot_c, ok)
        vc = jax.vmap(_write_row)(cache["v"], v.astype(cache["v"].dtype),
                                  slot_c, ok)
        kc = constrain(kc, ("batch", "kv_seq", "kv_heads", None))
        vc = constrain(vc, ("batch", "kv_seq", "kv_heads", None))
        new_cache = {"k": kc, "v": vc}
        # keep the (possibly sequence-sharded) cache layout through the
        # attention compute: with one query token, GSPMD then runs
        # flash-decode split-S (partial softmax stats + tiny psum) instead
        # of all-gathering the cache to match head sharding.
        kcr = constrain(_repeat_kv(kc.astype(x.dtype), kv_repeat),
                        ("batch", "kv_seq", "kv_heads", None))
        vcr = constrain(_repeat_kv(vc.astype(x.dtype), kv_repeat),
                        ("batch", "kv_seq", "kv_heads", None))
        # all backends route through the flash-decode entry point (the ref
        # backend dispatches to the dense oracle inside).  valid_len clamps
        # to skv, which for rolling caches equals the window — every slot of
        # a wrapped rolling cache is live, partially-filled caches mask the
        # unwritten tail.
        from repro.kernels.attn_decode.ops import decode_attention
        bq, _, nkv_, g_, hd_ = q.shape
        qh = q.reshape(bq, nkv_ * g_, hd_)
        valid = jnp.minimum(posv + 1, skv)
        o = decode_attention(qh, kcr.transpose(0, 2, 1, 3),
                             vcr.transpose(0, 2, 1, 3),
                             valid_len=valid)
        o = o.reshape(bq, 1, nkv_, g_, hd_)

    o = o.reshape(b, s, a.n_heads, a.head_dim)
    with jax.named_scope("o_proj"):
        y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    return constrain(y, ("batch", "seq", "embed")), new_cache


def init_attn_cache(a: AttnConfig, batch: int, max_seq: int, *,
                    kv_repeat: int = 1, window: Optional[int] = None,
                    dtype=jnp.bfloat16) -> Dict:
    # kv_repeat intentionally ignored: the cache always stores the exact
    # (unreplicated) kv heads; replication happens at compute time.
    del kv_repeat
    # Rolling sliding-window caches are always the FULL window, even when
    # max_seq < window: the rolling invariant (slot i holds the token with
    # pos % window == i) needs all window slots, otherwise decode writes
    # past a clamped cache end are silently dropped and attention goes
    # stale the moment pos crosses the clamp.
    skv = window if window is not None else max_seq
    shape = (batch, skv, a.n_kv_heads, a.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
