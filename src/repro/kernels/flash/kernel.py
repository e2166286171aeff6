"""Pallas TPU flash-attention (prefill) kernel.

Online-softmax over KV blocks with (m, l, acc) carried in VMEM scratch
across the sequential innermost grid dimension.  Causal and sliding-window
masks are evaluated per block; fully-masked blocks are skipped with
``pl.when`` (predicated off on TPU — no MXU work issued).

Live-prefix contract (chunked prefill + KV bucketing): the grid's batch
dimension makes the causal block-skip *per row* — row b's chunk at offset
``q_offset[b]`` skips every KV block past ``q_offset[b] + bq - 1``, so a
short-prefix row in a mixed-length group never reads the long row's KV
blocks, and rows read at most their own live prefix even before the
serving layer slices the cache to the bucket.  The bucket (static ``Skv``)
then bounds what is *resident*, the skip bounds what is *touched*.

Ring-buffer contract (chunked prefill over rolling sliding-window caches):
with the static ``ring_len`` set, the first ``ring_len`` KV slots are a
ring with modulus ``window`` and per-row write cursor ``kv_wrap[b]``
(a second SMEM scalar riding next to ``q_offset``); the remaining slots
are the in-flight chunk at absolute positions ``kv_wrap[b] + (j -
ring_len)``.  The kernel recovers each slot's absolute position with the
modular formula and masks causally against it — the ring is unrolled
in-mask, never materialized as a rolled copy.  Block-skip: chunk-tail
coverage keeps the causal skip on its absolute positions; ring coverage
runs unless it lies entirely past an unwrapped cursor (slot order is not
position order, so no other ring skip is sound).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(qoff_ref, kvwrap_ref, q_ref, k_ref, v_ref, o_ref,
                  m_s, l_s, acc_s, *,
                  bq: int, bk: int, nk: int, causal: bool,
                  window: Optional[int], scale: float, kv_len: int,
                  ring_len: Optional[int]):
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # per-row query offset (chunked prefill); zeros for plain prefill
    q_start = qi * bq + qoff_ref[bi]
    k_start = ki * bk
    # block-level skip: k block entirely in the future (causal) or entirely
    # out of the attention window
    run = True
    if ring_len is None:
        if causal:
            run = k_start <= q_start + bq - 1
        if window is not None:
            run = jnp.logical_and(run, (q_start - (k_start + bk - 1)) < window)
    else:
        # ring slots run only if any was ever written: slot order !=
        # position order, but an unwrapped ring (wrap < window) has
        # written exactly slots [0, wrap), so ring coverage fully past
        # the cursor is dead.  Chunk-tail coverage keeps the causal skip
        # on its absolute positions.  A block may span both regions —
        # either live half forces it to run.
        wrap = kvwrap_ref[bi]
        ring_live = jnp.logical_and(
            k_start < ring_len,
            jnp.logical_or(wrap >= window, k_start < wrap))
        tail_first = wrap + jnp.maximum(k_start - ring_len, 0)
        tail_live = jnp.logical_and(k_start + bk > ring_len,
                                    tail_first <= q_start + bq - 1)
        run = jnp.logical_or(ring_live, tail_live)

    @pl.when(run)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        jidx = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if ring_len is None:
            kpos = jidx
            mask = jidx < kv_len
        else:
            wrap = kvwrap_ref[bi]
            ring_pos = wrap - 1 - jnp.mod(wrap - 1 - jidx, window)
            tail_pos = wrap + (jidx - ring_len)
            kpos = jnp.where(jidx < ring_len, ring_pos, tail_pos)
            # kpos < 0 marks never-written ring slots
            mask = (jidx < kv_len) & (kpos >= 0)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[...]                              # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_new
        acc_s[...] = (acc_s[...] * corr
                      + jax.lax.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32))

    @pl.when(ki == nk - 1)
    def _():
        o_ref[0, 0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-37)
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           q_offset=None,
                           kv_wrap=None, ring_len: Optional[int] = None,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q: [B, H, Sq, d]; k, v: [B, KVH, Skv, d] -> [B, H, Sq, d].

    ``q_offset`` (None, scalar, or [B] int32) shifts the causal/window mask
    per batch row: query i of row b sits at absolute position
    ``q_offset[b] + i`` (chunked prefill against a KV cache that already
    holds earlier chunks).  The offsets ride in SMEM; the block-skip
    predicate folds them in, so fully-masked KV blocks are still skipped.

    ``kv_wrap`` ([B] int32 write cursors) + static ``ring_len`` switch the
    first ``ring_len`` KV slots into a ring buffer with modulus ``window``
    (see module docstring) — the layout used when a chunk prefills against
    a rolling sliding-window cache."""
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    if ring_len is not None:
        assert causal and window is not None and kv_wrap is not None, \
            "ring KV layout requires causal attention, a window and kv_wrap"
    if q_offset is None:
        q_offset = 0
    qoff = jnp.broadcast_to(jnp.atleast_1d(
        jnp.asarray(q_offset, jnp.int32)), (b,))
    kwrap = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(
        0 if kv_wrap is None else kv_wrap, jnp.int32)), (b,))
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pad_q = (-sq) % bq
    pad_k = (-skv) % bk
    kv_len = skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[2] // bq
    nk = k.shape[2] // bk
    gsz = h // kvh
    kern = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window,
        scale=1.0 / math.sqrt(d), kv_len=kv_len, ring_len=ring_len)
    out = pl.pallas_call(
        kern,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // gsz, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi // gsz, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qoff, kwrap, q, k, v)
    return out[:, :, :sq] if pad_q else out
