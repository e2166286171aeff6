"""Pallas TPU flash-decode kernel: one query token vs a long KV cache.

Memory-bound by design (the paper's decode-phase bottleneck): each KV block
is streamed HBM->VMEM exactly once; the GQA query group [G, d] stays
resident; (m, l, acc) carried in VMEM scratch over the sequential KV-block
grid dimension.

Two long-KV provisions:

* **Per-row early-exit past ``valid_len``**: a KV block whose start lies at
  or beyond the row's live prefix is predicated off with ``pl.when`` — no
  MXU work and no VMEM traffic is issued for the dead tail, so a row at
  pos 1K inside a 64K cache reads ~1K rows, not 64K.
* **Split-K partial-softmax reduction**: the KV axis is divided into
  ``split_k`` independent segments that run under a *parallel* grid
  dimension, each emitting unnormalised partials ``(acc, m, l)``; a cheap
  jnp epilogue merges them with the standard online-softmax combine.  For
  long KV this turns one serial O(S) walk into ``split_k`` concurrent
  O(S/split_k) walks (flash-decoding), which is what keeps a single query
  token from under-utilising the chip at the paper's 57K+ contexts.
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


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, om_ref, ol_ref,
                   m_s, l_s, acc_s, *, bs: int, ns: int, scale: float):
    """Grid (B, KVH, split_k, ns): the last dim walks this split's KV blocks
    sequentially; splits/batch/heads are parallel.  Emits this split's
    unnormalised partials; the wrapper merges across splits."""
    sp = pl.program_id(2)
    si = pl.program_id(3)

    @pl.when(si == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    valid = len_ref[pl.program_id(0)]
    # early-exit: this block starts at or past the row's live prefix
    run = (sp * ns + si) * bs < valid

    @pl.when(run)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)            # [G, d]
        k = k_ref[0, 0, 0].astype(jnp.float32)         # [bs, d]
        v = v_ref[0, 0, 0].astype(jnp.float32)         # [bs, d]
        s = jax.lax.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kpos = ((sp * ns + si) * bs
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        s = jnp.where(kpos < valid, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_new
        acc_s[...] = (acc_s[...] * corr
                      + jax.lax.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32))

    @pl.when(si == ns - 1)
    def _():
        o_ref[0, 0, 0] = acc_s[...]
        om_ref[0, 0, 0] = m_s[...]
        ol_ref[0, 0, 0] = l_s[...]


def decode_attention_pallas(q, k, v, *, valid_len, block_s: int = 1024,
                            split_k: Optional[int] = None,
                            interpret: bool = False) -> jax.Array:
    """q: [B, H, d]; k, v: [B, KVH, S, d]; valid_len: scalar or [B].

    ``split_k`` (None = auto) partitions the KV axis into that many
    parallel partial-softmax segments; outputs are identical for every
    value (the combine is the exact online-softmax merge)."""
    b, h, d = q.shape
    kvh, s = k.shape[1], k.shape[2]
    g = h // kvh
    bs = min(block_s, s)
    nb = -(-s // bs)
    if split_k is None:
        # one extra segment per 4 KV blocks, capped: short caches stay
        # serial (no combine overhead), long caches fan out
        split_k = max(1, min(8, nb // 4))
    split_k = min(split_k, nb)
    ns = -(-nb // split_k)                       # blocks per split
    pad = split_k * ns * bs - s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vl = jnp.broadcast_to(jnp.asarray(valid_len, jnp.int32), (b,))
    qg = q.reshape(b, kvh, g, d)
    kern = functools.partial(_decode_kernel, bs=bs, ns=ns,
                             scale=1.0 / math.sqrt(d))
    acc, m, l = pl.pallas_call(
        kern,
        grid=(b, kvh, split_k, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, sp, si: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, bs, d),
                         lambda bi, hi, sp, si: (bi, hi, sp, si, 0)),
            pl.BlockSpec((1, 1, 1, bs, d),
                         lambda bi, hi, sp, si: (bi, hi, sp, si, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, g, d),
                         lambda bi, hi, sp, si: (bi, hi, sp, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda bi, hi, sp, si: (bi, hi, sp, 0, 0)),
            pl.BlockSpec((1, 1, 1, g, 1),
                         lambda bi, hi, sp, si: (bi, hi, sp, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, split_k, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, split_k, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, split_k, g, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(vl, qg, k.reshape(b, kvh, split_k, ns * bs, d),
      v.reshape(b, kvh, split_k, ns * bs, d))
    # exact online-softmax merge of the split partials (empty splits carry
    # m = NEG_INF, l = 0 and vanish; NEG_INF is finite, so no inf - inf)
    m_all = jnp.max(m, axis=2, keepdims=True)              # [B,KVH,1,G,1]
    alpha = jnp.exp(m - m_all)
    l_all = jnp.sum(l * alpha, axis=2)                     # [B,KVH,G,1]
    out = jnp.sum(acc * alpha, axis=2) / jnp.maximum(l_all, 1e-37)
    return out.astype(q.dtype).reshape(b, h, d)
