"""Pallas TPU kernel for the chunked Mamba-2 SSD scan.

TPU adaptation of the GPU `mamba_split_conv1d_scan_combined` insight
("minimize HBM I/O"): one pass over the sequence, chunk working set held in
VMEM, intra-chunk math expressed as dense matmuls on the MXU
(C·Bᵀ ⊙ decay) · (Δ⊙X), and the inter-chunk recurrence carried across
sequential grid steps in a VMEM scratch accumulator.

Grid: (B, H, S/chunk) — the chunk dimension is innermost and iterated
sequentially by the TPU, so the [P, N] state scratch is a legal carry.

Layout: Mosaic tiles the last two dims of every block, which must be
(8, 128)-aligned or span the whole array dim.  A per-head block of the
natural [B, S, H, P] layout has 1 in the head axis (second from last), so
the wrapper lays operands out head-major ([B, H, S, P]), group-major for
B/C ([B, G, S, N]), and dt as one [1, S] row per head ([B, H, 1, S]).  The
per-head scalars A and D ride whole in SMEM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, init_ref,
                y_ref, final_ref, state, *, nc: int, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        state[...] = init_ref[0, 0].astype(jnp.float32)

    xb = x_ref[0, 0].astype(jnp.float32)              # [Q, P]
    dt_row = dt_ref[0, 0].astype(jnp.float32)         # [1, Q]
    a = a_ref[hi]                                     # scalar (SMEM)
    bb = b_ref[0, 0].astype(jnp.float32)              # [Q, N]
    cb = c_ref[0, 0].astype(jnp.float32)              # [Q, N]
    dskip = d_ref[hi]

    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    da_col = (dt_row * a).T                           # [Q, 1] log-decay steps
    # inclusive cumsum as a masked sublane reduction (Mosaic has no cumsum)
    cum_row = jnp.sum(jnp.where(qi <= kj, da_col, 0.0), axis=0,
                      keepdims=True)                  # [1, Q]
    cum = cum_row.T                                   # [Q, 1]
    # intra-chunk: (C Bᵀ ⊙ L) (Δ ⊙ X)
    seg = cum - cum_row                               # [Q, Q] cum_i - cum_j
    lmat = jnp.where(qi >= kj, jnp.exp(seg), 0.0)     # [Q, Q]
    scores = jax.lax.dot(cb, bb.T,
                         preferred_element_type=jnp.float32) * lmat
    dtx = dt_row.T * xb                               # [Q, P]
    y = jax.lax.dot(scores, dtx, preferred_element_type=jnp.float32)
    # inter-chunk: C · state_in, decayed from chunk start
    y = y + jnp.exp(cum) * jax.lax.dot(cb, state[...].T,
                                       preferred_element_type=jnp.float32)
    y = y + dskip * xb
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: state_out = state_in * e^{cum_last} + (Δ X ⊙ d2e)ᵀ B
    last = cum_row[:, chunk - 1:chunk]                # [1, 1]
    d2e = jnp.exp(last - cum)                         # [Q, 1]
    state[...] = (state[...] * jnp.exp(last)
                  + jax.lax.dot_general(dtx * d2e, bb,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))

    @pl.when(ci == nc - 1)
    def _():
        final_ref[0, 0] = state[...]


def ssd_pallas(x, dt, A, Bm, Cm, D, *, chunk: int = 128,
               initial_state: Optional[jax.Array] = None,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)
    heads_per_group = h // g
    xt = x.transpose(0, 2, 1, 3)                      # [B, H, S, P]
    dtt = dt.reshape(b, s, h).transpose(0, 2, 1)[:, :, None, :]  # [B,H,1,S]
    bt = Bm.transpose(0, 2, 1, 3)                     # [B, G, S, N]
    ct = Cm.transpose(0, 2, 1, 3)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    kern = functools.partial(_ssd_kernel, nc=nc, chunk=chunk)
    y, final = pl.pallas_call(
        kern,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            smem,
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi // heads_per_group, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi // heads_per_group, ci, 0)),
            smem,
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, A.astype(jnp.float32), bt, ct, D.astype(jnp.float32),
      initial_state)
    return y.transpose(0, 2, 1, 3), final
