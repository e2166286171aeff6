"""Pallas TPU kernel for the Mamba-1 selective scan.

TPU adaptation of the GPU kernel's "state stays in SRAM" insight: the
[block_ch, N] state lives in VMEM scratch across sequential sequence-block
grid steps; within a block the recurrence is unrolled (VPU element-wise) —
d_state is small (16) so each step is a [bc, N] fma + a tiny contraction.

Grid: (B, C/block_ch, S/block_seq), sequence innermost (sequential).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, init_ref,
                 y_ref, final_ref, h_s, *, bs: int, ns: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _():
        h_s[...] = init_ref[0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)                 # [bc, N]
    xb = x_ref[0].astype(jnp.float32)                  # [bs, bc]
    dtb = dt_ref[0].astype(jnp.float32)                # [bs, bc]
    bb = b_ref[0].astype(jnp.float32)                  # [bs, N]
    cb = c_ref[0].astype(jnp.float32)                  # [bs, N]
    dsk = d_ref[...].astype(jnp.float32)               # [bc, 1]

    h = h_s[...]
    ys = []
    for t in range(bs):                                # unrolled recurrence
        da = jnp.exp(dtb[t][:, None] * a)              # [bc, N]
        h = h * da + (dtb[t] * xb[t])[:, None] * bb[t][None, :]
        ys.append(jnp.sum(h * cb[t][None, :], axis=1)) # [bc]
    h_s[...] = h
    y = jnp.stack(ys, axis=0) + xb * dsk.T             # [bs, bc]
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(si == ns - 1)
    def _():
        final_ref[0] = h

def selective_scan_pallas(x, dt, A, Bm, Cm, D, *,
                          initial_state: Optional[jax.Array] = None,
                          block_seq: int = 16, block_ch: int = 256,
                          interpret: bool = False
                          ) -> Tuple[jax.Array, jax.Array]:
    b, s, c = x.shape
    n = A.shape[-1]
    if initial_state is None:
        initial_state = jnp.zeros((b, c, n), jnp.float32)
    bs = min(block_seq, s)
    bc = min(block_ch, c)
    assert s % bs == 0 and c % bc == 0, (s, bs, c, bc)
    grid = (b, c // bc, s // bs)
    d2 = D.reshape(c, 1)
    kern = functools.partial(_scan_kernel, bs=bs, ns=s // bs)
    y, final = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bc), lambda bi, ci, si: (bi, si, ci)),
            pl.BlockSpec((1, bs, bc), lambda bi, ci, si: (bi, si, ci)),
            pl.BlockSpec((bc, n), lambda bi, ci, si: (ci, 0)),
            pl.BlockSpec((1, bs, n), lambda bi, ci, si: (bi, si, 0)),
            pl.BlockSpec((1, bs, n), lambda bi, ci, si: (bi, si, 0)),
            pl.BlockSpec((bc, 1), lambda bi, ci, si: (ci, 0)),
            pl.BlockSpec((1, bc, n), lambda bi, ci, si: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bc), lambda bi, ci, si: (bi, si, ci)),
            pl.BlockSpec((1, bc, n), lambda bi, ci, si: (bi, ci, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, c), x.dtype),
            jax.ShapeDtypeStruct((b, c, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bc, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, Bm, Cm, d2, initial_state)
    return y, final
