"""Pallas TPU kernel for causal depthwise conv1d (streaming, halo carried
in VMEM scratch across sequential sequence blocks).

Channels ride the 128-lane axis: the weight is passed tap-major ([K, C])
and the bias as a [1, C] row, so every block's last dim is a channel block
(a multiple of 128, or all of C)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _conv_kernel(x_ref, w_ref, b_ref, init_ref, y_ref, carry, *,
                 k: int, bs: int, silu: bool):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _():
        carry[...] = init_ref[0].astype(jnp.float32)

    xb = x_ref[0].astype(jnp.float32)                  # [bs, bc]
    full = jnp.concatenate([carry[...], xb], axis=0)   # [bs+k-1, bc]
    w = w_ref[...].astype(jnp.float32)                 # [k, bc]
    y = jnp.zeros_like(xb)
    for i in range(k):
        y = y + full[i:i + bs, :] * w[i:i + 1, :]
    y = y + b_ref[...].astype(jnp.float32)             # [1, bc]
    if silu:
        y = y * jax.nn.sigmoid(y)
    y_ref[0] = y.astype(y_ref.dtype)
    carry[...] = full[bs:, :]


def causal_conv1d_pallas(x, w, b, *, initial_state: Optional[jax.Array] = None,
                         activation: str = "silu", block_seq: int = 512,
                         block_ch: int = 128, interpret: bool = False
                         ) -> Tuple[jax.Array, jax.Array]:
    bsz, s, c = x.shape
    k = w.shape[-1]
    if initial_state is None:
        initial_state = jnp.zeros((bsz, k - 1, c), x.dtype)
    bs = min(block_seq, s)
    # a channel block must be a multiple of 128 lanes or all of C
    bc = block_ch if c % block_ch == 0 else c
    assert s % bs == 0 and c % bc == 0, (s, bs, c, bc)
    grid = (bsz, c // bc, s // bs)

    kern = functools.partial(_conv_kernel, k=k, bs=bs,
                             silu=(activation == "silu"))
    y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, bc), lambda bi, ci, si: (bi, si, ci)),
            pl.BlockSpec((k, bc), lambda bi, ci, si: (0, ci)),
            pl.BlockSpec((1, bc), lambda bi, ci, si: (0, ci)),
            pl.BlockSpec((1, k - 1, bc), lambda bi, ci, si: (bi, 0, ci)),
        ],
        out_specs=pl.BlockSpec((1, bs, bc), lambda bi, ci, si: (bi, si, ci)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((k - 1, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w.T, b.reshape(1, c), initial_state)
    xp = jnp.concatenate([initial_state.astype(x.dtype), x], axis=1)
    new_state = xp[:, s:, :]
    return y, new_state.astype(x.dtype)
