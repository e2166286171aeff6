"""Pallas TPU kernels for the fused decode step (conv shift + SSM update).

The decode hot loop is memory-bound: per token each Mamba layer must read
and rewrite its conv window and recurrent state.  Run eagerly, that is
four HBM round-trips (conv read/write, state read/write) plus the
intermediate dA/dBx tensors.  These kernels follow the paper's
"minimize HBM I/O, keep state resident" discipline: one grid step per
batch row pulls the row's working set into VMEM once, performs

  conv window shift -> silu -> (projections) -> softplus(dt)
  h' = h * exp(dt*A) + dt * B * x      y = C . h' + D * x

in-register, and writes back only the new state and y (plus, for
Mamba-1, the new window).

The Mamba-2 step runs on a (B, H/hb) grid: one batch row and a block of
``hb`` heads per step, so the [hb, P, N] f32 state block stays small in
VMEM at real widths (h=80, p=64, n=128) and the state stream is
pipelined.  Mosaic cannot reshape lanes into sublanes in-kernel, so the
wrapper presents every operand in the layout the kernel computes in: the
x channels as [.., H, P], B|C per group as one [.., 2N] row, the per-head
vectors as [H, 1] columns.  The new conv window is the shifted input
window; the wrapper forms it (a copy, no arithmetic).
The Mamba-1 step runs one grid step per batch row.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _conv_step(conv_ref, x_ref, w_ref, b_ref):
    """Shared conv shift step: returns (activated [1, C] f32, window [K, C])."""
    window = jnp.concatenate([conv_ref[0].astype(jnp.float32),
                              x_ref[...].astype(jnp.float32)], axis=0)
    w = w_ref[...].astype(jnp.float32)                 # [C, K]
    y = jnp.sum(window * w.T, axis=0, keepdims=True)   # [1, C]
    y = y + b_ref[...].astype(jnp.float32).reshape(1, -1)
    y = y * jax.nn.sigmoid(y)                          # silu
    return y, window


def _conv_silu(conv, x, w, b, dtype):
    """Depthwise conv over the [K-1, ...] window + the new token ([1, ...]),
    taps on the leading axis; silu, rounded to the input dtype like the
    oracle's conv boundary."""
    window = jnp.concatenate([conv.astype(jnp.float32),
                              x.astype(jnp.float32)], axis=0)
    y = jnp.sum(window * w.astype(jnp.float32), axis=0)
    y = y + b.astype(jnp.float32)
    y = y * jax.nn.sigmoid(y)                          # silu
    return y.astype(dtype).astype(jnp.float32)


def _m2_kernel(cx_ref, cbc_ref, xt_ref, xbc_ref, wx_ref, wbc_ref, bx_ref,
               bbc_ref, dt_ref, dtb_ref, al_ref, d_ref, ssm_ref, y_ref,
               nssm_ref, *, n: int):
    dtype = xt_ref.dtype
    xs = _conv_silu(cx_ref[0], xt_ref[...], wx_ref[...], bx_ref[...],
                    dtype)                             # [hb, P]
    bc = _conv_silu(cbc_ref[0, 0], xbc_ref[0, 0], wbc_ref[0], bbc_ref[0],
                    dtype)                             # [1, 2N]
    bm, cm = bc[:, :n], bc[:, n:]                      # [1, N] each
    dt = jax.nn.softplus(dt_ref[0].astype(jnp.float32)
                         + dtb_ref[...].astype(jnp.float32))   # [hb, 1]
    a = -jnp.exp(al_ref[...].astype(jnp.float32))     # [hb, 1]
    da = jnp.exp(dt * a)                               # [hb, 1]
    upd = (dt * bm)[:, None, :] * xs[:, :, None]       # [hb, P, N]
    hnew = ssm_ref[0] * da[:, :, None] + upd
    y = jnp.sum(hnew * cm[None], axis=-1)              # [hb, P]
    y = y + xs * d_ref[...].astype(jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    nssm_ref[0] = hnew


def _head_block(heads_per_group: int) -> int:
    """Heads per grid step: 8 (the f32 sublane tile) when a group's heads
    split into 8s, else one group's heads — a block never spans groups."""
    return 8 if heads_per_group % 8 == 0 else heads_per_group


def mamba2_decode_fused_pallas(conv_state, ssm_state, xbc_t, conv_w, conv_b,
                               dt_raw, dt_bias, A_log, D, *, n_groups: int,
                               d_state: int, headdim: int,
                               interpret: bool = False
                               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, km1, c = conv_state.shape
    k = km1 + 1
    g, n, p = n_groups, d_state, headdim
    di = c - 2 * g * n
    h = di // p
    hb = _head_block(h // g)
    grp = h // g // hb                                 # head blocks per group

    def bc_groups(t):
        """[..., 2*G*N] (B then C) -> [..., G, 2N] (each group's B|C row)."""
        lead = t.shape[:-1]
        t = t.reshape(*lead, 2, g, n)
        t = jnp.moveaxis(t, -3, -2)
        return t.reshape(*lead, g, 2 * n)

    cx = conv_state[..., :di].reshape(b, km1, h, p)
    cbc = jnp.moveaxis(bc_groups(conv_state[..., di:]), 2, 1)  # [B,G,K-1,2N]
    xt = xbc_t[:, :di].reshape(b, h, p)
    xbc = bc_groups(xbc_t[:, di:])[:, :, None, :]            # [B, G, 1, 2N]
    wt = conv_w.T                                            # [K, C]
    wx = wt[:, :di].reshape(k, h, p)
    wbc = jnp.moveaxis(bc_groups(wt[:, di:]), 1, 0)          # [G, K, 2N]
    bx = conv_b[:di].reshape(h, p)
    bbc = bc_groups(conv_b[di:])[:, None, :]                 # [G, 1, 2N]
    col = lambda v: v.reshape(h, 1)                          # noqa: E731

    y, nssm = pl.pallas_call(
        functools.partial(_m2_kernel, n=n),
        grid=(b, h // hb),
        in_specs=[
            pl.BlockSpec((1, km1, hb, p), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, 1, km1, 2 * n),
                         lambda bi, hi: (bi, hi // grp, 0, 0)),
            pl.BlockSpec((1, hb, p), lambda bi, hi: (bi, hi, 0)),
            pl.BlockSpec((1, 1, 1, 2 * n),
                         lambda bi, hi: (bi, hi // grp, 0, 0)),
            pl.BlockSpec((k, hb, p), lambda bi, hi: (0, hi, 0)),
            pl.BlockSpec((1, k, 2 * n), lambda bi, hi: (hi // grp, 0, 0)),
            pl.BlockSpec((hb, p), lambda bi, hi: (hi, 0)),
            pl.BlockSpec((1, 1, 2 * n), lambda bi, hi: (hi // grp, 0, 0)),
            pl.BlockSpec((1, hb, 1), lambda bi, hi: (bi, hi, 0)),
            pl.BlockSpec((hb, 1), lambda bi, hi: (hi, 0)),
            pl.BlockSpec((hb, 1), lambda bi, hi: (hi, 0)),
            pl.BlockSpec((hb, 1), lambda bi, hi: (hi, 0)),
            pl.BlockSpec((1, hb, p, n), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, p), lambda bi, hi: (bi, hi, 0)),
            pl.BlockSpec((1, hb, p, n), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, p), xbc_t.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(cx, cbc, xt, xbc, wx, wbc, bx, bbc, dt_raw.reshape(b, h, 1),
      col(dt_bias), col(A_log), col(D), ssm_state)
    nconv = jnp.concatenate([conv_state, xbc_t[:, None, :]], axis=1)[:, 1:]
    return y, nconv, nssm


def _m1_kernel(conv_ref, x_ref, w_ref, b_ref, xp_ref, dtp_ref, dtb_ref,
               al_ref, d_ref, ssm_ref, y_ref, nconv_ref, nssm_ref, *,
               di: int, n: int, dtr: int):
    xi, window = _conv_step(conv_ref, x_ref, w_ref, b_ref)
    xi = xi.astype(x_ref.dtype).astype(jnp.float32)    # [1, di]
    proj = jax.lax.dot(xi, xp_ref[...].astype(jnp.float32),
                       preferred_element_type=jnp.float32)  # [1, dtr+2N]
    # the ref emits the projections in the input dtype — round to match
    proj = proj.astype(x_ref.dtype).astype(jnp.float32)
    dt_low = proj[:, :dtr]
    bm = proj[:, dtr:dtr + n]                          # [1, N]
    cm = proj[:, dtr + n:]                             # [1, N]
    dt_in = jax.lax.dot(dt_low, dtp_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    dt_in = dt_in.astype(x_ref.dtype).astype(jnp.float32)
    dt = jax.nn.softplus(
        dt_in + dtb_ref[...].astype(jnp.float32).reshape(1, -1))  # [1, di]
    a = -jnp.exp(al_ref[...].astype(jnp.float32))      # [di, N]
    dA = jnp.exp(dt.T * a)                             # [di, N]
    dBx = (dt * xi).T * bm                             # [di, N]
    hnew = ssm_ref[0] * dA + dBx
    y = jnp.sum(hnew * cm, axis=-1, keepdims=True).T   # [1, di]
    y = y + xi * d_ref[...].astype(jnp.float32).reshape(1, -1)
    y_ref[...] = y
    nssm_ref[0] = hnew
    nconv_ref[0] = window[1:].astype(nconv_ref.dtype)


def mamba1_decode_fused_pallas(conv_state, ssm_state, xi_t, conv_w, conv_b,
                               x_proj, dt_proj, dt_bias, A_log, D, *,
                               d_state: int, dt_rank: int,
                               interpret: bool = False
                               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, km1, di = conv_state.shape
    k = km1 + 1
    n, dtr = d_state, dt_rank
    f = dtr + 2 * n
    kern = functools.partial(_m1_kernel, di=di, n=n, dtr=dtr)
    y, nconv, nssm = pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, k - 1, di), lambda bi: (bi, 0, 0)),
            pl.BlockSpec((1, di), lambda bi: (bi, 0)),
            pl.BlockSpec((di, k), lambda bi: (0, 0)),
            pl.BlockSpec((di,), lambda bi: (0,)),
            pl.BlockSpec((di, f), lambda bi: (0, 0)),
            pl.BlockSpec((dtr, di), lambda bi: (0, 0)),
            pl.BlockSpec((di,), lambda bi: (0,)),
            pl.BlockSpec((di, n), lambda bi: (0, 0)),
            pl.BlockSpec((di,), lambda bi: (0,)),
            pl.BlockSpec((1, di, n), lambda bi: (bi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, di), lambda bi: (bi, 0)),
            pl.BlockSpec((1, k - 1, di), lambda bi: (bi, 0, 0)),
            pl.BlockSpec((1, di, n), lambda bi: (bi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, di), jnp.float32),
            jax.ShapeDtypeStruct((b, k - 1, di),
                                 jnp.result_type(conv_state.dtype,
                                                 xi_t.dtype)),
            jax.ShapeDtypeStruct((b, di, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(conv_state, xi_t, conv_w, conv_b, x_proj, dt_proj, dt_bias, A_log, D,
      ssm_state)
    return y, nconv, nssm
