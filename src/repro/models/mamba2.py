"""Mamba-2 (SSD) block — in/out projections + conv1d + chunked SSD core."""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import SSMConfig
from repro.distributed.sharding import constrain
from repro.kernels.conv1d.ops import causal_conv1d
from repro.kernels.decode_fused.ops import mamba2_decode_fused
from repro.kernels.ssd.ops import ssd_chunked_raw
from repro.models.norms import gated_rms_norm, scale_by
from repro.models.params import ParamDef


def mamba2_param_defs(d_model: int, s: SSMConfig) -> Dict[str, ParamDef]:
    di = s.d_inner(d_model)
    nh = s.n_ssm_heads(d_model)
    gn = s.n_groups * s.d_state
    conv_dim = di + 2 * gn
    return {
        "wz": ParamDef((d_model, di), ("embed", "conv_dim"), fan_in=d_model),
        "wxBC": ParamDef((d_model, conv_dim), ("embed", "conv_dim"), fan_in=d_model),
        "wdt": ParamDef((d_model, nh), ("embed", "ssm_heads"), fan_in=d_model),
        "conv_w": ParamDef((conv_dim, s.conv_kernel), ("conv_dim", None),
                           fan_in=s.conv_kernel),
        "conv_b": ParamDef((conv_dim,), ("conv_dim",), init="zeros"),
        "A_log": ParamDef((nh,), ("ssm_heads",), init="a_log"),
        "D": ParamDef((nh,), ("ssm_heads",), init="ones"),
        "dt_bias": ParamDef((nh,), ("ssm_heads",), init="dt_bias"),
        "norm_scale": ParamDef((di,), ("conv_dim",), init="zeros"),
        "out_proj": ParamDef((di, d_model), ("conv_dim", "embed"),
                             init="normal_out", fan_in=di),
    }


def masked_conv_state(init_state: Optional[jax.Array], x_in: jax.Array,
                      mask: jax.Array, k: int) -> jax.Array:
    """Conv state after a ragged chunk: the trailing ``k-1`` *valid* inputs
    per row.  Valid tokens are a left-aligned prefix of the chunk (length
    ``mask.sum(1)``), so the window ends at that length, not at the padded
    chunk end.  x_in: [B, S, C] pre-conv inputs; mask: [B, S] bool."""
    b, _, c = x_in.shape
    if k <= 1:
        return jnp.zeros((b, 0, c), x_in.dtype)
    if init_state is None:
        init_state = jnp.zeros((b, k - 1, c), x_in.dtype)
    src = jnp.concatenate([init_state.astype(x_in.dtype), x_in], axis=1)
    lens = jnp.sum(mask, axis=1).astype(jnp.int32)
    return jax.vmap(
        lambda row, l: jax.lax.dynamic_slice_in_dim(row, l, k - 1, axis=0)
    )(src, lens)


def _split_xbc(xbc: jax.Array, s: SSMConfig, d_model: int):
    di = s.d_inner(d_model)
    gn = s.n_groups * s.d_state
    xs = xbc[..., :di]
    bm = xbc[..., di:di + gn]
    cm = xbc[..., di + gn:]
    lead = xbc.shape[:-1]
    return (xs, bm.reshape(*lead, s.n_groups, s.d_state),
            cm.reshape(*lead, s.n_groups, s.d_state))


def _scale_in_proj(z, xbc, dt_raw, zxbcdt: Sequence[float], s: SSMConfig,
                   d_model: int):
    """Falcon-H1's muP vector over the in-projection: one multiplier each
    for the z, x, B, C and dt slices."""
    mz, mx, mb, mc, mdt = zxbcdt
    gn = s.n_groups * s.d_state
    vec = np.concatenate([np.full(s.d_inner(d_model), mx, np.float32),
                          np.full(gn, mb, np.float32),
                          np.full(gn, mc, np.float32)])
    return scale_by(z, mz), scale_by(xbc, vec), scale_by(dt_raw, mdt)


def mamba2_block(p: Dict, x: jax.Array, s: SSMConfig, d_model: int, *,
                 cache: Optional[Dict] = None, eps: float = 1e-5,
                 mask: Optional[jax.Array] = None,
                 zxbcdt: Optional[Sequence[float]] = None
                 ) -> Tuple[jax.Array, Optional[Dict]]:
    """Full-sequence pass. If cache is given (prefill), returns final states.

    ``mask`` ([B, S] bool, chunked prefill): rows whose valid tokens are a
    left-aligned prefix.  Invalid tokens are inert — their dt is driven to
    zero (state passes through unchanged) and the conv state is rebuilt
    from the trailing *valid* inputs, so the returned states equal those of
    a prefill over only the valid prefix.

    ``zxbcdt`` (five floats, or None for none): muP multipliers of the
    in-projection's z, x, B, C and dt slices (Falcon-H1)."""
    b, seq, _ = x.shape
    di = s.d_inner(d_model)
    nh = s.n_ssm_heads(d_model)
    dt_ = x.dtype
    with jax.named_scope("ssm_in_proj"):
        z = jnp.einsum("bsd,de->bse", x, p["wz"].astype(dt_))
        xbc = jnp.einsum("bsd,de->bse", x, p["wxBC"].astype(dt_))
        dt_raw = jnp.einsum("bsd,dh->bsh", x, p["wdt"].astype(dt_))
        if zxbcdt is not None:
            z, xbc, dt_raw = _scale_in_proj(z, xbc, dt_raw, zxbcdt, s,
                                            d_model)
    xbc = constrain(xbc, ("batch", "seq", "conv_dim"))
    if mask is not None:
        # -30 ⇒ softplus -> 0 ⇒ invalid tokens update no SSM state
        dt_raw = jnp.where(mask[:, :, None], dt_raw, -30.0)
    xbc_in = xbc
    init_conv = cache["conv"] if cache is not None else None
    xbc, conv_state = causal_conv1d(xbc, p["conv_w"], p["conv_b"],
                                    initial_state=init_conv)
    if cache is not None and mask is not None:
        conv_state = masked_conv_state(init_conv, xbc_in, mask, s.conv_kernel)
    xs, bm, cm = _split_xbc(xbc, s, d_model)
    xh = constrain(xs.reshape(b, seq, nh, s.headdim),
                   ("batch", "seq", "ssm_heads", None))

    # pad sequence to a chunk multiple; padded dt_raw = -inf ⇒ softplus->0
    # ⇒ padded tokens are inert
    pad = (-seq) % s.chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt_raw = jnp.pad(dt_raw, ((0, 0), (0, pad), (0, 0)),
                         constant_values=-30.0)
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    init_ssm = cache["ssm"] if cache is not None else None
    y, ssm_state = ssd_chunked_raw(xh, dt_raw, p["dt_bias"], p["A_log"],
                                   bm, cm, p["D"], chunk=s.chunk,
                                   initial_state=init_ssm)
    y = y[:, :seq].reshape(b, seq, di)
    y = constrain(y, ("batch", "seq", "conv_dim"))
    y = gated_rms_norm(y, z, p["norm_scale"], eps, groups=s.n_groups)
    with jax.named_scope("ssm_out_proj"):
        out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(dt_))
    out = constrain(out, ("batch", "seq", "embed"))
    new_cache = None
    if cache is not None:
        new_cache = {"conv": conv_state.astype(cache["conv"].dtype),
                     "ssm": ssm_state.astype(cache["ssm"].dtype)}
    return out, new_cache


def mamba2_decode(p: Dict, x: jax.Array, s: SSMConfig, d_model: int, *,
                  cache: Dict, eps: float = 1e-5,
                  zxbcdt: Optional[Sequence[float]] = None
                  ) -> Tuple[jax.Array, Dict]:
    """Single-token step. x: [B, 1, D]; cache: {"conv": [B,K-1,C], "ssm": [B,H,P,N]}.
    Conv shift + state update run as one fused decode kernel.  ``zxbcdt``
    as in :func:`mamba2_block`."""
    b = x.shape[0]
    di = s.d_inner(d_model)
    dt_ = x.dtype
    xt = x[:, 0]
    with jax.named_scope("ssm_in_proj"):
        z = xt @ p["wz"].astype(dt_)
        xbc = xt @ p["wxBC"].astype(dt_)
        dt_raw = xt @ p["wdt"].astype(dt_)
        if zxbcdt is not None:
            z, xbc, dt_raw = _scale_in_proj(z, xbc, dt_raw, zxbcdt, s,
                                            d_model)
    y, conv_state, ssm_state = mamba2_decode_fused(
        cache["conv"], cache["ssm"], xbc, p["conv_w"], p["conv_b"],
        dt_raw, p["dt_bias"], p["A_log"], p["D"],
        n_groups=s.n_groups, d_state=s.d_state, headdim=s.headdim)
    y = y.reshape(b, di)
    y = gated_rms_norm(y[:, None, :], z[:, None, :], p["norm_scale"], eps,
                       groups=s.n_groups)[:, 0]
    with jax.named_scope("ssm_out_proj"):
        out = (y @ p["out_proj"].astype(dt_))[:, None, :]
    return out, {"conv": conv_state.astype(cache["conv"].dtype),
                 "ssm": ssm_state.astype(cache["ssm"].dtype)}


def init_mamba2_cache(d_model: int, s: SSMConfig, batch: int,
                      dtype=jnp.bfloat16) -> Dict:
    di = s.d_inner(d_model)
    nh = s.n_ssm_heads(d_model)
    conv_dim = di + 2 * s.n_groups * s.d_state
    return {
        "conv": jnp.zeros((batch, s.conv_kernel - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, nh, s.headdim, s.d_state), jnp.float32),
    }
