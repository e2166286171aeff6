"""Normalization layers (pure functions)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    with jax.named_scope("norm"):
        dt = x.dtype
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def gated_rms_norm(x: jax.Array, gate: jax.Array, scale: jax.Array,
                   eps: float = 1e-5, groups: int = 1) -> jax.Array:
    """Mamba2 gated RMSNorm: norm(x * silu(z)) with learned scale.  With
    ``groups`` > 1 the mean square is taken over each of ``groups`` equal
    slices of the last axis (the B/C groups), as Mamba-2's RMSNormGated
    does with ``group_size = d_inner / n_groups``."""
    with jax.named_scope("ssm_gate"):
        dt = x.dtype
        xf = x.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
        if groups > 1:
            lead, di = xf.shape[:-1], xf.shape[-1]
            xg = xf.reshape(*lead, groups, di // groups)
            var = jnp.mean(jnp.square(xg), axis=-1, keepdims=True)
            y = (xg * jax.lax.rsqrt(var + eps)).reshape(*lead, di)
        else:
            var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
            y = xf * jax.lax.rsqrt(var + eps)
        return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def scale_by(x: jax.Array, m) -> jax.Array:
    """``x * m`` for a muP multiplier ``m`` (a scalar, or a vector over the
    last axis): the product is taken in float32 and rounded once to
    ``x``'s dtype, so the multiplier itself is never rounded."""
    return (x.astype(jnp.float32) * jnp.asarray(m, jnp.float32)).astype(
        x.dtype)


def head_rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head RMSNorm over head_dim (qwen3-style qk-norm). x: [..., H, hd]."""
    with jax.named_scope("norm"):
        dt = x.dtype
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)
