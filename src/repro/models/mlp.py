"""Gated MLP (SwiGLU / GeGLU)."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.models.norms import scale_by
from repro.models.params import ParamDef


def mlp_param_defs(d_model: int, d_ff: int) -> Dict[str, ParamDef]:
    return {
        "wi": ParamDef((d_model, d_ff), ("embed", "ff"), fan_in=d_model),
        "wg": ParamDef((d_model, d_ff), ("embed", "ff"), fan_in=d_model),
        "wo": ParamDef((d_ff, d_model), ("ff", "embed"), init="normal_out",
                       fan_in=d_ff),
    }


def mlp(p: Dict, x: jax.Array, act: str = "silu",
        mults: Optional[Tuple[float, float]] = None) -> jax.Array:
    """``mults`` (Falcon-H1's muP ``mlp_multipliers``, or None): the gate
    projection is scaled before its activation, the output after the down
    projection."""
    with jax.named_scope("mlp"):
        dt = x.dtype
        h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(dt))
        g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(dt))
        if mults is not None:
            g = scale_by(g, mults[0])
        actf = jax.nn.silu if act == "silu" else jax.nn.gelu
        h = constrain(actf(g) * h, ("batch", "seq", "ff"))
        out = jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(dt))
        return out if mults is None else scale_by(out, mults[1])
