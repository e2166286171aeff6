"""Per-layer block composition: param defs, cache init, and application."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.models import mamba1 as m1
from repro.models import mamba2 as m2
from repro.models.attention import attention, attn_param_defs, init_attn_cache
from repro.models.mlp import mlp, mlp_param_defs
from repro.models.moe import moe, moe_param_defs
from repro.models.norms import rms_norm, scale_by
from repro.models.params import ParamDef

ATTN_KINDS = ("dense", "local", "encoder", "moe", "dense_moe")
MAMBA_KINDS = ("mamba2", "mamba2+shared", "mamba1")


def layer_param_defs(cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    D = cfg.d_model
    if kind in ("dense", "local", "encoder", "dense_moe"):
        return {
            "ln1": ParamDef((D,), ("embed",), init="zeros"),
            "attn": attn_param_defs(D, cfg.attn),
            "ln2": ParamDef((D,), ("embed",), init="zeros"),
            "mlp": mlp_param_defs(D, cfg.d_ff),
        }
    if kind == "moe":
        return {
            "ln1": ParamDef((D,), ("embed",), init="zeros"),
            "attn": attn_param_defs(D, cfg.attn),
            "ln2": ParamDef((D,), ("embed",), init="zeros"),
            "moe": moe_param_defs(D, cfg.moe),
        }
    if kind == "hybrid_par":
        return {
            "ln1": ParamDef((D,), ("embed",), init="zeros"),
            "attn": attn_param_defs(D, cfg.attn),
            "mamba": m2.mamba2_param_defs(D, cfg.ssm),
            "ln2": ParamDef((D,), ("embed",), init="zeros"),
            "mlp": mlp_param_defs(D, cfg.d_ff),
        }
    if kind in ("mamba2", "mamba2+shared"):
        return {
            "ln": ParamDef((D,), ("embed",), init="zeros"),
            "mamba": m2.mamba2_param_defs(D, cfg.ssm),
        }
    if kind == "mamba1":
        return {
            "ln": ParamDef((D,), ("embed",), init="zeros"),
            "mamba": m1.mamba1_param_defs(D, cfg.ssm),
        }
    raise ValueError(f"unknown layer kind {kind!r}")


def shared_block_defs(cfg: ModelConfig) -> Dict[str, Any]:
    """Zamba2-style shared transformer block (one copy, applied at every
    'mamba2+shared' position)."""
    D = cfg.d_model
    a = cfg.shared_attn
    return {
        "ln1": ParamDef((D,), ("embed",), init="zeros"),
        "attn": attn_param_defs(D, a),
        "ln2": ParamDef((D,), ("embed",), init="zeros"),
        "mlp": mlp_param_defs(D, cfg.shared_attn_d_ff or cfg.d_ff),
    }


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                     *, kv_repeat: int = 1, shared_kv_repeat: int = 1,
                     dtype=jnp.bfloat16) -> Optional[Dict]:
    if kind == "encoder":
        return {}
    if kind in ("dense", "moe", "dense_moe", "local"):
        window = cfg.attn.sliding_window if kind == "local" else None
        return init_attn_cache(cfg.attn, batch, max_seq, kv_repeat=kv_repeat,
                               window=window, dtype=dtype)
    if kind == "hybrid_par":
        c = m2.init_mamba2_cache(cfg.d_model, cfg.ssm, batch, dtype)
        c.update(init_attn_cache(cfg.attn, batch, max_seq,
                                 kv_repeat=kv_repeat, dtype=dtype))
        return c
    if kind == "mamba2":
        return m2.init_mamba2_cache(cfg.d_model, cfg.ssm, batch, dtype)
    if kind == "mamba2+shared":
        c = m2.init_mamba2_cache(cfg.d_model, cfg.ssm, batch, dtype)
        c["attn"] = init_attn_cache(cfg.shared_attn, batch, max_seq,
                                    kv_repeat=shared_kv_repeat, dtype=dtype)
        return c
    if kind == "mamba1":
        return m1.init_mamba1_cache(cfg.d_model, cfg.ssm, batch, dtype)
    raise ValueError(kind)


def _residual(x: jax.Array) -> jax.Array:
    """Sequence-parallel residual stream (no-op unless the plan enables
    the residual_seq rule)."""
    from repro.distributed.sharding import constrain
    return constrain(x, ("batch", "residual_seq", "embed"))


def apply_layer(cfg: ModelConfig, kind: str, p: Dict, x: jax.Array, *,
                rope, rope_local=None, cache: Optional[Dict] = None,
                pos: Optional[jax.Array] = None, kv_repeat: int = 1,
                shared: Optional[Dict] = None, shared_kv_repeat: int = 1,
                moe_groups: int = 1,
                chunk_mask: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Optional[Dict]]:
    """``chunk_mask`` ([B, S] bool) marks valid tokens during a chunked
    prefill (cache + S>1 + pos): attention offsets its causal mask / KV
    writes by ``pos``, SSM layers treat invalid tokens as inert."""
    eps = cfg.norm_eps
    x = _residual(x)
    if kind in ATTN_KINDS:
        window = cfg.attn.sliding_window if kind == "local" else None
        rt = rope_local if (kind == "local" and rope_local is not None) else rope
        h = rms_norm(x, p["ln1"], eps)
        attn_cache = cache if (cache is None or "k" in cache) else None
        a_out, new_attn_cache = attention(
            p["attn"], h, cfg.attn, rope=rt, window=window,
            cache=attn_cache if kind != "encoder" else None,
            pos=pos, kv_repeat=kv_repeat, chunk_mask=chunk_mask, eps=eps)
        x = x + a_out
        h = rms_norm(x, p["ln2"], eps)
        if kind == "moe":
            x = x + moe(p["moe"], h, cfg.moe, moe_groups, cfg.act)
        else:
            x = x + mlp(p["mlp"], h, cfg.act)
        new_cache = new_attn_cache if kind != "encoder" else {}
        return _residual(x), new_cache

    if kind == "hybrid_par":
        # Falcon-H1's parallel hybrid layer: one RMSNorm feeds attention
        # and a Mamba-2 mixer side by side, their outputs sum into the
        # residual, then a second RMSNorm feeds the MLP.  ``cfg.mup``
        # carries the published muP multipliers (none when it is None).
        mup = cfg.mup
        h = rms_norm(x, p["ln1"], eps)
        mcache = ({"conv": cache["conv"], "ssm": cache["ssm"]}
                  if cache is not None else None)
        h_ssm = h if mup is None else scale_by(h, mup.ssm_in)
        zxbcdt = None if mup is None else mup.ssm
        is_decode = cache is not None and x.shape[1] == 1 and pos is not None
        if is_decode:
            m_out, new_m = m2.mamba2_decode(p["mamba"], h_ssm, cfg.ssm,
                                            cfg.d_model, cache=mcache,
                                            eps=eps, zxbcdt=zxbcdt)
        else:
            m_out, new_m = m2.mamba2_block(p["mamba"], h_ssm, cfg.ssm,
                                           cfg.d_model, cache=mcache, eps=eps,
                                           mask=chunk_mask, zxbcdt=zxbcdt)
        attn_cache = ({"k": cache["k"], "v": cache["v"]}
                      if cache is not None else None)
        a_out, new_attn = attention(
            p["attn"], h if mup is None else scale_by(h, mup.attn_in),
            cfg.attn, rope=rope, cache=attn_cache, pos=pos,
            kv_repeat=kv_repeat, chunk_mask=chunk_mask, eps=eps,
            key_mult=None if mup is None else mup.key)
        if mup is not None:
            m_out = scale_by(m_out, mup.ssm_out)
            a_out = scale_by(a_out, mup.attn_out)
        x = x + (m_out + a_out)
        h = rms_norm(x, p["ln2"], eps)
        x = x + mlp(p["mlp"], h, cfg.act, None if mup is None else mup.mlp)
        new_cache = None
        if cache is not None:
            new_cache = dict(new_m or {})
            if new_attn is not None:
                new_cache.update(new_attn)
        return _residual(x), new_cache

    if kind in ("mamba2", "mamba2+shared"):
        h = rms_norm(x, p["ln"], eps)
        mcache = None
        if cache is not None:
            mcache = {"conv": cache["conv"], "ssm": cache["ssm"]}
        is_decode = cache is not None and x.shape[1] == 1 and pos is not None
        if is_decode:
            out, new_m = m2.mamba2_decode(p["mamba"], h, cfg.ssm, cfg.d_model,
                                          cache=mcache, eps=eps)
        else:
            out, new_m = m2.mamba2_block(p["mamba"], h, cfg.ssm, cfg.d_model,
                                         cache=mcache, eps=eps,
                                         mask=chunk_mask)
        x = x + out
        new_cache = new_m
        if kind == "mamba2+shared":
            assert shared is not None, "shared block params required"
            h = rms_norm(x, shared["ln1"], eps)
            a_out, new_shared_cache = attention(
                shared["attn"], h, cfg.shared_attn, rope=rope,
                cache=cache["attn"] if cache is not None else None,
                pos=pos, kv_repeat=shared_kv_repeat,
                chunk_mask=chunk_mask, eps=eps)
            x = x + a_out
            h = rms_norm(x, shared["ln2"], eps)
            x = x + mlp(shared["mlp"], h, cfg.act)
            if new_cache is not None:
                new_cache = dict(new_cache)
                new_cache["attn"] = new_shared_cache
        return _residual(x), new_cache

    if kind == "mamba1":
        h = rms_norm(x, p["ln"], eps)
        is_decode = cache is not None and x.shape[1] == 1 and pos is not None
        if is_decode:
            out, new_m = m1.mamba1_decode(p["mamba"], h, cfg.ssm, cfg.d_model,
                                          cache=cache, eps=eps)
        else:
            out, new_m = m1.mamba1_block(p["mamba"], h, cfg.ssm, cfg.d_model,
                                         cache=cache, eps=eps,
                                         mask=chunk_mask)
        return _residual(x + out), new_m

    raise ValueError(kind)
