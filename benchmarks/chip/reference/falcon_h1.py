"""Plain float32 reference for Falcon-H1's parallel hybrid language model,
and the benchmark's weight maker for it.

It imports nothing of the program under test; the Mamba-2 pieces (the
chunked SSD, the float32 projections and their float8 control, the gap)
come from ``ssm_lm.py``.  It reads the configuration file's ``model``
section and follows ``transformers``' ``modeling_falcon_h1.py``
(``FalconH1DecoderLayer``, ``FalconH1Mixer``, ``FalconH1Attention``,
``FalconH1MLP``, ``compute_mup_vector``), with ``mup`` the multipliers:

  x = embed[t] * embedding
  layer: h = rmsnorm(x) * (1 + ln1)
    Mamba-2 mixer on h * ssm_in: [z | x | B | C | dt] = h @ [wz | wxBC |
      wdt] scaled slice by slice by ``mup.ssm``; xBC = silu(depthwise causal
      conv1d + b); dt = softplus(dt + dt_bias); SSD with A = -exp(A_log)
      and skip D; y = rmsnorm_per_group(y * silu(z)) * (1 + norm_scale)
      over each of the n_groups slices; m = (y @ out_proj) * ssm_out
    attention on h * attn_in: q = h wq, k = (h wk) * key, v = h wv, rope
      (theta from the config) on q and k, causal softmax(q k^T / sqrt(hd))
      with 4 KV heads shared by 5 query heads each; a = (o wo) * attn_out
    x = x + (m + a); x = x + mlp(rmsnorm(x) * (1 + ln2)) with
      mlp(h) = ((h wi) * silu((h wg) * gate)) wo * down
  logits = (rmsnorm(x) * (1 + final_norm)) @ lm_head * lm_head_mult

Departures from the published code, none of which changes the function:
norm scales are stored as ``1 + scale`` (the program's layout) rather
than as the weight itself; rope is applied to halves (x1, x2) exactly as
``rotate_half`` does; sequences run one at a time in blocks of ``block``
tokens that carry the conv window, the SSM state and a float32 key/value
cache (of the sequence length rounded up to a power of two) from block to
block; attention is taken over query sub-blocks of ``Q_SUB`` rows
against the whole cache under the causal mask; the head is taken over
vocabulary blocks of ``V_SUB`` columns.  Every matmul runs in float32 at
``Precision.HIGHEST``; ``control=True`` rounds both operands of every
projection to float8 e4m3 instead (``ssm_lm.mm``).

``init_weights`` draws every weight as ``ssm_lm`` does for its kind, and
then divides each projection's draw by the multipliers that the model
applies to its output (to its input, for the Mamba in-projection's
``ssm_in``), so that at the draw every multiplied projection has the
scale an unmultiplied one would have and every branch moves the logits.
Each query head's draw then adds its KV head's key draw (``SELF``), so a
query meets its own key at about sqrt(head_dim) standard deviations
above the rest and attention keeps a share on the query's own position:
otherwise a random model's softmax over tens of thousands of keys is
nearly uniform, and a kernel that read the wrong part of its KV (half the
bucket, a shifted ``q_offset``) would leave the logits as they are.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.ssm_lm import (HI, _draw, _f32, _gap, mm, rmsnorm,
                                        segments, ssd)

Q_SUB = 128       # attention query rows a step
V_SUB = 16320     # head vocabulary columns a step (261120 / 16)
SELF = 1.0        # weight of a KV head's key draw in its query heads' draw


# --------------------------------------------------------------- sizes
def dims(m: Dict[str, Any]) -> Dict[str, int]:
    s, a = m["ssm"], m["attn"]
    d = m["d_model"]
    di = s["d_ssm"] if s.get("d_ssm") is not None else s["expand"] * d
    gn = s["n_groups"] * s["d_state"]
    mult = m["vocab_pad_multiple"]
    return {"D": d, "di": di, "H": di // s["headdim"], "P": s["headdim"],
            "N": s["d_state"], "G": s["n_groups"], "K": s["conv_kernel"],
            "conv_dim": di + 2 * gn, "F": m["d_ff"],
            "AH": a["n_heads"], "KV": a["n_kv_heads"], "hd": a["head_dim"],
            "V": m["vocab_size"], "Vp": -(-m["vocab_size"] // mult) * mult}


# --------------------------------------------------------------- weights
def _layer_shapes(m):
    d, u = dims(m), m["mup"]
    D, di, H, F = d["D"], d["di"], d["H"], d["F"]
    AH, KV, hd, gn = d["AH"], d["KV"], d["hd"], d["G"] * d["N"]
    mz, mx, mb, mc, mdt = u["ssm"]
    gate, down = u["mlp"]
    sin = u["ssm_in"]
    xbc_gain = np.concatenate([np.full(di, mx), np.full(gn, mb),
                               np.full(gn, mc)]) * sin
    return {"ln1": ((D,), "small", None, 1.0),
            "attn": {"wq": ((D, AH, hd), "normal", D, u["attn_in"]),
                     "wk": ((D, KV, hd), "normal", D,
                            u["attn_in"] * u["key"]),
                     "wv": ((D, KV, hd), "normal", D, u["attn_in"]),
                     "wo": ((AH, hd, D), "normal_out", AH * hd,
                            u["attn_out"])},
            "mamba": {
                "wz": ((D, di), "normal", D, sin * mz),
                "wxBC": ((D, d["conv_dim"]), "normal", D,
                         tuple(xbc_gain.tolist())),
                "wdt": ((D, H), "normal", D, sin * mdt),
                "conv_w": ((d["conv_dim"], d["K"]), "normal", d["K"], 1.0),
                "conv_b": ((d["conv_dim"],), "small", None, 1.0),
                "A_log": ((H,), "a_log", None, 1.0),
                "D": ((H,), "skip", None, 1.0),
                "dt_bias": ((H,), "dt_bias", None, 1.0),
                "norm_scale": ((di,), "small", None, 1.0),
                "out_proj": ((di, D), "normal_out", di, u["ssm_out"])},
            "ln2": ((D,), "small", None, 1.0),
            "mlp": {"wi": ((D, F), "normal", D, 1.0),
                    "wg": ((D, F), "normal", D, gate),
                    "wo": ((F, D), "normal_out", F, down)}}


def _is_spec(x) -> bool:
    return (isinstance(x, tuple) and len(x) == 4 and isinstance(x[1], str)
            and isinstance(x[0], tuple))


def weight_shapes(m: Dict[str, Any]):
    """Nested (shape, init, fan_in, gain) leaves in the program's layout;
    ``gain`` is the multiplier product the draw is divided by (one per
    output column where it is a tuple)."""
    d, u = dims(m), m["mup"]
    return {"embed": ((d["Vp"], d["D"]), "embed", None, u["embedding"]),
            "final_norm": ((d["D"],), "small", None, 1.0),
            "lm_head": ((d["D"], d["Vp"]), "normal", d["D"], u["lm_head"]),
            "segments": [tuple(jax.tree_util.tree_map(
                lambda s, n=n: ((n,) + s[0],) + s[1:], _layer_shapes(m),
                is_leaf=_is_spec) for _ in unit) for unit, n in segments(m)]}


def init_weights(m: Dict[str, Any], key: jax.Array, dtype=jnp.bfloat16):
    """Every weight from ``key``; jit it to make them on the device in one
    call.  Each leaf's stream is keyed by a crc32 of its path."""
    shapes = weight_shapes(m)
    leaves = jax.tree_util.tree_leaves_with_path(shapes, is_leaf=_is_spec)
    draws, gains = {}, {}
    for path, (shape, kind, fan_in, gain) in leaves:
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        draws[name] = _draw(k, shape, kind, fan_in, jnp.float32)
        gains[name] = (1.0 / np.asarray(gain, np.float32)
                       if isinstance(gain, tuple) else 1.0 / gain)
    group = m["attn"]["n_heads"] // m["attn"]["n_kv_heads"]
    for name in draws:
        if name.endswith("['attn']['wq']"):
            wk = draws[name[:-len("['wq']")] + "['wk']"]
            draws[name] = draws[name] + SELF * jnp.repeat(wk, group, axis=-2)
    out = [(draws[n] * gains[n]).astype(dtype) for n in draws]
    treedef = jax.tree_util.tree_structure(shapes, is_leaf=_is_spec)
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------- math
def rope(x, pos, theta: float):
    """Rotary embedding of x [T, heads, hd] at positions ``pos`` [T]:
    angles pos / theta^(2i / hd) in float32, ``rotate_half`` layout."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mamba_branch(m, d, w, h, state, control):
    """FalconH1Mixer on the normed input h [T, D] (before ssm_out)."""
    u, eps = m["mup"], m["norm_eps"]
    mz, mx, mb, mc, mdt = u["ssm"]
    gn, di = d["G"] * d["N"], d["di"]
    h = h * u["ssm_in"]
    z = mm("td,de->te", h, w["wz"], control) * mz
    vec = jnp.concatenate([jnp.full((di,), mx), jnp.full((gn,), mb),
                           jnp.full((gn,), mc)])
    xbc = mm("td,de->te", h, w["wxBC"], control) * vec
    dt = mm("td,dh->th", h, w["wdt"], control) * mdt
    K, T = d["K"], h.shape[0]
    win = jnp.concatenate([state["conv"], xbc], axis=0)          # [K-1+T, C]
    cw = _f32(w["conv_w"])
    conv = sum(win[k:k + T] * cw[:, k] for k in range(K)) + _f32(w["conv_b"])
    conv = jax.nn.silu(conv)
    xs = conv[:, :di].reshape(T, d["H"], d["P"])
    rep = d["H"] // d["G"]
    Bm = jnp.repeat(conv[:, di:di + gn].reshape(T, d["G"], d["N"]), rep, 1)
    Cm = jnp.repeat(conv[:, di + gn:].reshape(T, d["G"], d["N"]), rep, 1)
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"]))
    A = -jnp.exp(_f32(w["A_log"]))
    y, ssm_state = ssd(xs, dt, A, Bm, Cm, state["ssm"], m["ssm"]["chunk"])
    y = (y + xs * _f32(w["D"])[None, :, None]).reshape(T, di)
    g = (y * jax.nn.silu(z)).reshape(T, d["G"], di // d["G"])
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    y = g.reshape(T, di) * (1.0 + _f32(w["norm_scale"]))
    out = mm("te,ed->td", y, w["out_proj"], control)
    return out, {"conv": win[T:], "ssm": ssm_state}


def attention_branch(m, d, w, h, kv, pos0, control):
    """FalconH1Attention on the normed input h [T, D] (before attn_out):
    the block's keys and values join the float32 cache ``kv`` at
    ``pos0``; each query attends every cached position at or before its
    own."""
    u, a = m["mup"], m["attn"]
    T = h.shape[0]
    h = h * u["attn_in"]
    pos = pos0 + jnp.arange(T)
    q = mm("td,dhk->thk", h, w["wq"], control)
    k = mm("td,dhk->thk", h, w["wk"], control) * u["key"]
    v = mm("td,dhk->thk", h, w["wv"], control)
    q, k = rope(q, pos, a["rope_theta"]), rope(k, pos, a["rope_theta"])
    ck = jax.lax.dynamic_update_slice_in_dim(kv["k"], k, pos0, axis=0)
    cv = jax.lax.dynamic_update_slice_in_dim(kv["v"], v, pos0, axis=0)
    G = d["AH"] // d["KV"]
    scale = 1.0 / math.sqrt(d["hd"])
    kpos = jnp.arange(ck.shape[0])

    def rows(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * Q_SUB, Q_SUB, axis=0)
        qs = qs.reshape(Q_SUB, d["KV"], G, d["hd"])
        s = jnp.einsum("tkgd,skd->tkgs", qs, ck, precision=HI) * scale
        qp = pos0 + i * Q_SUB + jnp.arange(Q_SUB)
        s = jnp.where((kpos[None, :] <= qp[:, None])[:, None, None, :], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("tkgs,skd->tkgd", p, cv, precision=HI).reshape(
            Q_SUB, d["AH"] * d["hd"])

    o = jax.lax.map(rows, jnp.arange(T // Q_SUB)).reshape(T, d["AH"],
                                                            d["hd"])
    out = mm("thk,hkd->td", o, w["wo"], control)
    return out, {"k": ck, "v": cv}


def layer(m, d, p, x, state, pos0, control):
    u, eps = m["mup"], m["norm_eps"]
    h = rmsnorm(x, p["ln1"], eps)
    mo, ms = mamba_branch(m, d, p["mamba"], h, state, control)
    ao, kv = attention_branch(m, d, p["attn"], h, state, pos0, control)
    x = x + (mo * u["ssm_out"] + ao * u["attn_out"])
    h = rmsnorm(x, p["ln2"], eps)
    w = p["mlp"]
    gate, down = u["mlp"]
    g = mm("td,df->tf", h, w["wg"], control) * gate
    ff = mm("td,df->tf", h, w["wi"], control) * jax.nn.silu(g)
    x = x + mm("tf,fd->td", ff, w["wo"], control) * down
    return x, dict(ms, **kv)


def init_state(m: Dict[str, Any], length: int):
    """Zero carried state for a sequence of up to ``length`` positions,
    per segment and unit layer, stacked by repeat."""
    d = dims(m)
    return [tuple({"conv": jnp.zeros((n, d["K"] - 1, d["conv_dim"])),
                   "ssm": jnp.zeros((n, d["H"], d["P"], d["N"])),
                   "k": jnp.zeros((n, length, d["KV"], d["hd"])),
                   "v": jnp.zeros((n, length, d["KV"], d["hd"]))}
                  for _ in unit) for unit, n in segments(m)]


def block_forward(m, w, tokens, state, pos0, control: bool):
    """One block of ``tokens`` [T] at positions ``pos0`` ..: returns the
    final-normed hidden states [T, D] and the carried state."""
    d = dims(m)
    x = _f32(jnp.take(w["embed"], tokens, axis=0)) * m["mup"]["embedding"]
    new_state = []
    for seg_w, seg_s in zip(w["segments"], state):
        def body(x, xs):
            lw, ls = xs
            outs = []
            for p, s in zip(lw, ls):
                x, ns = layer(m, d, p, x, s, pos0, control)
                outs.append(ns)
            return x, tuple(outs)
        x, seg_new = jax.lax.scan(body, x, (seg_w, seg_s))
        new_state.append(seg_new)
    return rmsnorm(x, w["final_norm"], m["norm_eps"]), new_state


def logits(m, w, h, control: bool):
    """[n, D] final-normed hidden -> [n, V] logits over the real vocab,
    taken over blocks of the untied head."""
    head = w["lm_head"]
    D, Vp = head.shape
    vb = V_SUB if Vp % V_SUB == 0 else Vp
    blocks = head.reshape(D, Vp // vb, vb).transpose(1, 0, 2)
    out = jax.lax.map(lambda b: mm("td,dv->tv", h, b, control), blocks)
    out = out.transpose(1, 0, 2).reshape(h.shape[0], Vp)
    return out[:, :m["vocab_size"]] * m["mup"]["lm_head"]


class Reference:
    """Compiled block and head programs for one configuration and block
    size, with ``ssm_lm.Reference``'s interface."""

    def __init__(self, m: Dict[str, Any], block: int):
        q = m["ssm"]["chunk"]
        if block % q or block % Q_SUB:
            raise ValueError(f"block {block} is not a multiple of the SSD "
                             f"chunk {q} and of {Q_SUB}")
        self.m, self.block = m, block
        self._block = {c: jax.jit(lambda w, t, st, p0, c=c: block_forward(
            m, w, t, st, p0, c), donate_argnums=(2,)) for c in (False, True)}
        self._gap = jax.jit(_gap)
        self._head = {c: jax.jit(lambda w, h, c=c: logits(m, w, h, c))
                      for c in (False, True)}

    def hidden(self, w, seq: np.ndarray, first: int, control: bool):
        """Final-normed hidden states of positions ``first`` .. end of
        ``seq``, as one [n, D] device array."""
        n = len(seq)
        nb = -(-n // self.block)
        toks = np.zeros((nb * self.block,), np.int32)
        toks[:n] = seq
        length = 1 << (nb * self.block - 1).bit_length()
        state = init_state(self.m, length)
        keep = []
        for b in range(nb):
            lo = b * self.block
            h, state = self._block[control](
                w, jnp.asarray(toks[lo:lo + self.block]), state,
                jnp.int32(lo))
            if lo + self.block > first:
                keep.append(h[max(first - lo, 0):min(n - lo, self.block)])
        return jnp.concatenate(keep, axis=0)

    def gaps(self, w, prompt: np.ndarray, served: np.ndarray,
             control: bool = False):
        """For each served token, how far the float32 reference's logit of
        it lies below the reference's best logit at that position.  With
        ``control``, also the same gap of the token that the float8 control
        puts first at each position."""
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        first = len(prompt) - 1
        ref = self._head[False](w, self.hidden(w, seq, first, False))
        got = self._gap(ref, jnp.asarray(served, jnp.int32))
        if not control:
            return np.asarray(got), None
        ctl = self._head[True](w, self.hidden(w, seq, first, True))
        pick = self._gap(ref, jnp.argmax(ctl, axis=-1).astype(jnp.int32))
        return np.asarray(got), np.asarray(pick)
