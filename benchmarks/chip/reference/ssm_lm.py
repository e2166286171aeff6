"""Plain float32 reference for Mamba-2 language models, and the
benchmark's weight maker.

It imports nothing of the program under test.  It reads the configuration
file's ``model`` section and follows the published layer equations:

  Mamba-2 layer (arXiv:2405.21060, section 7): x += out_proj(gnorm(
      SSD(conv(xBC)), z)) with h = rmsnorm(x) * (1 + ln), [z | xBC | dt] =
      h @ [wz | wxBC | wdt], conv = silu(depthwise causal conv1d(xBC) + b),
      dt = softplus(dt + dt_bias), A = -exp(A_log), the SSD recurrence
      s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T, y_t = s_t C_t + D x_t,
      gnorm(y, z) = rmsnorm(y * silu(z)) * (1 + norm_scale).
  Head: rmsnorm(x) * (1 + final_norm) @ embed^T (tied), masked to vocab.

Sequences run one at a time in blocks of ``block`` tokens that carry the
conv window and the SSM state from block to block, so a 32K-token prompt
fits beside the weights.  Every matmul runs in float32 at
``Precision.HIGHEST``; ``control=True`` instead rounds both
operands of every projection to float8 e4m3 (per-row activation scales,
per-column weight scales), the precision below the bfloat16 the
configuration serves in.

The weight tree is laid out as the program loads it (``segments`` of
stacked layer units), and ``init_weights`` draws it from a key in the
dtype it is served in.
"""
from __future__ import annotations

import math
import zlib
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# --------------------------------------------------------------- sizes
def dims(m: Dict[str, Any]) -> Dict[str, int]:
    s = m["ssm"]
    d = m["d_model"]
    di = s["expand"] * d
    gn = s["n_groups"] * s["d_state"]
    mult = m["vocab_pad_multiple"]
    out = {"D": d, "di": di, "H": di // s["headdim"], "P": s["headdim"],
           "N": s["d_state"], "G": s["n_groups"], "K": s["conv_kernel"],
           "conv_dim": di + 2 * gn, "V": m["vocab_size"],
           "Vp": -(-m["vocab_size"] // mult) * mult}
    return out


def segments(m: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], int]]:
    """The layer list as (unit of kinds, repeats): whole periods of the
    pattern, then the remainder as one more unit."""
    unit = tuple(m["layer_pattern"])
    n_full, rem = divmod(m["n_layers"], len(unit))
    out = [(unit, n_full)] if n_full else []
    if rem:
        out.append((unit[:rem], 1))
    return out


# --------------------------------------------------------------- weights
def _layer_shapes(m):
    d = dims(m)
    D, di, H = d["D"], d["di"], d["H"]
    return {"ln": ((D,), "small", None),
            "mamba": {
                "wz": ((D, di), "normal", D),
                "wxBC": ((D, d["conv_dim"]), "normal", D),
                "wdt": ((D, H), "normal", D),
                "conv_w": ((d["conv_dim"], d["K"]), "normal", d["K"]),
                "conv_b": ((d["conv_dim"],), "small", None),
                "A_log": ((H,), "a_log", None),
                "D": ((H,), "skip", None),
                "dt_bias": ((H,), "dt_bias", None),
                "norm_scale": ((di,), "small", None),
                "out_proj": ((di, D), "normal_out", di)}}


def weight_shapes(m: Dict[str, Any]):
    """Nested (shape, init, fan_in) leaves in the program's layout."""
    d = dims(m)
    is_leaf = _is_spec
    tree: Dict[str, Any] = {
        "embed": ((d["Vp"], d["D"]), "embed", None),
        "final_norm": ((d["D"],), "small", None),
        "segments": [tuple(jax.tree_util.tree_map(
            lambda s, n=n: ((n,) + s[0], s[1], s[2]), _layer_shapes(m),
            is_leaf=is_leaf) for _ in unit) for unit, n in segments(m)]}
    return tree


def _is_spec(x) -> bool:
    return (isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)
            and isinstance(x[0], tuple))


def _draw(key, shape, kind, fan_in, dtype):
    if kind == "embed":
        v = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif kind in ("normal", "normal_out"):
        std = 1.0 / math.sqrt(fan_in) / (2.0 if kind == "normal_out" else 1.0)
        v = std * jax.random.normal(key, shape, jnp.float32)
    elif kind == "small":          # norm scales and biases: near 0
        v = 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif kind == "skip":           # D: near 1
        v = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif kind == "a_log":          # A in [1, 16]
        v = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif kind == "dt_bias":        # softplus^-1 of dt ~ LogUniform[1e-3, 1e-1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        v = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(kind)
    return v.astype(dtype)


def init_weights(m: Dict[str, Any], key: jax.Array, dtype=jnp.bfloat16):
    """Every weight from ``key``; jit it to make them on the device in one
    call.  Each leaf's stream is keyed by a crc32 of its path."""
    shapes = weight_shapes(m)
    leaves = jax.tree_util.tree_leaves_with_path(shapes, is_leaf=_is_spec)
    out = []
    for path, (shape, kind, fan_in) in leaves:
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        out.append(_draw(k, shape, kind, fan_in, dtype))
    treedef = jax.tree_util.tree_structure(shapes, is_leaf=_is_spec)
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------- math
def _f32(x):
    return x.astype(jnp.float32)


def _quant(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def mm(eq: str, a, w, control: bool):
    """Projection ``einsum(eq, activation, weight)`` in float32 at HIGHEST.
    Under ``control`` both operands go through float8 first, with one scale
    per slice along the contracted axes: per token row of the activation,
    per output column of the weight."""
    a, w = _f32(a), _f32(w)
    if control:
        lhs, out = eq.split("->")
        sa, sw = lhs.split(",")
        summed = set(sa) & set(sw)
        a = _quant(a, tuple(i for i, c in enumerate(sa) if c in summed))
        w = _quant(w, tuple(i for i, c in enumerate(sw) if c in summed))
    return jnp.einsum(eq, a, w, precision=HI)


def rmsnorm(x, scale, eps):
    x = _f32(x)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + _f32(scale))


def ssd(x, dt, A, Bm, Cm, h0, q: int):
    """Chunked SSD over one sequence in float32.  x [T,H,P], dt [T,H],
    A [H], Bm/Cm [T,H,N], h0 [H,P,N] -> (y [T,H,P] without D, hT)."""
    T, H, P = x.shape
    nc = T // q
    xs = (x * dt[..., None]).reshape(nc, q, H, P)
    a = (dt * A).reshape(nc, q, H)
    cum = jnp.cumsum(a, axis=1)                                  # [c,q,H]
    B = Bm.reshape(nc, q, H, -1)
    C = Cm.reshape(nc, q, H, -1)
    seg = cum[:, :, None, :] - cum[:, None, :, :]                # [c,q,k,H]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
    L = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("cqhn,ckhn->cqkh", C, B, precision=HI)
    y = jnp.einsum("cqkh,ckhp->cqhp", cb * L, xs, precision=HI)
    to_end = jnp.exp(cum[:, -1:, :] - cum)                       # [c,q,H]
    st = jnp.einsum("ckh,ckhn,ckhp->chpn", to_end, B, xs, precision=HI)
    decay = jnp.exp(cum[:, -1, :])                               # [c,H]

    def carry(h, inp):
        s, dcy = inp
        return h * dcy[:, None, None] + s, h

    hT, h_in = jax.lax.scan(carry, h0, (st, decay))
    y = y + jnp.einsum("cqhn,chpn->cqhp", C * jnp.exp(cum)[..., None], h_in,
                       precision=HI)
    return y.reshape(T, H, P), hT


def mamba_layer(m, d, p, x, state, control):
    eps = m["norm_eps"]
    h = rmsnorm(x, p["ln"], eps)
    w = p["mamba"]
    z = mm("td,de->te", h, w["wz"], control)
    xbc = mm("td,de->te", h, w["wxBC"], control)
    dt = mm("td,dh->th", h, w["wdt"], control)
    K, T = d["K"], x.shape[0]
    win = jnp.concatenate([state["conv"], xbc], axis=0)          # [K-1+T, C]
    cw = _f32(w["conv_w"])
    conv = sum(win[k:k + T] * cw[:, k] for k in range(K)) + _f32(w["conv_b"])
    conv = jax.nn.silu(conv)
    di, gn = d["di"], d["G"] * d["N"]
    xs = conv[:, :di].reshape(T, d["H"], d["P"])
    rep = d["H"] // d["G"]
    Bm = jnp.repeat(conv[:, di:di + gn].reshape(T, d["G"], d["N"]), rep, 1)
    Cm = jnp.repeat(conv[:, di + gn:].reshape(T, d["G"], d["N"]), rep, 1)
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"]))
    A = -jnp.exp(_f32(w["A_log"]))
    y, ssm = ssd(xs, dt, A, Bm, Cm, state["ssm"], m["ssm"]["chunk"])
    y = (y + xs * _f32(w["D"])[None, :, None]).reshape(T, di)
    y = rmsnorm(y * jax.nn.silu(z), w["norm_scale"], eps)
    x = x + mm("te,ed->td", y, w["out_proj"], control)
    return x, {"conv": win[T:], "ssm": ssm}


def init_state(m: Dict[str, Any]):
    """Zero carried state, per segment and unit layer, stacked by repeat."""
    d = dims(m)
    return [tuple({"conv": jnp.zeros((n, d["K"] - 1, d["conv_dim"])),
                   "ssm": jnp.zeros((n, d["H"], d["P"], d["N"]))}
                  for _ in unit) for unit, n in segments(m)]


def block_forward(m, w, tokens, state, control: bool):
    """One block of ``tokens`` [T]: returns the final-normed hidden states
    [T, D] and the carried state."""
    d = dims(m)
    x = _f32(jnp.take(w["embed"], tokens, axis=0))
    new_state = []
    for seg_w, seg_s in zip(w["segments"], state):
        def body(x, xs):
            lw, ls = xs
            outs = []
            for p, s in zip(lw, ls):
                x, ns = mamba_layer(m, d, p, x, s, control)
                outs.append(ns)
            return x, tuple(outs)
        x, seg_new = jax.lax.scan(body, x, (seg_w, seg_s))
        new_state.append(seg_new)
    return rmsnorm(x, w["final_norm"], m["norm_eps"]), new_state


def logits(m, w, h, control: bool):
    """[n, D] final-normed hidden -> [n, V] logits over the real vocab."""
    return mm("td,vd->tv", h, w["embed"][:m["vocab_size"]], control)


class Reference:
    """Compiled block and head programs for one configuration and block
    size."""

    def __init__(self, m: Dict[str, Any], block: int):
        q = m["ssm"]["chunk"]
        if block % q:
            raise ValueError(f"block {block} is not a multiple of the SSD "
                             f"chunk {q}")
        self.m, self.block = m, block
        self._block = {c: jax.jit(lambda w, t, st, c=c: block_forward(
            m, w, t, st, c), donate_argnums=(2,)) for c in (False, True)}
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
        state = init_state(self.m)
        keep = []
        for b in range(nb):
            lo = b * self.block
            h, state = self._block[control](
                w, jnp.asarray(toks[lo:lo + self.block]), state)
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


def _gap(ref, tokens):
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
