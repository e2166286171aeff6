"""Fused Mamba-2 decode step (``kernels/decode_fused``, Pallas ``_m2_kernel``):
conv-window shift, conv, and SSM state update and read-out for one token,
one call per Mamba-2 layer per decoded token.

Work per row: the depthwise conv (2 C K), the state update
s = exp(dt A) s + dt x B^T (4 H P N: decay, outer product, add) and the
read-out y = s C + D x (2 H P N + 2 H P).  Bytes per row: the float32 state
read and written, the conv window read and written, xBC and y in the
compute dtype, the conv taps and bias once per call.

A call's shapes are read from the trace: it returns y [B, H, P] and the
new state [B, H, P, N]; the conv width C and kernel K come from the model.
"""
from chipbench.trace import result_shapes

PATTERN = r"/%decode_fused[.\d]* = .* custom-call$"


def flops(b, h, p, n, c, k):
    return b * (2 * c * k + 6 * h * p * n + 2 * h * p)


def bytes_moved(b, h, p, n, c, k, act=2, state=4):
    return (b * (h * p * n * state * 2       # state in and out
                 + (k - 1) * c * act * 2     # conv window in and out
                 + c * act + h * p * act     # xBC in, y out
                 + h * state)                # dt
            + c * k * act + c * act)         # taps, bias


def work(model, key):
    """(flops, bytes) of one call, the operation ``key`` of the trace."""
    (b, h, p), (_, _, _, n) = result_shapes(key)[:2]
    s = model["ssm"]
    c = s["expand"] * model["d_model"] + 2 * s["n_groups"] * s["d_state"]
    return (flops(b, h, p, n, c, s["conv_kernel"]),
            bytes_moved(b, h, p, n, c, s["conv_kernel"]))
