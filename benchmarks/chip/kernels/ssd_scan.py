"""Mamba-2 SSD chunk scan (``kernels/ssd``, Pallas ``_ssd_kernel``): one
call per Mamba-2 layer of every prefill chunk program.

Work is the chunked dual form of the algorithm (arXiv:2405.21060, section
6) at the model's SSD chunk length Q, counted from the call's shapes:
per (row, head, chunk) the masked C B^T products against dt x (2 Q^2 P),
the chunk's final state (2 Q N P), its carry (2 N P), the state's read-out
(2 Q N P) and the skip (2 Q P); per (row, group, chunk) C B^T (2 Q^2 N).
Bytes are the operands read and written once: x, y, B, C in the compute
dtype, dt and both states in float32.

A call's shapes are read from the trace: it returns y [B, H, S, P] and
the final state [B, H, P, N]; the groups and Q come from the model.
"""
from chipbench.trace import result_shapes

PATTERN = r"/%ssd_core[.\d]* = .* custom-call$"


def flops(b, s, h, p, n, g, q):
    nc = -(-s // q)
    per_head = 2 * q * q * p + q * q + 4 * q * n * p + 2 * n * p + 2 * q * p
    return b * nc * (h * per_head + g * 2 * q * q * n)


def bytes_moved(b, s, h, p, n, g, act=2, state=4):
    return (b * s * h * p * act * 2          # x in, y out
            + b * s * g * n * act * 2        # B, C
            + b * s * h * state              # dt
            + b * h * p * n * state * 2)     # initial and final state


def work(model, key):
    """(flops, bytes) of one call, the operation ``key`` of the trace."""
    (b, h, s, p), (_, _, _, n) = result_shapes(key)[:2]
    g, q = model["ssm"]["n_groups"], model["ssm"]["chunk"]
    return flops(b, s, h, p, n, g, q), bytes_moved(b, s, h, p, n, g)
