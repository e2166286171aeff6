"""Flash attention of a prefill chunk (``kernels/flash``, Pallas
``_flash_kernel``): one call per attention layer of every chunk program,
named in the trace ``flash_prefill_kv<rows>`` after the KV rows it was
given (the chunk's KV bucket).

The bucket is the smallest rung of the power-of-two ladder at or above
the chunk's end, so every row that is still prefilling has at least half
the bucket live before the chunk ends.  Work per (row, query head) is
counted at that least live prefix: each of the chunk's Sq queries against
``prior = max(rows / 2 - Sq, 0)`` earlier keys, plus the chunk's own
causal triangle Sq (Sq + 1) / 2, at 4 d FLOPs a pair (q k and p v).  Bytes
are q read and the output written, and K and V read once per (row, KV
head) over the same least prefix plus the chunk, in the compute dtype.  So
the share can only understate.  A row that finished its prompt before the
bucket's lower half still runs in the call and attends less; in a mix
whose prompts are all longer than half the top rung, none does.

A call's shapes are read from the trace: it returns o [B, H, Sq, d]; the
KV heads come from the model.
"""
import re

from chipbench.trace import result_shapes

PATTERN = r"/%flash_prefill_kv\d+[.\d]* = .* custom-call$"


def pairs_per_query(rows, sq):
    """Least (query, key) pairs a query of the chunk attends, on average."""
    return max(rows // 2 - sq, 0) + (sq + 1) / 2


def flops(b, h, sq, d, rows):
    return 4 * d * b * h * sq * pairs_per_query(rows, sq)


def bytes_moved(b, h, kvh, sq, d, rows, act=2):
    return (2 * b * h * sq * d * act                      # q in, o out
            + 2 * b * kvh * (max(rows // 2 - sq, 0) + sq) * d * act)


def kv_rows(key):
    """The KV rows a call was given, from its name."""
    return int(re.search(r"%flash_prefill_kv(\d+)", key).group(1))


def work(model, key):
    """(flops, bytes) of one call, the operation ``key`` of the trace."""
    b, h, sq, d = result_shapes(key)[0]
    rows = kv_rows(key)
    kvh = model["attn"]["n_kv_heads"]
    return flops(b, h, sq, d, rows), bytes_moved(b, h, kvh, sq, d, rows)
