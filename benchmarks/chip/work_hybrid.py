"""Work that a parallel-hybrid model's prefill asks of the chip (Falcon-H1:
attention and a Mamba-2 mixer side by side in every layer, then an MLP),
counted from the model's sizes, the engine's own token counts and the
attention calls' shapes, with ``work.py``'s conventions.

Each prompt token prefilled multiplies through every weight matrix of its
layers (2 FLOPs per weight), runs the SSD scan (``kernels/ssd_scan.py``,
per token at the model's SSD chunk length) and attends earlier keys (4 d
FLOPs a pair a query head).  Attention is counted at the least live
prefix of ``kernels/flash_prefill.py``: the window's prompt tokens go to
the traced calls whose least pairs a query are fewest first, so the count
lies at or below the true one whichever rows were idle.  Each chunk
program reads the layers' weights and the untied head once and gathers its
tokens' rows of the embedding; each row of it reads and writes its SSM
states and conv windows once per full chunk of its tokens, and each token
writes its keys and values.  The head of a prefill (one row per prompt) is
left out.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from chipbench.kernels import flash_prefill, ssd_scan

ACT = 2      # bytes of a bfloat16 activation, weight, key or value
STATE = 4    # bytes of a float32 SSM state entry


def sizes(model) -> Dict[str, int]:
    s, a = model["ssm"], model["attn"]
    d = model["d_model"]
    di = s["d_ssm"] if s.get("d_ssm") is not None else s["expand"] * d
    gn = s["n_groups"] * s["d_state"]
    return {"D": d, "di": di, "H": di // s["headdim"], "P": s["headdim"],
            "N": s["d_state"], "G": s["n_groups"], "K": s["conv_kernel"],
            "C": di + 2 * gn, "Q": s["chunk"], "F": model["d_ff"],
            "AH": a["n_heads"], "KV": a["n_kv_heads"], "hd": a["head_dim"],
            "V": model["vocab_size"], "L": model["n_layers"]}


def matmul_weights(model) -> int:
    """Weights one token multiplies through, the head excluded."""
    z = sizes(model)
    attn = z["D"] * (z["AH"] + 2 * z["KV"]) * z["hd"] + z["AH"] * z["hd"] \
        * z["D"]
    mamba = z["D"] * (z["di"] + z["C"] + z["H"]) + z["di"] * z["D"]
    return z["L"] * (attn + mamba + 3 * z["D"] * z["F"])


def weight_bytes(model) -> int:
    """The weights a chunk program reads whole: the layers and the untied
    head (the embedding is gathered by token, ``prefill_work``)."""
    z = sizes(model)
    vp = -(-z["V"] // model["vocab_pad_multiple"]) * \
        model["vocab_pad_multiple"]
    small = z["L"] * (z["C"] * (z["K"] + 1) + 3 * z["H"] + z["di"]
                      + 2 * z["D"]) + z["D"]
    return ACT * (matmul_weights(model) + vp * z["D"] + small)


def row_state_bytes(z) -> int:
    """One row's SSM states and conv windows over all layers, one way."""
    return z["L"] * (z["H"] * z["P"] * z["N"] * STATE
                     + (z["K"] - 1) * z["C"] * ACT)


def attention_pairs(calls: Iterable[Tuple[float, int, int, int]],
                    tokens: float, layers: int) -> float:
    """Least (query, key) pairs a query head for ``tokens`` prompt tokens
    through ``layers`` layers, given the traced attention calls as
    (runs, batch rows, chunk tokens, KV rows): the tokens fill the calls
    with the fewest least pairs a query first."""
    left = tokens * layers
    pairs = 0.0
    for per, cap in sorted((flash_prefill.pairs_per_query(rows, sq),
                            runs * b * sq) for runs, b, sq, rows in calls):
        n = min(left, cap)
        pairs += n * per
        left -= n
    return pairs


def prefill_work(model, tokens: float, programs: float, chunk: int,
                 pairs: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``tokens`` prompt tokens prefilled by
    ``programs`` runs of the chunk program, ``chunk`` tokens a row, with
    ``pairs`` (query, key) pairs a query head over all layers."""
    z = sizes(model)
    scan = ssd_scan.flops(1, z["Q"], z["H"], z["P"], z["N"], z["G"],
                          z["Q"]) / z["Q"]
    flops = (tokens * (2 * matmul_weights(model) + z["L"] * scan)
             + 4 * z["hd"] * z["AH"] * pairs)
    moved = (programs * weight_bytes(model)
             + tokens * z["D"] * ACT
             + tokens / chunk * 2 * row_state_bytes(z)
             + tokens * z["L"] * 2 * z["KV"] * z["hd"] * ACT)
    return flops, moved
