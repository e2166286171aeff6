"""Work that the model's prefill and decode ask of the chip, counted from
the model's sizes and the engine's own token counts, the same whatever
implements it.

Each prompt token prefilled multiplies through every weight matrix
(2 FLOPs per weight) and runs the SSD scan (``kernels/ssd_scan.py``, per
token at the model's SSD chunk length); each chunk program reads every
weight once, and each row of it reads and writes its SSM states and conv
windows.  Each token decoded multiplies through every weight matrix and
the head and runs the fused state update (``kernels/decode_fused.py``);
each decode step reads every weight once, and each token's row reads and
writes its states.  The head of a prefill (one row per prompt) is left
out, and a row's state is counted once per full chunk of its tokens: the
least time these give lies at or below the true least time.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple

from chipbench.kernels import decode_fused, ssd_scan

ACT = 2      # bytes of a bfloat16 activation, weight or conv window entry
STATE = 4    # bytes of a float32 SSM state entry


def sizes(model) -> Dict[str, int]:
    s = model["ssm"]
    d = model["d_model"]
    di = s["expand"] * d
    gn = s["n_groups"] * s["d_state"]
    return {"D": d, "di": di, "H": di // s["headdim"], "P": s["headdim"],
            "N": s["d_state"], "G": s["n_groups"], "K": s["conv_kernel"],
            "C": di + 2 * gn, "Q": s["chunk"], "V": model["vocab_size"],
            "L": model["n_layers"]}


def matmul_weights(model) -> int:
    """Weights one token multiplies through, the head excluded."""
    z = sizes(model)
    return z["L"] * (z["D"] * (z["di"] + z["C"] + z["H"]) + z["di"] * z["D"])


def weight_bytes(model) -> int:
    """Every stored weight once: the layers, the tied embedding and head."""
    z = sizes(model)
    vp = -(-z["V"] // model["vocab_pad_multiple"]) * \
        model["vocab_pad_multiple"]
    small = z["L"] * (z["C"] * (z["K"] + 1) + 3 * z["H"] + z["di"]
                      + z["D"]) + z["D"]
    return ACT * (matmul_weights(model) + vp * z["D"] + small)


def row_state_bytes(z) -> int:
    """One row's SSM states and conv windows over all layers, one way."""
    return z["L"] * (z["H"] * z["P"] * z["N"] * STATE
                     + (z["K"] - 1) * z["C"] * ACT)


def prefill_work(model, tokens: float, programs: float,
                 chunk: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``tokens`` prompt tokens prefilled by
    ``programs`` runs of the chunk program, ``chunk`` tokens a row."""
    z = sizes(model)
    scan = ssd_scan.flops(1, z["Q"], z["H"], z["P"], z["N"], z["G"],
                          z["Q"]) / z["Q"]
    flops = tokens * (2 * matmul_weights(model) + z["L"] * scan)
    moved = (programs * weight_bytes(model)
             + tokens / chunk * 2 * row_state_bytes(z))
    return flops, moved


def decode_work(model, tokens: float, steps: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of ``tokens`` tokens decoded in ``steps`` steps."""
    z = sizes(model)
    per = (2 * matmul_weights(model) + 2 * z["D"] * z["V"]
           + z["L"] * decode_fused.flops(1, z["H"], z["P"], z["N"], z["C"],
                                         z["K"]))
    return (tokens * per,
            steps * weight_bytes(model) + tokens * 2 * row_state_bytes(z))


def roofline_share(works: Iterable[Tuple[float, float]], seconds: float,
                   peak: Dict[str, float]) -> Optional[float]:
    """Per cent of ``seconds`` that the chip needs at least for ``works``
    ((flops, bytes) pairs): each at the larger of its compute and its
    memory time at peak.  None where there is nothing to read."""
    works = [w for w in works if w[0] or w[1]]
    if not works or seconds <= 0:
        return None
    least = sum(max(f / peak["flops_per_s"], b / peak["hbm_bytes_per_s"])
                for f, b in works)
    return 100.0 * least / seconds


def kernel_share(kernel, ctx) -> Optional[float]:
    """Roofline share of one kernel's calls in the traced window: each
    call's work from its shapes in the trace (``kernel.work``) over their
    device time."""
    from chipbench.trace import device_seconds, runs
    s = ctx["summary"]
    works = [(n * f, n * b) for key, n in runs(s, kernel.PATTERN).items()
             for f, b in [kernel.work(ctx["model"], key)]]
    return roofline_share(works, device_seconds(s, kernel.PATTERN),
                          ctx["peak"])
