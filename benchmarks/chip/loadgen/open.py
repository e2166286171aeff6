"""Open loop: independent users arriving on a schedule fixed before the
window opens, whatever the server does.  The window holds
``rate_per_s * seconds`` requests.  Their gaps are drawn once from a Gamma
distribution of shape ``gamma_shape`` (coefficient of variation
1 / sqrt(shape); BurstGPT, arXiv:2401.17644, fits shapes under 1 to bursty
chat), scaled so the last request arrives inside the window; prompt and
output lengths are the quantile sets of ``prompt`` and ``output`` in an
order drawn once.  Every seed sends the same lengths at the same times;
the seed draws the prompt tokens.  ``rate_scale`` scales the rate, for a
sweep."""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from chipbench.loadgen.common import POOL_SEED, quantile_sizes, rng, tokens


class Generator:
    def __init__(self, mix: Dict, seed: int, seconds: float, vocab: int,
                 rate_scale: float = 1.0):
        n = max(1, int(round(mix["rate_per_s"] * rate_scale * seconds)))
        shape = float(mix["gamma_shape"])
        pool = rng(POOL_SEED)
        gaps = pool.gamma(shape, 1.0, n)
        # the first request opens the window; the n gaps fill it
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        due *= seconds / gaps.sum()
        prompts = pool.permutation(quantile_sizes(mix["prompt"], n))
        outputs = pool.permutation(quantile_sizes(mix["output"], n))
        g = rng(seed)
        self.schedule = [(float(t), i, tokens(g, int(p), vocab), int(o))
                         for i, (t, p, o) in enumerate(zip(due, prompts,
                                                           outputs))]

    def start(self) -> List[Tuple[float, int, object, int]]:
        return list(self.schedule)

    def done(self, client: int, now: float):
        return []
