"""Sizes shared by the generators.  A mix names a distribution of lengths;
its sizes are the distribution's quantiles at (i + 1/2) / n, a fixed set,
and their order (and an open loop's gaps) are drawn once from
``POOL_SEED``, so that every seed sends the same work at the same times:
the seed changes the tokens, not the work, and runs of different seeds
spread no more than runs of one."""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

POOL_SEED = 20240117


def quantile_sizes(spec: Dict, n: int) -> List[int]:
    """``n`` lengths of the distribution ``spec``:
    {"fixed": v} | {"dist": "loguniform", "lo", "hi"} |
    {"dist": "lognormal", "median", "sigma", "lo", "hi"} (clipped)."""
    if "fixed" in spec:
        return [int(spec["fixed"])] * n
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "loguniform":
        lo, hi = math.log(spec["lo"]), math.log(spec["hi"])
        vals = [math.exp(lo + q * (hi - lo)) for q in qs]
    elif spec["dist"] == "lognormal":
        z = NormalDist()
        vals = [spec["median"] * math.exp(spec["sigma"] * z.inv_cdf(q))
                for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = spec.get("lo", 1), spec.get("hi", math.inf)
    return [int(round(min(max(v, lo), hi))) for v in vals]


def rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed))


def tokens(g: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return g.integers(0, vocab, n).astype(np.int32)
