"""The engine's own counters, read from its ``MetricsRegistry``, for the
readers that divide them: ``values`` at each end of the traced stretch,
``delta`` between the two.  A counter is named as the registry names it,
a labelled child with its labels sorted: ``repro_checkpoint_bytes_total``,
``repro_request_seconds_total{state=queued}``."""
from __future__ import annotations

from typing import Dict


def key(name: str, labels: Dict[str, str]) -> str:
    if not labels:
        return name
    return name + "{" + ",".join(f"{k}={v}" for k, v in
                                 sorted(labels.items())) + "}"


def values(registry) -> Dict[str, float]:
    """Every counter of ``registry`` and every labelled child of one."""
    out = {}
    for name, m in registry.snapshot()["metrics"].items():
        if m["type"] == "counter":
            for s in m["samples"]:
                out[key(name, s["labels"])] = s["value"]
    return out


def delta(before: Dict[str, float], after: Dict[str, float]
          ) -> Dict[str, float]:
    """What each counter counted between two ``values``; one first seen
    after ``before`` counted from 0."""
    return {k: v - before.get(k, 0.0) for k, v in after.items()}
