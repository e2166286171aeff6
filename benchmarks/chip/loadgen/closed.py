"""Closed loop: ``clients`` callers, each sending its next request as soon
as its previous one has finished.  Requests come from one sequence, in
rounds: each round is the mix's fixed set of ``sizes`` prompt lengths in a
fixed order drawn once, so any stretch of the sequence holds the sizes in
nearly equal shares and every seed sends the same lengths in the same
order.  The seed draws the prompt tokens.

Mix keys: ``clients``, ``prompt`` and ``output`` (length specs, see
``common.quantile_sizes``), ``sizes`` (lengths per round)."""
from __future__ import annotations

from typing import Dict, List, Tuple

from chipbench.loadgen.common import POOL_SEED, quantile_sizes, rng, tokens


class Generator:
    def __init__(self, mix: Dict, seed: int, seconds: float, vocab: int):
        self.clients = int(mix["clients"])
        n = int(mix["sizes"])
        self.prompts = quantile_sizes(mix["prompt"], n)
        self.outputs = quantile_sizes(mix["output"], n)
        self.g = rng(seed)
        self.order = rng(POOL_SEED)
        self.vocab = vocab
        self._round: List[int] = []

    def _next(self, client: int, due: float):
        if not self._round:
            self._round = list(self.order.permutation(len(self.prompts)))
        i = self._round.pop()
        return (due, client, tokens(self.g, self.prompts[i], self.vocab),
                self.outputs[i])

    def start(self) -> List[Tuple[float, int, object, int]]:
        """(due second from the window's start, client, prompt, max_new)
        of the requests sent when the window opens."""
        return [self._next(c, 0.0) for c in range(self.clients)]

    def done(self, client: int, now: float):
        """The requests sent when ``client``'s request finished at
        ``now``."""
        return [self._next(client, now)]
