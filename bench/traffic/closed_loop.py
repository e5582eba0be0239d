"""Closed loop: ``clients`` callers, each sending its next request when its
last one returns.  Sizes come from one shuffled pool of ``pool`` requests,
handed out in order.  Parameters: ``clients``, ``warm_s``, ``pool``,
``prompt``/``output`` length distributions, ``max_total``.
"""

from __future__ import annotations

import numpy as np

from bench.traffic.lengths import lengths
from bench.traffic.open_loop import Req


class ClosedLoop:
    def __init__(self, spec: dict, seed: int, seconds: float, vocab: int):
        self.warm_s = float(spec["warm_s"])
        rng = np.random.default_rng([seed, 0xC105])
        n = int(spec["pool"])
        self._plen, self._olen = lengths(spec, n, rng)
        self._rng = rng
        self._vocab = vocab
        self._taken = 0
        self._ready = [self._draw(c, 0.0) for c in range(int(spec["clients"]))]

    def _draw(self, client: int, now: float) -> Req:
        if self._taken >= len(self._plen):
            raise RuntimeError("closed-loop pool exhausted: raise 'pool'")
        i = self._taken
        self._taken += 1
        prompt = self._rng.integers(0, self._vocab, int(self._plen[i]), dtype=np.int32)
        return Req(now, prompt, int(self._olen[i]), client)

    def pending(self, now: float):
        out, self._ready = self._ready, []
        return out

    def next_due(self):
        return None  # the next request waits for a reply, not for the clock

    def finished(self, req: Req, now: float) -> None:
        self._ready.append(self._draw(req.client, now))


def make(spec, seed, seconds, vocab):
    return ClosedLoop(spec, seed, seconds, vocab)
