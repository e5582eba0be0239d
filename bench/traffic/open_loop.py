"""Open loop: requests due on a Poisson schedule, sent whatever the system's
state.  Parameters: ``rate_per_s``, ``warm_s`` (traffic before the window,
counted as set-up), ``prompt``/``output`` length distributions, ``max_total``,
and optionally ``schedule_seed``: the order of the arrival gaps and sizes is
drawn from it, so every run plays the same schedule and ``--seed`` draws only
the token ids.  Without it ``--seed`` shuffles the order too.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from bench.traffic.lengths import exp_gaps, lengths


@dataclass
class Req:
    due: float  # seconds from the start of the traffic
    prompt: np.ndarray
    max_new: int
    client: int = -1


class OpenLoop:
    def __init__(self, spec: dict, seed: int, seconds: float, vocab: int):
        self.warm_s = float(spec["warm_s"])
        horizon = self.warm_s + seconds
        n = int(math.ceil(spec["rate_per_s"] * horizon))
        rng = np.random.default_rng([seed, 0x0FE7])
        order = (np.random.default_rng([spec["schedule_seed"], 0x0FE7])
                 if "schedule_seed" in spec else rng)
        due = np.cumsum(order.permutation(exp_gaps(spec["rate_per_s"], n)))
        plen, olen = lengths(spec, n, order)
        self.reqs = [Req(float(t), rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
                     for t, p, o in zip(due, plen, olen) if t < horizon]
        self._next = 0

    def pending(self, now: float):
        """Requests due by ``now`` and not yet handed out."""
        out = []
        while self._next < len(self.reqs) and self.reqs[self._next].due <= now:
            out.append(self.reqs[self._next])
            self._next += 1
        return out

    def next_due(self):
        return self.reqs[self._next].due if self._next < len(self.reqs) else None

    def finished(self, req: Req, now: float) -> None:
        pass


def make(spec, seed, seconds, vocab):
    return OpenLoop(spec, seed, seconds, vocab)
