"""The one traffic generator: it reads a mix's data file and yields
requests. The schedule (prompt lengths, output lengths and, open loop,
arrival gaps) is drawn once from the mix file's ``schedule_seed``; the
run's seed draws every token id. So every seed serves the same work at the
same times on other inputs, and a run's spread is the system's and not the
draw's. The schedule takes stratified quantiles of each distribution and
orders them in blocks of ``block`` consecutive requests, each block holding
one value from each of ``block`` strata, so that every stretch of a run
carries the same mix of long and short work.

A mix file names ``loop`` ("open": arrivals at ``rate_per_s``; "closed":
``clients`` that each send their next request when the last returns),
``preroll_s`` of the same traffic before the measured window, the
lognormal ``prompt`` and ``output`` lengths (``median``, ``sigma``,
clipped to ``min``..``max``), the ``pool`` of requests one draw holds
(a multiple of ``block``), and the ``schedule_seed``; for the record, the
``source`` of its lengths and what it ``assumed``.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

from bench.cell import rng


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n stratified lognormal lengths: the (i + 1/2)/n quantiles."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(int)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """n stratified exponential inter-arrival gaps at ``rate`` per second."""
    return np.array([-math.log(1 - (i + 0.5) / n) for i in range(n)]) / rate


def blocked(r: np.random.Generator, n: int, block: int) -> np.ndarray:
    """An order of n sorted values: each run of ``block`` positions takes
    one value from each of ``block`` equal strata, shuffled within."""
    nb = n // block
    out = np.empty((nb, block), dtype=int)
    for g in range(block):
        out[:, g] = g * nb + r.permutation(nb)
    for row in out:
        r.shuffle(row)
    return out.reshape(-1)


class Traffic:
    """Requests of one mix for one seed, as (prompt ids, output length)."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.vocab = vocab
        self._order = rng(spec["schedule_seed"], 5)
        self._ids = rng(seed, 2)
        n = spec["pool"]
        if n % spec["block"]:
            raise ValueError(f"pool {n} is not a multiple of block "
                             f"{spec['block']}")
        self._prompts = quantiles(spec["prompt"], n)
        self._outputs = quantiles(spec["output"], n)
        self._gaps = (exp_gaps(spec["rate_per_s"], n)
                      if spec["loop"] == "open" else None)
        self._queue: list = []
        self._t = 0.0

    def _refill(self):
        r = self._order
        n, b = self.spec["pool"], self.spec["block"]
        pl = self._prompts[blocked(r, n, b)]
        ol = self._outputs[blocked(r, n, b)]
        gaps = self._gaps[blocked(r, n, b)] if self._gaps is not None \
            else np.zeros(n)
        for p, o, g in zip(pl, ol, gaps):
            self._t += float(g)
            ids = self._ids.integers(0, self.vocab, size=int(p),
                                     dtype=np.int32)
            self._queue.append((self._t, ids, int(o)))
        self._queue.reverse()

    def next(self):
        """(due seconds from the pre-roll's start for an open loop, prompt
        ids, output length). A closed loop ignores the due time."""
        if not self._queue:
            self._refill()
        return self._queue.pop()
