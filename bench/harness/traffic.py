"""The one traffic generator. A mix is a parameter file in
``bench/traffic/``; the offered load (rate or clients) is the cell's,
in ``bench/cells/``.

The schedule's sizes (every prompt length, output budget, sampling
mode, system prompt, arrival gap and first-wave share) are a fixed set
of stratified quantiles of the mix's distributions, paired into
requests by a fixed permutation; ``--seed`` draws every token id and
every sampling stream. In an open loop the seed also draws the order in
which the requests and the gaps between arrivals come, so that a tail
is not that of one arrival order. A closed loop keeps one order of its
clients: there the order decides which requests finish inside the
window, and so the work itself (on one v5e chip, seeds that drew the
order moved longgen's tok_s from 26.9 to 30.3 while two runs of one
seed agreed within 0.13%).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Req:
    """One request as the client sends it."""
    prompt: np.ndarray          # int32 token ids
    max_new: int
    greedy: bool
    sample_seed: int
    due: float = 0.0            # open loop: seconds after the window opens


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution, as integers."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.round(v), dist["min"], dist["max"]).astype(np.int64)


def _greedy_flags(sampling: dict, n: int) -> np.ndarray:
    every = sampling.get("greedy_every", 0)
    return (np.arange(n) % every == 0) if every else np.zeros(n, bool)


class Generator:
    """Requests of one mix for one seed."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.t = traffic
        self.rng = np.random.default_rng(seed)            # ids, streams
        self.layout = np.random.default_rng(0)            # the pairing
        self.order = np.random.default_rng([seed, 2])     # open loop
        self.vocab = vocab
        sp = traffic.get("system_prompts", {})
        self.system = [self._tokens(sp["tokens"])
                       for _ in range(sp.get("count", 0))]

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, n).astype(np.int32)

    def _perm(self, a: np.ndarray) -> np.ndarray:
        return a[self.layout.permutation(len(a))]

    def _requests(self, n: int) -> List[Req]:
        user = self._perm(quantiles(self.t["prompt_tokens"], n))
        out = self._perm(quantiles(self.t["output_tokens"], n))
        greedy = self._perm(_greedy_flags(self.t["sampling"], n))
        system = self._perm(np.arange(n) % max(len(self.system), 1))
        reqs = []
        for i in range(n):
            parts = [self.system[system[i]]] if self.system else []
            parts.append(self._tokens(int(user[i])))
            reqs.append(Req(prompt=np.concatenate(parts),
                            max_new=int(out[i]), greedy=bool(greedy[i]),
                            sample_seed=int(self.rng.integers(2 ** 31))))
        return reqs

    def open_loop(self, rate: float, start: float, end: float) -> List[Req]:
        """Poisson arrivals at ``rate``/s due from ``start`` to ``end``
        seconds after the window opens (``start`` < 0 primes)."""
        n = max(1, int(round(rate * (end - start))))
        u = (np.arange(n) + 0.5) / n
        gaps = (-np.log1p(-u) / rate)[self.order.permutation(n)]
        due = start + np.cumsum(gaps) - gaps[0]
        reqs = self._requests(n)
        reqs = [reqs[i] for i in self.order.permutation(n)]
        for r, d in zip(reqs, due):
            r.due = float(d)
        return reqs

    def closed_loop(self, clients: int, per_client: int,
                    stagger: bool) -> List[List[Req]]:
        """Each client's queue. With ``stagger`` the first request of
        every client keeps its prompt and is given a stratified share
        of its output budget (at least one token), so that the first
        wave, prefilled in set-up, finishes spread over the run."""
        reqs = self._requests(clients * per_client)
        queues = [reqs[c * per_client:(c + 1) * per_client]
                  for c in range(clients)]
        if stagger:
            frac = self._perm((np.arange(clients) + 0.5) / clients)
            for c, q in enumerate(queues):
                q[0].max_new = max(1, int(math.ceil(frac[c] * q[0].max_new)))
        return queues
