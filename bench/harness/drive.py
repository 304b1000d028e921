"""Drives the window: wall-clock arrivals into ``AsyncEngine``.

Open loop (independent users): each request is submitted at its due
time whatever the server is doing, and its latency counts from that due
time, so a stall is charged to every request queued behind it. Closed
loop (a batch job): each client sends its next request the moment the
last one finishes.

The bench's own host spans (``jax.profiler.TraceAnnotation``) mark what
the driving thread is doing, so the trace can say what the host did in
each device idle gap: ``bench.wait`` (no work, waiting for the next
arrival), ``bench.add_request`` and ``bench.step`` (one
``AsyncEngine.step``). The driver reads only what ``AsyncEngine``'s
request handles show a client: tokens, their wall times, finished.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np

SPANS = ("bench.wait", "bench.add_request", "bench.step")


@dataclasses.dataclass
class Record:
    """What the client saw of one request (wall seconds, perf_counter)."""
    req: object
    handle: object
    due: float
    submit: float

    @property
    def tokens(self) -> List[int]:
        return self.handle.tokens

    @property
    def walls(self) -> List[float]:
        return self.handle.token_walls

    @property
    def finished(self) -> bool:
        return self.handle.finished


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def request_of(req, sampling: dict):
    from repro.serve.engine import Request
    return Request(prompt=req.prompt, max_new_tokens=req.max_new,
                   temperature=0.0 if req.greedy else sampling["temperature"],
                   top_k=sampling.get("top_k", 0), seed=req.sample_seed)


class Driver:
    """One process, one thread: submits, steps and reads the loop."""

    def __init__(self, loop, sampling: dict,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 on_tick: Optional[Callable[[float], None]] = None):
        self.loop = loop
        self.sampling = sampling
        self.clock = clock
        self.sleep = sleep
        self.on_tick = on_tick or (lambda now: None)
        self.records: List[Record] = []

    def submit(self, req, due: float) -> Record:
        with _span("bench.add_request"):
            h = self.loop.add_request(request_of(req, self.sampling))
        rec = Record(req, h, due=due, submit=self.clock())
        self.records.append(rec)
        return rec

    def step(self) -> None:
        with _span("bench.step"):
            self.loop.step()

    # -- open loop ------------------------------------------------------------

    def open_loop(self, reqs, window_open: float, close: float,
                  deadline: float) -> None:
        """Submit ``reqs`` (due seconds relative to ``window_open``) on
        time, stepping whenever there is work, until every request due
        before ``close`` has finished or ``deadline`` passes. Arrivals
        go on after ``close`` so that the requests still in flight keep
        the load they had."""
        reqs = sorted(reqs, key=lambda r: r.due)
        i = 0
        while True:
            now = self.clock()
            self.on_tick(now)
            while i < len(reqs) and window_open + reqs[i].due <= now:
                self.submit(reqs[i], window_open + reqs[i].due)
                i += 1
            if self.loop.has_work:
                self.step()
            elif i < len(reqs):
                with _span("bench.wait"):
                    self.sleep(max(0.0, min(window_open + reqs[i].due,
                                            deadline) - self.clock()))
            now = self.clock()
            submitted_all_due = (i == len(reqs)
                                 or window_open + reqs[i].due >= close)
            if now >= deadline or (i == len(reqs)
                                   and not self.loop.has_work):
                return
            if now >= close and submitted_all_due and all(
                    r.finished for r in self.records
                    if window_open <= r.due < close):
                return

    # -- closed loop ----------------------------------------------------------

    def start_clients(self, queues) -> None:
        """Submit every client's first request and step until each
        client's request in flight has its first token, that is, until
        every prompt is prefilled (the steady state the window starts
        from)."""
        self._queues = queues
        self._next = [1] * len(queues)
        self._heads = [self.submit(q[0], self.clock()) for q in queues]
        while not all(rec.tokens for rec in self._heads):
            self.step()
            self._refill()

    def closed_loop(self, until: float) -> None:
        """Keep every client's request in flight until ``until``: a
        client whose request finished sends its next one at once."""
        while self.clock() < until:
            self.on_tick(self.clock())
            self.step()
            self._refill()

    def _refill(self) -> None:
        for c, rec in enumerate(self._heads):
            q = self._queues[c]
            if rec.finished and self._next[c] < len(q):
                self._heads[c] = self.submit(q[self._next[c]], self.clock())
                self._next[c] += 1


def tokens_between(records: List[Record], t0: float, t1: float) -> int:
    """Output tokens that surfaced to clients in (t0, t1]."""
    n = 0
    for rec in records:
        w = np.asarray(rec.walls)
        n += int(((w > t0) & (w <= t1)).sum())
    return n
