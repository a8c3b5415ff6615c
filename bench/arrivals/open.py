"""Open loop: requests arrive on a fixed schedule whatever the answers, and
each is timed from when it was due.

Spec: ``{"kind": "open", "rate_per_s": r}``. One stream of
``round(r * seconds)`` requests. Every seed gets the same schedule: the
gaps are the stratified quantiles of an exponential law in one fixed
order, scaled to fill the window exactly. The order does not follow the
seed: where the short gaps cluster sets the tail, and a seed that moved
them would move the tail by more than any bound can hold (PERF.md).

The window's requests are those due before ``--seconds``; one with no
answer ``grace_s`` past the close never came.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from bench.window import Drive, Pump, Request

ARRIVAL_ORDER = 20260417  # the gaps' order, the same for every seed


def shape(spec, seconds: float):
    """(streams, requests per stream) this process asks of a key chooser."""
    return 1, int(round(float(spec["rate_per_s"]) * seconds))


def due_offsets(n: int, seconds: float) -> np.ndarray:
    """Due offsets of ``n`` arrivals in [0, seconds)."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    g = np.random.default_rng(ARRIVAL_ORDER).permutation(g) * (seconds / g.sum())
    return np.concatenate([[0.0], np.cumsum(g)[:-1]])


def drive(loop, query, wl, seconds: float, span, grace_s: float) -> Drive:
    stream = wl.streams[0]
    n = len(stream)
    pumps: List[Pump] = []
    owner: Dict[int, Request] = {}
    t0 = time.monotonic()
    reqs = [Request(params=p, due=t0 + float(d))
            for p, d in zip(stream, due_offsets(n, seconds))]
    give_up = t0 + seconds + grace_s
    i = 0
    while True:
        now = time.monotonic()
        with span("submit"):
            while i < n and reqs[i].due <= now:
                reqs[i].ticket = loop.submit(query, **reqs[i].params)
                reqs[i].submitted = time.monotonic()
                owner[id(reqs[i].ticket)] = reqs[i]
                i += 1
        start = time.monotonic()
        with span("pump"):
            done = loop.pump()
        if done:
            pumps.append(Pump(start, time.monotonic(), [owner[id(t)] for t in done]))
        now = time.monotonic()
        if (i == n and loop.pending == 0) or now > give_up:
            break
        flush = loop.next_due()
        wake = min(reqs[i].due if i < n else math.inf,
                   math.inf if flush is None else flush * 1e-6)
        if wake == math.inf:
            break  # nothing left to arrive and nothing the loop will serve
        if wake > now:
            with span("wait"):
                time.sleep(wake - now)
    return Drive(t0, t0 + seconds, time.monotonic(), reqs, pumps)


def in_window(d: Drive) -> List[Request]:
    """Every request due in the window."""
    return [r for r in d.requests if r.due < d.t_close]


def missing(d: Drive, window: List[Request]) -> int:
    """Requests of the window with no answer: error, rejection, timeout,
    none by the grace limit."""
    return sum(1 for r in window if not r.answered)
