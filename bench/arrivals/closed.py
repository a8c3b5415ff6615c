"""Closed loop: each client keeps one query outstanding and sends its next
on the answer to its last.

Spec: ``{"kind": "closed", "clients": c}``. ``c`` streams of ``STREAM``
requests, client ``i`` cycling through stream ``i``. The window closes at
the first completion at or after ``--seconds``, so no query is cut in
half, and that whole span is the divisor of a rate. The cell's clients
must be few enough that every one is answered inside the window: a client
with no answer by the close never got one (its tickets were dropped,
failed or starved), and so does every request still outstanding when the
loop stops answering before the window can close.
"""
from __future__ import annotations

import time
from typing import Dict, List

from bench.window import Drive, Pump, Request

STREAM = 64  # requests drawn per client


def shape(spec, seconds: float):
    """(streams, requests per stream) this process asks of a key chooser."""
    return int(spec["clients"]), STREAM


def drive(loop, query, wl, seconds: float, span, grace_s: float) -> Drive:
    pumps: List[Pump] = []
    reqs: List[Request] = []
    issued = [0] * len(wl.streams)
    owner: Dict[int, Request] = {}

    def issue(c: int):
        params = wl.streams[c][issued[c] % len(wl.streams[c])]
        issued[c] += 1
        r = Request(params=params, due=time.monotonic(), client=c)
        with span("submit"):
            r.ticket = loop.submit(query, **params)
        r.submitted = time.monotonic()
        owner[id(r.ticket)] = r
        reqs.append(r)

    t0 = time.monotonic()
    for c in range(len(wl.streams)):
        issue(c)
    close = None
    while close is None:
        start = time.monotonic()
        with span("pump"):
            done = loop.pump()
        if not done:
            due = loop.next_due()
            if due is None or time.monotonic() > t0 + seconds + grace_s:
                break  # the loop stopped answering: the window ends here
            time.sleep(max(due * 1e-6 - time.monotonic(), 0.0))
            continue
        pumps.append(Pump(start, time.monotonic(), [owner[id(t)] for t in done]))
        ends = [t.done_us * 1e-6 for t in done
                if t.done_us is not None and t.done_us * 1e-6 >= t0 + seconds]
        if ends:
            close = min(ends)  # the first completion at or after --seconds
            break
        for t in done:
            issue(owner[id(t)].client)
    stalled = close is None
    if stalled:
        close = time.monotonic()
    return Drive(t0, close, time.monotonic(), reqs, pumps, stalled)


def in_window(d: Drive) -> List[Request]:
    """Every request answered by the close."""
    return [r for r in d.requests if r.done is not None and r.done <= d.t_close]


def missing(d: Drive, window: List[Request]) -> int:
    """Clients with no answer in the window, requests of the window that
    failed, and, where the loop stalled, every request still outstanding."""
    answered = {r.client for r in window if r.answered}
    n = len({r.client for r in d.requests} - answered)
    n += sum(1 for r in window if not r.answered)
    if d.stalled:
        n += sum(1 for r in d.requests if r.done is None)
    return n
