"""The one traffic generator: turns a traffic file into the requests of a run.

A traffic file (``traffic/<name>.json``) is data only. Each of its parts
names, by its ``kind``, a module of its own that the registry finds:

  query     ``queries/<kind>.py``: the query, built through the engine's
            public builder, and its plain reference
  keys      ``keys/<kind>.py``: what each request asks about (its params)
  arrivals  ``arrivals/<kind>.py``: when requests are sent and how the
            window closes
  warmup    how many requests are served before the window, from a stream
            of their own

The seed picks the keys; the sizes and the schedule are the same for every
seed.
"""
from __future__ import annotations

from dataclasses import dataclass
from types import ModuleType
from typing import Any, Dict, List

import numpy as np


@dataclass
class Workload:
    arrivals: ModuleType  # the arrival process's module
    spec: Dict[str, Any]  # its part of the traffic file
    streams: List[List[Dict[str, int]]]  # params, one list per stream
    warmup: List[Dict[str, int]]


def make(reg, traffic: Dict[str, Any], dep, seed: int, seconds: float) -> Workload:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    warm_rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    arrivals = reg.arrivals(traffic["arrivals"]["kind"])
    n_streams, length = arrivals.shape(traffic["arrivals"], seconds)
    keys = reg.keys(traffic["keys"]["kind"])
    streams, warm = keys.draw(traffic["keys"], traffic["query"], dep, rng, warm_rng,
                              n_streams, length, int(traffic["warmup"]))
    return Workload(arrivals, traffic["arrivals"], streams, warm)
