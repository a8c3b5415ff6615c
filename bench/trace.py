"""Reduce a profiler trace of the measured window to the benchmark's numbers.

``read_xplane(path)`` pulls what the reduction needs out of the JAX
profiler's ``.xplane.pb``:

* device events: every op on a ``/device:`` plane's ``XLA Ops`` line, with
  the XLA module it belongs to, and every ``XLA Modules`` event;
* host spans: the events the benchmark opened with ``TraceAnnotation``
  (names starting ``bench.``).

``summarize(events)`` then gives, over the ``bench.window`` span:

* ``busy_s``: the union of the op intervals (averaged over the device
  planes), and ``window_s``; the idle share is ``1 - busy_s / window_s``;
* ``module_s``: device seconds per XLA module name (``jit_bfs``, ...),
  with any ``(<id>)`` suffix dropped;
* ``top_ops``: the device ops that took most time;
* ``idle_gaps``: idle time inside the window labelled by the benchmark
  span that covers most of each gap (``pump``, ``submit``, ``wait``),
  as totals per label and as the longest single gaps.

Times are seconds; the events are kept as plain tuples so that a test can
feed a small recorded trace as JSON.
"""
from __future__ import annotations

import bisect
import collections
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclass
class TraceEvents:
    # (plane, module, op name, start_ns, duration_ns)
    ops: List[Tuple[str, str, str, float, float]] = field(default_factory=list)
    # (plane, module name, start_ns, duration_ns)
    modules: List[Tuple[str, str, float, float]] = field(default_factory=list)
    # (span name, start_ns, end_ns)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    def to_json(self) -> Dict:
        return {"ops": self.ops, "modules": self.modules, "spans": self.spans}

    @classmethod
    def from_json(cls, d: Dict) -> "TraceEvents":
        return cls(ops=[tuple(x) for x in d["ops"]],
                   modules=[tuple(x) for x in d["modules"]],
                   spans=[tuple(x) for x in d["spans"]])


@dataclass
class Summary:
    busy_s: float
    window_s: float
    module_s: Dict[str, float]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    n_ops: int

    def idle_pct(self):
        if self.n_ops == 0 or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def module_name(name: str) -> str:
    return name.split("(")[0].strip()


def op_name(name: str) -> str:
    """An op's name without the HLO text that the TPU trace appends
    (``%fusion.12 = u8[...] fusion(...)`` -> ``%fusion.12``)."""
    return name.split(" = ")[0].strip()


def read_xplane(path) -> TraceEvents:
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    ev = TraceEvents()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        stats = dict(e.stats)
                        ev.ops.append((plane.name, str(stats.get("hlo_module", "")),
                                       op_name(e.name), e.start_ns, e.duration_ns))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        ev.modules.append((plane.name, module_name(e.name),
                                           e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        ev.spans.append((e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
    ev.ops = _attribute(ev.ops, ev.modules)
    return ev


def _attribute(ops, modules):
    """Give an op that carries no module name the module whose event on the
    same plane contains its start."""
    runs = collections.defaultdict(list)
    for plane, name, start, dur in modules:
        runs[plane].append((start, start + dur, name))
    for r in runs.values():
        r.sort()
    starts = {p: [a for a, _, _ in r] for p, r in runs.items()}
    out = []
    for plane, module, name, start, dur in ops:
        if not module and plane in runs:
            i = bisect.bisect_right(starts[plane], start) - 1
            if i >= 0 and runs[plane][i][1] >= start:
                module = runs[plane][i][2]
        out.append((plane, module, name, start, dur))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def summarize(ev: TraceEvents, top: int = 10) -> Summary:
    windows = [(a, b) for name, a, b in ev.spans if name == WINDOW_SPAN]
    stamps = [s for _, _, _, s, _ in ev.ops] + [s + d for *_, s, d in ev.ops]
    if windows:
        lo, hi = windows[0]
    elif stamps:
        lo, hi = min(stamps), max(stamps)
    else:
        return Summary(0.0, 0.0, {}, [], [], 0)

    by_plane: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(list)
    op_time: Dict[str, float] = collections.Counter()
    n_ops = 0
    for plane, module, name, start, dur in ev.ops:
        if start + dur <= lo or start >= hi:
            continue
        n_ops += 1
        by_plane[plane].append((start, start + dur))
        op_time[f"{module_name(module)}/{name}"] += dur * 1e-9
    busy = {p: _union(_clip(iv, lo, hi)) for p, iv in by_plane.items()}
    busy_s = (sum(b - a for iv in busy.values() for a, b in iv)
              / max(len(busy), 1)) * 1e-9

    module_s: Dict[str, float] = collections.Counter()
    for _plane, name, start, dur in ev.modules:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            module_s[name] += (b - a) * 1e-9

    gaps: List[Tuple[float, float]] = []
    for iv in busy.values():
        edge = lo
        for a, b in iv:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
        if hi > edge:
            gaps.append((edge, hi))
    leaf = sorted((a, b, n[len(SPAN_PREFIX):]) for n, a, b in ev.spans
                  if n != WINDOW_SPAN)
    starts = [a for a, _, _ in leaf]

    def label(a, b):
        best, best_ov = "other", 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(leaf) and leaf[i][0] < b:
            ov = min(b, leaf[i][1]) - max(a, leaf[i][0])
            if ov > best_ov:
                best, best_ov = leaf[i][2], ov
            i += 1
        return best

    totals: Dict[str, float] = collections.Counter()
    labelled = []
    for a, b in gaps:
        name = label(a, b)
        totals[name] += (b - a) * 1e-9
        labelled.append((name, (b - a) * 1e-9))
    n_planes = max(len(busy), 1)
    idle = [(f"{k} (all gaps)", v / n_planes)
            for k, v in sorted(totals.items(), key=lambda kv: -kv[1])]
    longest = sorted(labelled, key=lambda kv: -kv[1])[: max(top - len(idle), 0)]
    idle += [(f"{k} (one gap)", v) for k, v in longest]
    return Summary(
        busy_s=busy_s, window_s=(hi - lo) * 1e-9, module_s=dict(module_s),
        top_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=idle[:top], n_ops=n_ops,
    )
