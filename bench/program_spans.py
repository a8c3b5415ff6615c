"""The program's own spans in a profiler trace, on the device ops' clock.

The served path opens ``grf.*`` spans (``repro.tracing``: ``grf.ticket``
and, inside it, ``grf.bind``, ``grf.execute``, ``grf.path.prepare``,
``grf.traverse``, ``grf.path.to_batch``, ``grf.finalize``, ...) and its
jitted sweeps carry ``grf.*`` named scopes. ``read_program(path)`` pulls
both out of an ``.xplane.pb``: the host spans, and the device ops whose
metadata names a ``grf.`` scope. ``reduce(events, program)`` then gives,
inside the benchmark's ``bench.window`` span, per span name:

* ``count``: spans that start in the window;
* ``span_s``: their summed wall seconds;
* ``self_s``: the part of them under no child span;
* ``idle_s``: device-idle seconds inside them;
* ``idle_under``: device-idle seconds in their own part (the innermost
  span there);

and ``scope_s``: the union of the device op intervals under each
innermost named scope, in seconds.

``bench/trace.py`` keeps only the ``bench.`` spans, so none of this moves
``idle_gaps``, ``busy_s``, ``module_s`` or ``top_ops``.

    python -m bench.program_spans --workload <cell> --seed <n> --seconds <s> --trace 1

runs one cell exactly as ``bench/run.py`` does and prints the reduction as
one more line on stderr, ``program spans: {...}``.
"""
from __future__ import annotations

import bisect
import collections
import json
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from bench.trace import WINDOW_SPAN, TraceEvents, _clip, _union

PREFIX = "grf."
SCOPE = re.compile(r"grf\.[A-Za-z0-9_.]+")


@dataclass
class Program:
    # (span name, start_ns, end_ns)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    # (plane, innermost grf scope, start_ns, duration_ns)
    scoped_ops: List[Tuple[str, str, float, float]] = field(default_factory=list)


def read_program(path) -> Program:
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    out = Program()
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        out.spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    scopes = [s for v in dict(e.stats).values() if isinstance(v, str)
                              for s in SCOPE.findall(v)]
                    if scopes:
                        out.scoped_ops.append((plane.name, scopes[-1], e.start_ns,
                                               e.duration_ns))
    return out


def _own_parts(spans):
    """(name, a, b) stretches of each span not under a child span, for
    spans that nest (one thread)."""
    out = []
    stack: List[list] = []  # [name, end, cursor]

    def pop():
        name, end, cursor = stack.pop()
        out.append((name, cursor, end))
        if stack:
            stack[-1][2] = end

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= a:
            pop()
        if stack:
            out.append((stack[-1][0], stack[-1][2], a))
        stack.append([name, b, a])
    while stack:
        pop()
    return [(n, a, b) for n, a, b in out if b > a]


def reduce(ev: TraceEvents, program: Program) -> Dict:
    windows = [(a, b) for n, a, b in ev.spans if n == WINDOW_SPAN]
    if not windows:
        return {}
    lo, hi = windows[0]
    by_plane: Dict[str, list] = collections.defaultdict(list)
    for plane, _m, _n, start, dur in ev.ops:
        by_plane[plane].append((start, start + dur))
    busy = [_union(_clip(iv, lo, hi)) for iv in by_plane.values()] or [[]]
    planes = []  # (intervals, their starts, cumulative busy ns)
    for iv in busy:
        c = [0.0]
        for a, b in iv:
            c.append(c[-1] + b - a)
        planes.append((iv, [a for a, _ in iv], c))

    def idle(a, b):
        """Device-idle ns in [a, b], averaged over the device planes."""
        total = 0.0
        for iv, starts, c in planes:
            i = bisect.bisect_right(starts, a)
            j = bisect.bisect_left(starts, b)
            covered = c[j] - c[i]
            if i > 0:
                covered += max(min(iv[i - 1][1], b) - a, 0.0)
            if j > i and iv[j - 1][1] > b:
                covered -= iv[j - 1][1] - b
            total += (b - a) - covered
        return total / len(planes)

    spans = [(n, max(a, lo), min(b, hi)) for n, a, b in program.spans
             if lo <= a < hi]
    per: Dict[str, Dict[str, float]] = collections.defaultdict(lambda: dict(
        count=0, span_s=0.0, self_s=0.0, idle_s=0.0, idle_under=0.0))
    for name, a, b in spans:
        per[name]["count"] += 1
        per[name]["span_s"] += (b - a) * 1e-9
        per[name]["idle_s"] += idle(a, b) * 1e-9
    for name, a, b in _own_parts(spans):
        per[name]["self_s"] += (b - a) * 1e-9
        per[name]["idle_under"] += idle(a, b) * 1e-9
    scoped: Dict[str, Dict[str, list]] = collections.defaultdict(
        lambda: collections.defaultdict(list))
    for plane, scope, start, dur in program.scoped_ops:
        scoped[scope][plane].append((start, start + dur))
    scope_s = {s: sum(b - a for iv in on.values() for a, b in _union(_clip(iv, lo, hi)))
               * 1e-9 / len(on) for s, on in scoped.items()}
    return {"spans": {k: dict(v) for k, v in sorted(per.items())},
            "scope_s": dict(sorted(scope_s.items()))}


def main(argv=None, **kwargs) -> int:
    """``bench/run.py``'s main (``kwargs`` passed on), plus the reduction."""
    from bench import run

    found = {}
    read_xplane = run.read_xplane

    def read_both(path):
        ev = read_xplane(path)
        found.update(reduce(ev, read_program(path)))
        return ev

    run.read_xplane = read_both
    try:
        rc = run.main(argv, **kwargs)
    finally:
        run.read_xplane = read_xplane
    if found:
        run.log(f"program spans: {json.dumps(found, sort_keys=True)}")
    return rc


if __name__ == "__main__":
    from bench import run

    run.configure_compile_cache(run.ROOT)
    sys.exit(main())
