"""Rate sweep of an open-loop cell: the highest offered rate the served path
sustains without a growing backlog.

    python bench/sweep.py --workload gap-urand-s22.nbr2_open --seed <n> \
        --seconds 10 --rates 20,40,80,160

One process: the cell's set-up once, then one open-loop window per rate,
each with the cell's own traffic at that rate. For every rate it prints one
JSON line: offered and completed rate, p50/p95 of the latency from when each
request was due, the mean latency of the window's first and last quarter of
arrivals (a backlog that grows shows as a last quarter far above the
first), and how late the generator ran. The cell's rate is then fixed, as a
number in its traffic file, at about four fifths of the highest sustained
rate.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run, traffic  # noqa: E402
from bench.registry import Registry  # noqa: E402


def quarter_means(reqs):
    q = max(len(reqs) // 4, 1)
    lat = [((r.done or r.due) - r.due) * 1e3 for r in reqs]
    return sum(lat[:q]) / q, sum(lat[-q:]) / q


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True, help="comma-separated queries/s")
    args = ap.parse_args(argv)
    reg = Registry(run.ROOT)
    cell = reg.cell(args.workload)
    try:
        run.require_accelerator(cell.chips)
    except run.NoAccelerator as e:
        run.log(f"sweep: {e}")
        return 2
    run.configure_compile_cache(run.ROOT)
    dep, eng, loop, query, _, split = run.setup(cell, reg, args.seed, args.seconds)
    run.log(f"setup split (s): {json.dumps(split)}")
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        mix = json.loads(json.dumps(cell.traffic))
        mix["arrivals"]["rate_per_s"] = rate
        wl = traffic.make(reg, mix, dep, args.seed + 1 + i, args.seconds)
        d = wl.arrivals.drive(loop, query, wl, args.seconds,
                              lambda name: contextlib.nullcontext(), run.GRACE_S)
        reqs = d.requests
        lat = [((r.done if r.ok else d.t_close + run.GRACE_S) - r.due) * 1e3 for r in reqs]
        first, last = quarter_means(reqs)
        late = max((r.submitted - r.due for r in reqs if r.ticket is not None),
                   default=0.0)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "completed_per_s": sum(r.ok for r in reqs) / (d.t_end - d.t0),
            "p50_ms": run.nearest_rank(lat, 50.0), "p95_ms": run.nearest_rank(lat, 95.0),
            "first_quarter_ms": first, "last_quarter_ms": last,
            "drain_s": d.t_end - d.t_close, "generator_late_max_ms": late * 1e3,
        }), flush=True)
        time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
