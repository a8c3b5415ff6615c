"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``REPRO_BENCH_QUICK=1`` runs
reduced sizes. Roofline numbers (§Roofline) come from the dry-run
(``python -m repro.launch.dryrun``), not from here — this file is the
paper-experiment reproduction on CPU.

After the figure modules run, the harness derives the **plan-overhead
record**: for every fig8/fig9 point that has both a ``native_*`` (raw
traversal kernel) and a ``planned_*`` (full plan-IR prepared-plan path)
row, the planned/native ratio is written to ``BENCH_plan_overhead.json``
at the repo root. The compiled query runtime's contract is that prepared
plans add at most ``REPRO_PLAN_OVERHEAD_MAX`` (default 1.3x, the stored
threshold) on top of the raw kernels at S=32 lanes; the bench stage FAILS
when the worst ratio regresses above the threshold, so the perf
trajectory accumulates and is enforced from this PR on.

The fig13 module additionally publishes a **serving record**
(``BENCH_serving.json``): warm p50/p99 latency of the continuous-batching
``QueryLoop`` at a fixed offered QPS, gated two ways — the
machine-normalized ratio ``p99 / (flush_deadline + direct_execute)`` must
stay under ``REPRO_SERVING_P99_MAX`` (default 3.0, the stored threshold),
and the warm steady state must have executed purely from caches
(``warm_cache_hits_only``: PlanRuntime moved only on ``*_hits`` counters,
zero new plan builds).

The fig_sharded module publishes the **sharded-backend record**
(``BENCH_sharded.json``): the N=1 overhead ratio of the ``sharded``
traversal backend vs ``xla_coo`` (gated by ``REPRO_SHARDED_OVERHEAD_MAX``
— partitioning must not regress the single-device path), the 1->N
scaling curve at whatever device counts are visible, and
``warm_zero_repacks`` (warm queries hit the per-(epoch, shard) pack and
trace caches exclusively).

The fig_ingest module publishes the **streaming-ingest record**
(``BENCH_ingest.json``): bulk-load edges/sec to the first correct query,
per-batch insert p50/p99 (the p99 is the compaction stall), and warm-query
latency during sustained writes — gated by ``REPRO_INGEST_QUERY_MAX``
(under-writes / quiescent warm-query ratio; delta inserts must leave the
packing caches warm) plus the ``warm_zero_repacks`` and
``first_query_correct`` hard gates.
"""
from __future__ import annotations

import json
import os
import re
import sys
import traceback

from .common import emit

PLAN_OVERHEAD_THRESHOLD = 1.3  # stored threshold: planned vs raw, S=32 lanes
PLAN_OVERHEAD_PATH = "BENCH_plan_overhead.json"

SERVING_THRESHOLD = 3.0  # stored threshold: p99 / (deadline + direct exec)
SERVING_PATH = "BENCH_serving.json"


def plan_overhead_record(rows, threshold: float, quick: bool) -> dict:
    """Planned-vs-native per-query ratios for fig8/fig9 points."""
    by_name = {name: us for name, us, _ in rows}
    ratios = {}
    for name, us in by_name.items():
        m = re.match(r"(fig[89])/planned_(\w+)/(.+)", name)
        if not m:
            continue
        fig, kind, point = m.groups()
        native = by_name.get(f"{fig}/native_{kind}/{point}")
        if native:
            ratios[f"{fig}/{point}"] = round(us / native, 4)
    return {
        "ratios": ratios,
        "max_ratio": round(max(ratios.values()), 4) if ratios else None,
        "threshold": threshold,
        "lanes": 32,
        "quick": quick,
    }


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    quick = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"
    from . import (
        fig8_reachability,
        fig9_selectivity,
        fig10_triangles,
        fig11_sssp,
        fig12_pathjoin,
        fig13_serving,
        fig_ingest,
        fig_sharded,
        table1_construction,
    )

    mods = [
        ("fig8", fig8_reachability),
        ("fig9", fig9_selectivity),
        ("fig10", fig10_triangles),
        ("fig11", fig11_sssp),
        ("fig12", fig12_pathjoin),
        ("fig13", fig13_serving),
        ("fig_sharded", fig_sharded),
        ("fig_ingest", fig_ingest),
        ("table1", table1_construction),
    ]
    print("name,us_per_call,derived")
    failures = 0
    all_rows = []
    for name, mod in mods:
        try:
            rows = mod.run(quick=quick)
            emit(rows)
            all_rows.extend(rows)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)

    threshold = float(
        os.environ.get("REPRO_PLAN_OVERHEAD_MAX", PLAN_OVERHEAD_THRESHOLD)
    )
    record = plan_overhead_record(all_rows, threshold, quick)
    out_path = os.environ.get("REPRO_BENCH_JSON", PLAN_OVERHEAD_PATH)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    if record["ratios"]:
        print(
            f"plan_overhead/max,0.0,ratio={record['max_ratio']:.2f}x "
            f"(threshold {threshold:.2f}x) -> {out_path}",
            flush=True,
        )
        if record["max_ratio"] > threshold:
            print(
                f"plan_overhead/REGRESSION,0.0,max ratio "
                f"{record['max_ratio']:.2f}x exceeds stored threshold "
                f"{threshold:.2f}x",
                flush=True,
            )
            failures += 1

    srv_threshold = float(
        os.environ.get("REPRO_SERVING_P99_MAX", SERVING_THRESHOLD)
    )
    srv = getattr(fig13_serving, "RECORD", None)
    if srv is not None:
        srv = dict(srv, threshold=srv_threshold)
        srv_path = os.environ.get("REPRO_BENCH_SERVING_JSON", SERVING_PATH)
        with open(srv_path, "w") as f:
            json.dump(srv, f, indent=2, sort_keys=True)
            f.write("\n")
        print(
            f"serving/p99,0.0,ratio={srv['ratio']:.2f}x "
            f"(threshold {srv_threshold:.2f}x) -> {srv_path}",
            flush=True,
        )
        if srv["ratio"] > srv_threshold:
            print(
                f"serving/REGRESSION,0.0,p99 ratio {srv['ratio']:.2f}x "
                f"exceeds stored threshold {srv_threshold:.2f}x",
                flush=True,
            )
            failures += 1
        if not srv["warm_cache_hits_only"]:
            print(
                "serving/REGRESSION,0.0,warm steady state re-planned or "
                "re-built instead of hitting caches",
                flush=True,
            )
            failures += 1
    if getattr(fig_sharded, "RECORD", None) is not None:
        failures = fig_sharded.publish(fig_sharded.RECORD, failures)
    if getattr(fig_ingest, "RECORD", None) is not None:
        failures = fig_ingest.publish(fig_ingest.RECORD, failures)

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
