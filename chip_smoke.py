"""Bring-up smoke run: the graph-query engine's served path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded backend over four chips

Deployment: the paper's Twitter-scale reachability cell as
``repro.configs.grfusion`` defines it (§7, Fig. 8): a power-law graph of
V = 2^22 vertices and E = 2^25 edges, 8-hop reachability. Vertex table
with its ``vattr`` column, edge table with its ``weight`` column, and room
in the edge table for the insert phase. The one cut is the lane count of
the multi-source sweep (the paper batches 2048 queries): ``LANES`` anchor
rows, which is what one 16 GB chip holds on the fused kernel.

Phases (one process, each timed with its build included):

1. load: generate, ``create_table``, ``create_graph_view``;
2. served single-source reachability and SSSP queries through
   ``GRFusion.serving_loop`` (``QueryLoop`` -> ``PreparedPlan`` ->
   ``TraversalEngine``), ``auto`` backend;
3. one multi-source reachability query anchored on ``LANES`` seed rows,
   of which a vertex predicate selects most, so ``auto`` takes the fused
   Pallas kernel;
4. writes while live: ``GRFusion.insert`` into the delta buffer, phases
   2-3 again, ``compact``, phases 2-3 once more;
5. every answer of phases 2-4 against the numpy ``reference`` backend on
   the same data (pinned per query, counted apart).

With ``--chips 4`` only the load, phases 2-3 and their reference check
run, and ``auto`` must resolve to ``sharded``.

The script fails (non-zero exit, no ``ok`` line) off the TPU, when the
kernel runs interpreted, on any traversal fault or failover, on any
degraded answer, on any mismatch with the reference, and when an
expected backend never ran. Its last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The phases are plain functions of their sizes so a CPU test can rehearse
them at a tiny size.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import grfusion as CELL  # noqa: E402
from repro.core.engine import GRFusion  # noqa: E402
from repro.core.query import P, Query, col, param  # noqa: E402
from repro.data.synthetic import graph_tables, random_graph  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

HOPS = CELL.SHAPES["queries_twitter"]["hops"]
LANES = 16  # multi-source sweep width (paper: 2048; cut to one chip)
N_REACH = 4  # single-source reachability queries per round
INSERT_BATCH = 4096  # edges inserted while live
PS = P("PS")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str):
    print(msg, flush=True)


# ---------------------------------------------------------------- queries
def reach_query(backend=None):
    q = (Query().from_paths("G", "PS")
         .where((PS.start.id == param("src")) & (PS.end.id == param("dst")))
         .hint_max_length(HOPS)
         .select(exists=col("PS.exists"), length=col("PS.length")))
    return q.traversal_backend(backend) if backend else q


def sssp_query(backend=None):
    q = (Query().from_paths("G", "PS")
         .where((PS.start.id == param("src")) & (PS.end.id == param("dst")))
         .hint_shortest_path("weight")
         .select(distance=col("PS.distance"), length=col("PS.length")))
    return q.traversal_backend(backend) if backend else q


def multi_query(backend=None):
    """Reachability from every seed row whose vertex attribute passes the
    predicate to one target: one [LANES, V] sweep."""
    q = (Query().from_table("Seeds", "W").from_paths("G", "PS")
         .where((col("W.vattr") <= param("a"))
                & (PS.start.id == col("W.vid"))
                & (PS.end.id == param("dst")))
         .hint_max_length(HOPS)
         .select(src=col("W.vid"), exists=col("PS.exists"),
                 length=col("PS.length")))
    return q.traversal_backend(backend) if backend else q


# ------------------------------------------------------------- deployment
@dataclasses.dataclass
class Deployment:
    eng: GRFusion
    n_vertices: int
    n_edges: int
    rng: np.random.Generator
    pairs: list  # (src, dst) vertex ids of the single-source queries
    seed_ids: np.ndarray  # vertex ids of the anchor rows
    seed_cut: int  # predicate bound on the seeds' vattr
    seed_selected: int  # anchor rows the predicate keeps
    multi_dst: int
    answers: int = 0  # answers checked against the reference
    reference_calls: int = 0
    served_s: float = 0.0  # wall time of served queries (engine path)
    reference_s: float = 0.0  # wall time of the pinned reference queries


def device_bytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def peak_bytes(devices=None):
    """``peak_bytes_in_use`` per device (None where not reported)."""
    out = []
    for d in devices or jax.devices()[:1]:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def phase_load(n_vertices: int, n_edges: int, *, seed: int = 0,
               insert_room: int = INSERT_BATCH, n_devices: int = 1) -> Deployment:
    g = random_graph(n_vertices, n_edges, kind="powerlaw", seed=seed)
    vd, ed = graph_tables(g)
    eng = GRFusion(bfs_max_hops=HOPS)
    eng.traversal.n_devices = n_devices
    eng.create_table("V", vd)
    eng.create_table("E", ed, capacity=n_edges + insert_room)
    # the delta buffer takes the whole insert batch below the
    # compaction threshold, so writes stay on the delta path
    delta = 256
    while delta * eng.compact_threshold <= insert_room:
        delta *= 2
    eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                          e_src="src", e_dst="dst", delta_capacity=delta)
    rng = np.random.default_rng(seed + 1)
    ids = rng.choice(n_vertices, 2 * N_REACH + LANES + 1, replace=False)
    pairs = [(int(ids[2 * i]), int(ids[2 * i + 1])) for i in range(N_REACH)]
    seed_ids = ids[2 * N_REACH: 2 * N_REACH + LANES].astype(np.int32)
    seed_vattr = vd["vattr"][seed_ids]
    eng.create_table("Seeds", {"vid": seed_ids, "vattr": seed_vattr})
    # a vertex predicate that keeps at least 3/4 of the anchor rows
    seed_cut = int(np.sort(seed_vattr)[(3 * LANES) // 4 - 1])
    return Deployment(
        eng=eng, n_vertices=n_vertices, n_edges=n_edges, rng=rng,
        pairs=pairs, seed_ids=seed_ids, seed_cut=seed_cut,
        seed_selected=int((seed_vattr <= seed_cut).sum()),
        multi_dst=int(ids[-1]),
    )


def _same(a, b) -> bool:
    if a.count != b.count or bool(a.overflow) != bool(b.overflow):
        return False
    if set(a.columns) != set(b.columns):
        return False
    return all(
        np.array_equal(np.asarray(a.columns[k]), np.asarray(b.columns[k]))
        for k in a.columns
    )


def serve_and_check(dep: Deployment, requests) -> list:
    """Submit ``(query_fn, params)`` requests through the serving loop,
    then the same queries pinned to ``reference``; every served answer
    must be undegraded and equal to the reference one."""
    loop = dep.eng.serving_loop()
    t0 = time.perf_counter()
    served = [loop.submit(fn(), **params) for fn, params in requests]
    loop.drain()
    t1 = time.perf_counter()
    pinned = [loop.submit(fn("reference"), **params) for fn, params in requests]
    loop.drain()
    dep.served_s += t1 - t0
    dep.reference_s += time.perf_counter() - t1
    for (fn, params), t, r in zip(requests, served, pinned):
        for tk in (t, r):
            check(tk.status == "done",
                  f"{fn.__name__}{params}: ticket {tk.status}\n"
                  + "".join(traceback.format_exception(tk.error)))
            check(tk.result.degraded_backend is None,
                  f"{fn.__name__}{params}: degraded to "
                  f"{tk.result.degraded_backend}")
        check(_same(t.result, r.result),
              f"{fn.__name__}{params}: served answer differs from reference:"
              f" {t.result.columns} vs {r.result.columns}")
        dep.answers += 1
        dep.reference_calls += 1
    return [t.result for t in served]


def phase_single(dep: Deployment) -> list:
    reqs = [(reach_query, {"src": s, "dst": d}) for s, d in dep.pairs]
    s, d = dep.pairs[0]
    reqs.append((sssp_query, {"src": s, "dst": d}))
    return serve_and_check(dep, reqs)


def phase_multi(dep: Deployment):
    (res,) = serve_and_check(
        dep, [(multi_query, {"a": dep.seed_cut, "dst": dep.multi_dst})]
    )
    return res


def predicted_kernel_bytes(dep: Deployment, lanes: int = LANES) -> dict:
    """Device bytes the fused-kernel sweep needs at this size, from the
    packed stream's actual shape (built here, as the first kernel query
    would)."""
    te = dep.eng.traversal
    ps, _, _ = te.get_pack(dep.eng.views["G"].view, "G")
    T, J, BE = ps.shape
    slots = T * J * BE
    vp = T * te.block_rows
    return {
        "T": T, "J": J, "BE": BE, "lanes": lanes,
        "msgs": lanes * slots * 2,  # bf16 [S, T*J*BE]
        "pack": 3 * slots * 4 + 2 * slots * 4,  # packs + flat src/ldst
        "state": 6 * lanes * vp * 4,  # frontier/visited/dist, in + out
    }


def phase_writes(dep: Deployment, batch: int = INSERT_BATCH):
    """Insert ``batch`` edges while live. The first closes query pair 0
    in one hop and the second does the same for one seed lane, so the
    re-run rounds must read the writes back."""
    eng, rng, V = dep.eng, dep.rng, dep.n_vertices
    src = rng.integers(0, V, batch).astype(np.int32)
    dst = rng.integers(0, V, batch).astype(np.int32)
    src[0], dst[0] = dep.pairs[0]
    src[1], dst[1] = int(dep.seed_ids[0]), dep.multi_dst
    before = dict(eng.events)
    eng.insert("E", {
        "eid": np.arange(dep.n_edges, dep.n_edges + batch, dtype=np.int32),
        "src": src, "dst": dst,
        "weight": rng.uniform(0.1, 10.0, batch).astype(np.float32),
        "sel": rng.integers(0, 100, batch).astype(np.int32),
        "label": rng.integers(0, 3, batch).astype(np.int32),
    })
    grew = {k: v - before.get(k, 0) for k, v in eng.events.items()
            if v != before.get(k, 0)}
    check(grew.get("delta_inserts") == 1
          and not any("compaction" in k for k in grew),
          f"insert did not stay on the delta path: {grew}")


def check_engine(dep: Deployment, *, expect_backends):
    """Bring-up gates: no fault, no failover, and every expected backend
    actually ran."""
    te = dep.eng.traversal
    ev = dep.eng.events
    if ev["traversal_faults"] or ev["traversal_failovers"]:
        cause = "".join(traceback.format_exception(te.last_error)) \
            if te.last_error is not None else "unknown"
        raise SmokeFailure(
            f"traversal_faults={ev['traversal_faults']} "
            f"traversal_failovers={ev['traversal_failovers']}; last "
            f"error:\n{cause}"
        )
    stats = te.stats
    for b in expect_backends:
        check(stats[f"backend_{b}"] > 0, f"backend {b} never ran")


def run_phase(name: str, fn, *args, **kwargs):
    """Run one phase; log its wall time and, for query phases, the split
    between served queries and their reference twins."""
    dep = args[0] if args and isinstance(args[0], Deployment) else None
    before = (dep.served_s, dep.reference_s) if dep else None
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    line = f"phase {name}: {time.perf_counter() - t0:.3f} s"
    if dep and (dep.served_s, dep.reference_s) != before:
        line += (f" (served {dep.served_s - before[0]:.3f} s, reference "
                 f"{dep.reference_s - before[1]:.3f} s)")
    log(line)
    return out


def describe_load(dep: Deployment, devices):
    eng = dep.eng
    vb = eng.views["G"]
    tbytes = sum(device_bytes(t) for t in eng.tables.values())
    log(f"load: V={dep.n_vertices} E={dep.n_edges} "
        f"avg_fan_out={float(vb.view.avg_fan_out):.3f} "
        f"table_bytes={tbytes} view_bytes={device_bytes(vb.view)} "
        f"peak_bytes_in_use={peak_bytes(devices)}")


def run_one_chip(dep: Deployment, devices, *, batch: int = INSERT_BATCH):
    run_phase("single", phase_single, dep)
    pred = run_phase("pack", predicted_kernel_bytes, dep)
    log(f"kernel sweep: lanes={pred['lanes']} T={pred['T']} J={pred['J']} "
        f"BE={pred['BE']} predicted_bytes msgs={pred['msgs']} "
        f"pack={pred['pack']} state={pred['state']} "
        f"total={pred['msgs'] + pred['pack'] + pred['state']}")
    res = run_phase("multi", phase_multi, dep)
    log(f"multi: {len(dep.seed_ids)} anchor rows, {dep.seed_selected} with "
        f"vattr <= {dep.seed_cut}, {res.count} reached within {HOPS} hops")
    check(any("traversal backend: pallas_frontier" in e for e in res.explain),
          "multi-source query did not resolve to pallas_frontier")
    log(f"peak_bytes_in_use={peak_bytes(devices)}")
    run_phase("writes", phase_writes, dep, batch)
    single = run_phase("single+delta", phase_single, dep)
    check(int(single[0].columns["length"][0]) == 1,
          "inserted edge not visible to the served query")
    run_phase("multi+delta", phase_multi, dep)
    run_phase("compact", dep.eng.compact, "G")
    run_phase("single+compacted", phase_single, dep)
    run_phase("multi+compacted", phase_multi, dep)
    log(f"peak_bytes_in_use={peak_bytes(devices)}")
    check_engine(dep, expect_backends=("xla_coo", "pallas_frontier"))


def run_four_chips(dep: Deployment, devices):
    single = run_phase("single", phase_single, dep)
    res = run_phase("multi", phase_multi, dep)
    for r in single + [res]:
        check(any("traversal backend: sharded" in e for e in r.explain),
              "auto did not resolve to sharded")
    log(f"peak_bytes_in_use per device={peak_bytes(devices)}")
    check_engine(dep, expect_backends=("sharded",))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) visible", file=sys.stderr)
        return 2
    # before the first compile; the checks above compile nothing
    log(f"compile cache: {enable_compile_cache()}")
    devices = devices[: args.chips]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    try:
        dep = run_phase("load", phase_load, CELL.V, CELL.E, seed=args.seed,
                        n_devices=args.chips)
        check(not dep.eng.traversal.interpret,
              "Pallas kernels would run interpreted")
        describe_load(dep, devices)
        if args.chips == 1:
            run_one_chip(dep, devices)
        else:
            run_four_chips(dep, devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"reference: {dep.answers} answers equal, "
        f"{dep.reference_calls} reference queries")
    log(f"events: {dict(dep.eng.events)}")
    log(f"traversal stats: {dict(dep.eng.traversal.stats)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
