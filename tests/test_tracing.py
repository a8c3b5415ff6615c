"""Spans, host-sync counts and the hop counter of the served path
(``repro.tracing``), read back from a profiler trace recorded on the CPU."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import traversal as T
from repro.core import traversal_engine as TE
from repro.core.engine import GRFusion
from repro.core.graphview import build_graph_view
from repro.core.query import P, Query, col, param
from repro.core.table import Table
from repro.tracing import to_host

N = 8  # chain 0 -> 1 -> ... -> 7
EXECUTE_CHILDREN = {"grf.path.prepare", "grf.traverse", "grf.path.to_batch",
                    "grf.finalize"}


def _engine():
    eng = GRFusion()
    eng.create_table("V", {"vid": np.arange(N, dtype=np.int32)}, capacity=16)
    eng.create_table("E", {"src": np.arange(N - 1, dtype=np.int32),
                           "dst": np.arange(1, N, dtype=np.int32)}, capacity=32)
    eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                          e_src="src", e_dst="dst")
    return eng


def _nbr2():
    PS = P("PS")
    return (Query().from_paths("G", "PS").where(PS.start.id == param("src"))
            .hint_max_length(2).select(end=PS.end.id, length=PS.length))


def _reach():
    PS = P("PS")
    return (Query().from_paths("G", "PS")
            .where((PS.start.id == param("src")) & (PS.end.id == param("dst")))
            .hint_max_length(N).select(exists=col("PS.exists"),
                                       length=col("PS.length")))


def _syncs(eng):
    return collections.Counter(
        {k: v for k, v in eng.events.items() if k.startswith("host_sync.")})


def _grf_spans(trace_dir):
    """(name, start_ns, end_ns) of every ``grf.`` host event, by start."""
    (path,) = sorted(trace_dir.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("grf.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(spans, outer):
    _, a, b = outer
    return [s for s in spans if s is not outer and a <= s[1] and s[2] <= b]


def _direct(spans, outer):
    """Spans inside ``outer`` that no other span inside it contains."""
    inner = _inside(spans, outer)
    return [s for s in inner if not any(o is not s and o[1] <= s[1] and s[2] <= o[2]
                                        for o in inner)]


def test_spans_nest_as_served(tmp_path):
    eng = _engine()
    loop = eng.serving_loop()
    warm = [loop.submit(_nbr2(), src=1), loop.submit(_reach(), src=0, dst=3)]
    loop.drain()
    assert all(t.status == "done" for t in warm)
    eng.insert("E", {"src": np.array([7], np.int32), "dst": np.array([0], np.int32)})
    jax.profiler.start_trace(str(tmp_path))
    try:
        tickets = [loop.submit(_nbr2(), src=6), loop.submit(_reach(), src=0, dst=3)]
        loop.drain()
    finally:
        jax.profiler.stop_trace()
    assert [t.status for t in tickets] == ["done", "done"]
    # the delta edge 7 -> 0 was compacted in and is part of the answer
    assert sorted(zip(tickets[0].result.columns["end"].tolist(),
                      tickets[0].result.columns["length"].tolist())) == [(0, 2), (7, 1)]
    assert tickets[1].result.columns["length"].tolist() == [3]

    spans = _grf_spans(tmp_path)
    names = collections.Counter(n for n, _, _ in spans)
    assert names["grf.submit"] == 2 and names["grf.ticket"] == 2
    assert names["grf.compact"] == 1
    ticket_spans = [s for s in spans if s[0] == "grf.ticket"]
    for submit in (s for s in spans if s[0] == "grf.submit"):
        assert not _inside(spans, submit)
        assert not any(t[1] <= submit[1] < t[2] for t in ticket_spans)
    for ticket in ticket_spans:
        assert [n for n, _, _ in _direct(spans, ticket)] == ["grf.bind", "grf.execute"]
        (execute,) = [s for s in _direct(spans, ticket) if s[0] == "grf.execute"]
        assert {n for n, _, _ in _direct(spans, execute)} == EXECUTE_CHILDREN
    compact = next(s for s in spans if s[0] == "grf.compact")
    traverse = next(s for s in spans if s[0] == "grf.traverse"
                    and s[1] <= compact[1] and compact[2] <= s[2])
    assert any(t[1] < traverse[1] and traverse[2] < t[2] for t in ticket_spans)


def test_host_syncs_of_one_ticket_are_its_to_host_sites():
    eng = _engine()
    loop = eng.serving_loop()
    for q, p in ((_nbr2(), {"src": 1}), (_reach(), {"src": 0, "dst": 3})):
        loop.submit(q, **p)
        loop.drain()  # warm
        before = _syncs(eng)
        t = loop.submit(q, **p)
        loop.drain()
        assert t.status == "done"
        after = _syncs(eng)
        after.subtract(before)
        if "dst" in p:  # bfs: the result columns only (no policy read on CPU)
            assert +after == {"host_sync.finalize": 1}
        else:  # enumeration: delta check, overflow, result columns
            assert +after == {"host_sync.delta_check": 1, "host_sync.enum_overflow": 1,
                              "host_sync.finalize": 1}


def test_fan_out_is_read_once_per_topology_epoch():
    eng = _engine()
    loop = eng.serving_loop()
    reads = lambda: eng.events["host_sync.fan_out"]
    for src in (1, 2, 3):
        loop.submit(_nbr2(), src=src)
        loop.drain()
    assert reads() == 1
    eng.insert("E", {"src": np.array([7], np.int32), "dst": np.array([0], np.int32)})
    t = loop.submit(_nbr2(), src=6)  # compacts the delta edge in first
    loop.drain()
    assert t.status == "done" and reads() == 2
    loop.submit(_nbr2(), src=5)
    loop.drain()
    assert reads() == 3  # the compaction was a new epoch
    assert eng.traversal.fan_out(eng.views["G"].view, "G") == pytest.approx(1.0)
    assert reads() == 3


@pytest.mark.parametrize("agg", [False, True])
def test_finalize_keeps_the_select_order(agg):
    eng = _engine()
    q = Query().from_table("E", "R").where(col("R.src") >= 2)
    if agg:
        q = q.select_agg("zmax", "max", col("R.dst")).select_count("a_n")
        want = {"zmax": 7, "a_n": 5}
    else:
        q = q.select(zdst=col("R.dst"), asrc=col("R.src"))
        want = {"zdst": 3, "asrc": 2}
    before = eng.events["host_sync.finalize"]
    r = eng.run(q)
    assert list(r.columns) == list(want)
    assert int(r.scalar()) == want["zdst" if not agg else "zmax"]
    assert {k: int(r.scalar(k)) for k in want} == want
    assert eng.events["host_sync.finalize"] - before == 1


def test_to_host_counts_and_returns_numpy():
    events = collections.Counter()
    valid, col_a = to_host([jnp.ones(3, bool), jnp.arange(3)], "finalize", events)
    assert isinstance(valid, np.ndarray) and col_a.tolist() == [0, 1, 2]
    assert events == {"host_sync.finalize": 1}


def _chain_view():
    vt = Table.create("V", {"vid": np.arange(N, dtype=np.int32)})
    et = Table.create("E", {"src": np.arange(N - 1, dtype=np.int32),
                            "dst": np.arange(1, N, dtype=np.int32)})
    return build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst")


@pytest.mark.parametrize("target, max_hops, hops", [
    (None, 32, N),  # N - 1 hops that reach a vertex, one that finds none
    (None, 4, 4),  # the hop limit
    (3, 32, 3),  # stops once the target is reached
    (-1, 32, 0),  # an unresolvable target is done before the first hop
])
def test_hop_counter_counts_the_sweep(target, max_hops, hops):
    view = _chain_view()
    te = TE.TraversalEngine(default_backend="xla_coo")
    src = jnp.asarray([0], jnp.int32)
    tgt = None if target is None else jnp.asarray([target], jnp.int32)
    before = te.stats["hops_xla_coo"]
    dist = te.bfs(view, src, target_pos=tgt, max_hops=max_hops)
    assert te.stats["hops_xla_coo"] - before == hops
    assert np.array_equal(np.asarray(dist),
                          np.asarray(T.bfs(view, src, target_pos=tgt, max_hops=max_hops)))


def test_hop_counter_on_the_served_path():
    eng = _engine()
    loop = eng.serving_loop()
    for dst, hops in ((3, 3), (6, 6), (3, 3)):
        before = eng.traversal.stats["hops_xla_coo"]
        t = loop.submit(_reach(), src=0, dst=dst)
        loop.drain()
        assert t.result.columns["length"].tolist() == [hops]
        assert eng.traversal.stats["hops_xla_coo"] - before == hops


def test_hop_counter_sums_sweeps_without_a_host_read():
    view = _chain_view()
    te = TE.TraversalEngine(default_backend="xla_coo")
    for _ in range(3):
        te.bfs(view, jnp.asarray([0], jnp.int32), max_hops=2)
    assert not te.events  # no host sync while sweeping
    assert te.stats["hops_xla_coo"] == 6


def test_engine_sweep_keeps_the_module_name_and_distances():
    view = _chain_view()
    src = jnp.asarray([0, 2], jnp.int32)
    text = TE._bfs_xla.lower(view, src, max_hops=32, block_size=1024).as_text()
    assert "jit_bfs" in text.splitlines()[0]  # the benchmark reads jit_bfs
    dist, hops, _ = TE._bfs_xla(view, src, max_hops=32, block_size=1024)
    assert np.array_equal(np.asarray(dist), np.asarray(T.bfs(view, src, max_hops=32)))
    assert int(hops) == N  # lane 0 reaches vertex 7 on hop 7; hop 8 finds none
