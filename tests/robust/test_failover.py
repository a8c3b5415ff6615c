"""Backend failover: every injected backend fault degrades, bit-identically.

The four traversal backends are bit-identical by construction (the
differential suite proves it), which is exactly what makes failover
*result-preserving*: a query that falls from ``sharded`` to ``xla_coo``
to ``reference`` returns the same bytes it would have on the happy path.
This file injects dispatch faults at every backend and pins that
contract, plus the observability around it (``events`` counters,
``consume_degraded``, ``QueryResult.degraded_backend``).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import GRFusion
from repro.core.query import P, Query, col
from repro.core.traversal_engine import BACKENDS, FAILOVER_CHAIN, SITE_DISPATCH
from repro.robust import faults
from repro.robust.faults import FaultPlan, InjectedFault

pytestmark = pytest.mark.chaos

_MAX_HOPS = 24


@pytest.fixture
def eng():
    rng = np.random.default_rng(42)
    n, e = 16, 40
    eng = GRFusion()
    eng.create_table("V", {"vid": np.arange(n, dtype=np.int32)})
    eng.create_table(
        "E",
        {"src": rng.integers(0, n, e).astype(np.int32),
         "dst": rng.integers(0, n, e).astype(np.int32),
         "w": rng.uniform(0.1, 4.0, e).astype(np.float32)},
        capacity=128,
    )
    eng.create_graph_view(
        "G", vertexes="V", edges="E", v_id="vid", e_src="src", e_dst="dst",
        directed=True, delta_capacity=16,
    )
    return eng


def _bfs(eng, backend=None):
    view = eng.views["G"].view
    srcs = jnp.asarray(np.array([0, 3, 7, 11], np.int32))
    return np.asarray(eng.traversal.bfs(
        view, srcs, edge_mask_by_row=eng.tables["E"].valid,
        max_hops=_MAX_HOPS, backend=backend, graph="G",
    ))


def test_failover_chain_always_ends_at_reference():
    for b in BACKENDS:
        chain = FAILOVER_CHAIN[b]
        if b == "reference":
            assert chain == ()
        else:
            assert chain[-1] == "reference"
            assert b not in chain  # never falls over to itself


@pytest.mark.parametrize("backend", [b for b in BACKENDS if b != "reference"])
def test_dead_backend_degrades_bit_identically(eng, backend):
    expect = _bfs(eng, backend="reference")
    te = eng.traversal
    plan = FaultPlan({SITE_DISPATCH[backend]: "*"})
    with faults.fault_scope(plan):
        got = _bfs(eng, backend=backend)
    assert plan.fired[SITE_DISPATCH[backend]] >= 1  # the fault landed
    assert (got == expect).all()
    assert te.stats["backend_failovers"] >= 1
    assert te.stats[f"failover_{backend}_to_{FAILOVER_CHAIN[backend][0]}"] >= 1
    assert eng.events["traversal_failovers"] >= 1
    assert eng.events["traversal_faults"] >= 1


def test_consume_degraded_reports_then_clears(eng):
    te = eng.traversal
    with faults.fault_scope(FaultPlan({SITE_DISPATCH["sharded"]: "*"})):
        _bfs(eng, backend="sharded")
    assert te.consume_degraded() == FAILOVER_CHAIN["sharded"][0]
    assert te.consume_degraded() is None  # one-shot, per query
    _bfs(eng, backend="xla_coo")  # healthy query: nothing degraded
    assert te.consume_degraded() is None


def test_single_fault_absorbed_by_retry_not_failover(eng):
    te = eng.traversal
    expect = _bfs(eng, backend="xla_coo")
    plan = FaultPlan.at(SITE_DISPATCH["xla_coo"])  # first attempt only
    with faults.fault_scope(plan):
        got = _bfs(eng, backend="xla_coo")
    assert (got == expect).all()
    assert te.consume_degraded() is None  # same backend, second attempt
    assert te.stats["backend_retries"] >= 1
    assert eng.events["traversal_retries"] >= 1


def test_reference_fault_exhausts_the_chain(eng):
    with faults.fault_scope(FaultPlan({SITE_DISPATCH["reference"]: "*"})):
        with pytest.raises(InjectedFault):
            _bfs(eng, backend="reference")
    assert eng.events["traversal_backend_exhausted"] >= 1
    # the engine is not wedged: the next query (no faults) succeeds
    assert _bfs(eng, backend="reference").shape == (4, 16)


def test_every_backend_dead_raises_cleanly(eng):
    plan = FaultPlan({s: "*" for s in SITE_DISPATCH.values()})
    with faults.fault_scope(plan):
        with pytest.raises(InjectedFault):
            _bfs(eng, backend="sharded")
    # whole chain was attempted before giving up
    for b in ("sharded",) + FAILOVER_CHAIN["sharded"]:
        assert plan.hits[SITE_DISPATCH[b]] >= 1, b


@pytest.mark.parametrize("backend", [b for b in BACKENDS if b != "reference"])
def test_sssp_failover_bit_identical(eng, backend):
    te = eng.traversal
    view = eng.views["G"].view
    srcs = jnp.asarray(np.array([0, 5], np.int32))
    w = eng.tables["E"].col("w")
    valid = eng.tables["E"].valid

    def run(b):
        d, p = te.sssp(view, srcs, w, edge_mask_by_row=valid,
                       backend=b, graph="G")
        return np.asarray(d), np.asarray(p)

    dref, pref = run("reference")
    with faults.fault_scope(FaultPlan({SITE_DISPATCH[backend]: "*"})):
        d, p = run(backend)
    assert d.tobytes() == dref.tobytes()
    assert (p == pref).all()
    assert te.consume_degraded() == FAILOVER_CHAIN[backend][0]


def test_pack_build_fault_fails_over_instead_of_wedging(eng):
    """A fault in the frontier-pack builder (cache miss path) kills the
    pallas backend's attempt; the query degrades and still answers."""
    expect = _bfs(eng, backend="reference")
    with faults.fault_scope(FaultPlan({"traversal.pack_build": "*"})):
        got = _bfs(eng, backend="pallas_frontier")
    assert (got == expect).all()
    assert eng.traversal.consume_degraded() in FAILOVER_CHAIN["pallas_frontier"]
    # once the fault clears, the pack builds fine and the backend recovers
    assert (_bfs(eng, backend="pallas_frontier") == expect).all()
    assert eng.traversal.consume_degraded() is None


def test_query_result_carries_degraded_backend(eng):
    # a both-ends-anchored reachability gets the bfs physical — the one
    # that dispatches through the failover chain
    PS = P("PS")
    q = (Query().from_paths("G", "PS")
         .where((PS.start.id == 0) & (PS.end.id == 7))
         .select(exists=col("PS.exists"), length=col("PS.length"))
         .limit(1))
    clean = eng.run(q)
    assert any("traversal backend: xla_coo" in e for e in clean.explain)
    assert clean.degraded_backend is None
    # the engine's auto backend resolves to xla_coo on host: kill it
    with faults.fault_scope(FaultPlan({SITE_DISPATCH["xla_coo"]: "*"})):
        degraded = eng.run(q)
    assert degraded.degraded_backend == "reference"
    assert degraded.count == clean.count
    for c in ("exists", "length"):
        np.testing.assert_array_equal(
            np.asarray(degraded.columns[c])[: clean.count],
            np.asarray(clean.columns[c])[: clean.count],
        )
