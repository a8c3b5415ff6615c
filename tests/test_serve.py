"""Serving-path tests: LM slot server vs direct decode; batched query server."""
import jax
import numpy as np

from repro.core.engine import GRFusion
from repro.core.query import Query, P, col
from repro.data.synthetic import graph_tables, random_graph
from repro.models.transformer import LMConfig, decode_step, init_cache, init_params
from repro.serve.engine import LMServer, QueryServer, Request

import jax.numpy as jnp


def _lm_server_vs_direct_decode():
    cfg = LMConfig(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                   d_head=8, d_ff=64, vocab=31)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.array([1, 2, 3], np.int32)

    # direct greedy decode
    cache = init_cache(cfg, 1, 32)
    toks = list(prompt)
    for t in range(len(prompt)):
        lg, cache = decode_step(params, cache, jnp.asarray([[toks[t]]]),
                                jnp.asarray([t]), cfg)
    out_direct = []
    cur = int(jnp.argmax(lg[0, 0]))
    out_direct.append(cur)
    for t in range(len(prompt), len(prompt) + 3):
        lg, cache = decode_step(params, cache, jnp.asarray([[cur]]),
                                jnp.asarray([t]), cfg)
        cur = int(jnp.argmax(lg[0, 0]))
        out_direct.append(cur)

    srv = LMServer(params, cfg, n_slots=2, max_len=32)
    req = Request(0, prompt, max_new=4)
    assert srv.submit(req)
    done = []
    while not done:
        done = srv.step()
    assert req.out == out_direct



def test_lm_server_matches_direct_decode():
    _lm_server_vs_direct_decode()


def test_lm_server_matches_direct_decode_with_compile_cache(tmp_path):
    """The persistent cache changes dispatch timing; the server's host
    buffers must not be read after it mutates them."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        _lm_server_vs_direct_decode()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()


def test_query_server_batched_reachability():
    g = random_graph(300, 1200, seed=2)
    vd, ed = graph_tables(g)
    eng = GRFusion()
    eng.create_table("V", vd)
    eng.create_table("E", ed)
    eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                          e_src="src", e_dst="dst")
    srv = QueryServer(eng, "G", lane_width=16, max_hops=8)
    rng = np.random.default_rng(0)
    qs = [(int(rng.integers(0, 300)), int(rng.integers(0, 300))) for _ in range(20)]
    for s, d in qs:
        srv.submit(s, d)
    res = srv.flush()
    assert len(res) == 20
    # cross-check a few against the declarative engine path
    PS = P("PS")
    for r in res[:5]:
        q = (Query().from_table("V", "A").from_table("V", "B")
             .from_paths("G", "PS")
             .where((col("A.vid") == r["src"]) & (col("B.vid") == r["dst"])
                    & (PS.start.id == col("A.vid")) & (PS.end.id == col("B.vid")))
             .hint_max_length(8)
             .select(exists=col("PS.exists")).limit(1))
        out = eng.run(q)
        engine_reach = out.count > 0 and bool(out.columns["exists"][0])
        if r["src"] == r["dst"]:
            continue  # trivial self-reachability differs by convention
        assert engine_reach == r["reachable"], r
