"""QueryEngine serving semantics: fixed shapes, caching, backends."""
import numpy as np
import pytest

from repro.core.single_source import single_source_device
from repro.serve import EngineConfig, QueryEngine


@pytest.fixture()
def engine(small_graph, sling_index):
    return QueryEngine(sling_index, small_graph,
                       EngineConfig(pair_batch=16, source_batch=4,
                                    cache_size=32))


def test_compile_once_across_request_sizes(engine):
    """Arbitrary request sizes never introduce new dispatch shapes."""
    engine.warmup()
    before = set(engine.stats()["unique_shapes"])
    rng = np.random.default_rng(0)
    for q in (1, 3, 4, 5, 11):
        us = rng.integers(0, engine.index.n, q).astype(np.int32)
        vs = rng.integers(0, engine.index.n, q).astype(np.int32)
        engine.pairs(us, vs)
        engine.single_source(us)
        engine.topk(us, 7)
    after = set(engine.stats()["unique_shapes"])
    assert after == before, after - before


def test_warmup_does_not_pollute_traffic_stats(engine):
    """Bugfix: warmup() used to call the dispatchers directly, so a
    warmed engine started life with phantom batches/pad_slots (one
    full topk sweep per bucket). Warmup accounting is separate."""
    engine.warmup()
    st = engine.stats()
    assert st["batches"] == 0 and st["pad_slots"] == 0
    assert st["pair"] == 0 and st["source"] == 0 and st["topk"] == 0
    assert st["warmup_batches"] > 0 and st["warmup_pad_slots"] == 0
    warm = st["warmup_batches"]
    # real traffic counts normally and never retro-inflates warmup
    engine.single_source([1])
    engine.pairs([2, 3], [4, 5])        # 2 pairs pad to pair_batch=16
    st2 = engine.stats()
    assert st2["batches"] == 2
    assert st2["pad_slots"] == (engine.cfg.source_batch - 1) + 14
    assert st2["warmup_batches"] == warm
    assert st2["warmup_pad_slots"] == 0


def test_padded_requests_match_unpadded(engine, small_graph, sling_index):
    """Odd-size (padded) requests return the same scores as the raw
    device path on the exact batch."""
    us = np.array([3, 1, 4, 1, 5, 9, 2], np.int32)   # 7 % 4 != 0
    got = engine.single_source(us)
    ref = single_source_device(sling_index, small_graph, us)
    np.testing.assert_allclose(got, ref, atol=1e-6)


def test_pair_parity_with_host(engine, sling_index):
    rng = np.random.default_rng(1)
    us = rng.integers(0, engine.index.n, 10)
    vs = rng.integers(0, engine.index.n, 10)
    ref = [sling_index.query_pair_host(int(u), int(v))
           for u, v in zip(us, vs)]
    np.testing.assert_allclose(engine.pairs(us, vs), ref, atol=1e-4)


def test_pallas_pair_backend_parity(small_graph, sling_index):
    """Interpret-mode Pallas join == searchsorted join."""
    cfg_join = EngineConfig(pair_batch=16, pair_backend="join")
    cfg_pal = EngineConfig(pair_batch=16, pair_backend="pallas")
    e_join = QueryEngine(sling_index, small_graph, cfg_join)
    e_pal = QueryEngine(sling_index, small_graph, cfg_pal)
    rng = np.random.default_rng(2)
    us = rng.integers(0, sling_index.n, 16).astype(np.int32)
    vs = rng.integers(0, sling_index.n, 16).astype(np.int32)
    np.testing.assert_allclose(e_pal.pairs(us, vs), e_join.pairs(us, vs),
                               atol=1e-5)
    assert e_pal.stats()["pair_backend"] == "pallas"


def test_lru_cache_hits_and_consistency(engine):
    us = np.array([8, 8, 8], np.int32)
    first = engine.single_source(us[:1])
    h0 = engine.stats()["cache_hits"]
    again = engine.single_source(us)
    assert engine.stats()["cache_hits"] >= h0 + 3
    np.testing.assert_array_equal(np.repeat(first, 3, axis=0), again)
    b0 = engine.stats()["batches"]
    engine.single_source(us[:1])          # pure cache hit: no dispatch
    assert engine.stats()["batches"] == b0


def test_cache_counters_split_by_query_kind(engine):
    """stats() reports hit/miss per query kind: the aggregate LRU
    numbers could not distinguish a pair-path cache problem from a
    top-k one (the LRU was observable only by total size)."""
    engine.pairs([1], [2])                 # miss
    engine.pairs([2], [1])                 # hit (canonicalized pair)
    engine.single_source([3])              # miss
    engine.single_source([3])              # hit
    engine.topk([4], 5)                    # miss
    engine.topk([4], 5)                    # hit
    engine.topk([4], 7)                    # same bucket: hit
    st = engine.stats()
    assert st["cache_hits_by_kind"] == {"pair": 1, "src": 1, "topk": 2}
    assert st["cache_misses_by_kind"] == {"pair": 1, "src": 1,
                                          "topk": 1}
    assert st["cache_hits"] == 4 and st["cache_misses"] == 3


def test_cache_eviction_bounded(small_graph, sling_index):
    eng = QueryEngine(sling_index, small_graph,
                      EngineConfig(source_batch=4, cache_size=8))
    for u in range(20):
        eng.topk([u], 5)
    assert eng.stats()["cache_entries"] <= 8


def _churn(g, idx, seed=0, n_mut=8):
    """Apply a small random churn batch to a *copy-built* index."""
    from repro.core import build, update
    delta = update.random_delta(g, n_add=n_mut, n_del=n_mut, seed=seed)
    rep = build.update_index(idx, g, delta, exact_d=True)
    return rep


def _fresh_index(g):
    from repro.core import build
    return build.build_index(g, eps=0.1, exact_d=True, seed=0)


def test_swap_cannot_serve_stale_scores(small_graph):
    """Issue fix: the LRU must not serve pre-swap scores for nodes the
    update affected -- the explicit invalidation inside swap_index()."""
    from repro.core import build
    g = small_graph
    idx = _fresh_index(g)
    eng = QueryEngine(idx, g, EngineConfig(pair_batch=16, source_batch=4,
                                           cache_size=64))
    rep = _churn(g, idx, seed=11)
    hot = [int(x) for x in rep.affected[:4]]
    # populate the cache *before* the swap for affected nodes
    pre_pair = eng.pair(hot[0], hot[1])
    eng.single_source([hot[2]])
    eng.topk([hot[3]], 5)
    eng.swap_index(idx, rep.graph, affected=rep.affected)
    # the engine must serve the repaired index (idx, mutated in place),
    # not the pre-swap cache: tight comparison against direct dispatch
    # on idx; repair-vs-fresh accuracy is a plan-eps property
    # (tests/test_update.py), checked loosely below
    post = eng.pair(hot[0], hot[1])
    assert post == pytest.approx(
        idx.query_pair_host(hot[0], hot[1]), abs=1e-4)
    from repro.core.single_source import single_source_device
    got_src = eng.single_source([hot[2]])
    np.testing.assert_allclose(
        got_src, single_source_device(idx, rep.graph, np.array([hot[2]])),
        atol=1e-5)
    fresh = build.build_index(rep.graph, eps=0.1, exact_d=True, seed=0)
    assert abs(post - fresh.query_pair_host(hot[0], hot[1])) <= idx.plan.eps
    assert np.abs(got_src - single_source_device(
        fresh, rep.graph, np.array([hot[2]]))).max() <= idx.plan.eps
    del pre_pair  # the pre-swap value itself is irrelevant; serving it
    #               post-swap is what the assertions above rule out


def test_unaffected_source_cache_cannot_hide_affected_targets(small_graph):
    """A cached single-source/top-k vector for an UNAFFECTED source u
    still holds scores *at* affected targets, so targeted invalidation
    keyed on the query node alone would leak pre-swap scores through
    it. After swap_index(affected=...), those vectors must be served
    fresh from the repaired index."""
    from repro.core.single_source import single_source_device
    g = small_graph
    idx = _fresh_index(g)
    eng = QueryEngine(idx, g, EngineConfig(pair_batch=16, source_batch=4,
                                           cache_size=64))
    rep = _churn(g, idx, seed=7)
    cold = np.setdiff1d(np.arange(idx.n), rep.affected)[:8]
    assert len(cold), "churn affected every node; pick another seed"
    pre = eng.single_source(cold).copy()   # populate the cache pre-swap
    eng.topk(cold, 5)
    eng.swap_index(idx, rep.graph, affected=rep.affected)
    # idx was repaired in place: direct dispatch on it is the truth the
    # engine must now serve (a stale cache hit would return `pre`)
    ref = np.asarray(single_source_device(idx, rep.graph, cold))
    got = eng.single_source(cold)
    np.testing.assert_allclose(got, ref, atol=1e-6)
    # the guard has teeth only if some cold-source score really moved
    # at an affected target
    aff = np.asarray(rep.affected, np.int64)
    assert np.abs(pre[:, aff] - ref[:, aff]).max() > 1e-5
    # top-k for a cold source must likewise reflect the repaired index
    sv, _ = eng.topk(cold, 5)
    ref_sv = np.sort(ref, axis=1)[:, ::-1][:, :5]
    np.testing.assert_allclose(sv, ref_sv, atol=1e-6)
    # end-to-end: repaired scores stay within the planned eps of a
    # from-scratch build on the mutated graph
    from repro.core import build
    fresh = build.build_index(rep.graph, eps=0.1, exact_d=True, seed=0)
    fref = np.asarray(single_source_device(fresh, rep.graph, cold))
    assert np.abs(got - fref).max() <= idx.plan.eps


def test_unaffected_pair_dropped_when_meeting_node_hot(small_graph):
    """A cached pair whose endpoints are both unaffected still reads
    d_k at its meeting nodes; when the repair re-estimated such a d_k
    the entry must not survive the swap, or the old d_k leaks through
    a pair with two cold endpoints."""
    g = small_graph
    idx = _fresh_index(g)
    eng = QueryEngine(idx, g, EngineConfig(pair_batch=16, source_batch=4,
                                           cache_size=64))
    rep = _churn(g, idx, seed=7)
    aff = set(int(x) for x in rep.affected)
    cold = [u for u in range(idx.n) if u not in aff]
    # cold-endpoint rows are unrepaired, so their meeting sets are the
    # same before and after the churn
    found = next(((u, v) for u in cold for v in cold
                  if u < v and _meeting_nodes(idx, u, v) & aff), None)
    assert found, "no cold pair meets an affected node; pick another seed"
    u, v = found
    eng.pair(u, v)                            # cached pre-swap
    eng.swap_index(idx, rep.graph, affected=rep.affected)
    assert ("pair", u, v) not in eng._cache._d
    assert eng.pair(u, v) == pytest.approx(
        idx.query_pair_host(u, v), abs=1e-4)


def test_swap_triggers_zero_recompiles(small_graph):
    """Hot-swap shape-stability contract: a fitting repaired index
    swaps in with no new dispatch shapes and no bucket overflow."""
    g = small_graph
    idx = _fresh_index(g)
    eng = QueryEngine(idx, g, EngineConfig(pair_batch=16, source_batch=4))
    eng.warmup()
    before = set(eng.stats()["unique_shapes"])
    rng = np.random.default_rng(3)
    for i in range(3):
        rep = _churn(g, idx, seed=20 + i)
        g = rep.graph
        eng.swap_index(idx, g, affected=rep.affected)
        us = rng.integers(0, idx.n, 5).astype(np.int32)
        eng.pairs(us, us[::-1])
        eng.single_source(us)
        eng.topk(us, 7)
    st = eng.stats()
    assert set(st["unique_shapes"]) == before
    assert st["swap_recompiles"] == 0
    assert st["swaps"] == 3 and st["epoch"] == 3
    assert st["last_swap_ms"] > 0


def test_swap_bucket_overflow_is_counted_and_correct(small_graph):
    """An index wider than the capacity bucket still swaps correctly --
    it just pays one counted recompile."""
    g = small_graph
    idx = _fresh_index(g)
    eng = QueryEngine(idx, g, EngineConfig(pair_batch=16, source_batch=4,
                                           swap_headroom=1.0,
                                           cap_quantum=1))
    wide = _fresh_index(g)
    grow = eng._width_cap + 7
    keys = np.full((wide.n, grow), np.int32(2**31 - 1), np.int32)
    vals = np.zeros((wide.n, grow), np.float32)
    keys[:, :wide.hp.width] = wide.hp.keys
    vals[:, :wide.hp.width] = wide.hp.vals
    wide.hp.keys, wide.hp.vals, wide.hp.width = keys, vals, grow
    out = eng.swap_index(wide, g)
    assert out["recompiles"] == 1
    assert eng.stats()["swap_recompiles"] == 1
    ref = [wide.query_pair_host(i, (i * 7) % wide.n)
           for i in range(10)]
    got = eng.pairs(np.arange(10), (np.arange(10) * 7) % wide.n)
    np.testing.assert_allclose(got, ref, atol=1e-4)


def _meeting_nodes(idx, u, v):
    """Nodes k whose d_k the cached pair value (u, v) reads:
    s = sum over shared keys (l, k) of h_u * h_v * d_k."""
    hp = idx.hp
    ku = hp.keys[u, :hp.counts[u]]
    kv = hp.keys[v, :hp.counts[v]]
    return set((np.intersect1d(ku, kv).astype(np.int64) % idx.n).tolist())


def test_invalidate_is_targeted(small_graph, sling_index):
    """Single-source/top-k vectors span every target, so any non-empty
    hot set drops all of them; a pair entry survives iff the hot set
    misses its endpoints AND its meeting nodes (the value reads d_k
    there)."""
    eng = QueryEngine(sling_index, small_graph,
                      EngineConfig(pair_batch=16, source_batch=4,
                                   cache_size=64))
    n, hot = sling_index.n, 1
    cold_pair = next((a, b) for a in range(2, n) for b in range(a + 1, n)
                     if hot not in _meeting_nodes(sling_index, a, b))
    met_pair = next((a, b) for a in range(2, n) for b in range(a + 1, n)
                    if hot in _meeting_nodes(sling_index, a, b))
    eng.single_source([hot])
    eng.single_source([5])
    eng.topk([6], 5)
    eng.pair(*cold_pair)
    eng.pair(*met_pair)
    # dropped: both source vectors + the topk vector (they hold a score
    # at the hot node) + the pair meeting it through d_1; the pair that
    # reads the hot node nowhere survives
    assert eng.invalidate([hot]) == 4
    b0 = eng.stats()["batches"]
    eng.pair(*cold_pair)                 # untouched pair still cached
    assert eng.stats()["batches"] == b0
    eng.pair(*met_pair)                  # dropped: re-dispatches
    assert eng.stats()["batches"] == b0 + 1
    assert eng.invalidate([]) == 0       # empty hot set: no-op
    assert eng.invalidate() == 2         # full clear drops the rest


def test_k_bucketing_shares_programs(engine):
    """k=2..9 all land in one bucket: one compiled topk program."""
    engine.topk([0], 2)
    n_shapes = len(engine.stats()["unique_shapes"])
    for k in (3, 5, 9, 16):
        engine.topk([1], k)
    assert len(engine.stats()["unique_shapes"]) == n_shapes
    sv, si = engine.topk([4], 9)
    assert sv.shape == (1, 9)


# ----------------------------------------------------------------------
# push path: dense MXU product or per-edge scatter, from the graph's shape
# ----------------------------------------------------------------------
V5E_BYTES = 16 * 10**9


@pytest.mark.parametrize("n,m,mult,platform,want", [
    (7115, 103689, 1, "tpu", True),          # wiki-Vote's shape
    (10**6, 3 * 10**6, 1, "tpu", False),     # the 10^6-node bring-up
    (20000, 20000, 1, "tpu", False),         # past the crossover
    (30000, 2 * 10**6, 1, "tpu", False),     # operator over 1/16 of HBM
    (7115, 103689, 257, "tpu", False),       # counts past bfloat16
    (7115, 103689, 256, "tpu", True),
    (7115, 103689, 1, "cpu", False),         # no measured crossover
])
def test_push_path_rule_on_described_shapes(n, m, mult, platform, want):
    from repro.core.hp_index import capacity_bucket
    from repro.serve.engine import dense_push_fits
    assert dense_push_fits(n, capacity_bucket(m), mult, platform,
                           V5E_BYTES) is want


@pytest.fixture()
def dense_on_cpu(monkeypatch):
    """Give the CPU the TPU's crossover, so that the engine's dense
    path runs here."""
    from repro.serve import engine as engine_mod
    monkeypatch.setitem(engine_mod.DENSE_PUSH_CELLS_PER_EDGE, "cpu",
                        engine_mod.DENSE_PUSH_CELLS_PER_EDGE["tpu"])


def test_cpu_engine_keeps_the_scatter(small_graph, sling_index):
    eng = QueryEngine(sling_index, small_graph)
    assert eng.stats()["push_path"] == "sparse"


def test_dense_engine_matches_the_scatter_engine(small_graph, sling_index,
                                                 dense_on_cpu):
    cfg = EngineConfig(source_batch=4, cache_size=0, push_backend="lax")
    dense = QueryEngine(sling_index, small_graph, cfg)
    assert dense.stats()["push_path"] == "dense"
    us = np.array([0, 7, 42, 99, 149], np.int32)
    ref = single_source_device(sling_index, small_graph, us, backend="lax")
    got = dense.single_source(us)
    assert np.abs(got - ref).max() <= 1e-6 * np.abs(ref).max()
    sv, si = dense.topk(us, 10)
    for i in range(len(us)):
        np.testing.assert_allclose(sv[i], ref[i][si[i]], atol=1e-6)
        np.testing.assert_allclose(sv[i], np.sort(ref[i])[::-1][:10],
                                   atol=1e-6)
    shapes = dense.stats()["unique_shapes"]
    assert {s[-1] for s in shapes if s[0] in ("topk", "source")} == {"dense"}
    assert all(s[1] == 4 for s in shapes if s[0] == "topk")
    fn, _, _ = dense._topk_program(us[:4], 16)
    assert "topk" in fn.__name__


def test_dense_path_swap_compiles_nothing(small_graph, dense_on_cpu):
    """The operator keeps its (n_pad, n_pad) shape across a swap: after
    warm-up, swaps and traffic compile no new program."""
    from repro.core.single_source import batched_single_source_dense
    from repro.core.topk import batched_topk_dense
    g = small_graph
    idx = _fresh_index(g)
    eng = QueryEngine(idx, g, EngineConfig(pair_batch=16, source_batch=4))
    assert eng.stats()["push_path"] == "dense"
    eng.warmup()
    before = set(eng.stats()["unique_shapes"])
    programs = (batched_topk_dense._cache_size(),
                batched_single_source_dense._cache_size())
    rng = np.random.default_rng(5)
    for i in range(2):
        rep = _churn(g, idx, seed=40 + i)
        g = rep.graph
        eng.swap_index(idx, g, affected=rep.affected)
        us = rng.integers(0, idx.n, 5).astype(np.int32)
        eng.single_source(us)
        sv, si = eng.topk(us, 7)
        ref = single_source_device(idx, g, us, backend="lax")
        for j in range(len(us)):
            np.testing.assert_allclose(sv[j], ref[j][si[j]], atol=1e-6)
    st = eng.stats()
    assert set(st["unique_shapes"]) == before
    assert st["swap_recompiles"] == 0 and st["push_path"] == "dense"
    assert (batched_topk_dense._cache_size(),
            batched_single_source_dense._cache_size()) == programs


def test_multiplicity_past_bfloat16_keeps_the_scatter(dense_on_cpu):
    from repro.core import build
    from repro.graph import csr
    rng = np.random.default_rng(2)
    src = np.concatenate([rng.integers(0, 40, 200), np.zeros(257, int)])
    dst = np.concatenate([rng.integers(0, 40, 200), np.ones(257, int)])
    keep = src != dst
    g = csr.from_edges(40, src[keep], dst[keep], dedup=False)
    idx = build.build_index(g, eps=0.2, exact_d=True, seed=0)
    assert QueryEngine(idx, g).stats()["push_path"] == "sparse"
    g1 = csr.from_edges(40, src[keep], dst[keep])
    eng = QueryEngine(idx, g1)
    assert eng.stats()["push_path"] == "dense"
    # a swap that brings such counts falls back to the scatter, counted
    out = eng.swap_index(idx, g)
    assert out["recompiles"] >= 1
    assert eng.stats()["push_path"] == "sparse"
    us = np.array([0, 1, 5], np.int32)
    np.testing.assert_allclose(
        eng.single_source(us),
        single_source_device(idx, g, us, backend="lax"), atol=1e-6)


def test_mesh_and_pallas_engines_keep_the_scatter(small_graph, sling_index,
                                                  dense_on_cpu):
    from repro.core import shard_query
    mesh = shard_query.serving_mesh(1)
    for cfg in (EngineConfig(mesh=mesh), EngineConfig(push_backend="pallas")):
        eng = QueryEngine(sling_index, small_graph, cfg)
        assert eng.stats()["push_path"] == "sparse"


def test_engine_spans_name_the_push_path(small_graph, sling_index,
                                         dense_on_cpu):
    import time

    from repro.serve import spans
    eng = QueryEngine(sling_index, small_graph,
                      EngineConfig(source_batch=4, k_buckets=(4,)))
    rec = spans.Recorder(time.monotonic)
    spans.enable(rec)
    try:
        eng.topk([1, 2], 4)
        eng.single_source([3])
    finally:
        spans.disable()
    calls = {r[0]: r[6] for r in rec.records
             if r[0] in ("sling.engine.topk", "sling.engine.sources")}
    assert set(calls) == {"sling.engine.topk", "sling.engine.sources"}
    assert all(a["push"] == "dense" for a in calls.values())
    assert calls["sling.engine.sources"]["requests"] == 1
