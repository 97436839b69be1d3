"""The serving path's span recorder (serve/spans.py): what it records
through ServeFrontend and QueryEngine, how a request's parts join up,
and that it changes nothing while off."""
import numpy as np
import pytest

from repro.serve import (EngineConfig, FrontendConfig, QueryEngine,
                         ServeFrontend, VirtualClock, spans)

pytestmark = pytest.mark.serve

ECFG = EngineConfig(pair_batch=8, source_batch=4, cache_size=64,
                    k_buckets=(4, 16))
MAX_WAIT = 0.005


@pytest.fixture
def recording():
    """Enable a recorder (on the clock the test gives), disable after."""
    def start(now, cap=1 << 20):
        rec = spans.Recorder(now, cap)
        spans.enable(rec)
        return rec
    yield start
    spans.disable()


def frontend(index, g, clock, **over):
    cfg = dict(max_batch=3, max_pair_batch=4, max_wait=MAX_WAIT,
               engine=ECFG)
    cfg.update(over)
    return ServeFrontend(index, g, FrontendConfig(**cfg), clock=clock)


def drive(fe, clk, n, seed=5):
    """A mixed stream of pairs and top-k, with clock advances between
    admissions; returns the tickets."""
    rng = np.random.default_rng(seed)
    tickets = []
    for _ in range(40):
        if rng.random() < 0.6:
            tickets.append(fe.submit_pair(int(rng.integers(n)),
                                          int(rng.integers(n))))
        else:
            tickets.append(fe.submit_topk(int(rng.integers(n)), 5))
        if rng.random() < 0.5:
            clk.advance(float(rng.uniform(0, 1.5 * MAX_WAIT)))
    clk.advance(MAX_WAIT)
    fe.flush()
    return tickets


def by_name(rec, name):
    return [r for r in rec.records if r[0] == name]


def request_parts(rec, tickets):
    """Per answered ticket: (close - admission, batch start - close,
    fulfil - batch start), joined through the batch span's ids."""
    batch_of = {}
    for name, _id, _parent, start, _end, _th, attrs in by_name(
            rec, "sling.frontend.batch"):
        for rid in attrs["requests"]:
            assert rid not in batch_of
            batch_of[rid] = (attrs["closed"], start)
    parts = {}
    for t in tickets:
        closed, start = batch_of[t.id]
        parts[t.id] = (closed - t.submit_t, start - closed,
                       t.fulfil_t - start)
    return parts


def test_request_parts_tile_latency(small_graph, sling_index, recording):
    clk = VirtualClock()
    rec = recording(clk.now)
    fe = frontend(sling_index, small_graph, clk)
    tickets = drive(fe, clk, small_graph.n)
    fe.close()
    assert all(t.done() and not t.shed for t in tickets)
    parts = request_parts(rec, tickets)
    assert len(parts) == len(tickets)
    for t in tickets:
        p = parts[t.id]
        assert min(p) >= 0.0
        assert sum(p) == pytest.approx(t.latency, abs=1e-12)
    # inline dispatch runs a batch where it closes; the queue wait is
    # what the timer or the size left
    assert all(p[1] == 0.0 for p in parts.values())
    assert max(p[0] for p in parts.values()) == pytest.approx(MAX_WAIT)
    # batch_log keeps the close and the start apart
    for b in fe.batch_log:
        assert b.opened <= b.closed <= b.started


def test_parent_links_and_request_ids_join(small_graph, sling_index,
                                           recording):
    clk = VirtualClock()
    rec = recording(clk.now)
    fe = frontend(sling_index, small_graph, clk)
    tickets = drive(fe, clk, small_graph.n, seed=9)
    fe.close()
    assert rec.dropped == 0
    span = {r[1]: r for r in rec.records}
    assert len(span) == len(rec.records)
    parent = {r[1]: span[r[2]][0] if r[2] is not None else None
              for r in rec.records}
    want = {
        "sling.frontend.batch": {None, "sling.frontend.timer"},
        "sling.engine.pairs": {"sling.frontend.batch"},
        "sling.engine.topk": {"sling.frontend.batch"},
        "sling.engine.cache": {"sling.engine.pairs", "sling.engine.topk"},
        "sling.engine.pad": {"sling.engine.pairs", "sling.engine.topk"},
        "sling.engine.launch": {"sling.engine.pairs", "sling.engine.topk"},
        "sling.engine.sync": {"sling.engine.pairs", "sling.engine.topk"},
        "sling.frontend.fulfil": {"sling.frontend.batch"},
        "sling.frontend.timer": {None},
    }
    for r in rec.records:
        assert parent[r[1]] in want[r[0]], (r[0], parent[r[1]])
        lo, hi = (span[r[2]][3], span[r[2]][4]) if r[2] else (r[3], r[4])
        assert lo <= r[3] <= r[4] <= hi
    # every request sits in exactly one batch, whose size it counts
    batches = by_name(rec, "sling.frontend.batch")
    ids = [i for b in batches for i in b[6]["requests"]]
    assert sorted(ids) == sorted(t.id for t in tickets)
    assert sorted(ids) == list(range(1, len(tickets) + 1))
    assert all(b[6]["size"] == len(b[6]["requests"]) for b in batches)
    assert {b[6]["reason"] for b in batches} <= {"size", "wait", "flush"}
    # each engine call names what it padded and missed
    for r in by_name(rec, "sling.engine.pairs"):
        a = r[6]
        assert a["misses"] <= a["requests"]
        assert (a["misses"] + a["pad"]) % ECFG.pair_batch == 0


def test_off_records_nothing_and_changes_nothing(small_graph, sling_index,
                                                 recording):
    def served(record):
        clk = VirtualClock()
        rec = recording(clk.now) if record else None
        fe = frontend(sling_index, small_graph, clk)
        try:
            tickets = drive(fe, clk, small_graph.n, seed=3)
        finally:
            fe.close()
            spans.disable()
        answers = [t.result(timeout=0) for t in tickets]
        return answers, list(fe.batch_log), rec

    assert spans.span("sling.engine.pairs") is spans.OFF
    idle = spans.Recorder(VirtualClock().now)      # never enabled
    off_answers, off_log, _ = served(False)
    on_answers, on_log, rec = served(True)
    assert idle.records == [] and idle.dropped == 0
    assert len(rec.records) > 0
    assert off_log == on_log
    for a, b in zip(off_answers, on_answers):
        if isinstance(a, float):
            assert a == b
        else:
            np.testing.assert_array_equal(a[0], b[0])
            np.testing.assert_array_equal(a[1], b[1])


def test_bound_counts_dropped_records(recording):
    clk = VirtualClock()
    rec = recording(clk.now, cap=3)
    for _ in range(5):
        with spans.span("sling.engine.cache"):
            clk.advance(0.001)
    assert len(rec.records) == 3 and rec.dropped == 2
    assert [r[3] for r in rec.records] == pytest.approx([0.0, 0.001, 0.002])


def test_compile_inside_launch_is_charged_to_it(small_graph, sling_index,
                                                recording, tmp_path):
    """JAX reports a request for a new executable where the persistent
    cache is on; the recorder charges it to the launch span open on
    the calling thread, and a warm call compiles nothing."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        # a pair batch no other test uses, so its program is new here
        eng = QueryEngine(sling_index, small_graph,
                          EngineConfig(pair_batch=11, cache_size=0))
        rec = recording(lambda: 0.0)
        eng.pairs([1, 2], [3, 4])
        eng.pairs([5], [6])
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    launches = by_name(rec, "sling.engine.launch")
    assert len(launches) == 2
    assert launches[0][6].get("compiles", 0) >= 1
    assert launches[1][6].get("compiles", 0) == 0
    # the engine call is a root here: no frontend above it
    calls = by_name(rec, "sling.engine.pairs")
    assert [c[2] for c in calls] == [None, None]
    assert calls[0][6] == {"requests": 2, "misses": 2, "pad": 9}


def test_thread_dispatch_spans_on_the_worker(small_graph, sling_index,
                                             recording):
    """With the production clock, the worker's wait, batch, engine and
    fulfil spans all sit on its thread, and the request parts are
    non-negative and add up."""
    fe = ServeFrontend(sling_index, small_graph,
                       FrontendConfig(max_pair_batch=4, max_wait=MAX_WAIT,
                                      engine=ECFG))
    rec = recording(fe.clock.now)
    try:
        tickets = [fe.submit_pair(i, i + 1) for i in range(10)]
        for t in tickets:
            t.result(timeout=60)
    finally:
        fe.close()
    parts = request_parts(rec, tickets)
    for t in tickets:
        p = parts[t.id]
        assert min(p) >= 0.0
        assert sum(p) == pytest.approx(t.latency, abs=1e-9)
    worker = {r[5] for r in by_name(rec, "sling.worker.wait")}
    assert len(worker) == 1
    for name in ("sling.frontend.batch", "sling.engine.pairs",
                 "sling.engine.sync", "sling.frontend.fulfil"):
        assert {r[5] for r in by_name(rec, name)} == worker, name
    assert {r[5] for r in by_name(rec, "sling.frontend.timer")} \
        .isdisjoint(worker)


def test_program_texts_are_fresh_past_a_stale_persistent_cache(
        small_graph, sling_index, monkeypatch, tmp_path):
    """The persistent cache's key leaves debug info out, so a program
    it stored from code without the scopes comes back without them;
    ``program_texts`` compiles past it and finds them, and leaves the
    cache's settings as they were."""
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.core.topk import batched_topk
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_enable_compilation_cache")
    saved = {k: getattr(jax.config, k) for k in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        # a source batch no other test uses; its program is stored
        # from code whose scopes are gone
        eng = QueryEngine(sling_index, small_graph,
                          EngineConfig(source_batch=7, k_buckets=(4,),
                                       cache_size=0))
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            eng.topk([1, 2], 4)
        jax.clear_caches()
        fn, args, kw = eng._topk_program(np.zeros(7, np.int32), 4)
        assert fn is batched_topk
        stale = fn.lower(*args, **kw).compile().as_text()
        assert "sling.push" not in stale        # the cache's copy
        texts = dict(eng.program_texts())
        assert "sling.push" in texts["batched_topk"]
        assert "sling.select" in texts["batched_topk"]
        assert jax.config.jax_enable_compilation_cache == \
            saved["jax_enable_compilation_cache"]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        jax.clear_caches()
