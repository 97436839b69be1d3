"""bench/program.py on synthetic span records and a synthetic
host/device timeline: the request tiling, the frontend and engine
numbers, and the naming of idle device time by program span."""
import pytest
from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import program

T = 1e-3


def rec(name, id, parent, start, end, thread=1, **attrs):
    return (name, id, parent, start, end, thread, attrs)


# two pair batches on one worker; request 3 closes the second batch
RECORDS = [
    rec("sling.worker.wait", 1, None, 0 * T, 2 * T),
    rec("sling.frontend.batch", 2, None, 2 * T, 9 * T, closed=1.5 * T,
        requests=[1, 2]),
    rec("sling.engine.pairs", 3, 2, 2.5 * T, 7 * T),
    rec("sling.engine.launch", 4, 3, 3 * T, 4 * T),
    rec("sling.engine.sync", 5, 3, 4 * T, 6 * T),
    rec("sling.worker.wait", 6, None, 9 * T, 10 * T),
    rec("sling.frontend.batch", 7, None, 10 * T, 15 * T, closed=9.5 * T,
        requests=[3]),
    rec("sling.engine.pairs", 8, 7, 10 * T, 14 * T),
    rec("sling.engine.sync", 9, 8, 11 * T, 14 * T),
    rec("sling.frontend.timer", 10, None, 9.4 * T, 9.6 * T, thread=2),
]

REQUESTS = [
    {"sched": 0.0, "sent": 0.1 * T, "admit": 0.2 * T, "id": 1,
     "done": 9 * T},
    {"sched": 0.5 * T, "sent": 0.5 * T, "admit": 0.6 * T, "id": 2,
     "done": 9 * T},
    {"sched": 8 * T, "sent": 8.1 * T, "admit": 8.5 * T, "id": 3,
     "done": 15 * T},
    {"sched": 14 * T, "sent": 14 * T, "admit": 14 * T, "id": 4,
     "done": None},                          # never answered
]


def test_request_parts_tile_each_latency():
    parts = program.request_parts(REQUESTS, RECORDS)
    assert parts[0] == pytest.approx((0.1 * T, 0.1 * T, 1.3 * T, 0.5 * T,
                                      7 * T))
    assert parts[2] == pytest.approx((0.1 * T, 0.4 * T, 1.0 * T, 0.5 * T,
                                      5 * T))
    assert parts[3] is None
    for q, p in zip(REQUESTS[:3], parts):
        assert sum(p) == pytest.approx(q["done"] - q["sched"])
    assert program.tiling_violations(REQUESTS, parts) == 0
    assert program.queue_wait_ms(parts) == pytest.approx(1.0)
    assert program.handoff_ms(parts) == pytest.approx(0.5)


def test_tiling_catches_a_missing_link_and_a_negative_part():
    reqs = [dict(q) for q in REQUESTS]
    reqs[0]["id"] = 99                      # no batch lists it
    reqs[1]["admit"] = 1.6 * T              # admitted after its close
    parts = program.request_parts(reqs, RECORDS)
    assert parts[0] is None
    assert program.tiling_violations(reqs, parts) == 2


def test_engine_host_time_leaves_out_the_sync():
    # (7 - 2.5 - 2) and (14 - 10 - 3) ms
    assert program.engine_host_ms(RECORDS, "pairs", 0, 1) == \
        pytest.approx(1.75)
    assert program.engine_host_ms(RECORDS, "pairs", 5 * T, 1) == \
        pytest.approx(1.0)


def test_worker_gap_leaves_out_the_wait():
    # from 7 to 10 ms, less the wait of 9-10 ms
    assert program.worker_gap_ms(RECORDS, "pairs", 0, 1) == \
        pytest.approx(2.0)


def test_a_missing_span_reads_none_not_zero():
    assert program.engine_host_ms(RECORDS, "topk", 0, 1) is None
    assert program.worker_gap_ms(RECORDS[:5], "pairs", 0, 1) is None
    assert program.queue_wait_ms(program.request_parts(REQUESTS, [])) \
        is None
    assert program.handoff_ms([]) is None


# host spans in ns: a worker's batch holds an engine call holding a
# sync; another thread's timer; the benchmark's own wrapper
HOST = sorted([
    (0, 1000, "sling.frontend.batch"),
    (100, 900, "sling.engine.pairs"),
    (100, 900, "bench.engine.pairs"),
    (500, 800, "sling.engine.sync"),
    (950, 1200, "sling.worker.wait"),
    (1100, 1150, "sling.frontend.timer"),
    (2000, 2100, "bench.engine.pairs"),
])


def test_gap_label_is_the_innermost_span_covering_most_of_it():
    assert program.label(550, 750, HOST) == "host: sling.engine.sync"
    # the sync covers less than half of this one; the call covers all
    assert program.label(150, 600, HOST) == "host: sling.engine.pairs"
    # the timer is shorter, but covers a fifth: the wait holds it
    assert program.label(950, 1200, HOST) == "host: sling.worker.wait"
    # none covers half: the program span overlapping most
    assert program.label(1150, 1500, HOST) == "host: sling.worker.wait"
    # no program span: the benchmark's, then none, as bench/trace.py
    assert program.label(2000, 2050, HOST) == "host: bench.engine.pairs"
    assert program.label(3000, 3100, HOST) == "host: none"


def test_idle_by_span_splits_each_gap_by_the_innermost_open_span():
    idle = [(50, 150), (700, 1000), (1100, 1300)]
    got = program.idle_by_span(idle, HOST)
    # 50-100 batch, 100-150 call; 700-800 sync, 800-900 call, 900-950
    # batch, 950-1000 wait; 1100-1150 timer, 1150-1200 wait, then none
    want = {"sling.frontend.batch": 100, "sling.engine.pairs": 150,
            "sling.engine.sync": 100, "sling.worker.wait": 100,
            "sling.frontend.timer": 50, "none": 100}
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in idle) * 1e-9)


def test_gaps_are_the_window_less_the_busy_intervals():
    assert program.gaps([[10, 20], [30, 40]], 0, 50) == \
        [(0, 10), (20, 30), (40, 50)]
    assert program.gaps([[0, 50]], 0, 50) == []


# batch spans as the frontend notes them, and the engine calls in them
ATTRS = [
    rec("sling.frontend.batch", 1, None, 1 * T, 2 * T, kind="pair", size=2,
        cap=64, reason="wait", replica=0, ahead=1, closed=0.5 * T,
        requests=[1, 2]),
    rec("sling.engine.pairs", 2, 1, 1 * T, 2 * T, requests=2, misses=2,
        pad=254),
    rec("sling.frontend.batch", 3, None, 3 * T, 4 * T, kind="pair",
        size=16, cap=64, reason="wait", replica=0, ahead=3, closed=2 * T,
        requests=list(range(3, 19))),
    rec("sling.engine.pairs", 4, 3, 3 * T, 4 * T, requests=16, misses=12,
        pad=244),
    rec("sling.frontend.batch", 5, None, 5 * T, 6 * T, kind="pair",
        size=64, cap=64, reason="size", replica=0, ahead=2, closed=5 * T,
        requests=list(range(19, 83))),
    rec("sling.engine.pairs", 6, 5, 5 * T, 6 * T, requests=64, misses=64,
        pad=192),
]


def test_batch_summary_reads_every_attribute_of_the_batch_span():
    got = program.batch_summary(ATTRS, 0, 1)
    assert got["batches"] == 3
    assert got["fill_pct_median"] == pytest.approx(25.0)
    assert got["ahead_median"] == 2.0
    assert got["reason_pct"] == pytest.approx({"size": 100 / 3,
                                               "wait": 200 / 3})
    assert got["kinds"] == {"pair": 3} and got["replicas"] == {"0": 3}
    # only the batches begun in the window
    assert program.batch_summary(ATTRS, 4 * T, 1)["batches"] == 1
    assert program.batch_summary(RECORDS[:1], 0, 1) is None


def test_engine_summary_reads_misses_and_padding():
    got = program.engine_summary(ATTRS, "pairs", 0, 1)
    assert got["calls"] == 3 and got["requests"] == 82
    assert got["miss_pct"] == pytest.approx(100 * 78 / 82)
    assert got["pad_pct"] == pytest.approx(100 * 690 / 768)
    assert program.engine_summary(ATTRS, "topk", 0, 1) is None


def test_batch_log_handoff_is_start_less_close_per_batch():
    from repro.serve.frontend import BatchRecord

    def b(closed, started):
        return BatchRecord(kind="pair", key=("pair",), size=1, cap=64,
                           epoch=0, replica=0, reason="wait", opened=0.0,
                           closed=closed, started=started)
    log = [b(1 * T, 1.5 * T), b(2 * T, 4 * T), b(3 * T, 3 * T),
           b(9 * T, 20 * T)]
    assert program.batch_log_handoff_ms(log, 0, 5 * T) == \
        pytest.approx(0.5)
    assert program.batch_log_handoff_ms(log, 10 * T, 1) is None


def test_program_run_on_the_tiny_cell_on_the_cpu(tmp_path):
    """bench/program_run.py end to end through the harness on the CPU
    at the tiny size: every answered request tiles, the program-span
    numbers read, and the device ones read None (no TPU plane)."""
    import time

    import jax
    from bench_tiny import tiny_cell
    from jax.experimental.compilation_cache import compilation_cache

    from bench import program_run
    from repro.serve import spans
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in names}
    try:
        rec, out, texts = program_run.run(
            tiny_cell("pair-open"), 2**31 + 13, 0.5, str(tmp_path),
            t_start=time.monotonic(), require_tpu=False)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert spans.span("sling.engine.pairs") is spans.OFF
    assert rec["checks"] and out["answered"] > 0
    assert out["tiling_violations"] == 0 and out["dropped"] == 0
    layer = out["per_layer"]
    for name in ("frontend.queue_wait_ms.pair", "frontend.handoff_ms.pair",
                 "engine.host_ms.pair"):
        assert layer[name] is not None and layer[name] >= 0, name
    assert layer["device.fold_ms.pair"] is None
    assert layer["device.push_ms.topk"] is None
    assert out["batches"]["kinds"] == {"pair": out["batches"]["batches"]}
    assert out["engine"]["requests"] > 0
    assert 0 <= out["engine"]["pad_pct"] <= 100
    assert out["batch_log_handoff_ms"] >= 0
    assert {"sling.frontend.batch", "sling.engine.pairs",
            "sling.engine.sync"} <= set(out["spans"])
    assert [name for name, _ in texts] == ["_pair_query_batch"]
    assert set(out["end_to_end"]) == {"pair_p50_ms", "pair_p95_ms",
                                      "setup_s"}
