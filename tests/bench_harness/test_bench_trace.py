"""bench/trace.py on a small trace recorded on one v5e chip: a window
of 16 top-k requests (two batches of 8) and three batches of 64 pairs
on a 2,000-node index, with the benchmark's host annotations."""
import numpy as np
import pytest
from bench_tiny import ROOT

from bench import trace

XPLANE = ROOT / "tests" / "bench_harness" / "data" / "small_v5e.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_xplane(str(XPLANE))


def _device_events(line_name):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(XPLANE))
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(l for l in plane.lines if l.name == line_name)
    return [(e.name, e.start_ns, e.duration_ns) for e in line.events]


def test_known_numbers(reduced):
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.101769021, abs=1e-12)
    assert reduced["busy_s"] == pytest.approx(0.015472166, abs=1e-12)
    mods = reduced["modules"]
    assert set(mods) == {"batched_topk", "pair_query_batch_pallas"}
    assert mods["batched_topk"]["count"] == 2
    assert mods["batched_topk"]["total_s"] == pytest.approx(0.011782276, abs=1e-12)
    assert mods["pair_query_batch_pallas"]["count"] == 3
    assert mods["pair_query_batch_pallas"]["total_s"] == pytest.approx(
        0.003692797, abs=1e-12)


def test_busy_is_the_union_of_ops(reduced):
    # an independent union: mark every busy nanosecond of the window
    ops = _device_events("XLA Ops")
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    mask = np.zeros(int(t1 - t0) + 1, bool)
    for _, s, d in ops:
        mask[int(s - t0):int(s + d - t0)] = True
    assert reduced["busy_s"] == pytest.approx(mask.sum() * 1e-9, rel=1e-6)
    # idle is what is left of the window; the gaps are parts of it
    idle = reduced["window_s"] - reduced["busy_s"]
    assert 0 < sum(g for _, g in reduced["idle_gaps"]) <= idle + 1e-12
    assert all(l.startswith("host: ") for l, _ in reduced["idle_gaps"])


def test_module_time_is_the_sum_of_its_events(reduced):
    mods = _device_events("XLA Modules")
    want = sum(d for n, _, d in mods if trace.module_key(n) == "batched_topk")
    assert reduced["modules"]["batched_topk"]["total_s"] == pytest.approx(
        want * 1e-9)
    assert trace.module_key("jit_batched_topk(123)") == "batched_topk"


def test_top_ops_are_sorted_and_at_most_ten(reduced):
    secs = [s for _, s in reduced["device_ops"]]
    assert 0 < len(secs) <= 10 and secs == sorted(secs, reverse=True)
    assert sum(secs) <= reduced["busy_s"] + 1e-9


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
