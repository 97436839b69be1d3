"""The roofline's byte model counts the work, not an implementation:
the lax and the Pallas push engines dispatch the same batch shape and
are held to the same bytes at equal (B, n, m, l_max)."""
import importlib.util

import numpy as np
import pytest
from bench_tiny import ROOT, TINY

from bench import graphs, peaks


def _reader():
    path = ROOT / "bench" / "metrics" / "device.topk_roofline.py"
    spec = importlib.util.spec_from_file_location("roofline_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_byte_model():
    b, n, m, l = 8, 1000, 5000, 24
    assert peaks.topk_least_bytes(b, n, m, l) == \
        l * (12 * m + 8 * b * n) + 4 * b * n


def test_lax_and_pallas_push_are_held_to_the_same_bytes(tmp_path):
    from repro.core import build
    from repro.core.index import SlingIndex
    from repro.graph import csr
    from repro.serve import EngineConfig, QueryEngine
    src, dst = graphs.make_edges(TINY["graph"])
    g = csr.from_edges(TINY["graph"]["n"], src, dst)
    path = str(tmp_path / "idx.sling")
    build.build_index_scale(g, path, eps=0.2, c=0.6, seed=6)
    idx = SlingIndex.load(path, mmap=True)
    reader = _reader()
    got = {}
    for backend in ("lax", "pallas"):
        eng = QueryEngine(idx, g, EngineConfig(push_backend=backend))
        eng.topk(np.arange(3, dtype=np.int32), 10)
        rec = {"shapes": [list(s) for s in eng.stats()["unique_shapes"]]}
        assert any(backend in s for s in rec["shapes"])
        batch = reader.dispatched_batch(rec)
        got[backend] = peaks.topk_least_bytes(batch, idx.n, g.m,
                                              idx.plan.l_max)
    assert got["lax"] == got["pallas"]
    rec = {"shapes": [["topk", 8, 16, "lax"]],
           "index": {"n": idx.n, "m": g.m, "l_max": idx.plan.l_max},
           "peaks": peaks.peaks("TPU v5 lite"),
           "trace": {"modules": {"batched_topk": {"count": 2,
                                                  "total_s": 0.002}}}}
    want = 100 * got["lax"] / 819e9 / 1e-3
    assert reader.read(rec) == pytest.approx(want)
    rec["trace"]["modules"] = {}
    assert reader.read(rec) is None
