"""The check that decides ``correct`` fails a run whose timed path is
broken underneath: an answer altered where the engine produces it,
the ids of a top-k answer swapped, half of a batch's answers zeroed
(planted in ``QueryEngine``'s public answers, the seam that
``ServeFrontend`` reads); and an index built wrong, with its diagonal
scaled or half of its HP entries dropped (planted in the build, so the
artifact the serving path loads and the artifact reference reads are
both wrong, and only exact SimRank sees it)."""
import numpy as np
import pytest
from bench_tiny import run_tiny


def _break(monkeypatch, method, fault):
    from repro.serve.engine import QueryEngine
    real = getattr(QueryEngine, method)

    def broken(self, *a, **kw):
        out = real(self, *a, **kw)
        if method == "pairs":
            out = np.array(out)
            if fault == "score":
                out[0] += 1e-3
            else:                       # half
                out[::2] = 0.0
            return out
        v, i = np.array(out[0]), np.array(out[1])
        if fault == "score":
            v[:, 0] += 1e-3
        elif fault == "ids":
            i[:, [0, 1]] = i[:, [1, 0]]
        else:                           # half
            v[::2] = 0.0
        return v, i
    monkeypatch.setattr(QueryEngine, method, broken)


@pytest.mark.parametrize("fault", ["score", "ids", "half"])
def test_topk_fault_is_not_correct(monkeypatch, fault):
    _break(monkeypatch, "topk", fault)
    _, _, line = run_tiny("topk-closed")
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("fault", ["score", "half"])
def test_pair_fault_is_not_correct(monkeypatch, fault):
    _break(monkeypatch, "pairs", fault)
    _, _, line = run_tiny("pair-open")
    assert line["correct"] is False, line["checks"]


def _break_build(monkeypatch, fault):
    from repro.core import diagonal, index
    if fault == "d_scaled":
        real_d = diagonal.estimate_diagonal_chunked
        monkeypatch.setattr(diagonal, "estimate_diagonal_chunked",
                            lambda *a, **kw: 0.5 * real_d(*a, **kw))
        return
    real_pack = index.pack_coo_to_v3

    def pack(path, p, d, src, key, val, *a, **kw):
        keep = slice(None, None, 2)         # hp_dropped: every other entry
        return real_pack(path, p, d, src[keep], key[keep], val[keep], *a, **kw)
    monkeypatch.setattr(index, "pack_coo_to_v3", pack)


@pytest.mark.parametrize("fault", ["d_scaled", "hp_dropped"])
def test_build_fault_is_not_correct(monkeypatch, fault):
    _break_build(monkeypatch, fault)
    _, rec, line = run_tiny("topk-closed")
    assert line["correct"] is False, line["checks"]
    # the serving path agrees with the faulty artifact; exact SimRank
    # is what catches the build
    checks = rec["checks"]
    assert checks["topk_score_err"]["value"] <= checks["topk_score_err"]["limit"]
    assert checks["simrank_err"]["value"] > checks["simrank_err"]["limit"]
