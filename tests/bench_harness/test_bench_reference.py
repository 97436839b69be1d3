"""bench/reference.py agrees with the program's own host references
(``single_source_horner``, ``query_pair_host``) on a small int16
index, so the copy is known to agree with them today; and its
bfloat16 control does not. Its exact SimRank agrees with the program's
power method, and the program's answers lie within eps of it."""
import numpy as np
import pytest
from bench_tiny import TINY

from bench import graphs, reference


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    from repro.core import build
    from repro.core.index import SlingIndex
    from repro.graph import csr
    src, dst = graphs.make_edges(TINY["graph"])
    g = csr.from_edges(TINY["graph"]["n"], src, dst)
    path = str(tmp_path_factory.mktemp("ref") / "idx.sling")
    build.build_index_scale(g, path, eps=0.2, c=0.6, seed=5)
    idx = SlingIndex.load(path, mmap=True)
    art = reference.read_artifact(path)
    return g, idx, art, reference.Edges.of(src, dst, art.n, art.c)


NODES = [0, 7, 31, 100, 239]


def test_artifact_matches_the_loaded_index(built):
    g, idx, art, _ = built
    assert art.n == idx.n and art.l_max == idx.plan.l_max
    assert np.array_equal(np.asarray(art.keys), np.asarray(idx.hp.keys))
    assert np.allclose(art.vals, idx.vals_f32(), rtol=1e-6, atol=1e-9)
    assert np.allclose(art.d, idx.d, rtol=1e-6)


@pytest.mark.parametrize("u", NODES)
def test_horner_row_matches_single_source_horner(built, u):
    from repro.core.single_source import single_source_horner
    g, idx, art, e = built
    got = reference.horner_row(art, e, u)
    want = single_source_horner(idx, g, u)
    assert np.abs(got - want).max() < 1e-6
    # the bfloat16 control is visibly off the same row (a node with no
    # in-neighbour scores only itself, 1.0, exact in any precision)
    assert (want > 0).sum() <= 1 or np.abs(reference.horner_row(art, e, u, rnd=reference.bf16)
                  - want).max() > 1e-4


def test_topk_of_matches_topk_host(built):
    from repro.core.single_source import single_source_horner
    from repro.core.topk import topk_host
    g, idx, art, e = built
    for u in NODES:
        sv, si = reference.topk_of(reference.horner_row(art, e, u), 10)
        hv, hi = topk_host(idx, g, u, 10, method=single_source_horner)
        assert np.array_equal(si, hi)
        assert np.abs(sv - hv).max() < 1e-6


def test_pair_matches_query_pair_host(built):
    g, idx, art, e = built
    rng = np.random.default_rng(0)
    pairs = [(u, v) for u in NODES for v in rng.integers(0, art.n, 6)]
    pairs += [(u, u) for u in NODES]
    for u, v in pairs:
        assert abs(reference.pair(art, u, int(v))
                   - idx.query_pair_host(u, int(v), g)) < 1e-7


@pytest.fixture(scope="module")
def exact(built):
    g, idx, art, e = built
    return reference.ExactSimRank(e.src, e.dst, art.n, art.c)


def test_exact_simrank_matches_the_power_method(built, exact):
    from repro.baselines import power
    g, idx, art, e = built
    want = power.all_pairs(g, c=art.c, iters=60)
    got = np.stack([exact.row(u) for u in range(art.n)])
    assert exact.bound <= 1e-7
    # within the stopping rule's bound of the converged power method
    assert np.abs(got - want).max() <= exact.bound
    rng = np.random.default_rng(1)
    for u, v in rng.integers(0, art.n, (50, 2)).tolist() + [(3, 3)]:
        assert exact.pair(u, v) == got[u, v]


def test_the_index_is_within_eps_of_exact_simrank(built, exact):
    g, idx, art, e = built
    for u in NODES:
        assert np.abs(reference.horner_row(art, e, u) - exact.row(u)).max() < 0.2
