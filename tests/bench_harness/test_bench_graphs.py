"""bench/graphs.py: the Chung-Lu generator of the configurations."""
import numpy as np
import pytest
from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import graphs

# a scaled-down soc-Slashdot0902 shape: the same density and exponents
SPEC = {"n": 20000, "m": 231000, "gamma_in": 2.1, "gamma_out": 2.72,
        "max_in_degree": 620, "max_out_degree": 610,
        "structure_seed": 0}


@pytest.fixture(scope="module")
def edges():
    return graphs.make_edges(SPEC)


def test_n_and_m_are_exact(edges):
    src, dst = edges
    assert len(src) == SPEC["m"]
    assert src.min() >= 0 and max(src.max(), dst.max()) < SPEC["n"]
    assert not np.any(src == dst)
    assert len(np.unique(src * SPEC["n"] + dst)) == SPEC["m"]
    again = graphs.make_edges(SPEC)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])


def test_another_structure_seed_draws_another_graph(edges):
    src, dst = edges
    s2, d2 = graphs.make_edges(dict(SPEC, structure_seed=1))
    assert len(s2) == SPEC["m"] and not np.array_equal(src, s2)


def _tail_exponent(deg):
    """Discrete power-law MLE of the degree tail between the median
    and the weights' cap (Clauset, Shalizi and Newman 2009, eq. 3.7)."""
    d = deg[deg >= 2 * np.median(deg)].astype(np.float64)
    dmin = d.min()
    return 1.0 + len(d) / np.log(d / (dmin - 0.5)).sum()


@pytest.mark.parametrize("side,gamma", [("in", 2.1), ("out", 2.72)])
def test_tail_exponents_are_near_the_stated(edges, side, gamma):
    src, dst = edges
    deg = np.bincount(dst if side == "in" else src, minlength=SPEC["n"])
    assert _tail_exponent(deg) == pytest.approx(gamma, abs=0.25)


def test_in_degree_zero_share_is_far_below_powerlaw_fast(edges):
    from repro.graph import generators
    src, dst = edges
    ours = np.mean(np.bincount(dst, minlength=SPEC["n"]) == 0)
    theirs = np.mean(generators.powerlaw_fast(
        SPEC["n"], k=SPEC["m"] // SPEC["n"], seed=3).in_deg == 0)
    assert ours < 0.1 * theirs


def test_in_edges_reach_only_in_nodes():
    spec = dict(SPEC, n=7115, m=103689, in_nodes=2794, max_in_degree=457,
                max_out_degree=893)
    src, dst = graphs.make_edges(spec)
    assert len(np.unique(src * spec["n"] + dst)) == spec["m"]
    indeg = np.bincount(dst, minlength=spec["n"])
    # every candidate of the draw gets votes; no one else does
    assert (indeg > 0).sum() == spec["in_nodes"]
    assert abs(indeg.max() - spec["max_in_degree"]) < 0.1 * spec["max_in_degree"]
