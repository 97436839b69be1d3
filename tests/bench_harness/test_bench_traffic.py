"""The traffic generator: deterministic in the seed, Poisson at the
stated rate."""
import math

import numpy as np
import pytest
from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import traffic

N = 500
BIG = 2**31 + 12345


def _rng(seed):
    return np.random.default_rng([seed, 1])


@pytest.mark.parametrize("dist", [{"dist": "uniform"},
                                  {"dist": "zipf", "s": 1.0}])
def test_nodes_are_deterministic_in_the_seed(dist):
    a = traffic.draw_nodes(1000, 512, dist, _rng(BIG))
    b = traffic.draw_nodes(1000, 512, dist, _rng(BIG))
    c = traffic.draw_nodes(1000, 512, dist, _rng(BIG + 1))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 1000


def test_pairs_are_deterministic_in_the_seed():
    mix = {"nodes": {"dist": "uniform"}}
    u1, v1 = traffic.pair_requests(N, 300, mix, _rng(BIG))
    u2, v2 = traffic.pair_requests(N, 300, mix, _rng(BIG))
    u3, _ = traffic.pair_requests(N, 300, mix, _rng(BIG + 1))
    assert np.array_equal(u1, u2) and np.array_equal(v1, v2)
    assert not np.array_equal(u1, u3) and not np.array_equal(u1, v1)


@pytest.mark.parametrize("drive,kind", [("run_open", "topk"),
                                        ("run_closed", "pair")])
def test_a_loop_refuses_the_kind_no_cell_drives(drive, kind):
    with pytest.raises(ValueError):
        getattr(traffic, drive)(None, {"kind": kind}, 10, 1.0, _rng(BIG), 0.0)


def test_open_schedule_is_poisson_at_the_rate():
    rate, seconds = 400.0, 30.0
    t = traffic.open_schedule(rate, seconds, _rng(BIG))
    assert np.array_equal(t, traffic.open_schedule(rate, seconds, _rng(BIG)))
    assert len(t) == rate * seconds
    assert t[0] == 0.0 and t[-1] < seconds and np.all(np.diff(t) > 0)
    gaps = np.diff(t)
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.01)
    # exponential gaps: Kolmogorov-Smirnov distance to 1 - exp(-rate x)
    x = np.sort(gaps)
    ecdf = np.arange(1, len(x) + 1) / len(x)
    ks = np.abs(ecdf - (1 - np.exp(-rate * x))).max()
    assert ks < 1.36 / math.sqrt(len(x))
    # counts per 100 ms: a Poisson's variance equals its mean
    counts = np.bincount((t / 0.1).astype(int))
    assert counts.var() / counts.mean() == pytest.approx(1.0, abs=0.25)
    # another seed: the same sizes in another order
    t2 = traffic.open_schedule(rate, seconds, _rng(BIG + 1))
    assert len(t2) == len(t) and not np.array_equal(t, t2)
    n = len(t)
    quantiles = np.sort(-np.log1p(-(np.arange(n) + 0.5) / n) / rate)
    for d in (x, np.sort(np.diff(t2))):
        near = np.clip(np.searchsorted(quantiles, d), 1, n - 1)
        gap = np.minimum(abs(quantiles[near] - d), abs(quantiles[near - 1] - d))
        assert gap.max() < 1e-9
