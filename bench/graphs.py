"""Chung-Lu directed graphs with power-law in- and out-degree tails.

A configuration names the published graph's exact node and edge counts,
how many of its nodes receive edges (``in_nodes``, all ``n`` where the
key is absent) and the tail exponents of its in- and out-degrees.
Sources are drawn from out-weights over all nodes, destinations from
in-weights over the ``in_nodes`` receiving nodes; self-loops and
duplicate edges are dropped and the draw is topped up until the graph
has exactly ``m`` distinct edges. Weights follow the rank law
w_r = (r + r0) ** (-1 / (gamma - 1)), with r0 set so that the largest
expected degree is the configuration's ``max_*_degree``; a random
permutation from the seed assigns ranks to node ids, separately for
the two directions.

The edges are drawn once, from the configuration's ``structure_seed``:
the graph is the deployment's, like its sizes, and every run serves the
same one, in the same node order, so that every seed does the same
device work. A run's ``--seed`` draws its requests and the build's
random walks.
"""
from __future__ import annotations

import numpy as np


def rank_weights(n: int, m: int, gamma: float, max_degree: float) -> np.ndarray:
    """Weights over ranks 1..n whose largest expected degree is
    ``max_degree`` out of ``m`` draws (bisection on the offset r0)."""
    if gamma <= 1:
        raise ValueError("gamma must be > 1")
    if not 0 < max_degree <= m:
        raise ValueError("max_degree must be in (0, m]")
    r = np.arange(1, n + 1, dtype=np.float64)
    a = 1.0 / (gamma - 1.0)

    def top_degree(r0: float) -> float:
        w = (r + r0) ** -a
        return m * w[0] / w.sum()

    lo, hi = 0.0, float(n)
    if top_degree(lo) <= max_degree:
        return (r + lo) ** -a
    for _ in range(100):
        mid = 0.5 * (lo + hi)
        if top_degree(mid) > max_degree:
            lo = mid
        else:
            hi = mid
    return (r + hi) ** -a


def chung_lu_edges(n: int, m: int, gamma_in: float, gamma_out: float,
                   max_in_degree: float, max_out_degree: float,
                   rng: np.random.Generator,
                   in_nodes: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """``m`` distinct directed edges (src, dst) without self-loops,
    whose destinations are ``in_nodes`` of the ``n`` nodes."""
    in_nodes = n if in_nodes is None else int(in_nodes)
    if not 1 < in_nodes <= n or m > in_nodes * (n - 1):
        raise ValueError("more edges than a simple digraph holds")
    perm_out = rng.permutation(n)
    perm_in = rng.permutation(n)
    cdf_out = np.cumsum(rank_weights(n, m, gamma_out, max_out_degree))
    cdf_in = np.cumsum(rank_weights(in_nodes, m, gamma_in, max_in_degree))
    cdf_out /= cdf_out[-1]
    cdf_in /= cdf_in[-1]

    def draw(cdf, perm, size):
        r = np.searchsorted(cdf, rng.random(size), side="right")
        return perm[np.minimum(r, len(cdf) - 1)].astype(np.int64)

    keys = np.empty(0, np.int64)
    while len(keys) < m:
        size = int(1.1 * (m - len(keys))) + 64
        s = draw(cdf_out, perm_out, size)
        d = draw(cdf_in, perm_in, size)
        keys = np.concatenate([keys, (s * n + d)[s != d]])
        # keep the first draw of each edge, in draw order
        _, first = np.unique(keys, return_index=True)
        keys = keys[np.sort(first)]
    keys = keys[:m]
    return keys // n, keys % n


def make_edges(spec: dict) -> tuple[np.ndarray, np.ndarray]:
    """The configuration's edge list (``spec`` is its ``graph`` entry)."""
    rng = np.random.default_rng([int(spec["structure_seed"]), 0])
    return chung_lu_edges(spec["n"], spec["m"], spec["gamma_in"],
                          spec["gamma_out"], spec["max_in_degree"],
                          spec["max_out_degree"], rng, spec.get("in_nodes"))
