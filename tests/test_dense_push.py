"""The dense Horner push (core/single_source.horner_push_dense).

The dense body multiplies the pruned frontier, split into three
bfloat16 parts, by a bfloat16 edge-count operator with float32
accumulation: every product is exact, so it must agree with the
float64 Horner push to float32 rounding, and with the per-edge scatter
to summation order. A single bfloat16 pass, built here as the control,
must not.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oracle

from repro.core import build, single_source
from repro.core.device_state import serving_arrays
from repro.core.single_source import (batched_single_source,
                                      batched_single_source_dense,
                                      dense_push_operator, dense_side,
                                      max_edge_multiplicity,
                                      single_source_horner, split_bf16x3)
from repro.core.topk import batched_topk, batched_topk_dense
from repro.graph import csr

# agreement asked of the dense push, relative to the largest score
REL = 1e-6
SETTINGS = [(0.4, 0.15), (0.6, 0.1), (0.8, 0.2)]


def _zoo() -> dict[str, csr.Graph]:
    zoo = oracle.cases()
    # votes into a few candidates: most nodes have no in-edges
    rng = np.random.default_rng(11)
    src = rng.integers(0, 60, 240)
    dst = rng.integers(0, 12, 240)
    keep = src != dst
    zoo["no_in_edges"] = csr.from_edges(60, src[keep], dst[keep])
    return zoo


ZOO = _zoo()
_cache: dict = {}


def _cell(name: str, c: float, eps: float):
    key = (name, c, eps)
    if key not in _cache:
        g = ZOO[name]
        idx = build.build_index(g, eps=eps, c=c, exact_d=True, seed=0)
        st = serving_arrays(idx, g)
        adjT, s = dense_push_operator(g, idx.plan.sqrt_c, dense_side(g.n))
        _cache[key] = (g, idx, st, jnp.asarray(adjT), jnp.asarray(s))
    return _cache[key]


def _sources(g):
    return np.unique(np.array([0, 1, g.n // 3, g.n // 2, g.n - 1],
                              np.int32))


def _dense(idx, st, adjT, s, us):
    return np.asarray(batched_single_source_dense(
        st.keys, st.vals, st.d, adjT, s, jnp.asarray(us),
        jnp.float32(st.tau), n=idx.n, l_max=idx.plan.l_max))


def _rel(a, b, scale):
    return float(np.abs(np.asarray(a, np.float64) - b).max()) / scale


@pytest.mark.parametrize("c,eps", SETTINGS)
@pytest.mark.parametrize("name", sorted(ZOO))
def test_dense_single_source_matches_float64_and_scatter(name, c, eps):
    g, idx, st, adjT, s = _cell(name, c, eps)
    us = _sources(g)
    ref = np.stack([single_source_horner(idx, g, int(u)) for u in us])
    scale = float(np.abs(ref).max())
    got = _dense(idx, st, adjT, s, us)
    sparse = np.asarray(batched_single_source(
        st.keys, st.vals, st.d, st.edge_src, st.edge_dst, st.w,
        jnp.asarray(us), jnp.float32(st.tau), n=idx.n,
        l_max=idx.plan.l_max))
    assert got.shape == (len(us), g.n) and got.dtype == np.float32
    assert _rel(got, ref, scale) <= REL
    assert _rel(got, sparse.astype(np.float64), scale) <= REL


@pytest.mark.parametrize("c,eps", SETTINGS)
@pytest.mark.parametrize("name", sorted(ZOO))
def test_dense_topk_ids_match_scatter_up_to_near_ties(name, c, eps):
    g, idx, st, adjT, s = _cell(name, c, eps)
    us = jnp.asarray(_sources(g))
    k = min(16, g.n)
    tau = jnp.float32(st.tau)
    dv, di = batched_topk_dense(st.keys, st.vals, st.d, adjT, s, us, tau,
                                n=idx.n, l_max=idx.plan.l_max, k=k)
    sv, si = batched_topk(st.keys, st.vals, st.d, st.edge_src, st.edge_dst,
                          st.w, us, tau, n=idx.n, l_max=idx.plan.l_max, k=k)
    dv, di, sv, si = map(np.asarray, (dv, di, sv, si))
    scale = float(np.abs(sv).max())
    assert np.abs(dv - sv).max() <= REL * scale
    full = _dense(idx, st, adjT, s, np.asarray(us))
    for row in range(len(us)):
        for j in np.flatnonzero(di[row] != si[row]):
            # a swap only between scores a float32 rounding apart
            a, b = full[row, di[row, j]], full[row, si[row, j]]
            assert abs(a - b) <= REL * scale, (name, row, j, a, b)
    # ties go to the smaller id, as jax.lax.top_k breaks them
    for row in range(len(us)):
        same = np.flatnonzero(np.diff(dv[row]) == 0)
        assert np.all(di[row, same] < di[row, same + 1])


@pytest.mark.parametrize("c,eps", SETTINGS)
def test_one_bfloat16_pass_fails_the_tolerance(monkeypatch, c, eps):
    """The control: the frontier cast to bfloat16 once, in place of the
    three-way split, lands far outside REL; the split is what keeps
    the push a float32 one."""
    g, idx, st, adjT, s = _cell("powerlaw", c, eps)
    us = _sources(g)
    ref = np.stack([single_source_horner(idx, g, int(u)) for u in us])

    def one_pass(x):
        hi = x.astype(jnp.bfloat16)
        return jnp.concatenate([hi, jnp.zeros_like(hi), jnp.zeros_like(hi)])

    monkeypatch.setattr(single_source, "split_bf16x3", one_pass)
    ku, xu = st.keys[jnp.asarray(us)], st.vals[jnp.asarray(us)]
    got = jax.jit(lambda a, b: single_source.horner_push_dense(
        a, b, st.d, adjT, s, jnp.float32(st.tau), n=idx.n,
        l_max=idx.plan.l_max))(ku, xu)
    assert _rel(got, ref, float(np.abs(ref).max())) > 10 * REL


def test_three_parts_sum_to_the_float32_value():
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.random((4, 256)), 1e-9 * rng.random((4, 256)),
                        rng.standard_normal((4, 256))]).astype(np.float32)
    parts = np.asarray(split_bf16x3(jnp.asarray(x))).astype(np.float32)
    b = len(x)
    assert parts.shape == (3 * b, 256)
    assert np.array_equal(parts[:b] + parts[b:2 * b] + parts[2 * b:], x)
    # each part is a bfloat16 value, the next one at most 2^-8 of it
    assert np.all(np.abs(parts[b:2 * b]) <= 2.0 ** -8 * np.abs(parts[:b]))


def test_operator_counts_parallel_edges_and_scales_by_in_degree():
    src = np.array([0, 0, 0, 1, 2, 2], np.int32)
    dst = np.array([1, 1, 2, 2, 1, 0], np.int32)
    g = csr.from_edges(4, src, dst, dedup=False)
    adjT, s = dense_push_operator(g, 0.5, dense_side(g.n))
    assert adjT.shape == (128, 128) and adjT.dtype == jnp.bfloat16
    want = np.zeros((128, 128))
    np.add.at(want, (src, dst), 1)
    assert np.array_equal(adjT.astype(np.float64), want)
    np.testing.assert_array_equal(s[:4], np.float32([0.5, 0.5 / 3, 0.25, 0]))
    assert not s[4:].any()
    assert max_edge_multiplicity(g) == 2


def test_operator_refuses_counts_past_bfloat16():
    g = csr.from_edges(3, np.zeros(257, np.int32), np.ones(257, np.int32),
                       dedup=False)
    assert max_edge_multiplicity(g) == 257
    with pytest.raises(ValueError, match="bfloat16"):
        dense_push_operator(g, 0.5, dense_side(g.n))


def test_crossover_script_runs_small(tmp_path):
    """scripts/push_crossover.py end to end at a small size on the CPU:
    both bodies timed at each edge count, and the accuracy of the dense
    push, the scatter and the single-pass control against float64."""
    import importlib.util
    import json
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "scripts"
            / "push_crossover.py")
    spec = importlib.util.spec_from_file_location("push_crossover", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = tmp_path / "crossover.json"
    assert mod.main(["--nodes", "300", "--edges", "1500", "800",
                     "--reps", "1", "--out", str(out)]) == 0
    got = json.loads(out.read_text())
    assert got["n_pad"] == 384 and len(got["shapes"]) == 2
    first = got["shapes"][0]
    assert first["rel_err_dense"] <= REL and first["rel_err_sparse"] <= REL
    assert first["rel_err_one_bf16_pass"] > 10 * REL
    assert all(r["sparse_ms"] > 0 and r["dense_ms"] > 0 for r in got["shapes"])
