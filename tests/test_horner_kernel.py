"""Edge-case + property wall for the fused Pallas Horner-push kernel.

Everything runs the kernel in interpret mode (CPU CI); the comparisons
triangulate three implementations so layout bugs and kernel bugs are
distinguishable (kernels/horner_push/ref.py):

  * ``horner_push_pallas``    -- the kernel under test (blocked edges);
  * ``horner_push_blocked_ref`` -- float64 NumPy mirror of the blocked
    layout (same reduction structure, no Pallas);
  * ``single_source.horner_push`` -- the lax reference over the *flat*
    edge list (different layout entirely).

The randomized sweep (tests/prop.py forall, the offline stand-in for
hypothesis) drives graph shape AND kernel geometry: node-block height
``bn``, edge-chunk width ``eb``, query-block width ``bq``, with n not
a multiple of bn and B not a multiple of bq most of the time.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from prop import forall

from repro import compat
from repro.core import build
from repro.core.hp_index import INT32_PAD_KEY
from repro.core.single_source import horner_push
from repro.graph import generators
from repro.kernels.horner_push import (resolve_push_backend,
                                       use_push_backend)
from repro.kernels.horner_push import ops as hp_ops
from repro.kernels.horner_push import ref as hp_ref

pytestmark = pytest.mark.pallas

ATOL = 2e-5   # float32 kernel vs float64 references


# ----------------------------------------------------------------------
# case construction: raw packed rows + raw edges, no index build needed
# ----------------------------------------------------------------------
def _rand_case(rng, *, n, B, W, l_max, m, tau=1e-4, pad_frac=0.3):
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = rng.uniform(0.05, 0.6, m).astype(np.float32)
    ku = (rng.integers(0, l_max + 1, (B, W)) * n
          + rng.integers(0, n, (B, W))).astype(np.int32)
    ku[rng.random((B, W)) < pad_frac] = INT32_PAD_KEY
    xu = rng.uniform(0.01, 1.0, (B, W)).astype(np.float32)
    d = rng.uniform(0.3, 1.0, n).astype(np.float32)
    return dict(src=src, dst=dst, w=w, ku=ku, xu=xu, d=d,
                tau=np.float32(tau))


def _run_all(case, *, n, l_max, bn, eb, bq=8):
    """(pallas, blocked float64 ref, flat lax ref) for one case."""
    bs, bdl, bw = hp_ops.block_align_edges(
        case["src"], case["dst"], case["w"], n, bn=bn, eb=eb)
    got = np.asarray(hp_ops.horner_push_pallas(
        jnp.asarray(case["ku"]), jnp.asarray(case["xu"]),
        jnp.asarray(case["d"]), jnp.asarray(bs), jnp.asarray(bdl),
        jnp.asarray(bw), jnp.float32(case["tau"]),
        n=n, l_max=l_max, bn=bn, eb=eb, bq=bq, interpret=True))
    ref = hp_ref.horner_push_blocked_ref(
        case["ku"], case["xu"], case["d"], bs, bdl, bw, case["tau"],
        n=n, l_max=l_max, bn=bn)
    lax = np.asarray(horner_push(
        jnp.asarray(case["ku"]), jnp.asarray(case["xu"]),
        jnp.asarray(case["d"]), jnp.asarray(case["src"]),
        jnp.asarray(case["dst"]), jnp.asarray(case["w"]),
        jnp.float32(case["tau"]), n=n, l_max=l_max))
    return got, ref, lax


def _assert_agree(got, ref, lax):
    assert got.shape == ref.shape == lax.shape
    assert np.abs(got - ref).max() <= ATOL
    assert np.abs(got - lax).max() <= ATOL


# ----------------------------------------------------------------------
# randomized graph x geometry sweep
# ----------------------------------------------------------------------
def _sweep_case(rng, i):
    n = int(rng.integers(1, 40)) + i          # sizes ramp up with i
    geom = dict(n=n,
                l_max=int(rng.integers(0, 5)),
                bn=int(rng.choice([1, 2, 3, 8])),
                eb=int(rng.choice([8, 16, 128])),
                bq=int(rng.choice([1, 3, 8])))
    case = _rand_case(rng, n=n,
                      B=int(rng.integers(1, 10)),
                      W=int(rng.integers(1, 7)),
                      l_max=geom["l_max"],
                      m=int(rng.integers(0, 3 * n + 1)),
                      tau=float(rng.choice([0.0, 1e-4, 5e-2])))
    return {"case": case, **geom}


@forall(_sweep_case, n=20)
def test_property_random_graph_and_geometry(case, n, l_max, bn, eb, bq):
    _assert_agree(*_run_all(case, n=n, l_max=l_max, bn=bn, eb=eb, bq=bq))


# ----------------------------------------------------------------------
# named edge cases
# ----------------------------------------------------------------------
def test_batch_of_one():
    rng = np.random.default_rng(0)
    case = _rand_case(rng, n=17, B=1, W=4, l_max=3, m=40)
    _assert_agree(*_run_all(case, n=17, l_max=3, bn=8, eb=16))


def test_max_bucket_batch_and_padded_batch():
    rng = np.random.default_rng(1)
    # a full capacity bucket (B a multiple of bq) ...
    case = _rand_case(rng, n=23, B=32, W=3, l_max=2, m=60)
    _assert_agree(*_run_all(case, n=23, l_max=2, bn=8, eb=16, bq=8))
    # ... and a ragged one (B % bq != 0: pad columns must stay inert)
    case = _rand_case(rng, n=23, B=9, W=3, l_max=2, m=60)
    _assert_agree(*_run_all(case, n=23, l_max=2, bn=8, eb=16, bq=8))


def test_n_not_multiple_of_node_block():
    rng = np.random.default_rng(2)
    case = _rand_case(rng, n=13, B=4, W=4, l_max=3, m=30)
    got, ref, lax = _run_all(case, n=13, l_max=3, bn=8, eb=8)
    _assert_agree(got, ref, lax)
    assert got.shape == (4, 13)   # kernel padding rows never leak out


def test_empty_frontier_after_tau_prune():
    """tau above every score: pushes transport nothing, so the answer
    degenerates to the level-0 seed alone."""
    rng = np.random.default_rng(3)
    case = _rand_case(rng, n=11, B=3, W=4, l_max=4, m=40, tau=1e9)
    got, ref, lax = _run_all(case, n=11, l_max=4, bn=8, eb=8)
    _assert_agree(got, ref, lax)
    seed0 = np.zeros((3, 11))
    ls = np.where(case["ku"] == INT32_PAD_KEY, -1, case["ku"] // 11)
    ks = np.clip(case["ku"] % 11, 0, 10)
    for b in range(3):
        sel = np.where(ls[b] == 0, case["xu"][b] * case["d"][ks[b]], 0.0)
        np.add.at(seed0[b], ks[b], sel)
    assert np.abs(got - seed0).max() <= ATOL


def test_all_pad_rows_produce_zeros():
    rng = np.random.default_rng(4)
    case = _rand_case(rng, n=19, B=5, W=4, l_max=3, m=50)
    case["ku"][:] = INT32_PAD_KEY
    got, ref, lax = _run_all(case, n=19, l_max=3, bn=8, eb=8)
    _assert_agree(got, ref, lax)
    assert np.all(got == 0.0)


def test_duplicate_keys_accumulate():
    """The same (l, k) key twice in one packed row must contribute both
    entries to the in-kernel seed (the masked one-hot sum is additive
    by construction; this pins it)."""
    n, k_tgt = 9, 5
    case = dict(src=np.zeros(0, np.int32), dst=np.zeros(0, np.int32),
                w=np.zeros(0, np.float32),
                ku=np.full((1, 2), 0 * n + k_tgt, np.int32),
                xu=np.float32([[0.25, 0.125]]),
                d=np.linspace(0.5, 1.0, n).astype(np.float32),
                tau=np.float32(0.0))
    got, ref, lax = _run_all(case, n=n, l_max=0, bn=4, eb=8)
    _assert_agree(got, ref, lax)
    assert got[0, k_tgt] == pytest.approx(0.375 * float(case["d"][k_tgt]),
                                          abs=1e-6)


def test_tau_zero_keeps_all_positive_mass():
    rng = np.random.default_rng(5)
    case = _rand_case(rng, n=21, B=4, W=5, l_max=3, m=70, tau=0.0)
    _assert_agree(*_run_all(case, n=21, l_max=3, bn=8, eb=16))


# ----------------------------------------------------------------------
# layout builder properties
# ----------------------------------------------------------------------
def _layout_case(rng, i):
    n = int(rng.integers(1, 30)) + i
    return dict(n=n, m=int(rng.integers(0, 4 * n)),
                bn=int(rng.choice([1, 3, 8])),
                eb=int(rng.choice([4, 8, 128])),
                floor=int(rng.choice([0, 5, 64])),
                seed=int(rng.integers(0, 2**31)))


@forall(_layout_case, n=20)
def test_block_align_edges_is_a_permutation(n, m, bn, eb, floor, seed):
    """Every input edge lands exactly once, in the block row owning its
    destination; pads are inert; the width is an eb multiple >= floor."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, 1000, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    w = rng.uniform(0.1, 1.0, m).astype(np.float32)
    bs, bdl, bw = hp_ops.block_align_edges(src, dst, w, n, bn=bn, eb=eb,
                                           width_floor=floor)
    nb, width = bs.shape
    assert nb == max(1, -(-n // bn)) and width % eb == 0
    assert width >= min(floor, width) and (floor == 0 or width >= floor)
    live = bdl >= 0
    assert int(live.sum()) == m
    assert np.all(bw[~live] == 0.0)
    blk_rows = np.nonzero(live)[0]
    got = sorted(zip((blk_rows * bn + bdl[live]).tolist(),
                     bs[live].tolist(), bw[live].tolist()))
    want = sorted(zip(dst.tolist(), src.tolist(), w.tolist()))
    assert got == want


# ----------------------------------------------------------------------
# regression: the deprecated jax.ops.segment_sum is gone from the hot
# paths; compat.segment_sum is the pinned lax-based fallback
# ----------------------------------------------------------------------
def test_segment_sum_lax_fallback(monkeypatch):
    def _boom(*a, **k):
        raise AssertionError("deprecated jax.ops.segment_sum was called")

    if hasattr(jax, "ops") and hasattr(jax.ops, "segment_sum"):
        monkeypatch.setattr(jax.ops, "segment_sum", _boom)
    rng = np.random.default_rng(6)
    data = rng.uniform(-1, 1, 50).astype(np.float32)
    ids = rng.integers(0, 12, 50).astype(np.int32)
    ids[::7] = 12 + (ids[::7] % 3)       # out-of-range: must be dropped
    got = np.asarray(compat.segment_sum(jnp.asarray(data),
                                        jnp.asarray(ids),
                                        num_segments=12))
    want = np.zeros(12, np.float32)
    keep = ids < 12
    np.add.at(want, ids[keep], data[keep])
    np.testing.assert_allclose(got, want, atol=1e-6)
    # and the lax push path itself retraces cleanly with the shim only
    case = _rand_case(rng, n=15, B=2, W=3, l_max=2, m=25)
    out = horner_push(
        jnp.asarray(case["ku"]), jnp.asarray(case["xu"]),
        jnp.asarray(case["d"]), jnp.asarray(case["src"]),
        jnp.asarray(case["dst"]), jnp.asarray(case["w"]),
        jnp.float32(case["tau"]), n=15, l_max=2)
    assert np.asarray(out).shape == (2, 15)


# ----------------------------------------------------------------------
# backend switch plumbing
# ----------------------------------------------------------------------
def test_backend_switch_resolution():
    with use_push_backend("pallas"):
        assert resolve_push_backend(None) == "pallas"
        assert resolve_push_backend("lax") == "lax"
    with use_push_backend("lax"):
        assert resolve_push_backend(None) == "lax"
    # "auto" never picks the fused kernel: it does not compile for TPU
    with use_push_backend("auto"):
        assert resolve_push_backend(None) == "lax"
        assert resolve_push_backend("auto") == "lax"
    with pytest.raises(ValueError):
        resolve_push_backend("bogus")
    with pytest.raises(ValueError):
        use_push_backend("bogus").__enter__()


# ----------------------------------------------------------------------
# serving-engine composition: equivalence + zero-recompile discipline
# ----------------------------------------------------------------------
def test_engine_pallas_backend_equivalence_and_swap_stability():
    from repro.serve import EngineConfig, QueryEngine
    g = generators.barabasi_albert(150, 3, seed=0, directed=False)
    idx = build.build_index(g, eps=0.2, seed=0)
    qs = np.arange(12, dtype=np.int32) * 11 % g.n
    eng_l = QueryEngine(idx, g, EngineConfig(source_batch=8,
                                             cache_size=0,
                                             push_backend="lax"))
    eng_p = QueryEngine(idx, g, EngineConfig(source_batch=8,
                                             cache_size=0,
                                             push_backend="pallas"))
    assert eng_p.stats()["push_backend"] == "pallas"
    eng_l.warmup()
    eng_p.warmup()
    out_l = eng_l.single_source(qs)
    out_p = eng_p.single_source(qs)
    assert np.abs(out_p - out_l).max() <= 1e-5
    vl, il = eng_l.topk(qs, 10)
    vp, ip = eng_p.topk(qs, 10)
    assert np.array_equal(il, ip)
    np.testing.assert_allclose(vp, vl, atol=1e-5)
    # steady-state traffic compiles nothing new ...
    shapes0 = len(eng_p.stats()["unique_shapes"])
    eng_p.single_source(qs)
    eng_p.topk(qs, 10)
    assert len(eng_p.stats()["unique_shapes"]) == shapes0
    # ... and a same-shape hot swap stays inside the capacity buckets
    report = eng_p.swap_index(idx, g)
    assert report["recompiles"] == 0
    out_p2 = eng_p.single_source(qs)
    assert np.abs(out_p2 - out_l).max() <= 1e-5
    assert len(eng_p.stats()["unique_shapes"]) == shapes0


# ----------------------------------------------------------------------
# sharded composition at real shard counts (ci.sh mesh suite)
# ----------------------------------------------------------------------
@pytest.mark.mesh
@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_pallas_matches_lax_across_shards(n_shards):
    from repro.core import shard_query
    if jax.device_count() < n_shards:
        pytest.skip(f"needs {n_shards} devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
    g = generators.barabasi_albert(122, 3, seed=0, directed=False)
    idx = build.build_index(g, eps=0.2, seed=0)
    mesh = shard_query.serving_mesh(n_shards)
    si_l = shard_query.shard_index(idx, g, mesh, push_backend="lax")
    si_p = shard_query.shard_index(idx, g, mesh, push_backend="pallas")
    us = np.array([0, 7, g.n - 1], np.int32)
    out_l = shard_query.sharded_single_source(si_l, us, backend="lax")
    out_p = shard_query.sharded_single_source(si_p, us, backend="pallas")
    assert np.abs(out_p - out_l).max() <= 1e-5
    vl, il = shard_query.sharded_topk(si_l, us, 10, backend="lax")
    vp, ip = shard_query.sharded_topk(si_p, us, 10, backend="pallas")
    assert np.array_equal(il, ip)
    np.testing.assert_allclose(vp, vl, atol=1e-5)
    # explicit-pallas on a lax-only ShardedIndex must refuse, not fall
    # back silently
    with pytest.raises(ValueError):
        shard_query.sharded_single_source(si_l, us, backend="pallas")
