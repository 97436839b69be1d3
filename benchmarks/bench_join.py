"""Bulk-join throughput: device-streamed sweep vs naive top-k loop.

Rows (EXPERIMENTS.md "Bulk joins"):

  * ``join/sweep`` -- sources/sec of the tile-streamed sweep
    (repro.join.run_join), warm device state;
  * ``join/naive_topk_loop`` -- the strawman it replaces: one
    ``QueryEngine.topk([u], k)`` dispatch per source (per-call padding
    to the engine batch + per-call host round-trip). The sweep must be
    >= 3x faster at n >= 2000 (asserted);
  * ``join/recompiles_after_first_tile`` -- the zero-recompile gate:
    every tile after the first dispatches into the already-compiled
    program (asserted, all modes);
  * ``join/sweep_mesh`` -- mesh-scaling rows via ``run_mesh`` /
    ``mesh_subprocess`` (host devices forced before jax initializes in
    the child), with an artifact-equivalence assert against the
    single-device sweep.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import (cpu_mesh_subprocess, emit, emit_row,
                               timeit)
from repro.core import build
from repro.graph import generators
from repro.join import JoinConfig, compile_count, run_join
from repro.serve import EngineConfig, QueryEngine


def run(n: int = 2000, k: int = 16, tile: int = 64,
        n_sources: int = 256, eps: float = 0.15) -> float:
    """Sweep-vs-naive throughput + the recompile gate; returns the
    speedup (asserted >= 3x at the calibrated n >= 2000)."""
    g = generators.barabasi_albert(n, 4, seed=0, directed=False)
    idx = build.build_index(g, eps=eps, seed=0)
    rng = np.random.default_rng(0)
    sources = np.sort(rng.choice(n, n_sources,
                                 replace=False)).astype(np.int32)
    cfg = JoinConfig(k=k, tile=tile)

    run_join(idx, g, sources, cfg)       # prime: compile + device upload
    c0 = compile_count()
    t_join = timeit(lambda: run_join(idx, g, sources, cfg), repeat=3)
    grew = compile_count() - c0
    emit(f"join/sweep/n={n}/k={k}/tile={tile}", t_join / n_sources,
         f"{1e6 * n_sources / t_join:.0f} sources/s")
    emit(f"join/recompiles_after_first_tile/n={n}", float(grew),
         "must be 0")
    assert grew == 0, f"join recompiled across tiles: {grew} programs"

    # Pallas-backed tile program: same artifact ids, its own compiled
    # program, still zero recompiles across tiles (the blocked layout
    # is capacity-bucketed exactly like the flat edge arrays)
    ref = run_join(idx, g, sources, cfg)
    cfg_pl = JoinConfig(k=k, tile=tile, push_backend="pallas")
    run_join(idx, g, sources, cfg_pl)    # prime the pallas tile program
    c0 = compile_count()
    t_pl = timeit(lambda: run_join(idx, g, sources, cfg_pl), repeat=3)
    grew = compile_count() - c0
    assert grew == 0, \
        f"pallas join recompiled across tiles: {grew} programs"
    knn = run_join(idx, g, sources, cfg_pl)
    assert np.array_equal(knn.nbr_ids, ref.nbr_ids), \
        "pallas sweep ids diverge from lax sweep"
    for backend, t in (("lax", t_join), ("pallas", t_pl)):
        emit_row(f"join/sweep/k={k}/tile={tile}", n=n, backend=backend,
                 mesh=1, wall_us=t / n_sources,
                 throughput=1e6 * n_sources / t,
                 derived="zero-recompile OK"
                         + (", interpret-mode" if backend == "pallas"
                            else ""))

    eng = QueryEngine(idx, g, EngineConfig(source_batch=8,
                                           k_buckets=(k,),
                                           cache_size=0))
    eng.warmup()
    t_naive = timeit(lambda: [eng.topk([u], k) for u in sources],
                     repeat=2)
    speedup = t_naive / t_join
    emit(f"join/naive_topk_loop/n={n}/k={k}", t_naive / n_sources,
         f"sweep is {speedup:.1f}x faster")
    if n >= 2000:
        assert speedup >= 3.0, \
            f"join speedup {speedup:.2f}x < 3x at n={n}"
    return speedup


# ----------------------------------------------------------------------
# mesh scaling (own process: host devices must be forced before jax
# initializes; same pattern as bench_preprocess)
# ----------------------------------------------------------------------
def run_mesh(n: int = 1000, mesh: int = 2, k: int = 16, tile: int = 64,
             eps: float = 0.2) -> None:
    import jax

    from repro.core.shard_query import serving_mesh
    if jax.device_count() < mesh:
        raise RuntimeError(
            f"--mesh {mesh} needs {mesh} devices, found "
            f"{jax.device_count()}; run via mesh_subprocess so "
            "XLA_FLAGS can force host devices")
    g = generators.barabasi_albert(n, 4, seed=0, directed=False)
    idx = build.build_index(g, eps=eps, seed=0)
    ref = run_join(idx, g, config=JoinConfig(k=k, tile=tile))
    for S in sorted({1, mesh}):
        cfg = JoinConfig(k=k, tile=tile, mesh=serving_mesh(S))
        run_join(idx, g, config=cfg)     # prime compile + shard upload
        c0 = compile_count()
        t0 = time.perf_counter()
        knn = run_join(idx, g, config=cfg)
        dt = time.perf_counter() - t0
        assert compile_count() == c0, "mesh sweep recompiled across tiles"
        np.testing.assert_array_equal(knn.indptr, ref.indptr)
        np.testing.assert_allclose(knn.nbr_scores, ref.nbr_scores,
                                   atol=1e-5)
        emit(f"join/sweep_mesh/mesh={S}/n={n}/k={k}", 1e6 * dt / n,
             f"{n / dt:.0f} sources/s, equivalence OK")
    print("JOIN_MESH_OK")


def mesh_subprocess(mesh: int = 2, n: int = 500) -> None:
    """run.py --smoke hook: sharded sweep equivalence + recompile gate
    in a CPU child with forced host devices."""
    cpu_mesh_subprocess("benchmarks.bench_join",
                        ["--mesh", str(mesh), "--n", str(n)],
                        "JOIN_MESH_OK", "join/", mesh)


def _main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", type=int, default=2)
    ap.add_argument("--n", type=int, default=1000)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run_mesh(n=args.n, mesh=args.mesh)


if __name__ == "__main__":
    _main()
