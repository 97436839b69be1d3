"""Paper Figure 3 + Sections 5.1/5.4: preprocessing cost.

SLING with Algorithm 1 vs Algorithm 4 d_k estimation (the paper's
adaptive-sampling claim), HP-table construction host-driven vs
device-resident (the fused propagation scan), MC and Linearize; plus
the mesh-scaling rows for the sharded build
(``--mesh S``/:func:`run_mesh`, EXPERIMENTS.md "Preprocessing
scaling") and the diagonal-path recompile gate ``run.py --smoke``
drives through :func:`mesh_subprocess`.
"""
from __future__ import annotations

import time

from benchmarks.common import cpu_mesh_subprocess, emit
from repro.baselines import linearize, montecarlo
from repro.core import diagonal, hp_index, theory, walks
from repro.graph import generators


def run(sizes=(300, 1000), eps: float = 0.2):
    for n in sizes:
        g = generators.barabasi_albert(n, 3, seed=0, directed=False)
        p = theory.plan(eps=eps, n=g.n)

        t0 = time.perf_counter()
        diagonal.estimate_diagonal(g, p, seed=0, adaptive=False)
        t_alg1 = time.perf_counter() - t0
        emit(f"fig3/preprocess/d_alg1/n={n}", 1e6 * t_alg1, "fixed budget")

        t0 = time.perf_counter()
        diagonal.estimate_diagonal(g, p, seed=0, adaptive=True)
        t_alg4 = time.perf_counter() - t0
        emit(f"fig3/preprocess/d_alg4/n={n}", 1e6 * t_alg4,
             f"adaptive;speedup={t_alg1 / max(t_alg4, 1e-9):.1f}x")

        # host-vs-device HP build: the step-driven loop (one dispatch
        # + host sync per step, early exit) vs the fused windowed
        # scan. Each variant runs once untimed so the rows compare
        # steady-state build time, not first-call XLA compilation.
        for fused in (False, True):
            hp_index.build_hp_table(g, p.theta, p.sqrt_c, p.l_max,
                                    block=256, fused=fused)
        t0 = time.perf_counter()
        hp_index.build_hp_table(g, p.theta, p.sqrt_c, p.l_max,
                                block=256, fused=False)
        t_host = time.perf_counter() - t0
        emit(f"fig3/preprocess/hp_table_host/n={n}", 1e6 * t_host,
             "step-driven, per-step sync")
        t0 = time.perf_counter()
        hp_index.build_hp_table(g, p.theta, p.sqrt_c, p.l_max,
                                block=256, fused=True)
        t_dev = time.perf_counter() - t0
        emit(f"fig3/preprocess/hp_table_device/n={n}", 1e6 * t_dev,
             f"fused scan;speedup={t_host / max(t_dev, 1e-9):.1f}x")

        t0 = time.perf_counter()
        montecarlo.build(g, eps=eps, seed=0, n_w_override=1000)
        emit(f"fig3/preprocess/mc/n={n}",
             1e6 * (time.perf_counter() - t0), "n_w=1000")

        t0 = time.perf_counter()
        linearize.build(g, R=100, seed=0)
        emit(f"fig3/preprocess/linearize/n={n}",
             1e6 * (time.perf_counter() - t0), "R=100,L=3")


# ----------------------------------------------------------------------
# builder rows: prsim hub schedule vs sling blocked schedule
# ----------------------------------------------------------------------
def run_builders(n: int = 1000, eps: float = 0.15) -> None:
    """prsim-vs-sling HP-construction wall on a power-law graph
    (schema-v2 rows; DESIGN.md section 15). Both schedules emit the
    same certified entry set -- asserted here entry for entry, so the
    wall comparison is between genuinely equivalent builds."""
    import numpy as np

    from benchmarks.common import emit_row
    from repro import prsim
    from repro.graph import stats as gstats

    g = generators.powerlaw_fast(n, k=6, seed=0)
    p = theory.plan(eps=eps, n=g.n)
    skew = gstats.measure_skew(g)
    # warm the PageRank step once so the row compares steady-state
    # schedules, not first-call XLA compilation (same idiom as the
    # fused-vs-host rows in run())
    prsim.reverse_pagerank(g, max_iters=2)
    collected = {}
    for builder in ("sling", "prsim"):
        sink = hp_index._CooSink(None, tag=f"bench_{builder}")
        t0 = time.perf_counter()
        if builder == "prsim":
            ps = prsim.build_prsim_coo(g, p, sink)
            derived = (f"hubs={ps.n_hubs} hub_mass={ps.hub_mass:.3f} "
                       f"pr_iters={ps.pr_iters}")
        else:
            hp_index.sparse_hp_coo(g, p.theta, p.sqrt_c, p.l_max,
                                   4096, sink)
            derived = f"alpha={skew.alpha} score={skew.score:.1f}"
        wall = time.perf_counter() - t0
        collected[builder] = sink.collect()
        emit_row(f"preprocess/build/builder={builder}", n=n,
                 backend="host", mesh=1, wall_us=1e6 * wall,
                 derived=derived,
                 entries=int(len(collected[builder][1])))
    def _canon(triple):
        src, key, val = triple
        order = np.lexsort((key, src))
        return src[order], key[order], val[order]

    for a, b in zip(_canon(collected["sling"]),
                    _canon(collected["prsim"])):
        assert np.array_equal(a, b), "builder entry sets diverged"


def builder_smoke(n: int = 400) -> None:
    """run.py --smoke gate: ``builder='auto'`` must pick prsim on a
    measurably skewed graph and sling on a flat one (the selection
    contract in graph/stats.py)."""
    from benchmarks.common import emit_row
    from repro.core import build

    for gen, expect in ((generators.powerlaw_fast(n, k=6, seed=0),
                         "prsim"),
                        (generators.erdos_renyi(n, 4 * n, seed=0),
                         "sling")):
        got, skew = build.resolve_builder(gen, "auto")
        emit_row(f"preprocess/builder_auto/expect={expect}", n=n,
                 backend="host", mesh=1, wall_us=float("nan"),
                 derived=f"picked={got} {skew.as_row()}")
        assert got == expect, \
            f"auto picked {got}, expected {expect}: {skew.as_row()}"
    print("BUILDER_AUTO_OK")


# ----------------------------------------------------------------------
# mesh-scaling rows + the preprocess recompile gate
# ----------------------------------------------------------------------
def run_mesh(n: int = 1000, mesh: int = 2, eps: float = 0.2,
             block: int = 128) -> None:
    """Sharded-build scaling rows at mesh sizes 1 and ``mesh``.

    Asserts (a) the sharded table equals the single-device table entry
    for entry, and (b) the diagonal walk path compiles zero new
    programs across re-estimation once the chunk buckets are primed
    -- the two acceptance gates of the parallel-preprocessing issue.
    Needs ``mesh`` devices: run as its own process so XLA_FLAGS can
    force host devices (``mesh_subprocess``).
    """
    import jax
    import jax.random as jr
    import numpy as np

    from repro.core.shard_query import serving_mesh
    if jax.device_count() < mesh:
        raise RuntimeError(
            f"--mesh {mesh} needs {mesh} devices, found "
            f"{jax.device_count()}; run via mesh_subprocess so "
            "XLA_FLAGS can force host devices")
    g = generators.barabasi_albert(n, 3, seed=0, directed=False)
    p = theory.plan(eps=eps, n=g.n)

    ref = None
    for S in sorted({1, mesh}):
        m = serving_mesh(S)
        hp_index.shard_build_hp(g, p.theta, p.sqrt_c, p.l_max, m,
                                block=block)     # compile once
        t0 = time.perf_counter()
        hp = hp_index.shard_build_hp(g, p.theta, p.sqrt_c, p.l_max, m,
                                     block=block)
        t_build = time.perf_counter() - t0
        if ref is None:
            ref = hp_index.build_hp_table(g, p.theta, p.sqrt_c, p.l_max,
                                          block=block)
        assert (np.array_equal(hp.keys, ref.keys)
                and np.array_equal(hp.vals, ref.vals)
                and np.array_equal(hp.counts, ref.counts)), \
            f"sharded build != single-device at S={S}"
        emit(f"fig3/preprocess/hp_table_sharded/mesh={S}/n={n}",
             1e6 * t_build,
             f"{int(hp.counts.sum())} entries, equivalence OK")

    # recompile gate: primed chunk buckets absorb every ragged width
    dg = walks.DeviceGraph.from_graph(g)
    walks.prime_chunk_buckets(dg, jr.PRNGKey(0), p.sqrt_c, p.t_max)
    primed = walks.compile_count()
    for seed in (1, 2):
        diagonal.estimate_diagonal(g, p, seed=seed, dg=dg)
    grew = walks.compile_count() - primed
    emit(f"fig3/preprocess/d_recompiles/n={n}", float(grew),
         "programs compiled after bucket priming (must be 0)")
    assert grew == 0, f"diagonal path recompiled: {grew} new programs"
    print("MESH_PREPROCESS_OK")


def mesh_subprocess(mesh: int = 2, n: int = 240) -> None:
    """run.py --smoke hook: 2-shard build equivalence + the diagonal
    recompile gate in a CPU child (host devices must be forced before
    the child's jax initializes)."""
    cpu_mesh_subprocess("benchmarks.bench_preprocess",
                        ["--mesh", str(mesh), "--n", str(n)],
                        "MESH_PREPROCESS_OK", "fig3/", mesh)


def _main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", type=int, default=2)
    ap.add_argument("--n", type=int, default=1000)
    ap.add_argument("--eps", type=float, default=0.2)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run_mesh(n=args.n, mesh=args.mesh, eps=args.eps)


if __name__ == "__main__":
    _main()
