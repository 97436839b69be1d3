"""Paper Figure 2: average single-source query cost.

SLING Algorithm 6 (paper), the beyond-paper Horner push, the naive
n x Alg-3 strawman, the batched device path, and Linearize.

``python -m benchmarks.bench_single_source --mesh S`` adds the scaling
rows (EXPERIMENTS.md section Scaling): the node-sharded engine's
batched multi-source throughput at mesh sizes 1 and S, equivalence
against the single-device answer, and a zero-recompile assertion
across the micro-batches. Run as its own process -- the S host devices
must be forced before jax initializes (done here when XLA_FLAGS is
unset); ``run.py --smoke`` drives the 2-shard check through
``mesh_subprocess``.
"""
from __future__ import annotations

import os

import numpy as np

from benchmarks.common import (cpu_mesh_subprocess, emit, emit_row,
                               timeit)
from repro.baselines import linearize
from repro.core import build
from repro.core.single_source import (single_source_device,
                                      single_source_horner,
                                      single_source_naive,
                                      single_source_paper)
from repro.graph import generators


def run(sizes=(300, 1000, 3000), eps: float = 0.15, n_q: int = 5):
    for n in sizes:
        g = generators.barabasi_albert(n, 3, seed=0, directed=False)
        idx = build.build_index(g, eps=eps, seed=0)
        rng = np.random.default_rng(0)
        qs = rng.integers(0, g.n, n_q)

        t = timeit(lambda: [single_source_paper(idx, g, int(u))
                            for u in qs])
        emit(f"fig2/single_source/sling_alg6/n={n}", t / n_q, "paper")
        t = timeit(lambda: [single_source_horner(idx, g, int(u))
                            for u in qs])
        emit(f"fig2/single_source/sling_horner/n={n}", t / n_q,
             "beyond-paper O(L m)")
        batch = qs.astype(np.int32)
        single_source_device(idx, g, batch)
        t = timeit(lambda: single_source_device(idx, g, batch))
        emit(f"fig2/single_source/sling_device_batched/n={n}", t / n_q,
             "amortized")
        # serving path: same push, but through the engine's fixed-shape
        # dispatch (pad + chunk) -- measures the serving overhead
        from repro.serve import EngineConfig, QueryEngine
        eng = QueryEngine(idx, g, EngineConfig(source_batch=len(batch),
                                               cache_size=0))
        eng.warmup()
        t = timeit(lambda: eng.single_source(batch))
        emit(f"fig2/single_source/sling_engine/n={n}", t / n_q,
             "QueryEngine")
        if n <= 300:
            t = timeit(lambda: single_source_naive(idx, g, int(qs[0])),
                       repeat=1)
            emit(f"fig2/single_source/sling_naive_nxalg3/n={n}", t,
                 "strawman")
        lin = linearize.build(g, R=100, seed=0)
        t = timeit(lambda: [linearize.query_single_source(lin, g, int(u))
                            for u in qs])
        emit(f"fig2/single_source/linearize/n={n}", t / n_q, "")


# ----------------------------------------------------------------------
# push-backend rows: lax reference vs fused Pallas kernel
# ----------------------------------------------------------------------
def run_backends(n: int = 300, eps: float = 0.15, n_q: int = 16,
                 op_count_n: int = 10_000) -> None:
    """lax-vs-pallas rows for the batched single-source push.

    Wall-time rows are honest but weak evidence on CPU (the Pallas
    kernel runs in interpret mode there), so the backend gate is the
    trace-only op count at ``op_count_n``: the number of
    frontier-sized HBM materializations per compiled program
    (``count_hbm_intermediates``), asserted pallas <= lax. Equivalence
    of the two backends' answers is asserted on the real ``n`` run.
    """
    g = generators.barabasi_albert(n, 3, seed=0, directed=False)
    idx = build.build_index(g, eps=eps, seed=0)
    rng = np.random.default_rng(0)
    qs = rng.integers(0, g.n, n_q).astype(np.int32)
    got = {}
    for backend in ("lax", "pallas"):
        single_source_device(idx, g, qs, backend=backend)  # prime
        t = timeit(lambda b=backend: single_source_device(idx, g, qs,
                                                          backend=b))
        got[backend] = single_source_device(idx, g, qs, backend=backend)
        emit_row("fig2/single_source/push", n=n, backend=backend,
                 mesh=1, wall_us=t / n_q, throughput=n_q / (t * 1e-6),
                 derived="interpret-mode" if backend == "pallas" else "")
    err = float(np.abs(got["pallas"] - got["lax"]).max())
    assert err < 1e-5, f"pallas != lax single-source: {err}"
    emit(f"fig2/single_source/backend_equivalence/n={n}", err,
         "max |pallas - lax|, must be < 1e-5")
    op_count_gate(n=op_count_n)


def op_count_gate(n: int = 10_000) -> None:
    """Trace-only fusion gate at production-ish n (no graph is built --
    the programs are traced on ShapeDtypeStructs, so this is cheap even
    at n = 10^4). The measurement and the budgets live in the
    ``hbm-budget`` analysis pass (repro.analysis.jaxpr_passes); this
    hook only renders its rows and asserts them -- one budget
    definition, two consumers (DESIGN.md section 14)."""
    from repro.analysis import jaxpr_passes
    from repro.kernels.horner_push import ops as hp_ops

    rows = jaxpr_passes.hbm_budget_report(n=n)
    by = {(r.program, r.backend): r for r in rows}
    for r in rows:
        if r.program != "source":
            continue
        emit_row("fig2/single_source/hbm_ops", n=n, backend=r.backend,
                 mesh=1, wall_us=float("nan"), throughput=None,
                 ops=r.measured, model_bytes=r.model_bytes,
                 derived=f"{r.measured} frontier-sized ops "
                         "(trace-only)")
    for r in rows:
        assert not r.over, (
            f"{r.program}/{r.backend} materializes {r.measured} "
            f"frontier-sized HBM intermediates, over budget {r.budget}")
    for prog in ("source", "topk"):
        c_pl, c_lax = by[(prog, "pallas")], by[(prog, "lax")]
        assert c_pl.measured <= c_lax.measured, \
            f"{prog}: pallas materializes more HBM intermediates: " \
            f"{c_pl.measured} > {c_lax.measured}"
    geo = jaxpr_passes.HBM_GEOMETRY
    m = geo["deg"] * n
    bn, eb = hp_ops.DEFAULT_BN, hp_ops.DEFAULT_EB
    nb = -(-n // bn)
    ep = max(eb, -(-((m + nb - 1) // nb) // eb) * eb)
    cost = hp_ops.push_cost_model(n, m, geo["B"], ep, geo["l_max"],
                                  bn=bn, eb=eb)
    from benchmarks import roofline
    roofline.push_sanity(cost, n=n)


# ----------------------------------------------------------------------
# scaling rows: node-sharded serving over a device mesh
# ----------------------------------------------------------------------
def run_mesh(n: int = 1000, mesh: int = 4, eps: float = 0.15,
             n_q: int = 32, batch: int = 8) -> None:
    """Batched multi-source throughput on the node-sharded engine.

    Emits one row per mesh size in (1, mesh); asserts the sharded
    answers match the single-device engine and that the micro-batch
    stream compiles zero new programs after warmup.
    """
    import jax

    from repro.core import shard_query
    from repro.serve import EngineConfig, QueryEngine
    if jax.device_count() < mesh:
        raise RuntimeError(
            f"--mesh {mesh} needs {mesh} devices, found "
            f"{jax.device_count()}; run as its own process so "
            "XLA_FLAGS can force host devices")
    g = generators.barabasi_albert(n, 3, seed=0, directed=False)
    idx = build.build_index(g, eps=eps, seed=0)
    rng = np.random.default_rng(0)
    qs = rng.integers(0, g.n, n_q).astype(np.int32)
    ref = None
    for S in sorted({1, mesh}):
        m = shard_query.serving_mesh(S) if S > 1 else None
        eng = QueryEngine(idx, g, EngineConfig(source_batch=batch,
                                               cache_size=0, mesh=m))
        eng.warmup()
        shapes0 = len(eng.stats()["unique_shapes"])
        got = eng.single_source(qs)           # the measured micro-batch
        if ref is None:
            ref = got
        else:
            err = np.abs(got - ref).max()
            assert err < 1e-4, f"sharded != single-device: {err}"
        t_us = timeit(lambda: eng.single_source(qs))   # us per stream
        grew = len(eng.stats()["unique_shapes"]) - shapes0
        assert grew == 0, f"micro-batch recompiled: {grew} new shapes"
        qps = n_q / (t_us * 1e-6)
        emit(f"fig2/single_source/sling_sharded/mesh={S}/n={n}",
             t_us / n_q,
             f"{qps:.0f} q/s batched multi-source, zero-recompile OK")
    print("MESH_OK")


def mesh_subprocess(mesh: int = 2, n: int = 300) -> None:
    """run.py --smoke hook: the sharded query check in a CPU child
    (host devices must be forced before the child's jax initializes).
    """
    cpu_mesh_subprocess(
        "benchmarks.bench_single_source",
        ["--mesh", str(mesh), "--n", str(n), "--queries", "16"],
        "MESH_OK", "fig2/", mesh)


def _main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", type=int, default=4)
    ap.add_argument("--n", type=int, default=1000)
    ap.add_argument("--eps", type=float, default=0.15)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()
    # before any jax computation: module imports above only define jits
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.mesh}")
    print("name,us_per_call,derived")
    run_mesh(n=args.n, mesh=args.mesh, eps=args.eps,
             n_q=args.queries, batch=args.batch)


if __name__ == "__main__":
    _main()
