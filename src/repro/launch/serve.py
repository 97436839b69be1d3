"""Serving launcher: thin CLI over repro.serve.QueryEngine.

``python -m repro.launch.serve --queries 64`` builds an index over a
synthetic graph, primes the engine's compile cache, then serves a
query stream through the unified engine -- single-source by default;
``--mode pair|topk|mixed`` exercises the other paths. Batching,
padding, k-bucketing, and caching all live in the engine; this file
only parses flags, generates traffic, and reports latency.

``--mesh S`` serves node-sharded: the index partitions over an S-way
"data" mesh axis and single-source/top-k fan out with shard_map
(DESIGN.md section 8). Under ``JAX_PLATFORMS=cpu`` the S host devices
are forced via XLA_FLAGS before jax initializes (done here when the
flag is unset); on an accelerator the mesh takes S of its devices.

Compiled programs persist across runs (``repro.launch.compile_cache``).

``--mutate N`` appends an edge-churn replay (DESIGN.md section 7,
EXPERIMENTS.md "Dynamic workloads"): N random insert/delete batches of
``--churn`` fraction of the edges each are applied with the
incremental ``update_index`` and hot-swapped into the live engine
between query batches, reporting repair time, swap latency, recompile
count (must stay 0), and the accumulated staleness vs the plan's
reserve -- including the full-rebuild trigger firing.

``--save-index P`` persists the built index as a format-v3 artifact;
``--index P [--mmap]`` serves a persisted artifact instead of
building (mmap: O(1) zero-copy load); ``--quantize int16|bf16
--quant-frac F`` serves an eps-charged quantized index (DESIGN.md
section 13).

``--frontend R`` serves through the async SLO-aware admission layer
(repro.serve.ServeFrontend, DESIGN.md section 12) instead of calling
the engine directly: R engine replicas over the one index artifact,
deadline-aware batch formation (``--max-wait-ms``), per-request
deadlines with shed-on-expiry (``--deadline-ms``), least-loaded or
round-robin routing (``--routing``), and a Zipf(``--zipf``) power-law
query stream -- the realistic millions-of-users shape. Reports
p50/p99 admission-to-result latency, shed rate, mean batch occupancy,
and throughput; ``--mutate`` swaps go through the frontend's epoch
barrier so no dispatched batch mixes epochs.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from repro.core import build, update
from repro.graph import generators
from repro.serve import EngineConfig, QueryEngine


def _percentiles(lat: list[float]) -> str:
    a = 1e3 * np.asarray(lat)
    return (f"p50 {np.percentile(a, 50):.2f} ms  "
            f"p99 {np.percentile(a, 99):.2f} ms")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--deg", type=int, default=4)
    ap.add_argument("--eps", type=float, default=0.1)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--mode", default="source",
                    choices=("source", "pair", "topk", "mixed"))
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--pair-backend", default="auto",
                    choices=("auto", "join", "pallas"))
    ap.add_argument("--mesh", type=int, default=0, metavar="S",
                    help="node-shard the index over an S-way mesh and "
                         "serve single-source/top-k via shard_map "
                         "(0 = single-device)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mutate", type=int, default=0, metavar="N",
                    help="replay N edge-churn batches with incremental "
                         "update_index + hot-swap after the query loop")
    ap.add_argument("--churn", type=float, default=0.01,
                    help="fraction of edges mutated per --mutate batch")
    ap.add_argument("--theta-r", type=float, default=None,
                    help="repair threshold override (default: plan "
                         "theta, the sound operating point)")
    ap.add_argument("--stale-frac", type=float, default=0.2,
                    help="fraction of eps reserved for update staleness")
    ap.add_argument("--frontend", type=int, default=0, metavar="R",
                    help="serve through the async SLO-aware frontend "
                         "with R engine replicas (0 = direct engine)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="frontend batch-close wait bound")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline; expired requests are "
                         "shed, not served (0 = no deadline)")
    ap.add_argument("--zipf", type=float, default=1.1,
                    help="frontend query-skew exponent (0 = uniform)")
    ap.add_argument("--routing", default="least_loaded",
                    choices=("least_loaded", "round_robin"))
    ap.add_argument("--index", default=None, metavar="PATH",
                    help="serve a persisted index artifact instead of "
                         "building one (graph is regenerated from "
                         "--n/--deg/--seed and must match)")
    ap.add_argument("--mmap", action="store_true",
                    help="with --index: map the artifact read-only "
                         "(format v3; O(1) load, replicas share pages)")
    ap.add_argument("--save-index", default=None, metavar="PATH",
                    help="persist the index (format v3) after building")
    ap.add_argument("--quantize", default="none",
                    choices=("none", "int16", "bf16"),
                    help="serve a quantized index (needs --quant-frac "
                         "> 0; DESIGN.md section 13)")
    ap.add_argument("--quant-frac", type=float, default=0.0,
                    help="fraction of eps reserved for quantization "
                         "error (plan eps_quant_frac)")
    args = ap.parse_args()
    if args.queries < 1 or args.batch < 1:
        ap.error("--queries and --batch must be >= 1")
    if args.quantize != "none" and args.quant_frac <= 0:
        ap.error("--quantize needs --quant-frac > 0 (the plan must "
                 "reserve the quantization budget)")
    if args.mutate and (args.quantize != "none" or args.mmap):
        ap.error("--mutate needs a writable fp32 index; quantized/"
                 "mmap'd artifacts are read-only")

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    mesh = None
    if args.mesh > 0:
        if os.environ.get("JAX_PLATFORMS") == "cpu":
            # must land before jax initializes its backend (the
            # imports above only define jitted functions, they run
            # nothing)
            os.environ.setdefault(
                "XLA_FLAGS",
                f"--xla_force_host_platform_device_count={args.mesh}")
        from repro.core import shard_query
        mesh = shard_query.serving_mesh(args.mesh)
        print(f"mesh: {args.mesh}-way node-sharded serving over 'data'")

    g = generators.barabasi_albert(args.n, args.deg, seed=args.seed,
                                   directed=False)
    print(f"graph: n={g.n} m={g.m}")
    t0 = time.perf_counter()
    if args.index:
        from repro.core.index import SlingIndex
        idx = SlingIndex.load(args.index, mmap=args.mmap)
        if idx.n != g.n:
            raise SystemExit(f"--index has n={idx.n}, graph has "
                             f"n={g.n}; pass matching --n/--deg/--seed")
        print(f"index loaded in {time.perf_counter() - t0:.3f}s "
              f"({idx.nbytes() / 1e6:.1f} MB"
              f"{', mmap' if args.mmap else ''}"
              f"{', ' + idx.quant.scheme if idx.quant else ''})")
    else:
        idx = build.build_index(g, eps=args.eps, verbose=True,
                                stale_frac=args.stale_frac if args.mutate
                                else 0.0,
                                quant_frac=args.quant_frac)
        if args.quantize != "none":
            from repro.core import quantize
            idx = quantize.quantize_index(idx, scheme=args.quantize)
            print(f"index quantized ({args.quantize}): "
                  f"{idx.nbytes() / 1e6:.1f} MB")
        print(f"index built in {time.perf_counter() - t0:.1f}s "
              f"({idx.nbytes() / 1e6:.1f} MB)")
    if args.save_index:
        idx.save(args.save_index)
        print(f"index saved -> {args.save_index}")

    if args.frontend > 0:
        _frontend_serve(args, g, idx, mesh)
        return

    eng = QueryEngine(idx, g, EngineConfig(
        source_batch=args.batch, pair_batch=max(args.batch, 16),
        pair_backend=args.pair_backend, mesh=mesh))
    warm = eng.warmup()
    print("warmup (compile priming): "
          + "  ".join(f"{k}={v:.2f}s" for k, v in warm.items()))

    rng = np.random.default_rng(args.seed)
    qs = rng.integers(0, g.n, args.queries).astype(np.int32)
    modes = {"source": ["source"], "pair": ["pair"], "topk": ["topk"],
             "mixed": ["source", "pair", "topk"]}[args.mode]
    shapes_before = len(eng.stats()["unique_shapes"])
    for mode in modes:
        lat = []
        for lo in range(0, args.queries, args.batch):
            batch = qs[lo:lo + args.batch]
            t0 = time.perf_counter()
            if mode == "source":
                scores = eng.single_source(batch)
                sample = scores[0][:5]
            elif mode == "pair":
                vs = rng.integers(0, g.n, len(batch)).astype(np.int32)
                sample = eng.pairs(batch, vs)[:5]
            else:
                sv, si = eng.topk(batch, args.k)
                sample = sv[0]
            lat.append((time.perf_counter() - t0) / len(batch))
        print(f"[{mode}] {args.queries} queries, batch={args.batch}: "
              f"{_percentiles(lat)} per query")
        print(f"[{mode}] sample: {np.round(np.asarray(sample), 4)}")

    st = eng.stats()
    grew = len(st["unique_shapes"]) - shapes_before
    print(f"engine: {st['batches']} batches, {st['pad_slots']} pad "
          f"slots, cache {st['cache_hits']}/{st['cache_hits'] + st['cache_misses']} hits, "
          f"backend={st['pair_backend']}, mesh={st['mesh_shards']}")
    print(f"compiled shapes: {len(st['unique_shapes'])} total, "
          f"{grew} new after warmup "
          f"({'compile-once OK' if grew == 0 else 'RECOMPILED'})")

    if args.mutate:
        _mutate_replay(args, g, idx, eng, qs)


def _frontend_serve(args, g, idx, mesh) -> None:
    """Zipf traffic through the SLO-aware frontend (DESIGN.md §12)."""
    from repro.serve import FrontendConfig, ServeFrontend, zipf_nodes
    fe = ServeFrontend(idx, g, FrontendConfig(
        max_batch=args.batch, max_pair_batch=max(args.batch, 16),
        max_wait=args.max_wait_ms / 1e3,
        default_timeout=(args.deadline_ms / 1e3
                         if args.deadline_ms > 0 else None),
        replicas=args.frontend, routing=args.routing,
        engine=EngineConfig(source_batch=args.batch,
                            pair_batch=max(args.batch, 16),
                            pair_backend=args.pair_backend, mesh=mesh)))
    warm = fe.warmup()
    deadline = (f"{args.deadline_ms:g}ms" if args.deadline_ms > 0
                else "none")
    print(f"frontend: {args.frontend} replicas, {args.routing} routing, "
          f"max_wait {args.max_wait_ms}ms, deadline {deadline}, "
          f"zipf s={args.zipf}")
    print("warmup (compile priming, max over replicas): "
          + "  ".join(f"{k}={v:.2f}s" for k, v in warm.items()))
    us = zipf_nodes(g.n, args.queries, s=args.zipf, seed=args.seed)
    vs = zipf_nodes(g.n, args.queries, s=args.zipf, seed=args.seed + 1)
    modes = {"source": ["source"], "pair": ["pair"], "topk": ["topk"],
             "mixed": ["source", "pair", "topk"]}[args.mode]
    shapes_before = len(fe.stats()["unique_shapes"])
    for mode in modes:
        t0 = time.perf_counter()
        if mode == "source":
            tickets = [fe.submit_source(int(u)) for u in us]
        elif mode == "pair":
            tickets = [fe.submit_pair(int(u), int(v))
                       for u, v in zip(us, vs)]
        else:
            tickets = [fe.submit_topk(int(u), args.k) for u in us]
        fe.flush()
        fe.drain(timeout=120.0)
        wall = time.perf_counter() - t0
        lat = [t.latency for t in tickets if not t.shed]
        shed = sum(t.shed for t in tickets)
        pct = (_percentiles(lat) if lat else "all shed")
        print(f"[frontend {mode}] {args.queries} requests: {pct}  "
              f"shed {shed}/{args.queries}  "
              f"{args.queries / wall:.0f} req/s")
    if args.mutate:
        _frontend_mutate(args, g, idx, fe, us)
    st = fe.stats()
    grew = len(st["unique_shapes"]) - shapes_before
    print(f"frontend: {st['batches']} batches, occupancy "
          f"{st['mean_occupancy']:.2f}, cache "
          f"{st['cache_hits']}/{st['cache_hits'] + st['cache_misses']} "
          f"hits over {st['replicas']} replicas")
    print(f"compiled shapes: {len(st['unique_shapes'])} total, "
          f"{grew} new after warmup "
          f"({'compile-once OK' if grew == 0 else 'RECOMPILED'})")
    fe.close()


def _frontend_mutate(args, g, idx, fe, us) -> None:
    """Edge-churn replay through the frontend's epoch swap barrier."""
    m_batch = max(1, int(g.m * args.churn))
    print(f"\n[mutate] {args.mutate} batches x {m_batch} edges through "
          f"the frontend swap barrier")
    for i in range(args.mutate):
        delta = update.random_delta(g, n_add=m_batch // 2,
                                    n_del=m_batch - m_batch // 2,
                                    seed=args.seed + 100 + i)
        t0 = time.perf_counter()
        rep = build.update_index(idx, g, delta, seed=args.seed + i,
                                 theta_r=args.theta_r)
        t_repair = time.perf_counter() - t0
        sw = fe.swap_index(idx, rep.graph, affected=rep.affected)
        g = rep.graph
        tickets = [fe.submit_source(int(u)) for u in us[:args.batch]]
        fe.flush()
        fe.drain(timeout=120.0)
        sample = tickets[0].result(timeout=10.0)[:3]
        print(f"[mutate {i}] repair={t_repair * 1e3:.0f}ms "
              f"swap={sw['swap_ms']:.1f}ms barrier_batches="
              f"{sw['barrier_batches']} recompiles={sw['recompiles']} "
              f"epoch={sw['epoch']} "
              f"sample={np.round(np.asarray(sample), 4)}")


def _mutate_replay(args, g, idx, eng, qs) -> None:
    """Edge-churn replay: update -> hot-swap -> serve, N times."""
    m_batch = max(1, int(g.m * args.churn))
    print(f"\n[mutate] {args.mutate} batches x {m_batch} edges "
          f"(churn {args.churn:.2%}), theta_r="
          f"{args.theta_r if args.theta_r is not None else 'plan.theta'}")
    shapes0 = len(eng.stats()["unique_shapes"])
    for i in range(args.mutate):
        delta = update.random_delta(g, n_add=m_batch // 2,
                                    n_del=m_batch - m_batch // 2,
                                    seed=args.seed + 100 + i)
        t0 = time.perf_counter()
        rep = build.update_index(idx, g, delta, seed=args.seed + i,
                                 theta_r=args.theta_r)
        t_repair = time.perf_counter() - t0
        sw = eng.swap_index(idx, rep.graph, affected=rep.affected)
        g = rep.graph
        scores = eng.single_source(qs[:args.batch])
        trigger = " REBUILD-TRIGGER" if rep.needs_rebuild else ""
        print(f"[mutate {i}] touched={len(rep.touched)} "
              f"rows={rep.rows_repaired} d={rep.d_updated} "
              f"repair={t_repair * 1e3:.0f}ms swap={sw['swap_ms']:.1f}ms "
              f"dropped={sw['cache_dropped']} "
              f"stale={rep.stale:.4f}/{rep.eps_stale:.4f}{trigger} "
              f"sample={np.round(scores[0][:3], 4)}")
        if rep.needs_rebuild:
            t0 = time.perf_counter()
            idx = build.build_index(g, eps=args.eps, seed=args.seed,
                                    stale_frac=args.stale_frac)
            eng.swap_index(idx, g)  # full invalidation: new epoch 0
            print(f"[mutate {i}] full rebuild in "
                  f"{time.perf_counter() - t0:.1f}s, engine re-armed")
    st = eng.stats()
    grew = len(st["unique_shapes"]) - shapes0
    print(f"[mutate] {st['swaps']} swaps, last {st['last_swap_ms']:.1f}ms, "
          f"{st['swap_recompiles']} bucket overflows, {grew} new shapes "
          f"({'zero-recompile swap OK' if grew == 0 and not st['swap_recompiles'] else 'RECOMPILED'})")


if __name__ == "__main__":
    main()
