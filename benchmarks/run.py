"""Benchmark driver: one module per paper figure/table + serving path.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
``--fast`` shrinks graph sizes so the whole suite finishes in a few
minutes on one CPU core; default sizes match the figures in
EXPERIMENTS.md. ``--smoke`` is the CI mode (scripts/ci.sh): tiny
graphs, every section exercised once, plus the n=500 serving-path
latency guard, the zero-recompile-on-swap guard (bench_update), the
lax-vs-pallas push equivalence + op-count fusion gates
(bench_single_source.run_backends), and the per-backend
zero-recompile-across-tiles join gate (bench_join) -- finishes in ~a
minute.

Every mode writes ALL structured rows to ``BENCH_<mode>.json`` --
every ``emit()`` records one (n/backend/mesh parsed from the row
name), not just the benches that call ``emit_row`` directly (schema:
bench, n, backend, mesh, wall, throughput; see benchmarks.common).
``--compare OLD.json`` is the cross-PR regression mode: after the run
it diffs this run's wall/throughput against a prior
``BENCH_<mode>.json`` on the (bench, n, backend, mesh) identity and
prints ``# compare`` rows; ``--compare-strict`` exits non-zero on any
regression beyond ``--compare-ratio`` (default 1.5x).

    PYTHONPATH=src python -m benchmarks.run [--fast|--smoke] [--only ...]
    PYTHONPATH=src python -m benchmarks.run --smoke --compare BENCH_smoke.json
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: minimal sizes + n=500 serving guard")
    ap.add_argument("--only", default=None,
                    help="comma list: pair,source,preprocess,space,"
                         "accuracy,topk,serve,update,join,roofline")
    ap.add_argument("--scale", action="store_true",
                    help="run the 10^6-node out-of-core space bench "
                         "(bench_space.run_scale); minutes of wall "
                         "time, never part of --smoke CI")
    ap.add_argument("--compare", default=None, metavar="OLD.json",
                    help="diff this run's rows against a prior "
                         "BENCH_<mode>.json (regression mode)")
    ap.add_argument("--compare-ratio", type=float, default=1.5,
                    help="wall ratio (or inverse throughput ratio) "
                         "beyond which a row counts as REGRESSED")
    ap.add_argument("--compare-strict", action="store_true",
                    help="exit non-zero when --compare finds "
                         "regressions")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    def want(name: str) -> bool:
        return only is None or name in only

    if args.smoke:
        sizes = (300,)
    elif args.fast:
        sizes = (300, 1000)
    else:
        sizes = (300, 1000, 3000)
    print("name,us_per_call,derived")

    if want("pair"):
        from benchmarks import bench_single_pair
        bench_single_pair.run(sizes=sizes)
    if want("source"):
        from benchmarks import bench_single_source
        bench_single_source.run(sizes=sizes)
        # lax-vs-pallas push rows + the smoke gates: backend
        # equivalence on the real run, trace-only op-count fusion
        # check at n = 10^4 (both assert)
        bench_single_source.run_backends(n=sizes[0])
        if args.smoke:
            # 2-shard sharded-serving check (subprocess: forces host
            # devices before the child's jax backend initializes)
            bench_single_source.mesh_subprocess(mesh=2, n=300)
    if want("preprocess"):
        from benchmarks import bench_preprocess
        bench_preprocess.run(sizes=sizes[:2])
        # prsim-vs-sling build-wall rows (entry-set equality asserted)
        bench_preprocess.run_builders(n=max(sizes))
        if args.smoke:
            # auto-selection gate: builder="auto" must pick prsim on
            # a power-law graph and sling on an ER graph
            bench_preprocess.builder_smoke(n=400)
            # preprocess smoke (subprocess, forced host devices):
            # 2-shard build equivalence + the diagonal walk-path
            # recompile gate
            bench_preprocess.mesh_subprocess(mesh=2, n=240)
    if want("space"):
        from benchmarks import bench_space
        bench_space.run(sizes=sizes, smoke=args.smoke)
        # prsim-vs-sling artifact bytes/node + serve-throughput rows
        bench_space.run_builders(n=1000 if args.smoke else 2000)
        if args.scale:
            # 10^6-node out-of-core build + mmap serving row; also
            # runs in full mode at 10^5 so the scale path stays
            # benchmarked without the full-minute 10^6 build
            bench_space.run_scale(n=1_000_000)
        elif not (args.smoke or args.fast):
            bench_space.run_scale(n=100_000)
    if want("accuracy") and not args.smoke:
        from benchmarks import bench_accuracy
        bench_accuracy.run(n=300, n_runs=2 if args.fast else 3)
    if want("topk"):
        from benchmarks import bench_topk
        if args.smoke:
            bench_topk.run_engine(n=300)
        else:
            bench_topk.run(n=300)
    if want("serve"):
        from benchmarks import bench_serve
        bench_serve.run(n=500, n_q=16 if args.smoke else 32,
                        smoke=args.smoke)
    if want("update"):
        from benchmarks import bench_update
        if args.smoke:
            bench_update.run(n=500, smoke=True)   # zero-recompile guard
        elif args.fast:
            bench_update.run(n=1500)
        else:
            bench_update.run(n=3000)              # >= 5x @ 1% churn gate
    if want("join"):
        from benchmarks import bench_join
        if args.smoke:
            # small sweep: recompile gate asserted, 3x gate is only
            # calibrated at n >= 2000; plus the 2-shard mesh sweep
            # equivalence check (subprocess: forced host devices)
            bench_join.run(n=300, n_sources=64, tile=32)
            bench_join.mesh_subprocess(mesh=2, n=300)
        elif args.fast:
            bench_join.run(n=1000, n_sources=128)
        else:
            bench_join.run(n=2000)               # >= 3x sweep gate
            bench_join.mesh_subprocess(mesh=2, n=1000)
    if want("roofline") and not args.smoke:
        from benchmarks import roofline
        roofline.run()

    from benchmarks import common
    mode = "smoke" if args.smoke else ("fast" if args.fast else "full")
    # compare BEFORE writing: --compare BENCH_<mode>.json (the usual
    # previous-run path) must diff against the OLD rows, not the file
    # this run is about to overwrite
    regressed = []
    if args.compare:
        regressed = common.compare_json(args.compare,
                                        slow_ratio=args.compare_ratio)
    common.write_json(mode)
    if regressed and args.compare_strict:
        sys.exit(f"{len(regressed)} benchmark rows regressed "
                 f"beyond x{args.compare_ratio:g}")


if __name__ == "__main__":
    main()
