#!/usr/bin/env python3
"""End-to-end smoke of the SLING serving path on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # node-sharded serving over four

One chip: build a 10^6-node power-law index with ``build_index_scale``
(eps = 0.1, int16 vals, certified chunked diagonal), mmap-load the
format-v3 artifact, and answer single-source, pair and top-k requests
through ``ServeFrontend`` -> ``QueryEngine`` with the backends that
``"auto"`` resolves to. Answers are checked against the host
references: float64 Horner and paper Alg 6 for single source, the
merge join ``query_pair_host`` for pairs, and the device single-source
row for top-k. No compile may happen after warm-up.

``--chips 4``: the same artifact, served node-sharded over a 4-device
mesh and by a single-device engine; the two must agree.

Refuses to run (exit 2) when JAX finds no TPU; any failed check
raises. The last line of stdout is the JSON verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

N_NODES = 10**6
DEGREE = 6
SEED = 0
ARTIFACT_DIR = os.path.join(ROOT, ".smoke_index")
TOPK = 10
SRC_TOL = 1e-4        # device f32 vs host f64 Horner
PAIR_TOL = 1e-4       # device join vs host merge join
TOPK_TOL = 1e-5       # top-k program vs single-source program
SHARD_TOL = 1e-5      # sharded vs single-device scores


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def build_and_load(n: int):
    """Generate the graph, build the artifact, mmap-load it."""
    from repro.core import build
    from repro.core.index import SlingIndex
    from repro.graph import generators

    g = generators.powerlaw_fast(n, k=DEGREE, seed=SEED)
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    path = os.path.join(ARTIFACT_DIR, "idx.sling")
    t0 = time.perf_counter()
    stats = build.build_index_scale(g, path, seed=SEED)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx = SlingIndex.load(path, mmap=True)
    t_load = time.perf_counter() - t0
    check(stats["d_mode"] == "estimate" and not idx.uncertified_d,
          "diagonal is not the certified estimate")
    check(idx.quant is not None and idx.quant.scheme == "int16",
          "artifact is not int16-quantized")
    check(isinstance(idx.hp.vals, np.memmap), "artifact is not mmap'd")
    say("graph", f"n={g.n} m={g.m}")
    say("plan", f"eps={idx.plan.eps} c={idx.plan.c} "
        f"l_max={idx.plan.l_max}")
    say("artifact", f"width={idx.hp.width} bytes={stats['bytes']} "
        f"entries={stats['entries']} builder={stats['builder']}")
    say("build_s", f"{t_build:.3f}")
    say("load_s", f"{t_load:.4f}")
    return g, idx


def serving_compile_count() -> int:
    """Compiled programs across every single-device serving jit."""
    from repro.analysis.runtime import compile_count
    from repro.core import index, single_source, topk
    from repro.kernels.hp_join import ops as hj
    return compile_count(
        single_source.batched_single_source,
        single_source.batched_single_source_pallas,
        topk.batched_topk, topk.batched_topk_pallas,
        index._pair_query_batch, hj.pair_query_batch_pallas)


def peak_bytes(dev) -> str:
    st = dev.memory_stats()
    return "not reported" if not st else str(st["peak_bytes_in_use"])


def sample_sources(g):
    """16 sources alternating between the 256 largest in-degrees and
    all nodes with in-neighbours (a node without them is similar to
    itself only, which checks nothing)."""
    rng = np.random.default_rng(SEED)
    hubs = np.argsort(-g.in_deg, kind="stable")[:256]
    active = np.flatnonzero(g.in_deg > 0)
    return np.stack([rng.choice(hubs, 8, replace=False),
                     rng.choice(active, 8, replace=False)],
                    axis=1).ravel().astype(np.int32)


def one_chip(g, idx, dev) -> None:
    from repro.core.single_source import (single_source_horner,
                                          single_source_paper)
    from repro.serve import FrontendConfig, ServeFrontend

    n = idx.n
    sources = sample_sources(g)

    with ServeFrontend(idx, g, FrontendConfig(replicas=1)) as fe:
        t0 = time.perf_counter()
        fe.warmup()
        say("warmup_s", f"{time.perf_counter() - t0:.3f}")
        st = fe.stats()["per_replica"][0]
        say("backends", f"push={st['push_backend']} "
            f"pair={st['pair_backend']}")
        shapes0 = len(fe.stats()["unique_shapes"])
        progs0 = serving_compile_count()

        t0 = time.perf_counter()
        src_t = [fe.submit_source(int(u)) for u in sources]
        topk_t = [fe.submit_topk(int(u), TOPK) for u in sources]
        fe.flush()
        rows = np.stack([t.result(timeout=600) for t in src_t])
        tops = [t.result(timeout=600) for t in topk_t]
        # pairs: each source with its 4 nearest others (scores that
        # are not 0) and 64 pairs of random nodes
        rng = np.random.default_rng(SEED)
        near = [(int(u), int(v)) for u, (_, ids) in zip(sources, tops)
                for v in ids[ids != u][:4]]
        pu, pv = np.array(near + [tuple(p) for p in
                                  rng.integers(0, n, (64, 2))]).T
        pair_t = [fe.submit_pair(int(u), int(v)) for u, v in zip(pu, pv)]
        fe.flush()
        pairs = np.array([t.result(timeout=600) for t in pair_t])
        say("serve_s", f"{time.perf_counter() - t0:.3f}")
        fst = fe.stats()
        shapes_grew = len(fst["unique_shapes"]) - shapes0
        progs_grew = serving_compile_count() - progs0
    say("peak_bytes_in_use", peak_bytes(dev))
    say("answered", f"source={len(rows)} pair={len(pairs)} "
        f"topk={len(tops)} shed={fst['shed']} "
        f"nonzero_pairs={int((pairs > 0).sum())}")
    say("compiled_after_warmup", f"shapes+{shapes_grew} "
        f"programs+{progs_grew}")
    check(fst["shed"] == 0, "requests were shed")
    check(shapes_grew == 0 and progs_grew == 0,
          "serving compiled after warm-up")
    check(bool(np.isfinite(rows).all()) and rows.shape == (16, n),
          "single-source rows are not finite (16, n)")

    t0 = time.perf_counter()
    err_h = err_p = 0.0
    for i in range(2):
        u = int(sources[i])
        err_h = max(err_h, float(np.abs(
            rows[i] - single_source_horner(idx, g, u)).max()))
        err_p = max(err_p, float(np.abs(
            rows[i] - single_source_paper(idx, g, u)).max()))
    err_pair = float(np.abs(pairs - np.array(
        [idx.query_pair_host(int(u), int(v), g)
         for u, v in zip(pu, pv)])).max())
    err_top = 0.0
    for row, (sv, si) in zip(rows, tops):
        check(sv.shape == (TOPK,) and bool((np.diff(sv) <= 0).all()),
              "top-k scores are not descending")
        err_top = max(err_top, float(np.abs(row[si] - sv).max()))
        # nothing outside the returned ids may beat the k-th score
        rest = np.delete(row, si)
        err_top = max(err_top, float(rest.max() - sv[-1]))
    say("reference_s", f"{time.perf_counter() - t0:.3f}")
    say("max_err", f"source_vs_horner={err_h:.3e} "
        f"source_vs_paper={err_p:.3e} (eps={idx.plan.eps}) "
        f"pair_vs_host={err_pair:.3e} topk_vs_row={err_top:.3e}")
    check(err_h <= SRC_TOL, "single source disagrees with host Horner")
    check(err_p <= idx.plan.eps, "single source outside plan eps")
    check(err_pair <= PAIR_TOL, "pairs disagree with query_pair_host")
    check(err_top <= TOPK_TOL, "top-k ids disagree with the source row")


def four_chips(g, idx) -> None:
    import jax

    from repro.core import shard_query
    from repro.serve import EngineConfig, QueryEngine

    mesh = shard_query.serving_mesh(4)
    single = QueryEngine(idx, g, EngineConfig())
    sharded = QueryEngine(idx, g, EngineConfig(mesh=mesh))
    si = sharded._sharded
    placed = {s.device for s in si.keys.addressable_shards}
    shard_rows = {s.data.shape[0] for s in si.keys.addressable_shards}
    say("mesh", f"shards={si.n_shards} devices={len(placed)} "
        f"rows_per_shard={sorted(shard_rows)} n_pad={si.n_pad}")
    check(len(placed) == 4 and shard_rows == {si.n_loc},
          "index slabs are not spread over four devices")
    sources = sample_sources(g)
    # no warm-up: only the two programs compared here compile
    t0 = time.perf_counter()
    a = single.single_source(sources)
    va, ia = single.topk(sources, TOPK)
    say("single_device_s", f"{time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    b = sharded.single_source(sources)
    vb, ib = sharded.topk(sources, TOPK)
    say("sharded_s", f"{time.perf_counter() - t0:.3f}")
    err_src = float(np.abs(a - b).max())
    err_val = float(np.abs(va - vb).max())
    # where the two lists differ, the ids must be tied: the single
    # device's row scores the sharded pick like its own
    err_tie = float(np.abs(np.take_along_axis(a, ib, axis=1) - va).max())
    same_ids = int((ia == ib).all(axis=1).sum())
    say("peak_bytes_in_use", " ".join(peak_bytes(d)
                                      for d in jax.devices()[:4]))
    say("sharded_vs_single", f"source_max_err={err_src:.3e} "
        f"topk_score_err={err_val:.3e} topk_tie_err={err_tie:.3e} "
        f"identical_topk_rows={same_ids}/{len(sources)}")
    check(err_src <= SHARD_TOL, "sharded single source disagrees")
    check(err_val <= SHARD_TOL and err_tie <= SHARD_TOL,
          "sharded top-k disagrees")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips}: JAX found {len(devices)} devices",
              file=sys.stderr)
        return 2

    say("compile_cache", cache)
    say("device", f"{devices[0].device_kind} x{len(devices)}")
    try:
        g, idx = build_and_load(N_NODES)
        if args.chips == 4:
            four_chips(g, idx)
        else:
            one_chip(g, idx, devices[0])
    finally:
        shutil.rmtree(ARTIFACT_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
