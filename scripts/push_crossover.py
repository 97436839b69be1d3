"""Time the two Horner-push bodies of the top-k program on one device.

Runs ``batched_topk`` (per-edge scatter) and ``batched_topk_dense``
(dense bfloat16 operator on the MXU) at one node count and several edge
counts, and prints, per edge count, the milliseconds per batch of each
and the dense operator's cells per edge slot (n_pad**2 / edge_cap): the
engine's rule sends a graph to the dense push when that ratio is at
most ``serve.engine.DENSE_PUSH_CELLS_PER_EDGE`` for the platform, so
the crossover read here sets that constant. It also checks the two
bodies against each other and against a float64 NumPy push, and a
single-bfloat16-pass push against the same, so that the run shows the
three-way split is what keeps float32.

The graph is a benchmark configuration's (default: wiki-Vote's shape,
``bench/configs/wikivote-e0.025.json``); fewer edges are a seeded
subsample of its edge list on the same nodes. Packed rows are random
(a row's values do not change either body's work).

    python scripts/push_crossover.py [--nodes 16384] [--edges 103689 40000 20000]

With ``--out`` it also writes every number to that JSON file.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def packed_rows(n, width, l_max, sqrt_c, rng):
    """(n, width) keys l * n + k and float32 values ~ (sqrt c)^l, a
    quarter of each row padding."""
    from repro.core.hp_index import INT32_PAD_KEY
    ls = rng.integers(0, l_max + 1, (n, width))
    keys = (ls * n + rng.integers(0, n, (n, width))).astype(np.int32)
    vals = (0.5 * sqrt_c ** ls * rng.random((n, width))).astype(np.float32)
    pad = rng.random((n, width)) < 0.25
    keys[pad] = INT32_PAD_KEY
    vals[pad] = 0.0
    return keys, vals


def numpy_push(keys, vals, d, src, dst, w, u, tau, n, l_max):
    """Float64 Horner push of one source (the reference)."""
    row_k, row_v = keys[u], vals[u].astype(np.float64)
    ok = row_k != np.iinfo(np.int32).max
    ls, ks = row_k[ok] // n, row_k[ok] % n
    seeds = np.zeros((l_max + 1, n))
    np.add.at(seeds, (ls, ks), row_v[ok] * d[ks])
    acc = seeds[l_max]
    for l in range(l_max - 1, -1, -1):
        acc = np.where(acc > tau, acc, 0.0)
        acc = np.bincount(dst, acc[src] * w, minlength=n) + seeds[l]
    return acc


def one_bf16_pass(dense_args, n, l_max):
    """The control: the dense push with the frontier cast to bfloat16
    once, in place of the three-way split."""
    import jax
    import jax.numpy as jnp

    from repro.core import single_source as ss

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return jnp.concatenate([hi, jnp.zeros_like(hi), jnp.zeros_like(hi)])

    keys, vals, d, adjT, s, us, tau = dense_args
    saved, ss.split_bf16x3 = ss.split_bf16x3, split
    try:
        return jax.jit(lambda ku, xu: ss.horner_push_dense(
            ku, xu, d, adjT, s, tau, n=n, l_max=l_max))(keys[us], vals[us])
    finally:
        ss.split_bf16x3 = saved


def time_ms(fn, reps):
    fn()[0].block_until_ready()          # compile and warm
    t = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()[0].block_until_ready()
        t.append(1e3 * (time.perf_counter() - t0))
    return float(np.median(t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config",
                    default=os.path.join(ROOT, "bench", "configs",
                                         "wikivote-e0.025.json"))
    ap.add_argument("--nodes", type=int, default=None,
                    help="draw the graph at this node count, its receiving "
                         "nodes scaled alike, with the largest --edges")
    ap.add_argument("--edges", type=int, nargs="*", default=[])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--width", type=int, default=640)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None, help="JSON file for the results")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import graphs
    from repro.core import theory
    from repro.core.hp_index import capacity_bucket
    from repro.core.single_source import (dense_push_operator, dense_side,
                                          prune_tau)
    from repro.core.topk import batched_topk, batched_topk_dense
    from repro.graph import csr

    conf = json.load(open(args.config))
    spec = dict(conf["graph"])
    if args.nodes:
        if "in_nodes" in spec:
            spec["in_nodes"] = round(spec["in_nodes"] * args.nodes / spec["n"])
        spec["n"], spec["m"] = args.nodes, max(args.edges or [spec["m"]])
    # the plan of build_index_scale's default artifact (int16 values,
    # a fifth of eps reserved for quantization)
    plan = theory.plan(eps=conf["plan"]["eps"], c=conf["plan"]["c"],
                       eps_quant_frac=0.2)
    n, l_max, sqrt_c, tau = spec["n"], plan.l_max, plan.sqrt_c, prune_tau(plan)
    rng = np.random.default_rng(args.seed)
    src_all, dst_all = graphs.make_edges(spec)
    keys, vals = packed_rows(n, args.width, l_max, sqrt_c, rng)
    d = (0.4 + 0.6 * rng.random(n)).astype(np.float32)
    us = rng.choice(n, args.batch, replace=False).astype(np.int32)
    dev = dict(keys=jnp.asarray(keys), vals=jnp.asarray(vals),
               d=jnp.asarray(d), us=jnp.asarray(us), tau=jnp.float32(tau))
    n_pad = dense_side(n)
    out = {"device": jax.devices()[0].device_kind, "n": n, "n_pad": n_pad,
           "l_max": l_max, "batch": args.batch, "width": args.width,
           "k": args.k, "shapes": []}
    print(f"device {out['device']}  n {n}  n_pad {n_pad}  l_max {l_max}  "
          f"B {args.batch}", flush=True)
    for m in (args.edges or [len(src_all)]):
        pick = np.sort(rng.choice(len(src_all), m, replace=False))
        g = csr.from_edges(n, src_all[pick], dst_all[pick])
        ec = capacity_bucket(g.m)
        e_src = np.zeros(ec, np.int32)
        e_dst = np.zeros(ec, np.int32)
        e_w = np.zeros(ec, np.float32)
        e_src[:g.m], e_dst[:g.m] = g.edge_src, g.edge_dst
        e_w[:g.m] = csr.normalized_pull_weights(g, sqrt_c)
        sparse_args = (dev["keys"], dev["vals"], dev["d"], jnp.asarray(e_src),
                       jnp.asarray(e_dst), jnp.asarray(e_w), dev["us"],
                       dev["tau"], n, l_max, args.k)
        A, s = dense_push_operator(g, sqrt_c, n_pad)
        t0 = time.perf_counter()
        adjT, s_dev = jnp.asarray(A), jnp.asarray(s)
        adjT.block_until_ready()
        upload_s = time.perf_counter() - t0
        dense_args = (dev["keys"], dev["vals"], dev["d"], adjT, s_dev,
                      dev["us"], dev["tau"], n, l_max, args.k)
        row = {"m": g.m, "edge_cap": ec,
               "cells_per_edge": n_pad * n_pad / ec,
               "sparse_ms": time_ms(lambda: batched_topk(*sparse_args),
                                    args.reps),
               "dense_ms": time_ms(lambda: batched_topk_dense(*dense_args),
                                   args.reps),
               "dense_upload_s": upload_s}
        if m == (args.edges or [len(src_all)])[0]:
            # accuracy at the first shape: full scores of both bodies
            from repro.core.single_source import (
                batched_single_source, batched_single_source_dense)
            sp = np.asarray(batched_single_source(
                *sparse_args[:8], n=n, l_max=l_max))
            dn = np.asarray(batched_single_source_dense(
                *dense_args[:7], n=n, l_max=l_max))
            bf = np.asarray(one_bf16_pass(dense_args[:7], n, l_max))
            ref = np.stack([numpy_push(keys, vals, d, g.edge_src, g.edge_dst,
                                       csr.normalized_pull_weights(
                                           g, sqrt_c).astype(np.float64),
                                       int(u), tau, n, l_max) for u in us])
            scale = float(np.abs(ref).max())
            row["rel_err_dense"] = float(np.abs(dn - ref).max()) / scale
            row["rel_err_sparse"] = float(np.abs(sp - ref).max()) / scale
            row["rel_err_one_bf16_pass"] = float(np.abs(bf - ref).max()) / scale
            row["dense_vs_sparse"] = float(np.abs(dn - sp).max()) / scale
        out["shapes"].append(row)
        print(json.dumps(row), flush=True)
    # crossover: where the sparse time, linear in edge_cap, meets the
    # dense time, flat in it (least squares over the shapes)
    rows = out["shapes"]
    if len(rows) >= 2:
        ec = np.array([r["edge_cap"] for r in rows], float)
        sp_ms = np.array([r["sparse_ms"] for r in rows])
        slope, icpt = np.polyfit(ec, sp_ms, 1)
        dense = float(np.median([r["dense_ms"] for r in rows]))
        if slope > 0 and dense > icpt:
            cap = (dense - icpt) / slope
            out["crossover"] = {"edge_cap": cap,
                                "cells_per_edge": n_pad * n_pad / cap,
                                "sparse_ns_per_edge_slot_step":
                                    1e6 * slope / l_max,
                                "sparse_fixed_ms": icpt, "dense_ms": dense}
        print(json.dumps({"crossover": out.get("crossover")}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0 if math.isfinite(rows[0]["dense_ms"]) else 1


if __name__ == "__main__":
    sys.exit(main())
