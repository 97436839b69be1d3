"""Host-side layout builder + jit-friendly wrapper for the fused
Horner-push kernel, plus the HBM-traffic models the benchmarks gate on.

Layout contract (DESIGN.md section 11): edges are grouped by
destination-node block of ``bn`` rows (same ELL idea as
``kernels/spmv_ell.block_align`` but vectorized -- the python loop
there is O(m) interpreter time) into (NB, E_pad) arrays with slab-local
destinations and -1 pads; E_pad is a multiple of the chunk width ``eb``
and can be floored to a capacity bucket so hot-swapped indices keep the
compiled grid shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hp_index import INT32_PAD_KEY
from repro.kernels.horner_push.horner_push import horner_step

DEFAULT_BN = 8
DEFAULT_EB = 128


def block_align_edges(src, dst_local, w, n_slab: int, *, bn: int = DEFAULT_BN,
                      eb: int = DEFAULT_EB, width_floor: int = 0):
    """Flat slab edges -> (NB, E_pad) dest-block-grouped ELL layout.

    src: frontier-global source ids; dst_local: slab-local destination
    ids in [0, n_slab); w: pull weights. Pad slots carry (src 0,
    dstl -1, w 0) -- the kernel masks on ``dstl >= 0``. E_pad is the
    max per-block count rounded up to a multiple of ``eb`` and at least
    ``width_floor`` (itself rounded up to an eb multiple), the
    capacity-bucket hook for swap-stable compiled shapes.
    """
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst_local, np.int64)
    w = np.asarray(w, np.float32)
    nb = max(1, -(-int(n_slab) // bn))
    blk = dst // bn
    counts = np.bincount(blk, minlength=nb) if len(dst) else \
        np.zeros(nb, np.int64)
    width = int(counts.max()) if len(dst) else 0
    width = max(width, 1, int(width_floor))
    width = -(-width // eb) * eb
    bs = np.zeros((nb, width), np.int32)
    bdl = np.full((nb, width), -1, np.int32)
    bw = np.zeros((nb, width), np.float32)
    if len(dst):
        order = np.argsort(blk, kind="stable")
        starts = np.zeros(nb + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        ob = blk[order]
        pos = np.arange(len(order), dtype=np.int64) - starts[ob]
        bs[ob, pos] = src[order]
        bdl[ob, pos] = (dst[order] - ob * bn).astype(np.int32)
        bw[ob, pos] = w[order]
    return bs, bdl, bw


def graph_block_layout(g, sqrt_c: float, *, bn: int = DEFAULT_BN,
                       eb: int = DEFAULT_EB, width_floor: int = 0):
    """Whole-graph layout (the single-device slab covers all n nodes)."""
    from repro.graph import csr
    w = csr.normalized_pull_weights(g, sqrt_c)
    return block_align_edges(g.edge_src, g.edge_dst, w, g.n,
                             bn=bn, eb=eb, width_floor=width_floor)


def required_block_width(g, *, bn: int = DEFAULT_BN) -> int:
    """Largest per-node-block edge count (>= 1): the quantity the
    engine capacity-buckets so swapped indices keep the (NB, E_pad)
    compiled shape."""
    if g.m == 0:
        return 1
    return int(np.bincount(np.asarray(g.edge_dst, np.int64) // bn).max())


def horner_push_pallas(ku, xu, d, blk_src, blk_dstl, blk_w, tau, *,
                       n: int, l_max: int, bn: int = DEFAULT_BN,
                       eb: int = DEFAULT_EB, bq: int = 8,
                       slab_start: int = 0, slab_size: int | None = None,
                       d_offset: int | None = None, gather=None,
                       interpret: bool):
    """Drop-in Pallas backend for ``single_source.horner_push``.

    Same argument contract and (B, slab_size) float32 return, except
    the flat (src, dst, w) edge arrays are replaced by the blocked
    (NB, E_pad) layout from :func:`block_align_edges`. ``gather`` (the
    sharded frontier all-gather) maps a node-major (slab_size, B)
    array to the node-major full frontier and stays *outside* the
    kernel -- a collective cannot run inside a Pallas grid program, and
    because the prune is elementwise it commutes with the gather, so
    pruning at in-kernel gather time is exact (DESIGN.md section 11).

    The Horner recursion runs the uniform form

        acc = 0;  for l = l_max .. 0:  acc = A_hat prune(acc) + seed_l

    (push(0) = 0, so the first step degenerates to seeding level
    l_max exactly like the reference's explicit ``acc = seed(L)``).
    """
    B, W = ku.shape
    slab_size = n if slab_size is None else slab_size
    d_offset = slab_start if d_offset is None else d_offset
    ls = jnp.where(ku == INT32_PAD_KEY, -1, ku // n).astype(jnp.int32)
    ks = jnp.clip(ku % n, 0, n - 1)
    contrib = (xu * d[jnp.clip(ks - d_offset, 0, d.shape[0] - 1)]
               ).astype(jnp.float32)
    k_loc = ks - slab_start
    in_slab = (k_loc >= 0) & (k_loc < slab_size)
    # out-of-slab keys are masked via ls = -1 (never equals a level)
    ls = jnp.where(in_slab, ls, -1)
    k_loc = jnp.clip(k_loc, 0, slab_size - 1).astype(jnp.int32)

    bq = min(bq, B)
    b_pad = -(-B // bq) * bq
    if b_pad != B:
        pad = ((0, b_pad - B), (0, 0))
        ls = jnp.pad(ls, pad, constant_values=-1)
        k_loc = jnp.pad(k_loc, pad)
        contrib = jnp.pad(contrib, pad)

    NB = blk_src.shape[0]
    assert NB * bn >= slab_size, (NB, bn, slab_size)
    tau_arr = jnp.full((1, 1), tau, jnp.float32)
    acc = jnp.zeros((NB * bn, b_pad), jnp.float32)
    for l in range(l_max, -1, -1):   # unrolled; l_max is static
        x = acc if gather is None else gather(acc[:slab_size])
        acc = horner_step(x, ls, k_loc, contrib, blk_src, blk_dstl,
                          blk_w, tau_arr,
                          jnp.full((1, 1), l, jnp.int32),
                          bn=bn, eb=eb, bq=bq, interpret=interpret)
    return acc[:slab_size].T[:B]


# ----------------------------------------------------------------------
# HBM-traffic models (benchmarks/roofline.py sanity check)
# ----------------------------------------------------------------------
def push_cost_model(n: int, m: int, B: int, W: int, l_max: int, *,
                    bn: int = DEFAULT_BN, eb: int = DEFAULT_EB) -> dict:
    """Per-query-batch HBM word traffic of one full Horner push.

    lax: every step materializes prune (read+write B*n), the edge
    gather (read B*n scattered + write B*m messages), the weighted
    messages (read+write B*m), the segment-sum (read B*m + write B*n),
    and the seed add (read+write B*n) -- each a separate HLO with its
    operands round-tripping HBM.

    pallas: per step the frontier is read once (B*n), the edge chunks
    stream once (3 * NB * E_pad words, padding included), the packed
    rows stream once (3*B*W), and the accumulator is written once
    (B*n); prune/gather/seed never touch HBM (DESIGN.md section 11).
    """
    steps = l_max + 1
    nb = max(1, -(-n // bn))
    e_pad = nb * max(-(-max(1, (m + nb - 1) // nb) // eb) * eb, eb)
    lax = steps * (6 * B * n + 3 * B * m)
    pallas = steps * (2 * B * n + 3 * e_pad + 3 * B * W)
    return {"steps": steps, "lax_words": int(lax),
            "pallas_words": int(pallas),
            "lax_bytes": int(4 * lax), "pallas_bytes": int(4 * pallas)}


def count_hbm_intermediates(fn, *args, min_elems: int) -> int:
    """Interpret-measurable fusion metric: the number of traced ops
    (recursively, through jit/scan sub-jaxprs) producing an array of
    >= ``min_elems`` elements. Each such op is a frontier-sized HBM
    materialization candidate; the fused kernel collapses the
    per-step prune/gather/messages/scatter/add chain to one pallas_call
    op, so its count is structurally smaller at every n -- the op-count
    form of the acceptance gate, measurable on CPU without a TPU run.

    Promoted to a general analyzer pass (repro.analysis.jaxpr_passes:
    the ``hbm-budget`` pass gates every push program against baselined
    budgets); this thin re-export keeps the historical call sites."""
    from repro.analysis.jaxpr_passes import \
        count_hbm_intermediates as _count
    return _count(fn, *args, min_elems=min_elems)
