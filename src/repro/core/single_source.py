"""Single-source SimRank queries (Algorithm 6) + the Horner-stacked
beyond-paper variant.

Paper Alg 6: for every step l present in H(u), seed
rho^0(k) = h~^(l)(u,k) * d_k and push l times through the *same* pull
operator A_hat used to build the index (the paper phrases it as an
out-neighbor push; for each out-neighbor v_y of v_x the update is
rho(v_y) += sqrt(c)/|I(v_y)| * rho(v_x), i.e. exactly
rho^(t) = A_hat rho^(t-1)). Entries <= (sqrt c)^l * theta are pruned per
step. Total work O(sum_l l * m) = O(m log^2 (1/eps)) (Lemma 12).

Beyond-paper optimization ("Horner push", EXPERIMENTS.md §Perf): the
answer is sum_l A_hat^l seed_l, which Horner-factorizes as

    acc = seed_L;  for l = L-1 .. 0:  acc = A_hat acc + seed_l

-- L pushes instead of L(L+1)/2, an O(L) speedup with *tighter* error:
we prune at the smallest of the paper's per-group thresholds
tau = (sqrt c)^L * theta (``prune_tau``), so every dropped contribution
is one the paper would also have dropped. Accuracy therefore dominates
Alg 6's.

Every device path -- single-device batched, the model-axis pod push,
and the node-sharded serving fan-out (core/shard_query.py) -- runs the
same :func:`horner_push` kernel over a node *slab*; the single-device
case is simply the slab that covers all n nodes with an identity
frontier gather.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core.hp_index import INT32_PAD_KEY
from repro.graph import csr


def prune_tau(plan) -> float:
    """The Horner prune threshold tau = (sqrt c)^l_max * theta.

    The smallest of Alg 6's per-group thresholds (see module
    docstring); resolved on host once so the device kernels never
    re-derive it from (theta, c) -- an earlier revision hardcoded
    sqrt(0.6) inside the kernel, which over-pruned for c < 0.6.
    """
    return float(plan.theta * plan.sqrt_c ** plan.l_max)


def _seed_matrix(idx, u: int, g: csr.Graph) -> np.ndarray:
    """(L+1, n) float64: seeds[l, k] = h~^(l)(u,k) * d_k."""
    n = idx.n
    seeds = np.zeros((idx.plan.l_max + 1, n), dtype=np.float64)
    keys, vals = idx._host_entries(u, g)
    ls = keys // n
    ks = keys % n
    # np.add.at, not fancy-index +=: a row carrying a duplicate (l, k)
    # key must contribute BOTH entries (buffered scatter keeps only the
    # last hit and silently drops the rest of the mass)
    np.add.at(seeds, (ls, ks), vals * idx.d[ks].astype(np.float64))
    return seeds


def single_source_paper(idx, g: csr.Graph, u: int) -> np.ndarray:
    """Faithful Alg 6 on dense n-vectors (host/NumPy)."""
    n = idx.n
    sc = idx.plan.sqrt_c
    theta = idx.plan.theta
    w = csr.normalized_pull_weights(g, sc).astype(np.float64)
    seeds = _seed_matrix(idx, u, g)
    out = np.zeros(n, dtype=np.float64)
    for l in range(seeds.shape[0]):
        rho = seeds[l]
        if not rho.any():
            continue
        tau = (sc ** l) * theta
        for _ in range(l):
            rho = np.where(rho > tau, rho, 0.0)
            nxt = np.zeros(n, dtype=np.float64)
            np.add.at(nxt, g.edge_dst, rho[g.edge_src] * w)
            rho = nxt
        out += rho
    return out


def single_source_horner(idx, g: csr.Graph, u: int) -> np.ndarray:
    """Beyond-paper Horner-stacked push (host/NumPy)."""
    n = idx.n
    w = csr.normalized_pull_weights(g, idx.plan.sqrt_c).astype(np.float64)
    seeds = _seed_matrix(idx, u, g)
    L = seeds.shape[0] - 1
    tau = prune_tau(idx.plan)
    acc = seeds[L].copy()
    for l in range(L - 1, -1, -1):
        acc = np.where(acc > tau, acc, 0.0)
        nxt = np.zeros(n, dtype=np.float64)
        np.add.at(nxt, g.edge_dst, acc[g.edge_src] * w)
        acc = nxt + seeds[l]
    return acc


# ----------------------------------------------------------------------
# the shared device kernel: Horner push over a node slab
# ----------------------------------------------------------------------
def horner_push(ku, xu, d, src, dst, w, tau, *, n: int, l_max: int,
                slab_start=0, slab_size: int | None = None,
                d_offset=None, gather=None):
    """Horner-stacked push for a batch of sources over one node slab.

    The one body behind every device path (DESIGN.md section 3):

      * single device (:func:`batched_single_source`): the slab covers
        all ``n`` nodes, ``gather`` is the identity;
      * model-axis pod push (:func:`batched_single_source_sharded`):
        the slab is this shard's node rows, ``d`` stays replicated
        (``d_offset=0``), ``gather`` all-gathers the pruned frontier
        over "model";
      * node-sharded serving (core/shard_query.py): the slab is this
        shard's rows with ``d`` sharded alongside (``d_offset`` =
        ``slab_start``), ``gather`` runs over the "data" axis.

    ku/xu: (B, W) packed H rows of the query nodes (replicated across
    shards); ``d`` is indexed at (key target - d_offset); ``src`` holds
    frontier-global edge sources, ``dst`` slab-local destinations;
    ``tau`` is the resolved prune threshold (:func:`prune_tau`).
    Returns (B, slab_size) float32 scores for the slab's nodes.
    """
    B = ku.shape[0]
    slab_size = n if slab_size is None else slab_size
    d_offset = slab_start if d_offset is None else d_offset
    ls = jnp.where(ku == INT32_PAD_KEY, -1, ku // n)
    ks = jnp.clip(ku % n, 0, n - 1)
    contrib = xu * d[jnp.clip(ks - d_offset, 0, d.shape[0] - 1)]
    k_loc = ks - slab_start
    in_slab = (k_loc >= 0) & (k_loc < slab_size)
    k_loc = jnp.clip(k_loc, 0, slab_size - 1)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]

    def seed(l):
        sel = jnp.where((ls == l) & in_slab, contrib, 0.0)    # (B, W)
        z = jnp.zeros((B, slab_size), jnp.float32)
        return z.at[rows, k_loc].add(sel)

    def push(x):
        xp = jnp.where(x > tau, x, 0.0)                       # (B, slab)
        xg = xp if gather is None else gather(xp)             # (B, frontier)
        msgs = xg[:, src] * w[None, :]                        # (B, E)
        return jax.vmap(lambda mm: compat.segment_sum(
            mm, dst, num_segments=slab_size))(msgs)

    acc = seed(l_max)
    for l in range(l_max - 1, -1, -1):  # unrolled; l_max is static
        acc = push(acc) + seed(l)
    return acc


# ----------------------------------------------------------------------
# batched device path: (B,) query nodes -> (B, n) scores
# ----------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n", "l_max"))
def batched_single_source(keys, vals, d, edge_src, edge_dst, w,
                          us, tau, n: int, l_max: int):
    """Horner push for a batch of sources entirely on device.

    keys/vals: packed HP table (N, K); us: (B,) int32; ``tau``: the
    resolved prune threshold (:func:`prune_tau`). Returns (B, n)
    float32.
    """
    return horner_push(keys[us], vals[us], d, edge_src, edge_dst, w,
                       tau, n=n, l_max=l_max)


@partial(jax.jit, static_argnames=("n", "l_max", "bn", "eb", "interpret"))
def batched_single_source_pallas(keys, vals, d, blk_src, blk_dstl,
                                 blk_w, us, tau, n: int, l_max: int,
                                 bn: int, eb: int, interpret: bool):
    """Pallas-backed twin of :func:`batched_single_source`.

    Same (B, n) float32 result (up to float32 reduction order -- the
    blocked layout sums each destination's messages in ELL order, the
    lax path in edge-list order); takes the (NB, E_pad) blocked edge
    layout (``kernels/horner_push.block_align_edges``) in place of the
    flat edge arrays. Kept as a separate jit so the two backends never
    share a cache entry and ``_cache_size`` gates can tell them apart.
    """
    from repro.kernels.horner_push import ops as hp_ops
    return hp_ops.horner_push_pallas(
        keys[us], vals[us], d, blk_src, blk_dstl, blk_w, tau,
        n=n, l_max=l_max, bn=bn, eb=eb, interpret=interpret)


def single_source_device(idx, g: csr.Graph, us: np.ndarray,
                         backend: str | None = None) -> np.ndarray:
    """One-shot batched device path. The index/graph upload is warm
    after the first call (core/device_state.py), so repeated calls
    measure query compute, not H2D transfer.

    ``backend``: "lax" | "pallas" | None/"auto" (defer to the
    process-wide switch, ``repro.kernels.horner_push``).
    """
    from repro.core import device_state
    from repro.kernels import interpret_mode
    from repro.kernels.horner_push import resolve_push_backend
    st = device_state.serving_arrays(idx, g)
    if resolve_push_backend(backend) == "pallas":
        bl = device_state.blocked_push_arrays(idx, g)
        out = batched_single_source_pallas(
            st.keys, st.vals, st.d, bl.blk_src, bl.blk_dstl, bl.blk_w,
            jnp.asarray(us, jnp.int32), jnp.float32(st.tau),
            idx.n, idx.plan.l_max, bl.bn, bl.eb,
            interpret=interpret_mode())
    else:
        out = batched_single_source(
            st.keys, st.vals, st.d, st.edge_src, st.edge_dst, st.w,
            jnp.asarray(us, jnp.int32), jnp.float32(st.tau),
            idx.n, idx.plan.l_max)
    return np.asarray(out)


def single_source_batch(idx, g: csr.Graph, us,
                        mesh=None, axis: str = "data") -> np.ndarray:
    """Public multi-source batched entry point: (B,) ids -> (B, n).

    Sources are vmapped inside one compiled program, so a serving
    micro-batch amortizes a single compile (and, with ``mesh``, a
    single mesh fan-out) across all B queries. With ``mesh`` the query
    runs node-sharded over ``mesh[axis]`` (core/shard_query.py); for a
    long-lived serving loop prefer building the
    :class:`~repro.core.shard_query.ShardedIndex` once (or use
    :class:`~repro.serve.QueryEngine` with ``EngineConfig(mesh=...)``)
    instead of re-uploading per call.
    """
    us = np.atleast_1d(np.asarray(us, np.int32))
    if mesh is None:
        return single_source_device(idx, g, us)
    from repro.core import shard_query
    si = shard_query.shard_index(idx, g, mesh, axis=axis)
    return shard_query.sharded_single_source(si, us)


def single_source_naive(idx, g: csr.Graph, u: int) -> np.ndarray:
    """n invocations of Alg 3 (the paper's strawman; Figure 2)."""
    return np.array([idx.query_pair_host(u, v, g) for v in range(idx.n)])


# ----------------------------------------------------------------------
# pod-scale path: shard_map Horner push with dst-partitioned edges
# ----------------------------------------------------------------------
def batched_single_source_sharded(keys, vals, d, blk_src, blk_dstl,
                                  blk_w, us, tau: float, n: int,
                                  l_max: int, mesh,
                                  bf16_frontier: bool = False):
    """Pod-scale Alg 6 (Horner form): queries sharded over the data
    axes, nodes over "model"; per push the frontier is all-gathered over
    "model" only (the single collective) and the segment-sum lands on
    local node rows via dst-partitioned edge blocks -- the same layout
    and argument as models/gnn_sharded.py (GSPMD's scatter handling
    otherwise all-reduces the full (B, n) frontier per push;
    EXPERIMENTS.md section Perf, sling-serve iteration).

    keys/vals: full (N, W) packed rows gathered for us on the fly;
    blk_*: (NS_m, E_max) edges grouped by dst model-shard; ``tau``: the
    resolved prune threshold (:func:`prune_tau`). Returns (B, n)
    scores sharded (data, model).
    """
    from jax.sharding import PartitionSpec as P
    data_axes = tuple(a for a in ("pod", "data")
                      if a in mesh.shape and mesh.shape[a] > 1)
    ns_m = mesh.shape["model"]
    n_l = n // ns_m
    manual = set(data_axes) | {"model"}

    def local(ku, xu, d_full, bs, bd, bw):
        midx = jax.lax.axis_index("model")

        def gather(xp):
            if bf16_frontier:
                # halves the dominant AG payload; bf16 rel-err ~2^-8
                # per push accumulates to <~1% of each score -- callers
                # must fold it into the eps budget (perf-mode only).
                # optimization_barrier stops XLA's simplifier from
                # commuting the converts back across the all-gather.
                xp = jax.lax.optimization_barrier(
                    xp.astype(jnp.bfloat16))
            x_full = jax.lax.all_gather(xp, "model", axis=1, tiled=True)
            if bf16_frontier:
                x_full = jax.lax.optimization_barrier(x_full)
            return x_full.astype(jnp.float32)

        return horner_push(ku, xu, d_full, bs[0], bd[0], bw[0], tau,
                           n=n, l_max=l_max, slab_start=midx * n_l,
                           slab_size=n_l, d_offset=0, gather=gather)

    from repro import compat
    sm = compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(data_axes, None), P(data_axes, None), P(),
                  P(("model",), None), P(("model",), None),
                  P(("model",), None)),
        out_specs=P(data_axes, ("model",)),
        axis_names=manual)
    ku = keys[us]
    xu = vals[us]
    return sm(ku, xu, d, blk_src, blk_dstl, blk_w)
