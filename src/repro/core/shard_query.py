"""Mesh-partitioned SLING serving: the node-sharded index and the
shard_map single-source / top-k fan-out (DESIGN.md section 8).

SLING's near-optimal O(n/eps) single-source bound is per device; to
serve graphs larger than one device's memory (and to scale query
throughput with the mesh) the index itself is partitioned. Shard s of
an S-way mesh axis owns the node slab [s*n_loc, (s+1)*n_loc):

  * its slab of packed HP rows (``hp_index.pad_packed_rows``),
  * its slice of the diagonal correction vector d,
  * every graph edge whose *destination* lands in the slab
    (``partition_edges``), with slab-local dst ids.

A query is a three-stage fan-out inside one ``shard_map`` program:

  1. **psum row fetch** -- the query ids are replicated; each shard
     contributes the packed H(u) rows it owns (zeros elsewhere) and a
     single ``lax.psum`` makes the (B, W) rows replicated. The owner is
     unique, so the sum *is* the row -- including the INT32_PAD_KEY
     sentinel, which survives because non-owners add exactly 0.
  2. **Horner push over the local slab** -- the shared
     :func:`~repro.core.single_source.horner_push` kernel seeds only
     the slab's targets (reading the local d slice) and per push
     all-gathers the pruned frontier over the mesh axis (the single
     collective per step), landing the segment-sum on local rows via
     the dst-partitioned edge block.
  3. **merge** -- single-source emits the slab scores with
     ``out_specs P(None, axis)`` (the global (B, n_pad) matrix, node
     dim sharded); top-k takes a shard-local ``lax.top_k`` over the
     slab (pad rows masked to -1, below every real score) and merges
     the all-gathered (B, S*k') candidates with a second ``top_k``.
     Shard-concatenation order equals global id order, so ties still
     break toward the smaller node id, exactly like the single-device
     path.

Shapes are swap-stable: rows are padded to ``width_cap`` and edge
blocks to ``edge_cap`` capacity buckets (``hp_index.capacity_bucket``),
so a hot-swapped repaired index re-uses every compiled program until a
bucket overflows -- the same contract as the engine's single-device
arrays (DESIGN.md section 7). The fan-out kernels are module-level jits
keyed on (mesh, axis, static shapes): rebuilding a ShardedIndex for a
swap hits the same executable.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro import compat
from repro.core import hp_index
from repro.core.single_source import horner_push, prune_tau
from repro.graph import csr
from repro.kernels import interpret_mode
from repro.launch.sharding import sling_index_specs


def serving_mesh(n_shards: int, axis: str = "data"):
    """1-D serving mesh over the first ``n_shards`` local devices."""
    if jax.device_count() < n_shards:
        raise RuntimeError(
            f"mesh needs {n_shards} devices, found {jax.device_count()} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before jax initializes)")
    return compat.make_mesh((n_shards,), (axis,))


def required_edge_cap(g: csr.Graph, n_shards: int, n_loc: int) -> int:
    """Largest per-shard dst-partitioned edge count (>= 1)."""
    if g.m == 0:
        return 1
    counts = np.bincount(g.edge_dst // n_loc, minlength=n_shards)
    return int(counts.max())


def partition_edges(g: csr.Graph, sqrt_c: float, n_shards: int,
                    n_loc: int, edge_cap: int):
    """Group the pull-oriented edge list by destination shard.

    Returns (blk_src, blk_dstl, blk_w), each (n_shards, edge_cap):
    global source ids, slab-local destination ids, and pull weights
    sqrt(c)/|I(dst)|. Pad slots are (src 0, dst_local 0, weight 0) --
    an additive no-op in every push, so padded and exact dispatch agree
    bit for bit (same convention as the engine's edge buckets).
    """
    if edge_cap < required_edge_cap(g, n_shards, n_loc):
        raise ValueError("edge_cap below the largest shard block")
    w = csr.normalized_pull_weights(g, sqrt_c)
    shard = g.edge_dst // n_loc
    counts = np.bincount(shard, minlength=n_shards)
    order = np.argsort(shard, kind="stable")
    bs = np.zeros((n_shards, edge_cap), np.int32)
    bdl = np.zeros((n_shards, edge_cap), np.int32)
    bw = np.zeros((n_shards, edge_cap), np.float32)
    off = 0
    for s in range(n_shards):
        es = order[off:off + counts[s]]
        off += counts[s]
        bs[s, :len(es)] = g.edge_src[es]
        bdl[s, :len(es)] = g.edge_dst[es] - s * n_loc
        bw[s, :len(es)] = w[es]
    return bs, bdl, bw


@dataclasses.dataclass
class ShardedIndex:
    """Device state of a node-sharded SLING index over one mesh axis."""
    mesh: object
    axis: str
    n: int
    n_pad: int
    n_loc: int
    n_shards: int
    l_max: int
    tau: float           # resolved Horner prune threshold (prune_tau)
    width_cap: int       # packed-row capacity bucket
    edge_cap: int        # per-shard edge-block capacity bucket
    keys: jax.Array      # (n_pad, width_cap)  P(axis, None)
    vals: jax.Array      # (n_pad, width_cap)  P(axis, None)
    d: jax.Array         # (n_pad,)            P(axis)
    blk_src: jax.Array   # (n_shards, edge_cap) P(axis, None)
    blk_dstl: jax.Array
    blk_w: jax.Array
    epoch: int = 0
    # Pallas push backend state (kernels/horner_push, DESIGN.md §11):
    # per-shard dest-block-grouped edges, built by shard_index when the
    # resolved push backend is "pallas". pblk_cap is the per-node-block
    # width capacity bucket (the swap-stability knob for the blocked
    # layout, the analogue of edge_cap for the flat per-shard blocks).
    pblk_src: jax.Array | None = None   # (S, NB_loc, pblk_cap) P(axis,)
    pblk_dstl: jax.Array | None = None
    pblk_w: jax.Array | None = None
    bn: int = 0
    eb: int = 0
    pblk_cap: int = 0

    def nbytes_per_shard(self) -> int:
        """Device bytes each shard holds (the memory-scaling claim)."""
        total = sum(int(a.size) * a.dtype.itemsize for a in
                    (self.keys, self.vals, self.d, self.blk_src,
                     self.blk_dstl, self.blk_w))
        return total // self.n_shards


def required_pblk_width(g: csr.Graph, n_shards: int, n_loc: int,
                        bn: int) -> int:
    """Largest per-(shard, node-block) edge count (>= 1) for the
    Pallas blocked layout -- the quantity ``pblk_cap`` buckets."""
    if g.m == 0:
        return 1
    shard = g.edge_dst // n_loc
    nb_loc = max(1, -(-n_loc // bn))
    key = shard * nb_loc + (g.edge_dst - shard * n_loc) // bn
    return int(np.bincount(key).max())


def partition_blocked_edges(g: csr.Graph, sqrt_c: float, n_shards: int,
                            n_loc: int, *, bn: int, eb: int,
                            width_cap: int):
    """Per-shard dest-block-grouped edges for the Pallas push backend.

    Returns (pbs, pbdl, pbw), each (n_shards, NB_loc, width_cap):
    shard s's slab edges in the ``kernels/horner_push`` ELL layout
    (frontier-global src, block-local dst, -1 pads). ``width_cap``
    must be a multiple of eb and >= :func:`required_pblk_width` so
    every shard shares one compiled grid shape.
    """
    from repro.kernels.horner_push import ops as hp_ops
    if width_cap % eb or width_cap < required_pblk_width(
            g, n_shards, n_loc, bn):
        raise ValueError(f"pblk width_cap {width_cap} below requirement "
                         "or not a multiple of eb")
    w = csr.normalized_pull_weights(g, sqrt_c)
    shard = g.edge_dst // n_loc
    out = []
    for s in range(n_shards):
        m = shard == s
        out.append(hp_ops.block_align_edges(
            g.edge_src[m], g.edge_dst[m] - s * n_loc, w[m], n_loc,
            bn=bn, eb=eb, width_floor=width_cap))
    pbs, pbdl, pbw = (np.stack([t[i] for t in out]) for i in range(3))
    return pbs, pbdl, pbw


def shard_index(idx, g: csr.Graph, mesh, axis: str = "data",
                width_cap: int | None = None,
                edge_cap: int | None = None,
                cap_quantum: int = 64,
                headroom: float = 1.25,
                push_backend: str | None = None,
                pblk_cap: int | None = None,
                bn: int | None = None,
                eb: int | None = None) -> ShardedIndex:
    """Partition a built SlingIndex + graph across ``mesh.shape[axis]``.

    ``width_cap``/``edge_cap``/``pblk_cap`` are capacity-bucket
    *floors* (pass the previous ShardedIndex's caps on hot-swap to keep
    compiled shapes); when the index does not fit a floor the cap grows
    to ``hp_index.capacity_bucket`` of the requirement -- callers that
    care (QueryEngine) detect the growth and count the recompile.

    ``push_backend`` ("lax" | "pallas" | None/"auto", resolved via
    ``repro.kernels.horner_push``) controls whether the per-shard
    blocked edge layout for the Pallas kernel is built alongside the
    flat blocks (the flat blocks always exist -- they back the lax
    fallback and the bf16-frontier pod path).
    """
    from repro.kernels.horner_push import ops as hp_ops
    from repro.kernels.horner_push import resolve_push_backend
    S = int(mesh.shape[axis])
    n_pad, n_loc = hp_index.shard_layout(idx.n, S)
    wc = int(width_cap or 0)
    if wc < idx.hp.width:
        wc = hp_index.capacity_bucket(idx.hp.width, cap_quantum, headroom)
    ec = int(edge_cap or 0)
    e_req = required_edge_cap(g, S, n_loc)
    if ec < e_req:
        ec = hp_index.capacity_bucket(e_req, cap_quantum, headroom)

    # dequantized_hp: shard slabs are built fp32 (quantization is a
    # storage format; device arrays stay fp32 on every backend)
    keys, vals = hp_index.pad_packed_rows(idx.dequantized_hp(), n_pad, wc)
    d = np.zeros(n_pad, np.float32)
    d[:idx.n] = np.asarray(idx.d, np.float32)
    bs, bdl, bw = partition_edges(g, idx.plan.sqrt_c, S, n_loc, ec)

    specs = sling_index_specs(axis)

    def put(x, spec):
        return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))

    pallas_state: dict = {}
    if resolve_push_backend(push_backend) == "pallas":
        bn = bn or hp_ops.DEFAULT_BN
        eb = eb or hp_ops.DEFAULT_EB
        pc = int(pblk_cap or 0)
        p_req = required_pblk_width(g, S, n_loc, bn)
        if pc < p_req:
            pc = hp_index.capacity_bucket(p_req, cap_quantum, headroom)
        pc = -(-pc // eb) * eb   # grid shape needs an eb multiple
        pbs, pbdl, pbw = partition_blocked_edges(
            g, idx.plan.sqrt_c, S, n_loc, bn=bn, eb=eb, width_cap=pc)
        pallas_state = dict(
            pblk_src=put(pbs, specs["pblk"]),
            pblk_dstl=put(pbdl, specs["pblk"]),
            pblk_w=put(pbw, specs["pblk"]),
            bn=bn, eb=eb, pblk_cap=pc)

    return ShardedIndex(
        mesh=mesh, axis=axis, n=idx.n, n_pad=n_pad, n_loc=n_loc,
        n_shards=S, l_max=idx.plan.l_max, tau=prune_tau(idx.plan),
        width_cap=wc, edge_cap=ec,
        keys=put(keys, specs["keys"]), vals=put(vals, specs["vals"]),
        d=put(d, specs["d"]), blk_src=put(bs, specs["blk_src"]),
        blk_dstl=put(bdl, specs["blk_dstl"]),
        blk_w=put(bw, specs["blk_w"]), epoch=idx.epoch,
        **pallas_state)


# ----------------------------------------------------------------------
# shard_map fan-out kernels
# ----------------------------------------------------------------------
def _replicate_query_rows(keys, vals, us, n_loc: int, axis: str):
    """psum row fetch: (B,) replicated query ids -> replicated (B, W)
    packed rows from the row-sharded table. Each shard contributes the
    rows it owns and zeros elsewhere; the owner is unique, so the psum
    reconstructs the row exactly (PAD keys included: non-owners add 0).
    """
    i = jax.lax.axis_index(axis)
    u_loc = us - i * n_loc
    mine = (u_loc >= 0) & (u_loc < n_loc)
    uc = jnp.clip(u_loc, 0, n_loc - 1)
    ku = jnp.where(mine[:, None], keys[uc], 0)
    xu = jnp.where(mine[:, None], vals[uc], 0.0)
    return jax.lax.psum(ku, axis), jax.lax.psum(xu, axis)


def _slab_scores(keys, vals, d, bs, bdl, bw, us, tau, *, axis: str,
                 n: int, n_loc: int, l_max: int):
    """Stages 1+2 of the fan-out: replicated rows, then the shared
    Horner push over this shard's slab (frontier all-gathered over
    ``axis`` per step). Returns (B, n_loc) slab scores."""
    ku, xu = _replicate_query_rows(keys, vals, us, n_loc, axis)
    i = jax.lax.axis_index(axis)

    def gather(xp):
        return jax.lax.all_gather(xp, axis, axis=1, tiled=True)

    return horner_push(ku, xu, d, bs[0], bdl[0], bw[0], tau,
                       n=n, l_max=l_max, slab_start=i * n_loc,
                       slab_size=n_loc, gather=gather)


def _slab_scores_pallas(keys, vals, d, pbs, pbd, pbw, us, tau, *,
                        axis: str, n: int, n_loc: int, l_max: int,
                        bn: int, eb: int, interpret: bool):
    """Pallas twin of :func:`_slab_scores`: same psum row fetch, then
    the fused kernel over this shard's slab. The per-step frontier
    all-gather stays *outside* the kernel (a collective cannot run
    inside a Pallas grid program); the kernel's at-gather-time prune is
    elementwise, so prune-then-gather and gather-then-prune agree
    exactly (DESIGN.md section 11). The kernel works node-major, so
    the gather concatenates slabs over axis 0."""
    from repro.kernels.horner_push import ops as hp_ops
    ku, xu = _replicate_query_rows(keys, vals, us, n_loc, axis)
    i = jax.lax.axis_index(axis)

    def gather(xp):   # (n_loc, B) node-major slab frontier
        return jax.lax.all_gather(xp, axis, axis=0, tiled=True)

    return hp_ops.horner_push_pallas(
        ku, xu, d, pbs[0], pbd[0], pbw[0], tau, n=n, l_max=l_max,
        bn=bn, eb=eb, slab_start=i * n_loc, slab_size=n_loc,
        gather=gather, interpret=interpret)


def _index_in_specs(axis: str):
    s = sling_index_specs(axis)
    return (s["keys"], s["vals"], s["d"], s["blk_src"], s["blk_dstl"],
            s["blk_w"], s["queries"])


def _pallas_in_specs(axis: str):
    s = sling_index_specs(axis)
    return (s["keys"], s["vals"], s["d"], s["pblk"], s["pblk"],
            s["pblk"], s["queries"])


@partial(jax.jit,
         static_argnames=("mesh", "axis", "n", "n_loc", "l_max"))
def _sharded_source(keys, vals, d, blk_src, blk_dstl, blk_w, us, tau, *,
                    mesh, axis: str, n: int, n_loc: int, l_max: int):
    """(B,) ids -> (B, n_pad) scores, node dim sharded over ``axis``."""
    from jax.sharding import PartitionSpec as P

    def local(keys, vals, d, bs, bdl, bw, us):
        return _slab_scores(keys, vals, d, bs, bdl, bw, us, tau,
                            axis=axis, n=n, n_loc=n_loc, l_max=l_max)

    sm = compat.shard_map(local, mesh=mesh, in_specs=_index_in_specs(axis),
                          out_specs=P(None, (axis,)))
    return sm(keys, vals, d, blk_src, blk_dstl, blk_w, us)


@partial(jax.jit,
         static_argnames=("mesh", "axis", "n", "n_loc", "l_max", "k"))
def _sharded_topk(keys, vals, d, blk_src, blk_dstl, blk_w, us, tau, *,
                  mesh, axis: str, n: int, n_loc: int, l_max: int,
                  k: int):
    """(B,) ids -> replicated ((B, k) scores, (B, k) global node ids).

    Shard-local top-k over the slab feeds a global merge: each shard's
    candidate list covers its true top-min(k, n_loc) (the global top-k
    restricted to a slab can never be longer), so the merged
    ``top_k`` over the S * min(k, n_loc) >= k all-gathered candidates
    is exact.
    """
    from jax.sharding import PartitionSpec as P
    k_loc = min(k, n_loc)

    def local(keys, vals, d, bs, bdl, bw, us):
        acc = _slab_scores(keys, vals, d, bs, bdl, bw, us, tau,
                           axis=axis, n=n, n_loc=n_loc, l_max=l_max)
        i = jax.lax.axis_index(axis)
        gids = i * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
        # pad rows (global id >= n) must never win: scores are >= 0
        masked = jnp.where(gids[None, :] < n, acc, -1.0)
        v_l, i_l = jax.lax.top_k(masked, k_loc)
        g_l = i * n_loc + i_l.astype(jnp.int32)
        vc = jax.lax.all_gather(v_l, axis, axis=1, tiled=True)
        gc = jax.lax.all_gather(g_l, axis, axis=1, tiled=True)
        # concat order == global id order, so equal scores resolve to
        # the smaller node id, matching single-device lax.top_k
        v_m, pos = jax.lax.top_k(vc, k)
        return v_m, jnp.take_along_axis(gc, pos, axis=1)

    sm = compat.shard_map(local, mesh=mesh, in_specs=_index_in_specs(axis),
                          out_specs=(P(None, None), P(None, None)))
    return sm(keys, vals, d, blk_src, blk_dstl, blk_w, us)


@partial(jax.jit,
         static_argnames=("mesh", "axis", "n", "n_loc", "l_max", "bn",
                          "eb", "interpret"))
def _sharded_source_pallas(keys, vals, d, pbs, pbd, pbw, us, tau, *,
                           mesh, axis: str, n: int, n_loc: int,
                           l_max: int, bn: int, eb: int,
                           interpret: bool):
    """Pallas twin of :func:`_sharded_source` (separate jit: the two
    backends close over different edge layouts and must never share a
    cache entry)."""
    from jax.sharding import PartitionSpec as P

    def local(keys, vals, d, bs, bd, bw, us):
        return _slab_scores_pallas(keys, vals, d, bs, bd, bw, us, tau,
                                   axis=axis, n=n, n_loc=n_loc,
                                   l_max=l_max, bn=bn, eb=eb,
                                   interpret=interpret)

    sm = compat.shard_map(local, mesh=mesh,
                          in_specs=_pallas_in_specs(axis),
                          out_specs=P(None, (axis,)))
    return sm(keys, vals, d, pbs, pbd, pbw, us)


@partial(jax.jit,
         static_argnames=("mesh", "axis", "n", "n_loc", "l_max", "k",
                          "bn", "eb", "interpret"))
def _sharded_topk_pallas(keys, vals, d, pbs, pbd, pbw, us, tau, *,
                         mesh, axis: str, n: int, n_loc: int,
                         l_max: int, k: int, bn: int, eb: int,
                         interpret: bool):
    """Pallas twin of :func:`_sharded_topk`: the fused slab push feeds
    the identical shard-local top-k + global merge, so tie-breaking
    and the exactness argument carry over unchanged."""
    from jax.sharding import PartitionSpec as P
    k_loc = min(k, n_loc)

    def local(keys, vals, d, bs, bd, bw, us):
        acc = _slab_scores_pallas(keys, vals, d, bs, bd, bw, us, tau,
                                  axis=axis, n=n, n_loc=n_loc,
                                  l_max=l_max, bn=bn, eb=eb,
                                  interpret=interpret)
        i = jax.lax.axis_index(axis)
        gids = i * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
        masked = jnp.where(gids[None, :] < n, acc, -1.0)
        v_l, i_l = jax.lax.top_k(masked, k_loc)
        g_l = i * n_loc + i_l.astype(jnp.int32)
        vc = jax.lax.all_gather(v_l, axis, axis=1, tiled=True)
        gc = jax.lax.all_gather(g_l, axis, axis=1, tiled=True)
        v_m, pos = jax.lax.top_k(vc, k)
        return v_m, jnp.take_along_axis(gc, pos, axis=1)

    sm = compat.shard_map(local, mesh=mesh,
                          in_specs=_pallas_in_specs(axis),
                          out_specs=(P(None, None), P(None, None)))
    return sm(keys, vals, d, pbs, pbd, pbw, us)


# ----------------------------------------------------------------------
# public query entry points
# ----------------------------------------------------------------------
def _resolve_si_backend(si: ShardedIndex, backend: str | None) -> str:
    from repro.kernels.horner_push import resolve_push_backend
    resolved = resolve_push_backend(backend)
    if resolved == "pallas" and si.pblk_src is None:
        if backend is not None:
            raise ValueError(
                "ShardedIndex was built without the pallas edge layout; "
                "rebuild with shard_index(..., push_backend='pallas')")
        resolved = "lax"   # process default: fall back quietly
    return resolved


def sharded_single_source(si: ShardedIndex, us,
                          backend: str | None = None) -> np.ndarray:
    """Batched single-source over the mesh: (B,) ids -> (B, n).

    ``backend``: "lax" | "pallas" | None/"auto". The pallas route
    needs a ShardedIndex built with ``push_backend="pallas"``; with
    the default/auto backend an index lacking the blocked layout falls
    back to lax rather than failing mid-serve.
    """
    us = jnp.asarray(np.atleast_1d(np.asarray(us, np.int32)))
    if _resolve_si_backend(si, backend) == "pallas":
        out = _sharded_source_pallas(
            si.keys, si.vals, si.d, si.pblk_src, si.pblk_dstl,
            si.pblk_w, us, jnp.float32(si.tau), mesh=si.mesh,
            axis=si.axis, n=si.n, n_loc=si.n_loc, l_max=si.l_max,
            bn=si.bn, eb=si.eb,
            interpret=interpret_mode())
    else:
        out = _sharded_source(
            si.keys, si.vals, si.d, si.blk_src, si.blk_dstl, si.blk_w,
            us, jnp.float32(si.tau), mesh=si.mesh, axis=si.axis,
            n=si.n, n_loc=si.n_loc, l_max=si.l_max)
    return np.asarray(out)[:, :si.n]


def sharded_topk(si: ShardedIndex, us, k: int,
                 backend: str | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Batched top-k over the mesh; k clamped to n.

    Returns ((B, k) scores descending, (B, k) int32 node ids), ties
    toward smaller ids -- the same contract as ``topk_device``.
    ``backend`` routes the slab push exactly like
    :func:`sharded_single_source`.
    """
    k = max(1, min(int(k), si.n))
    us = jnp.asarray(np.atleast_1d(np.asarray(us, np.int32)))
    if _resolve_si_backend(si, backend) == "pallas":
        v, i = _sharded_topk_pallas(
            si.keys, si.vals, si.d, si.pblk_src, si.pblk_dstl,
            si.pblk_w, us, jnp.float32(si.tau), mesh=si.mesh,
            axis=si.axis, n=si.n, n_loc=si.n_loc, l_max=si.l_max,
            k=k, bn=si.bn, eb=si.eb,
            interpret=interpret_mode())
    else:
        v, i = _sharded_topk(
            si.keys, si.vals, si.d, si.blk_src, si.blk_dstl, si.blk_w,
            us, jnp.float32(si.tau), mesh=si.mesh, axis=si.axis,
            n=si.n, n_loc=si.n_loc, l_max=si.l_max, k=k)
    return np.asarray(v), np.asarray(i)
