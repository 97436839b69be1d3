"""Unified SimRank query engine: one front-end for all three query types.

``QueryEngine`` serves single-pair, single-source, and top-k queries
from a built :class:`~repro.core.index.SlingIndex` with the properties
a traffic-serving system needs (README section "Serving"):

  * **fixed batch shapes** -- requests of any size are chunked and
    padded to the configured batch sizes, so each query type compiles
    exactly once and every later request reuses the compiled program
    (no per-shape recompiles; ``stats()["unique_shapes"]`` stays
    constant under arbitrary request sizes);
  * **k-bucketing** -- top-k requests round k up to the next configured
    bucket and slice the answer, so odd k values share programs;
  * **LRU score cache** -- repeated queries (hot nodes dominate real
    query streams) are answered from an LRU keyed by
    (type, node(s), bucket) without touching the device;
  * **warmup priming** -- ``warmup()`` compiles every fixed shape ahead
    of traffic so the first real request is served at steady-state
    latency;
  * **pluggable pair backend** -- the batched pair path runs either the
    vmapped searchsorted join (core/index.py) or the Pallas all-pairs
    equality-join kernel (kernels/hp_join, DESIGN.md section 2); "auto"
    picks the kernel on a TPU and the searchsorted join elsewhere;
  * **node-sharded serving** -- with ``EngineConfig(mesh=...)`` the
    index partitions across the mesh axis and single-source/top-k
    queries dispatch through the shard_map fan-out
    (core/shard_query.py, DESIGN.md section 8); batching, k-bucketing,
    caching and hot-swap semantics are unchanged, and swaps re-use the
    compiled fan-out programs via the same capacity-bucket contract;
  * **materialized kNN lookups** -- ``attach_knn()`` installs a bulk
    join artifact (:mod:`repro.join`, DESIGN.md section 10) and
    ``knn(u)`` answers "k most similar to u" as an O(1) host lookup
    with an epoch staleness check against hot-swapped indices;
  * **epoch-based hot-swap** -- ``swap_index()`` installs an
    incrementally repaired index (core/update.py) behind the same
    compiled executables: device arrays live in capacity buckets
    (width/edge count with headroom), so a swap is an upload plus
    targeted cache invalidation, not a recompile (DESIGN.md
    section 7); ``stats()`` reports swap latency and any bucket
    overflows.

The engine is deliberately synchronous: batching policy (how requests
accumulate into a batch) lives in the caller; this layer guarantees
that however requests arrive, the device only ever sees the fixed
shapes it has already compiled.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hp_index
from repro.core.hp_index import INT32_PAD_KEY
from repro.core.index import SlingIndex, _pair_query_batch
from repro.core.single_source import batched_single_source, prune_tau
from repro.core.topk import batched_topk
from repro.graph import csr
from repro.kernels import interpret_mode


class _LRU:
    """Minimal LRU map with total and per-query-kind hit/miss
    accounting (keys lead with the kind tag: "pair" / "src" /
    "topk")."""

    def __init__(self, cap: int):
        self.cap = cap
        self._d: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.hits_by_kind: dict[str, int] = {}
        self.misses_by_kind: dict[str, int] = {}

    def get(self, key):
        kind = key[0]
        if self.cap > 0 and key in self._d:
            self._d.move_to_end(key)
            self.hits += 1
            self.hits_by_kind[kind] = self.hits_by_kind.get(kind, 0) + 1
            return self._d[key]
        self.misses += 1
        self.misses_by_kind[kind] = self.misses_by_kind.get(kind, 0) + 1
        return None

    def put(self, key, value) -> None:
        if self.cap <= 0:
            return
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.cap:
            self._d.popitem(last=False)

    def __len__(self) -> int:
        return len(self._d)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    pair_batch: int = 256        # fixed pair-path batch shape
    source_batch: int = 8        # fixed single-source/top-k batch shape
    k_buckets: tuple[int, ...] = (1, 16, 64, 256)
    cache_size: int = 256        # LRU entries across all query types
    pair_backend: str = "auto"   # "auto" | "join" | "pallas"
    # Horner-push backend for single-source/top-k (DESIGN.md §11):
    # "lax" | "pallas" | "auto" ("auto" defers to the process-wide
    # switch in repro.kernels.horner_push, which itself resolves to
    # lax on every platform). Resolved once at engine construction so
    # a long-lived engine never flips programs mid-traffic.
    push_backend: str = "auto"
    # hot-swap shape stability (DESIGN.md section 7): device arrays are
    # padded to capacity buckets with this headroom, so a repaired
    # index whose packed width or edge count grew a little swaps in
    # under the *same* compiled programs. A swap only recompiles when
    # the new index overflows its bucket (counted in stats()).
    swap_headroom: float = 1.25
    cap_quantum: int = 64        # buckets are multiples of this
    # node-sharded serving (DESIGN.md section 8): a jax Mesh whose
    # ``mesh_axis`` partitions the index's node slabs; single-source
    # and top-k dispatch through the shard_map fan-out
    # (core/shard_query.py). None = single-device. The pair path stays
    # on the default device -- its merge join reads two packed rows,
    # not the graph, so fanning it out would add a collective per pair
    # for no memory win.
    mesh: object = None
    mesh_axis: str = "data"
    # serve an index whose diagonal carries no eps_d certificate
    # (build_index_scale(uncertified_diagonal=True), recorded in the
    # artifact header). Off by default: an uncertified d silently
    # voids the Theorem-1 bound every answer is sold under, so the
    # engine refuses unless the operator opts in explicitly.
    allow_uncertified: bool = False


class QueryEngine:
    """Front-end over a SlingIndex for all three SimRank query types."""

    def __init__(self, index: SlingIndex, g: csr.Graph,
                 config: EngineConfig | None = None):
        self.cfg = config or EngineConfig()
        if getattr(index, "uncertified_d", False) \
                and not self.cfg.allow_uncertified:
            raise ValueError(
                "index diagonal is uncertified (built with "
                "uncertified_diagonal=True): the Theorem-1 eps bound "
                "does not hold. Rebuild with a certified d_mode, or "
                "pass EngineConfig(allow_uncertified=True) to serve "
                "it anyway (DESIGN.md section 15)")
        backend = self.cfg.pair_backend
        if backend == "auto":
            backend = ("pallas" if jax.default_backend() == "tpu"
                       else "join")
        self._pair_backend = backend
        from repro.kernels.horner_push import resolve_push_backend
        self._push_backend = resolve_push_backend(
            None if self.cfg.push_backend == "auto"
            else self.cfg.push_backend)
        self._cache = _LRU(self.cfg.cache_size)
        self._shapes: set = set()
        # warmup dispatches prime shapes but are not traffic: they
        # count under warmup_* so stats()["batches"]/["pad_slots"]
        # measure only real requests
        self._counts = {"pair": 0, "source": 0, "topk": 0, "knn": 0,
                        "knn_stale_rejects": 0,
                        "batches": 0, "pad_slots": 0,
                        "warmup_batches": 0, "warmup_pad_slots": 0}
        self._knn = None          # attached KnnGraph artifact (if any)
        self._in_warmup = False
        self._swaps = {"swaps": 0, "last_swap_ms": 0.0,
                       "swap_recompiles": 0, "invalidated": 0}
        self._width_cap = self._bucket(index.hp.width)
        self._edge_cap = self._bucket(g.m)
        self._shard_edge_cap = 0     # set by the first sharded install
        self._pblk_cap = 0           # pallas blocked-layout width bucket
        self._shard_pblk_cap = 0
        self._install(index, g)
        assert index.n >= 1

    # ------------------------------------------------------------------
    # device state install / hot-swap
    # ------------------------------------------------------------------
    def _bucket(self, x: int) -> int:
        return hp_index.capacity_bucket(x, self.cfg.cap_quantum,
                                        self.cfg.swap_headroom)

    def _install(self, index: SlingIndex, g: csr.Graph) -> None:
        """Upload ``index``/``g`` padded to the capacity buckets.

        Shape contract: every device array a compiled program closes
        over keeps its shape as long as the new index fits the buckets
        -- keys/vals (n, width_cap), d (n,), edges (edge_cap,). Pad
        rows carry the INT32_PAD_KEY sentinel (ignored by every join)
        and pad edges carry weight 0 into segment 0 (additive no-op in
        every push), so padded and exact dispatch agree bit-for-bit.
        """
        n = index.n
        wc, ec = self._width_cap, self._edge_cap
        keys = np.full((n, wc), INT32_PAD_KEY, np.int32)
        vals = np.zeros((n, wc), np.float32)
        keys[:, :index.hp.width] = index.hp.keys
        # vals_f32: quantized indexes (core/quantize.py) dequantize
        # here, host-side -- compiled programs keep fp32 shapes/dtypes
        # for every storage scheme, so hot-swapping a quantized index
        # stays zero-recompile
        vals[:, :index.hp.width] = index.vals_f32()
        self._keys = jnp.asarray(keys)
        self._vals = jnp.asarray(vals)
        self._d = jnp.asarray(np.asarray(index.d, np.float32))
        if self.cfg.mesh is None:
            e_src = np.zeros(ec, np.int32)
            e_dst = np.zeros(ec, np.int32)
            e_w = np.zeros(ec, np.float32)
            e_src[:g.m] = g.edge_src
            e_dst[:g.m] = g.edge_dst
            e_w[:g.m] = csr.normalized_pull_weights(g, index.plan.sqrt_c)
            self._edge_src = jnp.asarray(e_src)
            self._edge_dst = jnp.asarray(e_dst)
            self._w = jnp.asarray(e_w)
        else:
            # mesh mode: source/topk dispatch through the sharded edge
            # blocks and the pair join reads only keys/vals/d -- the
            # single-device edge arrays would be dead device memory
            self._edge_src = self._edge_dst = self._w = None
        self._blk_src = self._blk_dstl = self._blk_w = None
        if self._push_backend == "pallas" and self.cfg.mesh is None:
            # blocked edge layout for the fused push kernel, padded to
            # its own capacity bucket (an eb multiple: the chunk count
            # is part of the compiled grid shape)
            from repro.kernels.horner_push import ops as hp_ops
            self._pblk_bn = hp_ops.DEFAULT_BN
            self._pblk_eb = hp_ops.DEFAULT_EB
            req = hp_ops.required_block_width(g, bn=self._pblk_bn)
            cap = max(self._pblk_cap, self._bucket(req))
            cap = -(-cap // self._pblk_eb) * self._pblk_eb
            self._pblk_cap = cap
            bs, bdl, bw = hp_ops.graph_block_layout(
                g, index.plan.sqrt_c, bn=self._pblk_bn,
                eb=self._pblk_eb, width_floor=cap)
            self._blk_src = jnp.asarray(bs)
            self._blk_dstl = jnp.asarray(bdl)
            self._blk_w = jnp.asarray(bw)
        self._tau = jnp.float32(prune_tau(index.plan))
        for a in (self._keys, self._vals, self._d, self._edge_src,
                  self._edge_dst, self._w):
            if a is not None:
                a.block_until_ready()
        # node-sharded serving state: rebuilt with the same capacity
        # buckets so a hot-swap re-uses every compiled fan-out program
        self._sharded = None
        if self.cfg.mesh is not None:
            from repro.core import shard_query
            self._sharded = shard_query.shard_index(
                index, g, self.cfg.mesh, axis=self.cfg.mesh_axis,
                width_cap=self._width_cap,
                edge_cap=self._shard_edge_cap,
                cap_quantum=self.cfg.cap_quantum,
                headroom=self.cfg.swap_headroom,
                push_backend=self._push_backend,
                pblk_cap=self._shard_pblk_cap)
            self._shard_edge_cap = self._sharded.edge_cap
            self._shard_pblk_cap = self._sharded.pblk_cap
            self._width_cap = max(self._width_cap,
                                  self._sharded.width_cap)
        self.index = index
        self.g = g

    def swap_index(self, index: SlingIndex, g: csr.Graph,
                   affected=None) -> dict:
        """Epoch-based hot-swap: install a repaired index behind the
        already-compiled executables.

        As long as the repaired index fits the engine's capacity
        buckets (width_cap / edge_cap) and keeps the plan's static
        shape parameters (n, l_max), the swap triggers **zero
        recompilations** -- it is a device upload plus cache
        invalidation. Overflow grows the bucket and is counted in
        ``stats()["swap_recompiles"]`` (the next dispatch recompiles).
        The same uncertified-diagonal refusal as construction applies:
        a hot swap must not launder an uncertified artifact past the
        certificate gate.

        ``affected`` (e.g. ``UpdateReport.affected``) restricts
        invalidation of *pair* entries to those reading an affected
        node (as an endpoint or as a meeting node whose d_k the repair
        may have re-estimated); cached single-source/top-k vectors
        hold scores for every target node, so any non-empty
        ``affected`` drops all of them. ``None`` drops the whole
        cache. Returns swap metrics (also in ``stats()``).
        """
        t0 = time.perf_counter()
        if getattr(index, "uncertified_d", False) \
                and not self.cfg.allow_uncertified:
            raise ValueError(
                "refusing to hot-swap in an uncertified-diagonal "
                "index; pass EngineConfig(allow_uncertified=True) "
                "(DESIGN.md section 15)")
        if index.n != self.index.n:
            raise ValueError("hot-swap requires a fixed node set "
                             f"({index.n} != {self.index.n}); changed n "
                             "is a rebuild + new engine")
        recompiles = 0
        if index.plan.l_max != self.index.plan.l_max:
            recompiles += 1  # l_max is a static argument of the pushes
        if index.hp.width > self._width_cap:
            self._width_cap = self._bucket(index.hp.width)
            recompiles += 1
        if self._sharded is None and g.m > self._edge_cap:
            # single-device mode only: in mesh mode no compiled
            # program closes over the (unbuilt) total-edge bucket --
            # the per-shard check below is the real one
            self._edge_cap = self._bucket(g.m)
            recompiles += 1
        if self._sharded is not None:
            # a shifted edge distribution can overflow one shard's
            # block even when the total m still fits its bucket
            # (packed-width overflow is already counted above: the
            # sharded width cap tracks self._width_cap)
            from repro.core import shard_query
            req = shard_query.required_edge_cap(
                g, self._sharded.n_shards, self._sharded.n_loc)
            if req > self._shard_edge_cap:
                recompiles += 1
            if self._push_backend == "pallas":
                p_req = shard_query.required_pblk_width(
                    g, self._sharded.n_shards, self._sharded.n_loc,
                    self._sharded.bn)
                if p_req > self._shard_pblk_cap:
                    recompiles += 1
        elif self._push_backend == "pallas":
            # blocked-layout bucket: E_pad is part of the pallas grid
            # shape, so a per-node-block width overflow recompiles even
            # when the total edge count still fits self._edge_cap
            from repro.kernels.horner_push import ops as hp_ops
            p_req = hp_ops.required_block_width(g, bn=self._pblk_bn)
            if self._bucket(p_req) > self._pblk_cap:
                recompiles += 1
        self._install(index, g)
        dropped = self.invalidate(affected)
        ms = 1e3 * (time.perf_counter() - t0)
        self._swaps["swaps"] += 1
        self._swaps["last_swap_ms"] = ms
        self._swaps["swap_recompiles"] += recompiles
        return {"swap_ms": ms, "recompiles": recompiles,
                "cache_dropped": dropped, "epoch": index.epoch}

    def invalidate(self, nodes=None) -> int:
        """Drop cached scores whose value may depend on ``nodes``
        (``nodes=None`` drops everything). A single-source or top-k
        entry holds scores for *all* n targets -- a cached vector for
        an unaffected source still contains stale scores *at* affected
        targets (e.g. a node gaining its first in-edge moves s(u, v)
        from 0 to ~c*d_w for sources u far outside the repaired set)
        -- so any non-empty hot set drops every one of them. A pair
        entry depends on its endpoints' HP rows *and* on d at their
        meeting nodes (the cached value is sum h_u * h_v * d_k over
        shared keys), so it is dropped when an endpoint or a meeting
        node is hot. Returns the count dropped. Tested by
        tests/test_engine.py::test_swap_cannot_serve_stale_scores,
        ::test_unaffected_source_cache_cannot_hide_affected_targets
        and ::test_unaffected_pair_dropped_when_meeting_node_hot."""
        if nodes is None:
            dropped = len(self._cache)
            self._cache._d.clear()
        else:
            hot = set(np.asarray(nodes).ravel().tolist())
            stale = [] if not hot else [
                k for k in self._cache._d
                if k[0] != "pair" or k[1] in hot or k[2] in hot
                or self._pair_meets_hot(k[1], k[2], hot)]
            for k in stale:
                del self._cache._d[k]
            dropped = len(stale)
        self._swaps["invalidated"] += dropped
        return dropped

    def _pair_meets_hot(self, u: int, v: int, hot: set) -> bool:
        """Does the cached pair (u, v) read d at a hot meeting node?
        Checked against the *current* index: the endpoints are not hot,
        so their rows were not repaired and the key intersection equals
        the one the cached value was computed from."""
        hp = self.index.hp
        ku = hp.keys[u, :hp.counts[u]]
        kv = hp.keys[v, :hp.counts[v]]
        meet = np.intersect1d(ku, kv, assume_unique=True)
        if not len(meet):
            return False
        return not hot.isdisjoint(
            (meet.astype(np.int64) % self.index.n).tolist())

    # ------------------------------------------------------------------
    # dispatch helpers
    # ------------------------------------------------------------------
    def _k_bucket(self, k: int) -> int:
        """Smallest configured bucket >= k, clamped to n; k past the
        largest bucket gets the full-ranking n bucket. The bucket set
        is closed ({buckets} | {n}), so warmup() can prime every
        program the engine will ever dispatch -- no ad-hoc bucket may
        recompile mid-traffic."""
        k = max(1, min(int(k), self.index.n))
        fits = [b for b in self.cfg.k_buckets if b >= k]
        return min(min(fits), self.index.n) if fits else self.index.n

    def _record(self, kind: str, shape) -> None:
        key = "warmup_batches" if self._in_warmup else "batches"
        self._counts[key] += 1
        self._shapes.add((kind,) + tuple(shape))

    def _count_pad(self, pad: int) -> None:
        key = "warmup_pad_slots" if self._in_warmup else "pad_slots"
        self._counts[key] += pad

    def _dispatch_pairs(self, us: np.ndarray, vs: np.ndarray) -> np.ndarray:
        B = self.cfg.pair_batch
        pad = (-len(us)) % B
        self._count_pad(pad)
        us_p = np.concatenate([us, np.zeros(pad, np.int32)]).astype(np.int32)
        vs_p = np.concatenate([vs, np.zeros(pad, np.int32)]).astype(np.int32)
        out = np.empty(len(us_p), np.float32)
        for lo in range(0, len(us_p), B):
            u_b, v_b = us_p[lo:lo + B], vs_p[lo:lo + B]
            self._record("pair", (B, self._pair_backend))
            if self._pair_backend == "pallas":
                from repro.kernels.hp_join.ops import \
                    pair_query_batch_pallas
                chunk = pair_query_batch_pallas(
                    self._keys, self._vals, self._d, jnp.asarray(u_b),
                    jnp.asarray(v_b), n=self.index.n,
                    interpret=interpret_mode())
            else:
                chunk = _pair_query_batch(
                    self._keys, self._vals, self._d,
                    jnp.asarray(u_b), jnp.asarray(v_b), self.index.n)
            out[lo:lo + B] = np.asarray(chunk)
        return out[:len(us)]

    def _dispatch_sources(self, us: np.ndarray) -> np.ndarray:
        B = self.cfg.source_batch
        pad = (-len(us)) % B
        self._count_pad(pad)
        us_p = np.concatenate([us, np.full(pad, us[0] if len(us) else 0,
                                           np.int32)]).astype(np.int32)
        out = np.empty((len(us_p), self.index.n), np.float32)
        for lo in range(0, len(us_p), B):
            self._record("source", self._shape_tag(B))
            if self._sharded is not None:
                from repro.core import shard_query
                out[lo:lo + B] = shard_query.sharded_single_source(
                    self._sharded, us_p[lo:lo + B],
                    backend=self._push_backend)
            elif self._push_backend == "pallas":
                from repro.core.single_source import \
                    batched_single_source_pallas
                out[lo:lo + B] = np.asarray(batched_single_source_pallas(
                    self._keys, self._vals, self._d, self._blk_src,
                    self._blk_dstl, self._blk_w,
                    jnp.asarray(us_p[lo:lo + B]), self._tau,
                    n=self.index.n, l_max=self.index.plan.l_max,
                    bn=self._pblk_bn, eb=self._pblk_eb,
                    interpret=interpret_mode()))
            else:
                out[lo:lo + B] = np.asarray(batched_single_source(
                    self._keys, self._vals, self._d, self._edge_src,
                    self._edge_dst, self._w, jnp.asarray(us_p[lo:lo + B]),
                    self._tau, n=self.index.n,
                    l_max=self.index.plan.l_max))
        return out[:len(us)]

    def _dispatch_topk(self, us: np.ndarray, bucket: int):
        B = self.cfg.source_batch
        pad = (-len(us)) % B
        self._count_pad(pad)
        us_p = np.concatenate([us, np.full(pad, us[0] if len(us) else 0,
                                           np.int32)]).astype(np.int32)
        sv = np.empty((len(us_p), bucket), np.float32)
        si = np.empty((len(us_p), bucket), np.int32)
        for lo in range(0, len(us_p), B):
            self._record("topk", self._shape_tag(B, bucket))
            if self._sharded is not None:
                from repro.core import shard_query
                v, i = shard_query.sharded_topk(
                    self._sharded, us_p[lo:lo + B], bucket,
                    backend=self._push_backend)
            elif self._push_backend == "pallas":
                from repro.core.topk import batched_topk_pallas
                v, i = batched_topk_pallas(
                    self._keys, self._vals, self._d, self._blk_src,
                    self._blk_dstl, self._blk_w,
                    jnp.asarray(us_p[lo:lo + B]), self._tau,
                    self.index.n, self.index.plan.l_max, bucket,
                    self._pblk_bn, self._pblk_eb,
                    interpret=interpret_mode())
            else:
                v, i = batched_topk(
                    self._keys, self._vals, self._d, self._edge_src,
                    self._edge_dst, self._w, jnp.asarray(us_p[lo:lo + B]),
                    self._tau, self.index.n, self.index.plan.l_max,
                    bucket)
            sv[lo:lo + B] = np.asarray(v)
            si[lo:lo + B] = np.asarray(i)
        return sv[:len(us)], si[:len(us)]

    def _shape_tag(self, *shape):
        """Dispatch-shape key; sharded programs and the two push
        backends are distinct compiled programs, hence distinct
        shapes."""
        shape = shape + (self._push_backend,)
        if self._sharded is not None:
            return shape + ("mesh", self._sharded.n_shards)
        return shape

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def pairs(self, us, vs) -> np.ndarray:
        """s(u_i, v_i) for aligned arrays of node ids."""
        us = np.asarray(us, np.int32).ravel()
        vs = np.asarray(vs, np.int32).ravel()
        assert us.shape == vs.shape
        self._counts["pair"] += len(us)
        out = np.empty(len(us), np.float32)
        miss_pos = []
        for i, (u, v) in enumerate(zip(us.tolist(), vs.tolist())):
            # s(u,v) = s(v,u): canonicalize so (v,u) hits a cached (u,v)
            hit = self._cache.get(("pair", min(u, v), max(u, v)))
            if hit is None:
                miss_pos.append(i)
            else:
                out[i] = hit
        if miss_pos:
            got = self._dispatch_pairs(us[miss_pos], vs[miss_pos])
            for j, i in enumerate(miss_pos):
                out[i] = got[j]
                u, v = int(us[i]), int(vs[i])
                self._cache.put(("pair", min(u, v), max(u, v)),
                                float(got[j]))
        return out

    def pair(self, u: int, v: int) -> float:
        return float(self.pairs([u], [v])[0])

    def single_source(self, us) -> np.ndarray:
        """(Q, n) scores for an array of query nodes."""
        us = np.atleast_1d(np.asarray(us, np.int32))
        self._counts["source"] += len(us)
        out = np.empty((len(us), self.index.n), np.float32)
        miss_pos = []
        for i, u in enumerate(us.tolist()):
            hit = self._cache.get(("src", u))
            if hit is None:
                miss_pos.append(i)
            else:
                out[i] = hit
        if miss_pos:
            got = self._dispatch_sources(us[miss_pos])
            for j, i in enumerate(miss_pos):
                out[i] = got[j]
                # copy: got[j] is a view retaining the whole padded batch
                self._cache.put(("src", int(us[i])), got[j].copy())
        return out

    def topk(self, us, k: int) -> tuple[np.ndarray, np.ndarray]:
        """Top-k similar nodes per query: (Q, k') scores + node ids,
        k' = min(k, n), scores descending, ties toward small ids."""
        us = np.atleast_1d(np.asarray(us, np.int32))
        k_eff = min(int(k), self.index.n)
        bucket = self._k_bucket(k_eff)
        self._counts["topk"] += len(us)
        sv = np.empty((len(us), k_eff), np.float32)
        si = np.empty((len(us), k_eff), np.int32)
        miss_pos = []
        for i, u in enumerate(us.tolist()):
            hit = self._cache.get(("topk", u, bucket))
            if hit is None:
                miss_pos.append(i)
            else:
                sv[i], si[i] = hit[0][:k_eff], hit[1][:k_eff]
        if miss_pos:
            gv, gi = self._dispatch_topk(us[miss_pos], bucket)
            for j, i in enumerate(miss_pos):
                sv[i], si[i] = gv[j, :k_eff], gi[j, :k_eff]
                self._cache.put(("topk", int(us[i]), bucket),
                                (gv[j].copy(), gi[j].copy()))
        return sv, si

    # ------------------------------------------------------------------
    # materialized kNN lookups (repro.join, DESIGN.md section 10)
    # ------------------------------------------------------------------
    def attach_knn(self, knn, allow_stale: bool = False) -> None:
        """Attach a materialized :class:`~repro.join.KnnGraph` so
        ``knn(u)`` answers from the artifact instead of the device.

        The artifact must cover this engine's graph (same n) and, unless
        ``allow_stale``, match the served index's epoch -- an artifact
        swept before a hot-swap holds pre-swap scores.
        """
        if knn.n != self.index.n:
            raise ValueError(f"KnnGraph covers n={knn.n} nodes, engine "
                             f"serves n={self.index.n}")
        if not allow_stale and knn.epoch != self.index.epoch:
            raise ValueError(
                f"KnnGraph was swept at index epoch {knn.epoch}, engine "
                f"serves epoch {self.index.epoch}; re-run the join "
                "(repro.join.run_join) or pass allow_stale=True")
        self._knn = knn

    def knn(self, u: int, k: int | None = None,
            allow_stale: bool = False) -> tuple[np.ndarray, np.ndarray]:
        """(ids, scores) of u's materialized nearest neighbors.

        Served from the attached :class:`~repro.join.KnnGraph` -- an
        O(1) host lookup, no device dispatch. **Staleness check**: a
        ``swap_index`` bumps the served epoch past the artifact's, after
        which lookups raise (counted in
        ``stats()["knn_stale_rejects"]``) until a fresh join is
        attached; ``allow_stale=True`` serves the pre-swap scores
        explicitly. ``k`` truncates the stored row (scores are stored
        descending).
        """
        self._counts["knn"] += 1
        if self._knn is None:
            raise RuntimeError("no KnnGraph attached; run the bulk join "
                               "(repro.join.run_join) and attach_knn() "
                               "its artifact")
        if not allow_stale and self._knn.epoch != self.index.epoch:
            self._counts["knn_stale_rejects"] += 1
            raise RuntimeError(
                f"attached KnnGraph is stale: swept at epoch "
                f"{self._knn.epoch}, index now at epoch "
                f"{self.index.epoch} (hot-swap); re-run the join or "
                "pass allow_stale=True")
        ids, scores = self._knn.neighbors(int(u))
        if k is not None:
            ids, scores = ids[:int(k)], scores[:int(k)]
        return ids, scores

    # ------------------------------------------------------------------
    def warmup(self) -> dict:
        """Compile every fixed shape before traffic arrives.

        Returns {path: seconds}. Results are not cached, so warmup
        never pollutes the LRU; dispatch accounting lands in
        ``stats()["warmup_batches"]``/``["warmup_pad_slots"]``, so a
        warmed engine starts traffic with zero ``batches``/
        ``pad_slots`` (one full topk sweep per bucket used to be
        indistinguishable from real traffic)."""
        out = {}
        self._in_warmup = True
        try:
            z_pair = np.zeros(self.cfg.pair_batch, np.int32)
            t0 = time.perf_counter()
            self._dispatch_pairs(z_pair, z_pair)
            out["pair"] = time.perf_counter() - t0
            z_src = np.zeros(self.cfg.source_batch, np.int32)
            t0 = time.perf_counter()
            self._dispatch_sources(z_src)
            out["source"] = time.perf_counter() - t0
            buckets = {self._k_bucket(b) for b in self.cfg.k_buckets}
            buckets.add(self.index.n)   # the k > max(buckets) fallback
            for b in sorted(buckets):
                t0 = time.perf_counter()
                self._dispatch_topk(z_src, b)
                out[f"topk@{b}"] = time.perf_counter() - t0
        finally:
            self._in_warmup = False
        return out

    def stats(self) -> dict:
        return {
            **self._counts,
            **self._swaps,
            "epoch": self.index.epoch,
            "stale": self.index.stale,
            "cache_hits": self._cache.hits,
            "cache_misses": self._cache.misses,
            "cache_hits_by_kind": dict(self._cache.hits_by_kind),
            "cache_misses_by_kind": dict(self._cache.misses_by_kind),
            "cache_entries": len(self._cache),
            "knn_attached": self._knn is not None,
            "unique_shapes": sorted(self._shapes),
            "pair_backend": self._pair_backend,
            "push_backend": self._push_backend,
            "quantized": (self.index.quant.scheme
                          if self.index.quant is not None else None),
            "mesh_shards": (self._sharded.n_shards
                            if self._sharded is not None else 0),
        }

    # ------------------------------------------------------------------
    @classmethod
    def from_index_file(cls, path: str, g: csr.Graph,
                        config: EngineConfig | None = None,
                        mmap: bool = False) -> "QueryEngine":
        """Serve from an index persisted with SlingIndex.save.

        ``mmap=True`` (format v3 only) keeps the artifact on disk and
        maps it read-only: load is O(1), engines/replicas in other
        processes share the page cache, and install dequantizes/pads
        into device arrays as usual.
        """
        return cls(SlingIndex.load(path, mmap=mmap), g, config)
