"""Device-streamed bulk similarity join: the all-sources top-k sweep.

The online engine answers one micro-batch at a time; the workload
DESIGN.md section 5's feature consumers actually have is *bulk*: "for
every node (or a large node set), its k most SimRank-similar nodes",
materialized once and then read as a static kNN graph. A naive loop of
``QueryEngine.topk([u], k)`` calls pays per-call padding, cache
bookkeeping, and host round-trips for every source; the sweep instead

  * partitions the source set into **fixed-shape tiles** (``tile``
    sources, last tile padded by repeating a real source), so the whole
    sweep dispatches exactly one compiled program per mesh layout --
    the capacity-bucket discipline of DESIGN.md sections 7-8 applied to
    the batch dimension (zero recompiles after the first tile,
    :func:`compile_count` is the gate);
  * streams every tile through the shared Horner-push slab kernel and a
    **device-resident ``lax.top_k`` reduction**
    (:func:`~repro.core.topk.batched_topk`, or the shard-local-top-k +
    global-merge fan-out :func:`~repro.core.shard_query.sharded_topk`
    when a serving mesh is configured) -- only (tile, k') values and
    ids ever leave the device, never a tile's (tile, n) score slab and
    never an n x n score matrix;
  * accumulates tile results into a host buffer with **tile-granular
    checkpoints** (atomic-rename npz, fingerprinted against the sweep
    configuration), so a million-node join survives preemption and a
    resumed sweep is bit-identical to an uninterrupted one;
  * finalizes into a versioned :class:`~repro.join.artifact.KnnGraph`
    CSR artifact carrying the plan's eps certificate and the index
    epoch (staleness handshake with ``QueryEngine.knn``).

Threshold variant: ``JoinConfig(tau=...)`` keeps every neighbor with
``sim >= tau`` instead of a fixed k. The device program is the same
fixed-shape top-k reducer with k = ``cap`` candidates per source; the
host keeps the prefix above tau. When a source's cap-th candidate still
scores >= tau the row may be incomplete and is flagged in
``KnnGraph.truncated`` -- never silently dropped (re-run with a larger
``cap`` to resolve flagged rows).
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from repro.join.artifact import CKPT_FORMAT_VERSION, KnnGraph


@dataclasses.dataclass(frozen=True)
class JoinConfig:
    """Sweep configuration (all static: part of the compile key and the
    checkpoint fingerprint)."""
    k: int = 16               # neighbors per source (top-k mode)
    tau: float | None = None  # sim >= tau threshold mode when set
    cap: int = 256            # device candidates/source in threshold mode
    tile: int = 64            # fixed source-tile shape
    exclude_self: bool = False  # drop s(u, u) from u's row
    mesh: object = None       # serving mesh: nodes shard over mesh_axis
    mesh_axis: str = "data"
    checkpoint_path: str | None = None  # tile-granular resume state
    checkpoint_every: int = 8           # tiles between checkpoint writes
    # Horner-push backend for the tile program ("lax" | "pallas" |
    # None/"auto" = process-wide switch); part of the checkpoint
    # fingerprint -- the blocked layout sums messages in a different
    # float32 order, so tiles from the two backends are not
    # interchangeable bit-for-bit.
    push_backend: str | None = None


def compile_count() -> int:
    """Distinct compiled tile programs in this process (single-device
    fused top-k + sharded fan-out, both push backends) -- the
    regression gate for recompiles across tiles
    (benchmarks/bench_join.py). Thin re-export of
    :func:`repro.analysis.runtime.join_compile_count` (one
    cache-introspection definition, shared with the walk gate)."""
    from repro.analysis.runtime import join_compile_count
    return join_compile_count()


def _kq(cfg: JoinConfig, n: int) -> int:
    """Device candidates fetched per source: k (or cap), plus one slot
    when the self entry is to be dropped on host, clamped to n."""
    base = cfg.cap if cfg.tau is not None else cfg.k
    return max(1, min(n, int(base) + (1 if cfg.exclude_self else 0)))


def _fingerprint(idx, g, sources: np.ndarray, cfg: JoinConfig,
                 kq: int) -> dict:
    """Everything a resumed sweep must agree on for its cached tiles to
    be interchangeable with freshly computed ones (bit-stability): the
    graph/index identity, the tile geometry, and the mesh layout (a
    different shard count changes float reduction order)."""
    return {
        "n": int(idx.n), "m": int(g.m), "epoch": int(idx.epoch),
        "eps": float(idx.plan.eps), "c": float(idx.plan.c),
        "theta": float(idx.plan.theta), "l_max": int(idx.plan.l_max),
        "mode": "threshold" if cfg.tau is not None else "topk",
        "k": int(cfg.k),
        "tau": None if cfg.tau is None else float(cfg.tau),
        "cap": int(cfg.cap), "tile": int(cfg.tile), "kq": int(kq),
        "exclude_self": bool(cfg.exclude_self),
        "mesh_shards": _mesh_shards(cfg),
        "n_sources": int(len(sources)),
        "push_backend": _resolved_backend(cfg),
    }


def _resolved_backend(cfg: JoinConfig) -> str:
    from repro.kernels.horner_push import resolve_push_backend
    return resolve_push_backend(cfg.push_backend)


def _mesh_shards(cfg: JoinConfig) -> int:
    return 1 if cfg.mesh is None else int(cfg.mesh.shape[cfg.mesh_axis])


# ----------------------------------------------------------------------
# checkpoints (tile-granular resume; format in INDEX_FORMAT.md)
# ----------------------------------------------------------------------
def _save_checkpoint(path: str, fp: dict, sources: np.ndarray,
                     tiles_done: int, vals: np.ndarray,
                     ids: np.ndarray) -> None:
    """Atomic write (tmp + rename): a preemption mid-write leaves the
    previous checkpoint intact, never a torn file. Only the completed
    ``tiles_done * tile`` row prefix is persisted -- writing the whole
    (S_pad, kq) accumulator every time would make total checkpoint I/O
    quadratic in sweep size, exactly the million-node regime
    checkpoints exist for."""
    done = tiles_done * fp["tile"]
    meta = dict(fp)
    meta["_format_version"] = CKPT_FORMAT_VERSION
    meta["tiles_done"] = int(tiles_done)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez_compressed(f, meta=json.dumps(meta), sources=sources,  # slinglint: disable=banned-api -- the atomic writer itself (tmp + os.replace below)
                            vals=vals[:done], ids=ids[:done])
    os.replace(tmp, path)


def _load_checkpoint(path: str, fp: dict, sources: np.ndarray):
    """Returns (tiles_done, vals_prefix, ids_prefix) or None when no
    checkpoint exists. A checkpoint whose fingerprint (or source set)
    differs from the running sweep is refused, never partially reused
    -- mixing tiles from two sweep configurations would corrupt the
    artifact silently."""
    if not os.path.exists(path):
        return None
    z = np.load(path, allow_pickle=False)
    meta = json.loads(str(z["meta"]))
    version = meta.pop("_format_version", 0)
    if version > CKPT_FORMAT_VERSION:
        raise ValueError(
            f"join checkpoint is format v{version}, this build reads "
            f"<= v{CKPT_FORMAT_VERSION} (see INDEX_FORMAT.md)")
    tiles_done = int(meta.pop("tiles_done"))
    if meta != fp:
        diff = {k for k in set(meta) | set(fp) if meta.get(k) != fp.get(k)}
        raise ValueError(
            "join checkpoint fingerprint mismatch on "
            f"{sorted(diff)}: the checkpoint was written by a different "
            "sweep (graph, index epoch, tile geometry, or mesh layout "
            "changed); delete it or fix the configuration")
    if not np.array_equal(z["sources"].astype(np.int32), sources):
        raise ValueError("join checkpoint source set differs from the "
                         "running sweep; refusing to resume")
    vals, ids = z["vals"].astype(np.float32), z["ids"].astype(np.int32)
    if vals.shape != (tiles_done * fp["tile"], fp["kq"]):
        raise ValueError("join checkpoint arrays do not cover its "
                         f"claimed {tiles_done} tiles")
    return tiles_done, vals, ids


# ----------------------------------------------------------------------
# the sweep
# ----------------------------------------------------------------------
def _tile_runner(idx, g, cfg: JoinConfig, kq: int):
    """One compiled program for every tile: the fused single-device
    top-k, or the mesh fan-out with the index sharded once up front.
    The resolved push backend selects the tile program's Horner-push
    body (lax reference or the Pallas kernel); either way every tile
    reuses the one compiled program."""
    backend = _resolved_backend(cfg)
    if cfg.mesh is None:
        import jax.numpy as jnp
        from repro.core import device_state
        from repro.core.topk import batched_topk, batched_topk_pallas
        from repro.kernels import interpret_mode
        st = device_state.serving_arrays(idx, g)
        if backend == "pallas":
            bl = device_state.blocked_push_arrays(idx, g)

            def run_tile(us):
                v, i = batched_topk_pallas(
                    st.keys, st.vals, st.d, bl.blk_src, bl.blk_dstl,
                    bl.blk_w, jnp.asarray(us, jnp.int32),
                    jnp.float32(st.tau), idx.n, idx.plan.l_max, kq,
                    bl.bn, bl.eb,
                    interpret=interpret_mode())
                return np.asarray(v), np.asarray(i)
            return run_tile

        def run_tile(us):
            v, i = batched_topk(
                st.keys, st.vals, st.d, st.edge_src, st.edge_dst, st.w,
                jnp.asarray(us, jnp.int32), jnp.float32(st.tau),
                idx.n, idx.plan.l_max, kq)
            return np.asarray(v), np.asarray(i)
        return run_tile

    from repro.core import shard_query
    si = shard_query.shard_index(idx, g, cfg.mesh, axis=cfg.mesh_axis,
                                 push_backend=backend)

    def run_tile(us):
        return shard_query.sharded_topk(si, us, kq, backend=backend)
    return run_tile


def run_join(idx, g, sources=None, config: JoinConfig | None = None,
             *, stop_after_tiles: int | None = None) -> KnnGraph | None:
    """Sweep ``sources`` (default: all n nodes) through the join and
    return the materialized :class:`KnnGraph`.

    With ``config.checkpoint_path`` the sweep saves tile-granular
    progress every ``checkpoint_every`` tiles and resumes from an
    existing compatible checkpoint; ``stop_after_tiles`` (tests /
    benchmarks) aborts after that many *newly computed* tiles, after
    forcing a checkpoint write, and returns None -- simulating
    preemption. A resumed sweep replays only the missing tiles through
    the same compiled program, so its artifact is bit-identical to an
    uninterrupted sweep's (tests/test_join.py).
    """
    cfg = config or JoinConfig()
    n = idx.n
    if sources is None:
        srcs = np.arange(n, dtype=np.int32)
    else:
        srcs = np.asarray(sources, np.int32).ravel()
        if len(srcs) == 0:
            raise ValueError("empty source set")
        if len(np.unique(srcs)) != len(srcs):
            raise ValueError("join sources must be unique (duplicate "
                             "rows would shadow each other in the "
                             "artifact's row lookup)")
        if srcs.min() < 0 or srcs.max() >= n:
            raise ValueError(f"source id outside [0, {n})")
    kq = _kq(cfg, n)
    S = len(srcs)
    n_tiles = -(-S // cfg.tile)
    S_pad = n_tiles * cfg.tile
    # pad the ragged tail by repeating a real source: identical math,
    # results discarded -- the same convention as the engine's batches
    srcs_pad = np.concatenate(
        [srcs, np.full(S_pad - S, srcs[0], np.int32)])

    fp = _fingerprint(idx, g, srcs, cfg, kq)
    vals = np.zeros((S_pad, kq), np.float32)
    ids = np.zeros((S_pad, kq), np.int32)
    start_tile = 0
    if cfg.checkpoint_path is not None:
        ck = _load_checkpoint(cfg.checkpoint_path, fp, srcs)
        if ck is not None:
            start_tile, done_v, done_i = ck
            vals[:len(done_v)] = done_v
            ids[:len(done_i)] = done_i

    run_tile = _tile_runner(idx, g, cfg, kq)
    done_this_run = 0
    for t in range(start_tile, n_tiles):
        lo = t * cfg.tile
        v, i = run_tile(srcs_pad[lo:lo + cfg.tile])
        vals[lo:lo + cfg.tile] = v
        ids[lo:lo + cfg.tile] = i
        done_this_run += 1
        finished = t + 1 == n_tiles
        if cfg.checkpoint_path is not None and not finished and (
                done_this_run % cfg.checkpoint_every == 0
                or done_this_run == stop_after_tiles):
            _save_checkpoint(cfg.checkpoint_path, fp, srcs, t + 1,
                             vals, ids)
        if done_this_run == stop_after_tiles and not finished:
            return None

    knn = _finalize(idx, srcs, vals[:S], ids[:S], cfg, kq)
    if cfg.checkpoint_path is not None \
            and os.path.exists(cfg.checkpoint_path):
        os.remove(cfg.checkpoint_path)  # complete: the artifact is the state
    return knn


def _finalize(idx, srcs: np.ndarray, vals: np.ndarray, ids: np.ndarray,
              cfg: JoinConfig, kq: int) -> KnnGraph:
    """Host reduction of the (S, kq) candidate block to the CSR rows:
    drop the self entry (exclude_self), cut at tau (threshold mode),
    flag possibly-incomplete threshold rows. Pure array bookkeeping --
    deterministic, so artifact equality reduces to tile-result
    equality."""
    S = len(srcs)
    threshold = cfg.tau is not None
    truncated = np.zeros(S, bool) if threshold else None
    budget = cfg.cap if threshold else cfg.k
    if not threshold and not cfg.exclude_self:
        # plain top-k: every row is the full kq-candidate block -- the
        # CSR is a reshape, no per-source host loop (the loop below is
        # a serial O(S) tail after a device-bound sweep)
        nbr_ids, nbr_scores = ids.ravel(), vals.ravel()
        indptr = np.arange(S + 1, dtype=np.int64) * kq
    else:
        row_ids: list[np.ndarray] = []
        row_scores: list[np.ndarray] = []
        lengths = np.empty(S, np.int64)
        for i in range(S):
            r_ids, r_sc = ids[i], vals[i]
            if cfg.exclude_self:
                keep = r_ids != srcs[i]
                if keep.all():
                    # self fell below the kq-th candidate (possible
                    # only under heavy ties): drop the last slot so
                    # the row stays <= k entries
                    keep[-1] = False
                r_ids, r_sc = r_ids[keep], r_sc[keep]
            r_ids, r_sc = r_ids[:budget], r_sc[:budget]
            if threshold:
                # candidates are sorted descending: the cut is a prefix
                cut = int((r_sc >= cfg.tau).sum())
                if cut == len(r_sc) and kq < idx.n and len(r_sc) > 0:
                    truncated[i] = True  # cap-th candidate still >= tau
                r_ids, r_sc = r_ids[:cut], r_sc[:cut]
            row_ids.append(r_ids)
            row_scores.append(r_sc)
            lengths[i] = len(r_ids)
        indptr = np.zeros(S + 1, np.int64)
        np.cumsum(lengths, out=indptr[1:])
        nbr_ids = (np.concatenate(row_ids) if row_ids
                   else np.zeros(0, np.int32))
        nbr_scores = (np.concatenate(row_scores) if row_scores
                      else np.zeros(0, np.float32))
    return KnnGraph(
        n=idx.n, mode="threshold" if threshold else "topk",
        k=int(budget), tau=cfg.tau, exclude_self=cfg.exclude_self,
        tile=cfg.tile, eps=float(idx.plan.eps), c=float(idx.plan.c),
        theta=float(idx.plan.theta), l_max=int(idx.plan.l_max),
        epoch=int(idx.epoch), mesh_shards=_mesh_shards(cfg),
        sources=srcs,
        indptr=indptr,
        nbr_ids=nbr_ids.astype(np.int32),
        nbr_scores=nbr_scores.astype(np.float32),
        truncated=truncated)
