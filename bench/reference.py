"""Plain host references for the served answers.

They read the index artifact's bytes themselves (format v3, as
INDEX_FORMAT.md lays it out) and the edge list that ``bench/graphs.py``
generated, and share no code with the program: a fault in the
program's loader, dequantization, push or join shows up as a gap
between its answers and these.

* :func:`horner_row`: single-source scores of one node, the Horner
  form of Algorithm 6 in float64 (``np.bincount`` for the scatter),
  with the program's prune threshold tau = sqrt(c)**l_max * theta;
* :func:`topk_of`: the k best of a row, ties toward the smaller id;
* :func:`pair`: the merge join of two HP rows (Algorithm 3).

Each takes a ``dtype`` rounding function; the default keeps float64,
:func:`bf16` rounds every stored value and every intermediate to
bfloat16, which is the control that the comparison has to reject.

:class:`ExactSimRank` starts from the edge list alone and reads nothing
that the build made: SimRank itself, to which Theorem 1 holds every
served answer within eps.
"""
from __future__ import annotations

import dataclasses
import json
import struct

import numpy as np

MAGIC = b"SLINGIDX"
PAD_KEY = 2**31 - 1


def f64(x):
    return np.asarray(x, np.float64)


def bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


@dataclasses.dataclass
class Artifact:
    n: int
    keys: np.ndarray      # (n, width) int32, sorted per row, PAD after counts
    vals: np.ndarray      # (n, width) float64 dequantized
    counts: np.ndarray    # (n,)
    d: np.ndarray         # (n,) float64
    c: float
    theta: float
    l_max: int

    @property
    def tau(self) -> float:
        return float(self.theta * np.sqrt(self.c) ** self.l_max)


def _dequantize(raw: np.ndarray, scheme: str, scale: float) -> np.ndarray:
    if scheme == "int16":
        # the stored int16 codes times the stored step, in float64
        return raw.astype(np.float64) * float(scale)
    raise ValueError(f"unknown quantization scheme {scheme!r}")


def read_artifact(path: str) -> Artifact:
    """Read a format-v3 index file (INDEX_FORMAT.md): preamble,
    header JSON, 64-byte aligned raw arrays."""
    with open(path, "rb") as f:
        magic, _version, hlen = struct.unpack("<8sII", f.read(16))
        if magic != MAGIC:
            raise ValueError(f"{path}: not a format-v3 index")
        header = json.loads(f.read(hlen).decode())
    start = (16 + hlen + 63) & ~63
    arrays = {}
    for name, spec in header["arrays"].items():
        shape = tuple(int(s) for s in spec["shape"])
        if spec["dtype"] == "bfloat16":
            raise ValueError("bfloat16 vals are not read by the reference")
        arrays[name] = np.memmap(path, dtype=np.dtype(spec["dtype"]),
                                 mode="r", offset=start + int(spec["offset"]),
                                 shape=shape)
    quant = header.get("quant")
    vals, d = arrays["vals"], arrays["d"]
    if quant is None:
        vals, d = f64(vals), f64(d)
    else:
        vals = _dequantize(vals, quant["scheme"], quant["scale"])
        d = (_dequantize(d, "int16", quant["d_scale"])
             if quant.get("d_scale", 0.0) > 0 else f64(d))
    plan = header["plan"]
    keys = arrays["keys"]
    return Artifact(n=int(keys.shape[0]), keys=keys, vals=vals,
                    counts=np.asarray(arrays["counts"]), d=d,
                    c=float(plan["c"]), theta=float(plan["theta"]),
                    l_max=int(plan["l_max"]))


@dataclasses.dataclass
class Edges:
    """The directed edge list, with the pull weights sqrt(c)/|I(dst)|."""
    src: np.ndarray
    dst: np.ndarray
    w: np.ndarray

    @classmethod
    def of(cls, src, dst, n: int, c: float) -> "Edges":
        indeg = np.bincount(dst, minlength=n).astype(np.float64)
        w = np.sqrt(c) / np.maximum(indeg, 1.0)[dst]
        return cls(np.asarray(src, np.int64), np.asarray(dst, np.int64), w)


def _row(a: Artifact, u: int, rnd):
    cnt = int(a.counts[u])
    return (np.asarray(a.keys[u, :cnt], np.int64),
            rnd(np.asarray(a.vals[u, :cnt])))


def horner_row(a: Artifact, e: Edges, u: int, rnd=f64) -> np.ndarray:
    """(n,) scores s(u, .) by the Horner push, rounded by ``rnd``."""
    n = a.n
    keys, vals = _row(a, u, rnd)
    ls, ks = keys // n, keys % n
    d, w = rnd(a.d), rnd(e.w)
    seeds = np.zeros((a.l_max + 1, n))
    for l in range(a.l_max + 1):
        sel = ls == l
        seeds[l] = rnd(np.bincount(ks[sel], rnd(vals[sel] * d[ks[sel]]),
                                   minlength=n))
    tau = a.tau
    acc = seeds[a.l_max]
    for l in range(a.l_max - 1, -1, -1):
        acc = np.where(acc > tau, acc, 0.0)
        pushed = rnd(np.bincount(e.dst, rnd(acc[e.src] * w), minlength=n))
        acc = rnd(pushed + seeds[l])
    return acc


def topk_of(row: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    order = np.argsort(-row, kind="stable")[:k]
    return row[order], order


def pair(a: Artifact, u: int, v: int, rnd=f64) -> float:
    """s(u, v): merge join of the two sorted HP rows, summed in the
    join's order."""
    ku, vu = _row(a, u, rnd)
    kv, vv = _row(a, v, rnd)
    ku_l, kv_l = ku.tolist(), kv.tolist()
    mi, mj = [], []
    i = j = 0
    while i < len(ku_l) and j < len(kv_l):
        if ku_l[i] == kv_l[j]:
            mi.append(i)
            mj.append(j)
            i += 1
            j += 1
        elif ku_l[i] < kv_l[j]:
            i += 1
        else:
            j += 1
    if not mi:
        return 0.0
    d = rnd(a.d[ku[mi] % a.n])
    terms = rnd(rnd(vu[mi] * d) * vv[mj])
    s = 0.0
    for t in terms.tolist():
        s = float(rnd(s + t))
    return s


class ExactSimRank:
    """SimRank of the edge list by the Jeh-Widom iteration, in float64.

    s(a, a) = 1, and for a != b
    s(a, b) = c / (|I(a)| |I(b)|) * sum over i in I(a), j in I(b) of s(i, j),
    which is 0 where a or b has no in-neighbour. Only the block of the
    nodes with in-neighbours is iterated: with A[i, a] = 1 / |I(a)| for
    each edge i -> a, the off-diagonal part X of that block follows
    X <- offdiag(c * (A^T A + A_B^T X A_B)), A_B the rows of A of the
    block. The map contracts by c (A's columns sum to 1), so once a step
    changes X by at most delta the error left is at most
    delta * c / (1 - c); the iteration stops when that is under ``tol``.
    """

    MAX_BLOCK = 16384       # a dense float64 block of 2 GiB

    def __init__(self, src, dst, n: int, c: float, tol: float = 1e-7):
        from scipy import sparse
        src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
        indeg = np.bincount(dst, minlength=n)
        self.block = np.flatnonzero(indeg)
        nb = len(self.block)
        if nb > self.MAX_BLOCK:
            raise ValueError(f"{nb} nodes with in-neighbours: too many "
                             "for a dense exact SimRank")
        self.n = n
        self.pos = np.full(n, -1, np.int64)
        self.pos[self.block] = np.arange(nb)
        a = sparse.csr_matrix((1.0 / indeg[dst], (src, self.pos[dst])),
                              shape=(n, nb))
        base = c * (a.T @ a).toarray()
        a_b = a[self.block].toarray()
        x = np.zeros((nb, nb))
        self.steps, self.bound = 0, 1.0
        while self.bound > tol:
            nxt = base + c * (a_b.T @ x @ a_b)
            np.fill_diagonal(nxt, 0.0)
            delta = float(np.abs(nxt - x).max())
            x = nxt
            self.steps += 1
            self.bound = delta * c / (1.0 - c)
        self.x = x

    def row(self, u: int) -> np.ndarray:
        """(n,) exact s(u, .)."""
        out = np.zeros(self.n)
        if self.pos[u] >= 0:
            out[self.block] = self.x[self.pos[u]]
        out[u] = 1.0
        return out

    def pair(self, u: int, v: int) -> float:
        if u == v:
            return 1.0
        pu, pv = self.pos[u], self.pos[v]
        return float(self.x[pu, pv]) if pu >= 0 and pv >= 0 else 0.0
