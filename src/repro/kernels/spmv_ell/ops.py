"""jit'd wrapper + host-side layout builder for the SpMM kernel."""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.graph import csr
from repro.kernels import interpret_mode
from repro.kernels.spmv_ell import ref as ref_mod
from repro.kernels.spmv_ell.spmv_ell import spmm_block


def block_align(g: csr.Graph, w: np.ndarray, bn: int, eb: int):
    """Group pull-oriented edges by destination-node block and pad each
    block's edge list to a multiple of eb. Returns (blk_src,
    blk_dst_local, blk_w) with shape (NB, E_pad)."""
    n = g.n
    nb = -(-n // bn)
    per_block: list[list[int]] = [[] for _ in range(nb)]
    for e in range(g.m):
        per_block[g.edge_dst[e] // bn].append(e)
    width = max((len(b) for b in per_block), default=1)
    width = max(-(-width // eb) * eb, eb)
    blk_src = np.zeros((nb, width), dtype=np.int32)
    blk_dstl = np.full((nb, width), -1, dtype=np.int32)
    blk_w = np.zeros((nb, width), dtype=np.float32)
    for b, edges in enumerate(per_block):
        for i, e in enumerate(edges):
            blk_src[b, i] = g.edge_src[e]
            blk_dstl[b, i] = g.edge_dst[e] - b * bn
            blk_w[b, i] = w[e]
    return blk_src, blk_dstl, blk_w


def spmm(x, g: csr.Graph, w: np.ndarray, bn: int = 8, eb: int = 16):
    """out[v] = sum_{u in I(v)} w_(u->v) * x[u]; kernel-backed."""
    blk_src, blk_dstl, blk_w = block_align(g, w, bn, eb)
    out = spmm_block(jnp.asarray(x, jnp.float32), jnp.asarray(blk_src),
                     jnp.asarray(blk_dstl), jnp.asarray(blk_w),
                     bn=bn, eb=eb, interpret=interpret_mode())
    return out[: g.n]


def spmm_reference(x, g: csr.Graph, w: np.ndarray):
    return ref_mod.spmm_ref(jnp.asarray(x, jnp.float32),
                            jnp.asarray(g.edge_src),
                            jnp.asarray(g.edge_dst),
                            jnp.asarray(w, jnp.float32), g.n)
