"""Pallas TPU kernel: block-CSR segment-sum SpMM (the A_hat operator).

TPU adaptation of the paper's local-update propagation (DESIGN.md
section 2): instead of a hash-map push (CPU) or atomic scatter (GPU),
edges are pre-grouped by destination-node block; each grid cell
(node-block i, edge-chunk j) loads an EB-wide chunk of gathered
messages into VMEM and accumulates

    out_block += one_hot(dst_local) @ msgs        # (BN,EB)@(EB,F) MXU

so the irregular reduction becomes a dense matmul on the systolic
array. x rows are gathered per-chunk with dynamic loads (TPU: VMEM
row DMA; interpret mode: jnp take).

Grid: (n_blocks, edge_chunks). BlockSpecs keep out (BN, F) resident in
VMEM across the inner j loop (revisiting grid dim), msgs are (EB, F).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(src_ref, dstl_ref, w_ref, x_ref, o_ref, *, bn: int, eb: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    src = src_ref[0, :]           # (EB,) int32 global row ids
    dstl = dstl_ref[0, :]         # (EB,) int32 local dst in [0, bn), -1 pad
    w = w_ref[0, :]               # (EB,)
    valid = dstl >= 0
    rows = x_ref[jnp.clip(src, 0, x_ref.shape[0] - 1), :]       # (EB, F)
    msgs = jnp.where(valid[:, None], rows * w[:, None], 0.0)
    onehot = (dstl[None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (bn, eb), 0)).astype(msgs.dtype)             # (BN, EB)
    o_ref[...] += jax.lax.dot(onehot, msgs,
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bn", "eb", "interpret"))
def spmm_block(x, blk_src, blk_dst_local, blk_w, *, bn: int, eb: int,
               interpret: bool):
    """x (N, F) f32; blk_* (NB, E_pad) block-aligned edges.

    Returns (NB*bn, F). E_pad must be a multiple of eb.
    """
    NB, E_pad = blk_src.shape
    assert E_pad % eb == 0, (E_pad, eb)
    F = x.shape[1]
    n_chunks = E_pad // eb
    grid = (NB, n_chunks)
    out_shape = jax.ShapeDtypeStruct((NB * bn, F), jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, bn=bn, eb=eb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, eb), lambda i, j: (i, j)),
            pl.BlockSpec((1, eb), lambda i, j: (i, j)),
            pl.BlockSpec((1, eb), lambda i, j: (i, j)),
            pl.BlockSpec(x.shape, lambda i, j: (0, 0)),   # x resident
        ],
        out_specs=pl.BlockSpec((bn, F), lambda i, j: (i, 0)),
        out_shape=out_shape,
        interpret=interpret,
    )(blk_src, blk_dst_local, blk_w, x)
