"""Pallas TPU kernel: one fused Horner-push step over a node slab.

One grid cell (query-block q, node-block i, edge-chunk j) fuses the
three lax ops the reference :func:`repro.core.single_source.horner_push`
round-trips through HBM per step -- tau-prune, edge-gather/SpMV, and
the Horner seed-accumulate -- into a single VMEM-resident program
(DESIGN.md section 11):

  * at ``j == 0`` the (bn, bq) accumulator block is *initialized with
    the Horner seed block* for this step's level l, computed in-kernel
    from the resident packed-row refs (a masked one-hot reduction over
    the row width W);
  * each edge chunk then gathers its frontier rows with the prune
    applied at read time (``x > tau``) and lands the messages on the
    accumulator via a one-hot MXU matmul, exactly the
    ``kernels/spmv_ell`` idiom (dest-block-grouped edges, -1 pads).

Seed-then-add is valid because the reference computes
``A_hat @ prune(x) + seed_l`` and addition commutes; prune-at-gather is
valid because the prune is elementwise, so it commutes with the row
gather. The accumulator block stays resident across the inner j loop
(its BlockSpec index ignores j), so the (B, n) frontier is read once
and written once per step instead of materializing prune/gather/scatter
intermediates between ops.

The frontier is node-major (n_frontier, B) -- B plays the role the
feature dim F plays in spmv_ell -- and the step level l and prune tau
arrive as (1, 1) operands so all l_max+1 steps share one kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _step_kernel(ls_ref, kloc_ref, contrib_ref, src_ref, dstl_ref,
                 w_ref, tau_ref, lvl_ref, x_ref, o_ref, *,
                 bn: int, eb: int, bq: int, width: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _seed():
        # Horner seed block for level l: o[v_loc, b] =
        #   sum_w [k_loc[b, w] - i*bn == v_loc] * contrib[b, w] * [ls == l]
        lvl = lvl_ref[0, 0]
        cc = jnp.where(ls_ref[...] == lvl, contrib_ref[...], 0.0)  # (bq, W)
        loc = kloc_ref[...] - i * bn                               # (bq, W)
        eq = loc[None, :, :] == jax.lax.broadcasted_iota(
            jnp.int32, (bn, bq, width), 0)
        o_ref[...] = jnp.sum(jnp.where(eq, cc[None, :, :], 0.0), axis=2)

    src = src_ref[0, :]           # (eb,) int32 frontier-global row ids
    dstl = dstl_ref[0, :]         # (eb,) int32 local dst in [0, bn), -1 pad
    w = w_ref[0, :]               # (eb,) pull weights sqrt(c)/|I(dst)|
    valid = dstl >= 0
    rows = x_ref[jnp.clip(src, 0, x_ref.shape[0] - 1), :]        # (eb, bq)
    rows = jnp.where(rows > tau_ref[0, 0], rows, 0.0)            # fused prune
    msgs = jnp.where(valid[:, None], rows * w[:, None], 0.0)
    onehot = (dstl[None, :] == jax.lax.broadcasted_iota(
        jnp.int32, (bn, eb), 0)).astype(msgs.dtype)              # (bn, eb)
    o_ref[...] += jax.lax.dot(onehot, msgs,
                              preferred_element_type=jnp.float32)


def horner_step(x, ls, kloc, contrib, blk_src, blk_dstl, blk_w, tau,
                lvl, *, bn: int, eb: int, bq: int, interpret: bool):
    """One fused push step: returns seed_l + A_hat @ prune(x).

    x (n_frontier, B) f32 node-major frontier; ls/kloc/contrib (B, W)
    decoded packed rows (wrapper-prepared, see ops.py); blk_* (NB,
    E_pad) dest-block-grouped slab edges; tau/lvl (1, 1) scalars.
    Returns (NB*bn, B) f32. B % bq == 0 and E_pad % eb == 0 (wrapper
    invariants).
    """
    NB, E_pad = blk_src.shape
    B, W = ls.shape
    assert B % bq == 0 and E_pad % eb == 0, (B, bq, E_pad, eb)
    grid = (B // bq, NB, E_pad // eb)
    out_shape = jax.ShapeDtypeStruct((NB * bn, B), jnp.float32)
    return pl.pallas_call(
        functools.partial(_step_kernel, bn=bn, eb=eb, bq=bq, width=W),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, W), lambda q, i, j: (q, 0)),     # ls
            pl.BlockSpec((bq, W), lambda q, i, j: (q, 0)),     # kloc
            pl.BlockSpec((bq, W), lambda q, i, j: (q, 0)),     # contrib
            pl.BlockSpec((1, eb), lambda q, i, j: (i, j)),     # src chunk
            pl.BlockSpec((1, eb), lambda q, i, j: (i, j)),     # dstl chunk
            pl.BlockSpec((1, eb), lambda q, i, j: (i, j)),     # w chunk
            pl.BlockSpec((1, 1), lambda q, i, j: (0, 0)),      # tau
            pl.BlockSpec((1, 1), lambda q, i, j: (0, 0)),      # lvl
            pl.BlockSpec((x.shape[0], bq), lambda q, i, j: (0, q)),
        ],
        out_specs=pl.BlockSpec((bn, bq), lambda q, i, j: (i, q)),
        out_shape=out_shape,
        interpret=interpret,
    )(ls, kloc, contrib, blk_src, blk_dstl, blk_w, tau, lvl, x)
