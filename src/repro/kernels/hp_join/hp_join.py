"""Pallas TPU kernel: batched sorted-key join for single-pair SimRank.

The C++ SLING query is a pointer-chasing merge join -- hostile to TPU
vector units. TPU adaptation: an all-pairs equality join. For each
query pair the kernel compares every key of the u-row against the
whole v-row and contracts the matches against the values:

    s = sum_ij [ku_i == kv_j] * vu_i * vv_j

Values arrive pre-multiplied by sqrt(d_k) (see ref.py), so no gather
occurs in the inner loop.

Layout: the rows are transposed to key-major (K, B) so the query pairs
lie along the 128-wide lane axis. One grid cell holds ``bq`` pairs as
(K, bq) blocks and loops over the K keys of the u-rows; each step
broadcasts one (1, bq) key row against the (K, bq) v-block, so every
vector op is 2-D and the output block is a lane-dense (1, bq) row.
(The earlier rank-3 (bq, K, K) broadcast with a rank-1 output block is
refused by the v5e compiler.)

Grid: (B_pad // bq,); B is padded to a multiple of bq with PAD keys.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

PAD = 2**31 - 1  # python int: jnp scalars would be captured consts


def _kernel(ku_ref, vu_ref, kv_ref, vv_ref, o_ref):
    kv = kv_ref[...]                 # (K, bq) key-major v-rows
    vv = vv_ref[...]

    def body(i, acc):
        ku_i = ku_ref[pl.ds(i, 1), :]                 # (1, bq)
        vu_i = vu_ref[pl.ds(i, 1), :]
        hit = jnp.where((kv == ku_i) & (ku_i != PAD), vv, 0.0)
        return acc + vu_i * jnp.sum(hit, axis=0, keepdims=True)

    o_ref[...] = jax.lax.fori_loop(0, kv.shape[0], body,
                                   jnp.zeros(o_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("bq", "interpret"))
def hp_join(ku, vu, kv, vv, *, interpret: bool, bq: int = 128):
    """ku/vu/kv/vv: (B, K) packed rows; returns (B,) f32 scores.

    ``bq`` pairs share a grid cell; on a TPU it must be a multiple of
    128 (the lane width)."""
    B, K = ku.shape
    b_pad = -(-B // bq) * bq

    def key_major(x, fill):
        return jnp.pad(x, ((0, b_pad - B), (0, 0)),
                       constant_values=fill).T

    args = (key_major(ku, PAD), key_major(vu, 0.0),
            key_major(kv, PAD), key_major(vv, 0.0))
    out = pl.pallas_call(
        _kernel,
        grid=(b_pad // bq,),
        in_specs=[pl.BlockSpec((K, bq), lambda i: (0, i))] * 4,
        out_specs=pl.BlockSpec((1, bq), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, b_pad), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[0, :B]
