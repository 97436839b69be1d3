"""Pallas TPU kernel: fused CIN layer (xDeepFM feature interaction).

The reference materializes the (B, h, m, D) outer product in HBM. The
fused kernel never leaves VMEM: for each (batch-block, embedding dim d)
grid cell it forms Z = vec(xk[:, :, d] (x) x0[:, :, d]) on the fly as a
(BB, h*m) tile and hits the MXU with the reshaped weight (h*m, h'):

    out[:, :, d] = Z @ W_flat^T

Arithmetic intensity rises from O(1) (outer product streamed to HBM)
to O(h') per element -- the xDeepFM hot path becomes MXU-bound, which
is exactly the hardware-adaptation story for recsys interaction ops.

Grid: (B // BB, D). VMEM per cell: x0 (BB, m), xk (BB, h),
Z (BB, h*m), W (h*m, h') -- for the assigned config (h=h'=200, m=39,
BB=64) about 4.4 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x0_ref, xk_ref, w_ref, o_ref):
    x0 = x0_ref[..., 0]                   # (BB, m)
    xk = xk_ref[..., 0]                   # (BB, h)
    W = w_ref[...]                        # (h*m, h')
    BB = x0.shape[0]
    z = (xk[:, :, None] * x0[:, None, :]).reshape(BB, -1)   # (BB, h*m)
    o_ref[..., 0] = jax.lax.dot(z, W, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def cin_layer(x0, xk, W, *, interpret: bool, bb: int = 64):
    """x0 (B, m, D), xk (B, h, D), W (h', h, m) -> (B, h', D)."""
    B, m, D = x0.shape
    h = xk.shape[1]
    hp = W.shape[0]
    assert B % bb == 0, (B, bb)
    w_flat = W.reshape(hp, h * m).T                       # (h*m, h')
    grid = (B // bb, D)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, m, 1), lambda i, d: (i, 0, d)),
            pl.BlockSpec((bb, h, 1), lambda i, d: (i, 0, d)),
            pl.BlockSpec((h * m, hp), lambda i, d: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, hp, 1), lambda i, d: (i, 0, d)),
        out_shape=jax.ShapeDtypeStruct((B, hp, D), jnp.float32),
        interpret=interpret,
    )(x0, xk, w_flat)
