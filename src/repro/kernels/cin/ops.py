"""jit'd wrapper: full CIN stack through the fused kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.cin import ref as ref_mod
from repro.kernels.cin.cin import cin_layer


def cin_forward(x0, weights, bb: int = 64):
    """x0 (B, m, D); weights: list of (h_k, h_{k-1}, m).

    Returns (B, sum h_k) sum-pooled CIN features (kernel-backed)."""
    xk = x0
    pooled = []
    for W in weights:
        xk = cin_layer(x0, xk, W, bb=bb, interpret=interpret_mode())
        pooled.append(xk.sum(-1))
    return jnp.concatenate(pooled, axis=-1)


def cin_forward_reference(x0, weights):
    xk = x0
    pooled = []
    for W in weights:
        xk = ref_mod.cin_layer_ref(x0, xk, W)
        pooled.append(xk.sum(-1))
    return jnp.concatenate(pooled, axis=-1)
