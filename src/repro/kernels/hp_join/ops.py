"""jit'd wrappers: batched single-pair queries through the join kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.hp_join import ref as ref_mod
from repro.kernels.hp_join.hp_join import PAD, hp_join


def fold_sqrt_d(index):
    """Pre-multiply packed HP values by sqrt(d_k) (key % n -> k).

    Returns (keys, folded_vals) ready for the kernel; see ref.py."""
    n = index.n
    keys = np.asarray(index.hp.keys)
    vals = index.vals_f32().astype(np.float64)
    ks = (keys.astype(np.int64) % n).clip(0, n - 1)
    sd = np.sqrt(np.maximum(index.d.astype(np.float64), 0.0))
    folded = (vals * sd[ks]).astype(np.float32)
    folded[keys == np.int32(PAD)] = 0.0
    return keys, folded


@partial(jax.jit, static_argnames=("n", "interpret"))
def pair_query_batch_pallas(keys, vals, d, us, vs, *, n: int,
                            interpret: bool):
    """Pallas twin of ``core.index._pair_query_batch``: same device
    arrays and (B,) float32 result. The sqrt(d_k) fold of ref.py is
    applied to the gathered (B, K) rows here, so the serving state
    needs no second, folded copy of the packed table."""
    sd = jnp.sqrt(jnp.maximum(d, 0.0))

    def fold(k, x):
        return jnp.where(k == PAD, 0.0,
                         x * sd[jnp.clip(k % n, 0, n - 1)])

    ku, kv = keys[us], keys[vs]
    return hp_join(ku, fold(ku, vals[us]), kv, fold(kv, vals[vs]),
                   interpret=interpret)


def query_pairs_kernel(index, us, vs, bq: int = 128) -> np.ndarray:
    keys, folded = fold_sqrt_d(index)
    out = hp_join(jnp.asarray(keys[us]), jnp.asarray(folded[us]),
                  jnp.asarray(keys[vs]), jnp.asarray(folded[vs]),
                  bq=bq, interpret=interpret_mode())
    return np.asarray(out)


def query_pairs_reference(index, us, vs) -> np.ndarray:
    keys, folded = fold_sqrt_d(index)
    out = ref_mod.join_ref(jnp.asarray(keys[us]), jnp.asarray(folded[us]),
                           jnp.asarray(keys[vs]), jnp.asarray(folded[vs]))
    return np.asarray(out)
