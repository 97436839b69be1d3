"""The repo's one spelling of jax APIs that moved across releases.

Written for the installed jax (0.9): ``jax.shard_map`` with
``axis_names``/``check_vma`` and ``jax.make_mesh(..., axis_types=...)``.
Every call site goes through these helpers, so a version bump is a
one-file change.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def segment_sum(data, segment_ids, num_segments: int):
    """``jax.ops.segment_sum`` replacement on the scatter-add primitive.

    ``jax.ops.segment_sum`` is deprecated (and removed past the jax.ops
    namespace sunset); the indexed-add lowering is the same XLA scatter
    the old wrapper produced, so switching call sites is
    bit-equivalent. Negative or >= num_segments ids are dropped
    (scatter's out-of-bounds fill mode), matching the old semantics.
    """
    shape = (num_segments,) + data.shape[1:]
    return jnp.zeros(shape, data.dtype).at[segment_ids].add(
        data, mode="drop")


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` without varying-manual-axes checks.

    ``axis_names``: the manually-mapped mesh axes (defaults to all).
    """
    names = (frozenset(axis_names) if axis_names is not None
             else frozenset(mesh.axis_names))
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=names,
                         check_vma=False)
