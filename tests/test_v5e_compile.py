"""Compile the serving programs for a v5e chip at the deployment's widths.

Nothing runs: each program is lowered against a *described* v5e:2x2
topology and compiled by the TPU compiler installed with jax, which
refuses what the chip would refuse (mis-tiled Pallas blocks, ops Mosaic
cannot lower, programs that do not fit HBM). The widths are those of
the ``chip_smoke.py`` deployment: ``powerlaw_fast(10**6, k=6)`` built by
``build_index_scale`` at eps = 0.1 (l_max = 24), padded to the engine's
capacity buckets. Every backend that ``"auto"`` resolves to on a TPU is
compiled here.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and test collection must be
the same in every worker.
"""
import os

import pytest

N = 10**6
M = 6 * N              # edges drawn; 3,047,035 remain after dedup
WIDTH = 316            # packed width of the built 10^6 artifact
L_MAX = 24
SOURCE_BATCH = 8       # EngineConfig defaults
PAIR_BATCH = 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from the
    # persistent cache, so keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _serving_args(one_chip):
    """ShapeDtypeStructs of the engine's single-device arrays."""
    import jax
    import jax.numpy as jnp

    from repro.core.hp_index import capacity_bucket

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wc, ec = capacity_bucket(WIDTH), capacity_bucket(M)
    return dict(keys=s((N, wc), jnp.int32), vals=s((N, wc), jnp.float32),
                d=s((N,), jnp.float32), src=s((ec,), jnp.int32),
                dst=s((ec,), jnp.int32), w=s((ec,), jnp.float32),
                us=s((SOURCE_BATCH,), jnp.int32), tau=s((), jnp.float32),
                pu=s((PAIR_BATCH,), jnp.int32))


def _fits_hbm(compiled):
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes)
    assert used < 16 * 2**30, used


def test_lax_single_source_compiles_for_v5e(one_chip):
    from repro.core.single_source import batched_single_source
    a = _serving_args(one_chip)
    compiled = batched_single_source.lower(
        a["keys"], a["vals"], a["d"], a["src"], a["dst"], a["w"],
        a["us"], a["tau"], n=N, l_max=L_MAX).compile()
    _fits_hbm(compiled)


@pytest.mark.parametrize("k", [16, 256])
def test_lax_topk_compiles_for_v5e(one_chip, k):
    from repro.core.topk import batched_topk
    a = _serving_args(one_chip)
    compiled = batched_topk.lower(
        a["keys"], a["vals"], a["d"], a["src"], a["dst"], a["w"],
        a["us"], a["tau"], n=N, l_max=L_MAX, k=k).compile()
    _fits_hbm(compiled)


def test_lax_pair_join_compiles_for_v5e(one_chip):
    from repro.core.index import _pair_query_batch
    a = _serving_args(one_chip)
    compiled = _pair_query_batch.lower(
        a["keys"], a["vals"], a["d"], a["pu"], a["pu"], n=N).compile()
    _fits_hbm(compiled)


def test_hp_join_compiles_for_v5e(one_chip):
    """The TPU pair default: the Pallas kernel must be in the program,
    compiled by Mosaic, not interpreted."""
    from repro.kernels.hp_join.ops import pair_query_batch_pallas
    a = _serving_args(one_chip)
    compiled = pair_query_batch_pallas.lower(
        a["keys"], a["vals"], a["d"], a["pu"], a["pu"], n=N,
        interpret=False).compile()
    _fits_hbm(compiled)
    assert "tpu_custom_call" in compiled.as_text()
