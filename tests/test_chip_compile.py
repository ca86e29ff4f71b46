"""Compile the main path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler builds for a topology that is described
and not attached, and refuses what the chip would refuse (a kernel block
over the VMEM limit, a shape not aligned to the tiling).  Nothing runs, so
these tests say nothing about results or times.

Every v5e compile lives in this one file.  The topology is described inside
a fixture, never while a module is imported: only one process at a time may
load the TPU library, and the test worker that is given this file is the
one that loads it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import costmodel, features, predictors
from repro.dse_campaign import default_campaign_space
from repro.kernels import dse_sweep

N_CI_WORKLOADS = 6          # the six dry-run cells a campaign sweeps
FUSED_CHUNK = 32768         # the campaign benchmark's fused tile size


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("tile,n_workloads", [
    (4096, N_CI_WORKLOADS),
    (FUSED_CHUNK, N_CI_WORKLOADS),
    (131072, N_CI_WORKLOADS),
    # a batched serving window: more rows shrink the lane block
    (FUSED_CHUNK, 20),
])
def test_pallas_sweep_compiles_for_v5e(one_chip, tile, n_workloads):
    """The fused Pallas sweep plus its on-device screen and compaction,
    float32 as the campaign runs it on the chip, lowers to a Mosaic kernel;
    the compaction gathers block rows and scatters nothing."""
    lanes = dse_sweep.padded_lanes(tile, n_workloads)
    fn = dse_sweep._jit_dse_sweep(costmodel.SimConfig(), None, None, True,
                                  False, 2048)
    compiled = fn.lower(
        _sds(one_chip, (len(dse_sweep.CAND_COLS), lanes)),
        _sds(one_chip, (n_workloads, len(costmodel.WL_COLS)))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert " gather(" in text and " scatter(" not in text


def test_fused_jit_sweep_compiles_for_v5e(one_chip):
    """The fused jit sweep (plain XLA, no kernel) at the campaign tile."""
    fn = costmodel._jit_sweep_reduced(costmodel.SimConfig(), None, None,
                                      True, 2048)
    row = _sds(one_chip, (FUSED_CHUNK,))
    compiled = fn.lower(
        _sds(one_chip, (N_CI_WORKLOADS, len(costmodel.WL_COLS))),
        {k: row for k in costmodel.SWEEP_GATHER_FIELDS},
        *[row] * 6).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16e9


def test_forest_walk_compiles_for_v5e(one_chip):
    """The predictor-only path's jitted forest walk over the whole default
    space (a 40-tree forest padded to 1024 nodes per tree)."""
    n_trees = predictors.RandomForestRegressor().n_trees
    n_nodes = 1024
    ints = _sds(one_chip, (n_trees, n_nodes), jnp.int32)
    floats = _sds(one_chip, (n_trees, n_nodes))
    X = _sds(one_chip, (len(default_campaign_space()),
                        len(features.FEATURE_NAMES)))
    compiled = predictors._forest_predict_jnp.lower(
        ints, floats, ints, ints, floats, X, max_depth=12).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16e9


def test_knn_predict_compiles_for_v5e(one_chip):
    """The predictor-only path's KNN over the whole default space against a
    census-sized training set: the row-blocked distances stay far inside
    the chip's memory (the unblocked [N, n_train, F] block needed 14 GB)."""
    n_feat = len(features.FEATURE_NAMES)
    compiled = predictors._knn_predict_jnp.lower(
        _sds(one_chip, (2048, n_feat)), _sds(one_chip, (2048,)),
        _sds(one_chip, (len(default_campaign_space()), n_feat)),
        k=5).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 1e9
