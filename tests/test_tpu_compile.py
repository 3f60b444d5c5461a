"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The served path's programs at the chip-share RM1's widths: both shard
bag kernels at one MN's call shapes (64 bags x 200, 320, 80 or 201
tables x 80 slots, D=128, over a 400-table shard) and the CN dense step at RM1's published
MLP widths; and at DLRM-DCNv2's (MLPerf): both kernels at the table
counts its 4-MN pool routes to one MN (2,048 bags x 1, 7, 8 or 10 tables
x 100 slots over a 2,098,249-row shard) and its cross-network dense step
at 2,048 rows.  A compile that passes here is what the v5e compiler accepts;
it runs nothing, so it says nothing about results or times.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and every test worker imports
this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import dcnv2, rm1
from repro.kernels import embedding_bag as eb
from repro.models.dlrm import DLRMModel
from repro.serving.cluster import dense_step

CFG = rm1.CHIP_SHARE.dlrm
B, T_MN, SHARD_TABLES = 64, 200, 400


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    cache_on = jax.config.jax_enable_compilation_cache
    # a described chip's compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["embedding_bag_fused_flat",
                                    "embedding_bag_nmp_flat"])
def test_bag_kernel_compiles_for_v5e(kernel, one_chip):
    D, R, P = CFG.embed_dim, CFG.rows_per_table, CFG.avg_pooling
    fn = functools.partial(getattr(eb, kernel), interpret=False)
    compiled = jax.jit(fn).lower(
        _spec((SHARD_TABLES * R, D), jnp.float32, one_chip),
        _spec((T_MN,), jnp.int32, one_chip),
        _spec((B, T_MN, P), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel keeps its name= on the device, where a trace reads it
    assert f"%{kernel.removesuffix('_flat')}" in text
    assert compiled.out_info.shape == (B, T_MN, D)


@pytest.mark.parametrize("t_mn", [320, 80, 201])
@pytest.mark.parametrize("kernel", ["embedding_bag_fused_flat",
                                    "embedding_bag_nmp_flat"])
def test_bag_kernel_compiles_for_v5e_at_routed_shapes(kernel, t_mn,
                                                       one_chip):
    """The table counts a 2 DDR + 2 NMP pool routes to one MN (320 to an
    NMP MN, 80 to a DDR MN), and 201, which the wrapper pads to 208
    lanes for the fused call."""
    D, R, P = CFG.embed_dim, CFG.rows_per_table, CFG.avg_pooling
    fn = functools.partial(getattr(eb, kernel), interpret=False)
    compiled = jax.jit(fn).lower(
        _spec((SHARD_TABLES * R, D), jnp.float32, one_chip),
        _spec((t_mn,), jnp.int32, one_chip),
        _spec((B, t_mn, P), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (B, t_mn, D)


def test_dense_step_compiles_for_v5e(one_chip):
    model = DLRMModel(rm1.CHIP_SHARE)
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          model.param_shapes())
    compiled = dense_step(model).lower(
        params,
        _spec((B, CFG.num_dense_features), jnp.float32, one_chip),
        _spec((B, CFG.num_tables, CFG.embed_dim), jnp.float32,
              one_chip)).compile()
    assert compiled.out_info.shape == (B,)
    assert compiled.as_text().startswith("HloModule jit_dense_step,")


DCN = dcnv2.CHIP_SHARE.dlrm
DCN_B, DCN_SHARD_ROWS = 2048, 2_098_249


@pytest.mark.parametrize("t_mn", [1, 7, 8, 10])
@pytest.mark.parametrize("kernel", ["embedding_bag_fused_flat",
                                    "embedding_bag_nmp_flat"])
def test_bag_kernel_compiles_for_v5e_at_dcn_shapes(kernel, t_mn, one_chip):
    """DLRM-DCNv2's routed calls at the widest slot block (its 100-lookup
    table's): the NMP kernel's (2,048, 100) index tile per table does not
    fit SMEM whole, so its wrapper blocks the bags over grid steps."""
    fn = functools.partial(getattr(eb, kernel), interpret=False)
    compiled = jax.jit(fn).lower(
        _spec((DCN_SHARD_ROWS, DCN.embed_dim), jnp.float32, one_chip),
        _spec((t_mn,), jnp.int32, one_chip),
        _spec((DCN_B, t_mn, DCN.bag_width), jnp.int32, one_chip),
        _spec((t_mn,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (DCN_B, t_mn, DCN.embed_dim)


def test_dcn_dense_step_compiles_for_v5e(one_chip):
    model = DLRMModel(dcnv2.CHIP_SHARE)
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip),
                          model.param_shapes())
    assert set(params) == {"embed", "bottom", "cross", "top"}
    compiled = dense_step(model).lower(
        params,
        _spec((DCN_B, DCN.num_dense_features), jnp.float32, one_chip),
        _spec((DCN_B, DCN.num_tables, DCN.embed_dim), jnp.float32,
              one_chip)).compile()
    assert compiled.out_info.shape == (DCN_B,)
    assert compiled.as_text().startswith("HloModule jit_dense_step,")
