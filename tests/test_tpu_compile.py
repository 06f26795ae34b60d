"""The Pallas kernels compile for a TPU v5e at the paper's shapes.

Interpret mode (the rest of the suite) cannot see what the chip's
compiler refuses: unaligned blocks, vector shapes Mosaic cannot lower,
more VMEM than a kernel may use. These tests lower and compile each kernel
of the served path with interpret=False for a described v5e chip — no chip
attached — at every stage of the paper's deployment (configs/cmax_camel.py:
240x180 sensor, three stages, its megakernel capacity) and, for the
batched megakernel, at batch 1 and at the service's largest batch class.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import cmax_camel
from repro.kernels.blur_stats import blur_stats_streaming
from repro.kernels.iwe_accum import CHUNK, tile_accumulate
from repro.kernels.megakernel import megakernel_stats

CFG = cmax_camel.MEGAKERNEL
STAGES = [pytest.param(i, id=f"s{st.scale:g}")
          for i, st in enumerate(CFG.stages)]
CAP = -(-CFG.engine_capacity // CHUNK) * CHUNK
TILE = (8, 128)      # ops.iwe_accum's default tile
BLUR_RB = 16         # ops.blur_stats's default row block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _stage_dims(si):
    st = CFG.stages[si]
    H, W = CFG.camera.grid(st.scale)
    k = st.blur_taps
    Wp = -(-(W + k // 2) // 128) * 128
    return H, W, k, Wp


def _assert_compiled_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("si", STAGES)
def test_megakernel_compiles_for_v5e(one_chip, si, batch):
    H, W, k, Wp = _stage_dims(si)
    rb = CFG.engine_rb
    n_slabs = -(-(H + k // 2) // rb)
    compiled = megakernel_stats.lower(
        _sds((batch, n_slabs, 1, CAP), jnp.int32, one_chip),
        _sds((batch, n_slabs, 4, CAP), jnp.float32, one_chip),
        _sds((k,), jnp.float32, one_chip),
        rb=rb, k=k, H=H, W=W, Wp=Wp, interpret=False).compile()
    _assert_compiled_kernel(compiled)


@pytest.mark.parametrize("si", STAGES)
def test_blur_stats_compiles_for_v5e(one_chip, si):
    H, W, k, Wp = _stage_dims(si)
    Hp = -(-(H + k // 2) // BLUR_RB) * BLUR_RB
    compiled = blur_stats_streaming.lower(
        _sds((Hp, 4, Wp), jnp.float32, one_chip),
        _sds((k,), jnp.float32, one_chip),
        rb=BLUR_RB, k=k, H=H, W=W, interpret=False).compile()
    _assert_compiled_kernel(compiled)


@pytest.mark.parametrize("si", STAGES)
def test_tile_accumulate_compiles_for_v5e(one_chip, si):
    H, W, _, _ = _stage_dims(si)
    n_tiles = -(-H // TILE[0]) * -(-W // TILE[1])
    compiled = tile_accumulate.lower(
        _sds((n_tiles, 1, CAP), jnp.int32, one_chip),
        _sds((n_tiles, 4, CAP), jnp.float32, one_chip),
        p_tile=TILE[0] * TILE[1], interpret=False).compile()
    _assert_compiled_kernel(compiled)
