"""Compile the main path's Pallas programs for a described TPU v5e chip.

No chip is attached: the TPU compiler builds each program for a chip
described by ``get_topology_desc`` and refuses what the chip would
refuse (block tiling, unsupported lowerings, scoped VMEM) — the faults
that interpret mode never raises.  Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports every test file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import encounter_screen, segment_pipeline
from repro.tracks.segments import BUCKET_SIZES

GRID = (24.0, 50.0, -125.0, -66.0, 8.0)     # SyntheticGlobeDEM's grid
DEM_SHAPE = (209, 473)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip cannot be read back from
    # the persistent cache without one: keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("B", [1, 4, 64])
@pytest.mark.parametrize("agl_oracle", [False, True])
@pytest.mark.parametrize("K", BUCKET_SIZES)
def test_fused_pipeline_compiles_for_v5e(one_chip, K, agl_oracle, B):
    N = 128 if K == 128 else 256
    fn = functools.partial(segment_pipeline._pipeline, grid=GRID, dt=1.0,
                           interpret=False, use_pallas=True,
                           agl_oracle=agl_oracle)
    f32, i32 = jnp.float32, jnp.int32
    text = _compiled_text(fn, [(DEM_SHAPE, f32), ((B, N), f32),
                               ((B, 3, N), f32), ((B,), i32),
                               ((B, K), f32), ((B,), i32)], one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("C,K,T", [
    (2, 240, 4096),     # aerodrome-dense occupancy, a long cell span
    (64, 8, 128),       # en-route-sparse pairs
])
def test_screen_kernel_compiles_for_v5e(one_chip, C, K, T):
    fn = functools.partial(encounter_screen._screen_batch_pallas,
                           h_m=926.0, v_m=152.4, interpret=False)
    text = _compiled_text(fn, [((C, K, T), jnp.float32)] * 4, one_chip)
    assert "tpu_custom_call" in text
