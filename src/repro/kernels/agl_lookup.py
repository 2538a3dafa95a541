"""Pallas TPU kernel: AGL altitude via bilinear DEM lookup.

The paper's step 3 computes above-ground-level altitude for every
observation: AGL = MSL - DEM(lat, lon). On CPU/GPU this is a 4-point
gather from the elevation raster. Fine-grained gathers are the worst case
for the TPU memory system, so we adapt:

  1. *Spatial locality*: one aircraft track covers a tiny DEM window
     (§V: per-sensor tracks bound the DEM working set — the paper calls
     out wide-area OpenSky tracks as the expensive case). Per track we
     prefetch one (TH, TW) DEM tile into VMEM, selected by a per-track
     block origin carried as scalar-prefetch operands.
  2. *Gather -> matmul*: bilinear interpolation of M points from a VMEM
     tile is computed as  colsum(A * (tile @ C))  where A (TH, M) holds
     the row weights (1-di, di) at rows (i0, i0+1) and C (TW, M) the
     column weights, points on the lanes. One MXU matmul + one VPU
     reduction replace M scattered 4-point gathers.

Tracks wider than a tile are clamped to its border; ops.py routes such
tracks (rare, detected on host) to the jnp oracle instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_H = 128
TILE_W = 256


def _kernel(oi_ref, oj_ref, fi_ref, fj_ref, alt_ref, dem_ref, out_ref):
    # Scalar prefetch: oi/oj (B,) block-origin indices (in tiles).
    # Blocks (row dim squeezed): fi/fj/alt/out (1, M), dem (TH, TW).
    b = pl.program_id(0)
    fi = fi_ref[...]                        # (1, M) fractional rows (global)
    fj = fj_ref[...]
    alt = alt_ref[...]
    tile = dem_ref[...]                     # (TH, TW) VMEM tile

    # Tile-local coordinates, clamped inside the tile.
    fi_loc = jnp.clip(fi - oi_ref[b].astype(jnp.float32) * TILE_H,
                      0.0, TILE_H - 1.001)
    fj_loc = jnp.clip(fj - oj_ref[b].astype(jnp.float32) * TILE_W,
                      0.0, TILE_W - 1.001)
    i0 = jnp.floor(fi_loc).astype(jnp.int32)
    j0 = jnp.floor(fj_loc).astype(jnp.int32)
    di = fi_loc - i0.astype(jnp.float32)
    dj = fj_loc - j0.astype(jnp.float32)

    M = fi.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (TILE_H, M), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (TILE_W, M), 0)
    # Bilinear weights as sparse one-hot-pair matrices, points on lanes.
    a_t = (jnp.where(rows == i0, 1.0 - di, 0.0)
           + jnp.where(rows == i0 + 1, di, 0.0))           # (TH, M)
    c_t = (jnp.where(cols == j0, 1.0 - dj, 0.0)
           + jnp.where(cols == j0 + 1, dj, 0.0))           # (TW, M)
    # (TH, TW) @ (TW, M) -> (TH, M) column-interpolated rows; then the
    # row-weighted sublane sum -> (1, M).  HIGHEST: a bf16 pass would
    # round terrain heights by metres.
    colsel = jnp.dot(tile, c_t, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    elev = jnp.sum(a_t * colsel, axis=0, keepdims=True)
    out_ref[...] = alt - elev


@functools.partial(jax.jit, static_argnames=("interpret",))
def agl_lookup_pallas(dem: jax.Array, fi: jax.Array, fj: jax.Array,
                      alt_msl: jax.Array, oi: jax.Array, oj: jax.Array,
                      *, interpret: bool = True) -> jax.Array:
    """AGL altitudes for B tracks of M points each.

    dem (H, W) f32 — H, W multiples of TILE_H/TILE_W (ops.py pads);
    fi/fj/alt_msl (B, M) f32 — global fractional DEM indices + MSL (m),
    M a multiple of 128; oi/oj (B,) i32 — per-track tile origins, in
    tile units.  Returns (B, M) f32 AGL (m).
    """
    B, M = fi.shape
    H, W = dem.shape
    if H % TILE_H or W % TILE_W:
        raise ValueError(f"dem {dem.shape} not tile-aligned")
    # Point rows become a unit sublane axis so each (1, M) block matches
    # the array's last two dims (the TPU tiling rule) for any B.
    row = pl.BlockSpec((None, 1, M), lambda b, oi, oj: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            row, row, row,
            pl.BlockSpec((TILE_H, TILE_W), lambda b, oi, oj: (oi[b], oj[b])),
        ],
        out_specs=row,
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, M), jnp.float32),
        interpret=interpret,
    )(oi.astype(jnp.int32), oj.astype(jnp.int32),
      fi.astype(jnp.float32).reshape(B, 1, M),
      fj.astype(jnp.float32).reshape(B, 1, M),
      alt_msl.astype(jnp.float32).reshape(B, 1, M), dem.astype(jnp.float32))
    return out.reshape(B, M)
