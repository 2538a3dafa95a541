"""Fused, device-resident segment pipeline (one jit, zero host hops).

The per-task hot path of the track workflow composes the three Pallas
kernels (``track_interp``, ``agl_lookup``, ``dynamic_rates``) plus the
DEM fractional-index math and the padding masks under ONE ``jax.jit``:
inputs go up once, the nine output planes come down once, and every
intermediate (the resampled grid, fi/fj, tile origins, rate stack)
stays on device.

AGL tile fallback: tracks that span more than one DEM tile cannot use
the single-tile Pallas kernel.  The standalone ``ops.agl_lookup``
detects this from the interpolated indices on the host; here the
caller proves the single-tile property BEFORE launching — the interp
output is a convex combination of the raw knots, so knot extents bound
it — and tile-crossing buckets compile the oracle gather variant
(``agl_oracle=True``) while everything else compiles gather-free.  No
sync, no runtime branch, and the per-variant graphs stay bit-identical
to the standalone kernels (a runtime ``lax.cond``/``where`` mix would
let XLA contract the two sides differently at ulp level).

Ragged batching: callers bin segments into power-of-two width buckets
(:data:`repro.tracks.segments.BUCKET_SIZES`) and invoke this pipeline
once per bucket shape; jit caches one compilation per shape.  Widths
must be multiples of 128 (TPU lane width) — the wrapper pads if not.
The bucket shapes need not come from payload data at all: the columnar
track store (:mod:`repro.store`) records every segment's
(``seg_knots``, ``seg_grid``) pair in its manifest at ingest, via the
same :func:`repro.tracks.segments.segment_shape` helper the live
batcher uses, so ``StoreManifest.bucket_histogram`` /
``TrackStore.plan`` hand this pipeline its bucket plan from the index
while the shard payloads are still compressed on disk (and the store's
prefetcher decodes shard N+1 while this pipeline runs shard N).

On TPU the input buffers are donated (they are packing scratch, never
reused), letting XLA reuse them for intermediates; donation is skipped
on CPU where it is unsupported and only warns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.agl_lookup import TILE_H, TILE_W, agl_lookup_pallas
from repro.kernels.dynamic_rates import dynamic_rates_pallas
from repro.kernels.track_interp import track_interp_pallas

#: Output planes of the fused pipeline, in order.
FIELDS = ("times", "lat", "lon", "alt_msl", "alt_agl",
          "vrate", "gspeed", "heading", "turn")

_LANE = 128     # TPU lane width; all batched track axes pad to this


def _next_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pipeline(dem, t_in, v_in, count_in, t_out, count_out,
              *, grid: tuple, dt: float, interpret: bool,
              use_pallas: bool, agl_oracle: bool):
    """Traced body: interp -> fi/fj -> AGL -> rates -> masks, on device."""
    lat_min, lat_max, lon_min, lon_max, cells_per_deg = grid
    B, K = t_out.shape
    H, W = dem.shape

    # 1. Resample onto the uniform grid (MXU masked-matmul kernel).
    if use_pallas:
        block_m = min(256, K)
        interp = track_interp_pallas(t_in, v_in, count_in, t_out,
                                     block_m=block_m, interpret=interpret)
    else:
        interp = jnp.moveaxis(
            ref.track_interp_ref(t_in, v_in, count_in, t_out), 2, 1)
    # Stage-boundary barrier: it pins the interp result's f32 roundings
    # to those of the standalone ops (``ops.track_interp`` feeding
    # ``ops.agl_lookup`` / ``ops.dynamic_rates``) and of the
    # ``use_pallas=False`` graph, which the tests compare against.
    # Without it XLA may contract interp's epilogue into downstream
    # FMAs and drift the fused outputs an ulp off both.
    interp = jax.lax.optimization_barrier(interp)           # (B, 3, K)
    lat = interp[:, 0]
    lon = interp[:, 1]
    alt = interp[:, 2]

    # 2. DEM fractional indices from the affine grid — previously host
    #    VPU elementwise.  The optimization barrier pins the rounding of
    #    fi/fj to that of the standalone ``ops.agl_lookup`` inputs:
    #    without it XLA may fuse the affine math into the AGL kernel's
    #    tile-local index FMAs and drift an ulp off them (amplified by
    #    the local terrain gradient).
    fi = (jnp.clip(lat, lat_min, lat_max) - lat_min) * cells_per_deg
    fj = (jnp.clip(lon, lon_min, lon_max) - lon_min) * cells_per_deg
    fi = jnp.clip(fi, 0.0, H - 1.001)
    fj = jnp.clip(fj, 0.0, W - 1.001)
    fi, fj = jax.lax.optimization_barrier((fi, fj))

    # 3. AGL = MSL - bilinear DEM elevation.  ``agl_oracle`` is decided
    #    STATICALLY by the caller (from raw knot extents — interp
    #    output is a convex combination of knots): a bucket proven to
    #    stay inside one DEM tile compiles the single-tile Pallas
    #    kernel and no gather at all; a bucket that may cross a tile
    #    border compiles the oracle gather for all of its rows.  A
    #    runtime per-row select (`lax.cond`/`where` mixing the two) is
    #    deliberately avoided: XLA contracts the mixed graphs
    #    differently and the selected values drift an ulp off the
    #    standalone kernels.
    if use_pallas and not agl_oracle:
        dem_p = jnp.pad(dem, ((0, _next_mult(H, TILE_H) - H),
                              (0, _next_mult(W, TILE_W) - W)))
        oi = jnp.floor(jnp.min(fi, axis=1) / TILE_H).astype(jnp.int32)
        oj = jnp.floor(jnp.min(fj, axis=1) / TILE_W).astype(jnp.int32)
        oi = jnp.minimum(oi, dem_p.shape[0] // TILE_H - 1)
        oj = jnp.minimum(oj, dem_p.shape[1] // TILE_W - 1)
        agl = agl_lookup_pallas(dem_p, fi, fj, alt, oi, oj,
                                interpret=interpret)
    else:
        agl = ref.agl_lookup_ref(dem, fi, fj, alt)

    # 4. Dynamic rates over the resampled grid (VPU stencil kernel).
    if use_pallas:
        rates = dynamic_rates_pallas(interp, count_out, dt,
                                     interpret=interpret)
    else:
        rates = ref.dynamic_rates_ref(interp, count_out, dt)

    # 5. Padding masks, still on device.
    mask = (jax.lax.broadcasted_iota(jnp.int32, (B, K), 1)
            < count_out[:, None]).astype(jnp.float32)
    return {
        "times": t_out * mask,
        "lat": lat * mask, "lon": lon * mask,
        "alt_msl": alt * mask, "alt_agl": agl * mask,
        "vrate": rates[:, 0] * mask, "gspeed": rates[:, 1] * mask,
        "heading": rates[:, 2] * mask, "turn": rates[:, 3] * mask,
    }


@functools.lru_cache(maxsize=None)
def _jitted(grid: tuple, dt: float, interpret: bool, use_pallas: bool,
            agl_oracle: bool, donate: bool):
    # ``grid`` is static (one DEM per processor): five fewer traced
    # scalars to ship per dispatch.
    fn = functools.partial(_pipeline, grid=grid, dt=dt,
                           interpret=interpret, use_pallas=use_pallas,
                           agl_oracle=agl_oracle)
    if donate:
        # t_in / v_in / t_out are packing scratch — donate on TPU.
        return jax.jit(fn, donate_argnums=(1, 2, 4))
    return jax.jit(fn)


def _pad_tracks(t_in, v_in, t_out):
    """Pad the track axes to the 128-lane multiple the kernels need.

    Knot padding is FINITE and increasing (last time + 1, 2, ...) so the
    masked interp weights are exactly zero (inf padding would produce
    0 * inf = nan inside the MXU mask product); values hold the last
    knot.  Query padding holds the last query (constant extrapolation,
    masked out afterwards).
    """
    N = t_in.shape[1]
    K = t_out.shape[1]
    Np, Kp = _next_mult(N, _LANE), _next_mult(K, _LANE)
    if Np != N:
        step = np.arange(1, Np - N + 1, dtype=np.float32)
        t_in = jnp.concatenate(
            [t_in, t_in[:, -1:] + step[None, :]], axis=1)
        v_in = jnp.concatenate(
            [v_in, jnp.broadcast_to(v_in[:, :, -1:],
                                    v_in.shape[:2] + (Np - N,))], axis=2)
    if Kp != K:
        t_out = jnp.concatenate(
            [t_out, jnp.broadcast_to(t_out[:, -1:],
                                     (t_out.shape[0], Kp - K))], axis=1)
    return t_in, v_in, t_out, K


def process_segments(dem, t_in, v_in, count_in, t_out, count_out, *,
                     grid, dt: float = 1.0, use_pallas: bool = True,
                     agl_oracle: bool = False,
                     interpret: bool = True, donate: bool = False):
    """Run the fused pipeline on one (B, K) bucket of segments.

    Args:
      dem: (H, W) f32 elevation grid (un-padded; padded inside the jit).
      t_in, v_in, count_in: (B, N), (B, 3, N) lat/lon/alt knots, (B,).
      t_out, count_out: (B, K) query grid + (B,) valid lengths.
      grid: (lat_min, lat_max, lon_min, lon_max, cells_per_deg) — the
        DEM affine transform, traced as scalars (no retrace per value).
      dt: uniform grid spacing (static).
      use_pallas: False composes the pure-jnp oracles instead (the
        correctness reference for tests).
      agl_oracle: True runs the oracle AGL gather for every row (the
        variant for tracks that may cross a DEM tile border — always
        correct, TPU-slow); False (default) runs the single-tile Pallas
        kernel, which clamps tile-crossing tracks to the tile border —
        callers must prove their tracks fit (segments.py proves it from
        raw knot extents).
      interpret: run Pallas in interpret mode (CPU).
      donate: donate the packing buffers (TPU only; CPU warns).

    Returns:
      dict of (B, K) f32 planes keyed by :data:`FIELDS`, all masked to
      ``count_out`` (device arrays; fetch with one ``jax.device_get``).
    """
    t_in = jnp.asarray(t_in, jnp.float32)
    v_in = jnp.asarray(v_in, jnp.float32)
    t_out = jnp.asarray(t_out, jnp.float32)
    t_in, v_in, t_out, K = _pad_tracks(t_in, v_in, t_out)
    fn = _jitted(tuple(float(g) for g in grid), float(dt),
                 bool(interpret), bool(use_pallas), bool(agl_oracle),
                 bool(donate))
    out = fn(jnp.asarray(dem, jnp.float32), t_in, v_in,
             jnp.asarray(count_in, jnp.int32), t_out,
             jnp.asarray(count_out, jnp.int32))
    if out["times"].shape[1] != K:
        out = {k: v[:, :K] for k, v in out.items()}
    return out
