"""Public jit'd wrappers for the track-processing kernels.

Each op pads inputs to kernel-friendly shapes, dispatches to the Pallas
kernel (compiled on a TPU, interpreted elsewhere: see
:func:`repro.device.interpret_kernels`) or to the pure-jnp oracle
(``backend='ref'``), and unpads the result. The segments pipeline and the
benchmarks call these, never the kernels directly.
"""

from __future__ import annotations

import threading
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.device import interpret_kernels, on_tpu
from repro.kernels import ref, segment_pipeline
from repro.kernels.agl_lookup import TILE_H, TILE_W, agl_lookup_pallas
from repro.kernels.dynamic_rates import dynamic_rates_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.track_interp import track_interp_pallas

Backend = Literal["pallas", "ref"]


# ---------------------------------------------------------------------------
# Pipeline instrumentation (read by chipbench/run.py for
# ``pipeline_calls.process``, and by chip_smoke.py).
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()
_STATS = {"compile_hits": 0, "compile_misses": 0}
_SEEN_FUSED_SHAPES: set = set()


def reset_pipeline_stats(forget_shapes: bool = True) -> None:
    """Zero the compile counters.  ``forget_shapes=False``
    keeps the seen-shape set so already-compiled bucket shapes keep
    counting as cache hits (steady-state measurement)."""
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0
        if forget_shapes:
            _SEEN_FUSED_SHAPES.clear()


def get_pipeline_stats() -> dict:
    with _STATS_LOCK:
        return dict(_STATS)


def get_pipeline_shapes() -> list[dict]:
    """The distinct fused-pipeline programs requested since the last
    reset, one dict each: argument shapes, DEM grid, ``dt``, backend
    and AGL variant (the keys of :func:`process_segments`' cache)."""
    names = ("dem", "t_in", "t_out", "grid", "dt", "use_pallas",
             "agl_oracle")
    with _STATS_LOCK:
        return [dict(zip(names, key)) for key in sorted(_SEEN_FUSED_SHAPES)]


def _pad_to(x: jax.Array, axis: int, multiple: int,
            value: float = 0.0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def track_interp(t_in, v_in, count, t_out, *,
                 backend: Backend = "pallas", block_m: int = 256):
    """(B,N),(B,C,N),(B,),(B,M) -> (B,M,C). See ref.track_interp_ref."""
    if backend == "ref":
        return ref.track_interp_ref(t_in, v_in, count, t_out)
    M = t_out.shape[1]
    block_m = min(block_m, _next_mult(M, 128))
    t_out_p = _pad_to(jnp.asarray(t_out), 1, block_m)
    # Pad knot axis to 128 lanes with +inf times so padding never brackets.
    t_in_p = _pad_to(jnp.asarray(t_in, jnp.float32), 1, 128, value=np.inf)
    v_in_p = _pad_to(jnp.asarray(v_in, jnp.float32), 2, 128)
    out = track_interp_pallas(t_in_p, v_in_p, count, t_out_p,
                              block_m=block_m, interpret=interpret_kernels())
    return jnp.moveaxis(out, 1, 2)[:, :M, :]


def dynamic_rates(v, count, dt, *, backend: Backend = "pallas"):
    """(B,3,M),(B,) -> (B,4,M). See ref.dynamic_rates_ref."""
    if backend == "ref":
        return ref.dynamic_rates_ref(v, count, dt)
    M = v.shape[2]
    v_p = _pad_to(jnp.asarray(v, jnp.float32), 2, 128)
    out = dynamic_rates_pallas(v_p, count, float(dt),
                               interpret=interpret_kernels())
    return out[:, :, :M]


# The spanning-row oracle fallback runs jitted so its f32 rounding
# matches the fused pipeline's oracle variant (which evaluates the same
# oracle under jit); eager op-by-op evaluation can drift an ulp off it.
_agl_lookup_ref_jit = jax.jit(ref.agl_lookup_ref)


def agl_lookup(dem, fi, fj, alt_msl, *, backend: Backend = "pallas"):
    """(H,W),(B,M),(B,M),(B,M) -> (B,M) AGL. See ref.agl_lookup_ref.

    Computes per-track tile origins on the host side; tracks that span
    more than one DEM tile (rare wide-area tracks — the paper's §V
    'hundreds of nautical miles' case) are routed — row by row, not
    whole-batch — to the oracle, while every other row stays on the
    Pallas tile path.  The origin math runs in numpy on the caller's
    arrays, so host inputs (the common case) cost no device->host sync;
    the fully device-resident variant of this op is
    :func:`process_segments`.
    """
    if backend == "ref":
        return ref.agl_lookup_ref(dem, fi, fj, alt_msl)
    H, W = dem.shape
    # Host-side (concrete) clip + origin/extent math — numpy throughout,
    # so already-host inputs never bounce off the device first.
    fi_c = np.clip(np.asarray(fi, np.float32), 0.0,
                   np.float32(H - 1.001))
    fj_c = np.clip(np.asarray(fj, np.float32), 0.0,
                   np.float32(W - 1.001))
    alt_np = np.asarray(alt_msl, np.float32)
    oi = (fi_c.min(axis=1) // TILE_H).astype(np.int32)
    oj = (fj_c.min(axis=1) // TILE_W).astype(np.int32)
    spans = (((fi_c.max(axis=1) - oi * TILE_H) >= TILE_H - 1)
             | ((fj_c.max(axis=1) - oj * TILE_W) >= TILE_W - 1))
    B, M = fi_c.shape
    dem = jnp.asarray(dem, jnp.float32)
    if bool(spans.all()):
        return _agl_lookup_ref_jit(dem, fi_c, fj_c, alt_np)

    fit = ~spans
    dem_p = _pad_to(_pad_to(dem, 0, TILE_H), 1, TILE_W)
    # Keep origins inside the padded grid.
    oi = np.minimum(oi[fit], dem_p.shape[0] // TILE_H - 1)
    oj = np.minimum(oj[fit], dem_p.shape[1] // TILE_W - 1)
    fi_p = _pad_to(jnp.asarray(fi_c[fit]), 1, 128)
    fj_p = _pad_to(jnp.asarray(fj_c[fit]), 1, 128)
    alt_p = _pad_to(jnp.asarray(alt_np[fit]), 1, 128)
    out_fit = agl_lookup_pallas(dem_p, fi_p, fj_p, alt_p,
                                jnp.asarray(oi), jnp.asarray(oj),
                                interpret=interpret_kernels())[:, :M]
    if not spans.any():
        return out_fit
    out_spanning = _agl_lookup_ref_jit(dem, fi_c[spans], fj_c[spans],
                                       alt_np[spans])
    out = jnp.zeros((B, M), jnp.float32)
    out = out.at[np.flatnonzero(fit)].set(out_fit)
    return out.at[np.flatnonzero(spans)].set(out_spanning)


def process_segments(dem, t_in, v_in, count_in, t_out, count_out, *,
                     grid, dt: float = 1.0, backend: Backend = "pallas",
                     agl_oracle: bool = False):
    """Fused on-device segment pipeline: interp + AGL + rates, one jit.

    Replaces the ``track_interp -> host numpy -> agl_lookup ->
    dynamic_rates`` sequence with a single compiled call: DEM
    fractional-index math, bilinear AGL lookup (with a per-row oracle
    fallback for tile-spanning tracks), rate estimation and the padding
    masks all execute on device; no intermediate ever crosses the
    host<->device boundary.  See :mod:`repro.kernels.segment_pipeline`.

    Args:
      dem: (H, W) elevation grid.
      t_in, v_in, count_in: (B, N) knot times, (B, 3, N) lat/lon/alt
        knots, (B,) valid knot counts.
      t_out, count_out: (B, K) query grid, (B,) valid output lengths.
      grid: (lat_min, lat_max, lon_min, lon_max, cells_per_deg) DEM
        affine transform.
      dt: uniform grid spacing in seconds.
      backend: 'pallas' fuses the Pallas kernels; 'ref' composes the
        pure-jnp oracles (the correctness reference).
      agl_oracle: True computes AGL with the oracle gather for every
        row (the always-correct variant for tracks that may cross a
        DEM tile border); False (default) uses the single-tile Pallas
        kernel — the caller must prove the tracks fit one tile
        (segments.py proves it from the raw knot extents).

    Returns:
      dict of (B, K) f32 device arrays keyed by
      :data:`segment_pipeline.FIELDS`, masked to ``count_out``.
    """
    use_pallas = backend != "ref"
    key = (np.shape(dem), np.shape(t_in), np.shape(t_out),
           tuple(float(g) for g in grid), float(dt), use_pallas,
           bool(agl_oracle))
    with _STATS_LOCK:
        if key in _SEEN_FUSED_SHAPES:
            _STATS["compile_hits"] += 1
        else:
            _SEEN_FUSED_SHAPES.add(key)
            _STATS["compile_misses"] += 1
    return segment_pipeline.process_segments(
        dem, t_in, v_in, count_in, t_out, count_out, grid=grid, dt=dt,
        use_pallas=use_pallas, agl_oracle=agl_oracle,
        interpret=interpret_kernels(), donate=on_tpu())


def flash_attention(q, k, v, *, causal: bool = True,
                    backend: Backend = "pallas",
                    block_q: int = 128, block_k: int = 128):
    """Blocked online-softmax attention (GQA): q (B,H,T,hd),
    k/v (B,KV,S,hd) -> (B,H,T,hd). Pads T/S to block multiples.

    This is the real-TPU attention path (attention_impl='flash' on
    ArchConfig); the dry-run keeps stock-XLA attention so cost_analysis
    stays faithful (DESIGN.md §3)."""
    if backend == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    B, H, T, hd = q.shape
    S = k.shape[2]
    bq = min(block_q, _next_mult(T, 128))
    bk = min(block_k, _next_mult(S, 128))
    q_p = _pad_to(jnp.asarray(q), 2, bq)
    k_p = _pad_to(jnp.asarray(k), 2, bk)
    v_p = _pad_to(jnp.asarray(v), 2, bk)
    out = flash_attention_pallas(q_p, k_p, v_p, causal=causal,
                                 block_q=bq, block_k=bk,
                                 q_len=T, kv_len=S,
                                 interpret=interpret_kernels())
    return out[:, :, :T]


def _next_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
