"""Pure-jnp oracles for every Pallas kernel (the correctness reference).

These are straightforward, unfused implementations; tests sweep shapes
and dtypes asserting the Pallas kernels (interpret mode on CPU) match.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def track_interp_ref(t_in: jax.Array, v_in: jax.Array, count: jax.Array,
                     t_out: jax.Array) -> jax.Array:
    """Piecewise-linear resample of tracks onto a new time grid.

    Args:
      t_in:  (B, N) sorted observation times (padding after ``count``).
      v_in:  (B, C, N) channel values at t_in.
      count: (B,) int32 — number of valid observations per track (>= 2).
      t_out: (B, M) query times.
    Returns:
      (B, M, C) linearly interpolated values; t_out clamped to the valid
      time range of each track (constant extrapolation at the ends).
    """
    B, N = t_in.shape
    C = v_in.shape[1]
    M = t_out.shape[1]

    def one(tb, vb, cb, qb):
        last = cb - 1
        t0 = tb[0]
        tl = tb[last]
        q = jnp.clip(qb, t0, tl)
        # Right bracketing index in [1, last].
        idx = jnp.searchsorted(tb[:], q, side="right")
        idx = jnp.clip(idx, 1, last)
        tj = tb[idx - 1]
        tj1 = tb[idx]
        w = jnp.where(tj1 > tj, (q - tj) / jnp.where(tj1 > tj, tj1 - tj, 1.0),
                      0.0)
        vl = vb[:, idx - 1]     # (C, M)
        vr = vb[:, idx]
        return ((1.0 - w)[None, :] * vl + w[None, :] * vr).T   # (M, C)

    return jax.vmap(one)(t_in, v_in, count, t_out)


def dynamic_rates_ref(v: jax.Array, count: jax.Array,
                      dt: float) -> jax.Array:
    """Dynamic rates from a uniformly resampled track (paper §III.A).

    Args:
      v: (B, 3, M) — lat (deg), lon (deg), altitude (m) on a uniform grid.
      count: (B,) int32 valid lengths.
      dt: grid spacing in seconds.
    Returns:
      (B, 4, M): vertical rate (m/s), ground speed (m/s), heading (rad,
      from north, clockwise), turn rate (rad/s). Positions >= count are 0.
    """
    B, _, M = v.shape
    lat, lon, alt = v[:, 0], v[:, 1], v[:, 2]
    m_per_deg = 111_111.0
    idx = jnp.arange(M)[None, :]
    last = (count - 1)[:, None]
    li = jnp.maximum(idx - 1, 0)
    ri = jnp.clip(idx + 1, 0, jnp.maximum(last, 0))
    denom = jnp.maximum(ri - li, 1).astype(jnp.float32) * dt

    def central(x):
        # difference between clamped neighbors: central inside the valid
        # range, one-sided at both track ends.
        return (jnp.take_along_axis(x, ri, axis=1)
                - jnp.take_along_axis(x, li, axis=1)) / denom

    vrate = central(alt)
    dn = central(lat) * m_per_deg                       # north velocity m/s
    de = central(lon) * m_per_deg * jnp.cos(jnp.deg2rad(lat))
    gspeed = jnp.sqrt(dn * dn + de * de)
    heading = jnp.arctan2(de, dn)
    dh = central(heading) * dt                          # un-normalized diff
    dh = (dh + jnp.pi) % (2.0 * jnp.pi) - jnp.pi        # wrap to (-pi, pi]
    turn = dh / dt
    out = jnp.stack([vrate, gspeed, heading, turn], axis=1)
    return jnp.where(idx[:, None, :] < count[:, None, None], out, 0.0)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True) -> jax.Array:
    """Plain-softmax GQA attention oracle for the flash kernel.

    q (B, H, T, hd); k, v (B, KV, S, hd) -> (B, H, T, hd) f32. Causal
    alignment: query t attends keys <= t + (S - T).
    """
    B, H, T, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    g = H // KV
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * hd ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, vv.astype(jnp.float32))


#: A host scalar, not a device array: importing the module initialises
#: no JAX backend (worker processes import it and must not).
_SCREEN_BIG = np.float32(1e30)


def encounter_screen_ref(lat: jax.Array, lon: jax.Array, alt: jax.Array,
                         valid: jax.Array, *, h_thresh_m: float,
                         v_thresh_m: float):
    """Pairwise miss-distance screen over time-aligned rows (one cell).

    Args:
      lat, lon, alt: (K, T) f32 — samples on a common 1-sample grid.
      valid: (K, T) f32 0/1 — sample presence mask.
      h_thresh_m / v_thresh_m: candidate thresholds (meters).
    Returns:
      ``(hit, min_dh, min_dv, t_idx)``, each (K, K) f32, populated on
      the strict upper triangle (i < j) only.  ``hit[i, j]`` is 1.0
      when rows i and j are simultaneously within *both* thresholds at
      some jointly valid instant; ``min_dh``/``min_dv`` are the minima
      of horizontal/vertical separation over those hit instants (1e30
      where no hit); ``t_idx`` is the first time index attaining
      ``min_dh``.  Local-tangent metric: 1 deg = 111_111 m, east
      meters scaled by cos of the pair's mean latitude — matching
      :func:`dynamic_rates_ref`.
    """
    K, T = lat.shape
    m_per_deg = jnp.float32(111_111.0)
    li, lj = lat[:, None, :], lat[None, :, :]
    dn = (li - lj) * m_per_deg
    de = ((lon[:, None, :] - lon[None, :, :]) * m_per_deg
          * jnp.cos(jnp.deg2rad(jnp.float32(0.5) * (li + lj))))
    dh = jnp.sqrt(dn * dn + de * de)
    dv = jnp.abs(alt[:, None, :] - alt[None, :, :])
    both = (valid[:, None, :] * valid[None, :, :]) > 0.5
    tri = (jnp.arange(K)[:, None] < jnp.arange(K)[None, :])[:, :, None]
    hit_t = both & tri & (dh <= jnp.float32(h_thresh_m)) \
        & (dv <= jnp.float32(v_thresh_m))
    dh_m = jnp.where(hit_t, dh, _SCREEN_BIG)
    dv_m = jnp.where(hit_t, dv, _SCREEN_BIG)
    hit = jnp.max(hit_t.astype(jnp.float32), axis=-1)
    min_dh = jnp.min(dh_m, axis=-1)
    min_dv = jnp.min(dv_m, axis=-1)
    t_idx = jnp.argmin(dh_m, axis=-1).astype(jnp.float32)
    return hit, min_dh, min_dv, t_idx


def agl_lookup_ref(dem: jax.Array, fi: jax.Array, fj: jax.Array,
                   alt_msl: jax.Array) -> jax.Array:
    """AGL altitude: MSL altitude minus bilinear DEM elevation.

    Args:
      dem: (H, W) elevation grid (m).
      fi, fj: (B, M) fractional row/col indices into dem.
      alt_msl: (B, M) MSL altitudes (m).
    Returns:
      (B, M) AGL altitudes (m).
    """
    H, W = dem.shape
    fi = jnp.clip(fi, 0.0, H - 1.000001)
    fj = jnp.clip(fj, 0.0, W - 1.000001)
    i0 = jnp.floor(fi).astype(jnp.int32)
    j0 = jnp.floor(fj).astype(jnp.int32)
    di = fi - i0
    dj = fj - j0
    z00 = dem[i0, j0]
    z01 = dem[i0, j0 + 1]
    z10 = dem[i0 + 1, j0]
    z11 = dem[i0 + 1, j0 + 1]
    elev = ((1 - di) * (1 - dj) * z00 + (1 - di) * dj * z01
            + di * (1 - dj) * z10 + di * dj * z11)
    return alt_msl - elev
