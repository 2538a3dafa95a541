"""En-route hourly file: the program's ``tracks.datasets._synth_track_points``,
vectorised.  One straight-ish track per aircraft, 12-119 observations
10 s apart, all starting at the hour's first second."""

from __future__ import annotations

import numpy as np

from chipbench.gen import M_PER_DEG, Tracks, seg_cumsum


def tracks(gen: dict, n: int, rng: np.random.Generator,
           region: list) -> Tracks:
    """``n`` tracks starting uniformly in ``region`` = [lat0, lat1, lon0,
    lon1]."""
    lo_n, hi_n = gen["obs_per_track"]
    n_obs = rng.integers(lo_n, hi_n, size=n)
    offsets = np.concatenate([[0], np.cumsum(n_obs)]).astype(np.int64)
    rows = int(offsets[-1])
    tid = np.repeat(np.arange(n), n_obs)
    k = np.arange(rows) - np.repeat(offsets[:-1], n_obs)
    period = float(gen["period_s"])
    lat0 = rng.uniform(region[0], region[1], n)
    lon0 = rng.uniform(region[2], region[3], n)
    heading = rng.uniform(0.0, 360.0, n)
    speed = rng.uniform(*gen["speed_ms"], n)
    alt0 = rng.uniform(*gen["alt_m"], n)
    turn = seg_cumsum(rng.normal(0.0, gen["turn_deg_sd"], rows), offsets)
    hdg = np.deg2rad(heading[tid] + turn)
    dlat = speed[tid] * np.cos(hdg) * period / M_PER_DEG
    dlon = (speed[tid] * np.sin(hdg) * period
            / (M_PER_DEG * np.cos(np.deg2rad(lat0[tid]))))
    # Position k is the start plus the first k steps.
    lat = lat0[tid] + seg_cumsum(dlat, offsets) - dlat
    lon = lon0[tid] + seg_cumsum(dlon, offsets) - dlon
    vr = rng.normal(0.0, gen["vrate_sd_ms"], rows)
    alt = np.maximum(alt0[tid] + seg_cumsum(vr * period, offsets),
                     gen["alt_floor_m"])
    geo = alt + rng.normal(0.0, gen["gps_sd_m"], rows)
    cols = {
        "time": gen["t0_s"] + k * period,
        "lat": lat, "lon": lon,
        "velocity": speed[tid],
        "heading": np.rad2deg(hdg) % 360.0,
        "vertrate": vr, "baroaltitude": alt, "geoaltitude": geo,
    }
    return Tracks(ids=[f"{i:06d}.csv" for i in range(n)], offsets=offsets,
                  cols=cols)
