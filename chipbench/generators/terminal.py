"""Aerodrome terminal traffic: aircraft at terminal hotspots and on the
corridors between them (the program's ``tracks.datasets._SCREEN_HOTSPOTS``
and ``screen_density_trails``), each seen in a few gap-separated
segments with heavy-tailed durations (``bench.kernels.WORKLOADS
['heavy_tail']``)."""

from __future__ import annotations

import numpy as np

from chipbench.gen import M_PER_DEG, Tracks, seg_cumsum


def hour(gen: dict, hour: int, rng: np.random.Generator) -> Tracks:
    """One hour of traffic at the hotspots: ``aircraft_per_hour``
    aircraft, the first ``terminal_share`` of them at a hotspot (round
    robin), the rest on corridors between two hotspots."""
    hot = np.asarray(gen["hotspots"], np.float64)
    n = int(gen["aircraft_per_hour"])
    n_term = int(round(n * gen["terminal_share"]))
    seg_lo, seg_hi = gen["segments_per_aircraft"]
    n_seg = rng.integers(seg_lo, seg_hi + 1, size=n)
    S = int(n_seg.sum())
    ac = np.repeat(np.arange(n), n_seg)                      # segment -> ac
    first_seg = np.concatenate([[0], np.cumsum(n_seg)[:-1]])
    # Segment durations and observation spacing (bench heavy_tail mix);
    # times are whole seconds, as OpenSky state vectors stamp them.
    dur = np.clip(rng.lognormal(np.log(gen["seg_median_s"]),
                                gen["seg_sigma"], S), *gen["seg_clip_s"])
    dt_obs = rng.uniform(*gen["obs_dt_s"], S)
    n_obs = np.maximum(gen["min_obs"], (dur / dt_obs).astype(np.int64) + 1)
    rows = int(n_obs.sum())
    offs = np.concatenate([[0], np.cumsum(n_obs)]).astype(np.int64)
    sid = np.repeat(np.arange(S), n_obs)
    step_obs = np.maximum(1.0, np.rint(dt_obs[sid] * rng.uniform(
        0.5, 1.5, rows)))
    step_obs[offs[:-1]] = 0.0
    rel = seg_cumsum(step_obs, offs)
    dur = rel[offs[1:] - 1]
    gap = np.rint(rng.uniform(*gen["seg_gap_s"], S))
    # Segment start times: the aircraft's first at a whole second of the
    # hour, each later one after the previous one's end and a gap.
    t_first = hour * 3600.0 + rng.integers(0, 3600, n)
    step = np.concatenate([[0.0], (dur + gap)[:-1]])
    c = np.cumsum(step)
    t_seg = t_first[ac] + c - np.repeat(c[first_seg], n_seg)
    # Where each segment is flown.
    is_term = ac < n_term
    home = hot[ac % len(hot)]
    a = rng.integers(0, len(hot), n)
    b = (a + rng.integers(1, len(hot), n)) % len(hot)        # b != a
    f = rng.random(S)
    corr = hot[a[ac]] + f[:, None] * (hot[b[ac]] - hot[a[ac]])
    sd = np.where(is_term, gen["terminal_sd_deg"], gen["corridor_sd_deg"])
    p0 = np.where(is_term[:, None], home, corr) + rng.normal(
        0.0, 1.0, (S, 2)) * sd[:, None]
    speed = rng.uniform(*gen["speed_ms"], n)[ac]
    hdg = rng.uniform(0.0, 2.0 * np.pi, S)
    alt0 = rng.lognormal(np.log(gen["alt_median_m"]), gen["alt_sigma"], S)
    # Observations.
    t = t_seg[sid] + rel
    dist = speed[sid] * (t - t_seg[sid]) / M_PER_DEG
    lat = p0[sid, 0] + np.cos(hdg[sid]) * dist
    lon = p0[sid, 1] + np.sin(hdg[sid]) * dist / np.maximum(
        np.cos(np.deg2rad(p0[sid, 0])), 0.2)
    alt = np.maximum(alt0[sid] + seg_cumsum(
        rng.normal(0.0, gen["alt_walk_sd_m"], rows), offs),
        gen["alt_floor_m"])
    geo = alt + rng.normal(0.0, gen["gps_sd_m"], rows)
    vr = np.zeros(rows)
    # Regroup segments by aircraft: segments are already contiguous per
    # aircraft and in time order, so an aircraft's rows are contiguous.
    ac_obs = np.bincount(ac, weights=n_obs, minlength=n).astype(np.int64)
    offsets = np.concatenate([[0], np.cumsum(ac_obs)]).astype(np.int64)
    cols = {"time": t, "lat": lat, "lon": lon, "velocity": speed[sid],
            "heading": np.rad2deg(hdg[sid]) % 360.0, "vertrate": vr,
            "baroaltitude": alt, "geoaltitude": geo}
    return Tracks(ids=[f"h{hour:03d}_a{i:04d}.csv" for i in range(n)],
                  offsets=offsets, cols=cols)
