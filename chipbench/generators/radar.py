"""§V terminal-radar tracks: one deidentified id per track, scans 4.8 s
apart, each id a departure from or an arrival to the radar's airport on
an arc of constant turn radius, some with one coast gap over the
program's 120-s segment gap."""

from __future__ import annotations

import numpy as np

from chipbench.gen import M_PER_DEG, Tracks


def ids(gen: dict, radar: str, n: int, rng: np.random.Generator) -> Tracks:
    """The first ``n`` ids of ``radar`` in id order, named
    ``<year>_<radar>_<month range>_<id>.csv``."""
    lat_site, lon_site = gen["sites"][radar]
    scan = float(gen["scan_s"])
    dur = np.clip(rng.lognormal(np.log(gen["dur_median_s"]),
                                gen["dur_sigma"], n), *gen["dur_clip_s"])
    n_obs = (dur // scan).astype(np.int64) + 1
    offsets = np.concatenate([[0], np.cumsum(n_obs)]).astype(np.int64)
    rows = int(offsets[-1])
    tid = np.repeat(np.arange(n), n_obs)
    k = np.arange(rows) - np.repeat(offsets[:-1], n_obs)
    # Coast: the scans from k0 on come ``gap`` seconds later; the
    # aircraft flies on meanwhile.  Both sides keep ten or more scans.
    coast = rng.random(n) < gen["coast_share"]
    gap = np.where(coast, rng.uniform(*gen["coast_gap_s"], n), 0.0)
    k0 = rng.integers(10, np.maximum(n_obs - 10, 11))
    tau = k * scan + np.where(k >= k0[tid], gap[tid], 0.0)
    total = (n_obs - 1) * scan + gap                  # seconds flown
    t0 = rng.uniform(0.0, gen["window_s"] - total)
    # The arc: speed v, turn radius r, either sense; a departure leaves
    # the airport at tau = 0, an arrival reaches it at tau = total.
    v = rng.uniform(*gen["speed_ms"], n)
    r = rng.uniform(*gen["turn_radius_km"], n) * 1000.0
    w = v / r * np.where(rng.random(n) < 0.5, -1.0, 1.0)
    h0 = rng.uniform(0.0, 2.0 * np.pi, n)
    arrival = rng.random(n) < 0.5
    s = np.where(arrival[tid], total[tid] - tau, tau)  # from the airport
    h = h0[tid] + w[tid] * s
    east = v[tid] / w[tid] * (np.cos(h0[tid]) - np.cos(h))
    north = v[tid] / w[tid] * (np.sin(h) - np.sin(h0[tid]))
    lat0 = lat_site + rng.normal(0.0, gen["site_sd_deg"], n)
    lon0 = lon_site + rng.normal(0.0, gen["site_sd_deg"], n)
    sd = gen["pos_sd_m"]
    lat = lat0[tid] + (north + rng.normal(0.0, sd, rows)) / M_PER_DEG
    lon = lon0[tid] + (east + rng.normal(0.0, sd, rows)) / (
        M_PER_DEG * np.cos(np.deg2rad(lat0[tid])))
    floor = gen["alt_floor_m"]
    top = rng.uniform(*gen["alt_top_m"], n)
    alt = floor + (top - floor)[tid] * s / total[tid]
    geo = np.maximum(alt + rng.normal(0.0, gen["alt_sd_m"], rows), floor)
    climb = np.where(arrival, -1.0, 1.0) * (top - floor) / total
    heading = np.rad2deg(h + np.where(arrival[tid], np.pi, 0.0)) % 360.0
    cols = {"time": t0[tid] + tau, "lat": lat, "lon": lon,
            "velocity": v[tid], "heading": heading,
            "vertrate": climb[tid], "baroaltitude": alt,
            "geoaltitude": geo}
    name = f"{gen['year']}_{radar}_{gen['month_range']}_"
    return Tracks(ids=[f"{name}{i:08d}.csv" for i in range(n)],
                  offsets=offsets, cols=cols)
