"""Plain references of the two phases, in float64 numpy.

Nothing here imports the program.  The terrain and the aerodromes are
the benchmark's own copies of the deployment's fixed inputs (the
program's ``SyntheticGlobeDEM()`` and ``synthetic_aerodromes(n=64)``),
rebuilt from their published recipe, and the observations come straight
from :mod:`chipbench.gen`, never from the store.

* :func:`segment_planes` -- what the process phase computes for every
  segment of every track: gap splitting (120 s, ten observations),
  resampling onto a 1 Hz grid, MSL and AGL altitude (bilinear terrain),
  vertical rate, ground speed and heading by central differences, and
  the airspace class of the nearest aerodrome.  ``precision="bfloat16"``
  rounds every stage to bfloat16: the control that a correct comparison
  must fail.
* :func:`screen_pairs` -- the all-pairs encounter screen over those
  segments: every pair of rows of different aircraft, at every second
  both cover, within the horizontal and vertical thresholds.
"""

from __future__ import annotations

import dataclasses

import numpy as np

M_PER_DEG = 111_111.0
GAP_S = 120.0            # a new segment after a gap longer than this
MIN_OBS = 10             # segments with fewer observations are dropped
MAX_POINTS = 1024        # knots and grid points kept per segment
DT_S = 1.0               # resampling grid
RADIUS_DEG = 8.0 * 1852.0 / M_PER_DEG     # terminal cylinder, 8 NM
PLANES = ("lat", "lon", "alt_msl_m", "alt_agl_m", "vrate_ms", "gspeed_ms",
          "heading_rad")
_CHUNK_POINTS = 1 << 21


# ---------------------------------------------------------------------------
# fixed inputs of the deployment
# ---------------------------------------------------------------------------

class Terrain:
    """The synthetic continental terrain (8 cells per degree over
    24-50 N, 125-66 W, seed 5), rebuilt from its recipe."""

    def __init__(self):
        self.lat_min, self.lat_max = 24.0, 50.0
        self.lon_min, self.lon_max = -125.0, -66.0
        self.cpd = 8
        lats = np.linspace(24.0, 50.0, 26 * 8 + 1)
        lons = np.linspace(-125.0, -66.0, 59 * 8 + 1)
        rng = np.random.default_rng(5)
        glat, glon = np.meshgrid(lats, lons, indexing="ij")
        z = np.zeros_like(glat)
        for _ in range(12):
            fx, fy = rng.uniform(0.02, 0.45, size=2)
            ph1, ph2 = rng.uniform(0, 2 * np.pi, size=2)
            amp = rng.uniform(80, 420)
            z += amp * np.sin(fx * glon + ph1) * np.sin(fy * glat + ph2)
        z += 2200.0 * np.exp(-((glon + 107.5) / 6.0) ** 2)
        z += 600.0 * np.exp(-((glon + 80.0) / 3.5) ** 2)
        z *= np.clip((glat - 23.0) / 4.0, 0.2, 1.0)
        self.elevation = np.maximum(z, 0.0)

    def bilinear(self, lat: np.ndarray, lon: np.ndarray) -> np.ndarray:
        H, W = self.elevation.shape
        fi = np.clip((np.clip(lat, self.lat_min, self.lat_max)
                      - self.lat_min) * self.cpd, 0.0, H - 1.001)
        fj = np.clip((np.clip(lon, self.lon_min, self.lon_max)
                      - self.lon_min) * self.cpd, 0.0, W - 1.001)
        i0, j0 = np.floor(fi).astype(np.int64), np.floor(fj).astype(np.int64)
        di, dj = fi - i0, fj - j0
        e = self.elevation
        return ((1 - di) * (1 - dj) * e[i0, j0] + (1 - di) * dj * e[i0, j0 + 1]
                + di * (1 - dj) * e[i0 + 1, j0] + di * dj * e[i0 + 1, j0 + 1])


def aerodromes(n: int = 64, seed: int = 15):
    """(lat, lon, class) arrays of the synthetic aerodrome registry."""
    metros = [
        (33.64, -84.43), (41.98, -87.90), (32.90, -97.04), (39.86, -104.67),
        (40.64, -73.78), (33.94, -118.41), (37.62, -122.38), (47.45, -122.31),
        (25.79, -80.29), (42.36, -71.01), (38.85, -77.04), (29.98, -95.34),
        (36.08, -115.15), (40.79, -111.98), (45.59, -122.60), (39.18, -76.67),
    ]
    rng = np.random.default_rng(seed)
    lat, lon, cls = [], [], []
    for _ in range(n):
        if rng.random() < 0.6:
            m = metros[int(rng.integers(0, len(metros)))]
            la = m[0] + rng.normal(0, 0.35)
            lo = m[1] + rng.normal(0, 0.45)
            c = "BCD"[int(rng.choice([0, 1, 2], p=[0.25, 0.35, 0.40]))]
        else:
            la = float(rng.uniform(26.0, 48.0))
            lo = float(rng.uniform(-123.0, -68.0))
            c = "BCD"[int(rng.choice([0, 1, 2], p=[0.02, 0.18, 0.80]))]
        rng.normal(900, 800)                       # elevation, unused
        lat.append(la)
        lon.append(lo)
        cls.append(c)
    return np.array(lat), np.array(lon), np.array(cls)


# ---------------------------------------------------------------------------
# process phase
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Segments:
    """Every segment of every track, and its planes, concatenated."""

    track: np.ndarray     # (S,) track index
    k: np.ndarray         # (S,) segment number within its track
    t0: np.ndarray        # (S,) absolute time of the first observation
    m: np.ndarray         # (S,) grid points
    offsets: np.ndarray   # (S + 1,) into the planes
    planes: dict          # name -> (P,) float64
    airspace: np.ndarray  # (S,) class letter
    margin: np.ndarray    # (S,) degrees from a change of airspace class

    def __len__(self) -> int:
        return len(self.m)


def split(tracks) -> tuple:
    """Segment (start row, knots, grid points, track, k) of every track."""
    t = tracks.cols["time"]
    n = len(t)
    brk = np.zeros(n, bool)
    brk[tracks.offsets[:-1][np.diff(tracks.offsets) > 0]] = True
    brk[1:] |= np.diff(t) > GAP_S
    starts = np.flatnonzero(brk)
    ends = np.append(starts[1:], n)
    keep = ends - starts >= MIN_OBS
    starts, ends = starts[keep], ends[keep]
    track = np.searchsorted(tracks.offsets, starts, side="right") - 1
    first = np.r_[True, track[1:] != track[:-1]]
    idx = np.arange(len(starts))
    k = idx - np.maximum.accumulate(np.where(first, idx, 0))
    knots = np.minimum(ends - starts, MAX_POINTS)
    dur = t[starts + knots - 1] - t[starts]
    m = np.minimum((dur / DT_S).astype(np.int64) + 1, MAX_POINTS)
    return starts, knots, m, track, k


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return x
    if precision == "bfloat16":
        import ml_dtypes
        return x.astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def segment_planes(tracks, terrain: Terrain, aero,
                   precision: str = "float64") -> Segments:
    """The process phase's planes for every segment, from observations."""
    starts, knots, m, track, k = split(tracks)
    S = len(starts)
    offsets = np.concatenate([[0], np.cumsum(m)]).astype(np.int64)
    planes = {p: np.empty(int(offsets[-1])) for p in PLANES}
    cols = tracks.cols
    a = 0
    while a < S:                                   # chunks of segments
        b = int(np.searchsorted(offsets, offsets[a] + _CHUNK_POINTS,
                                side="right")) - 1
        b = min(max(b, a + 1), S)
        _planes_chunk(cols, starts[a:b], knots[a:b], m[a:b],
                      offsets[a:b + 1] - offsets[a], terrain, precision,
                      {p: v[offsets[a]:offsets[b]] for p, v in planes.items()})
        a = b
    alat, alon, acls = aero
    lat0 = planes["lat"][offsets[:-1]]
    lon0 = planes["lon"][offsets[:-1]]
    d2 = ((alat[None, :] - lat0[:, None]) ** 2
          + ((alon[None, :] - lon0[:, None])
             * np.cos(np.deg2rad(lat0))[:, None]) ** 2)
    order = np.argsort(d2, axis=1)[:, :2]
    near = order[:, 0]
    dist = np.sqrt(np.take_along_axis(d2, order, axis=1))
    airspace = np.where(dist[:, 0] <= RADIUS_DEG, acls[near], "G")
    # How far the start may move before its class could change: to the
    # nearest cylinder's edge, or to where a second cylinder of another
    # class becomes the nearest.
    margin = np.abs(dist[:, 0] - RADIUS_DEG)
    rival = (dist[:, 1] <= RADIUS_DEG) & (acls[order[:, 1]] != acls[near])
    margin = np.where(rival, np.minimum(margin, 0.5 * (dist[:, 1]
                                                       - dist[:, 0])),
                      margin)
    return Segments(track=track, k=k, t0=cols["time"][starts], m=m,
                    offsets=offsets, planes=planes, airspace=airspace,
                    margin=margin)


def _planes_chunk(cols, starts, knots, m, offs, terrain, precision, out):
    S = len(starts)
    P = int(offs[-1])
    rnd = lambda x: _round(x, precision)           # noqa: E731
    # Knots: rows of the observation table, segment-relative times.
    kidx = np.repeat(starts, knots) + (
        np.arange(int(knots.sum())) - np.repeat(np.cumsum(knots) - knots,
                                                knots))
    kseg = np.repeat(np.arange(S), knots)
    kt = rnd(cols["time"][kidx] - cols["time"][starts][kseg])
    kv = [rnd(cols[c][kidx]) for c in ("lat", "lon", "geoaltitude")]
    # Queries: 0, 1, ..., m - 1 seconds.
    qseg = np.repeat(np.arange(S), m)
    j = np.arange(P) - offs[:-1][qseg]
    tq = j * DT_S
    # Interval of each query among its segment's knots (np.interp).
    span = float(kt.max() if len(kt) else 0.0) + 2.0
    kstart = np.concatenate([[0], np.cumsum(knots)[:-1]])
    idx = np.searchsorted(kseg * span + kt, qseg * span + tq,
                          side="right") - 1
    idx = np.clip(idx, kstart[qseg], kstart[qseg] + knots[qseg] - 2)
    t_a, t_b = kt[idx], kt[idx + 1]
    # Knots that a lower precision rounds together get a zero weight.
    gap = t_b - t_a
    w = np.where(gap > 0, (tq - t_a) / np.where(gap > 0, gap, 1.0), 0.0)
    lat, lon, alt = (rnd(v[idx] + w * (v[idx + 1] - v[idx])) for v in kv)
    terrain_m = rnd(terrain.bilinear(lat, lon))
    li = np.maximum(j - 1, 0) + offs[:-1][qseg]
    ri = np.minimum(j + 1, m[qseg] - 1) + offs[:-1][qseg]
    denom = np.maximum(ri - li, 1) * DT_S
    dn = (lat[ri] - lat[li]) / denom * M_PER_DEG
    de = (lon[ri] - lon[li]) / denom * M_PER_DEG * np.cos(np.deg2rad(lat))
    out["lat"][:] = lat
    out["lon"][:] = lon
    out["alt_msl_m"][:] = alt
    out["alt_agl_m"][:] = rnd(alt - terrain_m)
    out["vrate_ms"][:] = rnd((alt[ri] - alt[li]) / denom)
    out["gspeed_ms"][:] = rnd(np.hypot(dn, de))
    out["heading_rad"][:] = rnd(np.arctan2(de, dn))


#: Ground speed above which headings are compared (a heading of a slow
#: track is the angle of a short difference of rounded positions).
HEADING_MIN_MS = 50.0


def plane_errors(got: dict, want: Segments) -> dict:
    """Largest disagreement of each plane, over every point."""
    w = want.planes
    lat = w["lat"]
    dpos = np.hypot((got["lat"] - lat) * M_PER_DEG,
                    (got["lon"] - w["lon"]) * M_PER_DEG
                    * np.cos(np.deg2rad(lat)))
    fast = w["gspeed_ms"] > HEADING_MIN_MS
    dhead = np.abs((got["heading_rad"] - w["heading_rad"] + np.pi)
                   % (2 * np.pi) - np.pi)[fast]

    def worst(x):
        if not len(x):
            return 0.0
        x = np.where(np.isfinite(x), x, np.inf)
        return float(x.max())

    return {
        "pos_m": worst(dpos),
        "alt_m": worst(np.abs(got["alt_msl_m"] - w["alt_msl_m"])),
        "agl_m": worst(np.abs(got["alt_agl_m"] - w["alt_agl_m"])),
        "vrate_ms": worst(np.abs(got["vrate_ms"] - w["vrate_ms"])),
        "gspeed_ms": worst(np.abs(got["gspeed_ms"] - w["gspeed_ms"])),
        "heading_rad": worst(dhead),
    }


# ---------------------------------------------------------------------------
# screen phase
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Pair:
    """One pair's closest approach under narrowed and widened limits:
    ``h``/``v`` are the least horizontal and vertical separations over
    the seconds inside both limits (``inf`` when there are none)."""

    h_in: float
    v_in: float
    h_out: float
    v_out: float


def row_ids(tracks, segs: Segments) -> list:
    return [f"{tracks.ids[t]}#s{k:03d}" for t, k in zip(segs.track, segs.k)]


def overlap_work(segs: Segments) -> tuple:
    """(pairs of rows of different tracks whose spans share a second,
    the seconds they share, the grid points of rows in such a pair)."""
    t0 = segs.t0
    t1 = t0 + segs.m - 1
    pairs = samples = 0
    used = np.zeros(len(segs), bool)
    for i in range(len(segs)):
        ov = (np.minimum(t1[i], t1[i + 1:]) - np.maximum(t0[i], t0[i + 1:])
              + 1)
        ok = (ov > 0) & (segs.track[i + 1:] != segs.track[i])
        if ok.any():
            pairs += int(ok.sum())
            samples += int(ov[ok].sum())
            used[i] = True
            used[i + 1:][ok] = True
    return pairs, samples, int(segs.m[used].sum())


def screen_pairs(ids: list, segs: Segments, h_m: float, v_m: float,
                 dh_m: float, dv_m: float) -> dict:
    """All-pairs screen.  Returns ``{(a, b): Pair}`` for every pair of
    rows (a < b) of different tracks inside the widened limits
    (``h_m + dh_m``, ``v_m + dv_m``) at some shared second."""
    p = segs.planes
    off = segs.offsets
    t0 = segs.t0.astype(np.int64)
    t1 = t0 + segs.m - 1
    lat, lon, alt = p["lat"], p["lon"], p["alt_msl_m"]
    n = len(segs)
    blat0 = np.minimum.reduceat(lat, off[:-1]) if n else lat
    blat1 = np.maximum.reduceat(lat, off[:-1]) if n else lat
    blon0 = np.minimum.reduceat(lon, off[:-1]) if n else lon
    blon1 = np.maximum.reduceat(lon, off[:-1]) if n else lon
    h_hi, v_hi = h_m + dh_m, v_m + dv_m
    h_lo, v_lo = h_m - dh_m, v_m - dv_m
    pad_lat = h_hi / M_PER_DEG
    # A longitude pad valid below 60 degrees of latitude; no pruning on
    # longitude above.
    pad_lon = (pad_lat / 0.5 if n and np.abs(lat).max() < 59.0
               else np.inf)
    out = {}
    for i in range(n):
        cand = np.flatnonzero(
            (t0[i + 1:] <= t1[i]) & (t1[i + 1:] >= t0[i])
            & (segs.track[i + 1:] != segs.track[i])
            & (blat0[i + 1:] <= blat1[i] + pad_lat)
            & (blat1[i + 1:] >= blat0[i] - pad_lat)
            & (blon0[i + 1:] <= blon1[i] + pad_lon)
            & (blon1[i + 1:] >= blon0[i] - pad_lon)) + i + 1
        for j in cand.tolist():
            a, b = max(t0[i], t0[j]), min(t1[i], t1[j])
            si = slice(off[i] + a - t0[i], off[i] + b - t0[i] + 1)
            sj = slice(off[j] + a - t0[j], off[j] + b - t0[j] + 1)
            dn = (lat[si] - lat[sj]) * M_PER_DEG
            de = ((lon[si] - lon[sj]) * M_PER_DEG
                  * np.cos(np.deg2rad(0.5 * (lat[si] + lat[sj]))))
            dh = np.hypot(dn, de)
            dv = np.abs(alt[si] - alt[sj])
            wide = (dh <= h_hi) & (dv <= v_hi)
            if not wide.any():
                continue
            narrow = (dh <= h_lo) & (dv <= v_lo)
            key = tuple(sorted((ids[i], ids[j])))
            out[key] = Pair(
                h_in=float(dh[narrow].min()) if narrow.any() else np.inf,
                v_in=float(dv[narrow].min()) if narrow.any() else np.inf,
                h_out=float(dh[wide].min()), v_out=float(dv[wide].min()))
    return out


def screen_errors(cands: list, ref: dict) -> dict:
    """``candidates.json`` entries against the reference's pairs.

    * ``pairs_missed``: pairs inside the narrowed limits that are not
      candidates;
    * ``pairs_extra``: candidates not even inside the widened limits;
    * ``h_gap_m`` / ``v_gap_m``: how far a candidate's least separation
      lies outside the range that the narrowed and widened limits give
      the reference's (0 inside it).
    """
    got = {}
    for c in cands:
        got[(c["a"], c["b"])] = c
    missed = sum(1 for k, p in ref.items()
                 if np.isfinite(p.h_in) and k not in got)
    extra = sum(1 for k in got if k not in ref)
    h_gap = v_gap = 0.0
    for k, c in got.items():
        p = ref.get(k)
        if p is None:
            continue
        h_gap = max(h_gap, p.h_out - c["h_m"], c["h_m"] - p.h_in)
        v_gap = max(v_gap, p.v_out - c["v_m"], c["v_m"] - p.v_in)
    return {"pairs_missed": missed, "pairs_extra": extra,
            "h_gap_m": float(h_gap), "v_gap_m": float(v_gap)}
