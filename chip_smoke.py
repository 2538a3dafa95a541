#!/usr/bin/env python3
"""Smoke run of the track workflow on one TPU chip.

Runs the batch path end to end in this one process, through
``TrackWorkflow(...).run()`` with ``exec_backend="threads"``,
``input="store"`` and ``screen=True``: organize -> archive ->
store-build -> fused segment pipeline -> encounter screen, on one
Monday-shaped hourly file generated from ``--seed``.  Then it checks
what came out:

* the output planes of a sample of tracks, covering every bucket width
  and both AGL variants, against the jnp oracle (``backend="ref"``,
  searchsorted and gather, no matmuls) run on the chip, and against a
  float64 numpy evaluation on the host, within the tolerances below;
* the ``candidates.json`` pair set against ``brute_force_screen`` over a
  dense subset of the screened rows;
* the Pallas screen kernel against the ``jit`` backend that the
  workflow runs, on the workflow's densest cells and on an
  aerodrome-density batch, whose pair set must also equal brute
  force's.

It prints the wall seconds of each phase, the compile count and compile
seconds (set-up), the points processed, the bucket histogram, and
whether each compiled Pallas program holds a ``tpu_custom_call``.  The
last line of standard output is one JSON object naming the device.
With no TPU, or when any phase or check fails, it exits non-zero and
prints no result.

    python chip_smoke.py [--scale S] [--seed N] [--root DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Tolerances of the chip's planes against the oracle and numpy.  A
# float32 longitude at CONUS latitudes resolves ~0.8 m, and a rate
# differences two such positions over 2 s; a bfloat16 matmul pass
# would miss by kilometres.
POS_TOL_M = 3.0          # lat/lon, as a horizontal distance
ALT_TOL_M = 0.05         # interpolated MSL altitude
AGL_TOL_M = 0.5          # MSL minus bilinear terrain
VRATE_TOL_MS = 0.05      # vertical rate
GSPEED_TOL_MS = 2.0      # ground speed
HEADING_TOL_RAD = 0.05   # heading, where ground speed > HEADING_MIN_MS
HEADING_MIN_MS = 50.0
# Tolerances of screen minima (pair sets must be equal).
SCREEN_H_TOL_M = 0.5
SCREEN_V_TOL_M = 0.05
SCREEN_T_TOL_S = 0.0

# The default cut of the data: the hourly file at 1/DEFAULT_SCALE of
# its size.  Each screen-phase cell task re-reads its member tracks one
# by one, and each read decodes a whole store shard on the host, so the
# screen phase grows with the track count and the full file would not
# finish inside a 1200 s run.
DEFAULT_SCALE = 10.0

WORKERS = 8              # workflow threads
SAMPLE_TRACKS = 128      # tracks whose planes are compared
BRUTE_MAX_ROWS = 600     # rows in the brute-force subset
DENSE_CELLS = 64         # workflow cells in the pallas-vs-jit batch
M_PER_DEG = 111_111.0


class SmokeFailure(Exception):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _require_tpu() -> dict:
    """The device as JAX reports it; exits non-zero without a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's device is "
              f"{devs[0].platform!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        sys.exit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileLog:
    """Counts XLA compiles and their seconds, from JAX's monitoring."""

    def __init__(self):
        import jax
        self.n = self.hits = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snap(self) -> tuple:
        return self.n, self.secs, self.hits


# ---------------------------------------------------------------------------
# host numpy reference of the fused pipeline (float64)
# ---------------------------------------------------------------------------

def numpy_planes(obs, s, dem) -> dict:
    """One segment's planes, from the raw observations, in float64."""
    import numpy as np
    from repro.tracks.segments import RESAMPLE_DT_S, segment_shape
    n, m = segment_shape(obs["time"], s)
    sl = slice(s.start, s.start + n)
    t = obs["time"][sl] - obs["time"][s.start]
    grid = np.arange(m) * RESAMPLE_DT_S
    lat = np.interp(grid, t, obs["lat"][sl])
    lon = np.interp(grid, t, obs["lon"][sl])
    alt = np.interp(grid, t, obs["alt"][sl])
    elev = dem.elevation_m
    H, W = elev.shape
    cpd = dem.cells_per_deg
    fi = np.clip((np.clip(lat, dem.lat_min, dem.lat_max) - dem.lat_min)
                 * cpd, 0.0, H - 1.001)
    fj = np.clip((np.clip(lon, dem.lon_min, dem.lon_max) - dem.lon_min)
                 * cpd, 0.0, W - 1.001)
    i0, j0 = np.floor(fi).astype(int), np.floor(fj).astype(int)
    di, dj = fi - i0, fj - j0
    terrain = ((1 - di) * (1 - dj) * elev[i0, j0]
               + (1 - di) * dj * elev[i0, j0 + 1]
               + di * (1 - dj) * elev[i0 + 1, j0]
               + di * dj * elev[i0 + 1, j0 + 1])
    idx = np.arange(m)
    li, ri = np.maximum(idx - 1, 0), np.minimum(idx + 1, m - 1)
    denom = np.maximum(ri - li, 1) * RESAMPLE_DT_S

    def central(x):
        return (x[ri] - x[li]) / denom

    dn = central(lat) * M_PER_DEG
    de = central(lon) * M_PER_DEG * np.cos(np.deg2rad(lat))
    return {"count": m, "lat": lat, "lon": lon, "alt_msl_m": alt,
            "alt_agl_m": alt - terrain, "vrate_ms": central(alt),
            "gspeed_ms": np.hypot(dn, de),
            "heading_rad": np.arctan2(de, dn)}


def plane_errors(got: dict, want: dict) -> dict:
    """Largest disagreement per plane, in metres, m/s or radians."""
    import numpy as np
    lat = want["lat"]
    dpos = np.hypot((got["lat"] - lat) * M_PER_DEG,
                    (got["lon"] - want["lon"]) * M_PER_DEG
                    * np.cos(np.deg2rad(lat)))
    fast = want["gspeed_ms"] > HEADING_MIN_MS
    dhead = np.abs((got["heading_rad"] - want["heading_rad"] + np.pi)
                   % (2 * np.pi) - np.pi)[fast]
    return {
        "pos_m": float(dpos.max()),
        "alt_m": float(np.abs(got["alt_msl_m"] - want["alt_msl_m"]).max()),
        "agl_m": float(np.abs(got["alt_agl_m"] - want["alt_agl_m"]).max()),
        "vrate_ms": float(np.abs(got["vrate_ms"] - want["vrate_ms"]).max()),
        "gspeed_ms": float(np.abs(got["gspeed_ms"]
                                  - want["gspeed_ms"]).max()),
        "heading_rad": float(dhead.max()) if dhead.size else 0.0,
    }


_LIMITS = {"pos_m": POS_TOL_M, "alt_m": ALT_TOL_M, "agl_m": AGL_TOL_M,
           "vrate_ms": VRATE_TOL_MS, "gspeed_ms": GSPEED_TOL_MS,
           "heading_rad": HEADING_TOL_RAD}
_PLANES = ("lat", "lon", "alt_msl_m", "alt_agl_m", "vrate_ms",
           "gspeed_ms", "heading_rad")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def run_workflow(args, log: CompileLog):
    from repro.kernels import ops
    from repro.tracks.workflow import TrackWorkflow
    shutil.rmtree(args.root, ignore_errors=True)
    wf = TrackWorkflow(args.root, n_workers=WORKERS,
                       exec_backend="threads", input="store", screen=True,
                       tasks_per_message=4, poll_interval=0.005,
                       seed=args.seed)
    t0 = time.perf_counter()
    n_files = wf.generate_raw(n_files=1, scale=args.scale)
    gen_s = time.perf_counter() - t0
    raw_bytes = sum(os.path.getsize(os.path.join(wf.raw_dir, f))
                    for f in os.listdir(wf.raw_dir))
    print(f"data      : {n_files} Mondays hourly file, {raw_bytes} bytes "
          f"of CSV (seed {args.seed}, scale {args.scale}), generated in "
          f"{gen_s:.2f}s")
    if args.scale != 1.0:
        print(f"reduced: file size cut by {args.scale:g}x, to "
              f"{raw_bytes} of about {int(raw_bytes * args.scale)} bytes"
              + (" (the host-bound screen phase does not fit a 1200 s "
                 "run at full size)" if args.scale == DEFAULT_SCALE
                 else ""))
    ops.reset_pipeline_stats()
    c0 = log.snap()
    t0 = time.perf_counter()
    reports = wf.run()
    wall = time.perf_counter() - t0
    c1 = log.snap()
    for r in reports:
        print(f"phase     : {r.phase:12s} {r.job_seconds:9.3f}s wall, "
              f"{r.tasks} tasks on {r.workers} threads")
    phase_sum = sum(r.job_seconds for r in reports)
    print(f"workflow  : {wall:.3f}s wall ({wall - phase_sum:.3f}s between "
          f"phases: store finalize, screen planning)")
    _check([r.phase for r in reports]
           == ["organize", "archive", "store-build", "process", "screen"],
           f"phases run: {[r.phase for r in reports]}")
    print(f"compile   : {c1[0] - c0[0]} XLA compiles, "
          f"{c1[1] - c0[1]:.3f}s (set-up), {c1[2] - c0[2]} persistent "
          f"cache hits")
    return wf


def check_coverage(wf) -> list:
    from repro.kernels import ops
    from repro.store.reader import TrackStore
    from repro.tracks.segments import BUCKET_SIZES
    store = TrackStore(wf.store_dir)
    man = store.manifest
    n_obs = sum(t.n_obs for t in man.tracks)
    n_grid = sum(sum(t.seg_grid) for t in man.tracks)
    n_segs = sum(t.n_segments for t in man.tracks)
    print(f"points    : {n_obs} observations in {len(man.tracks)} tracks, "
          f"{n_segs} segments, {n_grid} resampled 1 Hz points")
    print(f"buckets   : segments per width {man.bucket_histogram()}")
    stats = ops.get_pipeline_stats()
    shapes = [s for s in ops.get_pipeline_shapes() if s["use_pallas"]]
    widths = sorted({s["t_out"][1] for s in shapes})
    variants = sorted({s["agl_oracle"] for s in shapes})
    print(f"pipeline  : {len(shapes)} fused programs (compile misses "
          f"{stats['compile_misses']}, hits {stats['compile_hits']}); "
          f"widths {widths}; agl_oracle variants {variants}")
    _check(set(BUCKET_SIZES) <= set(widths),
           f"bucket widths run {widths}, want all of {BUCKET_SIZES}")
    _check(variants == [False, True],
           f"AGL variants run {variants}, want both")
    return shapes


def sample_tracks(wf, shapes, rng) -> list:
    """Track ids covering every (width, AGL variant) pair the workflow
    ran, plus a random sample up to SAMPLE_TRACKS."""
    from repro.store.reader import TrackStore
    from repro.tracks.segments import (
        SegmentProcessor, bucket_width, split_segments)
    store = TrackStore(wf.store_dir)
    proc = SegmentProcessor()
    ids = [t.track_id for t in store.manifest.tracks]
    want = {(s["t_out"][1], s["agl_oracle"]) for s in shapes}
    chosen, seen = [], set()
    for i in rng.permutation(len(ids)):
        if len(chosen) >= SAMPLE_TRACKS and want <= seen:
            break
        obs = store.read_track(ids[i])
        combos = set()
        for s in split_segments(obs["time"]):
            rec = proc._records([(obs, [s])])[0]
            combos.add((bucket_width(max(rec.n, rec.m)), rec.may_span))
        if len(chosen) < SAMPLE_TRACKS or combos - seen:
            chosen.append(ids[i])
            seen |= combos
    _check(want <= seen, f"sample covers {sorted(seen)}")
    return sorted(chosen)


def check_planes(wf, track_ids) -> None:
    import numpy as np
    from repro.core.messages import Task
    from repro.geometry.aerodromes import synthetic_aerodromes
    from repro.geometry.dem import SyntheticGlobeDEM
    from repro.store.reader import TrackStore, make_store_uri
    from repro.tracks.segments import SegmentProcessor, split_segments
    dem = SyntheticGlobeDEM()
    aero = synthetic_aerodromes(n=64)
    tasks = [Task(task_id=t, payload=make_store_uri(wf.store_dir, track=t))
             for t in track_ids]
    chip = SegmentProcessor(dem=dem, aerodromes=aero).process_batch(tasks)
    oracle = SegmentProcessor(dem=dem, aerodromes=aero,
                              backend="ref").process_batch(tasks)
    store = TrackStore(wf.store_dir)
    worst = {"oracle": {}, "numpy": {}}
    n_seg = 0
    for tid in track_ids:
        obs = store.read_track(tid)
        segs = split_segments(obs["time"])
        got, ora = chip[tid], oracle[tid]
        _check(got.airspace == ora.airspace, f"{tid}: airspace differs")
        for k, s in enumerate(segs):
            ref = numpy_planes(obs, s, dem)
            m = ref["count"]
            _check(int(got.count[k]) == m and int(ora.count[k]) == m,
                   f"{tid} segment {k}: counts {got.count[k]}, "
                   f"{ora.count[k]}, want {m}")
            g = {p: getattr(got, p)[k, :m].astype(np.float64)
                 for p in _PLANES}
            o = {p: getattr(ora, p)[k, :m].astype(np.float64)
                 for p in _PLANES}
            for p in _PLANES:
                _check(np.isfinite(g[p]).all(), f"{tid}: {p} not finite")
                _check(not getattr(got, p)[k, m:].any(),
                       f"{tid}: {p} padding not zero")
            for name, want in (("oracle", o), ("numpy", ref)):
                for key, err in plane_errors(g, want).items():
                    worst[name][key] = max(worst[name].get(key, 0.0), err)
            n_seg += 1
    for name in ("oracle", "numpy"):
        errs = ", ".join(f"{k} {v:.4g} (limit {_LIMITS[k]})"
                         for k, v in worst[name].items())
        print(f"planes    : chip vs {name} over {len(track_ids)} tracks, "
              f"{n_seg} segments: max {errs}")
        for key, err in worst[name].items():
            _check(err <= _LIMITS[key],
                   f"chip vs {name}: {key} {err} > {_LIMITS[key]}")


def _cands_agree(a: list, b: list, what: str) -> None:
    pa = [(c["a"], c["b"]) for c in a]
    pb = [(c["a"], c["b"]) for c in b]
    _check(pa == pb, f"{what}: pair sets differ ({len(pa)} vs {len(pb)}, "
           f"{len(set(pa) ^ set(pb))} not shared)")
    for x, y in zip(a, b):
        _check(abs(x["h_m"] - y["h_m"]) <= SCREEN_H_TOL_M
               and abs(x["v_m"] - y["v_m"]) <= SCREEN_V_TOL_M
               and abs(x["t_s"] - y["t_s"]) <= SCREEN_T_TOL_S,
               f"{what}: {x} vs {y}")


def screen_rows(wf) -> list:
    from repro.geometry.aerodromes import synthetic_aerodromes
    from repro.geometry.dem import SyntheticGlobeDEM
    from repro.tracks.segments import (
        SegmentProcessor, segment_tasks_from_store)
    from repro.tracks.workflow import _screen_rows_for_uri
    proc = SegmentProcessor(dem=SyntheticGlobeDEM(),
                            aerodromes=synthetic_aerodromes(n=64))
    rows = []
    for t in segment_tasks_from_store(wf.store_dir, granularity="shard"):
        rows.extend(_screen_rows_for_uri(proc, t.payload))
    return rows


def check_candidates(wf, rows, rng) -> list:
    """candidates.json against brute force over a dense subset: every
    row of a (sampled) candidate pair plus the rows of the most crowded
    0.5-degree squares."""
    from repro.kernels.encounter_screen import brute_force_screen
    with open(wf.candidates_path) as f:
        cands = json.load(f)["candidates"]
    by_id = {r.row_id: r for r in rows}
    picked = list(cands)
    if len(picked) > BRUTE_MAX_ROWS // 4:
        picked = [cands[i] for i in sorted(rng.choice(
            len(cands), BRUTE_MAX_ROWS // 4, replace=False))]
    subset = {c["a"] for c in picked} | {c["b"] for c in picked}
    sq = {}
    for r in rows:
        key = (int(r.lat[0] // 0.5), int(r.lon[0] // 0.5))
        sq.setdefault(key, []).append(r.row_id)
    for key in sorted(sq, key=lambda k: (-len(sq[k]), k)):
        if len(subset) >= BRUTE_MAX_ROWS:
            break
        subset.update(sq[key][:BRUTE_MAX_ROWS - len(subset)])
    sub_rows = [by_id[i] for i in sorted(subset)]
    t0 = time.perf_counter()
    brute = brute_force_screen(sub_rows, config=wf.screen_config)
    want = [c for c in cands if c["a"] in subset and c["b"] in subset]
    print(f"screen    : {len(cands)} candidates from {len(rows)} rows; "
          f"brute force over a dense subset of {len(sub_rows)} rows "
          f"finds {len(brute)} pairs, the workflow {len(want)} "
          f"({time.perf_counter() - t0:.2f}s)")
    _cands_agree(want, brute, "candidates vs brute force")
    return sub_rows


def check_screen_backends(wf, sub_rows, seed: int) -> None:
    """Pallas screen kernel vs the jit backend: the densest workflow
    cells of the subset, and an aerodrome-density batch."""
    import numpy as np
    from repro.kernels.encounter_screen import (
        ScreenConfig, ScreenRow, bin_screen_rows, brute_force_screen,
        screen_cells)
    from repro.geometry.gridhash import GridSpec
    from repro.tracks.datasets import (
        SCREEN_TRAIL_DT_S, screen_density_trails)

    def both(cells, config, what):
        out = {}
        for backend in ("pallas", "jit"):
            cfg = ScreenConfig(h_thresh_m=config.h_thresh_m,
                               v_thresh_m=config.v_thresh_m,
                               dt_s=config.dt_s, backend=backend)
            out[backend] = screen_cells(cells, config=cfg)
        occ = max(len(v) for v in cells.values())
        print(f"screen    : pallas vs jit on {what}: {len(cells)} cells, "
              f"max occupancy {occ}, {out['jit'][1]['pairs_screened']} "
              f"pairs, {len(out['jit'][0])} candidates")
        _cands_agree(out["pallas"][0], out["jit"][0], f"pallas vs jit, "
                     f"{what}")
        return out["jit"][0]

    by_id = {r.row_id: r for r in sub_rows}
    bins = bin_screen_rows(sub_rows, grid=wf.screen_grid,
                           config=wf.screen_config)
    top = sorted((k for k in bins if len(bins[k]) >= 2),
                 key=lambda k: (-len(bins[k]), k))[:DENSE_CELLS]
    _check(bool(top), "no multi-row cell in the screen subset")
    both({k: [by_id[i] for i in bins[k]] for k in top}, wf.screen_config,
         "the workflow's densest cells")

    trails = screen_density_trails("dense", 3000, seed + 11)
    rows = [ScreenRow(row_id=f"{a}#s000", group=a, t0=float(ts[0]),
                      lat=la.astype(np.float32), lon=lo.astype(np.float32),
                      alt=al.astype(np.float32), dt_s=SCREEN_TRAIL_DT_S)
            for a, ts, la, lo, al in trails]
    config = ScreenConfig(dt_s=SCREEN_TRAIL_DT_S)
    grid = GridSpec()
    bins = bin_screen_rows(rows, grid=grid, config=config)
    by_id = {r.row_id: r for r in rows}
    cands = both({k: [by_id[i] for i in v] for k, v in bins.items()},
                 config, "an aerodrome-density batch")
    brute = brute_force_screen(rows, config=config)
    print(f"screen    : brute force over the aerodrome-density rows finds "
          f"{len(brute)} pairs, the grid screen {len(cands)}")
    _cands_agree(cands, brute, "aerodrome-density grid vs brute force")


def check_custom_calls(shapes: list) -> None:
    """Compile each fused program again (a cache hit) and look for the
    Pallas kernels in it; then the screen kernel at a dense shape."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.kernels import encounter_screen, segment_pipeline

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    n_ok = 0
    for s in shapes:
        B, N = s["t_in"]
        K = s["t_out"][1]
        N = -(-N // 128) * 128
        fn = segment_pipeline._jitted(s["grid"], s["dt"], False, True,
                                      s["agl_oracle"], True)
        text = fn.lower(sds(s["dem"]), sds((B, N)), sds((B, 3, N)),
                        sds((B,), jnp.int32), sds((B, K)),
                        sds((B,), jnp.int32)).compile().as_text()
        n_ok += "tpu_custom_call" in text
    print(f"kernels   : {n_ok} of {len(shapes)} compiled fused programs "
          f"hold a tpu_custom_call")
    _check(n_ok == len(shapes), "a fused program runs no Pallas kernel")
    fn = jax.jit(functools.partial(encounter_screen._screen_batch_pallas,
                                   h_m=926.0, v_m=152.4, interpret=False))
    x = sds((1, 240, 1024))
    text = fn.lower(x, x, x, x).compile().as_text()
    print(f"kernels   : screen kernel at (K=240, T=1024) holds a "
          f"tpu_custom_call: {'tpu_custom_call' in text}")
    _check("tpu_custom_call" in text, "screen program has no kernel")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=DEFAULT_SCALE,
                    help="divides the hourly file's size (1 = full size)")
    ap.add_argument("--root", default=os.path.join(
        HERE, "experiments", "chip_smoke"))
    args = ap.parse_args()

    info = _require_tpu()
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro import device
    except ImportError as e:
        print(f"chip_smoke: the repro package is missing next to this "
              f"script: {e}", file=sys.stderr)
        return 2
    import numpy as np
    print(f"device    : {info}")
    print(f"cache     : {device.enable_compile_cache()}")
    log = CompileLog()
    rng = np.random.default_rng(args.seed)
    t_all = time.perf_counter()
    try:
        wf = run_workflow(args, log)
        shapes = check_coverage(wf)
        check_planes(wf, sample_tracks(wf, shapes, rng))
        rows = screen_rows(wf)
        sub_rows = check_candidates(wf, rows, rng)
        check_screen_backends(wf, sub_rows, args.seed)
        check_custom_calls(shapes)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(args.root, ignore_errors=True)
    n, secs, hits = log.snap()
    print(f"total     : {time.perf_counter() - t_all:.3f}s; {n} XLA "
          f"compiles in all, {secs:.3f}s, {hits} persistent cache hits")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
