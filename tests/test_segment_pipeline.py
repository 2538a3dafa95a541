"""Fused on-device segment pipeline: pallas-vs-ref, fused-vs-standalone
kernels, bucketing/reassembly invariance, padding accounting, the
synthetic segment mixes, and the satellite vectorizations.

Tolerancing notes: the fused-vs-standalone comparison is gated at 1e-5
— both run the same kernels on the same values (padding columns
contribute exact zeros; stage boundaries are pinned with optimization
barriers), so in practice they agree bitwise.  The pallas-vs-ref
comparison tolerates ulp-level association differences (the AGL matmul
formulation vs the 4-term oracle), amplified by the terrain gradient;
tracks drift east so dynamic-rate headings stay clear of the arctan2
branch cut at +-pi.
"""

import collections
import zipfile

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.geometry.aerodromes import Aerodrome, synthetic_aerodromes
from repro.kernels import ops, ref
from repro.kernels.segment_pipeline import FIELDS
from repro.tracks.datasets import WORKLOADS, SegmentMixSpec, synth_items
from repro.tracks.segments import (
    _PLANE_ATTRS, BUCKET_SIZES, MAX_SEG_POINTS, RESAMPLE_DT_S,
    ProcessedSegments, SegmentProcessor, _empty, _pipeline_stats,
    _round_rows, bucket_width, segment_shape, split_segments)

# Equatorial test grid: f32 lat/lon ulp is ~60x smaller near 0 than at
# CONUS latitudes, so central-difference rates don't amplify the
# pallas-vs-ref interp ulp into m/s-scale noise.
GRID = (0.0, 26.0, 0.0, 59.0, 8.0)
ATTRS = ("times", "lat", "lon", "alt_msl_m", "alt_agl_m", "vrate_ms",
         "gspeed_ms", "heading_rad", "turn_rad_s")


def _dem(seed=7, H=209, W=473):
    rng = np.random.default_rng(seed)
    return rng.uniform(0, 2500, (H, W)).astype(np.float32)


def _ragged_inputs(B, K, seed=0):
    """One bucket batch: B tracks of <=K knots drifting east (headings
    stay off the arctan2 branch cut)."""
    rng = np.random.default_rng(seed)
    t_in = np.zeros((B, K), np.float32)
    v_in = np.zeros((B, 3, K), np.float32)
    count_in = np.zeros((B,), np.int32)
    t_out = np.zeros((B, K), np.float32)
    count_out = np.zeros((B,), np.int32)
    for b in range(B):
        n = int(rng.integers(10, K + 1))
        m = int(rng.integers(2, K + 1))
        t = np.cumsum(rng.uniform(1.0, 6.0, n))
        t -= t[0]
        t_in[b, :n] = t
        t_in[b, n:] = t[-1] + np.arange(1, K - n + 1)
        v_in[b, 0, :n] = rng.uniform(1, 3) \
            + np.cumsum(rng.normal(0, 2e-4, n))
        v_in[b, 1, :n] = rng.uniform(2, 20) \
            + np.cumsum(rng.uniform(5e-4, 2e-3, n))        # eastward
        v_in[b, 2, :n] = 1500 + np.cumsum(rng.normal(0, 2, n))
        v_in[b, :, n:] = v_in[b, :, n - 1:n]
        count_in[b] = n
        t_out[b, :m] = np.arange(m)
        t_out[b, m:] = t_out[b, m - 1]
        count_out[b] = m
    return t_in, v_in, count_in, t_out, count_out


@pytest.mark.parametrize("K", BUCKET_SIZES)
def test_process_segments_pallas_matches_ref_across_buckets(K):
    dem = _dem()
    args = _ragged_inputs(3, K, seed=K)
    got = {k: np.asarray(v) for k, v in ops.process_segments(
        dem, *args, grid=GRID, backend="pallas").items()}
    want = {k: np.asarray(v) for k, v in ops.process_segments(
        dem, *args, grid=GRID, backend="ref").items()}
    assert set(got) == set(FIELDS)
    # Rate fields amplify interp ulp by ~m_per_deg/(2 dt), and a query
    # landing on a knot boundary may bracket the adjacent interval —
    # both are sub-m/s effects; structural kernel bugs are orders of
    # magnitude larger.
    atol = {"vrate": 0.5, "gspeed": 0.5, "heading": 0.1, "turn": 0.5}
    for f in FIELDS:
        np.testing.assert_allclose(got[f], want[f], rtol=1e-3,
                                   atol=atol.get(f, 1e-2), err_msg=f)


def test_process_segments_masks_padding():
    dem = _dem()
    args = _ragged_inputs(4, 128, seed=1)
    count_out = args[4]
    out = ops.process_segments(dem, *args, grid=GRID)
    idx = np.arange(128)[None, :]
    for f in FIELDS:
        plane = np.asarray(out[f])
        assert (plane[idx >= count_out[:, None]] == 0).all(), f


def test_process_segments_counts_compile_cache():
    dem = _dem()
    ops.reset_pipeline_stats()
    args = _ragged_inputs(2, 128, seed=3)
    ops.process_segments(dem, *args, grid=GRID)
    ops.process_segments(dem, *args, grid=GRID)
    stats = ops.get_pipeline_stats()
    assert stats["compile_misses"] == 1
    assert stats["compile_hits"] == 1


# ---------------------------------------------------------------------------
# The fused path against the standalone kernels and the oracles, on
# golden (real workflow) archives and on the synthetic segment mixes.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def golden_archives(tmp_path_factory):
    from repro.tracks.segments import segment_tasks_from_archive_tree
    from repro.tracks.workflow import TrackWorkflow
    root = str(tmp_path_factory.mktemp("golden"))
    wf = TrackWorkflow(root, n_workers=2, poll_interval=0.003)
    wf.generate_raw(n_files=4, scale=2e4)
    wf.run()
    tasks = segment_tasks_from_archive_tree(wf.archive_dir)
    assert tasks
    return tasks


def _processor(**kw):
    return SegmentProcessor(aerodromes=synthetic_aerodromes(n=64), **kw)


#: The mixes' size in these tests: small, so tier-1 stays quick.
MIX = dict(n_archives=3, segments_per_archive=4, seed=0)

_oracle_agl = jax.jit(ref.agl_lookup_ref)


def _standalone_planes(proc, items) -> list[dict]:
    """The reference for the fused path: every segment of ``items`` in
    one fixed (B, MAX_SEG_POINTS) tile through the three standalone ops,
    with the DEM index math in host numpy between them.  AGL takes the
    oracle gather for the rows the processor routes there
    (``_may_span``).  Returns per archive a dict of (rows, 1024) planes
    keyed like :data:`FIELDS`."""
    segs = [(obs, s) for obs, ss in items for s in ss]
    B, M = len(segs), MAX_SEG_POINTS
    N = max(segment_shape(obs["time"], s)[0] for obs, s in segs)
    t_in = np.zeros((B, N), np.float32)
    v_in = np.zeros((B, 3, N), np.float32)
    count_in = np.zeros((B,), np.int32)
    t_out = np.zeros((B, M), np.float32)
    count_out = np.zeros((B,), np.int32)
    span = np.zeros((B,), bool)
    for b, (obs, s) in enumerate(segs):
        n, m = segment_shape(obs["time"], s)
        sl = slice(s.start, s.start + n)
        t = obs["time"][sl]
        t_in[b, :n] = t - t[0]
        t_in[b, n:] = (t[-1] - t[0]) + np.arange(1, N - n + 1)
        for c, key in enumerate(("lat", "lon", "alt")):
            v_in[b, c, :n] = obs[key][sl]
        v_in[b, :, n:] = v_in[b, :, n - 1:n]
        count_in[b] = n
        t_out[b, :m] = np.arange(m) * RESAMPLE_DT_S
        t_out[b, m:] = t_out[b, m - 1]
        count_out[b] = m
        span[b] = proc._may_span(obs["lat"][sl], obs["lon"][sl])

    interp = np.asarray(ops.track_interp(t_in, v_in, count_in, t_out))
    lat, lon, alt = interp[:, :, 0], interp[:, :, 1], interp[:, :, 2]
    dem = proc.dem
    H, W = proc._dem_f32.shape
    fi = (np.clip(lat, dem.lat_min, dem.lat_max) - dem.lat_min) \
        * dem.cells_per_deg
    fj = (np.clip(lon, dem.lon_min, dem.lon_max) - dem.lon_min) \
        * dem.cells_per_deg
    fi = np.clip(fi.astype(np.float32), 0.0, np.float32(H - 1.001))
    fj = np.clip(fj.astype(np.float32), 0.0, np.float32(W - 1.001))
    agl = np.array(ops.agl_lookup(proc._dem_f32, fi, fj, alt))
    if span.any():
        agl[span] = np.asarray(_oracle_agl(proc._dem_f32, fi[span],
                                           fj[span], alt[span]))
    rates = np.asarray(ops.dynamic_rates(
        np.stack([lat, lon, alt], axis=1), count_out, RESAMPLE_DT_S))
    mask = np.arange(M)[None, :] < count_out[:, None]
    planes = {"times": t_out, "lat": lat, "lon": lon, "alt_msl": alt,
              "alt_agl": agl, "vrate": rates[:, 0], "gspeed": rates[:, 1],
              "heading": rates[:, 2], "turn": rates[:, 3]}
    out, row = [], 0
    for _, ss in items:
        rows = slice(row, row + len(ss))
        out.append({k: (v * mask)[rows] for k, v in planes.items()})
        row += len(ss)
    return out


def _case_items(case, golden_archives) -> list:
    if case == "golden":
        proc = SegmentProcessor()
        items = []
        for task in golden_archives:
            obs = proc.read_observations(task.payload)
            if obs:
                items.append((obs, split_segments(obs["time"])))
        return items
    if case == "tile_border":
        return _reassembly_items("mixed_buckets")
    return synth_items(SegmentMixSpec(workload=case, **MIX))


@pytest.mark.parametrize("case", ["golden", "tile_border",
                                  *sorted(WORKLOADS)])
def test_fused_matches_ref(case, golden_archives):
    """The fused pipeline == the standalone kernels composed with host
    hops between them, within 1e-5, on the golden archives, on tracks
    that cross a DEM tile border, and on each synthetic segment mix;
    the planes are narrower than a fixed 1024 tile, whose tail is pure
    padding.  Against the pure-jnp oracles (``backend='ref'``) it holds
    the positions and the vertical rate to the op-level tolerances of
    test_process_segments_pallas_matches_ref_across_buckets; the
    horizontal rates are left out, as the arctan2 branch cut amplifies
    an ulp of interp there.  Its padding beats a fixed (B, 1024) tile
    unless every segment already fills the widest bucket."""
    items = _case_items(case, golden_archives)
    fused = _processor()
    got = fused._process_many(items)
    want = _standalone_planes(fused, items)
    oracle = _processor(backend="ref")._process_many(items)
    compared = 0
    for (obs, segs), f, w, r in zip(items, got, want, oracle):
        assert f.icao24 == r.icao24 == [str(obs["icao24"][s.start])
                                        for s in segs]
        assert f.airspace == r.airspace
        np.testing.assert_array_equal(f.count, r.count)
        np.testing.assert_array_equal(
            f.count, [segment_shape(obs["time"], s)[1] for s in segs])
        width = f.times.shape[1]
        for plane, attr in _PLANE_ATTRS:
            a = getattr(f, attr)
            if not a.size:
                continue
            np.testing.assert_allclose(a, w[plane][:, :width], atol=1e-5,
                                       rtol=1e-5, err_msg=attr)
            assert not w[plane][:, width:].any()
            assert getattr(r, attr).shape == a.shape
            if plane not in ("gspeed", "heading", "turn"):
                np.testing.assert_allclose(
                    a, getattr(r, attr), rtol=1e-3,
                    atol=0.5 if plane == "vrate" else 1e-2, err_msg=attr)
            compared += 1
    assert compared > 0
    stats = fused.last_stats
    fixed = (stats["n_segments"] * MAX_SEG_POINTS - stats["valid_points"]) \
        / stats["valid_points"]
    if case == "long_cruise":
        assert set(stats["bucket_rows"]) == {MAX_SEG_POINTS}
    else:
        assert stats["padded_fraction"] < fixed


def test_fused_launches_no_standalone_kernel(golden_archives, monkeypatch):
    """The fused path makes one device call per bucket and never calls
    the standalone ops, each of which is a round trip through the host."""
    def launched(*a, **kw):
        raise AssertionError("the fused path called a standalone op")

    for name in ("track_interp", "agl_lookup", "dynamic_rates"):
        monkeypatch.setattr(ops, name, launched)
    out = _processor().process_batch(golden_archives)
    assert any(len(ps) for ps in out.values())


def test_pipeline_counters_match_last_stats(golden_archives):
    """One ``process_batch`` counts one compile hit or miss per device
    call it makes (the counter ``pipeline_calls.process`` reads); the
    same batch again compiles nothing new."""
    proc = _processor()

    def calls():
        st = ops.get_pipeline_stats()
        return st["compile_hits"], st["compile_misses"]

    h0, m0 = calls()
    proc.process_batch(golden_archives)
    h1, m1 = calls()
    n = proc.last_stats["pipeline_calls"]
    assert n > 0 and (h1 + m1) - (h0 + m0) == n
    proc.process_batch(golden_archives)
    h2, m2 = calls()
    assert m2 == m1 and h2 - h1 == n > 0


def test_read_observations_golden_zip_roundtrip(golden_archives):
    """The vectorized zip/CSV parse yields sorted, finite columns."""
    obs = _processor().read_observations(golden_archives[0].payload)
    if not obs:
        pytest.skip("first archive empty")
    assert (np.diff(obs["time"]) >= 0).all()
    for key in ("time", "lat", "lon", "alt"):
        assert np.isfinite(obs[key]).all()
    assert len(obs["icao24"]) == len(obs["time"])


# ---------------------------------------------------------------------------
# Bucketing / reassembly.
# ---------------------------------------------------------------------------

def test_bucket_width_boundaries():
    assert bucket_width(1) == 128
    assert bucket_width(128) == 128
    assert bucket_width(129) == 256
    assert bucket_width(256) == 256
    assert bucket_width(1024) == 1024
    assert bucket_width(5000) == 1024      # capped at MAX_SEG_POINTS
    assert bucket_width(MAX_SEG_POINTS) == MAX_SEG_POINTS


def test_round_rows():
    assert [_round_rows(b) for b in (1, 2, 3, 5, 8, 9, 17)] == \
        [1, 2, 4, 8, 8, 16, 24]


def _synth_archive(rng, n_segs):
    """One archive of eastward-drifting segments (10-400 obs each)."""
    ts, lats, lons, alts = [], [], [], []
    t = 0.0
    for _ in range(n_segs):
        n = int(rng.integers(10, 400))
        seg_t = t + np.cumsum(rng.uniform(1.0, 7.0, n))
        ts.append(seg_t)
        lats.append(rng.uniform(30, 45) + np.cumsum(rng.normal(0, 2e-4, n)))
        lons.append(rng.uniform(-115, -80)
                    + np.cumsum(rng.uniform(5e-4, 2e-3, n)))
        alts.append(1000 + np.cumsum(rng.normal(0, 2, n)))
        t = seg_t[-1] + 400.0
    obs = {"time": np.concatenate(ts), "lat": np.concatenate(lats),
           "lon": np.concatenate(lons), "alt": np.concatenate(alts),
           "icao24": np.array(["deadbe"] * sum(len(x) for x in ts))}
    return obs, split_segments(obs["time"])


def test_fused_handles_zero_segment_archives():
    """An items entry with no segments yields an empty ProcessedSegments
    (the fused path must not choke on empty rows)."""
    rng = np.random.default_rng(3)
    full = _synth_archive(rng, 2)
    empty = ({"time": np.array([0.0, 1.0]), "lat": np.zeros(2),
              "lon": np.zeros(2), "alt": np.zeros(2),
              "icao24": np.array(["x", "x"])}, [])
    out = SegmentProcessor()._process_many([full, empty])
    assert len(out) == 2
    assert len(out[0]) == 2
    assert len(out[1]) == 0


# ---------------------------------------------------------------------------
# The synthetic segment mixes and the bucket table's padding bound.
# ---------------------------------------------------------------------------

TINY = SegmentMixSpec(workload="heavy_tail", n_archives=2,
                      segments_per_archive=3, seed=5)


def test_synth_items_deterministic_and_segmented():
    items_a = synth_items(TINY)
    items_b = synth_items(TINY)
    assert len(items_a) == TINY.n_archives
    for (oa, sa), (ob, sb) in zip(items_a, items_b):
        assert sa == sb and len(sa) == TINY.segments_per_archive
        for k in oa:
            assert (oa[k] == ob[k]).all()


def test_bad_spec_rejected():
    with pytest.raises(ValueError):
        SegmentMixSpec(workload="nope")


def test_metrics_deterministic_for_fixed_seed():
    """Two runs of one seed's mix: the same ``last_stats`` and the same
    planes, bit for bit."""
    runs = []
    for _ in range(2):
        proc = SegmentProcessor()
        runs.append((proc._process_many(synth_items(TINY)),
                     proc.last_stats))
    (out_a, stats_a), (out_b, stats_b) = runs
    assert stats_a == stats_b
    for a, b in zip(out_a, out_b):
        for attr in ATTRS + ("count",):
            assert getattr(a, attr).tobytes() == getattr(b, attr).tobytes()


@pytest.mark.parametrize("mix", sorted(WORKLOADS))
def test_bucket_padding_bound(mix):
    """Each segment lands in the smallest bucket that holds it, so a
    bucket wastes under 2x on any segment longer than the narrowest
    bucket; the allocation is the buckets' rounded rows times their
    widths.  On the heavy-tailed mix that cuts the padding at least 5x
    against one fixed 1024-wide tile."""
    items = synth_items(SegmentMixSpec(workload=mix, **MIX))
    proc = SegmentProcessor()
    proc._process_many(items)
    stats = proc.last_stats
    recs = proc._records(items)
    for rec in recs:
        k = max(rec.n, rec.m)
        assert rec.width == bucket_width(k)
        assert k <= rec.width
        assert rec.width < 2 * k or rec.width == BUCKET_SIZES[0]
    rows = collections.Counter((rec.width, rec.may_span) for rec in recs)
    assert stats["pipeline_calls"] == len(rows)
    assert stats["allocated_points"] == sum(_round_rows(c) * w
                                            for (w, _), c in rows.items())
    assert stats["valid_points"] == sum(rec.m for rec in recs)
    fixed = (len(recs) * MAX_SEG_POINTS - stats["valid_points"]) \
        / stats["valid_points"]
    if mix == "heavy_tail":
        assert fixed / stats["padded_fraction"] >= 5.0


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(2, 4))
def test_bucketing_reassembly_is_batch_composition_invariant(seed, n_arch):
    """Per-archive outputs must not depend on what else shares the
    batch: processing archives together == processing them alone."""
    rng = np.random.default_rng(seed)
    items = [_synth_archive(rng, int(rng.integers(1, 4)))
             for _ in range(n_arch)]
    proc = SegmentProcessor(aerodromes=synthetic_aerodromes(n=16))
    together = proc._process_many(items)
    for item, batched in zip(items, together):
        alone = proc._process_many([item])[0]
        assert alone.icao24 == batched.icao24
        assert alone.airspace == batched.airspace
        np.testing.assert_array_equal(alone.count, batched.count)
        for attr in ATTRS:
            np.testing.assert_array_equal(
                getattr(alone, attr), getattr(batched, attr), err_msg=attr)


# ---------------------------------------------------------------------------
# Reassembly: whole-bucket gathers == the per-row reference, bit for bit.
# ---------------------------------------------------------------------------

def _per_row_reassemble(self, items, records, buckets, fetched, allocated
                        ) -> list[ProcessedSegments]:
    """The per-row reassembly that whole-bucket gathers replaced, kept
    as the reference (``self`` is a SegmentProcessor)."""
    planes: dict[int, dict[str, np.ndarray]] = {}   # gi -> field rows
    for idxs, host in fetched:
        for r, gi in enumerate(idxs):
            planes[gi] = {k: v[r] for k, v in host.items()}

    # Airspace class for every segment in one vectorized query.
    lat0 = np.array([planes[gi]["lat"][0] for gi in range(len(records))])
    lon0 = np.array([planes[gi]["lon"][0] for gi in range(len(records))])
    airspace = self._airspace_classes(lat0, lon0)

    valid = sum(rec.m for rec in records)
    bucket_rows: dict[int, int] = {}
    for (width, _), ix in buckets.items():
        bucket_rows[int(width)] = bucket_rows.get(int(width), 0) \
            + len(ix)
    self.last_stats = _pipeline_stats(
        self.backend, len(records), int(valid),
        int(allocated), bucket_rows, len(buckets))

    out_list: list[ProcessedSegments] = []
    gi = 0
    for ai, (_, segs) in enumerate(items):
        rows = list(range(gi, gi + len(segs)))
        gi += len(segs)
        if not rows:
            out_list.append(_empty())
            continue
        wmax = max(records[r].width for r in rows)
        fields = {attr: np.zeros((len(rows), wmax), np.float32)
                  for _, attr in _PLANE_ATTRS}
        for b, r in enumerate(rows):
            w = records[r].width
            for plane, attr in _PLANE_ATTRS:
                fields[attr][b, :w] = planes[r][plane]
        out_list.append(ProcessedSegments(
            icao24=[records[r].name for r in rows],
            count=np.array([records[r].m for r in rows], np.int32),
            airspace=[airspace[r] for r in rows],
            **fields))
    return out_list


#: (observations, seconds between them): the grid points fall in the
#: 128, 256, 512 and 1024 buckets.
_WIDTH_SHAPES = {128: (20, 5.0), 256: (50, 5.0), 512: (90, 5.0),
                 1024: (150, 7.0)}


def _shaped_archive(name, shapes):
    """One archive of segments 400 s apart, one per (width, span):
    ``span`` starts the segment just south of the DEM tile border at
    40 deg N and drifts north across it (``_may_span`` True); otherwise
    it stays near 35 deg N, inside one tile."""
    ts, lats, lons = [], [], []
    t = 0.0
    for i, (width, span) in enumerate(shapes):
        n, dt = _WIDTH_SHAPES[width]
        ts.append(t + dt * np.arange(1, n + 1))
        lat0 = 39.9 if span else 35.0 + 0.3 * i
        lats.append(lat0 + (0.004 if span else 1e-4) * np.arange(n))
        lons.append(-100.0 + 0.2 * i + 1e-3 * np.arange(n))
        t = ts[-1][-1] + 400.0
    obs = {"time": np.concatenate(ts), "lat": np.concatenate(lats),
           "lon": np.concatenate(lons),
           "alt": np.full(sum(len(x) for x in ts), 1500.0),
           "icao24": np.array([name] * sum(len(x) for x in ts))}
    segs = split_segments(obs["time"])
    assert len(segs) == len(shapes)
    return obs, segs


def _no_segments():
    return ({"time": np.array([0.0, 1.0]), "lat": np.zeros(2),
             "lon": np.zeros(2), "alt": np.zeros(2),
             "icao24": np.array(["e0e0e0"] * 2)}, [])


def _reassembly_items(case):
    mixed = [_shaped_archive("a00001", [(128, False), (1024, True),
                                        (512, False), (256, True)]),
             _shaped_archive("a00002", [(256, False), (128, True)]),
             _shaped_archive("a00003", [(1024, False), (1024, True),
                                        (128, False)])]
    if case == "mixed_buckets":
        return mixed
    if case == "zero_segment_archives":
        return [_no_segments(), mixed[0], _no_segments(), _no_segments(),
                mixed[1], _no_segments()]
    if case == "one_segment":
        return [_shaped_archive("b00001", [(256, False)])]
    rng = np.random.default_rng(29)
    return [_shaped_archive(f"c{i:05x}", [(int(rng.choice(BUCKET_SIZES)),
                                          bool(rng.random() < 0.3))])
            for i in range(60)]


def _noise_process_segments(dem, t_in, v_in, count_in, t_out, count_out,
                            **kw):
    """Stand-in for the device call: read-only (B, K) f32 planes of
    noise over every column (so the reassembly must carry a bucket row
    whole), starting at each row's first knot (so airspace classes read
    real start points)."""
    rng = np.random.default_rng(int(count_out.sum()) + t_out.shape[1])
    out = {}
    for f in FIELDS:
        plane = rng.normal(0.0, 100.0, t_out.shape).astype(np.float32)
        if f in ("lat", "lon"):
            plane[:, 0] = v_in[:, 0 if f == "lat" else 1, 0]
        plane.flags.writeable = False
        out[f] = plane
    return out


@pytest.mark.parametrize("device", ["noise", "pipeline"])
@pytest.mark.parametrize("case", ["mixed_buckets", "zero_segment_archives",
                                  "one_segment", "many_one_segment"])
def test_reassembly_matches_per_row_reference(case, device, monkeypatch):
    items = _reassembly_items(case)
    if device == "noise":
        monkeypatch.setattr(ops, "process_segments",
                            _noise_process_segments)
    # Aerodromes on some segments' start points: classes other than G.
    starts = [(obs["lat"][s.start], obs["lon"][s.start])
              for obs, segs in items for s in segs][::3]
    aero = [Aerodrome(f"X{i}", float(la), float(lo), "BCD"[i % 3], 0.0)
            for i, (la, lo) in enumerate(starts)]
    proc = SegmentProcessor(aerodromes=aero)
    seen = {}
    real = proc._reassemble

    def spy(items, records, buckets, fetched, allocated):
        seen["args"] = (items, records, buckets, list(fetched), allocated)
        return real(items, records, buckets, fetched, allocated)

    monkeypatch.setattr(proc, "_reassemble", spy)
    got = proc._process_many(items)
    ref = SegmentProcessor(aerodromes=aero)
    want = _per_row_reassemble(ref, *seen["args"])
    if case == "mixed_buckets":
        assert {k[1] for k in seen["args"][2]} == {False, True}
        assert {k[0] for k in seen["args"][2]} == set(BUCKET_SIZES)
    assert proc.last_stats == ref.last_stats
    assert len(got) == len(want) == len(items)
    assert any(c != "G" for w in want for c in w.airspace)

    def same(a, b):
        return (a.dtype == b.dtype and a.shape == b.shape
                and a.tobytes() == b.tobytes())

    for g, w in zip(got, want):
        assert g.icao24 == w.icao24 and g.airspace == w.airspace
        assert same(g.count, w.count)
        for attr in ATTRS + ("count",):
            plane = getattr(g, attr)
            assert same(plane, getattr(w, attr)), attr
            assert plane.flags.writeable and plane.flags.c_contiguous
    # A write into one track's planes leaves every other track's alone.
    hit = next(i for i, g in enumerate(got) if len(g))
    got[hit].lat[0, 0] += 1.0
    for i, (g, w) in enumerate(zip(got, want)):
        for attr in ATTRS:
            if i == hit and attr == "lat":
                assert not same(g.lat, w.lat)
            else:
                assert same(getattr(g, attr), getattr(w, attr)), (i, attr)


# ---------------------------------------------------------------------------
# Satellite: vectorized CSV parse.
# ---------------------------------------------------------------------------

CSV = ("time,icao24,lat,lon,geoaltitude\n"
       "30.0,abc123,40.5,-100.25,1200.0\n"
       "\n"
       "10.0,abc123,40.1,-100.10,1100.0\n"
       "10.0,abc123,40.2,-100.15,1150.0\n"
       "20.5,abc123,40.3,-100.20,1180.0\n")


def test_read_observations_vectorized_parse(tmp_path):
    p = tmp_path / "abc123.csv"
    p.write_text(CSV)
    proc = SegmentProcessor()
    obs = proc.read_observations(str(p))
    np.testing.assert_array_equal(obs["time"], [10.0, 10.0, 20.5, 30.0])
    # stable sort: the two t=10 rows keep file order
    np.testing.assert_array_equal(obs["lat"], [40.1, 40.2, 40.3, 40.5])
    np.testing.assert_array_equal(obs["lon"],
                                  [-100.10, -100.15, -100.20, -100.25])
    np.testing.assert_array_equal(obs["alt"],
                                  [1100.0, 1150.0, 1180.0, 1200.0])
    assert list(obs["icao24"]) == ["abc123"] * 4


def test_read_observations_zip_and_column_order(tmp_path):
    # shuffled header order must not matter
    csv = ("lat,geoaltitude,time,icao24,lon\n"
           "40.0,1000.0,5.0,ff0011,-99.5\n"
           "40.1,1001.0,4.0,ff0011,-99.6\n")
    z = tmp_path / "ff0011.zip"
    with zipfile.ZipFile(z, "w") as zf:
        zf.writestr("ff0011.csv", csv)
    obs = SegmentProcessor().read_observations(str(z))
    np.testing.assert_array_equal(obs["time"], [4.0, 5.0])
    np.testing.assert_array_equal(obs["lat"], [40.1, 40.0])
    np.testing.assert_array_equal(obs["alt"], [1001.0, 1000.0])
    assert list(obs["icao24"]) == ["ff0011"] * 2


def test_read_observations_header_only(tmp_path):
    p = tmp_path / "empty.csv"
    p.write_text("time,icao24,lat,lon,geoaltitude\n")
    assert SegmentProcessor().read_observations(str(p)) == {}


# ---------------------------------------------------------------------------
# Satellite: vectorized airspace classification.
# ---------------------------------------------------------------------------

def test_airspace_classes_match_scalar_reference():
    from repro.geometry.queries import RADIUS_DEG
    aero = synthetic_aerodromes(n=40)
    proc = SegmentProcessor(aerodromes=aero)
    rng = np.random.default_rng(11)
    # half random points, half exactly on aerodromes (inside the radius)
    lat = np.r_[rng.uniform(25, 49, 20), [a.lat for a in aero[:20]]]
    lon = np.r_[rng.uniform(-124, -67, 20), [a.lon for a in aero[:20]]]
    got = proc._airspace_classes(lat, lon)

    def scalar(la, lo):
        d2 = ((np.array([a.lat for a in aero]) - la) ** 2
              + ((np.array([a.lon for a in aero]) - lo)
                 * np.cos(np.deg2rad(la))) ** 2)
        i = int(np.argmin(d2))
        return aero[i].airspace_class if d2[i] <= RADIUS_DEG ** 2 else "G"

    assert got == [scalar(la, lo) for la, lo in zip(lat, lon)]
    assert any(g != "G" for g in got)       # on-aerodrome points classified
    assert proc._airspace_class(lat[0], lon[0]) == got[0]


def test_airspace_classes_no_aerodromes():
    proc = SegmentProcessor()
    assert proc._airspace_classes(np.array([40.0]),
                                  np.array([-100.0])) == ["G"]


# ---------------------------------------------------------------------------
# A message of single-track store tasks: one grouped read, one pass.
# ---------------------------------------------------------------------------

def test_process_batch_of_300_single_track_tasks_matches_per_id(
        block_store, monkeypatch):
    """A 300-task message of single-track ``store://`` tasks, in random
    order, == each task processed alone, bit for bit; the message reads
    each shard it touches once."""
    from repro.obs import Tracer
    from repro.store import StoreManifest
    from repro.tracks.segments import segment_tasks_from_store
    tasks = segment_tasks_from_store(block_store, granularity="track")
    rng = np.random.default_rng(8)
    tasks = [tasks[i] for i in rng.permutation(len(tasks))[:300]]
    proc = SegmentProcessor(aerodromes=synthetic_aerodromes(n=16))
    alone = {t.task_id: proc.process_batch([t])[t.task_id] for t in tasks}
    tr = Tracer()
    proc.attach_tracer(tr)
    batched = proc.process_batch(tasks)
    proc.attach_tracer(None)
    assert set(batched) == set(alone)
    for tid, want in alone.items():
        got = batched[tid]
        assert got.icao24 == want.icao24
        assert got.airspace == want.airspace
        assert got.count.dtype == want.count.dtype
        np.testing.assert_array_equal(got.count, want.count)
        for attr in ATTRS:
            a, b = getattr(got, attr), getattr(want, attr)
            assert a.dtype == b.dtype and a.shape == b.shape, attr
            assert a.tobytes() == b.tobytes(), (tid, attr)
    decodes = [e for e in tr.events if e[2] == "store_decode"]
    shards = [e[6]["shard"] for e in decodes]
    assert shards == sorted(set(shards))
    n_obs = {t.track_id: t.n_obs
             for t in StoreManifest.load(block_store).tracks}
    assert sum(e[6]["obs"] for e in decodes) == sum(n_obs[t.task_id]
                                                    for t in tasks)


def test_process_batch_returns_free_heap_after_each_message(block_store,
                                                            monkeypatch):
    """Each message ends by handing the C heap's free pages back (glibc
    ``malloc_trim``); the results are those of a run without it."""
    from repro.tracks import segments
    from repro.tracks.segments import segment_tasks_from_store
    fn = segments._malloc_trim()
    assert fn is None or fn(0) in (0, 1)
    tasks = segment_tasks_from_store(block_store, granularity="track")[:40]
    proc = SegmentProcessor(aerodromes=synthetic_aerodromes(n=16))
    want = proc._process_batch(tasks)
    calls = []
    monkeypatch.setattr(segments, "_release_free_heap",
                        lambda: calls.append(1))
    got = [proc.process_batch(tasks[a:a + 10]) for a in range(0, 40, 10)]
    assert len(calls) == 4
    merged = {k: v for part in got for k, v in part.items()}
    assert set(merged) == set(want)
    for tid, ps in want.items():
        for attr in ATTRS:
            assert getattr(merged[tid], attr).tobytes() \
                == getattr(ps, attr).tobytes()
