"""The generator and the references on tiny cases."""

import copy

import numpy as np
import pytest

from conftest import load


def _tracks(config: str, cut: dict, seed: int = 3, **gen):
    from chipbench import gen as g
    cfg = copy.deepcopy(load("configs", config))
    cfg["generator"].update(gen)
    return g.make_tracks(cfg, {"cut": cut}, seed)


def test_csv_round_trip_is_bit_exact(tmp_path):
    """The store parses back exactly the numbers the reference uses."""
    from chipbench import gen
    from repro.tracks.segments import read_observations
    tr = _tracks("aerodrome_terminal", {"hotspots": 1}, aircraft_per_hour=40)
    gen.write_csv_tree(tr, str(tmp_path))
    for i, tid in enumerate(tr.ids):
        obs = read_observations(str(tmp_path / tid))
        want = tr.track(i)
        for a, b in (("time", "time"), ("lat", "lat"), ("lon", "lon"),
                     ("alt", "geoaltitude")):
            assert np.array_equal(obs[a], want[b]), (tid, a)


def test_shard_cut_fills_whole_shards(tmp_path):
    from chipbench import gen
    from repro.store.writer import build_store
    cfg = copy.deepcopy(load("configs", "mondays_enroute"))
    cfg["deployment"]["shard_points"] = 500
    tr = gen.make_tracks(cfg, {"cut": {"shards": 3}}, 5)
    gen.write_csv_tree(tr, str(tmp_path / "csv"))
    man = build_store(str(tmp_path / "csv"), str(tmp_path / "store"),
                      target_points=500)
    assert len(man.shards) == 3
    assert man.n_points == tr.n_obs


def test_fixed_inputs_equal_the_deployments():
    from chipbench import reference
    from repro.geometry.aerodromes import synthetic_aerodromes
    from repro.geometry.dem import SyntheticGlobeDEM
    assert np.array_equal(reference.Terrain().elevation,
                          SyntheticGlobeDEM().elevation_m)
    lat, lon, cls = reference.aerodromes()
    prog = synthetic_aerodromes(n=64)
    assert lat.tolist() == [a.lat for a in prog]
    assert lon.tolist() == [a.lon for a in prog]
    assert cls.tolist() == [a.airspace_class for a in prog]


def test_straight_level_flight():
    """Constant velocity north at 100 m/s: every plane is known."""
    from chipbench import gen, reference
    n = 31
    t = np.arange(n) * 7.0
    lat = 40.0 + 100.0 * t / reference.M_PER_DEG
    tr = gen.Tracks(ids=["a.csv"], offsets=np.array([0, n]),
                    cols={"time": t, "lat": lat, "lon": np.full(n, -100.0),
                          "geoaltitude": 1000.0 + 2.0 * t})
    segs = reference.segment_planes(tr, reference.Terrain(),
                                    reference.aerodromes())
    m = int(segs.m[0])
    assert m == 211 and len(segs) == 1
    p = segs.planes
    np.testing.assert_allclose(p["gspeed_ms"], 100.0, rtol=1e-9)
    np.testing.assert_allclose(p["heading_rad"], 0.0, atol=1e-9)
    np.testing.assert_allclose(p["vrate_ms"], 2.0, rtol=1e-9)
    np.testing.assert_allclose(p["alt_msl_m"], 1000.0 + 2.0 * np.arange(m),
                               rtol=1e-12)


@pytest.mark.parametrize("config,cut,gen", [
    ("mondays_enroute", {"box_deg": [35.0, 35.6, -100.0, -99.4]},
     {"tracks_per_deg2": 60.0}),
    ("aerodrome_terminal", {"hotspots": 1}, {"aircraft_per_hour": 40}),
])
def test_planes_agree_with_the_programs_oracle(config, cut, gen):
    """The float64 reference and the program's jnp oracle (float32,
    ``backend="ref"``) agree within the plane limits, segment by
    segment, and split the tracks alike."""
    from chipbench import reference
    from repro.geometry.aerodromes import synthetic_aerodromes
    from repro.geometry.dem import SyntheticGlobeDEM
    from repro.tracks.segments import SegmentProcessor, split_segments
    tr = _tracks(config, cut, **gen)
    segs = reference.segment_planes(tr, reference.Terrain(),
                                    reference.aerodromes())
    proc = SegmentProcessor(dem=SyntheticGlobeDEM(),
                            aerodromes=synthetic_aerodromes(n=64),
                            backend="ref")
    lim = load("limits", "mondays.process")["limits"]
    s = 0
    for i in range(len(tr)):
        obs = dict(tr.track(i), alt=tr.track(i)["geoaltitude"],
                   icao24=np.array(["x"] * (tr.offsets[i + 1]
                                            - tr.offsets[i])))
        sl = split_segments(obs["time"])
        if not sl:
            continue
        ps = proc.process_arrays(obs, sl)
        for k in range(len(sl)):
            assert segs.track[s] == i and segs.k[s] == k
            m = int(segs.m[s])
            assert int(ps.count[k]) == m
            a = int(segs.offsets[s])
            want = reference.Segments(
                track=None, k=None, t0=None, m=None, offsets=None,
                planes={p: v[a:a + m] for p, v in segs.planes.items()},
                airspace=None, margin=None)
            got = {p: getattr(ps, p)[k, :m].astype(np.float64)
                   for p in reference.PLANES}
            for key, err in reference.plane_errors(got, want).items():
                assert err <= lim[key], (key, err)
            assert ps.airspace[k] == segs.airspace[s]
            s += 1
    assert s == len(segs)


def _rows(specs):
    """Tracks of one segment each: (t0, lat0, dlat per s, alt)."""
    from chipbench import gen
    cols = {"time": [], "lat": [], "lon": [], "geoaltitude": []}
    offs = [0]
    for t0, lat0, dlat, alt in specs:
        t = t0 + np.arange(60.0)
        cols["time"].append(t)
        cols["lat"].append(lat0 + dlat * np.arange(60.0))
        cols["lon"].append(np.full(60, -100.0))
        cols["geoaltitude"].append(np.full(60, alt))
        offs.append(offs[-1] + 60)
    return gen.Tracks(ids=[f"{i}.csv" for i in range(len(specs))],
                      offsets=np.array(offs),
                      cols={k: np.concatenate(v) for k, v in cols.items()})


def test_screen_hand_case():
    """Two rows converge head-on; a third flies with the first 500 m
    higher; a fourth is at the same place an hour later."""
    from chipbench import reference
    d = 50.0 / reference.M_PER_DEG          # 50 m/s
    tr = _rows([(0, 40.0, d, 1000.0), (0, 40.0 + 59 * d, -d, 1100.0),
                (0, 40.0, d, 1500.0),
                (3600, 40.0, d, 1000.0)])
    segs = reference.segment_planes(tr, reference.Terrain(),
                                    reference.aerodromes())
    ids = reference.row_ids(tr, segs)
    pairs = reference.screen_pairs(ids, segs, 926.0, 152.4, 0.0, 0.0)
    assert set(pairs) == {("0.csv#s000", "1.csv#s000")}
    p = pairs[("0.csv#s000", "1.csv#s000")]
    assert p.h_in == pytest.approx(50.0, abs=1e-6)   # 59 steps of 50 m
    assert p.v_in == pytest.approx(100.0, abs=1e-9)


def test_screen_reference_equals_brute_force():
    """Without a band, the all-pairs reference finds the pairs that the
    program's own numpy brute force finds on the same rows."""
    from chipbench import reference
    from repro.kernels.encounter_screen import (
        ScreenConfig, ScreenRow, brute_force_screen)
    tr = _tracks("aerodrome_terminal", {"hotspots": 1},
                 aircraft_per_hour=1200)
    segs = reference.segment_planes(tr, reference.Terrain(),
                                    reference.aerodromes())
    ids = reference.row_ids(tr, segs)
    pairs = reference.screen_pairs(ids, segs, 926.0, 152.4, 0.0, 0.0)
    o = segs.offsets
    rows = [ScreenRow(row_id=ids[s], group=ids[s].split("#")[0],
                      t0=float(segs.t0[s]),
                      lat=segs.planes["lat"][o[s]:o[s + 1]].astype(
                          np.float32),
                      lon=segs.planes["lon"][o[s]:o[s + 1]].astype(
                          np.float32),
                      alt=segs.planes["alt_msl_m"][o[s]:o[s + 1]].astype(
                          np.float32))
            for s in range(len(segs))]
    brute = brute_force_screen(rows, config=ScreenConfig())
    assert len(pairs) >= 3
    ours = {k for k, p in pairs.items() if np.isfinite(p.h_in)}
    theirs = {(c["a"], c["b"]) for c in brute}
    # float32 rows against float64 rows: only pairs at a threshold may
    # differ.
    widened = set(reference.screen_pairs(ids, segs, 926.0, 152.4, 1.0,
                                         0.01))
    assert theirs <= widened
    narrowed = {k for k, p in reference.screen_pairs(
        ids, segs, 926.0, 152.4, 1.0, 0.01).items() if np.isfinite(p.h_in)}
    assert narrowed <= theirs
    assert len(ours ^ theirs) <= len(widened) - len(narrowed)


def test_screen_errors_counts():
    from chipbench import reference
    inf = float("inf")
    ref = {("a", "b"): reference.Pair(100.0, 10.0, 99.0, 9.0),
           ("a", "c"): reference.Pair(inf, inf, 920.0, 150.0)}
    ok = reference.screen_errors(
        [{"a": "a", "b": "b", "h_m": 99.5, "v_m": 9.5}], ref)
    assert ok == {"pairs_missed": 0, "pairs_extra": 0, "h_gap_m": 0.0,
                  "v_gap_m": 0.0}
    bad = reference.screen_errors(
        [{"a": "a", "b": "c", "h_m": 900.0, "v_m": 150.0},
         {"a": "b", "b": "c", "h_m": 1.0, "v_m": 1.0}], ref)
    assert bad["pairs_missed"] == 1 and bad["pairs_extra"] == 1
    assert bad["h_gap_m"] == pytest.approx(20.0)


@pytest.mark.parametrize("name", [
    w["name"] for w in load("..", "BENCHMARK")["workloads"]])
def test_every_cell_is_found_by_name(name):
    """A cell's parts are files found by name: configuration, traffic,
    generator, driver, limits and its per-layer readers."""
    import os
    from chipbench import gen, run
    cell = run.Cell.load(name)
    mod, _ = gen._cut(cell.config, cell.traffic)
    assert callable(mod.make)
    assert hasattr(cell.driver, "Driver") and hasattr(cell.driver, "Check")
    for m in cell.per_layer:
        assert os.path.exists(os.path.join(run.HERE, "metrics",
                                           m["name"] + ".py")), m["name"]


def test_unknown_cut_is_an_error():
    from chipbench import gen
    with pytest.raises(ValueError, match="enroute_hotspots"):
        gen.make_tracks(load("configs", "mondays_enroute"),
                        {"cut": {"hotspots": 1}}, 1)
