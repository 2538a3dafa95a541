"""End-to-end --screen workflow: barrier vs DAG byte-identity + exactness.

One scaled raw feed (single hourly file so the synthetic aircraft share
an hour and actually co-bin), pushed through the full store-input
pipeline twice — barrier mode and streaming-DAG mode — with screening
enabled.  The candidates.json artifacts must be byte-identical, and
their pair set must equal the brute-force all-pairs screen over the
same store-derived rows.
"""

import json
import os

import numpy as np
import pytest

from repro.core.messages import Task
from repro.kernels.encounter_screen import (
    brute_force_screen, rows_from_track, screen_cells)
from repro.store.reader import make_store_uri
from repro.tracks.segments import (
    SegmentProcessor, segment_tasks_from_store, split_segments)
from repro.tracks.workflow import (
    ScreenWorker, TrackWorkflow, _screen_rows_for_uri)

# Calibrated so the ~60 co-located synthetic aircraft yield a small,
# non-empty candidate set (3 pairs) in a few screening cells.
SCREEN_KW = dict(
    input="store", store_target_points=2048, screen=True,
    screen_h_m=50_000.0, screen_v_m=1000.0, screen_cell_deg=1.0,
    n_workers=4, poll_interval=0.003)


def _run(root, mode):
    wf = TrackWorkflow(str(root), mode=mode, **SCREEN_KW)
    wf.generate_raw(n_files=1, scale=1e3)
    wf.run()
    return wf


@pytest.fixture(scope="module")
def barrier_wf(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("screen_barrier"), "barrier")


@pytest.fixture(scope="module")
def dag_wf(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("screen_dag"), "dag")


def test_barrier_candidates_artifact(barrier_wf):
    with open(barrier_wf.candidates_path) as f:
        doc = json.load(f)
    assert doc["schema"] == "repro.encounters/v1"
    assert doc["thresholds"] == {"h_m": 50_000.0, "v_m": 1000.0}
    assert doc["grid"]["cell_deg"] == 1.0
    cands = doc["candidates"]
    assert len(cands) >= 1
    # Canonical: a < b, sorted by (a, b), unique pairs.
    pairs = [(c["a"], c["b"]) for c in cands]
    assert all(a < b for a, b in pairs)
    assert pairs == sorted(set(pairs))


def test_dag_byte_identical_to_barrier(barrier_wf, dag_wf):
    with open(barrier_wf.candidates_path, "rb") as f:
        barrier = f.read()
    with open(dag_wf.candidates_path, "rb") as f:
        dag = f.read()
    assert barrier == dag


def test_candidates_equal_brute_force(barrier_wf):
    """The workflow's grid-screened candidates are exactly the brute
    force all-pairs set over the same store-derived rows."""
    proc = SegmentProcessor(backend=barrier_wf.backend)
    rows = []
    for t in segment_tasks_from_store(barrier_wf.store_dir,
                                      granularity="shard"):
        rows.extend(_screen_rows_for_uri(proc, t.payload))
    want = brute_force_screen(rows, config=barrier_wf.screen_config)
    with open(barrier_wf.candidates_path) as f:
        got = json.load(f)["candidates"]
    assert [(c["a"], c["b"]) for c in got] == \
        [(c["a"], c["b"]) for c in want]


def test_screen_resumes_when_artifact_missing(barrier_wf):
    """Deleting candidates.json and re-running only redoes screening
    (phases_done guard drops 'screen' when the artifact is gone)."""
    os.remove(barrier_wf.candidates_path)
    wf = TrackWorkflow(barrier_wf.root, mode="barrier", **SCREEN_KW)
    wf.run()
    assert os.path.exists(barrier_wf.candidates_path)
    test_barrier_candidates_artifact(barrier_wf)


def test_screen_requires_store_input(tmp_path):
    with pytest.raises(ValueError, match="store"):
        TrackWorkflow(str(tmp_path), screen=True, input="zip")


def test_workflow_keeps_benchmark_hooks():
    """The chip benchmark's screen driver subclasses ``TrackWorkflow``:
    it overrides the private methods it names in ``HOOKS`` and builds
    the workflow with keywords.  Read both from the driver's source, so
    renaming a hook or a keyword fails here, on the CPU, and not first
    in a run on the chip."""
    import ast
    import inspect
    path = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                        "drivers", "screen.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    hooks = next(ast.literal_eval(node.value) for node in ast.walk(tree)
                 if isinstance(node, ast.Assign)
                 and any(getattr(t, "id", None) == "HOOKS"
                         for t in node.targets))
    assert {"_screen_tasks_full", "_run_phase", "_save_ckpt"} <= set(hooks)
    for hook in hooks:
        assert callable(getattr(TrackWorkflow, hook, None)), hook
    calls = [node for node in ast.walk(tree)
             if isinstance(node, ast.Call)
             and isinstance(node.func, ast.Call)
             and getattr(node.func.func, "id", None) == "_workflow_class"]
    assert len(calls) == 1
    keywords = {kw.arg for kw in calls[0].keywords}
    assert {"input", "screen", "n_workers"} <= keywords
    inspect.signature(TrackWorkflow).bind("root", **dict.fromkeys(keywords))


# -- one cell task: grouped member read, one fused pass -----------------

#: Tracks of the cell store below: overlapping in time over a few km,
#: one every ``GAP_EVERY`` with a gap past the 120-s segment gap (two
#: segments), and one too short for any segment.
CELL_TRACKS, GAP_EVERY, SHORT = 14, 4, 9


def _write_cell_csvs(root):
    rng = np.random.default_rng(19)
    os.makedirs(root)
    for i in range(CELL_TRACKS):
        n = 5 if i == SHORT else int(rng.integers(12, 90))
        t = 60.0 * rng.random() + np.cumsum(
            rng.integers(3, 9, n)).astype(float)
        if i % GAP_EVERY == 1:
            t[n // 2:] += 200.0
        lat = 35.0 + rng.normal(0.0, 0.02) + np.cumsum(
            rng.normal(0.0, 5e-4, n))
        lon = -98.0 + rng.normal(0.0, 0.02) + np.cumsum(
            rng.normal(0.0, 5e-4, n))
        alt = 600.0 + rng.normal(0.0, 200.0) + np.cumsum(
            rng.normal(0.0, 3.0, n))
        rows = "".join(f"{a:.2f},{0xe00000 + i:06x},{b:.5f},{c:.5f},"
                       f"{d:.1f}\n" for a, b, c, d in zip(t, lat, lon, alt))
        with open(os.path.join(root, f"c{i:03d}.csv"), "w") as f:
            f.write("time,icao24,lat,lon,geoaltitude\n" + rows)


@pytest.fixture(scope="module")
def cell_store(tmp_path_factory):
    """A store of co-located tracks in several shards, the ids of its
    tracks with segments and of the one without, and all their rows."""
    from repro.store import build_store
    from repro.store.reader import TrackStore
    root = tmp_path_factory.mktemp("cell_store")
    _write_cell_csvs(str(root / "csv"))
    store = str(root / "store")
    assert len(build_store(str(root / "csv"), store,
                           target_points=120).shards) >= 3
    read = TrackStore(store).read_tracks(
        [t.track_id for t in TrackStore(store).manifest.tracks])
    segless = [tid for tid, (_obs, segs) in read.items() if not segs]
    assert len(segless) == 1
    proc = ScreenWorker(store, h_thresh_m=1.0, v_thresh_m=1.0)._processor()
    rows = []
    for t in segment_tasks_from_store(store, granularity="shard"):
        rows.extend(_screen_rows_for_uri(proc, t.payload))
    assert len({r.group for r in rows}) == CELL_TRACKS - 1
    return store, segless[0], sorted(r.row_id for r in rows)


def _cell_task(all_ids, new_ids):
    return Task(task_id="screen/c0/g1",
                payload=json.dumps({"cell": "c0", "all": sorted(all_ids),
                                    "new": sorted(new_ids)}))


def _cell_tasks(cell_store):
    store, segless, ids = cell_store
    return {
        "barrier": _cell_task(ids, ids),
        "generation": _cell_task(ids, ids[::3]),
        "segless_member": _cell_task(ids + [f"{segless}#s000"],
                                     ids + [f"{segless}#s000"]),
    }


def _per_track_reference(worker, task):
    """The cell task as one read and one pipeline call per member."""
    doc = json.loads(task.payload)
    wanted = set(doc["all"])
    proc = worker._processor()
    rows = []
    for tid in sorted({rid.rsplit("#", 1)[0] for rid in wanted}):
        obs = proc.read_observations(make_store_uri(worker.store_dir,
                                                    track=tid))
        segs = split_segments(obs["time"])
        if not segs:
            continue
        ps = proc.process_arrays(obs, segs)
        rows.extend(r for r in rows_from_track(tid, obs, segs, ps)
                    if r.row_id in wanted)
    new = set(doc["new"])
    return screen_cells({doc["cell"]: rows}, config=worker._config(),
                        new_ids=None if new >= wanted
                        else {doc["cell"]: new})


@pytest.mark.parametrize("kind", ["barrier", "generation",
                                  "segless_member"])
def test_cell_task_equals_per_track_derivation(cell_store, kind):
    """A cell task derives its members together and screens exactly the
    rows, pairs and numbers of one derivation per member track."""
    store = cell_store[0]
    task = _cell_tasks(cell_store)[kind]
    kw = dict(h_thresh_m=2_000.0, v_thresh_m=300.0)
    got = ScreenWorker(store, **kw)(task)
    want_cands, want_stats = _per_track_reference(
        ScreenWorker(store, **kw), task)
    assert got["candidates"] and got["candidates"] == want_cands
    assert got["stats"] == want_stats
    if kind == "generation":
        full = ScreenWorker(store, **kw)(_cell_tasks(cell_store)["barrier"])
        assert len(got["candidates"]) < len(full["candidates"])


def test_cell_task_reads_and_derives_members_once(cell_store):
    """Traced, a cell task makes one ``store_decode`` per shard its
    members touch, one device call per bucket of all their segments and
    one ``screen.rows`` span counting its rows and member tracks."""
    from repro.obs import Tracer
    from repro.runtime import run_job
    from repro.store.reader import TrackStore
    store, segless, ids = cell_store
    task = _cell_tasks(cell_store)["segless_member"]
    tr = Tracer()
    worker = ScreenWorker(store, h_thresh_m=2_000.0, v_thresh_m=300.0)
    r = run_job([task], worker, backend="threads", n_workers=1, tracer=tr)
    members = sorted({rid.rsplit("#", 1)[0] for rid in ids})
    spans = {}
    for e in tr.events:
        if e[1] >= 0.0 and e[5] == task.task_id:
            spans.setdefault(e[2], []).append(e)
    shards = TrackStore(store).plan([{"track": t}
                                     for t in members + [segless]])
    assert len(spans["store_decode"]) == len(shards) >= 3
    (rows_span,) = spans["screen.rows"]
    assert rows_span[6] == {"rows": len(ids), "tracks": len(members)}
    read = TrackStore(store).read_tracks(members)
    records = worker._processor()._records([read[t] for t in members])
    buckets = {(rec.width, rec.may_span) for rec in records}
    assert len(spans["segments.device"]) == len(buckets) < len(members)
    assert r.results[task.task_id]["stats"]["pairs_screened"] > 0
