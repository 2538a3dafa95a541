"""Real (scaled) track workflow: organize -> archive -> process."""

import os
import zipfile

import numpy as np
import pytest

from repro.core.messages import Task
from repro.tracks.archive import Archiver, archive_tasks_from_tree
from repro.tracks.datasets import (
    MONDAY_FILE_COUNT, ScaledDatasetSpec, aerodrome_manifest,
    monday_manifest, write_scaled_dataset)
from repro.tracks.organize import Organizer, organize_tasks_from_dir
from repro.tracks.registry import HierarchySpec, synthetic_registry
from repro.tracks.segments import (
    MIN_OBS_PER_SEGMENT, SegmentProcessor, split_segments)
from repro.tracks.workflow import TrackWorkflow


def test_manifests_match_paper_statistics():
    m = monday_manifest()
    assert len(m) == MONDAY_FILE_COUNT == 2425
    assert abs(sum(t.size_bytes for t in m) / 714e9 - 1) < 0.01
    a = aerodrome_manifest()
    assert len(a) == 136_884
    assert abs(sum(t.size_bytes for t in a) / 847e9 - 1) < 0.01
    # Fig 3: aerodrome sizes are heavy-tailed vs Monday's diurnal bump
    ms = np.array([t.size_bytes for t in m], float)
    as_ = np.array([t.size_bytes for t in a], float)
    assert ms.std() / ms.mean() < 0.5          # compact (Gaussian-ish)
    assert as_.std() / as_.mean() > 2.0        # sloping / heavy-tailed


def test_hierarchy_fanout_under_1000():
    reg = synthetic_registry(n=3000)
    h = HierarchySpec()
    paths = [h.aircraft_dir(2019, e, e.icao24) for e in reg.values()]
    assert h.validate_fanout(paths)


def test_split_segments_ten_obs_rule():
    t = np.concatenate([np.arange(0, 9),          # 9 obs -> dropped
                        1000 + np.arange(0, 50),   # 50 obs -> kept
                        5000 + np.arange(0, 10)])  # exactly 10 -> kept
    segs = split_segments(t, gap_s=120.0)
    assert len(segs) == 2
    assert segs[0].stop - segs[0].start == 50
    assert segs[1].stop - segs[1].start == MIN_OBS_PER_SEGMENT


@pytest.fixture(scope="module")
def workflow(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("wf"))
    wf = TrackWorkflow(root, n_workers=4, poll_interval=0.003)
    wf.generate_raw(n_files=5, scale=2e4)
    wf.run()
    return wf


def test_workflow_phases_complete(workflow):
    assert [r.phase for r in workflow.reports] == \
        ["organize", "archive", "process"]
    assert all(r.tasks > 0 for r in workflow.reports)


def test_organize_groups_by_aircraft(workflow):
    csvs = []
    for dirpath, _d, files in os.walk(workflow.organized_dir):
        csvs += [os.path.join(dirpath, f) for f in files
                 if f.endswith(".csv")]
    assert csvs
    for p in csvs:
        icao = os.path.basename(p)[:-4]
        with open(p) as f:
            header = f.readline().strip().split(",")
            idx = header.index("icao24")
            for line in f:
                assert line.split(",")[idx] == icao


def test_archive_mirrors_hierarchy_and_roundtrips(workflow):
    zips = []
    for dirpath, _d, files in os.walk(workflow.archive_dir):
        zips += [os.path.join(dirpath, f) for f in files
                 if f.endswith(".zip")]
    assert zips
    z = zips[0]
    rel = os.path.relpath(z, workflow.archive_dir)
    # replicated first three tiers: year/type/seats/bucket/<icao>.zip
    assert len(rel.split(os.sep)) == 5
    with zipfile.ZipFile(z) as zf:
        names = zf.namelist()
        assert names and all(n.endswith(".csv") for n in names)


def test_processing_produces_valid_segments(workflow):
    from repro.tracks.segments import segment_tasks_from_archive_tree
    tasks = segment_tasks_from_archive_tree(workflow.archive_dir)
    proc = SegmentProcessor(backend="pallas")
    out = proc(tasks[0])
    if len(out) == 0:
        pytest.skip("first archive had only short segments")
    assert np.isfinite(out.alt_agl_m).all()
    assert (out.count >= MIN_OBS_PER_SEGMENT).all() or \
        (out.count >= 2).all()    # resampled count can differ from raw
    # uniform 1 Hz grid
    b = 0
    m = out.count[b]
    if m > 2:
        dt = np.diff(out.times[b, :m])
        np.testing.assert_allclose(dt, 1.0, atol=1e-5)
    # AGL = MSL - DEM <= MSL for non-negative terrain
    mask = np.arange(out.times.shape[1])[None, :] < out.count[:, None]
    assert np.all(out.alt_agl_m[mask] <= out.alt_msl_m[mask] + 1e-3)


def test_segment_batch_matches_per_task(workflow):
    """process_batch (one vectorized pallas call per ASSIGN message) must
    agree with per-task dispatch."""
    from repro.tracks.segments import segment_tasks_from_archive_tree
    tasks = segment_tasks_from_archive_tree(workflow.archive_dir)[:3]
    assert tasks
    proc = SegmentProcessor(backend="pallas")
    batched = proc.process_batch(tasks)
    assert set(batched) == {t.task_id for t in tasks}
    for t in tasks:
        single = proc(t)
        b = batched[t.task_id]
        assert b.icao24 == single.icao24
        assert b.airspace == single.airspace
        np.testing.assert_array_equal(b.count, single.count)
        for field in ("times", "lat", "lon", "alt_msl_m", "alt_agl_m",
                      "vrate_ms", "gspeed_ms", "heading_rad", "turn_rad_s"):
            np.testing.assert_allclose(
                getattr(b, field), getattr(single, field),
                atol=1e-4, rtol=1e-4, err_msg=field)


def test_workflow_runs_on_process_backend(tmp_path):
    wf = TrackWorkflow(str(tmp_path), n_workers=2, poll_interval=0.003,
                       exec_backend="processes", tasks_per_message=2)
    wf.generate_raw(n_files=3, scale=2e4)
    reports = wf.run()
    assert [r.phase for r in reports] == ["organize", "archive", "process"]
    assert all(r.tasks > 0 for r in reports)


def test_process_backend_refused_on_tpu(tmp_path, monkeypatch):
    """On a TPU one process owns the chip: the device phases refuse
    worker processes up front, before any phase runs."""
    from repro import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match=r"\(process, screen\).*B\.1"):
        TrackWorkflow(str(tmp_path), exec_backend="processes",
                      input="store", screen=True)
    assert not os.listdir(tmp_path)
    TrackWorkflow(str(tmp_path), exec_backend="threads")


def test_workflow_checkpoint_resume(tmp_path):
    wf = TrackWorkflow(str(tmp_path), n_workers=2, poll_interval=0.003)
    wf.generate_raw(n_files=3, scale=2e4)
    wf.run()
    n_reports = len(wf.reports)
    # a second run must skip all completed phases
    wf2 = TrackWorkflow(str(tmp_path), n_workers=2, poll_interval=0.003)
    reports2 = wf2.run()
    assert reports2 == []
    assert n_reports == 3


def test_organizer_counts(tmp_path):
    spec = ScaledDatasetSpec(name="t", n_files=2, scale=2e4)
    paths = write_scaled_dataset(str(tmp_path / "raw"), spec)
    reg = synthetic_registry(n=500)
    org = Organizer(str(tmp_path / "org"), reg)
    res = org(Task(task_id=paths[0], payload=paths[0]))
    assert res.rows > 0 and res.aircraft > 0
    assert res.files_written == res.aircraft
