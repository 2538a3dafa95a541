"""Columnar track store: codec round-trips, writer determinism, reader
prefetch, store-vs-zip golden equivalence, workflow integration."""

import json
import os
import zipfile

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.messages import Task
from repro.store import (
    ShardChecksumError, ShardFormatError, StoreManifest, TrackStore,
    build_store, codec, make_store_uri, parse_store_uri)
from repro.store.writer import discover_sources, plan_shards
from repro.tracks.archive import Archiver, archive_tasks_from_tree
from repro.tracks.datasets import ScaledDatasetSpec, write_scaled_dataset
from repro.tracks.organize import Organizer, organize_tasks_from_dir
from repro.tracks.registry import synthetic_registry
from repro.tracks.segments import (
    SegmentProcessor, read_observations, segment_tasks_from_archive_tree,
    segment_tasks_from_store, split_segments)

PLANE_FIELDS = ("times", "lat", "lon", "alt_msl_m", "alt_agl_m",
                "vrate_ms", "gspeed_ms", "heading_rad", "turn_rad_s")

_DTYPES = ("<f8", "<f4", "<i8", "<i4", "<i2", "<u4", "<u2", "<u1")


# ---------------------------------------------------------------------------
# Codec: property tests.
# ---------------------------------------------------------------------------

def _column(dtype: str, seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if dtype.startswith("<f"):
        return (rng.standard_normal(n) * 1e4).astype(dtype)
    info = np.iinfo(np.dtype(dtype))
    return rng.integers(info.min, info.max, size=n,
                        endpoint=True).astype(dtype)


@settings(max_examples=10)
@given(st.lists(st.tuples(st.sampled_from(_DTYPES),
                          st.integers(min_value=0, max_value=2000),
                          st.integers(min_value=0, max_value=10 ** 6)),
                min_size=1, max_size=5),
       st.sampled_from(["zlib", "none"]))
def test_codec_roundtrip_bitwise(cols_spec, compression):
    """Arbitrary lengths/dtypes -> encode -> decode bitwise-equal."""
    columns = {f"c{i}": _column(dt, seed, n)
               for i, (dt, n, seed) in enumerate(cols_spec)}
    meta = {"n": len(columns)}
    data = codec.encode_shard(columns, meta=meta,
                              compression=compression)
    # canonical encoding: same inputs -> same bytes
    assert data == codec.encode_shard(columns, meta=meta,
                                      compression=compression)
    decoded, meta2 = codec.decode_shard(data)
    assert meta2 == meta
    assert set(decoded) == set(columns)
    for name, arr in columns.items():
        out = decoded[name]
        assert out.dtype == arr.dtype
        assert out.shape == arr.shape
        assert out.tobytes() == arr.tobytes()      # bitwise


@settings(max_examples=10)
@given(st.integers(min_value=0, max_value=10 ** 6))
def test_codec_corruption_rejected(seed):
    """Any single flipped payload byte must be detected."""
    rng = np.random.default_rng(seed)
    data = bytearray(codec.encode_shard(
        {"x": rng.standard_normal(64), "y": rng.integers(0, 99, 32)}))
    pos = int(rng.integers(0, len(data)))
    data[pos] ^= 0xFF
    with pytest.raises(ShardFormatError):
        codec.decode_shard(bytes(data))


def test_codec_truncation_and_magic_rejected():
    data = codec.encode_shard({"x": np.arange(10.0)})
    with pytest.raises(ShardFormatError):
        codec.decode_shard(data[:-3])
    with pytest.raises(ShardFormatError):
        codec.decode_shard(b"NOTASTORE" + data[9:])
    with pytest.raises(ShardChecksumError):
        codec.decode_shard(data[:40] + b"\x00" + data[41:])


def test_codec_column_subset_skips_payload():
    cols = {"big": np.arange(5000.0), "small": np.arange(4)}
    data = codec.encode_shard(cols)
    out, _ = codec.decode_shard(data, columns=["small"])
    assert list(out) == ["small"]
    np.testing.assert_array_equal(out["small"], cols["small"])
    with pytest.raises(KeyError):
        codec.decode_shard(data, columns=["absent"])


# ---------------------------------------------------------------------------
# Store URIs.
# ---------------------------------------------------------------------------

def test_store_uri_roundtrip():
    uri = make_store_uri("/tmp/st", shard="s00001", rows="0:8")
    root, sel = parse_store_uri(uri)
    assert root == "/tmp/st"
    assert sel == {"shard": "s00001", "rows": "0:8"}
    root2, sel2 = parse_store_uri(make_store_uri("/tmp/st"))
    assert (root2, sel2) == ("/tmp/st", {})
    with pytest.raises(ValueError):
        parse_store_uri("file:///tmp/st")
    with pytest.raises(ValueError):
        parse_store_uri("store:///tmp/st#bogus=1")
    with pytest.raises(ValueError):
        parse_store_uri("store:///tmp/st#rows=0:4")   # rows needs shard


# ---------------------------------------------------------------------------
# Golden end-to-end fixture: raw -> organize -> archive -> store.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def golden(tmp_path_factory):
    root = tmp_path_factory.mktemp("store_golden")
    raw, org, arc = (str(root / d) for d in ("raw", "org", "arc"))
    write_scaled_dataset(raw, ScaledDatasetSpec(name="g", n_files=4,
                                                scale=1e4))
    reg = synthetic_registry(n=2000, seed=13)
    organizer = Organizer(org, reg)
    for t in organize_tasks_from_dir(raw):
        organizer(t)
    archiver = Archiver(org, arc)
    for t in archive_tasks_from_tree(org):
        archiver(t)
    store_root = str(root / "store")
    manifest = build_store(arc, store_root, target_points=2048)
    return {"arc": arc, "store": store_root, "manifest": manifest,
            "root": str(root)}


def test_store_build_deterministic(golden):
    """Same-seed builds are byte-identical: manifest AND shard files."""
    rebuild = os.path.join(golden["root"], "store_rebuild")
    m2 = build_store(golden["arc"], rebuild, target_points=2048)
    assert golden["manifest"].canonical_bytes() == m2.canonical_bytes()
    for s in golden["manifest"].shards:
        with open(os.path.join(golden["store"], s.filename), "rb") as a, \
                open(os.path.join(rebuild, s.filename), "rb") as b:
            assert a.read() == b.read()


def test_manifest_index_matches_payload(golden):
    """seg_knots/seg_grid in the index == what a live parse computes."""
    from repro.tracks.segments import segment_shape
    store = TrackStore(golden["store"])
    for rec in golden["manifest"].tracks:
        obs = read_observations(
            os.path.join(golden["arc"], rec.track_id))
        assert rec.n_obs == len(obs["time"])
        shapes = [segment_shape(obs["time"], s)
                  for s in split_segments(obs["time"])]
        assert rec.seg_knots == tuple(n for n, _ in shapes)
        assert rec.seg_grid == tuple(m for _, m in shapes)
    # and the store-read payload is bitwise what the zip parse yields
    for rec in golden["manifest"].tracks[:3]:
        zip_obs = read_observations(
            os.path.join(golden["arc"], rec.track_id))
        st_obs = store.read_track(rec.track_id)
        for col in ("time", "lat", "lon", "alt"):
            assert np.array_equal(zip_obs[col], st_obs[col])
        assert [str(x) for x in zip_obs["icao24"]] == \
            [str(x) for x in st_obs["icao24"]]


def test_bucket_histogram_from_index(golden):
    """Index-driven bucket binning == the fused batcher's own binning."""
    from repro.tracks.segments import bucket_width
    proc = SegmentProcessor()
    widths: dict[int, int] = {}
    for rec in golden["manifest"].tracks:
        obs = read_observations(
            os.path.join(golden["arc"], rec.track_id))
        for r in proc._records([(obs, split_segments(obs["time"]))]):
            widths[r.width] = widths.get(r.width, 0) + 1
    assert golden["manifest"].bucket_histogram() == widths
    # plan() exposes the same histogram per shard, no payload touched
    plans = TrackStore(golden["store"]).plan()
    merged: dict[int, int] = {}
    for p in plans:
        for w, c in p.bucket_histogram.items():
            merged[w] = merged.get(w, 0) + c
    assert merged == widths
    assert all(w == bucket_width(w) for w in merged)


def test_store_vs_zip_process_batch_bitwise(golden):
    """THE golden gate: store-backed process_batch == zip-backed,
    bitwise, on every output plane."""
    ztasks = segment_tasks_from_archive_tree(golden["arc"])
    ttasks = segment_tasks_from_store(golden["store"],
                                      granularity="track")
    assert [t.task_id.replace(os.sep, "/") for t in ztasks] == \
        [t.task_id for t in ttasks]
    proc = SegmentProcessor()
    bz = proc.process_batch(ztasks)
    bs = proc.process_batch(ttasks)
    assert len(bz) == len(bs) == len(ztasks)
    for t in ztasks:
        rz, rs = bz[t.task_id], bs[t.task_id.replace(os.sep, "/")]
        assert rz.icao24 == rs.icao24
        assert rz.airspace == rs.airspace
        np.testing.assert_array_equal(rz.count, rs.count)
        for f in PLANE_FIELDS:
            np.testing.assert_array_equal(getattr(rz, f),
                                          getattr(rs, f), err_msg=f)


def test_shard_tasks_and_process_store_agree(golden):
    """Shard-granularity tasks and the prefetching process_store loop
    produce the same per-track results as track-granularity tasks."""
    proc = SegmentProcessor()
    per_track = proc.process_batch(
        segment_tasks_from_store(golden["store"], granularity="track"))
    via_shards: dict = {}
    for res in proc.process_batch(
            segment_tasks_from_store(golden["store"],
                                     granularity="shard")).values():
        via_shards.update(res)
    via_stream = proc.process_store(golden["store"], prefetch=2)
    assert set(per_track) == set(via_shards) == set(via_stream)
    for tid in per_track:
        for other in (via_shards[tid], via_stream[tid]):
            np.testing.assert_array_equal(per_track[tid].count,
                                          other.count)
            for f in PLANE_FIELDS:
                np.testing.assert_array_equal(
                    getattr(per_track[tid], f), getattr(other, f),
                    err_msg=f)


def test_iter_batches_prefetch_equivalence(golden):
    """prefetch=0 and prefetch=2 stream identical content/order."""
    store = TrackStore(golden["store"])
    sync = list(store.iter_batches(prefetch=0))
    pre = list(store.iter_batches(prefetch=2))
    assert [b.shard_id for b in sync] == [b.shard_id for b in pre]
    for a, b in zip(sync, pre):
        assert a.track_ids == b.track_ids
        for (obs_a, segs_a), (obs_b, segs_b) in zip(a.items, b.items):
            assert segs_a == segs_b
            for col in ("time", "lat", "lon", "alt"):
                assert np.array_equal(obs_a[col], obs_b[col])


def test_row_range_selection(golden):
    store = TrackStore(golden["store"])
    sid = golden["manifest"].shards[0].shard_id
    all_rows = store.read_selection({"shard": sid})
    part = store.read_selection({"shard": sid, "rows": "1:3"})
    assert [tid for tid, _, _ in part] == \
        [tid for tid, _, _ in all_rows][1:3]
    with pytest.raises(ValueError):
        store.read_selection({"shard": sid, "rows": "0:9999"})
    with pytest.raises(KeyError):
        store.read_selection({"shard": "nope"})


def test_prefetch_error_reaches_slow_consumer(golden):
    """A decode error in the prefetch thread must surface even when the
    consumer holds the (size-1) queue full — the producer retries the
    terminal event instead of dropping it (deadlock bug).  The slow
    consumer is driven by the reader's prefetch hooks, not sleeps: the
    test only resumes draining once the producer has verifiably blocked
    trying to enqueue the error, so the retry path runs on every
    machine, deterministically."""
    import threading
    root = os.path.join(golden["root"], "store_pershard")
    build_store(golden["arc"], root, target_points=1)
    manifest = StoreManifest.load(root)
    assert len(manifest.shards) >= 3
    path = os.path.join(root, manifest.shards[2].filename)
    blob = bytearray(open(path, "rb").read())
    blob[-1] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(blob))
    store = TrackStore(root)
    err_blocked = threading.Event()
    store.prefetch_hooks = {
        "blocked": lambda kind: (err_blocked.set() if kind == "err"
                                 else None)}
    got = []
    with pytest.raises(ShardFormatError):
        it = store.iter_batches(store.plan(), prefetch=1)
        # Shard 0 in hand, shard 1 filling the size-1 queue; the
        # producer hits the corrupt shard 2 and must now retry the
        # "err" event against the full queue.
        got.append(next(it).shard_id)
        assert err_blocked.wait(timeout=30.0), \
            "producer never blocked on the terminal error event"
        for batch in it:
            got.append(batch.shard_id)
    assert got == [s.shard_id for s in manifest.shards[:2]]


def test_live_iter_batches_invalidates_warm_prefetch_on_append(golden):
    """Regression: a warm prefetch must not pin a live iteration to a
    stale manifest.  Appending a shard (``commit_shard``) and
    ``reload()``-ing mid-iteration advances the generation; the live
    iterator must drop in-flight buffers decoded under the old
    generation, re-plan from the fresh index, and still yield every
    shard — the appended one included — exactly once."""
    import threading
    from repro.store.writer import ShardBuilder, commit_shard

    sources = discover_sources(golden["arc"])
    plans = plan_shards(sources, target_points=1)
    assert len(plans) >= 3
    root = os.path.join(golden["root"], "store_live")
    build = ShardBuilder(root)
    results = [build(Task(task_id=p.shard_id, payload=p.dumps()))
               for p in plans]
    for r in results[:-1]:
        commit_shard(root, r, target_points=1)
    store = TrackStore(root)
    gen0 = store.generation
    assert gen0 == len(plans) - 1
    queued_next = threading.Event()
    store.prefetch_hooks = {
        "queued": lambda kind, sid: (queued_next.set()
                                     if kind == "ok"
                                     and sid != plans[0].shard_id
                                     else None)}
    seen = []
    appended = False
    for batch in store.iter_batches(prefetch=1):
        seen.append(batch.shard_id)
        if not appended:
            # A warm buffer is verifiably in flight; now append.
            assert queued_next.wait(timeout=30.0)
            commit_shard(root, results[-1], target_points=1)
            assert store.reload()
            appended = True
    assert store.generation == gen0 + 1
    assert sorted(seen) == [p.shard_id for p in plans]
    assert len(seen) == len(set(seen))
    assert store.stats["stale_drops"] >= 1
    # Explicit plans stay pinned: appends never leak into them.
    store2 = TrackStore(root)
    pinned = [b.shard_id
              for b in store2.iter_batches(store2.plan()[:1], prefetch=1)]
    assert pinned == [plans[0].shard_id]


class _TickClock:
    """Fake monotonic clock: advances one unit per reading."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


def test_reader_stats_use_injected_clock(golden):
    """Exact decode_s/wait_s attribution under a fake monotonic clock —
    the timing stats must flow through the injected clock only, so
    tests assert exact values instead of flaky wall-time ratios."""
    clock = _TickClock()
    store = TrackStore(golden["store"], clock=clock)
    n = len(list(store.iter_batches(prefetch=0)))
    assert n == len(golden["manifest"].shards) > 0
    # one clock-step per decode, no consumer blocking measured
    assert store.stats["decode_s"] == pytest.approx(float(n))
    assert store.stats["wait_s"] == 0.0
    # frozen clock: every timing stat stays exactly zero, prefetch too
    frozen = TrackStore(golden["store"], clock=lambda: 0.0)
    assert len(list(frozen.iter_batches(prefetch=2))) == n
    assert frozen.stats["decode_s"] == 0.0
    assert frozen.stats["wait_s"] == 0.0


def test_corrupted_shard_detected_through_reader(golden):
    """Bit rot in a shard file surfaces as ShardChecksumError, also
    through the prefetch thread."""
    import shutil
    broken_root = os.path.join(golden["root"], "store_broken")
    shutil.copytree(golden["store"], broken_root)
    manifest = StoreManifest.load(broken_root)
    path = os.path.join(broken_root, manifest.shards[0].filename)
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(blob))
    store = TrackStore(broken_root)
    with pytest.raises(ShardFormatError):
        list(store.iter_batches(prefetch=0))
    with pytest.raises(ShardFormatError):
        list(store.iter_batches(prefetch=2))


def test_plan_shards_respects_target_and_order(golden):
    sources = discover_sources(golden["arc"])
    assert sources == sorted(sources, key=lambda s: s[0])
    plans = plan_shards(sources, target_points=1)   # one track per shard
    assert len(plans) == len(sources)
    assert [p.shard_id for p in plans] == \
        [f"s{i:05d}" for i in range(len(plans))]
    one = plan_shards(sources, target_points=10 ** 12)
    assert len(one) == 1
    assert [t for t, _ in one[0].sources] == [s[0] for s in sources]


# ---------------------------------------------------------------------------
# Workflow integration: the store-build phase.
# ---------------------------------------------------------------------------

def test_workflow_store_build_phase(tmp_path):
    from repro.tracks.workflow import TrackWorkflow
    wf = TrackWorkflow(str(tmp_path), n_workers=2, poll_interval=0.003,
                       input="store", store_target_points=2048,
                       tasks_per_message=2)
    wf.generate_raw(n_files=3, scale=2e4)
    reports = wf.run()
    assert [r.phase for r in reports] == \
        ["organize", "archive", "store-build", "process"]
    assert all(r.tasks > 0 for r in reports)
    manifest = StoreManifest.load(wf.store_dir)
    assert manifest.tracks and manifest.shards
    # resume skips every completed phase
    wf2 = TrackWorkflow(str(tmp_path), n_workers=2, input="store")
    assert wf2.run() == []


def test_workflow_store_build_resumes_past_checkpointed_shards(tmp_path):
    """Shard tasks completed before a mid-phase kill are excluded from
    re-dispatch by the restored manager; finalize must still index them
    (regression: KeyError on every pre-kill shard)."""
    from repro.runtime import ManagerCheckpoint
    from repro.store.writer import ShardBuilder
    from repro.tracks.workflow import TrackWorkflow

    wfz = TrackWorkflow(str(tmp_path), n_workers=2, poll_interval=0.003)
    wfz.generate_raw(n_files=3, scale=2e4)
    wfz.run()                      # organize + archive + (zip) process
    sources = discover_sources(wfz.archive_dir)
    plans = plan_shards(sources, target_points=1)
    assert len(plans) >= 2
    # shard 0 "completed before the kill": file committed, records lost
    store_dir = str(tmp_path / "store")
    done_task = Task(task_id=f"store/{plans[0].shard_id}",
                     payload=plans[0].dumps())
    ShardBuilder(store_dir)(done_task)
    with open(wfz.ckpt_path) as f:
        state = json.load(f)
    state["manager_phase"] = "store-build"
    state["manager"] = ManagerCheckpoint({done_task.task_id}, []).dumps()
    with open(wfz.ckpt_path, "w") as f:
        json.dump(state, f)

    wfs = TrackWorkflow(str(tmp_path), n_workers=2, poll_interval=0.003,
                        input="store", store_target_points=1)
    reports = wfs.run()
    assert [r.phase for r in reports] == ["store-build"]
    manifest = StoreManifest.load(store_dir)
    assert [s.shard_id for s in manifest.shards] == \
        [p.shard_id for p in plans]


def test_commit_shard_recommit_is_idempotent(golden):
    """A worker killed between the per-shard manifest append and the
    manager checkpoint save is re-dispatched the same shard task on
    resume: the second ``commit_shard`` of the same shard_id must not
    duplicate the manifest row, orphan a shard file, or change bytes."""
    from repro.store.writer import (
        ShardBuilder, commit_shard, finalize_manifest)

    sources = discover_sources(golden["arc"])
    plans = plan_shards(sources, target_points=1)
    assert len(plans) >= 2
    store_dir = os.path.join(golden["root"], "store_recommit")
    build = ShardBuilder(store_dir)
    results = [build(Task(task_id=f"store/{p.shard_id}",
                          payload=p.dumps())) for p in plans]
    for r in results:
        commit_shard(store_dir, r, target_points=1)
    first = StoreManifest.load(store_dir)
    shard0 = os.path.join(store_dir, first.shards[0].filename)
    blob0 = open(shard0, "rb").read()
    # the re-dispatched task rebuilds AND re-commits shard 0
    commit_shard(store_dir, build(
        Task(task_id=f"store/{plans[0].shard_id}",
             payload=plans[0].dumps())), target_points=1)
    again = StoreManifest.load(store_dir)
    assert [s.shard_id for s in again.shards] == \
        [p.shard_id for p in plans]                  # no duplicate row
    assert open(shard0, "rb").read() == blob0        # no byte churn
    on_disk = sorted(
        os.path.relpath(os.path.join(d, f), store_dir).replace(os.sep, "/")
        for d, _dirs, files in os.walk(store_dir) for f in files)
    assert on_disk == sorted(
        ["store_manifest.json"] + [s.filename for s in again.shards])
    manifest = finalize_manifest(store_dir, target_points=1)
    clean = build_store(golden["arc"],
                        os.path.join(golden["root"], "store_clean1"),
                        target_points=1)
    assert [s.to_doc() for s in manifest.shards] == \
        [s.to_doc() for s in clean.shards]
    assert [t.to_doc() for t in manifest.tracks] == \
        [t.to_doc() for t in clean.tracks]


def test_dag_store_build_recommits_unckpted_shard(tmp_path):
    """Workflow-level twin of the recommit test: a shard file + partial
    manifest row exist on disk but the (lost) checkpoint never recorded
    the task, so the streaming DAG re-runs it end to end.  The sealed
    store must equal a clean single-shot build — no duplicated or
    orphaned shard."""
    from repro.store.writer import ShardBuilder, commit_shard
    from repro.tracks.workflow import TrackWorkflow

    wfz = TrackWorkflow(str(tmp_path), n_workers=2, poll_interval=0.003)
    wfz.generate_raw(n_files=3, scale=2e4)
    wfz.run()                      # organize + archive + (zip) process
    sources = discover_sources(wfz.archive_dir)
    plans = plan_shards(sources, target_points=1)
    assert len(plans) >= 2
    store_dir = str(tmp_path / "store")
    commit_shard(store_dir, ShardBuilder(store_dir)(
        Task(task_id=f"store/{plans[0].shard_id}",
             payload=plans[0].dumps())), target_points=1)

    wfd = TrackWorkflow(str(tmp_path), n_workers=2, poll_interval=0.003,
                        input="store", store_target_points=1, mode="dag")
    reports = wfd.run()
    assert [r.phase for r in reports] == ["dag"]
    manifest = StoreManifest.load(store_dir)
    assert manifest.meta.get("partial") is None      # sealed
    clean = build_store(wfz.archive_dir, str(tmp_path / "store_clean"),
                        target_points=1)
    assert [s.to_doc() for s in manifest.shards] == \
        [s.to_doc() for s in clean.shards]
    assert [t.to_doc() for t in manifest.tracks] == \
        [t.to_doc() for t in clean.tracks]
    for s in manifest.shards:
        with open(os.path.join(store_dir, s.filename), "rb") as a, \
                open(os.path.join(str(tmp_path / "store_clean"),
                                  s.filename), "rb") as b:
            assert a.read() == b.read()


# ---------------------------------------------------------------------------
# Archiver crash-safety (satellite).
# ---------------------------------------------------------------------------

def test_archiver_cleans_orphaned_tmp(tmp_path):
    src_root = tmp_path / "org" / "2019" / "L2J" / "150" / "b0" / "abc123"
    src_root.mkdir(parents=True)
    (src_root / "abc123.csv").write_text("time,icao24\n1,abc123\n")
    arc_root = str(tmp_path / "arc")
    arch = Archiver(str(tmp_path / "org"), arc_root)
    rel = "2019/L2J/150/b0/abc123"
    # a killed worker's leftovers, both legacy and pid-suffixed
    parent = os.path.join(arc_root, "2019", "L2J", "150", "b0")
    os.makedirs(parent, exist_ok=True)
    zip_path = os.path.join(parent, "abc123.zip")
    for stale in (zip_path + ".tmp", zip_path + ".tmp.99999"):
        with open(stale, "w") as f:
            f.write("garbage from a dead worker")
    res = arch.archive_dir(rel)
    assert res.files == 1
    leftovers = [n for n in os.listdir(parent) if ".tmp" in n]
    assert leftovers == []
    with zipfile.ZipFile(zip_path) as zf:      # committed zip is valid
        assert zf.namelist() == ["abc123.csv"]


# ---------------------------------------------------------------------------
# Token shards on store primitives (satellite).
# ---------------------------------------------------------------------------

def test_token_shards_are_store_shards(tmp_path):
    from repro.data.pipeline import (
        SelfScheduledLoader, synthetic_token_shards,
        token_shard_manifests)
    shards = synthetic_token_shards(str(tmp_path), n_shards=4,
                                    tokens_per_shard_mean=4096, seed=3)
    # one shard-manifest implementation: the on-disk index IS a store
    # manifest, and reopening it yields the same loader views
    reopened = token_shard_manifests(str(tmp_path))
    assert reopened == shards
    cols, meta = codec.read_shard(shards[0].path)
    assert meta["shard_id"] == shards[0].shard_id
    assert cols["tokens"].dtype == np.int32
    assert len(cols["tokens"]) == shards[0].n_tokens
    loader = SelfScheduledLoader(shards, batch_size=2, seq_len=32,
                                 n_ingest_workers=2, poll_interval=0.003)
    batch = next(iter(loader))
    assert batch["tokens"].shape == (2, 32)
    # corruption fails the ingest job loudly
    blob = bytearray(open(shards[1].path, "rb").read())
    blob[-1] ^= 0xFF
    with open(shards[1].path, "wb") as f:
        f.write(bytes(blob))
    with pytest.raises(RuntimeError, match="failed"):
        SelfScheduledLoader(shards, batch_size=2, seq_len=32,
                            n_ingest_workers=2, poll_interval=0.003)


# ---------------------------------------------------------------------------
# Row blocks and row-group units: range decodes, grouped reads, the
# version-1 and version-2 layouts.
# ---------------------------------------------------------------------------

from _blockstore import (  # noqa: E402
    SMALL_BLOCK, build_block_store, encode_version2)

_POINT_COLS = ("time", "lat", "lon", "alt", "icao_codes")


def _full_decode(store_root, shard):
    cols, meta = codec.read_shard(os.path.join(store_root, shard.filename))
    return cols, meta


def _block_case(store, manifest, case):
    """(shard, tracks) for one case: a track inside one block, a track
    across a block edge, or every track of a shard."""
    shard = manifest.shards[1]
    rows = manifest.tracks_in(shard.shard_id)
    if case == "whole_shard":
        return shard, rows
    cols, _ = _full_decode(store, shard)
    off = cols["offsets"]
    for t in rows:
        lo, hi = int(off[t.row]), int(off[t.row + 1])
        across = lo // SMALL_BLOCK != (hi - 1) // SMALL_BLOCK
        if across == (case == "across_blocks"):
            return shard, [t]
    raise AssertionError(f"no track for {case}")


@pytest.mark.parametrize("case", ["inside_one_block", "across_blocks",
                                  "whole_shard"])
def test_block_reads_equal_slices_of_a_full_decode(block_store, case):
    """A track's rows decoded from its blocks == the slice of the whole
    shard's decode, bit for bit, from exactly the covering blocks."""
    from repro.obs import Tracer
    manifest = StoreManifest.load(block_store)
    shard, tracks = _block_case(block_store, manifest, case)
    cols, meta = _full_decode(block_store, shard)
    off = cols["offsets"]
    tr = Tracer()
    store = TrackStore(block_store, tracer=tr)
    got = store.read_tracks([t.track_id for t in tracks])
    want_blocks = set()
    for t in tracks:
        lo, hi = int(off[t.row]), int(off[t.row + 1])
        obs, segs = got[t.track_id]
        for name in ("time", "lat", "lon", "alt"):
            assert obs[name].dtype == cols[name].dtype
            assert obs[name].tobytes() == cols[name][lo:hi].tobytes()
        names = np.asarray(meta["icao_values"])[cols["icao_codes"][lo:hi]]
        np.testing.assert_array_equal(obs["icao24"], names)
        assert segs == split_segments(cols["time"][lo:hi])
        want_blocks |= set(range(lo // SMALL_BLOCK,
                                 (hi - 1) // SMALL_BLOCK + 1))
        # range decodes of the codec itself
        part, _ = codec.read_shard(
            os.path.join(block_store, shard.filename),
            columns=list(_POINT_COLS), rows=(lo, hi))
        for name in _POINT_COLS:
            assert part[name].tobytes() == cols[name][lo:hi].tobytes()
    (span,) = [e for e in tr.events if e[2] == "store_decode"]
    extra = span[6]
    assert extra["blocks"] == len(want_blocks)
    assert extra["obs"] == sum(t.n_obs for t in tracks)
    assert extra["obs_decoded"] == sum(
        min(SMALL_BLOCK, len(cols["time"]) - b * SMALL_BLOCK)
        for b in want_blocks)
    assert 0 < extra["bytes"] <= shard.size_bytes
    if case != "whole_shard":
        assert extra["obs_decoded"] < len(cols["time"])
        assert extra["bytes"] < shard.size_bytes // 4


def _encode_version1(columns, meta):
    """The version-1 writer: one block per column, scalar block keys."""
    import json as _json
    import zlib
    entries, blocks = [], []
    for name in sorted(columns):
        arr = np.ascontiguousarray(columns[name])
        raw = arr.tobytes()
        enc = zlib.compress(raw, codec.ZLIB_LEVEL)
        c = "zlib"
        if len(enc) >= len(raw):
            enc, c = raw, "none"
        entries.append({"name": name, "dtype": arr.dtype.str,
                        "shape": list(arr.shape), "codec": c,
                        "raw_bytes": len(raw), "enc_bytes": len(enc),
                        "crc32": zlib.crc32(raw) & 0xFFFFFFFF})
        blocks.append(enc)
    hdr = _json.dumps({"version": 1, "columns": entries, "meta": meta},
                      sort_keys=True, separators=(",", ":")).encode()
    return (codec.MAGIC + (1).to_bytes(4, "little")
            + len(hdr).to_bytes(8, "little")
            + (zlib.crc32(hdr) & 0xFFFFFFFF).to_bytes(4, "little")
            + hdr + b"".join(blocks))


def test_single_block_version1_shard_still_decodes(tmp_path):
    """A shard whose columns are single blocks (the version-1 layout)
    decodes whole and by row range, also through the reader."""
    store = build_block_store(str(tmp_path), n_tracks=30, seed=5,
                              target_points=10 ** 6)
    manifest = StoreManifest.load(store)
    (shard,) = manifest.shards
    path = os.path.join(store, shard.filename)
    cols, meta = codec.read_shard(path)
    old = _encode_version1(cols, meta)
    dec, meta1 = codec.decode_shard(old)
    assert meta1 == meta
    for name, arr in cols.items():
        assert dec[name].dtype == arr.dtype
        assert dec[name].tobytes() == arr.tobytes()
    part, _ = codec.decode_shard(old, columns=["lat"], rows=(100, 300))
    assert part["lat"].tobytes() == cols["lat"][100:300].tobytes()
    want = TrackStore(store).read_tracks(
        [t.track_id for t in manifest.tracks])
    with open(path, "wb") as f:
        f.write(old)
    got = TrackStore(store).read_tracks(
        [t.track_id for t in manifest.tracks])
    for tid, (obs, segs) in want.items():
        assert got[tid][1] == segs
        for name, arr in obs.items():
            np.testing.assert_array_equal(got[tid][0][name], arr)


def test_grouped_read_equals_read_track_and_decodes_each_block_once(
        block_store, monkeypatch):
    """read_tracks == read_track per id, bit for bit; each (shard,
    unit) is inflated once, in (shard, unit) order, for all the point
    columns; no returned array is a view of a decoded unit."""
    manifest = StoreManifest.load(block_store)
    rng = np.random.default_rng(3)
    ids = [manifest.tracks[i].track_id for i in rng.permutation(
        len(manifest.tracks))[:120]]
    store = TrackStore(block_store)
    per_id = {tid: store.read_track(tid) for tid in ids}
    seen = []
    orig = codec.ShardView._units

    def counted(view, grp, need, wanted):
        for u, raw in orig(view, grp, need, wanted):
            seen.append((view.meta["shard_id"],
                         tuple(c.name for c in grp.columns), u))
            yield u, raw

    monkeypatch.setattr(codec.ShardView, "_units", counted)
    got = store.read_tracks(ids)
    assert set(got) == set(ids)
    assert len(seen) == len(set(seen))
    shard_order = [s for s, _, _ in seen]
    assert shard_order == sorted(shard_order)
    units = [(s, i) for s, names, i in seen if names != ("offsets",)]
    assert units == sorted(units)
    assert {names for _, names, _ in seen} == {("offsets",),
                                               tuple(sorted(_POINT_COLS))}
    served: dict = {}
    for tid in ids:
        t = manifest.track(tid)
        served[t.shard_id] = served.get(t.shard_id, 0) + t.n_obs
    for tid in ids:
        obs, segs = got[tid]
        assert segs == split_segments(per_id[tid]["time"])
        for name, arr in obs.items():
            # a slice of one array that holds only the shard's served
            # rows, never a view of a decoded block
            owner = arr if arr.base is None else arr.base
            assert isinstance(owner, np.ndarray) and owner.base is None
            assert len(owner) <= served[manifest.track(tid).shard_id]
            assert arr.dtype == per_id[tid][name].dtype
            assert arr.tobytes() == per_id[tid][name].tobytes()


def test_block_store_rebuild_is_byte_identical(tmp_path):
    """Shards of many units: a rebuild gives the same shard bytes and
    manifest, and the per-point columns are one row group of units."""
    a = build_block_store(str(tmp_path / "a"), n_tracks=80, seed=2)
    b = build_block_store(str(tmp_path / "b"), n_tracks=80, seed=2)
    ma, mb = StoreManifest.load(a), StoreManifest.load(b)
    assert ma.shards == mb.shards and ma.tracks == mb.tracks
    for s in ma.shards:
        with open(os.path.join(a, s.filename), "rb") as fa, \
                open(os.path.join(b, s.filename), "rb") as fb:
            assert fa.read() == fb.read()
        with codec.ShardView(os.path.join(a, s.filename)) as view:
            grp = view.columns["time"].group
            assert [c.name for c in grp.columns] == sorted(_POINT_COLS)
            assert all(view.columns[n].group is grp for n in _POINT_COLS)
            assert grp.block_rows == min(SMALL_BLOCK, s.n_points)
            assert len(grp.units) == -(-s.n_points // SMALL_BLOCK)
            assert grp.row_bytes == 4 * 8 + 4


@settings(max_examples=10)
@given(st.integers(min_value=0, max_value=700),
       st.integers(min_value=1, max_value=100),
       st.integers(min_value=0, max_value=10 ** 6))
def test_codec_blocks_roundtrip_and_row_ranges(n, block_rows, seed):
    """Any block size: whole decodes are bitwise equal, the encoding is
    canonical, and every row range equals the slice of the whole."""
    rng = np.random.default_rng(seed)
    cols = {"f": _column("<f8", seed, n), "u": _column("<u4", seed + 1, n),
            "m": rng.standard_normal((n, 3)).astype("<f4")}
    data = codec.encode_shard(cols, meta={"n": n}, block_rows=block_rows)
    assert data == codec.encode_shard(cols, meta={"n": n},
                                      block_rows=block_rows)
    whole, meta = codec.decode_shard(data)
    assert meta == {"n": n}
    for name, arr in cols.items():
        assert whole[name].shape == arr.shape
        assert whole[name].tobytes() == arr.tobytes()
    for _ in range(4):
        lo = int(rng.integers(0, n + 1))
        hi = int(rng.integers(lo, n + 1))
        part, _ = codec.decode_shard(data, rows=(lo, hi))
        for name, arr in cols.items():
            assert part[name].tobytes() == arr[lo:hi].tobytes()
    view = codec.ShardView(data)
    view.read("f", 0, min(n, 1))
    assert view.column_blocks.get("f", 0) == min(n, 1)


def _point_columns(n, seed):
    """The five point columns of ``n`` rows and an ``offsets`` column."""
    rng = np.random.default_rng(seed)
    cols = {name: rng.standard_normal(n).cumsum()
            for name in ("time", "lat", "lon", "alt")}
    cols["icao_codes"] = rng.integers(0, 7, n).astype(np.uint32)
    cols["offsets"] = np.arange(0, n + 1, 9, dtype=np.int64)
    return cols


def _range_case(case, n, rng):
    """(lo, hi) of one case of ranges over ``n`` rows (units of
    ``SMALL_BLOCK``; ``n`` ends in a partial unit)."""
    b = SMALL_BLOCK
    if case == "whole_shard":                  # one run of tracks in order
        cuts = np.unique(np.r_[0, rng.integers(0, n, 40), n])
        return cuts[:-1], cuts[1:]
    if case == "rows_at_unit_edges":
        lo = np.r_[[k * b + d for k in range(1, n // b + 1) for d in (-1, 0)],
                   n - 1, 0]
        return lo, lo + 1
    if case == "random_range_sets":
        lo = rng.integers(0, n, 30)
        return lo, np.minimum(lo + rng.integers(0, 3 * b, 30), n)
    if case == "partial_last_unit":
        last = n // b * b
        return (np.array([last, n - 10, last - 5, n - 1]),
                np.array([n, n, n, n]))
    assert case == "empty_ranges"
    return np.array([0, 5, 5, n, 70]), np.array([0, 5, 9, n, 70])


@pytest.mark.parametrize("case", ["whole_shard", "rows_at_unit_edges",
                                  "random_range_sets", "partial_last_unit",
                                  "empty_ranges"])
def test_grouped_reads_equal_version2_reads(case):
    """Row ranges of the point columns read from a version-3 shard (one
    row group) equal, bit for bit, the same reads of the version-2 layout
    and the slices of the columns; the version-3 read inflates each unit
    that holds a range once for all five columns, version 2 each block
    of each column."""
    n = 15 * SMALL_BLOCK + 40
    cols = _point_columns(n, seed=7)
    v3 = codec.encode_shard(cols, meta={}, block_rows=SMALL_BLOCK,
                            groups=[_POINT_COLS])
    v2 = encode_version2(cols, {}, SMALL_BLOCK)
    assert v2[8:12] == (2).to_bytes(4, "little")
    lo, hi = _range_case(case, n, np.random.default_rng(3))
    units = {r // SMALL_BLOCK for a, b in zip(lo, hi) for r in range(a, b)}
    views = [codec.ShardView(data) for data in (v3, v2)]
    reads = [view.read_columns(_POINT_COLS, lo, hi) for view in views]
    for name in _POINT_COLS:
        for got3, got2, a, b in zip(reads[0][name], reads[1][name], lo, hi):
            assert got3.dtype == got2.dtype == cols[name].dtype
            assert got3.tobytes() == got2.tobytes() \
                == cols[name][a:b].tobytes()
    assert views[0].units == len(units)
    assert views[1].units == len(_POINT_COLS) * len(units)
    for view in views:
        assert view.column_blocks.get("time", 0) == len(units)
        assert view.rows.get("time", 0) == sum(
            min(SMALL_BLOCK, n - u * SMALL_BLOCK) for u in units)
    if case == "whole_shard":
        whole3, _ = codec.decode_shard(v3)
        whole2, _ = codec.decode_shard(v2)
        for name, arr in cols.items():
            assert whole3[name].tobytes() == whole2[name].tobytes() \
                == arr.tobytes()


def test_version2_store_still_decodes(tmp_path):
    """A store whose shards are rewritten in the version-2 layout reads
    the same tracks, with the same block counters; its reads count one
    unit per (column, block), five times the row group's."""
    from repro.obs import Tracer
    store = build_block_store(str(tmp_path), n_tracks=60, seed=4)
    manifest = StoreManifest.load(store)
    ids = [t.track_id for t in manifest.tracks][::3]
    runs = []
    for layout in (3, 2):
        if layout == 2:
            for s in manifest.shards:
                path = os.path.join(store, s.filename)
                cols, meta = codec.read_shard(path)
                with open(path, "wb") as f:
                    f.write(encode_version2(cols, meta, SMALL_BLOCK))
        tr = Tracer()
        got = TrackStore(store, tracer=tr).read_tracks(ids)
        runs.append((got, [e[6] for e in tr.events
                           if e[2] == "store_decode"]))
    (got3, spans3), (got2, spans2) = runs
    for tid in ids:
        assert got2[tid][1] == got3[tid][1]
        for name, arr in got3[tid][0].items():
            assert got2[tid][0][name].tobytes() == arr.tobytes()
    for e3, e2 in zip(spans3, spans2):
        for key in ("blocks", "obs", "obs_decoded"):
            assert e3[key] == e2[key]
        offsets_blocks = e3["units"] - e3["blocks"]
        assert offsets_blocks >= 1
        assert e2["units"] == 5 * e3["blocks"] + offsets_blocks


@settings(max_examples=10)
@given(st.integers(min_value=0, max_value=700),
       st.integers(min_value=1, max_value=100),
       st.integers(min_value=0, max_value=10 ** 6))
def test_codec_groups_roundtrip_and_row_ranges(n, block_rows, seed):
    """Any block size: a shard with a row group (of 1-D and 2-D columns)
    beside columns stored alone encodes canonically, whatever the order
    the groups are named in, decodes whole bit for bit, and every row
    range equals the slice of the whole."""
    rng = np.random.default_rng(seed)
    cols = {"f": _column("<f8", seed, n), "u": _column("<u4", seed + 1, n),
            "m": rng.standard_normal((n, 3)).astype("<f4"),
            "alone": _column("<i8", seed + 2, n + 1)}
    data = codec.encode_shard(cols, meta={"n": n}, block_rows=block_rows,
                              groups=[["u", "m", "f"]])
    assert data == codec.encode_shard(cols, meta={"n": n},
                                      block_rows=block_rows,
                                      groups=[("f", "m", "u")])
    whole, meta = codec.decode_shard(data)
    assert meta == {"n": n}
    for name, arr in cols.items():
        assert whole[name].shape == arr.shape
        assert whole[name].tobytes() == arr.tobytes()
    for _ in range(4):
        lo = int(rng.integers(0, n + 1))
        hi = int(rng.integers(lo, n + 1))
        part, _ = codec.decode_shard(data, columns=["f", "m", "u"],
                                     rows=(lo, hi))
        for name in ("f", "m", "u"):
            assert part[name].tobytes() == cols[name][lo:hi].tobytes()


@pytest.mark.parametrize("compression", ["zlib", "none"])
def test_corrupted_unit_raises_checksum_error(compression):
    """A flipped byte inside one unit fails every read of that unit with
    ShardChecksumError; the other units still read."""
    n = 6 * SMALL_BLOCK
    cols = _point_columns(n, seed=1)
    data = bytearray(codec.encode_shard(
        cols, compression=compression, block_rows=SMALL_BLOCK,
        groups=[_POINT_COLS]))
    grp = codec.ShardView(bytes(data)).columns["lat"].group
    off, _codec, _raw, enc, _crc = grp.units[2]
    data[off + enc // 2] ^= 0xFF
    broken = bytes(data)
    for name in _POINT_COLS:
        with pytest.raises(ShardChecksumError):
            codec.decode_shard(broken, columns=[name],
                               rows=(2 * SMALL_BLOCK + 3,
                                     2 * SMALL_BLOCK + 4))
    with pytest.raises(ShardChecksumError):
        codec.decode_shard(broken)
    part, _ = codec.decode_shard(broken, columns=list(_POINT_COLS),
                                 rows=(0, 2 * SMALL_BLOCK))
    for name in _POINT_COLS:
        assert part[name].tobytes() == cols[name][:2 * SMALL_BLOCK].tobytes()


@pytest.mark.parametrize("groups", [
    [("time", "offsets")],             # columns of different lengths
    [("time", "lat"), ("lat", "lon")],  # a column in two groups
    [("time", "speed")],               # a column the shard lacks
    [()],                              # an empty group
])
def test_ill_formed_group_is_refused(groups):
    cols = _point_columns(100, seed=2)
    with pytest.raises(ValueError):
        codec.encode_shard(cols, block_rows=SMALL_BLOCK, groups=groups)


def test_reads_inflate_each_unit_once(tmp_path, monkeypatch):
    """On a shard of 64 units: a whole-shard decode calls
    ``zlib.decompress`` once per unit and once per ``offsets`` block; a
    3-track random read exactly for the units that hold those tracks.
    The ``units`` counter of each ``store_decode`` span says the same."""
    import types
    import zlib

    from repro.obs import Tracer
    store = build_block_store(str(tmp_path), n_tracks=150, seed=19,
                              target_points=10 ** 6)
    manifest = StoreManifest.load(store)
    (shard,) = manifest.shards
    with codec.ShardView(os.path.join(store, shard.filename)) as view:
        grp = view.columns["time"].group
        n_offsets = len(view.columns["offsets"].group.units)
        assert all(u[1] == "zlib" for u in grp.units)
    assert len(grp.units) == 64
    calls = []

    def decompress(data, **kw):
        calls.append(len(data))
        return zlib.decompress(data, **kw)

    monkeypatch.setattr(codec, "zlib", types.SimpleNamespace(
        decompress=decompress, crc32=zlib.crc32, compress=zlib.compress,
        error=zlib.error))
    tr = Tracer()
    store_ = TrackStore(store, tracer=tr)
    whole = store_.read_shard_batch(shard.shard_id)
    assert len(calls) == 64 + n_offsets
    tracks = [manifest.tracks[i] for i in
              np.random.default_rng(5).choice(len(manifest.tracks), 3,
                                              replace=False)]
    off = np.asarray(codec.read_shard(os.path.join(store, shard.filename),
                                      columns=["offsets"])[0]["offsets"])
    calls.clear()
    got = store_.read_tracks([t.track_id for t in tracks])
    want = {u for t in tracks
            for u in range(off[t.row] // SMALL_BLOCK,
                           (off[t.row + 1] - 1) // SMALL_BLOCK + 1)}
    assert len(calls) == len(want) + n_offsets
    spans = [e[6] for e in tr.events if e[2] == "store_decode"]
    assert [e["units"] for e in spans] == [64 + n_offsets,
                                           len(want) + n_offsets]
    assert [e["blocks"] for e in spans] == [64, len(want)]
    for t in tracks:
        obs, _segs = got[t.track_id]
        (obs_whole, _), = [it for tid, it in zip(whole.track_ids,
                                                 whole.items)
                           if tid == t.track_id]
        for name, arr in obs_whole.items():
            assert obs[name].tobytes() == arr.tobytes()
