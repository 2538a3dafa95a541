"""Stage spans inside shard and cell tasks, and the workers' exec spans.

A traced threads ``run_job`` hands its tracer to the worker function
(``attach_tracer``) and binds each worker thread to its id and task ids,
so the store decode, segmentation, packing, device call and reassembly
of a shard task, and the member reads, row derivation and kernel of a
screen cell task, are spans under that task's id, inside its ``exec``
span.  Exec spans are the workers' own per-task timings, with the worker
thread's CPU seconds.
"""

import time

import jax.profiler
import pytest

from repro.core.messages import Task
from repro.obs import NULL_STAGE, Tracer, stage
from repro.runtime import run_job
from repro.store import build_store
from repro.tracks.archive import Archiver, archive_tasks_from_tree
from repro.tracks.datasets import ScaledDatasetSpec, write_scaled_dataset
from repro.tracks.organize import Organizer, organize_tasks_from_dir
from repro.tracks.registry import synthetic_registry
from repro.tracks.segments import SegmentProcessor, segment_tasks_from_store
from repro.tracks.workflow import TrackWorkflow

SEGMENT_STAGES = ("segments.records", "segments.pack", "segments.device",
                  "segments.reassemble")

#: Barrier screen settings of ``test_workflow_screen.py``: a few cells
#: with pairs in them.
SCREEN_KW = dict(
    input="store", store_target_points=2048, screen=True,
    screen_h_m=50_000.0, screen_v_m=1000.0, screen_cell_deg=1.0,
    n_workers=4, poll_interval=0.003)


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """A store of several small shards."""
    root = tmp_path_factory.mktemp("stage_store")
    raw, org, arc = (str(root / d) for d in ("raw", "org", "arc"))
    write_scaled_dataset(raw, ScaledDatasetSpec(name="s", n_files=2,
                                                scale=1e4))
    organizer = Organizer(org, synthetic_registry(n=2000, seed=13))
    for t in organize_tasks_from_dir(raw):
        organizer(t)
    archiver = Archiver(org, arc)
    for t in archive_tasks_from_tree(org):
        archiver(t)
    path = str(root / "store")
    manifest = build_store(arc, path, target_points=200)
    assert len(manifest.shards) >= 3
    return path


@pytest.fixture(scope="module")
def wide_store(tmp_path_factory):
    """One shard of one scaled hourly file: about a hundred tracks."""
    root = tmp_path_factory.mktemp("wide_store")
    raw, org, arc = (str(root / d) for d in ("raw", "org", "arc"))
    write_scaled_dataset(raw, ScaledDatasetSpec(name="w", n_files=1,
                                                scale=5e2))
    organizer = Organizer(org, synthetic_registry(n=2000, seed=13))
    for t in organize_tasks_from_dir(raw):
        organizer(t)
    archiver = Archiver(org, arc)
    for t in archive_tasks_from_tree(org):
        archiver(t)
    path = str(root / "store")
    assert len(build_store(arc, path, target_points=10 ** 6).shards) == 1
    return path


@pytest.fixture(scope="module")
def screen_trace(tmp_path_factory):
    """Events of a traced barrier-screen workflow."""
    tr = Tracer()
    wf = TrackWorkflow(str(tmp_path_factory.mktemp("stage_screen")),
                       tracer=tr, **SCREEN_KW)
    wf.generate_raw(n_files=1, scale=1e3)
    wf.run()
    assert tr.dropped == 0
    return tr.events


def _spans(events, name):
    return [e for e in events if e[2] == name and e[1] >= 0.0]


def _inside(span, windows, slack=1e-6):
    return any(a - slack <= span[0] and span[0] + span[1] <= b + slack
               for a, b in windows)


@pytest.mark.parametrize("tasks_per_message", [1, 3])
def test_shard_task_stages_lie_inside_their_exec_span(store,
                                                      tasks_per_message):
    tr = Tracer()
    tasks = segment_tasks_from_store(store, granularity="shard")
    proc = SegmentProcessor()
    run_job(tasks, proc, backend="threads", n_workers=2,
            tasks_per_message=tasks_per_message, tracer=tr)
    assert proc.tracer is None                 # detached after the job
    events = tr.events
    execs = {e[5]: e for e in _spans(events, "exec")}
    assert set(execs) == {t.task_id for t in tasks}
    stages = [e for e in events
              if e[2] in ("store_decode",) + SEGMENT_STAGES]
    for name in ("store_decode",) + SEGMENT_STAGES:
        covered = set()
        for e in stages:
            if e[2] == name:
                covered.update(e[6].get("tasks", (e[5],)))
        assert covered == set(execs), name
    for e in stages:
        assert e[4] == execs[e[5]][4] and str(e[4]).startswith("w")
        ids = e[6].get("tasks", (e[5],))
        assert len(ids) > 1 or tasks_per_message == 1
        window = (min(execs[t][0] for t in ids),
                  max(execs[t][0] + execs[t][1] for t in ids))
        assert _inside(e, [window]), e
    # Stage spans of one worker are siblings: none overlaps the next.
    for w in {e[4] for e in stages}:
        row = sorted((e for e in stages if e[4] == w), key=lambda e: e[0])
        assert all(a[0] + a[1] <= b[0] + 1e-9 for a, b in zip(row, row[1:]))


def test_device_counters_sum_to_last_stats(store):
    tr = Tracer()
    proc = SegmentProcessor()
    assert proc.attach_tracer(tr) is None
    proc.process_batch(segment_tasks_from_store(store, granularity="shard"))
    dev = _spans(tr.events, "segments.device")
    assert len(dev) == proc.last_stats["pipeline_calls"]
    assert sum(e[6]["valid"] for e in dev) \
        == proc.last_stats["valid_points"]
    assert sum(e[6]["allocated"] for e in dev) \
        == proc.last_stats["allocated_points"]
    pack = _spans(tr.events, "segments.pack")
    assert sum(e[6]["rows"] for e in pack) == proc.last_stats["n_segments"]


def test_reassemble_span_counts_whole_bucket_gathers(wide_store):
    """A shard task of many tracks: its reassembly span carries the
    segments it reassembled and the gather-assignments it made, at most
    one per pair of source bucket and width group."""
    tr = Tracer()
    tasks = segment_tasks_from_store(wide_store, granularity="shard")
    proc = SegmentProcessor()
    r = run_job(tasks, proc, backend="threads", n_workers=1, tracer=tr)
    (span,) = _spans(tr.events, "segments.reassemble")
    assert span[5] == tasks[0].task_id
    segments, gathers = span[6]["segments"], span[6]["gathers"]
    assert segments == proc.last_stats["n_segments"]
    tracks = [ps for res in r.results.values() for ps in res.values()
              if len(ps)]
    assert len(tracks) > 1
    groups = len({ps.lat.shape[1] for ps in tracks})
    buckets = proc.last_stats["pipeline_calls"]
    assert buckets <= gathers <= buckets * groups
    assert 10 * gathers <= segments


def test_screen_pass_stages_under_cell_tasks(screen_trace):
    events = screen_trace
    execs = {e[5]: e for e in _spans(events, "exec")
             if str(e[5]).startswith("screen/")}
    assert execs
    for name in ("screen.rows", "screen.kernel", "store_decode")  \
            + SEGMENT_STAGES:
        under = [e for e in _spans(events, name)
                 if str(e[5]).startswith("screen/")]
        assert under, name
        for e in under:
            x = execs[e[5]]
            assert e[4] == x[4] and _inside(e, [(x[0], x[0] + x[1])]), e
    kernels = [e for e in _spans(events, "screen.kernel")
               if str(e[5]).startswith("screen/")]
    assert len(kernels) == len(execs)
    assert sum(e[6]["pairs"] for e in kernels) > 0
    # The plan runs outside any task: its spans carry no task id.
    for name in ("screen.plan.rows", "screen.plan.bin"):
        plan = _spans(events, name)
        assert plan and all(e[5] is None for e in plan), name
    assert _spans(events, "screen.plan.bin")[0][6]["cells"] > 0


class _CountingAnnotation:
    opened = 0

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        type(self).opened += 1
        return self

    def __exit__(self, *exc):
        return False


def test_untraced_job_emits_nothing_and_opens_no_annotation(store,
                                                            monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.opened = 0
    idle = Tracer()
    tasks = segment_tasks_from_store(store, granularity="shard")
    run_job(tasks, SegmentProcessor(), backend="threads", n_workers=2,
            tracer=None)
    assert len(idle) == 0 and idle.emitted == 0
    assert _CountingAnnotation.opened == 0
    assert stage(None, "segments.pack", "task") is NULL_STAGE
    # Traced, every stage span opened one annotation of its name.
    tr = Tracer()
    run_job(tasks, SegmentProcessor(), backend="threads", n_workers=2,
            tracer=tr)
    n_stages = sum(1 for e in tr.events
                   if e[2] in ("store_decode",) + SEGMENT_STAGES)
    assert n_stages > 0 and _CountingAnnotation.opened == n_stages


def _sleep_payload(task):
    time.sleep(float(task.payload))
    return task.task_id


def test_exec_spans_are_task_times_not_their_average():
    tasks = [Task(task_id=f"t{i:02d}", payload=str(s))
             for i, s in enumerate([0.01, 0.04] * 4)]
    tr = Tracer()
    run_job(tasks, _sleep_payload, backend="threads", n_workers=1,
            tasks_per_message=2, organization="chronological", tracer=tr)
    execs = _spans(tr.events, "exec")
    assert len(execs) == len(tasks)
    want = {t.task_id: float(t.payload) for t in tasks}
    for e in execs:
        assert want[e[5]] <= e[1] < want[e[5]] + 0.02, e
    # One worker: its spans follow one another without overlapping.
    execs.sort(key=lambda e: e[0])
    assert all(a[0] + a[1] <= b[0] for a, b in zip(execs, execs[1:]))


def _spin(task):
    t_end = time.thread_time() + 0.005
    x = 0
    while time.thread_time() < t_end:
        x += 1
    return x


class _SpinBatch:
    def __call__(self, task):
        return _spin(task)

    def process_batch(self, tasks):
        return {t.task_id: _spin(t) for t in tasks}


@pytest.mark.parametrize("fn", [_spin, _SpinBatch()],
                         ids=["per_task", "batched"])
def test_exec_spans_carry_worker_cpu(fn):
    tr = Tracer()
    tasks = [Task(task_id=f"c{i}") for i in range(8)]
    run_job(tasks, fn, backend="threads", n_workers=2, tasks_per_message=4,
            tracer=tr)
    execs = _spans(tr.events, "exec")
    assert len(execs) == len(tasks)
    assert all(e[6]["worker_cpu"] > 0.0 for e in execs)
    assert sum(e[6]["worker_cpu"] for e in execs) >= 0.9 * 8 * 0.005


class _Attachable:
    """A worker function that records the tracers attached to it."""

    def __init__(self):
        self.attached = []

    def attach_tracer(self, tracer):
        self.attached.append(tracer)

    def __call__(self, task):
        return _spin(task)


def test_tracer_is_attached_on_threads_only():
    tasks = [Task(task_id=f"p{i}") for i in range(4)]
    tr = Tracer()
    fn = _Attachable()
    run_job(tasks, fn, backend="threads", n_workers=2, tracer=tr)
    assert fn.attached == [tr, None]           # attached, then restored
    tr = Tracer()
    fn = _Attachable()
    r = run_job(tasks, fn, backend="processes", n_workers=2, tracer=tr)
    assert r.completed_ids == {t.task_id for t in tasks}
    assert fn.attached == []       # each worker process attaches its own
    assert all(e[6]["worker_cpu"] > 0.0 for e in _spans(tr.events, "exec"))


def test_store_decode_outside_a_task_keeps_the_shard_track(store):
    tr = Tracer()
    proc = SegmentProcessor()
    proc.attach_tracer(tr)
    proc.process_store(store, prefetch=1)
    decodes = _spans(tr.events, "store_decode")
    assert decodes and all(e[5] is None for e in decodes)
    assert {e[4] for e in decodes} == {e[6]["shard"] for e in decodes}
