"""End-to-end tracing layer: span-ledger invariants + exporters.

The tracer is only trustworthy if its event stream is *exactly* the
run's history, so the core assertions here are ledger invariants over
real runs (property-tested via hypothesis / the tests/_compat shim):

  * exactly ONE ``exec`` span per completed task — per backend, under
    worker deaths and speculation;
  * every ``requeued`` task that later completed was re-``assigned``
    after the requeue;
  * a worker's ``exec`` spans never overlap on its own timeline (the
    live ``drive`` reconstruction clamps; the sim emits real windows);
  * same-seed sim traces are bitwise repeatable and their canonical
    summaries byte-identical.

Timing-sensitive span tests (store decode, ingest lifecycle) inject the
``_TickClock`` fake monotonic clock from ``test_store`` into the
*tracer* — zero sleeps, exact span arithmetic.  Exporters are checked by
round-trip (Perfetto) and by rendering (report CLI).
"""

import json

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.bench.schema import canonical_bytes, validate_obs_summary
from repro.core.cost_model import PHASES
from repro.core.messages import Task
from repro.obs import (
    INSTANT, Tracer, build_summary, from_chrome_trace, phase_of,
    summary_from_tracer, to_chrome_trace, write_trace_files)
from repro.obs.report import load_summary
from repro.obs.report import main as report_main
from repro.obs.report import render_report
from repro.runtime import run_job


class _TickClock:
    """Fake monotonic clock: advances one unit per reading."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


def _tasks(n, *, mb=4):
    return [Task(task_id=f"t{i:04d}", size_bytes=(i % 5 + 1) * mb * 100_000,
                 timestamp=i) for i in range(n)]


def _sizeof(task):               # module-level: picklable
    return task.size_bytes


# ---------------------------------------------------------------------------
# Tracer mechanics.
# ---------------------------------------------------------------------------

def test_ring_eviction_and_dropped_accounting():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.emit(float(i), INSTANT, "e", "task", 0, f"t{i}")
    assert len(tr) == 4
    assert tr.dropped == 6
    # Oldest evicted first: the ring retains the newest four.
    assert [e[0] for e in tr.events] == [6.0, 7.0, 8.0, 9.0]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_raw_fast_path_accounts_like_emit():
    a, b = Tracer(capacity=3), Tracer(capacity=3)
    for i in range(5):
        a.emit(float(i), INSTANT, "e", "task", 0)
    raw = b.raw
    for i in range(5):
        raw((float(i), INSTANT, "e", "task", 0, None, None))
    b.emitted += 5
    assert b.events == a.events
    assert b.dropped == a.dropped == 2


def test_clock_injection_and_rebind():
    clock = _TickClock()
    tr = Tracer(clock=clock)
    assert tr.now() == 1.0 and tr.now() == 2.0
    tr.instant("i", "sched", "m")          # reads the injected clock
    assert tr.events[-1][0] == 3.0
    tr.set_clock(lambda: 42.0)
    tr.span("s", "sched", "m", tr.now(), tr.now() + 1.0)
    assert tr.events[-1][:2] == (42.0, 1.0)


def test_capacity_validation():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_phase_of_buckets():
    assert phase_of("radar:t0042") == "radar"
    assert phase_of("t0042") == "all"
    assert phase_of(None) == "all"


# ---------------------------------------------------------------------------
# Span-ledger invariants over real runs.
# ---------------------------------------------------------------------------

def _ledger_invariants(events, completed_ids):
    """The invariants every traced run must satisfy (see module doc)."""
    completed = set(completed_ids)
    execs = [e for e in events if e[2] == "exec"]
    # Exactly one exec span per completed task, none for anything else.
    assert sorted(e[5] for e in execs) == sorted(completed)
    dones = [e[5] for e in events if e[2] == "done"]
    assert sorted(dones) == sorted(completed)
    # requeued -> later assigned for every task that finished.
    last_ass, last_req = {}, {}
    for i, e in enumerate(events):
        if e[2] == "assigned":
            last_ass[e[5]] = i
        elif e[2] == "requeued":
            last_req[e[5]] = i
    for tid, i in last_req.items():
        if tid in completed:
            assert last_ass.get(tid, -1) > i, \
                f"{tid} completed but never re-assigned after requeue"
    # Per-worker exec spans never overlap.
    by_worker = {}
    for e in execs:
        by_worker.setdefault(str(e[4]), []).append(e)
    for spans in by_worker.values():
        spans.sort(key=lambda e: e[0])
        for prev, nxt in zip(spans, spans[1:]):
            assert nxt[0] >= prev[0] + prev[1] - 1e-9


@st.composite
def _shapes(draw):
    n = draw(st.integers(4, 30))
    k = draw(st.integers(1, 3))
    org = draw(st.sampled_from(["largest_first", "chronological"]))
    seed = draw(st.integers(0, 4))
    return n, k, org, seed


@given(_shapes())
@settings(max_examples=8, deadline=None)
def test_sim_ledger_invariants_and_bitwise_repeatability(shape):
    n, k, org, seed = shape

    def run():
        tr = Tracer()
        res = run_job(_tasks(n), None, backend="sim", n_workers=3,
                      organization=org, tasks_per_message=k,
                      organize_seed=seed, cost_model=PHASES["process"],
                      worker_death={0: 2.0}, raise_on_failure=False,
                      tracer=tr)
        return tr, res

    tr, res = run()
    assert len(res.completed_ids) == n        # exactly-once under death
    _ledger_invariants(tr.events, res.completed_ids)
    tr2, _ = run()
    # Virtual-clock traces are bitwise repeatable...
    assert tr.events == tr2.events
    # ...and so are their canonical summary bytes.
    assert canonical_bytes(summary_from_tracer(tr, label="x")) \
        == canonical_bytes(summary_from_tracer(tr2, label="x"))


def test_sim_requeues_are_traced():
    tr = Tracer()
    run_job(_tasks(20), None, backend="sim", n_workers=3,
            cost_model=PHASES["process"], worker_death={0: 2.0},
            raise_on_failure=False, tracer=tr)
    names = {e[2] for e in tr.events}
    assert {"queued", "assigned", "exec", "done"} <= names
    assert "requeued" in names          # worker 0 died holding work
    assert any(e[2] == "worker_dead" and e[3] == "sched"
               for e in tr.events)


def test_live_threads_ledger_invariants():
    tr = Tracer()
    res = run_job(_tasks(12), _sizeof, backend="threads", n_workers=3,
                  tasks_per_message=2, tracer=tr)
    assert len(res.completed_ids) == 12
    _ledger_invariants(tr.events, res.completed_ids)
    # Live exec spans are the workers' own timings on the wall clock.
    assert all(e[1] >= 0.0 for e in tr.events if e[2] == "exec")


# ---------------------------------------------------------------------------
# Store + serving spans on an injected clock (zero sleeps).
# ---------------------------------------------------------------------------

@pytest.fixture()
def served_store(tmp_path):
    """A small committed store built through the serving ingest path."""
    import os

    from repro.serving import FeedSpec, IngestService, SyntheticFeed
    feed_dir = str(tmp_path / "feed")
    store_dir = str(tmp_path / "store")
    os.makedirs(feed_dir)
    feed = SyntheticFeed(feed_dir, FeedSpec(n_files=8, obs_per_file=48,
                                            seed=3))
    tr = Tracer(clock=_TickClock())
    svc = IngestService(feed_dir, store_dir, target_points=96, tracer=tr)
    feed.emit_all()
    svc.poll_once()
    manifest = svc.seal()
    return {"svc": svc, "tracer": tr, "store": store_dir,
            "manifest": manifest}


def test_ingest_lifecycle_spans_zero_sleep(served_store):
    tr = served_store["tracer"]
    serving = [e for e in tr.events if e[3] == "serving"]
    names = {e[2] for e in serving}
    assert {"ingest_scan", "ingest_cut", "ingest_build",
            "ingest_commit", "ingest_seal"} <= names
    builds = [e for e in serving if e[2] == "ingest_build"]
    commits = [e for e in serving if e[2] == "ingest_commit"]
    # One build + one commit span per committed shard, real durations
    # (the tick clock advances between the span's two readings).
    assert len(builds) == len(commits) \
        == len(served_store["manifest"].shards)
    assert all(e[1] > 0.0 for e in builds + commits)
    # Every serving event sits on the injected clock's timeline.
    assert all(0.0 < e[0] <= tr.clock.t for e in serving)


def test_store_reader_spans_zero_sleep(served_store):
    from repro.store.reader import TrackStore
    tr = Tracer(clock=_TickClock())
    store = TrackStore(served_store["store"], tracer=tr)
    n = len(list(store.iter_batches(prefetch=2)))
    assert n == len(served_store["manifest"].shards) > 1
    decodes = [e for e in tr.events if e[2] == "store_decode"]
    assert len(decodes) == n
    assert {e[4] for e in decodes} \
        == {s.shard_id for s in served_store["manifest"].shards}
    # extra carries the shard payload size for cost attribution, and
    # the observations decoded.
    assert all(isinstance(e[6]["bytes"], int) and e[6]["bytes"] > 0
               for e in decodes)
    assert sum(e[6]["obs"] for e in decodes) \
        == served_store["manifest"].n_points
    assert all(e[1] > 0.0 for e in decodes)
    # The prefetch thread emitted handoff instants through the same
    # ring (GIL-atomic appends), and the consumer measured its waits.
    assert sum(1 for e in tr.events if e[2] == "store_prefetch") == n
    assert all(e[1] >= 0.0 for e in tr.events if e[2] == "store_wait")


def test_frontend_query_spans(served_store):
    from repro.serving import Query, StoreFrontEnd
    svc, tr = served_store["svc"], served_store["tracer"]
    front = StoreFrontEnd(svc, tiny_slots=1)   # inherits svc's tracer
    assert front.tracer is tr
    q1 = Query(1, "latest", {"track_id": sorted(svc.retained)[0]})
    q2 = Query(2, "latest", {"track_id": sorted(svc.retained)[0]})
    assert front.admit(q1)
    assert not front.admit(q2)                 # one tiny slot -> reject
    front.step()
    names = [(e[2], e[5]) for e in tr.events if e[4] == "frontend"]
    assert ("query_admit", "latest:1") in names
    assert ("query_reject", "latest:2") in names
    spans = [e for e in tr.events
             if e[2] == "query" and e[5] == "latest:1"]
    assert len(spans) == 1 and spans[0][1] > 0.0


# ---------------------------------------------------------------------------
# Straggler attribution.
# ---------------------------------------------------------------------------

def test_summary_speed_estimates_rank_slowed_worker_last():
    tr = Tracer()
    speed = [1.0] * 8
    speed[5] = 0.25
    run_job(_tasks(200), None, backend="sim", n_workers=8,
            cost_model=PHASES["process"], worker_speed=speed,
            raise_on_failure=False, tracer=tr)
    doc = summary_from_tracer(tr, label="stragglers")
    workers = {w: d for w, d in doc["workers"].items()
               if isinstance(d, dict)}
    ranked = sorted(workers, key=lambda w: workers[w]["speed_est"])
    assert ranked[0] == "5"
    assert workers["5"]["speed_est"] < 0.5
    # Healthy workers estimate near nominal speed.
    assert all(workers[w]["speed_est"] > 0.7 for w in ranked[1:])
    # The 4x-slowed worker's tasks blow past the 2x straggler line.
    assert doc["scenario"]["metrics"]["straggler_count"] > 0
    assert any(s["worker"] == "5" for s in doc["stragglers"])


def test_summary_is_schema_valid_and_normalized():
    tr = Tracer()
    run_job(_tasks(20), None, backend="sim", n_workers=4,
            cost_model=PHASES["process"], tracer=tr)
    doc = summary_from_tracer(tr, label="norm")
    assert validate_obs_summary(doc) == []
    # Canonical bytes round-trip through JSON unchanged.
    assert canonical_bytes(json.loads(canonical_bytes(doc))) \
        == canonical_bytes(doc)


def test_summary_worker_table_is_capped():
    events = [(float(i), 1.0, "exec", "task", i, f"t{i}", 100)
              for i in range(10)]
    doc = build_summary(events, max_workers=4)
    workers = doc["workers"]
    assert workers["_dropped_workers"] == 6
    assert len(workers) == 5               # 4 kept + the drop marker
    assert doc["scenario"]["metrics"]["n_workers_seen"] == 10


# ---------------------------------------------------------------------------
# Exporters: Perfetto round-trip + report rendering.
# ---------------------------------------------------------------------------

def test_perfetto_round_trip_preserves_structure():
    tr = Tracer(clock=_TickClock())
    tr.instant("queued", "task", 0, task_id="a:t1")
    tr.span("exec", "task", 3, 10.0, 12.5, task_id="a:t1", extra=4096)
    tr.instant("admit", "dag", "radar", extra=7)
    doc = to_chrome_trace(tr.events, label="rt")
    doc = json.loads(json.dumps(doc))          # must be JSON-clean
    back = from_chrome_trace(doc)
    t0 = min(e[0] for e in tr.events)

    def norm(events, rel):
        return [(round(e[0] - (t0 if rel else 0.0), 6), round(e[1], 6),
                 e[2], e[3], str(e[4]), e[5], e[6]) for e in events]

    assert norm(back, rel=False) == norm(tr.events, rel=True)
    # Instants survive as instants (INSTANT sentinel restored).
    assert sum(1 for e in back if e[1] == INSTANT) == 2


def test_write_trace_files_and_report(tmp_path, capsys):
    tr = Tracer()
    run_job(_tasks(30), None, backend="sim", n_workers=4,
            cost_model=PHASES["process"], tracer=tr)
    paths = write_trace_files(tr, str(tmp_path), label="smoke")
    # The report CLI reads both artifacts and tells the same story.
    for path in (paths["trace"], paths["summary"]):
        assert report_main([path]) == 0
        out = capsys.readouterr().out
        assert "critical path" in out
        assert "slowest workers" in out
    # trace.json reduces to the same headline metrics as the canonical
    # summary (timestamps go through the us scaling, hence approx).
    via_trace = load_summary(paths["trace"])
    with open(paths["summary"]) as f:
        direct = json.load(f)
    for key in ("n_exec_spans", "straggler_count", "n_workers_seen"):
        assert via_trace["scenario"]["metrics"][key] \
            == direct["scenario"]["metrics"][key]
    assert via_trace["scenario"]["metrics"]["critical_path_s"] \
        == pytest.approx(direct["scenario"]["metrics"]["critical_path_s"],
                         rel=1e-6)


def test_report_summary_out_rebuilds_canonical_bytes(tmp_path):
    tr = Tracer()
    run_job(_tasks(10), None, backend="sim", n_workers=2,
            cost_model=PHASES["process"], tracer=tr)
    direct = summary_from_tracer(tr, label="rebuild")
    trace = tmp_path / "trace.json"
    with open(trace, "w") as f:
        json.dump(to_chrome_trace(tr.events, label="rebuild"), f)
    out = tmp_path / "TRACE_summary.json"
    assert report_main([str(trace), "--summary-out", str(out)]) == 0
    rebuilt = json.loads(out.read_bytes())
    assert validate_obs_summary(rebuilt) == []
    assert rebuilt["scenario"]["metrics"]["n_exec_spans"] \
        == direct["scenario"]["metrics"]["n_exec_spans"]


def test_report_rejects_unknown_documents(tmp_path):
    bogus = tmp_path / "nope.json"
    bogus.write_text('{"schema": "other/v1"}')
    assert report_main([str(bogus)]) == 1


def test_render_report_lines_cover_every_section():
    tr = Tracer()
    run_job(_tasks(40), None, backend="sim", n_workers=4,
            cost_model=PHASES["process"],
            worker_speed=[1.0, 1.0, 0.25, 1.0], tracer=tr)
    lines = render_report(summary_from_tracer(tr, label="full"))
    text = "\n".join(lines)
    for needle in ("makespan", "lifecycle:", "per-phase critical path:",
                   "slowest workers", "dispatch timeline"):
        assert needle in text
