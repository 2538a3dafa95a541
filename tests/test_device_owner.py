"""Worker processes with a device owner: the processes backend's path on
a chip, here on the CPU.

A :class:`repro.runtime.WorkerPool` of ``SegmentProcessor`` workers
launches its processes once, keeps them across jobs, and runs every
bucket's device call in this (the manager's) process; the worker
processes initialise no JAX backend.  These tests hold it to the
threads backend bit for bit, to exactly-once under a worker killed in
the middle of a message, and to the spans it returns.
"""

import os
import signal
import threading
import time

import numpy as np
import pytest

from repro.core.messages import Task
from repro.obs import Tracer
from repro.runtime import WorkerPool, run_job
from repro.runtime.transports import NO_BACKEND, OWNER_TRACK
from repro.tracks.segments import SegmentProcessor, segment_tasks_from_store

PLANES = ("times", "lat", "lon", "alt_msl_m", "alt_agl_m", "vrate_ms",
          "gspeed_ms", "heading_rad", "turn_rad_s", "count")
RUN = dict(organization="random", tasks_per_message=40, policy="static",
           poll_interval=0.01)


class _Probe(SegmentProcessor):
    """Records, on each result, whether its worker process holds a JAX
    backend; a message of ``touch-jax`` tasks makes the worker try to
    build a device array."""

    def process_batch(self, tasks):
        from jax._src import xla_bridge
        if any(t.task_id.startswith("touch-jax") for t in tasks):
            import jax.numpy as jnp
            jnp.zeros(1)
        out = super().process_batch(tasks)
        import jax
        for ps in out.values():
            ps.jax_backend = xla_bridge.backends_are_initialized()
            ps.jax_platforms = jax.config.jax_platforms
            ps.pid = os.getpid()
        return out


class _Doomed(SegmentProcessor):
    """The first worker process to send a device call is killed while
    the owner serves it; the owner takes ``serve_s`` over each call, so
    the reply finds the worker gone."""

    def __init__(self, marker: str, serve_s: float):
        super().__init__()
        self.marker = marker
        self.serve_s = serve_s

    def serve_device(self, *args):
        time.sleep(self.serve_s)
        return super().serve_device(*args)

    def attach_device(self, client):
        def doomed(args, counters=None):
            try:
                os.close(os.open(self.marker, os.O_CREAT | os.O_EXCL))
            except FileExistsError:
                return client(args, counters)
            threading.Timer(self.serve_s / 4, os.kill,
                            (os.getpid(), signal.SIGKILL)).start()
            return client(args, counters)
        return super().attach_device(doomed)


def _same_planes(want: dict, got: dict) -> None:
    assert set(got) == set(want)
    for tid, a in want.items():
        b = got[tid]
        assert b.icao24 == a.icao24 and b.airspace == a.airspace, tid
        for p in PLANES:
            np.testing.assert_array_equal(getattr(b, p), getattr(a, p),
                                          err_msg=f"{tid} {p}")


@pytest.fixture(scope="module")
def tasks(block_store):
    return segment_tasks_from_store(block_store, granularity="track")


@pytest.fixture(scope="module")
def threads_run(tasks):
    return run_job(tasks, SegmentProcessor(), backend="threads",
                   n_workers=3, **RUN)


@pytest.fixture(scope="module")
def pool():
    with WorkerPool(3, _Probe(), poll_interval=0.01) as p:
        yield p


def test_pool_planes_equal_threads_bit_for_bit(pool, tasks, threads_run):
    """Two jobs on one launch: every id done exactly once, on the same
    processes, with the threads backend's planes bit for bit."""
    pids = dict(pool.pids)
    for _ in range(2):
        r = run_job(tasks, backend="processes", pool=pool, **RUN)
        assert r.completed_ids == {t.task_id for t in tasks}
        dispatched = [tid for batch in r.batches for tid in batch]
        assert sorted(dispatched) == sorted(t.task_id for t in tasks)
        assert r.failed_workers == [] and not r.failures
        _same_planes(threads_run.results, r.results)
        assert pool.pids == pids
        assert {ps.pid for ps in r.results.values()} <= set(pids.values())


def test_worker_processes_hold_no_jax_backend(pool, tasks):
    r = run_job(tasks, backend="processes", pool=pool, **RUN)
    assert {ps.jax_backend for ps in r.results.values()} == {False}
    assert {ps.jax_platforms for ps in r.results.values()} == {NO_BACKEND}


def test_worker_process_that_touches_jax_fails_loudly(pool):
    """A worker that would build a device array raises in its task: no
    backend of its own, no CPU interpreter in its place."""
    tasks = [Task(task_id=f"touch-jax-{i}") for i in range(2)]
    r = run_job(tasks, backend="processes", pool=pool, organization="random",
                tasks_per_message=2, poll_interval=0.01,
                raise_on_failure=False)
    assert set(r.failures) == {t.task_id for t in tasks}
    for err in r.failures.values():
        assert "Unable to initialize backend" in err and NO_BACKEND in err


def test_worker_spans_reach_the_manager_ring(pool, tasks):
    # An untraced job first: its workers record nothing to carry over.
    run_job(tasks, backend="processes", pool=pool, **RUN)
    tr = Tracer()
    run_job(tasks, backend="processes", pool=pool, tracer=tr, **RUN)
    assert tr.dropped == 0
    spans = [e for e in tr.events if e[1] >= 0.0]
    stages = [e for e in spans if e[2] != "exec"
              and e[2] != "device.serve"]
    assert {e[2] for e in stages} == {
        "store_decode", "segments.records", "segments.pack",
        "segments.device", "segments.reassemble"}
    ids = {t.task_id for t in tasks}
    assert {e[4] for e in stages} <= set(pool.worker_ids)
    assert all(e[5] in ids for e in stages)
    execs = [e for e in spans if e[2] == "exec"]
    assert sorted(e[5] for e in execs) == sorted(ids)
    trips = [e for e in stages if e[2] == "segments.device"]
    served = [e for e in spans if e[2] == "device.serve"]
    assert len(served) == len(trips) > 0
    assert {e[4] for e in served} == {OWNER_TRACK}
    key = lambda e: (e[5], e[6]["valid"], e[6]["allocated"])  # noqa: E731
    assert sorted(map(key, served)) == sorted(map(key, trips))
    for e in served:
        assert {"queue_s", "bytes_in", "bytes_out", "valid",
                "allocated"} <= set(e[6])
        assert e[6]["queue_s"] >= 0.0 and e[6]["bytes_out"] > 0
        assert e[6]["bytes_in"] > 0
    # Worker spans sit on the manager's clock: inside the job's window.
    t0 = min(e[0] for e in execs)
    t1 = max(e[0] + e[1] for e in execs)
    assert all(t0 - 1e-3 <= e[0] and e[0] + e[1] <= t1 + 1e-3
               for e in stages + served)


def test_killed_worker_is_requeued_and_the_owner_serves_on(
        tasks, threads_run, tmp_path):
    """A worker killed while the owner serves its device call: its tasks
    are requeued and done exactly once, the owner drops the reply, and
    the next job on the pool launches a replacement."""
    fn = _Doomed(str(tmp_path / "doomed"), serve_s=0.2)
    with WorkerPool(3, fn, poll_interval=0.01) as p:
        before = dict(p.pids)
        r = run_job(tasks, backend="processes", pool=p,
                    **dict(RUN, tasks_per_message=80))
        assert os.path.exists(fn.marker)
        (dead,) = r.failed_workers
        assert r.reassigned_tasks > 0
        assert r.completed_ids == {t.task_id for t in tasks}
        _same_planes(threads_run.results, r.results)
        r2 = run_job(tasks, backend="processes", pool=p,
                     **dict(RUN, tasks_per_message=80))
        assert r2.failed_workers == []
        _same_planes(threads_run.results, r2.results)
        assert p.pids[dead] != before[dead]
        assert all(p.pids[w] == before[w] for w in before if w != dead)


def test_pool_jobs_take_the_pool_settings(pool, tasks):
    with pytest.raises(ValueError, match="WorkerPool"):
        run_job(tasks, backend="threads", pool=pool)
    with pytest.raises(ValueError, match="launched"):
        run_job(tasks, backend="processes", pool=pool,
                worker_fail_after={"w0": 1})
    with pytest.raises(ValueError, match="launched"):
        run_job(tasks, SegmentProcessor(), backend="processes", pool=pool)
    with pytest.raises(ValueError, match="launched"):
        run_job(tasks, backend="processes", pool=pool, failure_timeout=1.0)
