"""``radar_node`` mix: the §V job as triples mode launches it on a node.

The ``radar`` driver's job -- one task per deidentified id, 300 random
ids a message, each message one ``SegmentProcessor.process_batch`` --
on the processes backend, as the triple ``radar_128n_nppn8`` places it:
the node's 8 worker processes are launched once, before the warm-up
pass (``repro.runtime.WorkerPool``; set-up counts the launch), and every
pass's ``run_job`` runs on them.  They do the store reads, segmentation,
packing, reassembly and airspace classes; the pool's device owner in
this process, which holds the chip, runs each bucket's fused pipeline
call.  The results are checked by the ``process`` driver's ``Check``.

Each worker process runs in one memory slot (``memory_slot_bytes``).  A
daemon thread reads every worker's resident memory from
``/proc/<pid>/statm`` every 20 ms, from the launch until the ``Check`` is
built, as the ``radar`` driver's ``Slot`` reads its own process; when a
worker passes its slot, the workers are killed and the run ends at once
with exit code 3 and one line on standard error that names the worker,
its memory, the slot and the pass.  A worker process holds nothing but
its own work, so its whole resident memory is held to the slot.

The one program name this driver needs is imported as the module loads:
on a program without it the run ends there, before any worker starts.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.runtime import WorkerPool  # noqa: E402

from chipbench.drivers import process, radar  # noqa: E402


def statm_bytes(pid: int) -> int:
    """Resident memory of process ``pid`` (``/proc/<pid>/statm``); 0 once
    the process is gone."""
    try:
        with open(f"/proc/{pid}/statm") as f:
            return int(f.read().split()[1]) * radar._PAGE
    except (FileNotFoundError, ProcessLookupError):
        return 0


def kill_workers(pool) -> None:
    for pid in pool.pids.values():
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


class WorkerSlots:
    """Holds each worker process of ``pool`` to ``slot_bytes`` of resident
    memory; ``passes()`` names the pass.  ``peaks`` keeps the most each
    worker id held."""

    def __init__(self, slot_bytes: int, pool, passes, read=None, end=None,
                 period_s: float = radar.PERIOD_S):
        self.slot = int(slot_bytes)
        self._pool = pool
        self._read = read or statm_bytes
        self._end = end or self._end_run
        self._passes = passes
        self.peaks = {wid: 0 for wid in pool.worker_ids}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, args=(period_s,),
                                        daemon=True, name="chipbench-slots")
        self._thread.start()

    def _end_run(self, line: str) -> None:
        kill_workers(self._pool)
        radar.end_run(line)

    def _watch(self, period_s: float) -> None:
        while not self._stop.wait(period_s):
            for wid, pid in self._pool.pids.items():
                rss = self._read(pid)
                self.peaks[wid] = max(self.peaks[wid], rss)
                if rss > self.slot:
                    n = self._passes()
                    self._end(f"chipbench: memory slot exceeded: worker "
                              f"process {wid} (pid {pid}) holds {rss} "
                              f"resident bytes, past its slot of "
                              f"{self.slot} bytes (memory_slot_bytes), in "
                              f"pass {n} "
                              f"({'the warm-up' if n == 0 else 'timed'})")
                    return

    def stop(self) -> dict:
        """Stop watching; returns each worker's peak, in bytes."""
        self._stop.set()
        self._thread.join()
        return dict(self.peaks)


def make_processor():
    """The worker function: the process phase's ``SegmentProcessor``."""
    from repro.geometry.aerodromes import synthetic_aerodromes
    from repro.geometry.dem import SyntheticGlobeDEM
    from repro.tracks.segments import SegmentProcessor
    return SegmentProcessor(dem=SyntheticGlobeDEM(),
                            aerodromes=synthetic_aerodromes(n=64))


#: The pool and slots of the driver built last; the ``Check`` ends both.
_pool = None
_slots = None


def close() -> dict:
    """Stop the slots and close the pool; returns the workers' peaks."""
    global _pool, _slots
    peaks = _slots.stop() if _slots is not None else {}
    if _pool is not None:
        _pool.close()
    _pool = _slots = None
    return peaks


class Driver:
    """Drives passes over the store at ``store_dir`` on the node's
    worker processes, launched here."""

    phase = "process"

    def __init__(self, config: dict, traffic: dict, root: str,
                 store_dir: str):
        global _pool, _slots
        import jax
        dep = config["deployment"]
        self.rt = dep["runtime"]
        self.store_dir = store_dir
        self.passes = 0
        close()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("process.launch"):
            _pool = WorkerPool(self.rt["workers"], make_processor()).start()
        print(f"launch    : {len(_pool.worker_ids)} worker processes in "
              f"{time.perf_counter() - t0:.3f}s, pids "
              f"{sorted(_pool.pids.values())}; cpu_count {os.cpu_count()}")
        _slots = WorkerSlots(dep["memory_slot_bytes"], _pool,
                             lambda: self.passes)

    def run_pass(self, tracer=None) -> process.Pass:
        import jax
        from repro.runtime import run_job
        from repro.tracks.segments import segment_tasks_from_store
        rt = self.rt
        tasks = segment_tasks_from_store(self.store_dir, granularity="track")
        with jax.profiler.TraceAnnotation("process.job"):
            r = run_job(tasks, backend=rt["backend"], pool=_pool,
                        organization=rt["process_organization"],
                        tasks_per_message=rt["tasks_per_message"],
                        policy=rt["policy"], tracer=tracer)
        self.passes += 1
        return process.Pass(tasks=len(tasks), failed=len(r.failures),
                            job_s=r.job_seconds, outputs=dict(r.results))


class Check(process.Check):
    """The ``process`` driver's check; building it ends the slots and
    closes the worker processes."""

    def __init__(self, tracks, config: dict, limits: dict):
        slot = config["deployment"]["memory_slot_bytes"]
        peaks = close()
        if peaks:
            print(f"memory    : worker processes' peak resident memory "
                  f"{peaks} bytes (slot {slot} bytes each)")
        super().__init__(tracks, config, limits)
