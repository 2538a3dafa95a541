"""``radar`` mix: the §V job, one task per deidentified id.

A pass is one ``run_job`` over ``segment_tasks_from_store(store,
granularity="track")`` with the deployment's runtime (threads, 8
workers, random organization, 300 tasks a message, static policy), so
each 300-id message is one ``SegmentProcessor.process_batch`` call:
store reads of the message's ids -> segmentation -> bucketing -> the
fused Pallas pipeline -> reassembly and airspace class.  The results are
gathered per id and checked by the ``process`` driver's ``Check``.

The deployment runs each worker process in one memory slot
(``memory_slot_bytes``).  A daemon thread reads this process's resident
memory every 20 ms from the driver's construction (after the data step,
whose resident memory is the base) until the ``Check`` is built; when
it grows past the slot, the run ends at once with exit code 3 and one
line on standard error that names the slot, the growth and the pass.
"""

from __future__ import annotations

import os
import sys
import threading

from chipbench.drivers import process

#: Seconds between two readings of the resident memory.
PERIOD_S = 0.02
#: Exit code of a run that outgrew its slot.
EXIT_SLOT = 3
_PAGE = os.sysconf("SC_PAGE_SIZE")


def rss_bytes() -> int:
    """This process's resident memory (``/proc/self/statm``)."""
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * _PAGE


def end_run(line: str) -> None:
    """Print ``line`` on standard error and end the process."""
    sys.stdout.flush()
    os.write(2, (line + "\n").encode())
    os._exit(EXIT_SLOT)


class Slot:
    """Holds the run to ``slot_bytes`` of resident memory above the
    resident memory at construction; ``passes()`` names the pass."""

    def __init__(self, slot_bytes: int, passes, read=None, end=None,
                 period_s: float = PERIOD_S):
        self.slot = int(slot_bytes)
        self._read = read or rss_bytes
        self._end = end or end_run
        self._passes = passes
        self.base = self._read()
        self.peak = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, args=(period_s,),
                                        daemon=True, name="chipbench-slot")
        self._thread.start()

    def _watch(self, period_s: float) -> None:
        while not self._stop.wait(period_s):
            growth = self._read() - self.base
            self.peak = max(self.peak, growth)
            if growth > self.slot:
                n = self._passes()
                self._end(f"chipbench: memory slot exceeded: resident memory "
                          f"grew {growth} bytes over its base of {self.base} "
                          f"bytes, past the slot of {self.slot} bytes "
                          f"(memory_slot_bytes), in pass {n} "
                          f"({'the warm-up' if n == 0 else 'timed'})")
                return

    def stop(self) -> int:
        """Stop watching; returns the peak growth seen, in bytes."""
        self._stop.set()
        self._thread.join()
        return self.peak


#: The slot of the driver built last; the ``Check`` stops it.
_slot = None


class Driver:
    """Drives passes over the store at ``store_dir``."""

    phase = "process"

    def __init__(self, config: dict, traffic: dict, root: str,
                 store_dir: str):
        global _slot
        dep = config["deployment"]
        self.rt = dep["runtime"]
        self.store_dir = store_dir
        self.passes = 0
        if _slot is not None:
            _slot.stop()
        _slot = Slot(dep["memory_slot_bytes"], lambda: self.passes)

    def run_pass(self, tracer=None) -> process.Pass:
        import jax
        from repro.geometry.aerodromes import synthetic_aerodromes
        from repro.geometry.dem import SyntheticGlobeDEM
        from repro.runtime import run_job
        from repro.tracks.segments import (
            SegmentProcessor, segment_tasks_from_store)
        rt = self.rt
        tasks = segment_tasks_from_store(self.store_dir, granularity="track")
        with jax.profiler.TraceAnnotation("process.job"):
            r = run_job(tasks,
                        SegmentProcessor(dem=SyntheticGlobeDEM(),
                                         aerodromes=synthetic_aerodromes(
                                             n=64)),
                        backend=rt["backend"], n_workers=rt["workers"],
                        organization=rt["process_organization"],
                        tasks_per_message=rt["tasks_per_message"],
                        policy=rt["policy"], tracer=tracer)
        self.passes += 1
        return process.Pass(tasks=len(tasks), failed=len(r.failures),
                            job_s=r.job_seconds, outputs=dict(r.results))


class Check(process.Check):
    """The ``process`` driver's check; building it ends the slot."""

    def __init__(self, tracks, config: dict, limits: dict):
        global _slot
        if _slot is not None:
            peak = _slot.stop()
            print(f"memory    : resident memory grew at most {peak} bytes "
                  f"over the {_slot.base}-byte base (slot "
                  f"{_slot.slot} bytes)")
            _slot = None
        super().__init__(tracks, config, limits)
