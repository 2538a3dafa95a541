"""Share of the runtime's worker time spent executing shard tasks.

Source: the ``exec`` spans of the ``repro.obs`` tracer that the traced
run hands to ``run_job`` (threads backend): busy seconds of all workers
over the workers times the phase's wall seconds, summed over the
window's passes.  Static batching gives whole messages of shard tasks to
few workers, so this share bounds how far more threads could help.
"""


def read(run):
    busy = sum(e[1] for e in run.events
               if e[2] == "exec" and e[1] >= 0.0)
    wall = sum(p.job_s for p in run.passes)
    if busy <= 0.0 or wall <= 0.0:
        return None
    return 100.0 * busy / (run.workers * wall)
