"""CPU seconds the worker threads spend on the shard tasks of one pass.

Source: the program's counter ``worker_cpu`` (``time.thread_time`` of
the worker thread around each task) on the ``exec`` spans of the shard
tasks, summed over the window, per pass.  A worker thread whose
allocations go to one of glibc's secondary malloc arenas spends about
twice the CPU on the same work.
"""

from chipbench import stages


def read(run):
    cpu = stages.counters(stages.under(run.events, stages.SHARD), "exec",
                          "worker_cpu")
    if not cpu or not run.passes:
        return None
    return sum(cpu) / len(run.passes)
