"""Share of the fused pipeline's allocated output points that are
padding: rows rounded up and segments shorter than their bucket width.

Source: the program's counters on each ``segments.device`` span under
the shard tasks: 100 x (allocated - valid) / allocated, summed over the
window's calls.
"""

from chipbench import stages


def read(run):
    spans = stages.under(run.events, stages.SHARD)
    alloc = sum(stages.counters(spans, "segments.device", "allocated"))
    valid = sum(stages.counters(spans, "segments.device", "valid"))
    if alloc <= 0:
        return None
    return 100.0 * (alloc - valid) / alloc
