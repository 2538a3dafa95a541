"""Member tracks derived per fused-pipeline call in the screen cell
tasks: how many tracks share a device call when a cell task re-derives
its members in one pass.

Source: the program's counters and stage spans: the sum of ``tracks``
on the ``screen.rows`` spans under the cell tasks (``screen/<cell>/g1``)
over the count of ``segments.device`` spans under them.  A program whose
``screen.rows`` spans carry no ``tracks`` reads ``None``.
"""

from chipbench import stages


def read(run):
    spans = stages.under(run.events, stages.CELL)
    tracks = stages.counters(spans, "screen.rows", "tracks")
    calls = sum(1 for e in spans if e[2] == "segments.device")
    if not tracks or calls == 0:
        return None
    return sum(tracks) / calls
