"""Share of the worker processes' device calls spent waiting in the
device owner's queue: the node's 8 workers share one owner, which runs
one call at a time, so a bucket that arrives while another runs waits.

Source: the program's spans under the radar ids' tasks: 100 x the sum
of ``queue_s`` (arrival at the owner to the start of the device call) on
the owner's ``device.serve`` spans, over the seconds of the workers'
``segments.device`` spans (each bucket's whole round trip), over the
window.  A program without ``device.serve`` spans reads ``None``.
"""

from chipbench import stages

#: Task ids of the radar cell: its ids' first layout level, the year.
PREFIX = "2015_"


def read(run):
    spans = stages.under(run.events, PREFIX)
    queued = stages.counters(spans, "device.serve", "queue_s")
    trips = sum(e[1] for e in spans if e[2] == "segments.device")
    if not queued or trips <= 0.0:
        return None
    return 100.0 * sum(queued) / trips
