"""Share of the radar node's message execution spent moving buckets
between the worker processes and the device owner: what running on
worker processes costs over running on worker threads.

Source: the program's spans under the radar ids' tasks: 100 x (the
seconds of the workers' ``segments.device`` spans, each bucket's round
trip to the owner, minus the seconds of the owner's ``device.serve``
spans, each bucket's device call) over the ``exec`` seconds of the
messages, over the window.  The difference holds the pickling, the
pipe transfers both ways and the wait in the owner's queue.  A program
without ``device.serve`` spans reads ``None``.
"""

from chipbench import stages

#: Task ids of the radar cell: its ids' first layout level, the year.
PREFIX = "2015_"


def read(run):
    spans = stages.under(run.events, PREFIX)
    served = [e[1] for e in spans if e[2] == "device.serve"]
    trips = sum(e[1] for e in spans if e[2] == "segments.device")
    busy = sum(e[1] for e in spans if e[2] == "exec")
    if not served or busy <= 0.0:
        return None
    return 100.0 * (trips - sum(served)) / busy
