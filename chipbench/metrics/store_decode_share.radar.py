"""Share of the radar cell's message execution spent in store reads.

Source: the program's stage spans: seconds of the ``store_decode`` spans
that carry block counters (``blocks``) under the radar ids' tasks, over
those tasks' ``exec`` seconds, in every pass of the window.  A program
whose decodes carry no block counters reads ``None``.
"""

from chipbench import stages

#: Task ids of the radar cell: its ids' first layout level, the year.
PREFIX = "2015_"


def _blocked(e) -> bool:
    return e[2] != "store_decode" or (isinstance(e[6], dict)
                                      and "blocks" in e[6])


def read(run):
    events = [e for e in run.events if _blocked(e)]
    return stages.share(events, PREFIX, lambda name: name == "store_decode")
