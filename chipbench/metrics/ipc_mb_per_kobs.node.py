"""Megabytes moved between the worker processes and the device owner per
thousand observations the store served: each bucket's packed knots and
query grid out, its nine fetched planes back.

Source: the program's counters under the radar ids' tasks: the sum of
``bytes_in`` and ``bytes_out`` on the owner's ``device.serve`` spans,
over 10^6, per 1,000 of the ``obs`` counted on the workers'
``store_decode`` spans, over the window.  A program without
``device.serve`` spans reads ``None``.
"""

from chipbench import stages

#: Task ids of the radar cell: its ids' first layout level, the year.
PREFIX = "2015_"


def read(run):
    spans = stages.under(run.events, PREFIX)
    moved = (sum(stages.counters(spans, "device.serve", "bytes_in"))
             + sum(stages.counters(spans, "device.serve", "bytes_out")))
    served = sum(stages.counters(spans, "store_decode", "obs"))
    if moved <= 0 or served <= 0:
        return None
    return (moved / 1e6) / (served / 1000.0)
