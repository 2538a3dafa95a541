"""Compressed units the store inflated per thousand points it served,
in the radar cell's messages: one native inflate and one crc each.  A
shard that stores every column alone in row blocks counts one unit per
(column, block), about 29 here; one whose point columns are a row group
counts one per group unit, about 6.

Source: the program's counters on the ``store_decode`` spans under the
radar ids' tasks: 1,000 x sum of ``units`` over sum of ``obs``, over the
window, on the spans that carry ``units``.  A program whose decodes carry
no ``units`` reads ``None``.
"""

from chipbench import stages

#: Task ids of the radar cell: its ids' first layout level, the year.
PREFIX = "2015_"


def read(run):
    spans = [e for e in stages.under(run.events, PREFIX)
             if isinstance(e[6], dict) and "units" in e[6]]
    units = sum(stages.counters(spans, "store_decode", "units"))
    served = sum(stages.counters(spans, "store_decode", "obs"))
    if units <= 0 or served <= 0:
        return None
    return 1000.0 * units / served
