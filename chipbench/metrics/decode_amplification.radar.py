"""Points the store decompressed per point it served, in the radar
cell's messages: a whole-shard decode per id reads ~930 here, the row
blocks that hold a random 300-id message's ids about 12.

Source: the program's counters on the ``store_decode`` spans under the
radar ids' tasks: sum of ``obs_decoded`` over sum of ``obs``, over the
window.
"""

from chipbench import stages

#: Task ids of the radar cell: its ids' first layout level, the year.
PREFIX = "2015_"


def read(run):
    spans = stages.under(run.events, PREFIX)
    decoded = sum(stages.counters(spans, "store_decode", "obs_decoded"))
    served = sum(stages.counters(spans, "store_decode", "obs"))
    if decoded <= 0 or served <= 0:
        return None
    return decoded / served
