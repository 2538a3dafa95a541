"""Store shard decodes per screen cell task: one per member track, each
of which decodes that track's whole shard.

Source: the program's stage spans: ``store_decode`` spans under the cell
tasks over the cell tasks' ``exec`` spans.
"""

from chipbench import stages


def read(run):
    spans = stages.under(run.events, stages.CELL)
    tasks = sum(1 for e in spans if e[2] == "exec")
    decodes = sum(1 for e in spans if e[2] == "store_decode")
    if tasks == 0 or decodes == 0:
        return None
    return decodes / tasks
