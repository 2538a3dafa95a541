"""Share of the shard tasks' execution spent decoding store shards.

Source: the program's stage spans: ``store_decode`` seconds under the
shard tasks (``store/<shard>``) over their ``exec`` seconds, in every
pass of the window.
"""

from chipbench import stages


def read(run):
    return stages.share(run.events, stages.SHARD,
                        lambda name: name == "store_decode")
