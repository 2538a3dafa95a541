"""Share of the shard tasks' execution spent in host work around the
fused pipeline: segmentation into records, per-bucket packing of the
padded inputs, reassembly of the planes and airspace classes.

Source: the program's stage spans: ``segments.records``,
``segments.pack`` and ``segments.reassemble`` seconds under the shard
tasks over their ``exec`` seconds.
"""

from chipbench import stages

HOST = ("segments.records", "segments.pack", "segments.reassemble")


def read(run):
    return stages.share(run.events, stages.SHARD, lambda name: name in HOST)
