"""Share of the shard tasks' execution spent waiting on the device: each
fused-pipeline call from dispatch to the end of its fetch to the host.

Source: the program's stage spans: ``segments.device`` seconds under the
shard tasks over their ``exec`` seconds.
"""

from chipbench import stages


def read(run):
    return stages.share(run.events, stages.SHARD,
                        lambda name: name == "segments.device")
