"""Share of the screen cell tasks' execution spent re-reading member
tracks from the store and re-deriving their planes.

Source: the program's stage spans: ``store_decode`` and ``segments.*``
seconds under the cell tasks (``screen/<cell>/g1``) over their ``exec``
seconds.
"""

from chipbench import stages


def read(run):
    return stages.share(
        run.events, stages.CELL,
        lambda name: name == "store_decode" or stages.is_segments(name))
