"""The work a pass requires, counted from the reference, not from the
program's padded shapes.

The counts are what any implementation has to do for the pass, so a
roofline share built on them never exceeds 100%: a program can read
each input once and write each output once, and cannot do less.

* The fused segment pipeline (per resampled point: locate and blend
  three planes, bilinear terrain, four central-difference rates; per
  knot: read time, lat, lon and altitude) -- float32 operands.
* The encounter screen (per pair of rows of different aircraft whose
  spans share a second, per shared second: two differences, the
  horizontal distance with its cosine, the vertical distance, two
  compares and two minima) -- float32 operands, each row read once.
"""

from __future__ import annotations

import json
import os

from chipbench import reference

F32 = 4
#: Operations per resampled point: interpolation (weight: 3; three
#: planes: 6), terrain (index math: 6; blend of four cells: 8), rates
#: (four differences over a span: 8; east scaling with a cosine: 3;
#: hypot: 3; atan2: 1).
PIPELINE_FLOPS_PER_POINT = 38
#: Knot inputs (time, lat, lon, alt) and the nine output planes.
PIPELINE_IN_PER_KNOT = 4 * F32
PIPELINE_OUT_PER_POINT = 9 * F32
#: Operations per pair-second: lat/lon/alt differences (3), mean
#: latitude and its cosine (3), scaling (3), squared distance and root
#: (4), two compares and their conjunction (3), two minima (2).
SCREEN_FLOPS_PER_PAIR_SECOND = 18
SCREEN_IN_PER_POINT = 3 * F32


def pipeline(tracks) -> tuple:
    """(flops, bytes) of one process pass over ``tracks``."""
    _, knots, m, _, _ = reference.split(tracks)
    points = int(m.sum())
    return (PIPELINE_FLOPS_PER_POINT * points,
            PIPELINE_IN_PER_KNOT * int(knots.sum())
            + PIPELINE_OUT_PER_POINT * points)


def screen(segs) -> tuple:
    """(flops, bytes) of one screen pass: pair-seconds of overlapping
    rows, and one read of every row that is in such a pair."""
    _, pair_seconds, points = reference.overlap_work(segs)
    return (SCREEN_FLOPS_PER_PAIR_SECOND * pair_seconds,
            SCREEN_IN_PER_POINT * points)


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, device_s: float,
                   device_kind: str) -> float:
    """Percent of ``device_s`` that the least time takes: the larger of
    operations over peak FLOP/s and bytes over peak bandwidth."""
    pk = peaks(device_kind)
    least = max(flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"])
    return 100.0 * least / device_s
