"""Cut ``{"box_deg": [lat0, lat1, lon0, lon1]}`` of the en-route file:
the tracks that start in the box, at the configuration's density."""

from __future__ import annotations

from chipbench import gen as g
from chipbench.generators import enroute


def make(gen: dict, box: list, rng, target: int) -> g.Tracks:
    area = (box[1] - box[0]) * (box[3] - box[2])
    n = int(round(gen["tracks_per_deg2"] * area))
    return enroute.tracks(gen, n, rng, box)
