"""Cut ``{"hotspots": h}`` of terminal traffic: one hour, the tracks
that start within ``hotspot_halo_deg`` of the first h hotspots."""

from __future__ import annotations

import numpy as np

from chipbench import gen as g
from chipbench.generators import terminal


def make(gen: dict, h: int, rng, target: int) -> g.Tracks:
    tracks = terminal.hour(gen, 0, rng)
    hot = np.asarray(gen["hotspots"][:h])
    halo = gen["hotspot_halo_deg"]
    la = tracks.cols["lat"][tracks.offsets[:-1]]
    lo = tracks.cols["lon"][tracks.offsets[:-1]]
    near = ((np.abs(la[:, None] - hot[None, :, 0]) <= halo)
            & (np.abs(lo[:, None] - hot[None, :, 1]) <= halo))
    return tracks.take(np.flatnonzero(near.any(axis=1)))
