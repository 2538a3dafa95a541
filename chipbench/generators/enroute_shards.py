"""Cut ``{"shards": N}`` of the en-route file: the first N whole store
shards, in track-id order, as the store's planner will cut them."""

from __future__ import annotations

from chipbench import gen as g
from chipbench.generators import enroute


def make(gen: dict, shards: int, rng, target: int) -> g.Tracks:
    mean_obs = sum(gen["obs_per_track"]) / 2.0
    pool = int(shards * target / mean_obs * 1.2) + 64
    return g.first_shards(enroute.tracks(gen, pool, rng, gen["region_deg"]),
                          shards, target)


check_store = g.check_shards
