"""Cut ``{"shards": N}`` of terminal traffic: consecutive hours, then
the first N whole store shards, in track-id order, as the store's
planner will cut them."""

from __future__ import annotations

from chipbench import gen as g
from chipbench.generators import terminal


def make(gen: dict, shards: int, rng, target: int) -> g.Tracks:
    hours = int(shards * target / gen["mean_obs_per_aircraft"]
                / gen["aircraft_per_hour"] * 1.2) + 1
    pool = g.concat([terminal.hour(gen, h, rng) for h in range(hours)])
    return g.first_shards(pool, shards, target)


check_store = g.check_shards
