"""Cut ``{"shards": N}`` of the §V radar deployment: the first radar in
id order, its first ids that fill N whole store shards, as the store's
planner will cut them."""

from __future__ import annotations

from chipbench import gen as g
from chipbench.generators import radar


def make(gen: dict, shards: int, rng, target: int) -> g.Tracks:
    pool = int(shards * target / gen["mean_obs_per_id"] * 1.2) + 64
    first = sorted(gen["sites"])[0]
    return g.first_shards(radar.ids(gen, first, pool, rng), shards, target)


check_store = g.check_shards
