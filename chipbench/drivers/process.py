"""``process`` mix: the process phase's own call, one pass per call.

A pass is what ``TrackWorkflow`` runs as its process phase over a
columnar store, with the settings of ``python -m repro.tracks.workflow``:
one shard task per store shard, ``run_job`` on the threads backend
(8 workers, random organization, 4 tasks per message, static policy),
``SegmentProcessor`` as the worker: store decode -> segmentation ->
bucketing and padding -> the fused Pallas pipeline (``track_interp``,
``agl_lookup``, ``dynamic_rates``) -> reassembly and airspace class.
What the pass returns -- every track's planes -- is what the check
compares with the float64 reference.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import reference


@dataclasses.dataclass
class Pass:
    tasks: int           # runtime tasks (shards)
    failed: int
    job_s: float         # run_job's own wall time
    outputs: dict        # track id -> ProcessedSegments


class Driver:
    """Drives passes over the store at ``store_dir``."""

    phase = "process"

    def __init__(self, config: dict, traffic: dict, root: str,
                 store_dir: str):
        self.rt = config["deployment"]["runtime"]
        self.store_dir = store_dir

    def run_pass(self, tracer=None) -> Pass:
        import jax
        from repro.geometry.aerodromes import synthetic_aerodromes
        from repro.geometry.dem import SyntheticGlobeDEM
        from repro.runtime import run_job
        from repro.tracks.segments import (
            SegmentProcessor, segment_tasks_from_store)
        rt = self.rt
        tasks = segment_tasks_from_store(self.store_dir, granularity="shard")
        with jax.profiler.TraceAnnotation("process.job"):
            r = run_job(tasks,
                        SegmentProcessor(dem=SyntheticGlobeDEM(),
                                         aerodromes=synthetic_aerodromes(
                                             n=64)),
                        backend=rt["backend"], n_workers=rt["workers"],
                        organization=rt["process_organization"],
                        tasks_per_message=rt["tasks_per_message"],
                        policy=rt["policy"], tracer=tracer)
        outputs = {}
        for res in r.results.values():
            outputs.update(res)
        return Pass(tasks=len(tasks), failed=len(r.failures),
                    job_s=r.job_seconds, outputs=outputs)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def flatten(outputs: dict, ids: list, segs) -> dict:
    """The program's planes in the reference's layout, plus the counts
    of what does not line up: tracks without a result, segments whose
    grid length or zero padding is wrong, airspace classes."""
    got = {p: np.full(int(segs.offsets[-1]), np.nan)
           for p in reference.PLANES}
    missing = wrong = 0
    airspace = np.full(len(segs), "", dtype="U1")
    bounds = np.flatnonzero(np.r_[True, segs.track[1:] != segs.track[:-1],
                                  True])
    for i, j in zip(bounds[:-1].tolist(), bounds[1:].tolist()):
        ps = outputs.get(ids[int(segs.track[i])])
        m = segs.m[i:j]
        if ps is None or len(ps.count) != j - i \
                or ps.lat.shape[1] < m.max():
            missing += 1
            continue
        valid = np.arange(ps.lat.shape[1])[None, :] < m[:, None]
        a, b = int(segs.offsets[i]), int(segs.offsets[j])
        padded = np.zeros(j - i, bool)
        for p in reference.PLANES:
            arr = getattr(ps, p)
            got[p][a:b] = arr[valid]
            padded |= (np.where(valid, 0.0, arr) != 0).any(axis=1)
        wrong += int((padded | (np.asarray(ps.count) != m)).sum())
        airspace[i:j] = ps.airspace
    return {"planes": got, "tracks_missing": missing,
            "segments_wrong": wrong, "airspace": airspace}


def compare(got: dict, want, pos_m: float) -> dict:
    """Numbers compared with their limits.  A segment that starts within
    the position limit ``pos_m`` of a change of airspace class may fall
    either way under float32 rounding; its class is not compared."""
    out = reference.plane_errors(got["planes"], want)
    out["tracks_missing"] = got["tracks_missing"]
    out["segments_wrong"] = got["segments_wrong"]
    sure = want.margin > pos_m / reference.M_PER_DEG
    out["airspace_wrong"] = int((got["airspace"] != want.airspace)[sure]
                                .sum())
    return out


class Check:
    """The reference of one cell's data, computed once after the window."""

    def __init__(self, tracks, config: dict, limits: dict):
        self.pos_m = limits["limits"]["pos_m"]
        self.tracks = tracks
        self.terrain = reference.Terrain()
        self.aero = reference.aerodromes()
        self.segs = reference.segment_planes(tracks, self.terrain, self.aero)

    def program(self, last: Pass) -> dict:
        return compare(flatten(last.outputs, self.tracks.ids, self.segs),
                       self.segs, self.pos_m)

    def control(self) -> dict:
        low = reference.segment_planes(self.tracks, self.terrain, self.aero,
                                       precision="bfloat16")
        return compare({"planes": low.planes, "tracks_missing": 0,
                        "segments_wrong": int((low.m != self.segs.m).sum()),
                        "airspace": low.airspace}, self.segs, self.pos_m)
