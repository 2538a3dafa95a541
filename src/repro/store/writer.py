"""Store ingest: CSV trees / zip archives -> sharded columnar store.

The paper's §III.A zip workaround made the *file count* tractable but
left every run re-parsing CSV text out of zip members.  The writer does
that parse exactly once: it walks an organized CSV tree or a PR-0
archive tree, decodes each aircraft's observations, and packs the
columns (time/lat/lon/alt as contiguous float64 + per-track offsets)
into checksummed shards (:mod:`repro.store.codec`), sized so one shard
is one healthy batch for the PR-3 length-bucketed fused pipeline.  The
per-point columns are one row group, in units of :data:`BLOCK_POINTS`
rows of all of them, so a single track is read from its own units, each
inflated once for every column.

Segment shapes (``seg_knots``/``seg_grid``) are computed at ingest and
recorded in the manifest, so the reader bins segments into buckets from
the index alone.  Planning, shard assignment and encoding are all
deterministic: same inputs -> byte-identical shards and manifest.

Ingest can run standalone (:func:`build_store`, or the CLI below) or as
a self-scheduled ``run_job`` phase: :func:`plan_shards` emits one JSON
task payload per shard and :class:`ShardBuilder` is the picklable worker
fn (see ``tracks/workflow.py``'s ``store-build`` phase).

CLI::

    PYTHONPATH=src python -m repro.store.writer \
        --src experiments/trackwf/archived --out experiments/trackwf/store
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Optional, Sequence

import numpy as np

from repro.store import codec
from repro.store.format import (
    POINT_COLUMNS, SHARD_DIR, SHARD_SUFFIX, ShardRecord, StoreManifest,
    TrackRecord, write_atomic)

__all__ = ["DEFAULT_TARGET_POINTS", "BLOCK_POINTS", "EST_BYTES_PER_OBS",
           "ShardPlan",
           "discover_sources", "plan_shards", "build_shard",
           "ShardBuilder", "commit_shard", "finalize_manifest",
           "finalize_store", "build_store", "main"]

#: Default shard size in observation points.  At ~5-8 s between ADS-B
#: observations this is a few hundred segments per shard — comfortably
#: above the widest fused-pipeline bucket, so every bucket in a shard
#: batch runs near-full rows.
DEFAULT_TARGET_POINTS = 131_072

#: Rows per compressed unit of the point columns (and per block of
#: ``offsets``).  A single-track read decodes only the units that hold
#: the track's rows; 2,048 points (72 KiB of the five columns) keeps a
#: random 300-id message's decode within ~12-16x of the points it serves
#: while whole-shard decodes stay zlib-bound.
BLOCK_POINTS = 2048

#: Rough CSV bytes per observation row (scaled OpenSky state vectors);
#: only used to *estimate* points for shard planning before parsing.
EST_BYTES_PER_OBS = 80


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """One shard's work order: which source files it ingests."""

    shard_id: str
    sources: tuple[tuple[str, str], ...]    # (track_id, path)

    def dumps(self) -> str:
        return json.dumps({"shard_id": self.shard_id,
                           "sources": [list(s) for s in self.sources]})

    @classmethod
    def loads(cls, s: str) -> "ShardPlan":
        d = json.loads(s)
        return cls(shard_id=d["shard_id"],
                   sources=tuple((t, p) for t, p in d["sources"]))


def discover_sources(src_root: str) -> list[tuple[str, str, int]]:
    """Walk a source tree -> sorted (track_id, path, size_bytes).

    Accepts either a PR-0 archive tree (one ``<icao>.zip`` per aircraft)
    or an organized tree (per-aircraft ``.csv`` leaves).  The track_id is
    the root-relative path — identical to the task ids that
    ``segment_tasks_from_archive_tree`` would produce for the same tree.
    """
    out = []
    for dirpath, _dirs, files in os.walk(src_root):
        for f in files:
            if f.endswith(".zip") or f.endswith(".csv"):
                p = os.path.join(dirpath, f)
                rel = os.path.relpath(p, src_root).replace(os.sep, "/")
                out.append((rel, p, os.path.getsize(p)))
    out.sort(key=lambda s: s[0])
    if not out:
        raise FileNotFoundError(
            f"{src_root}: no .zip/.csv sources to ingest")
    return out


def plan_shards(sources: Sequence[tuple[str, str, int]], *,
                target_points: int = DEFAULT_TARGET_POINTS
                ) -> list[ShardPlan]:
    """Greedy sequential shard assignment from size estimates only.

    Tracks are taken in sorted-id order and a shard is cut when its
    estimated point count reaches ``target_points``; a single oversized
    track still becomes one (oversized) shard rather than being split,
    because the fused pipeline consumes whole tracks.
    """
    plans: list[ShardPlan] = []
    cur: list[tuple[str, str]] = []
    cur_points = 0
    for track_id, path, size_bytes in sources:
        est = max(size_bytes // EST_BYTES_PER_OBS, 1)
        if cur and cur_points + est > target_points:
            plans.append(ShardPlan(f"s{len(plans):05d}", tuple(cur)))
            cur, cur_points = [], 0
        cur.append((track_id, path))
        cur_points += est
    if cur:
        plans.append(ShardPlan(f"s{len(plans):05d}", tuple(cur)))
    return plans


def build_shard(out_root: str, plan: ShardPlan, *,
                compression: str = "zlib"
                ) -> tuple[ShardRecord, list[TrackRecord]]:
    """Parse one plan's sources and write ``shards/<shard_id>.shard``."""
    from repro.tracks.segments import (
        read_observations, segment_shape, split_segments)

    times, lats, lons, alts = [], [], [], []
    icao_codes: list[np.ndarray] = []
    icao_values: list[str] = []
    icao_index: dict[str, int] = {}
    offsets = [0]
    tracks: list[TrackRecord] = []
    for row, (track_id, path) in enumerate(plan.sources):
        obs = read_observations(path)
        if not obs:
            obs = {k: np.zeros(0) for k in ("time", "lat", "lon", "alt")}
            obs["icao24"] = np.zeros(0, dtype="U1")
        n = len(obs["time"])
        times.append(np.asarray(obs["time"], np.float64))
        lats.append(np.asarray(obs["lat"], np.float64))
        lons.append(np.asarray(obs["lon"], np.float64))
        alts.append(np.asarray(obs["alt"], np.float64))
        codes = np.zeros(n, np.uint32)
        names = [str(x) for x in obs["icao24"]]
        for i, name in enumerate(names):
            if name not in icao_index:
                icao_index[name] = len(icao_values)
                icao_values.append(name)
            codes[i] = icao_index[name]
        icao_codes.append(codes)
        offsets.append(offsets[-1] + n)
        segs = split_segments(obs["time"]) if n else []
        shapes = [segment_shape(obs["time"], s) for s in segs]
        tracks.append(TrackRecord(
            track_id=track_id, shard_id=plan.shard_id, row=row,
            n_obs=n, icao24=(names[0] if names else ""),
            seg_knots=tuple(s[0] for s in shapes),
            seg_grid=tuple(s[1] for s in shapes)))

    columns = {
        "time": np.concatenate(times) if times else np.zeros(0),
        "lat": np.concatenate(lats) if lats else np.zeros(0),
        "lon": np.concatenate(lons) if lons else np.zeros(0),
        "alt": np.concatenate(alts) if alts else np.zeros(0),
        "icao_codes": (np.concatenate(icao_codes) if icao_codes
                       else np.zeros(0, np.uint32)),
        "offsets": np.asarray(offsets, np.int64),
    }
    meta = {"shard_id": plan.shard_id,
            "track_ids": [t.track_id for t in tracks],
            "icao_values": icao_values}
    data = codec.encode_shard(columns, meta=meta, compression=compression,
                              block_rows=BLOCK_POINTS,
                              groups=[POINT_COLUMNS])
    filename = f"{SHARD_DIR}/{plan.shard_id}{SHARD_SUFFIX}"
    write_atomic(os.path.join(out_root, filename), data)
    rec = ShardRecord(
        shard_id=plan.shard_id, filename=filename,
        n_tracks=len(tracks), n_points=int(offsets[-1]),
        size_bytes=len(data),
        sha256=hashlib.sha256(data).hexdigest())
    return rec, tracks


class ShardBuilder:
    """Picklable ``run_job`` worker fn for the ``store-build`` phase.

    Task payload: ``ShardPlan.dumps()``.  Returns JSON-able record docs
    (the DONE message must survive the process-backend pickle and the
    manager-side merge in :func:`finalize_store`).
    """

    def __init__(self, out_root: str, compression: str = "zlib"):
        self.out_root = out_root
        self.compression = compression

    def __call__(self, task) -> dict:
        plan = ShardPlan.loads(task.payload)
        rec, tracks = build_shard(self.out_root, plan,
                                  compression=self.compression)
        return {"shard": rec.to_doc(),
                "tracks": [t.to_doc() for t in tracks]}


def commit_shard(out_root: str, result: dict, *,
                 compression: str = "zlib",
                 target_points: int = DEFAULT_TARGET_POINTS
                 ) -> ShardRecord:
    """Incrementally append ONE built shard to the store manifest.

    The streaming DAG commits shards as they complete (so downstream
    process tasks can read them immediately) instead of waiting for
    :func:`finalize_store`'s single end-of-phase merge.  ``result`` is a
    :class:`ShardBuilder` return doc.  Idempotent by shard id: a
    re-commit after a kill between manifest append and manager
    checkpoint is a no-op (the shard file itself is deterministic and
    atomically written, so re-running the build task is safe too) — the
    manifest never duplicates or orphans a shard.  Single-writer: only
    the manager calls this, so load-modify-save needs no lock.  Entries
    are kept in the same sorted order as :func:`finalize_store`, so
    after :func:`finalize_manifest` the manifest bytes are identical to
    a barrier build's.
    """
    try:
        manifest = StoreManifest.load(out_root)
    except FileNotFoundError:
        manifest = StoreManifest(compression=compression,
                                 target_points=target_points,
                                 meta={"partial": True})
    rec = ShardRecord.from_doc(result["shard"])
    if any(s.shard_id == rec.shard_id for s in manifest.shards):
        return rec
    manifest.shards = sorted(manifest.shards + [rec],
                             key=lambda s: s.shard_id)
    manifest.tracks = sorted(
        manifest.tracks + [TrackRecord.from_doc(d)
                           for d in result["tracks"]],
        key=lambda t: (t.shard_id, t.row))
    # Every real append advances the generation (re-commits above do
    # not), so readers detect growth by comparing generations alone.
    manifest.generation += 1
    manifest.save(out_root)
    return rec


def finalize_manifest(out_root: str, *,
                      compression: str = "zlib",
                      target_points: int = DEFAULT_TARGET_POINTS,
                      meta: Optional[dict] = None) -> StoreManifest:
    """Seal an incrementally-committed store: replace the provisional
    ``{"partial": True}`` meta and re-save.  The result is byte-identical
    to :func:`finalize_store` over the same shard results."""
    manifest = StoreManifest.load(out_root)
    manifest.compression = compression
    manifest.target_points = target_points
    manifest.meta = meta or {}
    manifest.shards = sorted(manifest.shards, key=lambda s: s.shard_id)
    manifest.tracks = sorted(manifest.tracks,
                             key=lambda t: (t.shard_id, t.row))
    # Normalize so a resumed incremental build (whose re-commits did not
    # bump the counter) seals byte-identically to a batch build.
    manifest.generation = len(manifest.shards)
    manifest.save(out_root)
    return manifest


def finalize_store(out_root: str, results: Sequence[dict], *,
                   compression: str = "zlib",
                   target_points: int = DEFAULT_TARGET_POINTS,
                   meta: Optional[dict] = None) -> StoreManifest:
    """Merge per-shard build results into the saved manifest."""
    shards = sorted((ShardRecord.from_doc(r["shard"]) for r in results),
                    key=lambda s: s.shard_id)
    tracks = sorted(
        (TrackRecord.from_doc(d) for r in results for d in r["tracks"]),
        key=lambda t: (t.shard_id, t.row))
    manifest = StoreManifest(compression=compression,
                             target_points=target_points,
                             generation=len(shards),
                             shards=shards, tracks=tracks,
                             meta=meta or {})
    manifest.save(out_root)
    return manifest


def build_store(src_root: str, out_root: str, *,
                compression: str = "zlib",
                target_points: int = DEFAULT_TARGET_POINTS
                ) -> StoreManifest:
    """One-call ingest: discover -> plan -> build every shard -> manifest."""
    sources = discover_sources(src_root)
    plans = plan_shards(sources, target_points=target_points)
    results = []
    for plan in plans:
        rec, tracks = build_shard(out_root, plan, compression=compression)
        results.append({"shard": rec.to_doc(),
                        "tracks": [t.to_doc() for t in tracks]})
    return finalize_store(out_root, results, compression=compression,
                          target_points=target_points,
                          meta={"source_root": os.path.abspath(src_root)})


def main(argv=None) -> int:
    """CLI: ingest a CSV/zip tree into a columnar track store."""
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m repro.store.writer",
        description="Ingest an organized CSV tree or zip-archive tree "
                    "into a sharded columnar track store.")
    ap.add_argument("--src", required=True,
                    help="source tree (PR-0 .zip archives or organized "
                         ".csv leaves)")
    ap.add_argument("--out", required=True, help="store root to create")
    ap.add_argument("--compression", default="zlib",
                    choices=list(codec.COMPRESSIONS))
    ap.add_argument("--target-points", type=int,
                    default=DEFAULT_TARGET_POINTS,
                    help="observation points per shard (default "
                         f"{DEFAULT_TARGET_POINTS})")
    args = ap.parse_args(argv)
    manifest = build_store(args.src, args.out,
                           compression=args.compression,
                           target_points=args.target_points)
    n_seg = sum(t.n_segments for t in manifest.tracks)
    print(f"wrote {len(manifest.shards)} shard(s), "
          f"{len(manifest.tracks)} tracks, {n_seg} segments, "
          f"{manifest.n_points} points, {manifest.size_bytes} bytes "
          f"-> {args.out}")
    hist = manifest.bucket_histogram()
    print("bucket histogram (from index): "
          + ", ".join(f"{w}:{c}" for w, c in hist.items()))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
