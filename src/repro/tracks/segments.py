"""Workflow step 3: process + interpolate into track segments (§III.A).

Per aircraft archive:
  1. split raw observations into segments on time gaps;
  2. drop segments with fewer than ten observations (paper rule);
  3. resample each segment onto a uniform grid  -> kernels.track_interp;
  4. AGL altitude = MSL - DEM elevation         -> kernels.agl_lookup;
  5. dynamic rates (vrate/speed/heading/turn)   -> kernels.dynamic_rates;
  6. airspace class tag (nearest aerodrome within the terminal cylinder).

Steps 3-5 run through the fused device-resident pipeline
(:func:`repro.kernels.ops.process_segments`): one jit'd call per length
bucket, no intermediate host<->device transfers.  Segments are binned
into power-of-two width buckets (:data:`BUCKET_SIZES`) instead of one
global (B, 1024) tile, bounding padding waste to <2x for any segment at
least half a bucket long (a fixed tile wastes ~100x on a
10-observation segment); one compilation is cached per bucket shape.
The chip benchmark (``chipbench/``) measures this path; the tests
compare it with the standalone kernels and the pure-jnp oracles
(``backend='ref'``).

Input is either the PR-0 zip/CSV path (text re-parsed per run) or the
columnar track store (:mod:`repro.store`): ``store://`` task payloads
select tracks, shards, or row ranges, and
:meth:`SegmentProcessor.process_store` streams whole shards through the
fused pipeline behind the store's async prefetcher.
"""

from __future__ import annotations

import ctypes
import dataclasses
import functools
import os
import zipfile
from typing import Optional, Sequence

import numpy as np

from repro.core.messages import Task
from repro.geometry.aerodromes import Aerodrome
from repro.geometry.dem import SyntheticGlobeDEM
from repro.geometry.queries import RADIUS_DEG
from repro.kernels import ops
from repro.obs.tracer import stage

MIN_OBS_PER_SEGMENT = 10       # paper: remove segments with <10 observations
SEGMENT_GAP_S = 120.0          # new segment after a 2-minute gap
RESAMPLE_DT_S = 1.0            # uniform 1 Hz grid
MAX_SEG_POINTS = 1024          # widest tile (pad/truncate ceiling)
BUCKET_SIZES = (128, 256, 512, 1024)   # ragged-batch width buckets


def bucket_width(n: int) -> int:
    """Smallest bucket that holds an ``n``-point segment (capped)."""
    for k in BUCKET_SIZES:
        if n <= k:
            return k
    return BUCKET_SIZES[-1]


def segment_shape(times: np.ndarray, s: slice) -> tuple[int, int]:
    """One segment's fused-pipeline shape: (raw knots n, grid points m).

    The single source of truth for shard ingest (``repro.store.writer``
    records these in the manifest index) and for live batching
    (:meth:`SegmentProcessor._records`), so index-driven bucket plans
    agree exactly with what the pipeline would compute from payloads.
    """
    n = min(s.stop - s.start, MAX_SEG_POINTS)
    t = times[s.start:s.start + n]
    m = min(int((t[-1] - t[0]) / RESAMPLE_DT_S) + 1, MAX_SEG_POINTS)
    return n, m


def read_observations(path: str) -> dict[str, np.ndarray]:
    """Read a per-aircraft CSV (possibly inside a .zip archive).

    The parse is vectorized: one ``np.loadtxt`` over the decoded payload
    per column group instead of a Python ``split(',')`` loop per line
    (the loop dominated small-archive task cost).  This text decode is
    what the columnar store (:mod:`repro.store`) pays exactly once, at
    ingest."""
    if path.endswith(".zip"):
        with zipfile.ZipFile(path) as zf:
            text = zf.read(zf.namelist()[0]).decode()
    else:
        with open(path) as f:
            text = f.read()
    nl = text.find("\n")
    if nl < 0 or not text[nl:].strip():
        return {}
    cols = {c: i for i, c in enumerate(text[:nl].strip().split(","))}
    lines = [ln for ln in text[nl + 1:].split("\n") if ln.strip()]
    num = np.loadtxt(lines, delimiter=",", ndmin=2,
                     usecols=[cols[c] for c in
                              ("time", "lat", "lon", "geoaltitude")])
    icao = np.loadtxt(lines, delimiter=",", dtype=str,
                      usecols=cols["icao24"], ndmin=1)
    t = num[:, 0]
    order = np.argsort(t, kind="stable")
    return {
        "time": t[order],
        "lat": num[order, 1],
        "lon": num[order, 2],
        "alt": num[order, 3],
        "icao24": icao[order],
    }


def _round_rows(b: int) -> int:
    """Round a bucket's row count up: powers of two below 8, multiples
    of 8 after — at most 7 padded rows, and far fewer compiled batch
    shapes per bucket width than one per distinct segment count."""
    p = 1
    while p < b and p < 8:
        p *= 2
    return p if b <= 8 else -(-b // 8) * 8


@dataclasses.dataclass
class ProcessedSegments:
    """One archive's processed segments as (B, W) planes; ``W`` is the
    archive's widest bucket (<= MAX_SEG_POINTS), ``count`` masks rows."""
    icao24: list[str]
    times: np.ndarray       # (B, W) uniform grid times
    lat: np.ndarray         # (B, W)
    lon: np.ndarray         # (B, W)
    alt_msl_m: np.ndarray   # (B, W)
    alt_agl_m: np.ndarray   # (B, W)
    vrate_ms: np.ndarray    # (B, W)
    gspeed_ms: np.ndarray   # (B, W)
    heading_rad: np.ndarray  # (B, W)
    turn_rad_s: np.ndarray  # (B, W)
    count: np.ndarray       # (B,)
    airspace: list[str]

    def __len__(self) -> int:
        return len(self.count)


# Field name mapping: fused-pipeline plane -> ProcessedSegments attribute.
_PLANE_ATTRS = (("times", "times"), ("lat", "lat"), ("lon", "lon"),
                ("alt_msl", "alt_msl_m"), ("alt_agl", "alt_agl_m"),
                ("vrate", "vrate_ms"), ("gspeed", "gspeed_ms"),
                ("heading", "heading_rad"), ("turn", "turn_rad_s"))


def split_segments(times: np.ndarray, gap_s: float = SEGMENT_GAP_S,
                   min_obs: int = MIN_OBS_PER_SEGMENT) -> list[slice]:
    """Split a sorted time vector into gap-delimited segments, dropping
    those shorter than ``min_obs`` (the paper's ten-observation rule)."""
    if len(times) == 0:
        return []
    breaks = np.flatnonzero(np.diff(times) > gap_s) + 1
    out = []
    for s, e in zip(np.r_[0, breaks], np.r_[breaks, len(times)]):
        if e - s >= min_obs:
            out.append(slice(int(s), int(e)))
    return out


def _is_store_uri(path) -> bool:
    """Lazy delegate to :mod:`repro.store.reader` (one URI definition)."""
    from repro.store.reader import is_store_uri
    return is_store_uri(path)


def _parse_store_uri(uri: str):
    from repro.store.reader import parse_store_uri
    return parse_store_uri(uri)


@dataclasses.dataclass
class _SegRecord:
    """One segment, flattened out of its archive for bucketed batching."""
    arch: int               # archive index in the _process_many items
    name: str
    t: np.ndarray           # raw times, truncated to MAX_SEG_POINTS
    lat: np.ndarray
    lon: np.ndarray
    alt: np.ndarray
    n: int                  # valid knots
    m: int                  # valid output grid points
    width: int              # bucket width (>= max(n, m))
    may_span: bool          # track may cross a DEM tile border


class SegmentProcessor:
    """Processes one organized/archived aircraft file into segments."""

    def __init__(self, dem: Optional[SyntheticGlobeDEM] = None,
                 aerodromes: Optional[Sequence[Aerodrome]] = None,
                 backend: str = "pallas"):
        self.dem = dem or SyntheticGlobeDEM()
        self.aerodromes = list(aerodromes or [])
        self.backend = backend
        self._stores: dict = {}          # store root -> TrackStore
        #: Optional repro.obs.Tracer (attach_tracer): stage spans of the
        #: fused path and of its stores' decodes.
        self.tracer = None
        #: In a worker process of the processes backend, its line to the
        #: device owner (attach_device); None runs serve_device here.
        self._device = None
        self._dem_f32 = self.dem.elevation_m.astype(np.float32)
        self._dem_grid = (self.dem.lat_min, self.dem.lat_max,
                          self.dem.lon_min, self.dem.lon_max,
                          float(self.dem.cells_per_deg))
        self.last_stats: dict = {}
        if self.aerodromes:
            self._aero_lat = np.array([a.lat for a in self.aerodromes])
            self._aero_lon = np.array([a.lon for a in self.aerodromes])
            self._aero_cls = [a.airspace_class for a in self.aerodromes]

    def __getstate__(self) -> dict:
        state = dict(self.__dict__)
        state.update(_stores={}, tracer=None, _device=None)
        return state

    def attach_tracer(self, tracer):
        """Emit stage spans into ``tracer`` (None: stop); returns the
        tracer attached before.  ``run_job`` calls this for a traced
        threads job, a worker process for each traced message."""
        prev, self.tracer = self.tracer, tracer
        for store in self._stores.values():
            store.tracer = tracer
        return prev

    def attach_device(self, client):
        """Send each bucket's device call through ``client`` instead of
        running :meth:`serve_device` here; returns the client before.

        A worker process of the processes backend
        (:class:`repro.runtime.transports.WorkerPool`) attaches its line
        to the device owner, ``client(args, counters)``: the owner runs
        the manager's own copy's :meth:`serve_device` on ``args`` and
        returns the fetched planes.  The worker builds no device array."""
        prev, self._device = self._device, client
        return prev

    def serve_device(self, t_in, v_in, count_in, t_out, count_out,
                     may_span) -> dict[str, np.ndarray]:
        """One packed bucket through the fused pipeline, fetched to the
        host: ONE jit'd call and one device->host fetch per plane, the
        pipeline's only downward transfer.  The device owner runs this
        for the worker processes."""
        out = ops.process_segments(
            self._dem_f32, t_in, v_in, count_in, t_out, count_out,
            grid=self._dem_grid, dt=RESAMPLE_DT_S, backend=self.backend,
            agl_oracle=may_span)
        return {k: np.asarray(v) for k, v in out.items()}

    # -- io -------------------------------------------------------------

    def __call__(self, task: Task):
        return self.process_file(task.payload or task.task_id)

    def read_observations(self, path: str) -> dict[str, np.ndarray]:
        """One source -> observation dict.  Accepts a CSV path, a PR-0
        zip archive, or a single-track ``store://`` URI (columnar-store
        reads skip the text parse entirely)."""
        if _is_store_uri(path):
            root, sel = _parse_store_uri(path)
            if "track" not in sel:
                raise ValueError(
                    f"read_observations needs a single track; {path!r} "
                    f"selects a shard (use process_file/process_batch)")
            return self._store_read(
                root, lambda st: st.read_track(sel["track"]))
        return read_observations(path)

    # -- store-backed input ----------------------------------------------

    def _store(self, root: str):
        """One cached TrackStore per store root (index parsed once)."""
        store = self._stores.get(root)
        if store is None:
            from repro.store.reader import TrackStore
            store = self._stores[root] = TrackStore(root,
                                                    tracer=self.tracer)
        return store

    def _store_read(self, root: str, fn):
        """Run one read against the cached store, retrying once after a
        manifest reload on a missed track/shard — a streaming-DAG store
        grows while it is being processed, so a worker's index snapshot
        can predate the shard its task names."""
        store = self._store(root)
        try:
            return fn(store)
        except KeyError:
            store.reload()
            return fn(store)

    def _store_items(self, uri: str) -> list[tuple[str, dict, list[slice]]]:
        """store:// URI -> [(track_id, obs, segs)] for its selection."""
        root, sel = _parse_store_uri(uri)
        return self._store_read(root, lambda st: st.read_selection(sel))

    def process_store(self, root: str, *, prefetch: int = 1,
                      plans=None) -> dict[str, "ProcessedSegments"]:
        """Stream the whole store (or ``plans``) through the fused
        pipeline: the async prefetcher decodes shard N+1 while the
        device processes shard N.  Returns {track_id: ProcessedSegments}.
        """
        store = self._store(root)
        out: dict[str, ProcessedSegments] = {}
        for batch in store.iter_batches(plans, prefetch=prefetch):
            out.update(self._process_triples(
                [(tid, obs, segs) for tid, (obs, segs)
                 in zip(batch.track_ids, batch.items)]))
        return out

    # -- processing -------------------------------------------------------

    def process_file(self, path: str):
        """One source -> ProcessedSegments; a multi-track ``store://``
        selection (shard / row range / whole store) -> a dict keyed by
        track_id."""
        if _is_store_uri(path):
            _root, sel = _parse_store_uri(path)
            if "track" not in sel:
                return self._process_selection(path)
        obs = self.read_observations(path)
        if not obs:
            return _empty()
        segs = split_segments(obs["time"])
        if not segs:
            return _empty()
        return self.process_arrays(obs, segs)

    def _process_selection(self, uri: str) -> dict:
        return self._process_triples(self._store_items(uri))

    def _process_triples(self, triples: list) -> dict:
        """[(track_id, obs, segs)] -> {track_id: ProcessedSegments},
        ONE fused pass over the non-empty items — the single merge
        helper behind store selections AND store streaming."""
        out = {tid: _empty() for tid, _obs, segs in triples if not segs}
        work = [(tid, (obs, segs)) for tid, obs, segs in triples if segs]
        if work:
            for (tid, _), ps in zip(
                    work, self._process_many([it for _, it in work])):
                out[tid] = ps
        return out

    def process_arrays(self, obs: dict[str, np.ndarray],
                       segs: list[slice]) -> ProcessedSegments:
        return self._process_many([(obs, segs)])[0]

    def process_batch(self, tasks: Sequence[Task]) -> dict:
        """Runtime batch hook: one multi-task ASSIGN message -> bucketed
        fused pipeline calls over every segment of every source in the
        batch, instead of per-task Python dispatch.  Returns
        ``{task_id: result}`` (what the worker reports DONE): a
        ProcessedSegments per zip/CSV/single-track task, a
        ``{track_id: ProcessedSegments}`` dict per multi-track
        ``store://`` task — with ONE fused pipeline pass over all of it.
        The message's single-track ``store://`` tasks are read together
        (:meth:`repro.store.reader.TrackStore.read_tracks`: each shard
        unit once); items keep the tasks' order.  Once the message's
        transients are freed, the C heap's free pages go back to the OS
        (:func:`_release_free_heap`).
        """
        out = self._process_batch(tasks)
        _release_free_heap()
        return out

    def _process_batch(self, tasks: Sequence[Task]) -> dict:
        out: dict[str, object] = {}
        items: list[tuple[dict, list[slice]]] = []
        # (task_id, track_key or None, item index); key None = the
        # task's result IS the ProcessedSegments, else it lands in the
        # task's per-track dict under that key.
        slots: list[tuple[str, Optional[str], int]] = []
        singles = self._read_single_tracks(tasks)
        for task in tasks:
            path = task.payload or task.task_id
            if _is_store_uri(path):
                root, sel = _parse_store_uri(path)
                if "track" in sel:
                    obs, segs = singles[root][sel["track"]]
                    if segs:
                        slots.append((task.task_id, None, len(items)))
                        items.append((obs, segs))
                    else:
                        out[task.task_id] = _empty()
                    continue
                out[task.task_id] = {}
                for tid, obs, segs in self._store_items(path):
                    if segs:
                        slots.append((task.task_id, tid, len(items)))
                        items.append((obs, segs))
                    else:
                        out[task.task_id][tid] = _empty()
                continue
            obs = self.read_observations(path)
            segs = split_segments(obs["time"]) if obs else []
            if segs:
                slots.append((task.task_id, None, len(items)))
                items.append((obs, segs))
            else:
                out[task.task_id] = _empty()
        if items:
            processed = self._process_many(items)
            for task_id, key, idx in slots:
                if key is None:
                    out[task_id] = processed[idx]
                else:
                    out[task_id][key] = processed[idx]
        return out

    def _read_single_tracks(self, tasks: Sequence[Task]
                            ) -> dict[str, dict[str, tuple]]:
        """The single-track ``store://`` tasks of a message, read with
        one grouped read per store: ``{root: {track_id: (obs, segs)}}``."""
        wanted: dict[str, list[str]] = {}
        for task in tasks:
            path = task.payload or task.task_id
            if _is_store_uri(path):
                root, sel = _parse_store_uri(path)
                if "track" in sel:
                    wanted.setdefault(root, []).append(sel["track"])
        return {root: self._store_read(root,
                                       lambda st: st.read_tracks(ids))
                for root, ids in wanted.items()}

    # -- fused, length-bucketed path --------------------------------------

    # Conservative guard band (in DEM cells) added to the host-side
    # tile-span check: the device predicate works on f32 interp output,
    # the host bound on f64 raw knots — the margin absorbs the rounding.
    _SPAN_MARGIN = 0.5

    def _may_span(self, lat: np.ndarray, lon: np.ndarray) -> bool:
        """Can this track's DEM window cross a tile border?  Interp
        output is a convex combination of the knots, so knot extents
        bound it; False proves the fused op needs no oracle fallback."""
        lat_min, lat_max, lon_min, lon_max, cpd = self._dem_grid
        H, W = self._dem_f32.shape

        def axis_spans(v, lo, hi, cells, tile):
            f0 = (min(max(float(v.min()), lo), hi) - lo) * cpd
            f1 = (min(max(float(v.max()), lo), hi) - lo) * cpd
            f0 = min(max(f0, 0.0), cells - 1.001)
            f1 = min(max(f1, 0.0), cells - 1.001)
            origin = (f0 // tile) * tile
            return (f1 - origin) >= tile - 1 - self._SPAN_MARGIN

        return (axis_spans(lat, lat_min, lat_max, H, ops.TILE_H)
                or axis_spans(lon, lon_min, lon_max, W, ops.TILE_W))

    def _records(self, items: list[tuple[dict, list[slice]]]
                 ) -> list[_SegRecord]:
        records: list[_SegRecord] = []
        for ai, (obs, segs) in enumerate(items):
            for s in segs:
                n, m = segment_shape(obs["time"], s)
                sl = slice(s.start, s.start + n)
                t = obs["time"][sl]
                lat, lon = obs["lat"][sl], obs["lon"][sl]
                records.append(_SegRecord(
                    arch=ai, name=str(obs["icao24"][s.start]), t=t,
                    lat=lat, lon=lon, alt=obs["alt"][sl], n=n, m=m,
                    width=bucket_width(max(n, m)),
                    may_span=self._may_span(lat, lon)))
        return records

    def _process_many(self, items: list[tuple[dict, list[slice]]]
                      ) -> list[ProcessedSegments]:
        """Bucketed ragged batching: flatten every archive's segments,
        bin them by power-of-two width, run ONE fused device call per
        bucket (cached compilation per shape), then reassemble the
        bucket planes into per-archive planes with whole-bucket gathers
        into one array per plane and archive width (:meth:`_reassemble`).

        With a tracer attached, each step is a stage span: the
        segmentation into records (``segments.records``), per bucket the
        host packing (``segments.pack``) and the device call up to its
        fetch (``segments.device``, with the bucket's valid and
        allocated points; in a worker process, the round trip to the
        device owner), and the reassembly (``segments.reassemble``,
        with the ``segments`` reassembled and the ``gathers`` made: one
        per pair of source bucket and width group).
        """
        tr = self.tracer
        with stage(tr, "segments.records", "task") as st:
            records = self._records(items)
            if tr is not None:
                st.extra = {"segments": len(records)}
        # Bucket key includes the fallback flag: a segment's compiled
        # graph variant must be a function of the segment alone, or
        # per-archive outputs could drift an ulp depending on which
        # other segments share its batch (XLA fuses the fallback and
        # no-fallback graphs differently).
        buckets: dict[tuple[int, bool], list[int]] = {}
        for gi, rec in enumerate(records):
            buckets.setdefault((rec.width, rec.may_span), []).append(gi)

        fetched: list[tuple[list[int], dict]] = []  # (rows, planes)
        allocated = 0
        for width, may_span in sorted(buckets):
            idxs = buckets[(width, may_span)]
            bk = len(idxs)
            bp = _round_rows(bk)
            allocated += bp * width
            with stage(tr, "segments.pack", "task") as st:
                # The knot axis gets its own (smaller) 128-multiple
                # width: raw observations are ~5-8x sparser than the 1 Hz
                # output grid, so tying knots to the output bucket would
                # waste most of the interp kernel's mask matmul.
                kn = -(-max(records[gi].n for gi in idxs) // 128) * 128
                t_in = np.zeros((bp, kn), np.float32)
                v_in = np.zeros((bp, 3, kn), np.float32)
                count_in = np.full((bp,), 2, np.int32)
                t_out = np.zeros((bp, width), np.float32)
                count_out = np.ones((bp,), np.int32)
                # Benign padding rows: strictly increasing knots, zero
                # values.
                t_in[bk:] = np.arange(kn, dtype=np.float32)[None, :]
                for r, gi in enumerate(idxs):
                    rec = records[gi]
                    n, m = rec.n, rec.m
                    t0 = rec.t[0]
                    t_in[r, :n] = rec.t - t0
                    t_in[r, n:] = (rec.t[-1] - t0) + np.arange(1, kn - n + 1)
                    v_in[r, 0, :n] = rec.lat
                    v_in[r, 1, :n] = rec.lon
                    v_in[r, 2, :n] = rec.alt
                    # hold last value through padding (keeps interp
                    # defined)
                    v_in[r, :, n:] = v_in[r, :, n - 1:n]
                    count_in[r] = n
                    t_out[r, :m] = np.arange(m) * RESAMPLE_DT_S
                    t_out[r, m:] = t_out[r, m - 1]
                    count_out[r] = m
                if tr is not None:
                    st.extra = {"rows": bk, "width": width}
            with stage(tr, "segments.device", "task") as st:
                args = (t_in, v_in, count_in, t_out, count_out, may_span)
                counters = {"valid": int(count_out[:bk].sum()),
                            "allocated": bp * width}
                # The device buffers are freed inside serve_device, in
                # this span; a worker process's owner fetches for it.
                fetched.append((idxs, self.serve_device(*args)
                                if self._device is None
                                else self._device(args, counters)))
                if tr is not None:
                    st.extra = counters

        with stage(tr, "segments.reassemble", "task") as st:
            out_list, gathers = self._reassemble(items, records, buckets,
                                                 fetched, allocated)
            del fetched     # the bucket planes are freed in this span too
            if tr is not None:
                st.extra = {"segments": len(records), "gathers": gathers}
        return out_list

    def _reassemble(self, items, records, buckets, fetched, allocated
                    ) -> tuple[list[ProcessedSegments], int]:
        """Fetched bucket planes -> per-archive ProcessedSegments, with
        airspace classes and :attr:`last_stats`; also returns the number
        of gather-assignments made.

        The numpy work scales with buckets, not rows.  Archives are
        grouped by ``wmax``, the widest bucket among their segments (at
        most ``len(BUCKET_SIZES)`` groups).  Each group gets one zeroed
        ``(plane, rows, wmax)`` block, filled by one gather-assignment
        per plane and (source bucket, group) pair; columns past a
        segment's own bucket width stay zero.  Each archive's planes are
        its contiguous row slice of its group's block: C-contiguous,
        writable, and sharing no row with another archive."""
        # Place each archive in its group, in archive order: its rows are
        # contiguous there.  Index bookkeeping is plain Python; numpy
        # runs once per plane and (bucket, group) pair.
        widths = [rec.width for rec in records]
        ms = [rec.m for rec in records]
        placed: list = []   # per archive: (first record, n, wmax, first row)
        group_m: dict[int, list[int]] = {}   # wmax -> its rows' counts
        dst_of: list[tuple[int, int]] = []   # per record: (wmax, row)
        s = 0
        for _, segs in items:
            n = len(segs)
            if not n:
                placed.append(None)
                continue
            w = max(widths[s:s + n])
            m = group_m.setdefault(w, [])
            placed.append((s, n, w, len(m)))
            dst_of.extend((w, r) for r in range(len(m), len(m) + n))
            m.extend(ms[s:s + n])
            s += n
        # One allocation per group, not one per plane.
        groups = {w: np.zeros((len(_PLANE_ATTRS), len(m), w), np.float32)
                  for w, m in group_m.items()}

        gathers = 0
        order: list[int] = []               # records in bucket order
        lat0, lon0 = [], []
        for idxs, host in fetched:
            order.extend(idxs)
            lat0.append(host["lat"][:len(idxs), 0])
            lon0.append(host["lon"][:len(idxs), 0])
            pairs: dict[int, tuple[list[int], list[int]]] = {}
            for r, gi in enumerate(idxs):
                w, row = dst_of[gi]
                if w not in pairs:
                    pairs[w] = ([], [])
                pairs[w][0].append(r)
                pairs[w][1].append(row)
            width = host["lat"].shape[1]
            for w, (src, dst) in pairs.items():
                src, dst = _row_index(src), _row_index(dst)
                for p, (plane, _) in enumerate(_PLANE_ATTRS):
                    groups[w][p, dst, :width] = host[plane][src]
                gathers += 1

        # Airspace class for every segment in one vectorized query over
        # the start points, then back into record order.
        airspace: list = [None] * len(records)
        if records:
            for gi, cls in zip(order, self._airspace_classes(
                    np.concatenate(lat0), np.concatenate(lon0))):
                airspace[gi] = cls
        names = [rec.name for rec in records]

        valid = sum(ms)
        bucket_rows: dict[int, int] = {}
        for (width, _), ix in buckets.items():
            bucket_rows[int(width)] = bucket_rows.get(int(width), 0) \
                + len(ix)
        self.last_stats = _pipeline_stats(
            self.backend, len(records), int(valid),
            int(allocated), bucket_rows, len(buckets))

        counts = {w: np.array(m, np.int32) for w, m in group_m.items()}
        out_list: list[ProcessedSegments] = []
        for place in placed:
            if place is None:
                out_list.append(_empty())
                continue
            s, n, w, r = place
            own = slice(r, r + n)
            out_list.append(ProcessedSegments(
                icao24=names[s:s + n], count=counts[w][own],
                airspace=airspace[s:s + n],
                **{attr: groups[w][p, own]
                   for p, (_, attr) in enumerate(_PLANE_ATTRS)}))
        return out_list, gathers

    # -- airspace ---------------------------------------------------------

    def _airspace_classes(self, lat0: np.ndarray,
                          lon0: np.ndarray) -> list[str]:
        """Class of the nearest aerodrome within the terminal radius for
        every segment at once (one (B, A) argmin), else 'G' (uncontrolled,
        below Class E floors — good enough a proxy)."""
        lat0 = np.atleast_1d(np.asarray(lat0, np.float64))
        lon0 = np.atleast_1d(np.asarray(lon0, np.float64))
        if not self.aerodromes:
            return ["G"] * len(lat0)
        d2 = ((self._aero_lat[None, :] - lat0[:, None]) ** 2
              + ((self._aero_lon[None, :] - lon0[:, None])
                 * np.cos(np.deg2rad(lat0))[:, None]) ** 2)
        nearest = np.argmin(d2, axis=1)
        best = d2[np.arange(len(lat0)), nearest]
        return [self._aero_cls[i] if b <= RADIUS_DEG ** 2 else "G"
                for i, b in zip(nearest, best)]

    def _airspace_class(self, lat: float, lon: float) -> str:
        return self._airspace_classes(np.array([lat]), np.array([lon]))[0]


def _pipeline_stats(backend: str, n_segments: int, valid: int,
                    allocated: int, bucket_rows: dict,
                    pipeline_calls: int) -> dict:
    """Padding accounting for one ``_process_many`` batch.

    ``padded_fraction`` is the padding-to-payload ratio — padded output
    elements per *valid* output element (0 = no padding; this is the
    quantity that multiplies wasted kernel compute).  ``padded_share``
    is the share of the allocated tile that is padding (in [0, 1))."""
    padded = allocated - valid
    return {
        "backend": backend,
        "n_segments": n_segments, "valid_points": valid,
        "allocated_points": allocated,
        "padded_fraction": padded / valid if valid else 0.0,
        "padded_share": padded / allocated if allocated else 0.0,
        "bucket_rows": bucket_rows,
        "pipeline_calls": pipeline_calls,
    }


@functools.cache
def _malloc_trim():
    """glibc's ``malloc_trim``, or None under another C library."""
    fn = getattr(ctypes.CDLL(None), "malloc_trim", None)
    if fn is not None:
        fn.argtypes = [ctypes.c_size_t]
        fn.restype = ctypes.c_int
    return fn


def _release_free_heap() -> None:
    """Return the free pages of the C heap to the OS.  Each worker
    thread allocates from its own glibc arena, which keeps the pages of
    a message's freed transients (read columns, packing buffers, fetched
    bucket planes) resident; without this the arenas of a run's worker
    threads grow pass after pass, past a worker's memory slot."""
    fn = _malloc_trim()
    if fn is not None:
        fn(0)


def _row_index(rows: list[int]):
    """Strictly ascending rows -> a slice where they are consecutive (a
    view, not a gathered copy), else an index array."""
    if rows[-1] - rows[0] == len(rows) - 1:
        return slice(rows[0], rows[-1] + 1)
    return np.array(rows, np.intp)


def _empty() -> ProcessedSegments:
    z = np.zeros((0, BUCKET_SIZES[0]), np.float32)
    return ProcessedSegments(
        icao24=[], times=z, lat=z, lon=z, alt_msl_m=z, alt_agl_m=z,
        vrate_ms=z, gspeed_ms=z, heading_rad=z, turn_rad_s=z,
        count=np.zeros((0,), np.int32), airspace=[])


def segment_tasks_from_archive_tree(archive_root: str) -> list[Task]:
    """One Task per aircraft .zip archive."""
    tasks = []
    for dirpath, _dirnames, filenames in os.walk(archive_root):
        for f in filenames:
            if f.endswith(".zip"):
                p = os.path.join(dirpath, f)
                tasks.append(Task(
                    task_id=os.path.relpath(p, archive_root),
                    size_bytes=os.path.getsize(p),
                    payload=p))
    tasks.sort(key=lambda t: t.task_id)
    return tasks


#: Index bytes per stored observation point (4 f64 columns + codes);
#: sizes store-backed tasks for largest-first organization.
_STORE_BYTES_PER_POINT = 36


def segment_tasks_from_store(store_root: str,
                             granularity: str = "shard",
                             rows_per_task: int = 4) -> list[Task]:
    """Store-backed processing tasks, sized from the index alone.

    ``granularity='shard'``: one Task per shard — a worker's ASSIGN
    batch maps 1:1 onto shard reads, so the prefetching reader streams
    whole shards to the fused pipeline.  ``granularity='track'``: one
    Task per track — drop-in parity with
    :func:`segment_tasks_from_archive_tree` task ids (the golden
    store-vs-zip equivalence tests rely on that).
    ``granularity='rows'``: one Task per ``rows_per_task`` consecutive
    rows of a shard (``store://...#shard=<id>&rows=a:b`` payloads),
    sized via :meth:`repro.store.format.StoreManifest.row_range_bytes`
    — the grain the ``shard_affinity`` scheduling policy groups by, so
    one worker streams a shard's ranges back-to-back off one decode.
    """
    from repro.store.format import StoreManifest
    from repro.store.reader import make_store_uri

    if granularity not in ("shard", "track", "rows"):
        raise ValueError(f"unknown granularity {granularity!r}")
    manifest = StoreManifest.load(store_root)
    tasks = []
    if granularity == "shard":
        for s in manifest.shards:
            tasks.append(Task(
                task_id=f"store/{s.shard_id}",
                size_bytes=s.n_points * _STORE_BYTES_PER_POINT,
                payload=make_store_uri(store_root, shard=s.shard_id)))
    elif granularity == "rows":
        if rows_per_task < 1:
            raise ValueError("rows_per_task must be >= 1")
        for s in manifest.shards:
            n_rows = len(manifest.tracks_in(s.shard_id))
            for a in range(0, n_rows, rows_per_task):
                b = min(a + rows_per_task, n_rows)
                tasks.append(Task(
                    task_id=f"store/{s.shard_id}/r{a:05d}",
                    size_bytes=manifest.row_range_bytes(s.shard_id, a, b),
                    payload=make_store_uri(store_root, shard=s.shard_id,
                                           rows=f"{a}:{b}")))
    else:
        for t in manifest.tracks:
            tasks.append(Task(
                task_id=t.track_id,
                size_bytes=t.n_obs * _STORE_BYTES_PER_POINT,
                payload=make_store_uri(store_root, track=t.track_id)))
    tasks.sort(key=lambda t: t.task_id)
    return tasks
