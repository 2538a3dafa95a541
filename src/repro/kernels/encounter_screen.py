"""Fused pairwise encounter screen: per-cell miss distances on device.

The screening workload (ROADMAP "encounter-screening workload") takes
the spatial-hash cells produced by :mod:`repro.geometry.gridhash` and,
within each cell, computes the pairwise horizontal/vertical separation
of every row pair over their time-aligned sample grids, emitting
*candidate encounters* — pairs that are simultaneously inside both
thresholds at some jointly valid instant.

Three numerically identical execution paths share one chunked pair
trace (:func:`_chunk_minima`):

  * ``backend="pallas"`` — the fused kernel: one program per
    (cell, 128-sample time chunk, 8-row block), folding each chunk into
    (K, K) running minima that stay resident in VMEM for the whole
    cell, so VMEM use does not grow with the cell's time span.
    Compiled on a TPU, interpreted elsewhere
    (:func:`repro.device.interpret_kernels`).
  * ``backend="jit"`` — the same chunked trace XLA-compiled over the
    whole (C, K, T) batch; the production CPU path.
  * ``backend="ref"`` — :func:`repro.kernels.ref.encounter_screen_ref`
    vmapped over cells (full-broadcast oracle; tests and tiny cells).

Cells are batched with the ``segment_pipeline`` bucket machinery: rows
round to multiples of 8 (:func:`repro.tracks.segments._round_rows`),
time to 128-sample widths (:func:`repro.tracks.segments.bucket_width`
for spans inside ``MAX_SEG_POINTS``), so a handful of compiled shapes
cover arbitrary cell populations.  Empty and singleton cells never
reach the kernel at all (there is no pair to screen) — asserted by the
``cells_skipped`` / ``kernel_calls`` counters in
:func:`get_screen_stats`.

Candidate records are plain dicts, canonically ordered so every path
(grid vs. brute force, barrier vs. streaming DAG) yields byte-identical
serializations: ``{"a", "b", "t_s", "h_m", "v_m"}`` with ``a < b``
(row ids), deduplicated across the multiple cells a pair may share.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.device import interpret_kernels
from repro.geometry.gridhash import CellKey, GridSpec, bin_samples
from repro.kernels.ref import encounter_screen_ref
from repro.tracks.segments import BUCKET_SIZES, _round_rows, bucket_width

__all__ = [
    "ScreenConfig", "ScreenRow", "rows_from_track", "bin_screen_rows",
    "screen_aligned", "screen_cells", "screen_rows_grid",
    "brute_force_screen", "dedup_candidates",
    "get_screen_stats", "reset_screen_stats",
]

_BIG = np.float32(1e30)
_M_PER_DEG = 111_111.0
_T_CHUNK = 128                  # lane-width time chunks
_ROW_BLOCK = 8                  # f32 sublane tile: 8 pair rows per program
_C_CHUNK_BYTES = 64 << 20       # cap jnp-path (C, K, K, Tc) intermediates


# ---------------------------------------------------------------------------
# shared chunk trace
# ---------------------------------------------------------------------------

def _chunk_minima(lat_i, lon_i, alt_i, val_i, lat_j, lon_j, alt_j, val_j,
                  tri, h_m: float, v_m: float):
    """Pair minima over one time chunk.

    ``*_i`` are (..., R, 1, Tc), ``*_j`` (..., 1, K, Tc), ``tri``
    (..., R, K, 1) bool.  Returns (hit, min_dh, argmin_dh, min_dv),
    each (..., R, K); minima are ``_BIG`` where the chunk has no hit.
    """
    m = jnp.float32(_M_PER_DEG)
    dn = (lat_i - lat_j) * m
    de = ((lon_i - lon_j) * m
          * jnp.cos(jnp.deg2rad(jnp.float32(0.5) * (lat_i + lat_j))))
    dh = jnp.sqrt(dn * dn + de * de)
    dv = jnp.abs(alt_i - alt_j)
    hit_t = ((val_i * val_j) > 0.5) & tri & (dh <= jnp.float32(h_m)) \
        & (dv <= jnp.float32(v_m))
    dh_m = jnp.where(hit_t, dh, _BIG)
    dv_m = jnp.where(hit_t, dv, _BIG)
    return (jnp.max(hit_t.astype(jnp.float32), axis=-1),
            jnp.min(dh_m, axis=-1),
            jnp.argmin(dh_m, axis=-1).astype(jnp.int32),
            jnp.min(dv_m, axis=-1))


def _fold_chunk(carry, chunk, t_base):
    """Fold one chunk's minima into the running (hit, dh, dv, ti) carry.

    Strict ``<`` on the running min keeps the *first* time index
    attaining the global minimum — bitwise-identical to the oracle's
    single ``argmin`` over the full time axis.
    """
    hit, mdh, mdv, tix = carry
    c_hit, c_dh, c_arg, c_dv = chunk
    better = c_dh < mdh
    return (jnp.maximum(hit, c_hit),
            jnp.where(better, c_dh, mdh),
            jnp.minimum(mdv, c_dv),
            jnp.where(better, (c_arg + t_base).astype(jnp.float32), tix))


# ---------------------------------------------------------------------------
# pallas kernel
# ---------------------------------------------------------------------------

def _screen_kernel(lat_ref, lon_ref, alt_ref, val_ref,
                   hit_ref, dh_ref, dv_ref, ti_ref, *,
                   h_m: float, v_m: float, rb: int, tc: int):
    # Grid (cell, time chunk, row block).  Input blocks are one (K, tc)
    # time chunk of the cell; the (K, K) outputs stay resident in VMEM
    # across the chunk and row-block axes and carry the running minima.
    tk = pl.program_id(1)
    i0 = pl.multiple_of(pl.program_id(2) * rb, rb)
    rows = pl.ds(i0, rb)
    K = lat_ref.shape[1]

    @pl.when(tk == 0)
    def _init():
        hit_ref[0, rows, :] = jnp.zeros((rb, K), jnp.float32)
        dh_ref[0, rows, :] = jnp.full((rb, K), _BIG, jnp.float32)
        dv_ref[0, rows, :] = jnp.full((rb, K), _BIG, jnp.float32)
        ti_ref[0, rows, :] = jnp.zeros((rb, K), jnp.float32)

    shape3 = (rb, K, lat_ref.shape[2])
    tri = (i0 + jax.lax.broadcasted_iota(jnp.int32, shape3, 0)
           < jax.lax.broadcasted_iota(jnp.int32, shape3, 1))

    def ci(ref):        # (rb, 1, tc)
        return ref[0, rows, :][:, None, :]

    def cj(ref):        # (1, K, tc)
        return ref[0][None, :, :]

    chunk = _chunk_minima(ci(lat_ref), ci(lon_ref), ci(alt_ref),
                          ci(val_ref), cj(lat_ref), cj(lon_ref),
                          cj(alt_ref), cj(val_ref), tri, h_m, v_m)
    carry = (hit_ref[0, rows, :], dh_ref[0, rows, :], dv_ref[0, rows, :],
             ti_ref[0, rows, :])
    hit, mdh, mdv, tix = _fold_chunk(carry, chunk, tk * tc)
    hit_ref[0, rows, :] = hit
    dh_ref[0, rows, :] = mdh
    dv_ref[0, rows, :] = mdv
    ti_ref[0, rows, :] = tix


def _screen_batch_pallas(lat, lon, alt, val, *, h_m, v_m, interpret):
    C, K, T = lat.shape
    rb, tc = _ROW_BLOCK, min(_T_CHUNK, T)
    in_spec = pl.BlockSpec((1, K, tc), lambda c, t, i: (c, 0, t))
    out_spec = pl.BlockSpec((1, K, K), lambda c, t, i: (c, 0, 0))
    shape = jax.ShapeDtypeStruct((C, K, K), jnp.float32)
    return pl.pallas_call(
        functools.partial(_screen_kernel, h_m=h_m, v_m=v_m, rb=rb, tc=tc),
        grid=(C, T // tc, K // rb),
        in_specs=[in_spec] * 4,
        out_specs=[out_spec] * 4,
        out_shape=[shape] * 4,
        interpret=interpret,
    )(lat, lon, alt, val)


# ---------------------------------------------------------------------------
# jnp (XLA) path — same chunked trace over the whole batch
# ---------------------------------------------------------------------------

def _screen_batch_jnp(lat, lon, alt, val, *, h_m, v_m):
    C, K, T = lat.shape
    tc = min(_T_CHUNK, T)
    tri = (jnp.arange(K)[:, None] < jnp.arange(K)[None, :])[None, :, :, None]

    def body(c, carry):
        t0 = c * tc

        def sl(x):
            return jax.lax.dynamic_slice_in_dim(x, t0, tc, axis=2)

        la, lo, al, va = sl(lat), sl(lon), sl(alt), sl(val)
        chunk = _chunk_minima(
            la[:, :, None, :], lo[:, :, None, :], al[:, :, None, :],
            va[:, :, None, :], la[:, None, :, :], lo[:, None, :, :],
            al[:, None, :, :], va[:, None, :, :], tri, h_m, v_m)
        return _fold_chunk(carry, chunk, t0)

    init = (jnp.zeros((C, K, K), jnp.float32),
            jnp.full((C, K, K), _BIG, jnp.float32),
            jnp.full((C, K, K), _BIG, jnp.float32),
            jnp.zeros((C, K, K), jnp.float32))
    return jax.lax.fori_loop(0, T // tc, body, init)


def _screen_batch_ref(lat, lon, alt, val, *, h_m, v_m):
    fn = functools.partial(encounter_screen_ref,
                           h_thresh_m=h_m, v_thresh_m=v_m)
    return jax.vmap(fn)(lat, lon, alt, val)


@functools.lru_cache(maxsize=None)
def _jitted(C: int, K: int, T: int, h_m: float, v_m: float,
            backend: str, interpret: bool):
    """One compiled screen per padded batch shape + thresholds."""
    if backend == "pallas":
        fn = functools.partial(_screen_batch_pallas, h_m=h_m, v_m=v_m,
                               interpret=interpret)
    elif backend == "jit":
        fn = functools.partial(_screen_batch_jnp, h_m=h_m, v_m=v_m)
    elif backend == "ref":
        fn = functools.partial(_screen_batch_ref, h_m=h_m, v_m=v_m)
    else:
        raise ValueError(f"unknown screen backend {backend!r}")
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------

_STATS: Dict[str, float] = {}


def reset_screen_stats() -> None:
    _STATS.clear()
    _STATS.update(kernel_calls=0, cells_screened=0, cells_skipped=0,
                  pairs_screened=0, padded_cells=0)


def get_screen_stats() -> dict:
    if not _STATS:
        reset_screen_stats()
    return dict(_STATS)


reset_screen_stats()


# ---------------------------------------------------------------------------
# batched screening over padded (C, K, T) arrays
# ---------------------------------------------------------------------------

def screen_aligned(lat, lon, alt, valid, *, h_thresh_m: float,
                   v_thresh_m: float, backend: str = "jit") -> dict:
    """Screen a (C, K, T) batch of time-aligned cells.

    Pads rows to the 8-row tile, time to 128-sample chunks, and the
    cell axis to a bounded set of bucket sizes, then dispatches to the
    requested backend.  Returns ``{"hit", "min_dh", "min_dv", "t_idx"}``
    as (C, K, K) float32 numpy arrays (strict upper triangle).
    """
    lat = np.asarray(lat, np.float32)
    C, K, T = lat.shape
    Kp = max(_ROW_BLOCK, _round_rows(K))
    Tp = -(-T // _T_CHUNK) * _T_CHUNK
    interp = interpret_kernels()

    def pad(x, fill=0.0):
        out = np.full((C, Kp, Tp), fill, np.float32)
        out[:, :K, :T] = np.asarray(x, np.float32)
        return out

    latp, lonp = pad(lat), pad(lon)
    altp, valp = pad(alt), pad(valid)

    c_max = max(1, _C_CHUNK_BYTES // (Kp * Kp * min(_T_CHUNK, Tp) * 4))
    outs = [np.empty((C, Kp, Kp), np.float32) for _ in range(4)]
    done = 0
    while done < C:
        n = min(c_max, C - done)
        Cp = min(max(1, _round_rows(n)), c_max)
        sl = slice(done, done + n)

        def cpad(x):
            if Cp == n:
                return jnp.asarray(x[sl])
            out = np.zeros((Cp, Kp, Tp), np.float32)
            out[:n] = x[sl]
            return jnp.asarray(out)

        fn = _jitted(Cp, Kp, Tp, float(h_thresh_m), float(v_thresh_m),
                     backend, interp)
        res = fn(cpad(latp), cpad(lonp), cpad(altp), cpad(valp))
        for dst, arr in zip(outs, res):
            dst[sl] = np.asarray(arr)[:n]
        _STATS["kernel_calls"] += 1
        _STATS["padded_cells"] += Cp - n
        done += n
    hit, mdh, mdv, tix = (o[:, :K, :K] for o in outs)
    return {"hit": hit, "min_dh": mdh, "min_dv": mdv, "t_idx": tix}


# ---------------------------------------------------------------------------
# rows, binning, cell screening
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScreenConfig:
    """Encounter-screen thresholds and execution knobs."""

    h_thresh_m: float = 926.0   # 0.5 NM horizontal
    v_thresh_m: float = 152.4   # 500 ft vertical
    dt_s: float = 1.0           # sample grid spacing (RESAMPLE_DT_S)
    backend: str = "jit"        # pallas | jit | ref

    def __post_init__(self) -> None:
        if self.h_thresh_m <= 0 or self.v_thresh_m <= 0 or self.dt_s <= 0:
            raise ValueError("ScreenConfig values must be positive")
        if self.backend not in ("pallas", "jit", "ref"):
            raise ValueError(f"unknown screen backend {self.backend!r}")


@dataclasses.dataclass
class ScreenRow:
    """One resampled segment, anchored at an absolute start time.

    Samples sit on a uniform ``dt_s`` grid starting at ``t0``; rows
    from the same aircraft share a ``group`` and are never paired
    against each other.
    """
    row_id: str
    group: str
    t0: float
    lat: np.ndarray
    lon: np.ndarray
    alt: np.ndarray
    dt_s: float = 1.0

    def __len__(self) -> int:
        return len(self.lat)

    @property
    def times(self) -> np.ndarray:
        return self.t0 + np.arange(len(self.lat)) * self.dt_s


def rows_from_track(track_id: str, obs: dict, segs: Sequence[slice],
                    processed) -> List[ScreenRow]:
    """ProcessedSegments planes + raw observation times -> ScreenRows.

    ``processed.times`` grids are segment-relative (they start at 0);
    the absolute anchor is the raw first-observation time of each
    segment, which is what places rows on the shared screening grid.
    """
    rows = []
    for k, s in enumerate(segs):
        if k >= len(processed):
            break
        m = int(processed.count[k])
        rows.append(ScreenRow(
            row_id=f"{track_id}#s{k:03d}", group=track_id,
            t0=float(obs["time"][s.start]),
            lat=np.asarray(processed.lat[k, :m], np.float32),
            lon=np.asarray(processed.lon[k, :m], np.float32),
            alt=np.asarray(processed.alt_msl_m[k, :m], np.float32)))
    return rows


def bin_screen_rows(rows: Sequence[ScreenRow], *, grid: GridSpec,
                    config: ScreenConfig) -> Dict[CellKey, List[str]]:
    """Halo-padded cell membership (cell -> row ids) for screen rows."""
    return bin_samples(
        [(r.row_id, r.times, r.lat, r.lon, r.alt) for r in rows],
        spec=grid, h_pad_m=config.h_thresh_m, v_pad_m=config.v_thresh_m)


def _pack_cell(rows: Sequence[ScreenRow], dt: float):
    """-> (t0_cell, T, lat, lon, alt, valid) on the cell's union grid."""
    t0c = min(r.t0 for r in rows)
    starts = [int(round((r.t0 - t0c) / dt)) for r in rows]
    T = max(s + len(r) for s, r in zip(starts, rows))
    K = len(rows)
    lat = np.zeros((K, T), np.float32)
    lon = np.zeros((K, T), np.float32)
    alt = np.zeros((K, T), np.float32)
    val = np.zeros((K, T), np.float32)
    for k, (s, r) in enumerate(zip(starts, rows)):
        m = len(r)
        lat[k, s:s + m] = r.lat
        lon[k, s:s + m] = r.lon
        alt[k, s:s + m] = r.alt
        val[k, s:s + m] = 1.0
    return t0c, T, lat, lon, alt, val


def dedup_candidates(cands: Iterable[dict]) -> List[dict]:
    """Canonical candidate list: unique pairs, sorted by (a, b).

    A pair screened in several cells (or several streaming generations)
    produces identical records — the pair trace depends only on the two
    rows' absolute-time samples — so keeping the first is exact."""
    seen: Set[Tuple[str, str]] = set()
    out = []
    for c in sorted(cands, key=lambda c: (c["a"], c["b"])):
        key = (c["a"], c["b"])
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


def screen_cells(cells: Dict[CellKey, Sequence[ScreenRow]], *,
                 config: ScreenConfig,
                 new_ids: Optional[Dict[CellKey, Set[str]]] = None,
                 dedup: bool = True):
    """Screen binned cells -> (candidates, stats).

    Cells are length-bucketed — (padded rows, padded time span) — and
    batched so one kernel launch covers many same-shape cells.  Empty
    and singleton cells are skipped before any batching.  With
    ``new_ids`` (streaming-DAG generations) only pairs touching a new
    row are emitted, so unioning generations never double-screens.
    """
    dt = config.dt_s
    skipped = screened = pairs = 0
    buckets: Dict[Tuple[int, int], list] = {}
    occ_max = 0
    for key in sorted(cells):
        rows = sorted(cells[key], key=lambda r: r.row_id)
        occ_max = max(occ_max, len(rows))
        if len(rows) < 2:
            skipped += 1
            continue
        screened += 1
        pairs += len(rows) * (len(rows) - 1) // 2
        t0c, T, *planes = _pack_cell(rows, dt)
        Kp = max(_ROW_BLOCK, _round_rows(len(rows)))
        Tp = (bucket_width(T) if T <= BUCKET_SIZES[-1]
              else -(-T // _T_CHUNK) * _T_CHUNK)
        buckets.setdefault((Kp, Tp), []).append((key, rows, t0c, T, planes))

    _STATS["cells_screened"] += screened
    _STATS["cells_skipped"] += skipped
    _STATS["pairs_screened"] += pairs

    cands: List[dict] = []
    for (Kp, Tp), items in sorted(buckets.items()):
        C = len(items)
        lat = np.zeros((C, Kp, Tp), np.float32)
        lon = np.zeros((C, Kp, Tp), np.float32)
        alt = np.zeros((C, Kp, Tp), np.float32)
        val = np.zeros((C, Kp, Tp), np.float32)
        for c, (_, rows, _, T, planes) in enumerate(items):
            K = len(rows)
            lat[c, :K, :T], lon[c, :K, :T] = planes[0], planes[1]
            alt[c, :K, :T], val[c, :K, :T] = planes[2], planes[3]
        res = screen_aligned(lat, lon, alt, val,
                             h_thresh_m=config.h_thresh_m,
                             v_thresh_m=config.v_thresh_m,
                             backend=config.backend)
        for c, (key, rows, t0c, _, _) in enumerate(items):
            fresh = None if new_ids is None else new_ids.get(key, set())
            ii, jj = np.nonzero(res["hit"][c] > 0.5)
            for i, j in zip(ii.tolist(), jj.tolist()):
                if i >= len(rows) or j >= len(rows):
                    continue
                a, b = rows[i], rows[j]
                if a.group == b.group:
                    continue
                if fresh is not None and a.row_id not in fresh \
                        and b.row_id not in fresh:
                    continue
                cands.append({
                    "a": a.row_id, "b": b.row_id,
                    "t_s": float(t0c + float(res["t_idx"][c, i, j]) * dt),
                    "h_m": float(res["min_dh"][c, i, j]),
                    "v_m": float(res["min_dv"][c, i, j]),
                })
    stats = {
        "cells": screened + skipped,
        "cells_screened": screened,
        "cells_skipped": skipped,
        "pairs_screened": pairs,
        "max_occupancy": occ_max,
        "candidates_raw": len(cands),
    }
    if dedup:
        cands = dedup_candidates(cands)
    stats["candidates"] = len(cands)
    return cands, stats


def screen_rows_grid(rows: Sequence[ScreenRow], *, grid: GridSpec,
                     config: ScreenConfig):
    """Bin rows into the spatial hash and screen every multi-row cell."""
    by_id = {r.row_id: r for r in rows}
    bins = bin_screen_rows(rows, grid=grid, config=config)
    cells = {key: [by_id[i] for i in ids] for key, ids in bins.items()}
    return screen_cells(cells, config=config)


# ---------------------------------------------------------------------------
# numpy brute-force reference (the baseline the kernel must beat)
# ---------------------------------------------------------------------------

def brute_force_screen(rows: Sequence[ScreenRow], *,
                       config: ScreenConfig) -> List[dict]:
    """All-pairs numpy screen on one global time grid — O(N^2 * T).

    No spatial pruning, no device: this is both the exactness reference
    (the grid + kernel path must emit the identical candidate set) and
    the speedup baseline in ``repro.bench.encounters``.
    """
    rows = sorted(rows, key=lambda r: r.row_id)
    if len(rows) < 2:
        return []
    dt = config.dt_s
    t0g = min(r.t0 for r in rows)
    starts = [int(round((r.t0 - t0g) / dt)) for r in rows]
    T = max(s + len(r) for s, r in zip(starts, rows))
    N = len(rows)
    lat = np.zeros((N, T), np.float32)
    lon = np.zeros((N, T), np.float32)
    alt = np.zeros((N, T), np.float32)
    val = np.zeros((N, T), bool)
    for k, (s, r) in enumerate(zip(starts, rows)):
        m = len(r)
        lat[k, s:s + m] = r.lat
        lon[k, s:s + m] = r.lon
        alt[k, s:s + m] = r.alt
        val[k, s:s + m] = True
    groups = np.array([r.group for r in rows])
    m_per_deg = np.float32(_M_PER_DEG)
    h_t = np.float32(config.h_thresh_m)
    v_t = np.float32(config.v_thresh_m)
    out = []
    for i in range(N - 1):
        lj = lat[i + 1:]
        dn = (lat[i][None, :] - lj) * m_per_deg
        de = ((lon[i][None, :] - lon[i + 1:]) * m_per_deg
              * np.cos(np.deg2rad(np.float32(0.5) * (lat[i][None, :] + lj))))
        dh = np.sqrt(dn * dn + de * de)
        dv = np.abs(alt[i][None, :] - alt[i + 1:])
        hit_t = (val[i][None, :] & val[i + 1:]
                 & (dh <= h_t) & (dv <= v_t)
                 & (groups[i + 1:] != groups[i])[:, None])
        js = np.nonzero(hit_t.any(axis=1))[0]
        for j in js.tolist():
            dh_m = np.where(hit_t[j], dh[j], _BIG)
            dv_m = np.where(hit_t[j], dv[j], _BIG)
            ti = int(np.argmin(dh_m))
            out.append({
                "a": rows[i].row_id, "b": rows[i + 1 + j].row_id,
                "t_s": float(t0g + ti * dt),
                "h_m": float(dh_m[ti]),
                "v_m": float(np.min(dv_m)),
            })
    return dedup_candidates(out)
