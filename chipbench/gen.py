"""Traffic generation: a deployment's observations from a seed.

A cell names a configuration (``configs/<name>.json``: the deployment,
its track shapes and its density) and a traffic mix
(``traffic/<mix>.json``: which phase runs and how the deployment is
cut).  :func:`make_tracks` turns the two and ``--seed`` into one
:class:`Tracks` table, :func:`write_csv_tree` writes it as one
OpenSky-style CSV per track, which the program's own ingest
(``repro.store.writer.build_store``) turns into the columnar store.

The configuration's ``generator.kind`` and the cut's one key name the
module that makes the tracks, ``chipbench/generators/<kind>_<cut>.py``
(see :mod:`chipbench.generators`); this module holds what they share.

Every value is quantised to the decimals that the CSV prints, so the
numbers the store parses back are bit-for-bit the numbers the
reference computes from.  This module imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import importlib
import os

import numpy as np

M_PER_DEG = 111_111.0

#: CSV header: OpenSky state-vector columns, in the program's order.
COLUMNS = ("time", "icao24", "lat", "lon", "velocity", "heading",
           "vertrate", "baroaltitude", "geoaltitude", "onground")
HEADER = ",".join(COLUMNS) + "\n"

#: Fixed-width fields of one row: (column, integer digits, decimals,
#: signed).  Zero-padded numbers parse as the plain ones do; every row
#: is ROW_BYTES long, so the store's shard planner (80 bytes per
#: observation) cuts shards of exactly its target point count.
_FIELDS = (("time", 9, 2, False), ("lat", 2, 5, True), ("lon", 3, 5, True),
           ("velocity", 3, 1, False), ("heading", 3, 1, False),
           ("vertrate", 2, 2, True), ("baroaltitude", 5, 1, True),
           ("geoaltitude", 5, 1, True))
ROW_BYTES = 80

#: Decimals of the columns the reference reads back.
DECIMALS = {"time": 2, "lat": 5, "lon": 5, "geoaltitude": 1}


@dataclasses.dataclass
class Tracks:
    """Observation columns of many tracks, concatenated in id order."""

    ids: list            # track ids, sorted; the CSV's relative path
    offsets: np.ndarray  # (n + 1,) row offsets
    cols: dict           # column name -> (rows,) float64 (icao24: str)

    def __len__(self) -> int:
        return len(self.ids)

    @property
    def n_obs(self) -> int:
        return int(self.offsets[-1])

    def track(self, i: int) -> dict:
        sl = slice(int(self.offsets[i]), int(self.offsets[i + 1]))
        return {k: self.cols[k][sl] for k in ("time", "lat", "lon",
                                              "geoaltitude")}

    def take(self, keep: np.ndarray) -> "Tracks":
        """The tracks whose index is in ``keep`` (sorted), renumbered."""
        keep = np.asarray(keep, np.int64)
        lens = np.diff(self.offsets)[keep]
        rows = _ranges(self.offsets[keep], lens)
        return Tracks(ids=[self.ids[i] for i in keep],
                      offsets=np.concatenate([[0], np.cumsum(lens)]),
                      cols={k: v[rows] for k, v in self.cols.items()})


def _ranges(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Concatenated ``arange(s, s + n)`` for each (s, n)."""
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    first = np.repeat(np.cumsum(lens) - lens, lens)
    return np.repeat(starts, lens) + np.arange(total) - first


def seg_cumsum(x: np.ndarray, offsets: np.ndarray) -> np.ndarray:
    """Cumulative sum of ``x`` restarting at every offset."""
    c = np.cumsum(x)
    lens = np.diff(offsets)
    base = np.concatenate([[0.0], c])[offsets[:-1]]
    return c - np.repeat(base, lens)


def _quantise(x: np.ndarray, decimals: int) -> np.ndarray:
    """The double that the ``%.{decimals}f`` text of ``x`` parses to."""
    scale = 10.0 ** decimals
    return np.rint(x * scale) / scale


def _shard_prefix(n_obs: np.ndarray, shards: int, target: int) -> int:
    """How many leading tracks fill exactly ``shards`` shards under the
    store planner's greedy cut (``plan_shards``: a track's point
    estimate is its file bytes over 80)."""
    est = (len(HEADER) + n_obs * ROW_BYTES) // ROW_BYTES
    cur, cut = 0, 0
    for i, e in enumerate(est.tolist()):
        if cur and cur + e > target:
            cut += 1
            cur = 0
            if cut == shards:
                return i
        cur += e
    raise ValueError(f"the generated pool fills only {cut} of {shards} "
                     f"shards; enlarge it")


def concat(parts: list) -> Tracks:
    """The tracks of ``parts``, one after another."""
    offs, base = [np.zeros(1, np.int64)], 0
    for p in parts:
        offs.append(p.offsets[1:] + base)
        base += p.n_obs
    return Tracks(ids=[i for p in parts for i in p.ids],
                  offsets=np.concatenate(offs),
                  cols={k: np.concatenate([p.cols[k] for p in parts])
                        for k in parts[0].cols})


def first_shards(tracks: Tracks, shards: int, target: int) -> Tracks:
    """The leading tracks that fill exactly ``shards`` store shards."""
    n = _shard_prefix(np.diff(tracks.offsets), int(shards), target)
    return tracks.take(np.arange(n))


def check_shards(shards: int, manifest) -> None:
    """Raise unless the built store has ``shards`` shards."""
    if len(manifest.shards) != shards:
        raise RuntimeError(f"the store has {len(manifest.shards)} shards, "
                           f"the cut asks for {shards}")


# ---------------------------------------------------------------------------
# the entry point
# ---------------------------------------------------------------------------

def _cut(config: dict, traffic: dict) -> tuple:
    """The generator module of the cell's kind and cut, and the cut's
    value."""
    (cut, value), = traffic["cut"].items()
    name = f"{config['generator']['kind']}_{cut}"
    try:
        mod = importlib.import_module(f"chipbench.generators.{name}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no generator chipbench/generators/{name}.py for "
                         f"this configuration and cut") from e
    return mod, value


def make_tracks(config: dict, traffic: dict, seed: int) -> Tracks:
    """The cell's tracks: configuration ``config``, cut by ``traffic``."""
    mod, value = _cut(config, traffic)
    rng = np.random.default_rng(int(seed) % 2 ** 64)
    tracks = mod.make(config["generator"], value, rng,
                      int(config["deployment"]["shard_points"]))
    for col, dec in DECIMALS.items():
        tracks.cols[col] = _quantise(tracks.cols[col], dec)
    base = int(config["generator"]["icao_base"], 16)
    tracks.cols["icao24"] = np.repeat(
        np.array([f"{base + i:06x}" for i in range(len(tracks))]),
        np.diff(tracks.offsets))
    return tracks


def check_store(config: dict, traffic: dict, manifest) -> None:
    """Raise if the built store is not what the cell's cut asks for."""
    mod, value = _cut(config, traffic)
    check = getattr(mod, "check_store", None)
    if check is not None:
        check(value, manifest)


# ---------------------------------------------------------------------------
# CSV
# ---------------------------------------------------------------------------

def _digits(k: np.ndarray, width: int) -> np.ndarray:
    """(n, width) ASCII digits of non-negative ints, zero padded."""
    out = np.empty((len(k), width), np.uint8)
    k = k.copy()
    for j in range(width - 1, -1, -1):
        out[:, j] = 48 + (k % 10)
        k //= 10
    if k.any():
        raise ValueError(f"a value needs more than {width} digits")
    return out


def csv_rows(tracks: Tracks) -> np.ndarray:
    """Every row of every track as one (rows, ROW_BYTES) byte array."""
    n = tracks.n_obs
    parts = []

    def sep():
        parts.append(np.full((n, 1), ord(","), np.uint8))

    for col, n_int, n_dec, signed in _FIELDS:
        if col == "lat":
            icao = np.frombuffer(
                tracks.cols["icao24"].astype("S6").tobytes(),
                np.uint8).reshape(n, 6)
            parts.append(icao)
            sep()
        x = np.rint(np.asarray(tracks.cols[col]) * 10.0 ** n_dec)
        k = np.abs(x).astype(np.int64)
        if signed:
            parts.append(np.where(x < 0, ord("-"), ord("+")).astype(
                np.uint8)[:, None])
        d = _digits(k, n_int + n_dec)
        parts.append(d[:, :n_int])
        parts.append(np.full((n, 1), ord("."), np.uint8))
        parts.append(d[:, n_int:])
        sep()
    parts.append(np.full((n, 1), ord("0"), np.uint8))       # onground
    parts.append(np.full((n, 1), ord("\n"), np.uint8))
    rows = np.concatenate(parts, axis=1)
    if rows.shape[1] != ROW_BYTES:
        raise AssertionError(f"row width {rows.shape[1]} != {ROW_BYTES}")
    return rows


def write_csv_tree(tracks: Tracks, root: str) -> int:
    """One CSV per track under ``root``; returns the bytes written."""
    os.makedirs(root, exist_ok=True)
    buf = csv_rows(tracks).tobytes()
    head = HEADER.encode()
    total = 0
    offs = tracks.offsets.tolist()
    for i, tid in enumerate(tracks.ids):
        body = buf[offs[i] * ROW_BYTES:offs[i + 1] * ROW_BYTES]
        with open(os.path.join(root, tid), "wb") as f:
            f.write(head)
            f.write(body)
        total += len(head) + len(body)
    return total
