"""A small store whose shards hold dozens of row-group units, for the
tests of unit reads (``tests/test_store.py``,
``tests/test_segment_pipeline.py`` through the ``block_store`` fixture of
``conftest.py``), and the version-2 layout of a shard's columns."""

import os

import numpy as np
import pytest

#: Rows per unit of the ``block_store`` fixture: small, so that its
#: shards hold dozens of units and many tracks straddle a unit edge.
SMALL_BLOCK = 64


def write_track_csvs(root: str, n_tracks: int, seed: int) -> None:
    """``n_tracks`` short synthetic tracks (12-40 observations 5-10 s
    apart, some with a gap over the 120-s segment gap), one CSV each."""
    rng = np.random.default_rng(seed)
    os.makedirs(root, exist_ok=True)
    for i in range(n_tracks):
        n = int(rng.integers(12, 41))
        t = 1000.0 * i + np.cumsum(rng.integers(5, 11, n)).astype(float)
        if rng.random() < 0.2:
            t[n // 2:] += 200.0
        lat = 35.0 + rng.normal(0.0, 0.01) + np.cumsum(
            rng.normal(0.0, 1e-3, n))
        lon = -98.0 + rng.normal(0.0, 0.01) + np.cumsum(
            rng.normal(0.0, 1e-3, n))
        alt = 500.0 + np.cumsum(rng.normal(0.0, 5.0, n))
        rows = "".join(f"{a:.2f},{0xd00000 + i:06x},{b:.5f},{c:.5f},"
                       f"{d:.1f}\n" for a, b, c, d in zip(t, lat, lon, alt))
        with open(os.path.join(root, f"t{i:05d}.csv"), "w") as f:
            f.write("time,icao24,lat,lon,geoaltitude\n" + rows)


def build_block_store(root: str, n_tracks: int = 320, seed: int = 11,
                      target_points: int = 2048) -> str:
    """A store of ``n_tracks`` synthetic tracks in shards of row blocks
    of :data:`SMALL_BLOCK` points; returns its root."""
    from repro.store import build_store, writer
    csv_dir = os.path.join(root, "csv")
    write_track_csvs(csv_dir, n_tracks, seed)
    store = os.path.join(root, "store")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(writer, "BLOCK_POINTS", SMALL_BLOCK)
        build_store(csv_dir, store, target_points=target_points)
    return store



def encode_version2(columns: dict, meta: dict, block_rows: int) -> bytes:
    """The version-2 layout of ``columns``: every column stored alone in
    blocks of ``block_rows`` rows, no row groups."""
    import json
    import zlib

    from repro.store import codec
    data = codec.encode_shard(columns, meta=meta, block_rows=block_rows)
    header, off = codec._parse_header(data)
    del header["groups"]
    header["version"] = 2
    hdr = json.dumps(header, sort_keys=True,
                     separators=(",", ":")).encode()
    return (codec.MAGIC + (2).to_bytes(4, "little")
            + len(hdr).to_bytes(8, "little")
            + (zlib.crc32(hdr) & 0xFFFFFFFF).to_bytes(4, "little")
            + hdr + data[off:])
