"""Columnar shard codec: byte-identical encode, checksummed block decode.

One shard file holds a set of named 1-D/2-D numpy columns, each split
along its first axis into row blocks of one fixed size; every block is
compressed and CRC-checked on its own, and a small JSON header indexes
the blocks and carries free-form shard metadata.  Layout::

    [ 0: 8)  magic   b"RPRSTOR1"
    [ 8:12)  u32 LE  format version (CODEC_VERSION)
    [12:20)  u64 LE  header length H
    [20:24)  u32 LE  crc32 of the header bytes
    [24:24+H)        header JSON (sorted keys, compact separators)
    [24+H: )         payload blocks, column by column, back-to-back

The header's ``columns`` list is sorted by column name and records, per
column: dtype string, shape, ``block_rows`` and one
``[codec, raw bytes, encoded bytes, crc32 of the raw bytes]`` entry per
block.  Everything about the encoding is canonical — sorted column
order, sorted-key compact JSON, a fixed zlib level, a fixed block size
per call — so encoding the same columns twice yields byte-identical
files (the reproducibility contract the store's acceptance tests gate
on).  Version-1 shards, whose columns are single blocks described by
scalar ``codec``/``raw_bytes``/``enc_bytes``/``crc32`` keys, are the
degenerate case and still decode.

A :class:`ShardView` parses the header once and decodes a row range of
a column from only the blocks that cover it; :func:`decode_shard` is
the whole-range convenience.  Decoding verifies magic, version, header
crc and every decoded block's crc; corruption raises
:class:`ShardChecksumError` (a :class:`ShardFormatError`) instead of
returning silently wrong arrays.
"""

from __future__ import annotations

import json
import os
import zlib
from typing import Any, Optional

import numpy as np

__all__ = ["CODEC_VERSION", "MAGIC", "COMPRESSIONS", "ZLIB_LEVEL",
           "ShardFormatError", "ShardChecksumError", "ShardView",
           "encode_shard", "decode_shard", "read_shard", "peek_meta"]

MAGIC = b"RPRSTOR1"
CODEC_VERSION = 2
_READ_VERSIONS = (1, 2)
ZLIB_LEVEL = 6                      # fixed: part of the canonical encoding
COMPRESSIONS = ("none", "zlib")

_HDR_FIXED = len(MAGIC) + 4 + 8 + 4


class ShardFormatError(ValueError):
    """The byte stream is not a valid shard (bad magic/version/header)."""


class ShardChecksumError(ShardFormatError):
    """A stored checksum does not match the decoded bytes."""


def _canonical_dtype(dt: np.dtype) -> np.dtype:
    """Little-endian is the one true byte order on disk.  ``dt.str``
    resolves native ('=') order, so this also catches native dtypes on
    big-endian hosts — shard bytes must not depend on the writer."""
    if dt.str.startswith(">"):
        return dt.newbyteorder("<")
    return dt


def _block_rows(arr: np.ndarray, block_rows: Optional[int]) -> int:
    """Rows per block of ``arr``: the whole first axis (one block) unless
    ``block_rows`` is given and the column is longer."""
    n = arr.shape[0] if arr.ndim else 1
    if block_rows is None or n <= block_rows:
        return max(n, 1)
    return block_rows


def encode_shard(columns: dict[str, np.ndarray], *,
                 meta: Optional[dict[str, Any]] = None,
                 compression: str = "zlib",
                 block_rows: Optional[int] = None) -> bytes:
    """Serialize named columns (+ JSON-able ``meta``) into shard bytes,
    each column in blocks of ``block_rows`` rows (None: one block)."""
    if compression not in COMPRESSIONS:
        raise ValueError(f"unknown compression {compression!r}; "
                         f"choose from {COMPRESSIONS}")
    if block_rows is not None and block_rows < 1:
        raise ValueError("block_rows must be >= 1")
    entries = []
    payload = []
    for name in sorted(columns):
        arr = np.ascontiguousarray(columns[name])
        arr = arr.astype(_canonical_dtype(arr.dtype), copy=False)
        rows = _block_rows(arr, block_rows)
        parts = ([arr] if arr.ndim == 0 or rows >= arr.shape[0]
                 else [arr[a:a + rows] for a in range(0, arr.shape[0],
                                                      rows)])
        blocks = []
        for part in parts:
            raw = part.tobytes()
            enc = zlib.compress(raw, ZLIB_LEVEL) \
                if compression == "zlib" else raw
            # Tiny/incompressible blocks: zlib can expand; store
            # whichever is smaller, per block (the header records it).
            codec = compression
            if compression == "zlib" and len(enc) >= len(raw):
                enc, codec = raw, "none"
            blocks.append([codec, len(raw), len(enc),
                           zlib.crc32(raw) & 0xFFFFFFFF])
            payload.append(enc)
        entries.append({"name": name, "dtype": arr.dtype.str,
                        "shape": list(arr.shape), "block_rows": rows,
                        "blocks": blocks})
    header = {"version": CODEC_VERSION, "columns": entries,
              "meta": meta or {}}
    hdr = json.dumps(header, sort_keys=True,
                     separators=(",", ":")).encode()
    out = bytearray()
    out += MAGIC
    out += CODEC_VERSION.to_bytes(4, "little")
    out += len(hdr).to_bytes(8, "little")
    out += (zlib.crc32(hdr) & 0xFFFFFFFF).to_bytes(4, "little")
    out += hdr
    for b in payload:
        out += b
    return bytes(out)


def _parse_header(data: bytes) -> tuple[dict, int]:
    if len(data) < _HDR_FIXED:
        raise ShardFormatError("shard truncated before header")
    if data[:len(MAGIC)] != MAGIC:
        raise ShardFormatError(f"bad magic {bytes(data[:len(MAGIC)])!r}")
    off = len(MAGIC)
    version = int.from_bytes(data[off:off + 4], "little")
    if version not in _READ_VERSIONS:
        raise ShardFormatError(f"unsupported shard version {version}")
    off += 4
    hlen = int.from_bytes(data[off:off + 8], "little")
    off += 8
    hcrc = int.from_bytes(data[off:off + 4], "little")
    off += 4
    hdr = data[off:off + hlen]
    if len(hdr) != hlen:
        raise ShardFormatError("shard truncated inside header")
    if (zlib.crc32(hdr) & 0xFFFFFFFF) != hcrc:
        raise ShardChecksumError("header crc mismatch")
    try:
        header = json.loads(bytes(hdr).decode())
    except ValueError as e:
        raise ShardFormatError(f"header is not valid JSON: {e}") from e
    return header, off + hlen


def peek_meta(data: bytes) -> dict:
    """Header ``meta`` without touching any payload block."""
    header, _ = _parse_header(data)
    return header.get("meta", {})


def _aranges(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Concatenated ``arange(s, s + c)`` for each (s, c)."""
    total = int(counts.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    head = np.repeat(np.cumsum(counts) - counts, counts)
    return np.repeat(starts, counts) + np.arange(total) - head


class _Column:
    """One column's block table: dtype, shape, rows per block and, per
    block, (file offset, codec, raw bytes, encoded bytes, crc32)."""

    __slots__ = ("name", "dtype", "shape", "block_rows", "blocks")

    def __init__(self, ent: dict, off: int):
        self.name = ent["name"]
        self.dtype = np.dtype(ent["dtype"])
        self.shape = tuple(ent["shape"])
        if "blocks" in ent:
            self.block_rows = int(ent["block_rows"])
            spec = ent["blocks"]
        else:                               # version 1: one block
            self.block_rows = max(self.shape[0] if self.shape else 1, 1)
            spec = [[ent["codec"], ent["raw_bytes"], ent["enc_bytes"],
                     ent["crc32"]]]
        self.blocks = []
        for codec, raw, enc, crc in spec:
            self.blocks.append((off, codec, int(raw), int(enc), int(crc)))
            off += int(enc)

    @property
    def end(self) -> int:
        off, _codec, _raw, enc, _crc = self.blocks[-1]
        return off + enc

    @property
    def rows(self) -> int:
        return self.shape[0] if self.shape else 1


class ShardView:
    """A shard whose header is parsed and whose blocks are decoded on
    demand, from the shard's bytes or from a shard file's path; a view
    of a file reads only the blocks it decodes, and is closed by
    :meth:`close` or by leaving a ``with`` block.

    Counters of what the view decoded: ``enc_bytes`` (encoded bytes of
    its blocks) and, per column name, ``column_blocks`` and ``rows``
    (the blocks decoded and the rows they hold)."""

    def __init__(self, source):
        if isinstance(source, str):
            self._file = open(source, "rb")
            try:
                size = os.fstat(self._file.fileno()).st_size
                head = self._pread(0, _HDR_FIXED)
                if head[:len(MAGIC)] == MAGIC and len(head) == _HDR_FIXED:
                    head = self._pread(0, _HDR_FIXED + int.from_bytes(
                        head[12:20], "little"))
                header, off = _parse_header(head)
            except BaseException:
                self._file.close()
                raise
        else:
            self._file = None
            self._data = memoryview(source)
            size = len(self._data)
            header, off = _parse_header(self._data)
        self.meta = header.get("meta", {})
        self.columns: dict[str, _Column] = {}
        for ent in header["columns"]:
            col = _Column(ent, off)
            off = col.end
            if off > size:
                self.close()
                raise ShardFormatError(
                    f"shard truncated inside column {col.name!r}")
            self.columns[col.name] = col
        self.enc_bytes = 0
        self.rows: dict[str, int] = {}
        self.column_blocks: dict[str, int] = {}

    def __enter__(self) -> "ShardView":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def close(self) -> None:
        if self._file is not None:
            self._file.close()

    def _pread(self, off: int, n: int) -> bytes:
        if self._file is None:
            return self._data[off:off + n]
        return os.pread(self._file.fileno(), n, off)

    def _column(self, name: str) -> _Column:
        try:
            return self.columns[name]
        except KeyError:
            raise KeyError(f"shard has no column(s) [{name!r}]") from None

    def block(self, name: str, i: int) -> np.ndarray:
        """Block ``i`` of column ``name``, decompressed and checked."""
        col = self._column(name)
        off, codec, raw_n, enc_n, crc = col.blocks[i]
        enc = self._pread(off, enc_n)
        if len(enc) != enc_n:
            raise ShardFormatError(
                f"shard truncated inside column {name!r}")
        if codec == "zlib":
            try:
                raw = zlib.decompress(enc)
            except zlib.error as e:
                raise ShardChecksumError(
                    f"column {name!r} failed to decompress "
                    f"(corrupted shard): {e}") from e
        else:
            raw = enc
        if len(raw) != raw_n or (zlib.crc32(raw) & 0xFFFFFFFF) != crc:
            raise ShardChecksumError(
                f"column {name!r} checksum mismatch (corrupted shard)")
        arr = np.frombuffer(raw, dtype=col.dtype)
        if not col.shape:
            arr = arr.reshape(())
        else:
            arr = arr.reshape((-1,) + col.shape[1:])
        self.enc_bytes += enc_n
        self.rows[name] = self.rows.get(name, 0) + (
            len(arr) if col.shape else 1)
        self.column_blocks[name] = self.column_blocks.get(name, 0) + 1
        return arr

    def read_ranges(self, name: str, lo, hi) -> list[np.ndarray]:
        """Rows ``[lo[i], hi[i])`` of column ``name`` for each i.  Every
        block that covers some range is decoded once, in block order;
        the rows served are copied out into one array that holds nothing
        else, and each range is a slice of it, so no result keeps a
        decoded block alive."""
        col = self._column(name)
        lo = np.asarray(lo, np.int64)
        hi = np.asarray(hi, np.int64)
        if not col.shape:
            whole = self.block(name, 0)
            return [whole.copy() for _ in range(len(lo))]
        br, n = col.block_rows, col.rows
        if ((lo < 0) | (lo > hi) | (hi > n)).any():
            raise ValueError(f"a row range is out of bounds for column "
                             f"{name!r} of {n} rows")
        size = hi - lo
        ends = np.cumsum(size)
        starts = ends - size
        if len(lo) and (lo[1:] == hi[:-1]).all():
            # One run of rows in order (a whole shard's tracks): its
            # blocks are consecutive and the run is one slice of them.
            a, b = int(lo[0]), int(hi[-1])
            first = a // br
            decoded = self._concat(name, range(first, -(-b // br))
                                   if b > a else ())
            off = a - first * br
            rows = (decoded if off == 0 and len(decoded) == b - a
                    else decoded[off:off + b - a])
        else:
            full = size > 0
            first, count = lo[full] // br, (hi[full] - 1) // br + 1
            count -= first
            need = np.unique(_aranges(first, count))
            decoded = self._concat(name, need.tolist())
            # Row r lies at position (rank of its block) * br + r % br of
            # the decoded rows: every block but a column's last is full.
            pos = np.zeros(len(lo), np.int64)
            pos[full] = (np.searchsorted(need, lo[full] // br) * br
                         + lo[full] % br)
            rows = decoded[_aranges(pos, size)]
        if rows.base is not None:
            rows = rows.copy()
        return [rows[a:b] for a, b in zip(starts.tolist(), ends.tolist())]

    def _concat(self, name: str, blocks) -> np.ndarray:
        """Blocks ``blocks`` of column ``name``, decoded in that order
        into one new array."""
        parts = [self.block(name, i) for i in blocks]
        if parts:
            return np.concatenate(parts)
        col = self.columns[name]
        return np.zeros((0,) + col.shape[1:], col.dtype)

    def read(self, name: str, lo: int = 0,
             hi: Optional[int] = None) -> np.ndarray:
        """Rows ``[lo, hi)`` (default: all) of column ``name``, owned."""
        col = self._column(name)
        if not col.shape:
            return self.read_ranges(name, [0], [1])[0]
        return self.read_ranges(name, [lo],
                                [col.rows if hi is None else hi])[0]


def decode_shard(data: bytes, *, columns: Optional[list[str]] = None,
                 rows: Optional[tuple[int, int]] = None
                 ) -> tuple[dict[str, np.ndarray], dict]:
    """-> (columns, meta).  ``columns`` restricts which columns are
    decoded (the others are skipped without decompression); ``rows``
    = ``(lo, hi)`` decodes only those rows of each, from the blocks that
    cover them.  Every decoded block's crc is verified."""
    return _decode(ShardView(data), columns, rows)


def _decode(view: ShardView, columns, rows) -> tuple[dict, dict]:
    names = list(view.columns) if columns is None else list(columns)
    missing = [n for n in names if n not in view.columns]
    if missing:
        raise KeyError(f"shard has no column(s) {sorted(missing)}")
    out = {n: (view.read(n) if rows is None else view.read(n, *rows))
           for n in names}
    return out, view.meta


def read_shard(path: str, *, columns: Optional[list[str]] = None,
               rows: Optional[tuple[int, int]] = None
               ) -> tuple[dict[str, np.ndarray], dict]:
    """Read + decode one shard file (see :func:`decode_shard`)."""
    with ShardView(path) as view:
        return _decode(view, columns, rows)
