"""Columnar shard codec: byte-identical encode, checksummed unit decode.

One shard file holds a set of named 1-D/2-D numpy columns.  A column is
stored either alone, split along its first axis into row blocks of one
fixed size, or in a *row group* with other columns of the same length:
rows ``[k*R, (k+1)*R)`` of all the group's columns form one unit.  Every
block and unit is compressed and CRC-checked on its own, and a small
JSON header indexes them and carries free-form shard metadata.  Layout::

    [ 0: 8)  magic   b"RPRSTOR1"
    [ 8:12)  u32 LE  format version (CODEC_VERSION)
    [12:20)  u64 LE  header length H
    [20:24)  u32 LE  crc32 of the header bytes
    [24:24+H)        header JSON (sorted keys, compact separators)
    [24+H: )         payload: the units of each group, group by group,
                     then the blocks of each column stored alone, column
                     by column, back-to-back

The header's ``columns`` list is sorted by column name and records, per
column, its dtype string and shape; a grouped column records the index
of its ``group``, a column stored alone its ``block_rows`` and one
``[codec, raw bytes, encoded bytes, crc32 of the raw bytes]`` entry per
block.  The ``groups`` list (version 3) records, per group, its sorted
``columns``, its ``block_rows`` R and one such entry per unit.  Inside a
unit the columns sit back to back in sorted name order, each part the
unit's rows of that column (``rows x`` its bytes per row), so one
inflate and one crc serve every column of the group.  Everything about
the encoding is canonical — sorted column and group order, sorted-key
compact JSON, a fixed zlib level, a fixed block size per call — so
encoding the same columns twice yields byte-identical files (the
reproducibility contract the store's acceptance tests gate on).
Version-2 shards (no groups) and version-1 shards, whose columns are
single blocks described by scalar ``codec``/``raw_bytes``/``enc_bytes``/
``crc32`` keys, still decode.

A :class:`ShardView` parses the header once and decodes row ranges of
columns from only the blocks or units that hold them
(:meth:`ShardView.read_columns`); :func:`decode_shard` is the
whole-range convenience.  Decoding verifies magic, version, header crc
and every decoded unit's crc; corruption raises
:class:`ShardChecksumError` (a :class:`ShardFormatError`) instead of
returning silently wrong arrays.
"""

from __future__ import annotations

import json
import os
import zlib
from typing import Any, Optional, Sequence

import numpy as np

__all__ = ["CODEC_VERSION", "MAGIC", "COMPRESSIONS", "ZLIB_LEVEL",
           "ShardFormatError", "ShardChecksumError", "ShardView",
           "encode_shard", "decode_shard", "read_shard", "peek_meta"]

MAGIC = b"RPRSTOR1"
CODEC_VERSION = 3
_READ_VERSIONS = (1, 2, 3)
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


def _pack(raw: bytes, compression: str, payload: list) -> list:
    """Append one block or unit's encoded bytes to ``payload``; -> its
    ``[codec, raw bytes, encoded bytes, crc32]`` header entry."""
    enc = zlib.compress(raw, ZLIB_LEVEL) if compression == "zlib" else raw
    # Tiny/incompressible blocks: zlib can expand; store whichever is
    # smaller, per block (the header records it).
    codec = compression
    if compression == "zlib" and len(enc) >= len(raw):
        enc, codec = raw, "none"
    payload.append(enc)
    return [codec, len(raw), len(enc), zlib.crc32(raw) & 0xFFFFFFFF]


def _encode_units(parts: list[np.ndarray], block_rows: Optional[int],
                  compression: str, payload: list) -> tuple[int, list]:
    """Store ``parts`` (columns of one length) together: -> rows per
    unit (the whole first axis unless ``block_rows`` is given and the
    columns are longer) and the units' header entries."""
    n = len(parts[0])
    rows = max(n, 1) if block_rows is None or n <= block_rows \
        else block_rows
    return rows, [_pack(b"".join(p[a:a + rows].tobytes() for p in parts),
                        compression, payload)
                  for a in range(0, max(n, 1), rows)]


def _check_groups(arrs: dict[str, np.ndarray],
                  groups: Sequence[Sequence[str]]) -> list[list[str]]:
    """``groups`` in canonical order (each sorted, then by first name),
    refused unless each names existing columns of one length, no column
    twice."""
    out = sorted(sorted(g) for g in groups)
    seen: set[str] = set()
    for names in out:
        if not names:
            raise ValueError("a column group is empty")
        for name in names:
            if name not in arrs:
                raise ValueError(f"column group names unknown column "
                                 f"{name!r}")
            if name in seen:
                raise ValueError(f"column {name!r} is in two groups")
            seen.add(name)
        if len({arrs[n].shape[0] for n in names}) > 1:
            raise ValueError(f"the columns of group {names} differ in "
                             f"length")
    return out


def encode_shard(columns: dict[str, np.ndarray], *,
                 meta: Optional[dict[str, Any]] = None,
                 compression: str = "zlib",
                 block_rows: Optional[int] = None,
                 groups: Sequence[Sequence[str]] = ()) -> bytes:
    """Serialize named columns (+ JSON-able ``meta``) into shard bytes.
    The columns of each of ``groups`` (lists of names of columns of one
    length) are stored together, in units of ``block_rows`` rows of them
    all; every other column alone, in blocks of ``block_rows`` rows
    (None: one block or unit)."""
    if compression not in COMPRESSIONS:
        raise ValueError(f"unknown compression {compression!r}; "
                         f"choose from {COMPRESSIONS}")
    if block_rows is not None and block_rows < 1:
        raise ValueError("block_rows must be >= 1")
    arrs = {}
    for name in sorted(columns):
        arr = np.ascontiguousarray(columns[name])       # ndim >= 1
        arrs[name] = arr.astype(_canonical_dtype(arr.dtype), copy=False)
    payload: list[bytes] = []
    group_ents = []
    group_of = {}
    for k, names in enumerate(_check_groups(arrs, groups)):
        rows, units = _encode_units([arrs[n] for n in names], block_rows,
                                    compression, payload)
        group_ents.append({"columns": names, "block_rows": rows,
                           "units": units})
        group_of.update(dict.fromkeys(names, k))
    entries = []
    for name, arr in arrs.items():
        ent = {"name": name, "dtype": arr.dtype.str,
               "shape": list(arr.shape)}
        if name in group_of:
            ent["group"] = group_of[name]
        else:
            ent["block_rows"], ent["blocks"] = _encode_units(
                [arr], block_rows, compression, payload)
        entries.append(ent)
    header = {"version": CODEC_VERSION, "columns": entries,
              "groups": group_ents, "meta": meta or {}}
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
    """One column: dtype, shape, bytes per row and the group that holds
    it."""

    __slots__ = ("name", "dtype", "shape", "row_bytes", "group")

    def __init__(self, ent: dict):
        self.name = ent["name"]
        self.dtype = np.dtype(ent["dtype"])
        self.shape = tuple(ent["shape"])
        self.row_bytes = self.dtype.itemsize * int(np.prod(self.shape[1:]))
        self.group: Optional[_Group] = None

    @property
    def rows(self) -> int:
        return self.shape[0]


class _Group:
    """Columns stored together, in units of ``block_rows`` rows of them
    all: per unit (file offset, codec, raw bytes, encoded bytes, crc32).
    A column stored alone is a group of one whose units are its blocks."""

    __slots__ = ("columns", "block_rows", "units", "row_bytes", "_lead")

    def __init__(self, block_rows: int, spec: list, off: int):
        self.columns: list[_Column] = []
        self.block_rows = int(block_rows)
        self.units = []
        for codec, raw, enc, crc in spec:
            self.units.append((off, codec, int(raw), int(enc), int(crc)))
            off += int(enc)
        self.row_bytes = 0
        self._lead: dict[str, int] = {}     # bytes per row before a column

    def add(self, col: _Column) -> None:
        """Append ``col``: the next part of every unit."""
        self._lead[col.name] = self.row_bytes
        self.row_bytes += col.row_bytes
        self.columns.append(col)
        col.group = self

    @property
    def end(self) -> int:
        off, _codec, _raw, enc, _crc = self.units[-1]
        return off + enc

    @property
    def rows(self) -> int:
        return self.columns[0].rows

    @property
    def what(self) -> str:
        names = [c.name for c in self.columns]
        return (f"column {names[0]!r}" if len(names) == 1
                else f"row group {names}")

    def unit_rows(self, u: int) -> int:
        return min(self.block_rows, self.rows - u * self.block_rows)

    def check(self) -> None:
        """Refuse a header whose units do not hold its columns' rows."""
        n, br = self.rows, self.block_rows
        if (len({c.rows for c in self.columns}) != 1
                or len(self.units) != max(-(-n // br), 1)
                or any(raw != self.unit_rows(u) * self.row_bytes
                       for u, (_o, _c, raw, _e, _r)
                       in enumerate(self.units))):
            raise ShardFormatError(f"header of {self.what} does not match "
                                   f"its rows")

    def part(self, raw, col: _Column, u: int) -> np.ndarray:
        """Column ``col``'s rows in unit ``u``'s raw bytes (a view)."""
        rows = self.unit_rows(u)
        inner = col.row_bytes // col.dtype.itemsize
        arr = np.frombuffer(raw, col.dtype, count=rows * inner,
                            offset=rows * self._lead[col.name])
        return arr.reshape((rows,) + col.shape[1:])


class ShardView:
    """A shard whose header is parsed and whose blocks and units are
    decoded on demand, from the shard's bytes or from a shard file's
    path; a view of a file reads only what it decodes, and is closed by
    :meth:`close` or by leaving a ``with`` block.

    Counters of what the view decoded: ``units`` (blocks and units
    inflated, of all columns), ``enc_bytes`` (their encoded bytes) and,
    per column name, ``column_blocks`` and ``rows`` (the blocks or units
    decoded for that column and the rows they hold)."""

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
        named = header.get("groups", [])
        groups = []
        for g in named:
            groups.append(_Group(g["block_rows"], g["units"], off))
            off = groups[-1].end
        self.columns: dict[str, _Column] = {}
        for ent in header["columns"]:
            col = _Column(ent)
            if "group" in ent:
                grp = groups[ent["group"]]
            else:
                if "blocks" in ent:
                    grp = _Group(ent["block_rows"], ent["blocks"], off)
                else:                               # version 1: one block
                    grp = _Group(max(col.rows, 1), [[
                        ent["codec"], ent["raw_bytes"], ent["enc_bytes"],
                        ent["crc32"]]], off)
                off = grp.end
                groups.append(grp)
            grp.add(col)
            self.columns[col.name] = col
        try:
            if off > size:
                raise ShardFormatError("shard truncated inside payload")
            for g, grp in zip(named, groups):
                if g["columns"] != [c.name for c in grp.columns]:
                    raise ShardFormatError(f"group {g['columns']} does not "
                                           f"match its columns")
            for grp in groups:
                grp.check()
        except ShardFormatError:
            self.close()
            raise
        self.units = 0
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

    def _units(self, grp: _Group, need: list, wanted: list):
        """Yield ``(u, raw bytes)`` for each unit ``u`` of ``need``
        (ascending), inflated and checked once; the encoded bytes of
        each run of consecutive units come in one read.  Counts the
        units and, per column of ``wanted``, the units and rows."""
        i = 0
        while i < len(need):
            j = i + 1
            while j < len(need) and need[j] == need[j - 1] + 1:
                j += 1
            start = grp.units[need[i]][0]
            last = grp.units[need[j - 1]]
            buf = memoryview(self._pread(start, last[0] + last[3] - start))
            if len(buf) != last[0] + last[3] - start:
                raise ShardFormatError(f"shard truncated inside {grp.what}")
            for u in need[i:j]:
                off, codec, raw_n, enc_n, crc = grp.units[u]
                enc = buf[off - start:off - start + enc_n]
                if codec == "zlib":
                    try:
                        raw = zlib.decompress(enc, bufsize=raw_n)
                    except zlib.error as e:
                        raise ShardChecksumError(
                            f"{grp.what} failed to decompress "
                            f"(corrupted shard): {e}") from e
                else:
                    raw = enc
                if len(raw) != raw_n or (zlib.crc32(raw)
                                         & 0xFFFFFFFF) != crc:
                    raise ShardChecksumError(
                        f"{grp.what} checksum mismatch (corrupted shard)")
                self.units += 1
                self.enc_bytes += enc_n
                rows = grp.unit_rows(u)
                for col in wanted:
                    self.rows[col.name] = self.rows.get(col.name, 0) + rows
                    self.column_blocks[col.name] = (
                        self.column_blocks.get(col.name, 0) + 1)
                yield u, raw
            i = j

    def read_columns(self, names: Sequence[str], lo, hi
                     ) -> dict[str, list[np.ndarray]]:
        """Rows ``[lo[i], hi[i])`` of each column of ``names``, for each
        i: ``{name: [array per range]}``.  Every block or unit that holds
        some range is inflated once, in order, for all the wanted
        columns it holds; their rows served are copied straight into one
        new array per column that holds nothing else, and each range is
        a slice of it, so no result keeps a unit alive."""
        lo = np.asarray(lo, np.int64)
        hi = np.asarray(hi, np.int64)
        by_group: dict[int, tuple[_Group, list[_Column]]] = {}
        for name in names:
            col = self._column(name)
            by_group.setdefault(id(col.group), (col.group, []))[1].append(
                col)
        out: dict[str, list[np.ndarray]] = {}
        for grp, wanted in by_group.values():
            out.update(self._read_group(grp, wanted, lo, hi))
        return {name: out[name] for name in names}

    def _read_group(self, grp: _Group, wanted: list, lo: np.ndarray,
                    hi: np.ndarray) -> dict[str, list[np.ndarray]]:
        br, n = grp.block_rows, grp.rows
        if ((lo < 0) | (lo > hi) | (hi > n)).any():
            raise ValueError(f"a row range is out of bounds for "
                             f"{grp.what} of {n} rows")
        size = hi - lo
        ends = np.cumsum(size)
        starts = ends - size
        total = int(ends[-1]) if len(ends) else 0
        outs = [np.empty((total,) + col.shape[1:], col.dtype)
                for col in wanted]
        if len(lo) and (lo[1:] == hi[:-1]).all():
            # One run of rows in order (a whole shard's tracks): each
            # unit's overlap with it is one slice.
            a, b = int(lo[0]), int(hi[-1])
            need = list(range(a // br, -(-b // br))) if b > a else []
            for u, raw in self._units(grp, need, wanted):
                s, e = max(a, u * br), min(b, (u + 1) * br)
                for col, out in zip(wanted, outs):
                    out[s - a:e - a] = grp.part(raw, col, u)[
                        s - u * br:e - u * br]
        else:
            src = _aranges(lo, size)            # rows served, output order
            unit = src // br
            order = np.argsort(unit, kind="stable")
            need, first = np.unique(unit[order], return_index=True)
            bounds = np.append(first, len(order))
            for k, (u, raw) in enumerate(self._units(grp, need.tolist(),
                                                     wanted)):
                dst = order[bounds[k]:bounds[k + 1]]
                rows = src[dst] - u * br
                for col, out in zip(wanted, outs):
                    out[dst] = grp.part(raw, col, u)[rows]
        spans = list(zip(starts.tolist(), ends.tolist()))
        return {col.name: [out[s:e] for s, e in spans]
                for col, out in zip(wanted, outs)}

    def read_ranges(self, name: str, lo, hi) -> list[np.ndarray]:
        """Rows ``[lo[i], hi[i])`` of column ``name`` for each i (see
        :meth:`read_columns`)."""
        return self.read_columns([name], lo, hi)[name]

    def read(self, name: str, lo: int = 0,
             hi: Optional[int] = None) -> np.ndarray:
        """Rows ``[lo, hi)`` (default: all) of column ``name``, owned."""
        hi = self._column(name).rows if hi is None else hi
        return self.read_ranges(name, [lo], [hi])[0]


def decode_shard(data: bytes, *, columns: Optional[list[str]] = None,
                 rows: Optional[tuple[int, int]] = None
                 ) -> tuple[dict[str, np.ndarray], dict]:
    """-> (columns, meta).  ``columns`` restricts which columns are
    decoded (the others are skipped without decompression, unless they
    share a unit with a wanted one); ``rows`` = ``(lo, hi)`` decodes only
    those rows of each, from the blocks or units that hold them.  Every
    decoded unit's crc is verified, each unit inflated once."""
    return _decode(ShardView(data), columns, rows)


def _decode(view: ShardView, columns, rows) -> tuple[dict, dict]:
    names = list(view.columns) if columns is None else list(columns)
    missing = [n for n in names if n not in view.columns]
    if missing:
        raise KeyError(f"shard has no column(s) {sorted(missing)}")
    out: dict[str, np.ndarray] = {}
    for name in names:
        if name not in out:
            grp = view.columns[name].group
            lo, hi = rows if rows is not None else (0, grp.rows)
            wanted = [n for n in names if view.columns[n].group is grp]
            out.update((n, parts[0]) for n, parts
                       in view.read_columns(wanted, [lo], [hi]).items())
    return {name: out[name] for name in names}, view.meta


def read_shard(path: str, *, columns: Optional[list[str]] = None,
               rows: Optional[tuple[int, int]] = None
               ) -> tuple[dict[str, np.ndarray], dict]:
    """Read + decode one shard file (see :func:`decode_shard`)."""
    with ShardView(path) as view:
        return _decode(view, columns, rows)
