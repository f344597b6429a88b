"""Native parquet decode path (GpuParquetScan.scala:2624 Table.readParquet
role, stage 1: host-native).

pyarrow parses the thrift FOOTER (metadata only); each eligible column
chunk's raw bytes then decode in the C++ runtime
(native/parquet_decode.cpp — page headers, Snappy, PLAIN +
RLE_DICTIONARY, definition levels) straight into numpy buffers without
the GIL, so a scan's decode work parallelizes across reader-pool
threads while the consumer uploads previous chunks to the device.
Columns outside the native envelope (strings, nested, v2 pages,
unsupported codecs) decode through pyarrow per row group — eligibility
is per COLUMN, not per file.

Used by io/scan.iter_file_tables when srt.sql.format.parquet.
nativeDecode.enabled is on (default); any error falls back to the
pyarrow path wholesale, keeping results identical.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from ..columnar import dtypes as dt
from ..plan.host_table import HostColumn, HostTable

# parquet physical type -> (wire id for the C++ decoder, numpy dtype)
_PHYS = {
    "INT32": (1, np.dtype(np.int32)),
    "INT64": (2, np.dtype(np.int64)),
    "FLOAT": (4, np.dtype(np.float32)),
    "DOUBLE": (5, np.dtype(np.float64)),
}
#: wire id marking the BYTE_ARRAY (string) lane, decoded by
#: parquet_decode_chunk_binary into offsets + bytes
_PHYS_BINARY = 100
_CODECS = {"UNCOMPRESSED": 0, "SNAPPY": 1, "GZIP": 2, "ZSTD": 3}
_OK_ENCODINGS = {"PLAIN", "RLE", "PLAIN_DICTIONARY", "RLE_DICTIONARY",
                 "BIT_PACKED", "DELTA_BINARY_PACKED", "BYTE_STREAM_SPLIT"}
#: byte-array pages additionally cover the DELTA string family
#: (Spark 3.3+ writers emit these with parquet.writer.version=v2;
#: GpuParquetScan.scala supports them via cuDF)
_OK_ENCODINGS_BINARY = _OK_ENCODINGS | {"DELTA_LENGTH_BYTE_ARRAY",
                                        "DELTA_BYTE_ARRAY"}


def _declared_ok(t: dt.DType) -> bool:
    """Declared dtypes whose host lanes are plain fixed-width ints or
    floats, plus strings (timestamps excluded: their unit
    normalization lives in the arrow path)."""
    if t == dt.TIMESTAMP or t.is_nested:
        return False
    if isinstance(t, dt.DecimalType):
        return not t.is_wide
    return True


def placed_lanes(schema) -> List[Tuple[str, np.dtype]]:
    """``(name, numpy dtype)`` of the columns the decoder can write
    straight into a batch's own buffers: the fixed-width lanes of this
    envelope. Strings have no place known before their bytes are read;
    they, and the columns pyarrow decodes (booleans among them), are
    assembled as before."""
    return [(n, np.dtype(t.physical)) for n, t in schema
            if t not in (dt.STRING, dt.BOOL) and _declared_ok(t)]


class _ChunkPlan:
    __slots__ = ("col_idx", "phys_id", "np_dtype", "codec", "max_def",
                 "offset", "length", "scratch")

    def __init__(self, col_idx, phys_id, np_dtype, codec, max_def,
                 offset, length, scratch):
        self.col_idx = col_idx
        self.phys_id = phys_id
        self.np_dtype = np_dtype
        self.codec = codec
        self.max_def = max_def
        self.offset = offset
        self.length = length
        self.scratch = scratch


def _plan_chunk(pf: "pq.ParquetFile", rg: int, col_idx: int,
                declared: dt.DType) -> Optional[_ChunkPlan]:
    """Eligibility check for one (row group, column); None -> pyarrow."""
    if not _declared_ok(declared):
        return None
    ct = pf.metadata.row_group(rg).column(col_idx)
    if ct.physical_type == "BYTE_ARRAY" and declared == dt.STRING:
        phys = (_PHYS_BINARY, None)
        ok_encs = _OK_ENCODINGS_BINARY
    else:
        if declared == dt.STRING:
            return None
        phys = _PHYS.get(ct.physical_type)
        ok_encs = _OK_ENCODINGS
    if phys is None:
        return None
    codec = _CODECS.get(ct.compression)
    if codec is None:
        return None
    if not set(ct.encodings) <= ok_encs:
        return None
    sc = pf.schema.column(col_idx)
    if sc.max_repetition_level != 0 or sc.max_definition_level > 1:
        return None
    offset = ct.data_page_offset
    if ct.has_dictionary_page and ct.dictionary_page_offset is not None:
        offset = min(offset, ct.dictionary_page_offset)
    # scratch: one uncompressed page + parked dictionary; the chunk's
    # total uncompressed size bounds both
    scratch = max(int(ct.total_uncompressed_size) * 2, 1 << 16)
    return _ChunkPlan(col_idx, phys[0], phys[1], codec,
                      sc.max_definition_level, offset,
                      int(ct.total_compressed_size), scratch)


def _decode_native(fh, plan: _ChunkPlan, rows: int, out=None):
    """-> (values ndarray, validity bool ndarray) or None on any
    decoder error (falls back). ``out``: the ``rows`` rows of a batch's
    own buffers ``(values, validity)`` this chunk has its place in; the
    decoder writes every row of both (zeros under nulls), a file type
    narrower than the buffer's is cast into it."""
    from ..native import parquet_decode_chunk, parquet_decode_chunk_binary
    fh.seek(plan.offset)
    chunk = fh.read(plan.length)
    # the decoder writes one byte a row, 0 or 1: a bool array's bytes
    validity = np.zeros(rows, bool) \
        if out is None or plan.phys_id == _PHYS_BINARY else out[1]
    valid_u8 = validity.view(np.uint8)
    scratch = np.empty(plan.scratch, np.uint8)
    if plan.phys_id == _PHYS_BINARY:
        offsets = np.zeros(rows + 1, np.int32)
        # first guess: the chunk's uncompressed footprint bounds the
        # string payload; -3 (overflow) retries once at 4x
        cap = max(plan.scratch, 1 << 16)
        for attempt in range(2):
            out_bytes = np.empty(cap, np.uint8)
            got = parquet_decode_chunk_binary(
                chunk, plan.codec, rows, plan.max_def, offsets,
                out_bytes, valid_u8, scratch)
            if got == -3 and attempt == 0:
                cap *= 4
                continue
            break
        if got != rows:
            return None
        # the decoder's own buffers (null rows zero-length): the column
        # carries them to the device as they are (HostColumn.utf8)
        return (offsets, out_bytes[:int(offsets[rows])].copy()), validity
    values = out[0] if out is not None and out[0].dtype == plan.np_dtype \
        else np.zeros(rows, plan.np_dtype)
    got = parquet_decode_chunk(chunk, plan.codec, plan.phys_id, rows,
                               plan.max_def, values, valid_u8, scratch)
    if got != rows:
        return None
    if out is not None and values is not out[0]:
        out[0][:] = values
        values = out[0]
    return values, validity


def _to_host_column(values: np.ndarray, validity: np.ndarray,
                    declared: dt.DType) -> HostColumn:
    if declared == dt.STRING:
        return HostColumn(None, validity, declared, utf8=values)
    phys = np.dtype(declared.physical)
    if values.dtype != phys:
        # e.g. file INT32 under a declared bigint/decimal(…,s)<=18
        values = values.astype(phys)
    return HostColumn(values, validity, declared)


def _decode_row_group(pf, fh, rg: int, rows: int, want, file_cols,
                      declared, options=None, dest=None):
    native: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
    fallback: List[str] = []
    for name in want:
        plan = _plan_chunk(pf, rg, file_cols[name], declared[name])
        out = _decode_native(fh, plan, rows, (dest or {}).get(name)) \
            if plan else None
        if out is None:
            fallback.append(name)
        else:
            native[name] = out
    stats = (options or {}).get("_decode_stats")
    if stats is not None and fallback:
        stats["host_columns"] += len(fallback)
    fb_table = None
    if fallback:
        from .arrow_convert import arrow_to_host_table
        fb_table = arrow_to_host_table(
            pf.read_row_group(rg, columns=fallback))
    cols, names = [], []
    for name in want:
        names.append(name)
        if name in native:
            v, m = native[name]
            cols.append(_to_host_column(v, m, declared[name]))
        else:
            src = fb_table.column(name)
            if src.dtype != declared[name]:
                raise ValueError(
                    f"column {name}: file type {src.dtype} != "
                    f"declared {declared[name]}")
            cols.append(src)
    return cols, names


def iter_row_group_tables_native(
        path: str, schema, options: dict, max_rows: int,
        partition_values: Optional[dict],
        dest: Optional[dict] = None) -> Iterator[HostTable]:
    """Row-group-chunked HostTables with per-column native decode.
    Raises on structural mismatch — the caller catches and reruns the
    pyarrow path.

    ``dest``: ``{column: (values, validity)}``, this file's rows of a
    batch's own buffers (io/scan.py ``_PlacedBatch``), for the
    fixed-width columns. Row groups decode into them one after another;
    a table all of whose ``dest`` columns ARE those rows says so in
    ``placed`` (its first row's place among the file's). A column or a
    row group that went through pyarrow, or a rebase that rewrote a
    lane, leaves ``placed`` None, and the rows it could not use
    unread."""
    from .scan import _apply_read_rebase
    declared: Dict[str, dt.DType] = dict(schema)
    part_names = set((partition_values or {}).keys())
    pf = pq.ParquetFile(path)
    file_cols = {c: i for i, c in enumerate(pf.schema_arrow.names)}
    want = [n for n, _ in schema
            if n in file_cols and n not in part_names]
    if pf.metadata.num_row_groups == 0:
        raise ValueError("no row groups")  # fallback handles empties
    at = 0
    with open(path, "rb") as fh:
        for rg in range(pf.metadata.num_row_groups):
            rows = pf.metadata.row_group(rg).num_rows
            here = {n: (v[at:at + rows], m[at:at + rows])
                    for n, (v, m) in (dest or {}).items()
                    if at + rows <= len(v)}
            try:
                cols, names = _decode_row_group(pf, fh, rg, rows, want,
                                                file_cols, declared,
                                                options, here)
            except Exception:
                # per-ROW-GROUP fallback: earlier row groups already
                # streamed out, so this one must be recovered in place
                # (never re-read the whole file — that would duplicate)
                from .arrow_convert import arrow_to_host_table
                from .scan import _conform
                fb = arrow_to_host_table(_conform(
                    pf.read_row_group(rg, columns=want),
                    [(n, declared[n]) for n in want]))
                cols = [fb.column(n) for n in want]
                names = list(want)
            # partition columns materialize as constant host columns
            # (no arrow round-trip); declared order is by construction
            by_name = dict(zip(names, cols))
            out_cols, out_names = [], []
            for name, t in schema:
                out_names.append(name)
                if name in by_name:
                    out_cols.append(by_name[name])
                    continue
                if name not in part_names:
                    raise ValueError(f"column {name} missing from file")
                v = (partition_values or {}).get(name)
                vals, mask = here.get(name) or (
                    np.empty(rows, object if t == dt.STRING
                             else np.dtype(t.physical)),
                    np.empty(rows, bool))
                vals[:] = v if v is not None else \
                    "" if t == dt.STRING else 0
                mask[:] = v is not None
                out_cols.append(HostColumn(vals, mask, t))
            ht = HostTable(out_cols, out_names)
            _apply_read_rebase(ht, options)
            if here and rows <= max_rows and all(
                    c._values is here[n][0]
                    for n, c in zip(out_names, out_cols) if n in here):
                ht.placed = at
            at += rows
            for start in range(0, rows, max_rows):
                if start == 0 and rows <= max_rows:
                    yield ht
                    break
                end = min(start + max_rows, rows)
                yield HostTable(
                    [c.slice(start, end) for c in ht.columns],
                    list(ht.names))
