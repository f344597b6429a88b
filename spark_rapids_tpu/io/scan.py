"""File scans: FileScan logical node + FileSourceScanExec.

Rebuild of GpuParquetScan.scala / GpuOrcScan.scala / GpuCSVScan.scala +
GpuMultiFileReader.scala + GpuFileSourceScanExec.scala (SURVEY §2.6),
re-architected for TPU: host threads decode (pyarrow) without holding
the device semaphore; decoded chunks upload to HBM as capacity-bucketed
ColumnarBatches. The reference's three reader types are kept:

- PERFILE       (GpuParquetPartitionReaderFactory): one file at a time,
                decoded by the thread that uploads
- COALESCING    (MultiFileParquetPartitionReader:1862): many small
                files concatenated into target-size batches before upload
- MULTITHREADED (MultiFileCloudParquetPartitionReader:2057): a batch a
                file (or a slice of one), never concatenated

COALESCING and MULTITHREADED read a scan of several files through one
stream (``FileSourceScanExec._decoded_files``): kept pool threads decode
files ahead of the scan's own thread, in file order, and that thread only
assembles batches and uploads them.

Predicate pushdown mirrors the reference's ParquetFilters handling:
supported conjuncts translate to pyarrow dataset filters (row-group /
file pruning); the full filter still re-runs on device, so pushdown is
purely an I/O reduction, never a semantics change.
"""

from __future__ import annotations

import errno
import glob as globlib
import logging
import os
import struct as structlib
import threading
import time
from typing import Iterator, List, Optional, Sequence

import numpy as np
import pyarrow as pa

from ..columnar import dtypes as dt
from ..columnar.vector import ColumnarBatch, choose_capacity
from ..conf import (MAX_READER_BATCH_SIZE_ROWS, PIPELINE_MAX_BYTES,
                    READER_THREADS, READER_TYPE)
from ..exec.base import ExecContext, Metric, Schema, TpuExec
from ..exec.pipeline import RunAhead
from ..expr import core as E
from ..expr import predicates as P
from ..obs.trace import annotate
from ..plan.host_table import (HostColumn, HostTable, concat_tables,
                               empty_like, table_to_batch)
from ..plan.logical import LogicalPlan
from ..robustness.faults import fault_point
from ..robustness.integrity import DataCorruption
from .arrow_convert import arrow_schema_to_schema, arrow_to_host_table

logger = logging.getLogger("spark_rapids_tpu.scan")

FORMATS = ("parquet", "orc", "csv", "json", "avro", "hivetext")


def _rewritten_roots(path_or_paths, conf=None) -> List[str]:
    from .filecache import rewrite_uri
    raw = ([path_or_paths] if isinstance(path_or_paths, str)
           else list(path_or_paths))
    from ..conf import URI_REWRITE_RULES, active_conf
    rules = (conf or active_conf()).get(URI_REWRITE_RULES)
    paths = [rewrite_uri(p, rules) for p in raw]
    return [p[len("file://"):] if p.startswith("file://") else p
            for p in paths]


def expand_paths(path_or_paths, conf=None) -> List[str]:
    paths = _rewritten_roots(path_or_paths, conf)
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            # skip _metadata/.hidden AND *.tmp staging leftovers from
            # writers killed between encode and rename (io/writer.py,
            # delta staging) — a tmp is never a readable data file
            for root, _dirs, files in os.walk(p):
                out.extend(os.path.join(root, f) for f in sorted(files)
                           if not f.startswith(("_", "."))
                           and not f.endswith(".tmp"))
        elif any(ch in p for ch in "*?["):
            out.extend(sorted(globlib.glob(p)))
        else:
            out.append(p)
    return out


HIVE_NULL_PART = "__HIVE_DEFAULT_PARTITION__"


def discover_partitions(roots: List[str], files: List[str]):
    """Hive-style key=value directory partitioning (the reference reads
    these through Spark's PartitioningAwareFileIndex; partition columns
    surface as constant columns per file, SURVEY §2.6).

    Returns (partition_schema, per-file value dicts) with types inferred
    int64 -> float64 -> string like Spark's partition inference."""
    from urllib.parse import unquote
    values: List[dict] = []
    key_order: List[str] = []
    for f in files:
        root = next((r for r in roots
                     if f.startswith(r.rstrip(os.sep) + os.sep)), None)
        vals = {}
        if root is not None:
            rel = os.path.relpath(f, root)
            for seg in rel.split(os.sep)[:-1]:
                if "=" in seg:
                    k, _, v = seg.partition("=")
                    v = unquote(v)
                    vals[k] = None if v == HIVE_NULL_PART else v
                    if k not in key_order:
                        key_order.append(k)
        values.append(vals)
    if not key_order:
        return [], values

    def infer(strs):
        present = [v for v in strs if v is not None]
        try:
            for v in present:
                int(v)
            return dt.INT64, int
        except ValueError:
            pass
        try:
            for v in present:
                float(v)
            return dt.FLOAT64, float
        except ValueError:
            return dt.STRING, str
    schema = []
    for k in key_order:
        col = [v.get(k) for v in values]
        t, conv = infer(col)
        for d in values:
            if k in d and d[k] is not None:
                d[k] = conv(d[k])
        schema.append((k, t))
    return schema, values


def infer_file_schema(path: str, fmt: str, options: dict) -> pa.Schema:
    if fmt == "parquet":
        import pyarrow.parquet as pq
        return pq.read_schema(path)
    if fmt == "orc":
        import pyarrow.orc as orc
        return orc.ORCFile(path).schema
    if fmt == "csv":
        table = _read_csv(path, options, head_only=True)
        return table.schema
    if fmt == "json":
        table = _read_json(path, options)
        return table.schema
    if fmt == "hivetext":
        # headerless by definition: Hive's LazySimpleSerDe names columns
        # positionally and types default to string
        sep = options.get("sep", "\x01")
        with open(path, "r", errors="replace") as f:
            first = f.readline().rstrip("\n")
        n = len(first.split(sep)) if first else 1
        return pa.schema([pa.field(f"_c{i}", pa.string())
                          for i in range(n)])
    raise ValueError(f"unknown format {fmt}")


def _read_csv(path: str, options: dict, head_only: bool = False) -> pa.Table:
    import pyarrow.csv as pacsv
    read_opts = pacsv.ReadOptions(
        autogenerate_column_names=not options.get("header", True))
    parse_opts = pacsv.ParseOptions(
        delimiter=options.get("sep", options.get("delimiter", ",")))
    conv_opts = pacsv.ConvertOptions(
        null_values=[options.get("nullValue", "")],
        strings_can_be_null=True)
    return pacsv.read_csv(path, read_options=read_opts,
                          parse_options=parse_opts,
                          convert_options=conv_opts)


def _read_hivetext(path: str, options: dict) -> pa.Table:
    """Hive LazySimpleSerDe text: delimiter-separated, NO quoting or
    escaping of the delimiter, nulls as \\N. (CSV quoting rules would
    corrupt values containing quote characters and turn empty strings
    into nulls.)"""
    import pyarrow.csv as pacsv
    read_opts = pacsv.ReadOptions(autogenerate_column_names=True)
    parse_opts = pacsv.ParseOptions(
        delimiter=options.get("sep", "\x01"),
        quote_char=False, escape_char=False)
    conv_opts = pacsv.ConvertOptions(null_values=["\\N"],
                                     strings_can_be_null=True)
    return pacsv.read_csv(path, read_options=read_opts,
                          parse_options=parse_opts,
                          convert_options=conv_opts)


def _read_json(path: str, options: dict) -> pa.Table:
    import pyarrow.json as pajson
    return pajson.read_json(path)


class FileScan(LogicalPlan):
    """Logical scan of files in one format (GpuFileSourceScanExec meta)."""

    def __init__(self, paths, fmt: str, schema: Optional[List] = None,
                 options: Optional[dict] = None,
                 pushed_filter: Optional[E.Expression] = None,
                 conf=None, partition_info=None):
        super().__init__()
        assert fmt in FORMATS, fmt
        self.paths = expand_paths(paths, conf)
        if not self.paths:
            raise FileNotFoundError(f"no files match {paths!r}")
        self.fmt = fmt
        self.options = options or {}
        self.pushed_filter = pushed_filter
        if partition_info is not None:
            # table formats (Delta/Iceberg) carry partition values in
            # their metadata instead of (only) the directory layout
            pschema, by_path = partition_info
            self.partition_schema = list(pschema)
            self._part_values = [dict(by_path.get(p, {}))
                                 for p in self.paths]
        else:
            self.partition_schema, self._part_values = \
                discover_partitions(_rewritten_roots(paths, conf),
                                    self.paths)
        if schema is None:
            if fmt == "avro":
                from .avro import infer_avro_schema
                schema = infer_avro_schema(self.paths[0])
            else:
                arrow_schema = infer_file_schema(self.paths[0], fmt,
                                                 self.options)
                schema = arrow_schema_to_schema(arrow_schema)
            names = [n for n, _ in schema]
            schema = list(schema) + [(k, t) for k, t in
                                     self.partition_schema
                                     if k not in names]
        self._schema = list(schema)

    def partition_values_for(self, path: str) -> dict:
        try:
            return self._part_values[self.paths.index(path)]
        except (ValueError, IndexError):
            return {}

    def pruned_paths(self) -> List[str]:
        """Static partition pruning: pushed-filter conjuncts that
        reference ONLY partition columns evaluate per file on its
        partition values; non-passing files never open (the
        PartitionPruning role; runtime row-level pruning is the join
        bloom filter in exec/join.py)."""
        if self.pushed_filter is None or not self.partition_schema:
            return self.paths
        import numpy as np

        from ..expr import predicates as P
        from ..plan import cpu_eval
        from ..plan.host_table import HostColumn, HostTable
        part_names = {k for k, _ in self.partition_schema}

        def conjuncts(e):
            if isinstance(e, P.And):
                return conjuncts(e.children[0]) + conjuncts(e.children[1])
            return [e]

        def refs(e, out):
            from ..expr import core as E_
            if isinstance(e, E_.ColumnRef):
                out.add(e.name)
            for c in e.children:
                refs(c, out)
            return out

        applicable = [c for c in conjuncts(self.pushed_filter)
                      if refs(c, set()) and refs(c, set()) <= part_names]
        if not applicable:
            return self.paths
        keep = []
        for path, vals in zip(self.paths, self._part_values):
            cols, names = [], []
            for k, t in self.partition_schema:
                v = vals.get(k)
                mask = np.array([v is not None])
                if t == dt.STRING:
                    arr = np.array([v if v is not None else ""],
                                   dtype=object)
                else:
                    arr = np.array([v if v is not None else 0],
                                   dtype=np.dtype(t.physical))
                cols.append(HostColumn(arr, mask, t))
                names.append(k)
            row = HostTable(cols, names)
            ok = True
            for c in applicable:
                try:
                    res = cpu_eval.evaluate(c, row)
                except Exception:
                    continue  # unevaluable conjunct: keep the file
                if not (len(res.values) and res.mask[0]
                        and bool(res.values[0])):
                    ok = False
                    break
            if ok:
                keep.append(path)
        return keep

    @property
    def schema(self) -> Schema:
        return self._schema

    def with_pushed_filter(self, f: Optional[E.Expression]) -> "FileScan":
        out = FileScan.__new__(FileScan)
        LogicalPlan.__init__(out)
        out.paths, out.fmt, out.options = self.paths, self.fmt, self.options
        out.pushed_filter = f
        out._schema = self._schema
        out.partition_schema = self.partition_schema
        out._part_values = self._part_values
        return out

    def with_schema(self, keep: "Schema") -> "FileScan":
        """Column-pruned COPY (ColumnPruning: scans are shared across
        DataFrames, so the original must stay intact)."""
        out = self.with_pushed_filter(self.pushed_filter)
        out._schema = list(keep)
        return out

    def node_description(self) -> str:
        pushed = f", pushed={self.pushed_filter!r}" \
            if self.pushed_filter is not None else ""
        return (f"FileScan[{self.fmt}, {len(self.paths)} files"
                f"{pushed}]")


# ---------------------------------------------------------------------------
# predicate pushdown: Expression -> pyarrow.dataset filter
# ---------------------------------------------------------------------------

def _drop_partition_conjuncts(expr: E.Expression, part_names):
    """Remove AND-conjuncts that reference any partition column; None
    when nothing survives."""
    def refs(e, out):
        if isinstance(e, E.ColumnRef):
            out.add(e.name)
        for c in e.children:
            refs(c, out)
        return out
    if isinstance(expr, P.And):
        l = _drop_partition_conjuncts(expr.children[0], part_names)
        r = _drop_partition_conjuncts(expr.children[1], part_names)
        if l is None:
            return r
        if r is None:
            return l
        return P.And(l, r)
    return None if refs(expr, set()) & part_names else expr


def to_arrow_filter(expr: E.Expression):
    """Best-effort translation; None = not translatable (no pushdown).
    Mirrors the reference's ParquetFilters: only conjuncts that map
    cleanly are pushed; the rest filter on device."""
    import pyarrow.compute as pc
    import pyarrow.dataset  # noqa: F401  (registers field/scalar)

    def field_of(e):
        if isinstance(e, E.ColumnRef):
            return pc.field(e.name)
        return None

    def scalar_of(e):
        if isinstance(e, E.Literal) and e.value is not None:
            v = e.value
            import datetime
            if isinstance(v, (int, float, str, bool, datetime.date,
                              datetime.datetime)):
                return pa.scalar(v)
        return None

    if isinstance(expr, P.And):
        l = to_arrow_filter(expr.children[0])
        r = to_arrow_filter(expr.children[1])
        if l is not None and r is not None:
            return l & r
        return l if r is None else r  # partial conjunction is sound
    if isinstance(expr, P.Or):
        l = to_arrow_filter(expr.children[0])
        r = to_arrow_filter(expr.children[1])
        return (l | r) if (l is not None and r is not None) else None
    if isinstance(expr, (P.EqualTo, P.LessThan, P.GreaterThan,
                         P.LessThanOrEqual, P.GreaterThanOrEqual)):
        f = field_of(expr.children[0])
        s = scalar_of(expr.children[1])
        if f is None or s is None:
            return None
        if isinstance(expr, P.EqualTo):
            return f == s
        if isinstance(expr, P.LessThan):
            return f < s
        if isinstance(expr, P.GreaterThan):
            return f > s
        if isinstance(expr, P.LessThanOrEqual):
            return f <= s
        return f >= s
    if isinstance(expr, P.IsNotNull):
        f = field_of(expr.children[0])
        return f.is_valid() if f is not None else None
    if isinstance(expr, P.IsNull):
        f = field_of(expr.children[0])
        return f.is_null() if f is not None else None
    if isinstance(expr, P.InSet):
        f = field_of(expr.children[0])
        vals = [v for v in expr.values if v is not None]
        if f is None or not vals:
            return None
        return f.isin(vals)
    return None


# ---------------------------------------------------------------------------
# host-side file reading (no device semaphore held)
# ---------------------------------------------------------------------------

def _with_partition_cols(table: "pa.Table", schema: Schema,
                         pvalues: Optional[dict]) -> "pa.Table":
    """Append constant partition-value columns (hive-style layout keeps
    them in the directory names, not the file)."""
    if not pvalues:
        return table
    from .arrow_convert import dtype_to_arrow_type
    for name, t in schema:
        if name in table.column_names or name not in pvalues:
            continue
        at = dtype_to_arrow_type(t)
        v = pvalues[name]
        arr = (pa.nulls(table.num_rows, at) if v is None
               else pa.array([v] * table.num_rows, type=at))
        table = table.append_column(pa.field(name, at), arr)
    return table


def _mark_decode(options, native: bool, cols: int = 0) -> None:
    """Per-scan decode-path visibility (VERDICT r4 weak #7): the exec
    plants a mutable stats dict in its (per-exec copy of) options;
    format branches record whether each FILE decoded through the
    native C++ lane or the pyarrow host path, and the parquet lane
    additionally counts per-column fallbacks."""
    stats = (options or {}).get("_decode_stats")
    if stats is None:
        return
    stats["native_files" if native else "host_files"] += 1
    if cols:
        stats["host_columns"] += cols


#: error classes treated as "this file is corrupt" under
#: srt.sql.ignoreCorruptFiles (Spark catches IOException +
#: RuntimeException inside FilePartitionReader the same broad way):
#: checksum failures, truncated/garbled streams (EOF, struct unpack),
#: decoder rejections (ValueError covers AvroUnsupported and the
#: native parquet/ORC validators), and pyarrow's ArrowException tree.
_CORRUPT_ERRORS = (DataCorruption, OSError, EOFError, ValueError,
                   structlib.error, pa.lib.ArrowException)


def _is_missing_file_error(e: BaseException) -> bool:
    return isinstance(e, FileNotFoundError) or (
        isinstance(e, OSError) and e.errno == errno.ENOENT)


def _timed_decode(tables: Iterator[HostTable], decode_time
                  ) -> Iterator[HostTable]:
    """Charge the time spent INSIDE ``tables`` (read + decode +
    conform of each table it yields, not the time its consumer holds
    the table) to the scan's ``scanDecodeTime``. It runs on whichever
    thread decodes — the reader pool's for a scan of several files — so
    the sum over threads may exceed the wall. A counter and no profiler
    range, on purpose: pool threads are busy most of the time, and a
    short range there would be taken for the cause of device idle gaps
    it only overlaps (PERF.md, "decode has no span")."""
    if decode_time is None:
        yield from tables
        return
    try:
        while True:
            t0 = time.perf_counter_ns()
            try:
                table = next(tables)
            except StopIteration:
                return
            finally:
                decode_time.add(time.perf_counter_ns() - t0)
            yield table
    finally:
        tables.close()


def iter_file_tables(path: str, fmt: str, schema: Schema,
                     options: dict, arrow_filter,
                     max_rows: int, conf=None,
                     partition_values: Optional[dict] = None,
                     dest: Optional[dict] = None
                     ) -> Iterator[HostTable]:
    """Path-naming wrapper over :func:`_iter_file_tables`: any decode
    error is re-raised with the failing file's path prepended (same
    exception type, so callers' handling is unchanged) — the
    GpuMultiFileReader contract that a multi-file task failure
    identifies WHICH file broke.

    Also the per-file seam for Spark's lenient-scan semantics:
    ``srt.sql.ignoreMissingFiles`` swallows files deleted between
    planning and read, and ``srt.sql.ignoreCorruptFiles`` swallows
    decode/checksum failures — both skip-and-warn, keeping any rows the
    file already yielded (FilePartitionReader.ignoreCorruptFiles
    contract). Default for both is false: fail fast.

    ``dest``: this file's rows of its batch's own buffers, where the
    scan laid its batches out (``_PlacedBatch``); the native parquet
    lane decodes into them and marks the tables it placed."""
    from ..conf import (IGNORE_CORRUPT_FILES, IGNORE_MISSING_FILES,
                        active_conf)
    cnf = conf or active_conf()
    try:
        fault_point("scan.file", detail=path)
        yield from _timed_decode(
            _named_file_tables(path, fmt, schema, options, arrow_filter,
                               max_rows, conf, partition_values, dest),
            (options or {}).get("_decode_time"))
    except Exception as e:
        if _is_missing_file_error(e):
            if cnf.get(IGNORE_MISSING_FILES):
                logger.warning(
                    "skipping missing file %s (srt.sql.ignoreMissingFiles"
                    "=true): %s", path, e)
                return
        elif isinstance(e, _CORRUPT_ERRORS):
            if cnf.get(IGNORE_CORRUPT_FILES):
                logger.warning(
                    "skipping corrupt file %s (srt.sql.ignoreCorruptFiles"
                    "=true): %s", path, e)
                return
        raise


def _named_file_tables(path: str, fmt: str, schema: Schema,
                       options: dict, arrow_filter,
                       max_rows: int, conf=None,
                       partition_values: Optional[dict] = None,
                       dest: Optional[dict] = None
                       ) -> Iterator[HostTable]:
    try:
        yield from _iter_file_tables(path, fmt, schema, options,
                                     arrow_filter, max_rows, conf,
                                     partition_values, dest)
    except Exception as e:
        if path not in str(e):
            if isinstance(e, OSError):
                # OSError renders str() from errno/strerror/filename,
                # not args — mutating args would silently drop the
                # prefix; raise a same-type replacement (errno and
                # filename preserved so errno-branching callers are
                # unaffected)
                if e.errno is not None:
                    ne = type(e)(
                        e.errno,
                        f"while reading {fmt} file {path}: "
                        f"{e.strerror or e}", e.filename)
                else:
                    ne = type(e)(f"while reading {fmt} file {path}: {e}")
                raise ne.with_traceback(e.__traceback__) from e
            head = str(e.args[0]) if e.args else str(e)
            e.args = (f"while reading {fmt} file {path}: {head}",
                      ) + tuple(e.args[1:])
        raise


def _native_parquet(conf, options) -> bool:
    """Whether a parquet file of this scan takes the native decode lane
    first. Default-on only when a real accelerator consumes the batches:
    the native path decodes EVERY row (the device filter is ~free on
    TPU); on the CPU-emulation backend pyarrow's row-level filter
    pushdown wins, so the default follows the backend (an explicit
    setting is always honored)."""
    from ..conf import PARQUET_NATIVE_DECODE, active_conf
    c = conf or active_conf()
    if not c.get(PARQUET_NATIVE_DECODE) or \
            (options or {}).get("__force_arrow_decode"):
        return False
    if PARQUET_NATIVE_DECODE.key not in c._settings:
        import jax
        return jax.default_backend() != "cpu"
    return True


def _iter_file_tables(path: str, fmt: str, schema: Schema,
                      options: dict, arrow_filter,
                      max_rows: int, conf=None,
                      partition_values: Optional[dict] = None,
                      dest: Optional[dict] = None
                      ) -> Iterator[HostTable]:
    """Decode one file on the host into row-sliced HostTables conforming
    to the DECLARED schema: positional rename when file column names
    differ (e.g. headerless CSV) and per-column cast to declared dtypes.

    Parquet streams CHUNKED: the dataset scanner yields <= max_rows
    record batches row-group-incrementally, so a single file larger than
    host memory never fully materializes (GpuParquetScan chunked-reader
    role, GpuParquetScan.scala:254). Other formats decode whole (their
    readers are not incremental) and slice.

    ``conf`` must be passed explicitly from pool worker threads (the
    active conf is a thread-local)."""
    from .filecache import resolve_read_path
    pos_deletes = (options or {}).get("__iceberg_pos_deletes")
    if pos_deletes is not None:
        import os as _os
        dels = pos_deletes.get(_os.path.abspath(path))
        if dels is not None and len(dels):
            # iceberg merge-on-read position deletes: drop rows whose
            # in-file position is in the delete set, preserving order
            # (chunked stream => track the running file offset)
            opts2 = {k: v for k, v in options.items()
                     if k != "__iceberg_pos_deletes"}
            # positions are RAW in-file row numbers: no row-level
            # filter pushdown and no native row-group pruning may run
            # underneath (the plan's Filter node still applies)
            opts2["__force_arrow_decode"] = True
            offset = 0
            for ht in iter_file_tables(path, fmt, schema, opts2,
                                       None, max_rows, conf,
                                       partition_values):
                n = ht.num_rows
                hit = dels[(dels >= offset) & (dels < offset + n)]
                offset += n
                if len(hit):
                    mask = np.ones(n, bool)
                    mask[hit - (offset - n)] = False
                    ht = ht.select_rows(mask)
                yield ht
            return
    path = resolve_read_path(path, conf)
    names = [n for n, _ in schema]
    if fmt == "parquet":
        if _native_parquet(conf, options):
            # native column-chunk decode (C++, GIL-free). Fallback to
            # the arrow path happens ONLY before the first table is
            # yielded (setup/footer surprises); after that, per-row-
            # group recovery inside the native iterator keeps the
            # stream alive — re-running the whole file here would
            # duplicate rows already emitted. The pushed arrow filter
            # is a row-level pruning OPTIMIZATION only — the Filter
            # node above the scan stays (push_down_filters), so
            # skipping it in the native path is correct.
            from .. import native
            from .native_parquet import iter_row_group_tables_native
            # a library that does not build is a failure of the
            # installation, not one of the per-file surprises caught
            # below: it raises here instead of becoming a host decode
            native.load()
            failed = False
            first = None
            try:
                it = iter_row_group_tables_native(
                    path, schema, options, max_rows, partition_values,
                    dest)
                first = next(it, None)
            except Exception:
                failed = True
            if not failed and first is not None:
                _mark_decode(options, native=True)
                yield first
                yield from it
                return
            # failed, or the file produced nothing (e.g. empty row
            # groups): the arrow path below also emits the schema-only
            # empty table contract
        _mark_decode(options, native=False)
        import pyarrow.dataset as ds
        dataset = ds.dataset(path, format="parquet")
        cols = names if set(names) <= set(dataset.schema.names) else None
        scanner = dataset.scanner(columns=cols, filter=arrow_filter,
                                  batch_size=max_rows)
        saw = False
        for rb in scanner.to_batches():
            if rb.num_rows == 0:
                continue
            saw = True
            t = _with_partition_cols(pa.Table.from_batches([rb]),
                                     schema, partition_values)
            ht = arrow_to_host_table(_conform(t, schema))
            _apply_read_rebase(ht, options)
            yield ht
        if not saw:
            yield arrow_to_host_table(_conform(
                _with_partition_cols(dataset.schema.empty_table(),
                                     schema, partition_values), schema))
        return
    if fmt == "avro":
        # from-scratch container decode (io/avro.py); route through
        # arrow so the shared _conform rename/cast applies like every
        # other format
        from .arrow_convert import host_table_to_arrow
        from .avro import read_avro_file
        table = host_table_to_arrow(read_avro_file(path))
    elif fmt == "hivetext":
        table = _read_hivetext(path, options)
    elif fmt == "orc":
        from ..conf import ORC_NATIVE_DECODE, active_conf
        if (conf or active_conf()).get(ORC_NATIVE_DECODE) and \
                not partition_values:
            from .native_orc import read_orc_native
            ht_native = read_orc_native(path, schema)
            if ht_native is not None:
                _mark_decode(options, native=True)
                if ht_native.num_rows <= max_rows:
                    # common case: no copy, yield the decoded table
                    _apply_read_rebase(ht_native, options)
                    yield ht_native
                    return
                for start in range(0, ht_native.num_rows, max_rows):
                    idx = np.arange(
                        start, min(start + max_rows,
                                   ht_native.num_rows))
                    ht = ht_native.take(idx)
                    _apply_read_rebase(ht, options)
                    yield ht
                return
        _mark_decode(options, native=False)
        import pyarrow.orc as orc
        f = orc.ORCFile(path)
        cols = names if set(names) <= set(f.schema.names) else None
        table = f.read(columns=cols)
    elif fmt == "csv":
        table = _read_csv(path, options)
    else:
        table = _read_json(path, options)
    table = _conform(_with_partition_cols(table, schema,
                                          partition_values), schema)
    for start in range(0, max(table.num_rows, 1), max_rows):
        sl = table.slice(start, max_rows)
        if sl.num_rows == 0 and start > 0:
            break
        ht = arrow_to_host_table(sl)
        if fmt == "orc":
            _apply_read_rebase(ht, options)
        yield ht


def read_file_to_tables(path: str, fmt: str, schema: Schema,
                        options: dict, arrow_filter,
                        max_rows: int, conf=None,
                        partition_values: Optional[dict] = None
                        ) -> List[HostTable]:
    """Materialized form of iter_file_tables."""
    return list(iter_file_tables(path, fmt, schema, options,
                                 arrow_filter, max_rows, conf,
                                 partition_values))


def _apply_read_rebase(ht: HostTable, options: dict) -> None:
    """datetimeRebaseModeInRead (datetimeRebaseUtils.scala): LEGACY
    rebases pre-1582-10-15 date/timestamp lanes from the hybrid Julian
    calendar the file was written with; EXCEPTION refuses them."""
    from ..expr import timezone as TZ
    mode = options.get("datetimeRebaseMode", "CORRECTED")
    if mode == "CORRECTED":
        return
    for name, col in zip(ht.names, ht.columns):
        if isinstance(col.dtype, dt.DateType):
            old_mask = col.values < TZ._GREGORIAN_CUTOVER_DAYS
            if not old_mask.any():
                continue
            if mode == "EXCEPTION":
                raise ValueError(
                    f"column {name!r} has dates before 1582-10-15; set "
                    "datetimeRebaseMode=LEGACY or CORRECTED "
                    "(spark.sql.parquet.datetimeRebaseModeInRead)")
            col.values = TZ.rebase_julian_to_gregorian_days(
                col.values).astype(col.values.dtype)
        elif isinstance(col.dtype, dt.TimestampType):
            old_mask = col.values < TZ._CUTOVER_US
            if not old_mask.any():
                continue
            if mode == "EXCEPTION":
                raise ValueError(
                    f"column {name!r} has timestamps before 1582-10-15; "
                    "set datetimeRebaseMode=LEGACY or CORRECTED")
            col.values = TZ.rebase_julian_to_gregorian_micros(col.values)
        elif col.dtype.is_nested:
            col.values = TZ.rebase_nested_lanes(
                col.values, col.dtype, to_gregorian=True,
                check_only=(mode == "EXCEPTION"))


def _conform(table: "pa.Table", schema: Schema) -> "pa.Table":
    """Select/rename/cast the decoded Arrow table to the declared
    schema (the read-schema projection the reference's scans apply)."""
    from .arrow_convert import dtype_to_arrow_type
    names = [n for n, _ in schema]
    if set(names) <= set(table.column_names):
        table = table.select(names)
    else:
        # positional mapping (headerless CSV autogenerated names, or a
        # user schema renaming columns)
        if table.num_columns < len(names):
            raise ValueError(
                f"file has {table.num_columns} columns, schema declares "
                f"{len(names)}")
        table = table.select(table.column_names[:len(names)]) \
            .rename_columns(names)
    target = pa.schema([pa.field(n, dtype_to_arrow_type(t))
                        for n, t in schema])
    if table.schema != target:
        table = table.cast(target)
    return table


def _footprint(path: str, fmt: str, schema: Schema):
    """``(bytes, row groups)`` of one file, from its footer. Bytes: what
    its tables will hold once decoded, for the reader pool's byte
    budget: rows (where the format has a footer) times the schema's
    fixed widths, plus the file's uncompressed size when a column is of
    variable width; formats without a footer count their size on disk.
    Row groups: a parquet file's row counts, else None. A file that
    cannot be sized counts ``(0, None)``: decoding it will say what is
    wrong with it, in file order."""
    groups = None
    try:
        size = os.path.getsize(path)
        if fmt == "parquet":
            import pyarrow.parquet as pq
            md = pq.read_metadata(path)
            rows = md.num_rows
            meta = [md.row_group(i) for i in range(md.num_row_groups)]
            size = sum(g.total_byte_size for g in meta)
            groups = [g.num_rows for g in meta]
        elif fmt == "orc":
            import pyarrow.orc as orc
            rows = orc.ORCFile(path).nrows
        else:
            return size, None
    except _CORRUPT_ERRORS:
        return 0, None
    # the host representation of each column: fixed width, or objects
    kinds = [c.values.dtype for c in empty_like(schema).columns]
    fixed = sum(k.itemsize + 1 for k in kinds if k != object)
    var = sum(k == object for k in kinds)
    return rows * fixed + (size + rows * 9 * var if var else 0), groups


class _PlacedBatch:
    """One batch of a scan that laid its batches out from the footers:
    files ``lo..hi`` of the scan, file ``i``'s rows from ``starts[i]``
    on, in buffers of the batch's capacity, one ``(values, validity)``
    pair for each column of ``lanes`` (name, numpy dtype). The buffers
    are the batch's own: made when the first of its files begins to
    decode, on that reader thread, written once by the decoders (every
    row below ``rows``; zero past it), handed to the upload as they are
    and dropped here (``take``)."""

    def __init__(self, lo: int, lanes):
        self.lo = self.hi = lo
        self.rows = 0
        self.lanes = lanes
        self.starts: dict = {}
        #: file -> [(first row among the file's, rows)], a table each
        self.tables: dict = {}
        self._lock = threading.Lock()
        self._buffers = None

    @property
    def capacity(self) -> int:
        return choose_capacity(self.rows)

    @property
    def pad_bytes(self) -> int:
        return (self.capacity - self.rows) * sum(
            k.itemsize + 1 for _, k in self.lanes)

    def add(self, i: int, groups) -> None:
        self.hi = i + 1
        self.starts[i] = self.rows
        self.tables[i] = [(sum(groups[:k]), r)
                          for k, r in enumerate(groups) if r]
        self.rows += sum(groups)

    def dest(self, i: int) -> dict:
        """File ``i``'s rows of the buffers, for its decoder."""
        with self._lock:
            if self._buffers is None:
                cap, n = self.capacity, self.rows
                self._buffers = {}
                for name, kind in self.lanes:
                    values, valid = np.empty(cap, kind), np.empty(cap, bool)
                    values[n:], valid[n:] = 0, False
                    self._buffers[name] = (values, valid)
            lo = self.starts[i]
            hi = lo + sum(r for _, r in self.tables[i])
            return {name: (v[lo:hi], m[lo:hi])
                    for name, (v, m) in self._buffers.items()}

    def take(self, got) -> Optional[HostTable]:
        """The batch as one table over its buffers, once every file
        delivered what its footer promised, each table in its place
        (``HostTable.placed``); None where one did not. ``got``:
        ``(file, table)`` as the files delivered them. Columns without a
        lane (strings) are laid end to end as in any coalesced batch."""
        with self._lock:
            buffers, self._buffers = self._buffers, None
        tables = [t for _, t in got if t.num_rows]
        if buffers is None or [
                (i, t.placed, t.num_rows) for i, t in got if t.num_rows] \
                != [(i, at, r) for i in range(self.lo, self.hi)
                    for at, r in self.tables[i]]:
            return None
        names = tables[0].names
        loose = [j for j, name in enumerate(names) if name not in buffers]
        joined = concat_tables(
            [HostTable([t.columns[j] for j in loose],
                       [names[j] for j in loose]) for t in tables]) \
            if loose else None
        cols = []
        for name, c in zip(names, tables[0].columns):
            if name in buffers:
                v, m = buffers[name]
                cols.append(HostColumn(v[:self.rows], m[:self.rows],
                                       c.dtype, padded=(v, m)))
            else:
                cols.append(joined.column(name))
        return HostTable(cols, names)


def _lay_out(groups, costs, reader: str, max_rows: int, max_bytes: int,
             lanes) -> dict:
    """``{file: _PlacedBatch}`` for the files whose batch can be decoded
    in place. ``groups[i]``: file ``i``'s row groups' rows (None:
    unknown). The batches are today's, by today's rule, which runs over
    the tables a file yields (a row group, cut at ``max_rows``):
    COALESCING adds tables until ``rows >= max_rows`` and flushes,
    MULTITHREADED makes a batch of each. A batch is placed when it has
    rows, each of its files lies in it whole with a footer that says
    where, and its files' costs plus the padding fit the budget alone;
    the other files are read as before."""
    batches, cur, rows = [], [], 0
    for i, rgs in enumerate(groups):
        for r in [min(max_rows, g - at) for g in rgs or ()
                  for at in range(0, g, max_rows)] or [0]:
            cur.append(i)
            rows += r
            if reader == "MULTITHREADED" or rows >= max_rows:
                batches.append(cur)
                cur, rows = [], 0
    if cur:
        batches.append(cur)
    seen = {}
    for b, files in enumerate(batches):
        for i in files:
            seen.setdefault(i, set()).add(b)
    placed = {}
    for files in batches:
        files = sorted(set(files))
        if any(groups[i] is None or len(seen[i]) > 1 for i in files):
            continue
        batch = _PlacedBatch(files[0], lanes)
        for i in files:
            batch.add(i, groups[i])
        if batch.rows and batch.pad_bytes + sum(
                costs[i] for i in files) <= max_bytes:
            placed.update((i, batch) for i in files)
    return placed


class FileSourceScanExec(TpuExec):
    """Leaf exec: host-decode files, upload to device.

    reader type (srt.sql.format.parquet.reader.type):
      PERFILE | COALESCING | MULTITHREADED
    """

    def __init__(self, scan: FileScan):
        super().__init__()
        self.scan = scan
        self._schema = scan.schema
        #: runtime dynamic partition pruning (GpuDynamicPruningExpression
        #: role): {partition column -> allowed values}, installed by a
        #: broadcast join after its build side materializes and BEFORE
        #: this scan's first file opens
        self.runtime_part_filter: Optional[dict] = None
        # partition columns live in directory names, not the files —
        # conjuncts over them must not reach the pyarrow file filter
        # (they drive pruned_paths instead)
        pushed = scan.pushed_filter
        if pushed is not None and scan.partition_schema:
            part = {k for k, _ in scan.partition_schema}
            pushed = _drop_partition_conjuncts(pushed, part)
        self._arrow_filter = (to_arrow_filter(pushed)
                              if pushed is not None else None)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def _host_tables(self, ctx: ExecContext) -> Iterator[HostTable]:
        conf = ctx.conf
        reader = self.scan.options.get("_reader_override") or \
            conf.get(READER_TYPE).upper()
        max_rows = conf.get(MAX_READER_BATCH_SIZE_ROWS)
        # resolve conf-driven per-read settings HERE (the session conf
        # is a thread-local; pool worker threads must not consult it)
        from ..conf import PARQUET_REBASE_READ
        options = dict(self.scan.options)
        options.setdefault("datetimeRebaseMode",
                           conf.get(PARQUET_REBASE_READ))
        # decode-path visibility: format branches bump these counters
        # (thread-safe enough: int += under the GIL) and do_execute
        # flushes them into scan metrics
        stats = self._decode_stats = {
            "native_files": 0, "host_files": 0, "host_columns": 0,
            "pooled_files": 0, "ahead_files": 0,
            "batches": 0, "inplace_batches": 0, "reader_threads_peak": 0}
        options["_decode_stats"] = stats
        # read + decode + conform of every file, timed where it runs
        # (iter_file_tables, on the pool's threads): thread time
        options["_decode_time"] = ctx.metrics_for(self.exec_id).setdefault(
            "scanDecodeTime",
            Metric("scanDecodeTime", Metric.MODERATE, "ns"))
        args = (self.scan.fmt, self._schema, options,
                self._arrow_filter, max_rows, conf)
        scan_paths = self.scan.pruned_paths()
        pruned = len(self.scan.paths) - len(scan_paths)
        if pruned:
            m = ctx.metrics_for(self.exec_id)
            m.setdefault("partitionsPruned",
                         Metric("partitionsPruned",
                                Metric.MODERATE)).add(pruned)
        if self.runtime_part_filter:
            before = len(scan_paths)
            scan_paths = [
                p for p in scan_paths
                if all(self.scan.partition_values_for(p).get(k) in vals
                       for k, vals in self.runtime_part_filter.items())]
            m = ctx.metrics_for(self.exec_id)
            m.setdefault("dppPrunedFiles",
                         Metric("dppPrunedFiles",
                                Metric.MODERATE)).add(
                before - len(scan_paths))

        def pv(p):
            return self.scan.partition_values_for(p)
        if reader in ("COALESCING", "MULTITHREADED") and len(scan_paths) > 1:
            files, placed = self._decoded_files(ctx, scan_paths, args, pv,
                                                reader)
            try:
                yield from self._batch_tables(files, placed, scan_paths,
                                              reader, max_rows, stats)
            finally:
                files.close()
                stats["pooled_files"] += files.pooled
                stats["ahead_files"] += files.ahead
                stats["reader_threads_peak"] = max(
                    stats["reader_threads_peak"], files.threads_peak)
        else:
            for p in scan_paths:
                for t in iter_file_tables(p, *args, pv(p)):
                    yield p, t

    @staticmethod
    def _batch_tables(files, placed: dict, scan_paths: List[str],
                      reader: str, max_rows: int, stats: dict):
        """``(path or None, table)`` a batch, from the pool's ``(file,
        table)`` stream. A placed batch (``_lay_out``) is the table over
        its own buffers once its files have all delivered in place;
        where one did not, and for every other file, the batch is
        today's: a table a batch (MULTITHREADED), or tables laid end to
        end until ``rows >= max_rows`` (COALESCING)."""
        def flush(tables):
            if reader == "MULTITHREADED":
                return [(scan_paths[i], t) for i, t in tables]
            return [(None, concat_tables([t for _, t in tables]))] \
                if tables else []

        def finish(batch, got):
            table = batch.take(got)
            if table is None:
                return flush(got)
            stats["inplace_batches"] += 1
            return [(scan_paths[batch.lo] if reader == "MULTITHREADED"
                     else None, table)]

        batch, got, rows = None, [], 0
        for i, t in files:
            if placed.get(i) is not batch:
                yield from finish(batch, got) if batch else flush(got)
                batch, got, rows = placed.get(i), [], 0
            got.append((i, t))
            rows += t.num_rows
            if batch is None and (reader == "MULTITHREADED"
                                  or rows >= max_rows):
                yield from flush(got)
                got, rows = [], 0
        yield from finish(batch, got) if batch else flush(got)

    def _decoded_files(self, ctx: ExecContext, scan_paths: List[str],
                       args: tuple, pv, reader: str):
        """The tables of ``scan_paths`` in file order, as ``(index of the
        file, HostTable)``, decoded ahead of this thread on the kept
        reader threads (exec/pipeline.py ``RunAhead``): at most
        srt.sql.multiThreadedRead.numThreads of them, no more than the
        host has cores, with no more decoded and not yet taken than
        srt.exec.pipeline.maxBytesInFlight, each file sized from its
        footer. A file over that budget alone streams through this
        thread row group by row group, as every file of a PERFILE scan
        does, so it never materialises whole.

        With them ``{file: _PlacedBatch}``: where parquet files go
        through the native lane, the footers also say which batch each
        file lands in and where, so a file decodes into its rows of that
        batch's buffers (``_lay_out``). Such a file's bytes stay on the
        budget until its batch's last file is taken, and the first file
        of a batch carries the padding."""
        conf = ctx.conf
        fmt, schema, options, _, max_rows, _ = args
        sized = [_footprint(p, fmt, schema) for p in scan_paths]
        costs = [cost for cost, _ in sized]
        max_bytes = conf.get(PIPELINE_MAX_BYTES)
        placed = {}
        if fmt == "parquet" and _native_parquet(conf, options) and \
                not options.get("__iceberg_pos_deletes"):
            from .native_parquet import placed_lanes
            lanes = placed_lanes(schema)
            if lanes:
                placed = _lay_out([g for _, g in sized], costs, reader,
                                  max_rows, max_bytes, lanes)
        for i, batch in placed.items():
            if i == batch.lo:
                costs[i] += batch.pad_bytes

        def task(i, p):
            batch = placed.get(i)
            if batch is None:
                return costs[i], lambda: iter_file_tables(p, *args, pv(p))
            return costs[i], lambda: iter_file_tables(
                p, *args, pv(p), batch.dest(i))
        return RunAhead(
            [task(i, p) for i, p in enumerate(scan_paths)],
            threads=min(conf.get(READER_THREADS), os.cpu_count() or 1),
            max_bytes=max_bytes, conf=conf,
            query=ctx.query, name=f"decode-{self.exec_id}",
            affinity=f"decode:{scan_paths[0]}",
            hold_until=[placed[i].hi - 1 if i in placed else i
                        for i in range(len(scan_paths))]), placed

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        m = ctx.metrics_for(self.exec_id)
        scan_time = m.setdefault("scanTime", Metric("scanTime",
                                                    Metric.MODERATE, "ns"))
        scan_wait = m.setdefault("scanWaitTime", Metric("scanWaitTime",
                                                        Metric.MODERATE,
                                                        "ns"))
        from ..expr.misc import set_input_file
        empty = True
        sizes = {}
        tables = self._host_tables(ctx)
        batches = 0
        try:
            while True:
                # this thread stands waiting for the next batch's table:
                # on the reader pool for the batch's files (and, where
                # they did not decode into the batch's own buffers, their
                # concatenation), or decoding inline where the scan has
                # no pool (one file, PERFILE, a file over the budget)
                t0 = time.perf_counter_ns()
                with annotate("scan.wait"):
                    item = next(tables, None)
                t1 = time.perf_counter_ns()
                scan_wait.add(t1 - t0)
                if item is None:
                    break
                path, table = item
                if table.num_rows == 0 and not empty:
                    continue
                empty = False
                with annotate("scan.upload"), ctx.semaphore:
                    # the semaphore is held only for the upload
                    batch = table_to_batch(table)
                scan_time.add(time.perf_counter_ns() - t1)
                # file context for input_file_name()/blocks: whole-file
                # reads report (0, file_size); coalesced multi-file
                # batches have no single file (empty name, Spark contract)
                if path is not None:
                    if path not in sizes:
                        try:
                            sizes[path] = os.path.getsize(path)
                        except OSError:
                            sizes[path] = 0
                    set_input_file(path, 0, sizes[path])
                else:
                    set_input_file(None)
                batches += 1
                yield batch
        finally:
            # an abandoned scan (LocalLimit, error unwind) parks its
            # reader threads here, on the thread that borrowed them
            tables.close()
        stats = getattr(self, "_decode_stats", None)
        if stats and (stats["native_files"] or stats["host_files"]):
            stats["batches"] = batches
            for key, mname in (("native_files", "scanNativeDecodedFiles"),
                               ("host_files", "scanHostDecodedFiles"),
                               ("host_columns",
                                "scanHostDecodedColumns"),
                               ("pooled_files", "scanPooledFiles"),
                               ("ahead_files", "scanDecodeAheadFiles"),
                               ("batches", "scanBatches"),
                               ("inplace_batches", "scanInPlaceBatches")):
                if stats[key]:
                    m.setdefault(mname, Metric(mname, Metric.MODERATE)) \
                        .add(stats[key])
            if stats["reader_threads_peak"]:
                # a gauge: the most reader threads inside a file at once
                # over the process while this scan's decoded, not a sum
                peak = m.setdefault("scanReaderThreadsPeak", Metric(
                    "scanReaderThreadsPeak", Metric.MODERATE))
                peak.set(max(peak.value, stats["reader_threads_peak"]))

    def node_description(self) -> str:
        desc = "Tpu" + self.scan.node_description()
        if self.scan.fmt in ("parquet", "orc"):
            # static plan-time marker; the scanNative/HostDecodedFiles
            # metrics carry the per-run truth
            desc += " decode=native-eligible"
        return desc
