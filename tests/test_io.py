"""I/O layer tests: scans (all reader modes), predicate pushdown,
writers, partitioned writes, round trips (SURVEY §2.6 equivalents)."""

import datetime
import decimal
import os
import time

import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.conf import READER_TYPE, SrtConf
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.plan import TpuSession
from spark_rapids_tpu.testing import (DateGen, DoubleGen, IntGen, StringGen,
                                      TimestampGen, assert_tpu_cpu_equal_df,
                                      gen_table)


@pytest.fixture(scope="module")
def session():
    return TpuSession()


@pytest.fixture(scope="module")
def pq_dir(tmp_path_factory, session):
    """Three parquet files with the same schema."""
    d = tmp_path_factory.mktemp("pq")
    gens = {"k": IntGen(lo=0, hi=9), "v": DoubleGen(no_special=True),
            "s": StringGen(max_len=6), "d": DateGen()}
    for i in range(3):
        data, schema = gen_table(gens, n=100, seed=i)
        df = session.create_dataframe(data, schema)
        df.write.mode("append").parquet(str(d))
    return str(d)


def test_parquet_roundtrip(session, tmp_path):
    data, schema = gen_table(
        {"i": IntGen(), "f": DoubleGen(), "s": StringGen(),
         "d": DateGen(), "t": TimestampGen()}, n=64)
    df = session.create_dataframe(data, schema)
    path = str(tmp_path / "rt")
    df.write.parquet(path)
    back = session.read.parquet(path)
    assert [t for _, t in back.schema] == [t for _, t in schema]
    orig = df.collect()
    got = back.collect()
    key = lambda r: str(sorted((k, str(v)) for k, v in r.items()))
    assert sorted(got, key=key) == sorted(orig, key=key)


def test_orc_roundtrip(session, tmp_path):
    data, schema = gen_table({"i": IntGen(), "s": StringGen()}, n=32)
    df = session.create_dataframe(data, schema)
    path = str(tmp_path / "orc")
    df.write.orc(path)
    back = session.read.orc(path).collect()
    assert len(back) == 32


def test_csv_roundtrip(session, tmp_path):
    df = session.create_dataframe(
        {"a": [1, 2, 3], "b": ["x", "y", "z"]})
    path = str(tmp_path / "csv")
    df.write.csv(path)
    back = session.read.csv(path).collect()
    assert sorted(r["a"] for r in back) == [1, 2, 3]


def test_json_roundtrip(session, tmp_path):
    df = session.create_dataframe({"a": [1, None, 3], "s": ["p", "q", None]})
    path = str(tmp_path / "json")
    df.write.json(path)
    back = session.read.json(path).collect()
    assert len(back) == 3
    assert any(r["a"] is None for r in back)


@pytest.mark.parametrize("reader", ["PERFILE", "COALESCING",
                                    "MULTITHREADED"])
def test_reader_modes(session, pq_dir, reader):
    conf = SrtConf({READER_TYPE.key: reader})
    s = TpuSession(conf)
    df = s.read.parquet(pq_dir)
    assert df.count() == 300
    agg = df.group_by("k").agg(CountStar().alias("n")).collect()
    assert sum(r["n"] for r in agg) == 300


def test_scan_filter_aggregate_differential(session, pq_dir):
    df = (session.read.parquet(pq_dir)
          .filter((col("k") >= 3) & col("v").is_not_null())
          .group_by("k").agg(Sum(col("v")).alias("sv"),
                             CountStar().alias("n")))
    assert_tpu_cpu_equal_df(df)


def test_predicate_pushdown_prunes(session, tmp_path):
    """Row-group pruning: a filter on a sorted column must reduce rows
    decoded (observable via the scan's arrow filter)."""
    from spark_rapids_tpu.io.scan import FileScan, to_arrow_filter
    d = tmp_path / "pp"
    df = session.create_dataframe({"x": list(range(1000))})
    df.write.parquet(str(d))
    scan = FileScan(str(d), "parquet")
    pushed = scan.with_pushed_filter(col("x") < 10)
    assert pushed.pushed_filter is not None
    assert to_arrow_filter(pushed.pushed_filter) is not None
    # full pipeline: filter over scan gets pushed and stays correct
    q = session.read.parquet(str(d)).filter(col("x") < 10)
    assert q.count() == 10


def test_pushdown_untranslatable_is_safe(session, tmp_path):
    from spark_rapids_tpu.io.scan import to_arrow_filter
    from spark_rapids_tpu.expr import mathfns as M
    # sqrt(x) < 3 is not translatable -> no pushdown, still correct
    assert to_arrow_filter(M.Sqrt(col("x")) < 3.0) is None
    d = tmp_path / "pu"
    session.create_dataframe({"x": [1.0, 4.0, 9.0, 16.0]}).write.parquet(
        str(d))
    out = session.read.parquet(str(d)).filter(
        M.Sqrt(col("x")) < 3.0).collect()
    assert sorted(r["x"] for r in out) == [1.0, 4.0]


def test_partitioned_write(session, tmp_path):
    d = str(tmp_path / "part")
    df = session.create_dataframe(
        {"k": ["a", "b", "a", None], "v": [1, 2, 3, 4]})
    stats = df.write.partition_by("k").parquet(d)
    assert stats.num_files == 3
    assert stats.num_rows == 4
    assert os.path.isdir(os.path.join(d, "k=a"))
    assert os.path.isdir(os.path.join(d, "k=__HIVE_DEFAULT_PARTITION__"))
    # partition column is recoverable from dir structure; data cols intact
    back = session.read.parquet(os.path.join(d, "k=a")).collect()
    assert sorted(r["v"] for r in back) == [1, 3]


def test_write_modes(session, tmp_path):
    d = str(tmp_path / "modes")
    df = session.create_dataframe({"v": [1]})
    df.write.parquet(d)
    with pytest.raises(FileExistsError):
        df.write.parquet(d)
    df.write.mode("append").parquet(d)
    assert session.read.parquet(d).count() == 2
    df.write.mode("overwrite").parquet(d)
    assert session.read.parquet(d).count() == 1


def test_decimal_parquet_roundtrip(session, tmp_path):
    vals = [decimal.Decimal("12.34"), decimal.Decimal("-0.01"), None]
    df = session.create_dataframe({"d": vals},
                                  [("d", dt.DecimalType(10, 2))])
    path = str(tmp_path / "dec")
    df.write.parquet(path)
    back = session.read.parquet(path).collect()
    assert [r["d"] for r in back] == vals


def test_headerless_csv_with_schema(session, tmp_path):
    p = tmp_path / "h.csv"
    p.write_text("1,x\n2,y\n")
    df = session.read.csv(str(p), header=False,
                          schema=[("a", dt.INT64), ("b", dt.STRING)])
    out = df.collect()
    assert out == [{"a": 1, "b": "x"}, {"a": 2, "b": "y"}]


def test_user_schema_casts_parquet(session, tmp_path):
    d = str(tmp_path / "cast")
    session.create_dataframe({"a": [1, 2]},
                             [("a", dt.INT32)]).write.parquet(d)
    back = session.read.parquet(d, schema=[("a", dt.INT64)])
    assert back.schema == [("a", dt.INT64)]
    rows = back.collect()
    assert sorted(r["a"] for r in rows) == [1, 2]
    # and the physical lanes really are int64 (sum works on device)
    assert back.agg(Sum(col("a")).alias("s")).collect()[0]["s"] == 3


# --- avro (from-scratch container codec) + hive text ------------------------

def test_avro_roundtrip(session, tmp_path):
    import datetime
    from spark_rapids_tpu.columnar import dtypes as dt
    data = {"i": [1, None, 3], "s": ["a", "b", None],
            "f": [1.5, None, -2.25],
            "d": [datetime.date(2020, 1, 2), None,
                  datetime.date(1999, 12, 31)],
            "t": [datetime.datetime(2021, 6, 1, 12, 30,
                                    tzinfo=datetime.timezone.utc),
                  None, None],
            "b": [True, False, None]}
    schema = [("i", dt.INT64), ("s", dt.STRING), ("f", dt.FLOAT64),
              ("d", dt.DATE), ("t", dt.TIMESTAMP), ("b", dt.BOOL)]
    df = session.create_dataframe(data, schema)
    path = str(tmp_path / "t.avro")
    import os
    os.makedirs(str(tmp_path / "av"), exist_ok=True)
    df.write.avro(str(tmp_path / "av"))
    back = session.read.avro(str(tmp_path / "av")).to_pydict()
    assert back == data


def test_avro_deflate_and_null_codecs(session, tmp_path):
    from spark_rapids_tpu.io.avro import read_avro_file, write_avro_file
    from spark_rapids_tpu.plan.host_table import from_pydict, to_pydict
    from spark_rapids_tpu.columnar import dtypes as dt
    data = {"x": list(range(500)), "y": [f"row{i}" for i in range(500)]}
    schema = [("x", dt.INT64), ("y", dt.STRING)]
    ht = from_pydict(data, schema)
    for codec in ("null", "deflate"):
        p = str(tmp_path / f"c_{codec}.avro")
        write_avro_file(ht, p, codec=codec)
        assert to_pydict(read_avro_file(p)) == data


def test_avro_query_through_engine(session, tmp_path):
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.expr.aggregates import Sum
    data = {"k": [1, 2, 1, 2, 1], "v": [10, 20, 30, 40, 50]}
    df = session.create_dataframe(data, [("k", dt.INT32), ("v", dt.INT64)])
    out_dir = str(tmp_path / "q")
    df.write.avro(out_dir)
    q = (session.read.avro(out_dir)
         .group_by(col("k")).agg(Sum(col("v")).alias("sv")))
    assert_tpu_cpu_equal_df(q)


def test_hive_text_roundtrip(session, tmp_path):
    from spark_rapids_tpu.columnar import dtypes as dt
    data = {"a": [1, 2, 3], "s": ["x", "yy", "zzz"]}
    schema = [("a", dt.INT64), ("s", dt.STRING)]
    df = session.create_dataframe(data, schema)
    out_dir = str(tmp_path / "ht")
    df.write.hive_text(out_dir)
    back = session.read.hive_text(out_dir, schema=schema).to_pydict()
    assert back == data


def test_hive_text_preserves_empty_and_quotes(session, tmp_path):
    """LazySimpleSerDe semantics: empty string is NOT null (null is \\N)
    and quote characters are literal data, not CSV quoting."""
    from spark_rapids_tpu.columnar import dtypes as dt
    data = {"s": ['a"b', "", None, "x,y"], "n": [1, 2, None, 4]}
    schema = [("s", dt.STRING), ("n", dt.INT64)]
    df = session.create_dataframe(data, schema)
    out_dir = str(tmp_path / "htq")
    df.write.hive_text(out_dir)
    back = session.read.hive_text(out_dir, schema=schema).to_pydict()
    assert back == data


def test_hive_text_schema_inference(session, tmp_path):
    """hive_text() without a schema infers _c0.. string columns."""
    from spark_rapids_tpu.columnar import dtypes as dt
    df = session.create_dataframe({"a": [1, 2], "b": ["x", "y"]},
                                  [("a", dt.INT64), ("b", dt.STRING)])
    out_dir = str(tmp_path / "hti")
    df.write.hive_text(out_dir)
    back = session.read.hive_text(out_dir)
    assert [n for n, _ in back.schema] == ["_c0", "_c1"]
    got = back.to_pydict()
    assert got["_c0"] == ["1", "2"] and got["_c1"] == ["x", "y"]


def test_avro_unknown_logical_type_raises(tmp_path):
    """decimal/time logical types must raise AvroUnsupported (clear CPU
    fallback), not silently decode base types into garbage."""
    import json as jsonlib

    import pytest

    from spark_rapids_tpu.io.avro import AvroUnsupported, schema_from_avro
    sch = {"type": "record", "name": "r", "fields": [
        {"name": "d", "type": {"type": "bytes", "logicalType": "decimal",
                               "precision": 10, "scale": 2}}]}
    with pytest.raises(AvroUnsupported):
        schema_from_avro(sch)


# ---------------------------------------------------------------------------
# multi-file scans decode on the kept reader threads, ahead of the scan's
# own thread (FileSourceScanExec._decoded_files, exec/pipeline.py RunAhead)
# ---------------------------------------------------------------------------

#: a budget no file with a row fits: they decode inline, on the scan's thread
INLINE = {"srt.exec.pipeline.maxBytesInFlight": "1"}
UNEVEN_ROWS = (700, 0, 130, 1024, 5, 333)


@pytest.fixture(scope="module")
def uneven_dir(tmp_path_factory):
    """Six parquet files of unequal row counts, one of them empty."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    d = tmp_path_factory.mktemp("uneven")
    start = 0
    for i, n in enumerate(UNEVEN_ROWS):
        pq.write_table(
            pa.table({"a": np.arange(start, start + n, dtype=np.int64),
                      "b": np.arange(start, start + n) / 7.0}),
            str(d / f"part-{i:05d}.parquet"))
        start += n
    return str(d)


def _host_tables(path, settings, reader="COALESCING", doomed=None):
    """What the scan's thread is handed, batch by batch: (file name or
    None, column a, column b), the decode counters, and the error that
    ended the scan. ``doomed`` is removed between planning and reading."""
    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.io.scan import FileScan, FileSourceScanExec
    conf = SrtConf({READER_TYPE.key: reader,
                    "srt.sql.reader.batchSizeRows": "512", **settings})
    node = FileSourceScanExec(FileScan(path, "parquet"))
    if doomed:
        os.remove(doomed)
    out, error = [], None
    try:
        for p, t in node._host_tables(ExecContext(conf)):
            out.append((p and os.path.basename(p),
                        t.column("a").values.tolist(),
                        t.column("b").values.tolist()))
    except Exception as e:
        error = e
    return out, node._decode_stats, error


def _rows(batches):
    return [v for _, a, _ in batches for v in a]


@pytest.mark.parametrize("reader", ["COALESCING", "MULTITHREADED"])
def test_pooled_scan_yields_the_inline_scans_batches(uneven_dir, reader):
    pooled, stats, _ = _host_tables(uneven_dir, {}, reader)
    inline, inline_stats, _ = _host_tables(uneven_dir, INLINE, reader)
    assert pooled == inline  # same files in the same batch, same order
    assert _rows(pooled) == list(range(sum(UNEVEN_ROWS)))
    assert stats["pooled_files"] == len(UNEVEN_ROWS)
    assert 0 <= stats["ahead_files"] <= stats["pooled_files"]
    # the empty file sizes 0 and fits any budget
    assert inline_stats["pooled_files"] == UNEVEN_ROWS.count(0)
    if reader == "MULTITHREADED":
        assert pooled[0][0] == "part-00000.parquet"
    else:
        assert {p for p, _, _ in pooled} == {None}


@pytest.mark.parametrize("reader", ["COALESCING", "MULTITHREADED"])
@pytest.mark.parametrize("lenient", [False, True])
def test_pooled_scan_corrupt_file_k_of_n(uneven_dir, tmp_path, reader,
                                         lenient):
    """File 3 of 6 is garbage: what the files before it gave arrives as
    it does from the inline scan, then the decoder's own exception with
    the path in its message; with ignoreCorruptFiles the file is
    skipped."""
    import shutil
    d = tmp_path / "mix"
    shutil.copytree(uneven_dir, d)
    victim = d / "part-00003.parquet"
    victim.write_bytes(b"PAR1 this is not a parquet file PAR1")
    before = sum(UNEVEN_ROWS[:3])
    settings = {"srt.sql.ignoreCorruptFiles": lenient,
                "srt.sql.reader.batchSizeRows": "100"}
    got, stats, error = _host_tables(str(d), settings, reader)
    inline, _, inline_error = _host_tables(str(d), {**settings, **INLINE},
                                           reader)
    assert got == inline and type(error) is type(inline_error)
    if lenient:
        assert error is None and _rows(got) == [
            v for v in range(sum(UNEVEN_ROWS))
            if not before <= v < before + UNEVEN_ROWS[3]]
        assert stats["pooled_files"] == len(UNEVEN_ROWS)
        return
    assert str(victim) in str(error) and str(error) == str(inline_error)
    # a batch a table: every row in front of the file; the coalescing
    # reader loses the batch it was filling, as it does inline
    assert _rows(got) == list(range(
        before if reader == "MULTITHREADED" else before - before % 100))


@pytest.mark.parametrize("lenient", [False, True])
def test_pooled_scan_missing_file(uneven_dir, tmp_path, lenient):
    import shutil
    d = tmp_path / "vanish"
    shutil.copytree(uneven_dir, d)
    gone = str(d / "part-00002.parquet")
    got, _, error = _host_tables(
        str(d), {"srt.sql.ignoreMissingFiles": lenient}, doomed=gone)
    if lenient:
        lo = sum(UNEVEN_ROWS[:2])
        assert error is None and _rows(got) == [
            v for v in range(sum(UNEVEN_ROWS))
            if not lo <= v < lo + UNEVEN_ROWS[2]]
    else:
        assert isinstance(error, FileNotFoundError) and gone in str(error)


def test_scan_file_fault_fires_on_a_reader_thread_in_scope(uneven_dir,
                                                          monkeypatch):
    """An armed ``scan.file`` clause matches the file it names wherever
    the file decodes, and the reader thread carries the scan operator's
    fault scope as the scan's own thread does."""
    import threading

    from spark_rapids_tpu.io import scan as scan_mod
    from spark_rapids_tpu.robustness import faults
    from spark_rapids_tpu.robustness.integrity import DataCorruption
    hits = []
    real = scan_mod.fault_point

    def spy(site, detail=None):
        hits.append((threading.current_thread().name, faults.current_op(),
                     os.path.basename(detail)))
        return real(site, detail=detail)
    monkeypatch.setattr(scan_mod, "fault_point", spy)
    plan = faults.arm_fault_plan(
        "seed=3|scan.file:corrupt@1~part-00004")
    try:
        from spark_rapids_tpu.exec.base import ExecContext
        from spark_rapids_tpu.io.scan import FileScan, FileSourceScanExec
        node = FileSourceScanExec(FileScan(uneven_dir, "parquet"))
        with pytest.raises(DataCorruption):
            list(node.execute(ExecContext(SrtConf({}))))
        [fired] = plan.fired("scan.file")
        assert fired.detail.endswith("part-00004.parquet")
    finally:
        faults.disarm_fault_plan()
    assert hits and all(
        name.startswith("srt-prefetch-decode-FileSourceScanExec")
        and op.startswith("FileSourceScanExec") for name, op, _ in hits)
    assert "part-00004.parquet" in [f for _, _, f in hits]


def test_limit_mid_scan_parks_the_reader_threads(uneven_dir):
    import threading

    from spark_rapids_tpu.exec import pipeline
    before = pipeline.prefetch_thread_leaks()
    s = TpuSession(SrtConf({"srt.sql.reader.batchSizeRows": "64"}))
    assert len(s.read.parquet(uneven_dir).limit(3).collect()) == 3
    assert pipeline.prefetch_thread_leaks() == before
    assert not [t for t in threading.enumerate()
                if t.name.startswith("srt-prefetch") and t.is_alive()]


def test_two_queries_over_one_table_share_their_reader_threads(
        uneven_dir, monkeypatch):
    import threading

    from spark_rapids_tpu.io import scan as scan_mod
    real = scan_mod.iter_file_tables
    idents = []

    def spy(path, *args):
        idents[-1].add(threading.get_ident())
        time.sleep(0.005)  # so that no one thread takes every file
        return real(path, *args)
    monkeypatch.setattr(scan_mod, "iter_file_tables", spy)
    s = TpuSession()
    for query in (lambda df: df.count(),
                  lambda df: df.filter(col("a") > 5).collect(),
                  lambda df: df.count()):
        idents.append(set())
        query(s.read.parquet(uneven_dir))
    me = threading.get_ident()
    assert len(idents[0]) > 1 and me not in idents[0]
    assert idents[0] >= idents[1] and idents[0] >= idents[2]


def test_scan_counts_pooled_and_decoded_ahead_files(uneven_dir, tmp_path):
    def counters(df):
        df.collect()
        totals = {}
        for per_exec in df.session._last_execution["ctx"].metrics.values():
            for name, metric in per_exec.items():
                totals[name] = totals.get(name, 0) + metric.value
        phases = df.session._last_execution["record"]["phases"]
        assert totals["scanBatches"] == phases["scan_batches"]
        assert totals.get("scanInPlaceBatches", 0) \
            == phases["scan_inplace_batches"]
        return (totals.get("scanPooledFiles", 0),
                totals.get("scanDecodeAheadFiles", 0),
                phases["scan_pooled_files"], phases["scan_ahead_files"],
                phases["scan_inplace_batches"])
    s = TpuSession()
    pooled, ahead, p_pooled, p_ahead, _ = counters(
        s.read.parquet(uneven_dir))
    assert pooled == p_pooled == len(UNEVEN_ROWS)
    assert 0 <= ahead == p_ahead <= pooled
    native = TpuSession(SrtConf(NATIVE))
    assert counters(native.read.parquet(uneven_dir))[4] == 2
    one = str(tmp_path / "one")
    s.create_dataframe({"a": [1, 2, 3]}).write.parquet(one)
    assert len(os.listdir(one)) == 1
    assert counters(s.read.parquet(one)) == (0, 0, 0, 0, 0)
    # a one-file scan has no pool and places nothing, whatever the lane
    assert counters(native.read.parquet(one)) == (0, 0, 0, 0, 0)


# ---------------------------------------------------------------------------
# a laid-out scan: files decode into their batch's own padded buffers
# (io/scan.py _lay_out / _PlacedBatch, io/native_parquet.py)
# ---------------------------------------------------------------------------

#: the native lane is the chip's default; here it is asked for
NATIVE = {"srt.sql.format.parquet.nativeDecode.enabled": "true",
          "srt.sql.reader.batchSizeRows": "1024"}


@pytest.fixture(scope="module")
def placed_dir(tmp_path_factory):
    """The uneven six files under one partition directory: ``a`` int64,
    ``b`` float64 with nulls, ``c`` INT32 (read as bigint), ``s`` string
    with nulls, ``f`` boolean (pyarrow decodes it: no lane of its own in
    a placed batch), and the partition column ``p``."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    d = tmp_path_factory.mktemp("placed")
    os.makedirs(d / "p=7")
    start = 0
    for i, n in enumerate(UNEVEN_ROWS):
        pq.write_table(pa.table({
            "a": np.arange(start, start + n, dtype=np.int64),
            "b": pa.array(np.arange(start, start + n) / 7.0,
                          mask=np.arange(n) % 5 == 0),
            "c": np.arange(n, dtype=np.int32),
            "s": pa.array([f"s{j % 13}" if j % 7 else None
                           for j in range(n)], type=pa.string()),
            "f": np.arange(n) % 3 == 0}),
            str(d / "p=7" / f"part-{i:05d}.parquet"))
        start += n
    return str(d)


def _device_batches(path, settings, reader):
    """The scan's device batches, each as (names, rows, every buffer of
    every column to its full capacity), and the scan's counters."""
    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.io.scan import FileScan, FileSourceScanExec
    conf = SrtConf({READER_TYPE.key: reader, **NATIVE, **settings})
    schema = [(n, dt.INT64 if n == "c" else t)
              for n, t in FileScan(path, "parquet").schema]
    node = FileSourceScanExec(FileScan(path, "parquet", schema=schema))
    ctx = ExecContext(conf)
    out = []
    for b in node.execute(ctx):
        buffers = [[np.asarray(getattr(c, slot)).tolist()
                    for slot in ("data", "offsets", "chars", "validity")
                    if hasattr(c, slot)] for c in b.columns]
        out.append((b.names, int(b.num_rows), buffers))
    counters = {k: m.value for k, m in ctx.metrics_for(node.exec_id).items()}
    return out, counters


@pytest.mark.parametrize("reader", ["COALESCING", "MULTITHREADED"])
def test_placed_batches_equal_the_inline_scans(placed_dir, reader):
    placed, counters = _device_batches(placed_dir, {}, reader)
    inline, inline_counters = _device_batches(placed_dir, INLINE, reader)
    # names, rows, capacity, values (zeros under nulls and in the padding)
    # and validity of every column, batch for batch
    assert placed == inline
    assert [n for _, n, _ in placed] == (
        [1854, 338] if reader == "COALESCING" else [700, 130, 1024, 5, 333])
    assert placed[0][0] == ["a", "b", "c", "s", "f", "p"]
    assert counters["scanInPlaceBatches"] == counters["scanBatches"] \
        == len(placed)
    assert "scanInPlaceBatches" not in inline_counters
    assert inline_counters["scanBatches"] == len(placed)


@pytest.mark.parametrize("reader", ["COALESCING", "MULTITHREADED"])
@pytest.mark.parametrize("spoiled", ["corrupt", "pyarrow"])
def test_a_file_that_cannot_be_placed_spoils_its_batch_alone(
        placed_dir, tmp_path, reader, spoiled):
    """File 2 of 6 is garbage and skipped, or of a codec the native lane
    leaves to pyarrow: its batch is assembled the old way from what its
    files delivered, the other batches still come from their buffers."""
    import shutil

    import pyarrow.parquet as pq
    d = tmp_path / "mix"
    shutil.copytree(placed_dir, d)
    victim = str(d / "p=7" / "part-00002.parquet")
    if spoiled == "corrupt":
        with open(victim, "wb") as f:
            f.write(b"PAR1 this is not a parquet file PAR1")
    else:
        pq.write_table(pq.read_table(victim), victim, compression="lz4")
    settings = {"srt.sql.ignoreCorruptFiles": True}
    got, counters = _device_batches(str(d), settings, reader)
    inline, _ = _device_batches(str(d), {**settings, **INLINE}, reader)
    assert got == inline
    lo = sum(UNEVEN_ROWS[:2])
    assert [v for _, n, cols in got for v in cols[0][0][:n]] == [
        v for v in range(sum(UNEVEN_ROWS))
        if spoiled == "pyarrow" or not lo <= v < lo + UNEVEN_ROWS[2]]
    assert counters["scanBatches"] == len(got)
    # COALESCING: files 0-3 are one batch; MULTITHREADED: a batch a file
    assert counters["scanInPlaceBatches"] == len(got) - (
        reader == "COALESCING" or spoiled == "pyarrow")
