"""Native ORC reader (VERDICT r3 #5; native/orc_decode.cpp +
io/native_orc.py — GpuOrcScan.scala device-decode role): protobuf
metadata walk + C++ deframe/RLEv2/bool-RLE, differential against both
the raw written data and the engine's pyarrow fallback path."""

import numpy as np
import pyarrow as pa
import pytest
from pyarrow import orc

import spark_rapids_tpu  # noqa: F401 (enables x64)
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.io.native_orc import read_orc_native
from spark_rapids_tpu.plan import TpuSession

SCHEMA = [("a", dt.INT64), ("b", dt.INT32), ("c", dt.FLOAT64),
          ("d", dt.INT64)]


def _write(tmp_path, comp, n=30_000, seed=1):
    rng = np.random.default_rng(seed)
    i64 = rng.integers(-10**12, 10**12, n)
    i32 = rng.integers(-10**6, 10**6, n).astype(np.int32)
    f64 = rng.random(n) * 1e6
    seq = np.arange(n) * 5 - 1000
    mask = rng.random(n) < 0.15
    t = pa.table({
        "a": pa.array(np.where(mask, 0, i64), mask=mask),
        "b": pa.array(i32),
        "c": pa.array(f64),
        "d": pa.array(seq),
    })
    p = str(tmp_path / f"t_{comp}.orc")
    orc.write_table(t, p, compression=comp)
    return p, i64, i32, f64, seq, mask


@pytest.mark.parametrize("comp", ["UNCOMPRESSED", "ZLIB", "SNAPPY",
                                  "ZSTD"])
def test_native_orc_roundtrip(tmp_path, comp):
    p, i64, i32, f64, seq, mask = _write(tmp_path, comp)
    ht = read_orc_native(p, SCHEMA)
    assert ht is not None, "file must be inside the native envelope"
    assert ht.num_rows == len(i64)
    assert np.array_equal(ht.column("a").mask, ~mask)
    assert np.array_equal(ht.column("a").values[~mask], i64[~mask])
    assert np.array_equal(ht.column("b").values, i32)
    assert np.allclose(ht.column("c").values, f64)
    assert np.array_equal(ht.column("d").values, seq)


def test_native_orc_matches_pyarrow_path(tmp_path):
    """Engine differential: native decode vs the pyarrow fallback must
    return identical query results."""
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.core import Alias, col
    p, *_ = _write(tmp_path, "ZLIB", n=20_000, seed=3)

    def q(df):
        return sorted(
            (r["b"], round(r["s"], 6))
            for r in df.group_by("b").agg(
                Alias(Sum(col("c")), "s")).collect())
    on = TpuSession(SrtConf(
        {"srt.sql.format.orc.nativeDecode.enabled": True}))
    off = TpuSession(SrtConf(
        {"srt.sql.format.orc.nativeDecode.enabled": False}))
    got_on = q(on.read.orc(p, schema=SCHEMA))
    got_off = q(off.read.orc(p, schema=SCHEMA))
    assert got_on == got_off and len(got_on) > 0


def test_native_orc_strings_decode(tmp_path):
    """Strings are inside the envelope since r5 (direct + dictionary
    encodings) — native decode must match the written data exactly."""
    t = pa.table({"s": pa.array(["x", "yy", None, "", "zzz"]),
                  "v": pa.array([1, 2, 3, 4, 5], pa.int64())})
    p = str(tmp_path / "s.orc")
    orc.write_table(t, p)
    ht = read_orc_native(p, [("s", dt.STRING), ("v", dt.INT64)])
    assert ht is not None
    s = ht.column("s")
    assert list(s.mask) == [True, True, False, True, True]
    assert [v for v, m in zip(s.values, s.mask) if m] == \
        ["x", "yy", "", "zzz"]
    # and the engine end-to-end agrees
    sess = TpuSession(SrtConf({}))
    rows = sess.read.orc(p, schema=[("s", dt.STRING),
                                    ("v", dt.INT64)]).collect()
    assert [r["v"] for r in rows] == [1, 2, 3, 4, 5]
    assert [r["s"] for r in rows] == ["x", "yy", None, "", "zzz"]


def test_native_orc_string_dictionary(tmp_path):
    """Low-cardinality strings trigger ORC's DICTIONARY_V2 encoding."""
    rng = np.random.default_rng(5)
    choices = np.array(["CA", "TX", "NY", "FL"])
    vals = choices[rng.integers(0, 4, 20_000)]
    mask = rng.random(20_000) < 0.1
    t = pa.table({"st": pa.array(np.where(mask, "", vals), mask=mask)})
    p = str(tmp_path / "dict.orc")
    orc.write_table(t, p, compression="ZLIB")
    ht = read_orc_native(p, [("st", dt.STRING)])
    assert ht is not None
    c = ht.column("st")
    assert (np.asarray(c.mask) == ~mask).all()
    got = np.asarray([v for v, m in zip(c.values, c.mask) if m])
    assert (got == vals[~mask]).all()


def test_native_orc_date_decimal_bool(tmp_path):
    import datetime
    import decimal
    days = [0, 1, 365, -100, 19000]
    decs = [decimal.Decimal("1.25"), decimal.Decimal("-99.99"),
            decimal.Decimal("0.01"), None, decimal.Decimal("12345.67")]
    bools = [True, False, None, True, False]
    t = pa.table({
        "dt": pa.array([datetime.date(1970, 1, 1)
                        + datetime.timedelta(days=d) for d in days]),
        "dec": pa.array(decs, pa.decimal128(9, 2)),
        "bl": pa.array(bools),
    })
    p = str(tmp_path / "ddb.orc")
    orc.write_table(t, p)
    schema = [("dt", dt.DATE), ("dec", dt.DecimalType(9, 2)),
              ("bl", dt.BOOL)]
    ht = read_orc_native(p, schema)
    assert ht is not None
    assert list(ht.column("dt").values) == days
    dc = ht.column("dec")
    assert list(dc.mask) == [True, True, True, False, True]
    got = [int(v) for v, m in zip(dc.values, dc.mask) if m]
    assert got == [125, -9999, 1, 1234567]
    bc = ht.column("bl")
    assert list(bc.mask) == [True, True, False, True, True]
    assert [bool(v) for v, m in zip(bc.values, bc.mask) if m] == \
        [True, False, True, False]
    # engine end-to-end (differential vs the pyarrow path)
    on = TpuSession(SrtConf({}))
    off = TpuSession(SrtConf({"srt.sql.format.orc.nativeDecode.enabled":
                              False}))
    r_on = on.read.orc(p, schema=schema).collect()
    r_off = off.read.orc(p, schema=schema).collect()
    assert r_on == r_off


def test_native_orc_timestamp_falls_back(tmp_path):
    import datetime
    t = pa.table({"ts": pa.array([datetime.datetime(2020, 1, 1),
                                  datetime.datetime(2021, 6, 15)])})
    p = str(tmp_path / "ts.orc")
    orc.write_table(t, p)
    assert read_orc_native(p, [("ts", dt.TIMESTAMP)]) is None


def test_native_orc_patched_base(tmp_path):
    """Sparse huge outliers force PATCHED_BASE runs; entry widths round
    to closestFixedBits(gap+patch) per the spec."""
    rng = np.random.default_rng(3)
    v = rng.integers(0, 100, 50_000)
    out_idx = rng.choice(50_000, 300, replace=False)
    v[out_idx] = rng.integers(10**14, 10**15, 300)
    p = str(tmp_path / "pb.orc")
    orc.write_table(pa.table({"x": pa.array(v)}), p, compression="ZLIB")
    ht = read_orc_native(p, [("x", dt.INT64)])
    assert ht is not None
    assert np.array_equal(ht.column("x").values, v)


def test_scan_decode_path_metric(tmp_path):
    """Native-vs-host decode is VISIBLE per scan (VERDICT r4 weak #7):
    an in-envelope file bumps scanNativeDecodedFiles, a fallback file
    bumps scanHostDecodedFiles."""
    import datetime
    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.plan import overrides

    def run_scan(path, schema):
        sess = TpuSession(SrtConf({}))
        df = sess.read.orc(path, schema=schema)
        conf = sess.conf
        physical = overrides.apply_overrides(df.plan, conf)
        ctx = ExecContext(conf)
        for _ in physical.execute(ctx):
            pass
        return {name: ms[name].value for ms in ctx.metrics.values()
                for name in ms
                if name.startswith("scan") and "Decoded" in name}

    native_t = pa.table({"v": pa.array([1, 2, 3], pa.int64())})
    p1 = str(tmp_path / "native.orc")
    orc.write_table(native_t, p1)
    m1 = run_scan(p1, [("v", dt.INT64)])
    assert m1.get("scanNativeDecodedFiles") == 1
    assert "scanHostDecodedFiles" not in m1

    import pyarrow as pa2
    ts_t = pa2.table({"ts": pa2.array([datetime.datetime(2020, 1, 1)])})
    p2 = str(tmp_path / "host.orc")
    orc.write_table(ts_t, p2)
    m2 = run_scan(p2, [("ts", dt.TIMESTAMP)])
    assert m2.get("scanHostDecodedFiles") == 1
    assert "scanNativeDecodedFiles" not in m2
