import pytest
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import batch_from_pydict, batch_to_pydict, dtypes as dt
from spark_rapids_tpu.columnar.vector import ColumnVector
from spark_rapids_tpu.expr import aggregates as agg
from spark_rapids_tpu.ops import kernels as K


def _mk(data, **kw):
    return batch_from_pydict(data, **kw)


def test_filter_compact():
    b = _mk({"a": [1, 2, 3, 4, 5], "s": ["a", "bb", "cc", "d", "e"]})
    keep = jnp.array([True, False, True, False, True, True, True, True])
    out = K.compact(b, keep)
    d = batch_to_pydict(out)
    assert d["a"] == [1, 3, 5]
    assert d["s"] == ["a", "cc", "e"]


def test_sort_single_key():
    b = _mk({"a": [3, 1, None, 2, 1]})
    out = K.sort_batch(b, [b.column("a")], [True], [True])
    assert batch_to_pydict(out)["a"] == [None, 1, 1, 2, 3]
    out = K.sort_batch(b, [b.column("a")], [False], [False])
    assert batch_to_pydict(out)["a"] == [3, 2, 1, 1, None]


def test_sort_floats_nan():
    b = _mk({"a": [1.5, float("nan"), -0.0, None, 2.5]})
    out = K.sort_batch(b, [b.column("a")], [True], [True])
    r = batch_to_pydict(out)["a"]
    assert r[0] is None
    assert r[1] == 0.0 and r[2] == 1.5 and r[3] == 2.5
    assert np.isnan(r[4])


def test_sort_strings():
    b = _mk({"s": ["pear", "apple", None, "app", "banana"]})
    out = K.sort_batch(b, [b.column("s")], [True], [True])
    assert batch_to_pydict(out)["s"] == [None, "app", "apple", "banana", "pear"]


def test_sort_multi_key_stable():
    b = _mk({"k": [1, 2, 1, 2, 1], "v": [30, 10, 20, 40, 10]})
    out = K.sort_batch(b, [b.column("k"), b.column("v")], [True, False], [True, True])
    d = batch_to_pydict(out)
    assert d["k"] == [1, 1, 1, 2, 2]
    assert d["v"] == [30, 20, 10, 40, 10]


def test_group_aggregate_sum_count():
    b = _mk({"k": [1, 2, 1, None, 2, 1], "v": [10, 20, 30, 40, None, 50]})
    s = agg.Sum(None)
    c = agg.CountStar()
    key_batch, states = K.group_aggregate(
        b, [b.column("k")], [b.column("v"), None], [s, c])
    n = int(key_batch.num_rows)
    assert n == 3
    keys, kmask = key_batch.columns[0].to_numpy(n)
    sums = np.asarray(states[0]["sum"])[:n]
    counts = np.asarray(states[1]["count"])[:n]
    # sorted key order: null first, then 1, 2
    assert not kmask[0] and keys[1] == 1 and keys[2] == 2
    assert sums[0] == 40 and sums[1] == 90 and sums[2] == 20
    assert counts[0] == 1 and counts[1] == 3 and counts[2] == 2


def test_inner_join():
    left = _mk({"k": [1, 2, 3, None, 2], "lv": [10, 20, 30, 40, 50]})
    right = _mk({"k2": [2, 4, 2, None], "rv": [200, 400, 201, 999]})
    out, total = K.inner_join(left, right, [left.column("k")],
                              [right.column("k2")], 32)
    d = batch_to_pydict(out)
    rows = sorted(zip(d["k"], d["lv"], d["rv"]))
    assert rows == [(2, 20, 200), (2, 20, 201), (2, 50, 200), (2, 50, 201)]
    assert int(total) == 4


def test_left_join():
    left = _mk({"k": [1, 2, None], "lv": [10, 20, 30]})
    right = _mk({"k2": [2, 2], "rv": [100, 200]})
    out, _ = K.left_join(left, right, [left.column("k")],
                         [right.column("k2")], 32)
    d = batch_to_pydict(out)
    rows = sorted(zip([x if x is not None else -1 for x in d["k"]],
                      d["lv"], [x if x is not None else -1 for x in d["rv"]]))
    assert rows == [(-1, 30, -1), (1, 10, -1), (2, 20, 100), (2, 20, 200)]


def test_semi_anti_join():
    left = _mk({"k": [1, 2, 3, None], "lv": [10, 20, 30, 40]})
    right = _mk({"k2": [2, 3, 3]})
    semi, _ = K.semi_anti_join(left, [right.column("k2")],
                               [left.column("k")], right.live_mask(), False)
    assert batch_to_pydict(semi)["lv"] == [20, 30]
    anti, _ = K.semi_anti_join(left, [right.column("k2")],
                               [left.column("k")], right.live_mask(), True)
    assert batch_to_pydict(anti)["lv"] == [10, 40]


def test_string_join_keys():
    left = _mk({"k": ["a", "bb", "cc"], "lv": [1, 2, 3]})
    right = _mk({"k2": ["bb", "dd"], "rv": [20, 40]})
    out, _ = K.inner_join(left, right, [left.column("k")],
                          [right.column("k2")], 16)
    d = batch_to_pydict(out)
    assert d["k"] == ["bb"] and d["lv"] == [2] and d["rv"] == [20]


def test_concat_batches():
    b1 = _mk({"a": [1, 2], "s": ["x", "yy"]})
    b2 = _mk({"a": [3, None], "s": [None, "zz"]})
    out = K.concat_batches([b1, b2], 16)
    d = batch_to_pydict(out)
    assert d["a"] == [1, 2, 3, None]
    assert d["s"] == ["x", "yy", None, "zz"]


def test_local_limit():
    b = _mk({"a": [1, 2, 3, 4, 5]})
    out = K.local_limit(b, 3)
    assert batch_to_pydict(out)["a"] == [1, 2, 3]


# ---------------------------------------------------------------------------
# A compaction's and a cut's fixed-width columns carried through the sort
# (columnar/vector.py::sort_rows) against every column gathered
# ---------------------------------------------------------------------------

_DEC128 = dt.DecimalType(30, 2)
_CARRY_SCHEMA = [("i32", dt.INT32), ("i64", dt.INT64), ("f32", dt.FLOAT32),
                 ("f64", dt.FLOAT64), ("d", dt.DATE), ("b", dt.BOOL),
                 ("s", dt.STRING), ("dec", _DEC128)]
_FIXED = ["i32", "i64", "f32", "f64", "d", "b"]


def _carry_batch(n=200, cap=256):
    """Every fixed-width kind the sort carries, with nulls, extremes, NaN,
    infinities and -0.0, beside a string and a 128-bit decimal it does
    not."""
    import decimal
    rng = np.random.default_rng(39)

    def nulls(values):
        return [None if rng.random() < 0.15 else v for v in values]
    i64 = rng.integers(-2 ** 63, 2 ** 63, n, dtype=np.int64).tolist()
    i64[:2] = [-2 ** 63, 2 ** 63 - 1]
    data = {"i32": nulls(rng.integers(-2 ** 31, 2 ** 31, n).tolist()),
            "i64": nulls(i64),
            "f32": nulls(rng.standard_normal(n).astype(np.float32).tolist()),
            "f64": nulls([float("nan"), float("-inf"), -0.0, 1e308]
                         + rng.standard_normal(n - 4).tolist()),
            "d": nulls(rng.integers(0, 20000, n).tolist()),
            "b": nulls([bool(x) for x in rng.integers(0, 2, n)]),
            "s": nulls(["x" * (i % 9) for i in range(n)]),
            "dec": nulls([decimal.Decimal(f"{i * 10 ** 20 + i}.{i % 100:02d}")
                          for i in range(n)])}
    return batch_from_pydict(data, capacity=cap, schema=_CARRY_SCHEMA)


def _bits(tree):
    import jax
    return [(x.dtype, x.shape, np.asarray(x).tobytes())
            for x in jax.tree_util.tree_leaves(tree)]


def _traced(fn, gathers, name):
    """``fn`` jitted, with the gathers its program holds counted into
    ``gathers[name]``."""
    import jax

    def program(*args):
        gathers[name] = str(jax.make_jaxpr(fn)(*args)).count("gather[")
        return jax.jit(fn)(*args)
    return program


@pytest.mark.parametrize("case", ["compact_some", "compact_all",
                                  "compact_none", "filter_some",
                                  "cut_zero_capacity", "cut_over_capacity"])
def test_sort_carried_rows_equal_the_gathered(case):
    """The batches a compaction or a cut returns with the fixed-width
    columns riding the sort are, bit for bit, those every column gathered
    by the sorted positions gives: values, validity, ``num_rows``, dead
    rows zeroed and null; strings and 128-bit decimals, which are still
    gathered, with their buffers; a cut's slices too, where a partition
    takes no capacity and where its rows outrun its capacity."""
    from spark_rapids_tpu.columnar.vector import (ColumnarBatch,
                                                  compaction_indices)
    from spark_rapids_tpu.parallel.partition import cut_batch
    batch = _carry_batch()
    rng = np.random.default_rng(7)
    gathers = {}
    if case.startswith("cut"):
        pids = rng.integers(0, 8, 256).astype(np.int32)
        caps = (64, 0, 32, 64, 16, 64, 64, 64) if case == "cut_zero_capacity" \
            else (8,) * 8

        def gathered(b):
            # partition p's live rows in their order, the first caps[p]
            out = []
            for p, c in enumerate(caps):
                rows = [i for i in range(200) if pids[i] == p][:c]
                out.append(None if not c else b.gather(
                    jnp.asarray(rows + [255] * (c - len(rows)), jnp.int32),
                    jnp.int32(len(rows)), unique=True))
            return out
        carried = _traced(lambda b, p: cut_batch(b, p, 8, caps), gathers,
                          "carried")(batch, jnp.asarray(pids))
        want = _traced(gathered, gathers, "gathered")(batch)
        assert gathers["carried"] < gathers["gathered"]
        assert [c is None for c in carried] == [c == 0 for c in caps]
        for c, w, cap in zip(carried, want, caps):
            if not cap:
                continue
            assert c.capacity == cap and int(c.num_rows) == int(w.num_rows)
            assert _bits(c.select(_FIXED).columns) == \
                _bits(w.select(_FIXED).columns)
            assert _bits(c.column("dec")) == _bits(w.column("dec"))
            assert batch_to_pydict(c)["s"] == batch_to_pydict(w)["s"]
        if case == "cut_over_capacity":
            assert all(int(c.num_rows) == 8 for c in carried)
        return
    keep = {"compact_some": rng.random(256) < 0.5,
            "filter_some": rng.random(256) < 0.3,
            "compact_all": np.ones(256, bool),
            "compact_none": np.zeros(256, bool)}[case]
    keep = jnp.asarray(keep)
    if case == "filter_some":
        b = batch.column("b")
        cond = ColumnVector(keep & b.data, b.validity, dt.BOOL)
        keep = cond.data & cond.validity
        carried = _traced(K.filter_batch, gathers, "carried")(batch, cond)
    else:
        carried = _traced(K.compact, gathers, "carried")(batch, keep)

    def gathered(b: ColumnarBatch, k):
        k = k & b.live_mask()
        return b.gather(compaction_indices(k), jnp.sum(k).astype(jnp.int32),
                        unique=True)
    want = _traced(gathered, gathers, "gathered")(batch, keep)
    assert gathers["carried"] < gathers["gathered"]
    assert _bits(carried) == _bits(want)
    n = int(carried.num_rows)
    assert n == {"compact_all": 200, "compact_none": 0}.get(case, n)
    for name in _FIXED:
        c = carried.column(name)
        assert not np.asarray(c.validity)[n:].any()
        assert not np.asarray(c.data)[n:].astype(bool).any()
    rows = batch_to_pydict(batch)
    kept = [i for i in range(200) if bool(keep[i])]
    got = batch_to_pydict(carried)
    for name, _ in _CARRY_SCHEMA:
        exp = [rows[name][i] for i in kept]
        if name == "f64":
            assert [repr(x) for x in got[name]] == [repr(x) for x in exp]
        else:
            assert got[name] == exp


def test_sort_carries_from_column_kinds():
    """The rule the host and the program share: a column rides the sort
    by its type, a fixed-width one; strings, lists, structs, maps and
    128-bit decimals are gathered. A cut carries too, until its key, a
    slot's destination then its position, would pass 32 bits."""
    from types import SimpleNamespace
    from spark_rapids_tpu.columnar.vector import rides_sort, sort_carries
    from spark_rapids_tpu.parallel.partition import cut_carries
    riders = [t for _, t in _CARRY_SCHEMA if rides_sort(t)]
    assert riders == [t for n, t in _CARRY_SCHEMA if n in _FIXED]
    assert rides_sort(dt.DecimalType(18, 2)) and rides_sort(dt.TIMESTAMP)
    assert not any(rides_sort(t) for t in (
        dt.STRING, _DEC128, dt.ArrayType(dt.INT32),
        dt.StructType((("a", dt.INT32),)), dt.MapType(dt.STRING, dt.INT64)))
    batch = _carry_batch()
    assert sort_carries(batch.schema()) and cut_carries(batch, 8)
    assert not sort_carries(batch.select(["s", "dec"]).schema())
    huge = SimpleNamespace(capacity=1 << 28, schema=batch.schema)
    assert not cut_carries(huge, 8) and cut_carries(huge, 6)


@pytest.mark.parametrize("kinds", ["fixed_width", "strings"])
def test_compaction_counters_count_the_path_taken(kinds):
    """A filter's compaction and an exchange's cut land in the query's
    ``phases`` as ``compact_sorted_batches`` where the batch has a column
    that rides the sort and as ``compact_gathered_batches`` where every
    column is a string, with the right answers either way."""
    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.plan import TpuSession
    s = TpuSession(SrtConf({
        "srt.shuffle.partitions": 8,
        "srt.sql.adaptive.coalescePartitions.enabled": False}))
    k = [f"k{i % 11}" for i in range(100)]
    v = [float(i) for i in range(100)]
    taken, other = ("sorted", "gathered") if kinds == "fixed_width" \
        else ("gathered", "sorted")
    if kinds == "fixed_width":
        df = s.create_dataframe({"k": [i % 11 for i in range(100)], "v": v})
        kept = df.filter(col("v") > lit(60.0)).collect()
        assert [r["v"] for r in kept] == v[61:]
    else:
        df = s.create_dataframe({"k": k})
        kept = df.filter(col("k") == lit("k3")).collect()
        assert [r["k"] for r in kept] == [x for x in k if x == "k3"]
    phases = s._last_execution["phases"]
    assert phases[f"compact_{taken}_batches"] == 1
    assert phases[f"compact_{other}_batches"] == 0

    keys = k if kinds == "strings" else [i % 11 for i in range(100)]
    out = df.select(col("k")).distinct().collect()
    assert sorted(r["k"] for r in out) == sorted(set(keys))
    phases = s._last_execution["phases"]
    assert phases["exchange_split_batches"] >= 1
    assert phases[f"compact_{taken}_batches"] == \
        phases["exchange_split_batches"]
    assert phases[f"compact_{other}_batches"] == 0

