"""The star-join path (exec/join.py, exec/fused.py, ops/kernels.py): TPC-DS q3 / q42 / q52 as SQL
text against an independent pandas reference, the lookup join against ``join_gather_maps`` on the same
inputs, what the lookup refuses, the output-capacity rule, and the programs two like queries share.

Tables are seeded and shaped like ``benchmarks/configs/tpcds_sf1.json``: dense unique dimension keys
(``d_date_sk`` a run of Julian days, ``i_item_sk`` from 1), 1% NULL fact keys, the fact table in five
files read as three batches or more. Small enough for the CPU; the chip's run is the benchmark's.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import jit_registry
from spark_rapids_tpu.columnar.vector import batch_from_pydict, batch_to_pydict
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.exec import BatchScanExec, BroadcastHashJoinExec, ExecContext
from spark_rapids_tpu.exec import join as J
from spark_rapids_tpu.expr import col
from spark_rapids_tpu.plan import TpuSession

FIRST_SK = 2450816  # 1998-01-02, as the configuration has it
CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry", "Men", "Music", "Shoes", "Sports", "Women"]

SQL = {
    "q3": """SELECT d_year, i_brand_id AS brand_id, i_brand AS brand, SUM(ss_ext_sales_price) AS sum_agg
             FROM store_sales JOIN date_dim ON ss_sold_date_sk = d_date_sk JOIN item ON ss_item_sk = i_item_sk
             WHERE i_manufact_id = 7 AND d_moy = 11
             GROUP BY d_year, i_brand_id, i_brand ORDER BY d_year, sum_agg DESC, brand_id LIMIT 100""",
    "q42": """SELECT d_year, i_category_id, i_category, SUM(ss_ext_sales_price) AS total_sales
              FROM date_dim JOIN store_sales ON d_date_sk = ss_sold_date_sk JOIN item ON ss_item_sk = i_item_sk
              WHERE i_manager_id = 1 AND d_moy = 12 AND d_year = 1998
              GROUP BY d_year, i_category_id, i_category
              ORDER BY total_sales DESC, d_year, i_category_id, i_category LIMIT 100""",
    "q52": """SELECT d_year, i_brand_id AS brand_id, i_brand AS brand, SUM(ss_ext_sales_price) AS ext_price
              FROM date_dim JOIN store_sales ON d_date_sk = ss_sold_date_sk JOIN item ON ss_item_sk = i_item_sk
              WHERE i_manager_id = 1 AND d_moy = 11 AND d_year = 1999
              GROUP BY d_year, i_brand_id, i_brand ORDER BY d_year, ext_price DESC, brand_id LIMIT 100""",
}
#: query -> (date filter, item filter, group keys as source -> output, the sum's name, float rtol: the
#: precision the configuration states — float64 for all three, whose aggregates run at a few hundred rows)
STAR = {
    "q3": ({"d_moy": 11}, {"i_manufact_id": 7},
           {"d_year": "d_year", "i_brand_id": "brand_id", "i_brand": "brand"}, "sum_agg", 1e-9),
    "q42": ({"d_moy": 12, "d_year": 1998}, {"i_manager_id": 1},
            {"d_year": "d_year", "i_category_id": "i_category_id", "i_category": "i_category"}, "total_sales", 1e-9),
    "q52": ({"d_moy": 11, "d_year": 1999}, {"i_manager_id": 1},
            {"d_year": "d_year", "i_brand_id": "brand_id", "i_brand": "brand"}, "ext_price", 1e-9),
}


def _tables(seed=20261001):
    rng = np.random.default_rng(seed)
    days = pd.date_range("1998-01-02", periods=1900, freq="D")
    dates = pd.DataFrame({"d_date_sk": FIRST_SK + np.arange(len(days), dtype=np.int64),
                          "d_year": days.year.astype(np.int64), "d_moy": days.month.astype(np.int64)})
    n_item = 400
    brand = rng.integers(1, 41, n_item)
    category = rng.integers(1, 11, n_item)
    items = pd.DataFrame({"i_item_sk": np.arange(1, n_item + 1, dtype=np.int64), "i_brand_id": brand,
                          "i_brand": [f"brand#{b}" for b in brand], "i_category_id": category,
                          "i_category": [CATEGORIES[c - 1] for c in category],
                          "i_manufact_id": rng.integers(1, 11, n_item), "i_manager_id": rng.integers(1, 6, n_item)})
    n = 9000
    sales = pd.DataFrame({
        "ss_sold_date_sk": pd.array(rng.integers(FIRST_SK, FIRST_SK + len(days), n), dtype="Int64"),
        "ss_item_sk": pd.array(rng.integers(1, n_item + 1, n), dtype="Int64"),
        "ss_ext_sales_price": rng.uniform(1.0, 500.0, n)})
    sales.loc[rng.random(n) < 0.01, "ss_sold_date_sk"] = pd.NA
    sales.loc[rng.random(n) < 0.01, "ss_item_sk"] = pd.NA
    sales.loc[rng.random(n) < 0.02, "ss_ext_sales_price"] = np.nan
    return {"date_dim": dates, "item": items, "store_sales": sales}


def _reference(qid, tables):
    date_filter, item_filter, keys, total, _ = STAR[qid]
    dates, items = tables["date_dim"], tables["item"]
    for column, value in date_filter.items():
        dates = dates[dates[column] == value]
    for column, value in item_filter.items():
        items = items[items[column] == value]
    sales = tables["store_sales"].dropna(subset=["ss_sold_date_sk", "ss_item_sk"])  # a NULL key joins nothing
    sales = sales.astype({"ss_sold_date_sk": "int64", "ss_item_sk": "int64"})
    joined = sales.merge(dates, left_on="ss_sold_date_sk", right_on="d_date_sk") \
                  .merge(items, left_on="ss_item_sk", right_on="i_item_sk")
    out = joined.groupby(list(keys), as_index=False)["ss_ext_sales_price"].sum(min_count=1)
    return out.rename(columns={**keys, "ss_ext_sales_price": total})


def _join_counters(session):
    totals = {}
    for metrics in session._last_execution["ctx"].metrics.values():
        for name in J.JOIN_COUNTERS:
            if name in metrics:
                totals[name] = totals.get(name, 0) + metrics[name].value
    return totals


@pytest.fixture(scope="module")
def star(tmp_path_factory):
    """The three queries run once each through ``TpuSession.sql()`` (q3, then q52, then q42, from a cold
    program registry): answers, join counters, ``phases`` and the programs each registered."""
    tables = _tables()
    root = tmp_path_factory.mktemp("star")
    for name, frame in tables.items():
        (root / name).mkdir()
        pieces = np.array_split(np.arange(len(frame)), 5 if name == "store_sales" else 1)
        for i, rows in enumerate(pieces):
            pq.write_table(pa.Table.from_pandas(frame.iloc[rows], preserve_index=False),
                           str(root / name / f"{name}-{i}.parquet"))
    # five 1800-row files under a 2048-row batch: the fact table reaches the joins in three batches or more
    session = TpuSession(SrtConf({"srt.sql.reader.batchSizeRows": 2048, "srt.sql.batchSizeRows": 2048}))
    for name in tables:
        session.create_or_replace_temp_view(name, session.read.parquet(str(root / name)))
    jit_registry.clear()
    runs = {}
    for qid in ("q3", "q52", "q42"):
        before = jit_registry.stats()["misses"]
        rows = session.sql(SQL[qid]).collect()
        runs[qid] = {"rows": rows, "programs": jit_registry.stats()["misses"] - before,
                     "counters": _join_counters(session), "phases": dict(session._last_execution["phases"]),
                     "plan": session._last_execution["physical"].tree_string()}
    return tables, runs


@pytest.mark.parametrize("qid", ["q3", "q42", "q52"])
def test_star_query_equals_pandas_reference(star, qid):
    tables, runs = star
    _, _, keys, total, rtol = STAR[qid]
    names = list(keys.values())
    got = pd.DataFrame(runs[qid]["rows"])
    want = _reference(qid, tables)
    assert 0 < len(want) <= 100 and len(got) == len(want)
    got, want = (f.sort_values(names).reset_index(drop=True) for f in (got, want))
    for name in names:  # keys and strings exactly
        assert got[name].tolist() == want[name].tolist()
    np.testing.assert_allclose(got[total].to_numpy(float), want[total].to_numpy(float), rtol=rtol)
    # and in the query's own order: ORDER BY over the answer it gave
    order = {"q3": (["d_year", total, "brand_id"], [True, False, True]),
             "q42": ([total, "d_year", "i_category_id", "i_category"], [False, True, True, True]),
             "q52": (["d_year", total, "brand_id"], [True, False, True])}[qid]
    ordered = pd.DataFrame(runs[qid]["rows"])
    assert ordered.equals(ordered.sort_values(order[0], ascending=order[1], kind="stable").reset_index(drop=True))


@pytest.mark.parametrize("qid", ["q3", "q42", "q52"])
def test_star_query_joins_are_lookups_over_three_batches(star, qid):
    _, runs = star
    counters, plan = runs[qid]["counters"], runs[qid]["plan"]
    # both dimensions are built (q42 / q52 name the fact table second) and broadcast
    assert plan.count("BroadcastHashJoin[inner, build=") == 3  # two joins, one also inside the fused node
    assert "ShuffledHashJoin" not in plan
    # every fact batch through both joins, each pair a lookup
    assert counters["lookupJoinBatches"] >= 6 and counters.get("hashJoinBatches", 0) == 0
    assert counters["joinBuildTime"] > 0
    assert runs[qid]["phases"]["join_readbacks"] == counters["joinReadbacks"] > 0
    assert runs[qid]["phases"]["lookup_join_batches"] == counters["lookupJoinBatches"]


@pytest.mark.parametrize("qid", ["q3", "q42", "q52"])
def test_star_query_starts_its_three_producers_with_the_joins(star, qid):
    """The outer join starts item's ``broadcast`` producer and, through the inner join, date_dim's and the
    fact scan's: three a query, whichever side of a join the SQL names the fact table on."""
    _, runs = star
    assert runs[qid]["phases"]["prefetch_early_starts"] == 3


@pytest.mark.parametrize("qid", ["q3", "q42", "q52"])
def test_star_group_by_keeps_the_xla_branch(star, qid):
    """The star group-by runs over a few hundred joined rows, under the 1024-row gate of the grouped Pallas
    lane and of the sort-free preludes: no batch goes to the lane or through the comparison rounds, and no
    filter stands between the joins and the aggregate to hand over as a mask (the answers:
    ``test_star_query_equals_pandas_reference``)."""
    _, runs = star
    phases = runs[qid]["phases"]
    assert phases["pallas_batches"] == phases["groups_direct_batches"] == 0
    assert phases["groups_hash_claim_batches"] == phases["agg_masked_filter_batches"] == 0


def test_like_queries_share_programs(star):
    """q52 after q3 registers fewer programs than q3 did: the same plan with other literals."""
    _, runs = star
    assert 0 < runs["q52"]["programs"] < runs["q3"]["programs"]


# --- the lookup join against join_gather_maps on the same inputs ---

def _scan(data, nbatches=1, capacity=None):
    n = len(next(iter(data.values())))
    per = max(-(-n // nbatches), 1)
    batches = [batch_from_pydict({k: v[i:i + per] for k, v in data.items()}, capacity=capacity)
               for i in range(0, max(n, 1), per)]
    return BatchScanExec(batches, batches[0].schema())


def _collect(node, conf=None):
    ctx = ExecContext(conf)
    names = [n for n, _ in node.output_schema]
    rows = []
    for batch in node.execute(ctx):
        d = batch_to_pydict(batch)
        rows.extend(zip(*(d[n] for n in names)))
    counters = {name: m[name].value for m in [ctx.metrics_for(node.exec_id)] for name in J.JOIN_COUNTERS if name in m}
    return sorted(rows, key=repr), counters


def _join(probe, build, join_type, keys=("k",), build_keys=("dk",), lookup=True):
    probe_node, build_node = _scan(probe, nbatches=2), _scan(build)
    probe_keys, bkeys = [col(k) for k in keys], [col(k) for k in build_keys]
    if join_type == J.RIGHT_OUTER:  # the preserved (probe) side is the right child
        node = BroadcastHashJoinExec(build_node, probe_node, bkeys, probe_keys, join_type=join_type,
                                     build_side="left")
    else:
        node = BroadcastHashJoinExec(probe_node, build_node, probe_keys, bkeys, join_type=join_type)
    if not lookup:
        node._lookup_side = lambda ctx, build: None  # the general path, on the same inputs
    return node


PROBE = {"k": [5, None, 12, 40, -3, 12, 19, 10, None, 1 << 40, 20, 11], "v": list(range(12))}
#: a dimension filtered to a sub-range of the probe's keys, in no order; probe keys fall below, above and
#: far outside it, and two are NULL
DIMENSION = {"dk": [14, 10, 20, 12, 17, 11], "name": ["n14", "n10", None, "n12", "n17", "n11"]}
EMPTY = {"dk": [], "name": []}
JOIN_TYPES = [J.INNER, J.LEFT_OUTER, J.RIGHT_OUTER, J.LEFT_SEMI, J.LEFT_ANTI]


@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_lookup_join_equals_general_join(join_type):
    got, counters = _collect(_join(PROBE, DIMENSION, join_type))
    want, general = _collect(_join(PROBE, DIMENSION, join_type, lookup=False))
    assert got == want and len(got) > 0
    assert counters["lookupJoinBatches"] == 2 and "hashJoinBatches" not in counters
    assert general["hashJoinBatches"] == 2 and "lookupJoinBatches" not in general
    if join_type == J.INNER:  # NULL and out-of-range probe keys match nothing; the NULL name comes through
        assert got == sorted([(12, 2, 12, "n12"), (12, 5, 12, "n12"), (10, 7, 10, "n10"), (20, 10, 20, None),
                              (11, 11, 11, "n11")], key=repr)


@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_lookup_join_with_an_empty_build(join_type):
    build = batch_from_pydict({"dk": [1], "name": ["x"]})  # the schema, and no row
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.vector import ColumnarBatch
    empty = ColumnarBatch(build.columns, build.names, jnp.int32(0))
    results = []
    for lookup in (True, False):
        node = _join(PROBE, {"dk": [1], "name": ["x"]}, join_type, lookup=lookup)
        build_child = node.children[0 if join_type == J.RIGHT_OUTER else 1]
        build_child._batches = [empty]
        results.append(_collect(node)[0])
    assert results[0] == results[1]
    assert len(results[0]) == (12 if join_type in (J.LEFT_OUTER, J.RIGHT_OUTER, J.LEFT_ANTI) else 0)


def _keep(cap, one_in, seed=34):
    """``cap`` flags: none kept (``one_in`` 0), all kept (1), or each kept with probability 1 / ``one_in``."""
    if one_in < 2:
        return np.full(cap, bool(one_in))
    return np.random.default_rng(seed).integers(0, one_in, cap) == 0


#: case -> (keep flags, out_capacity); 2^14 rows at most: eager, a few small programs
FIRST_KEPT = {
    "nothing kept": (_keep(1024, 0), 64),
    "everything kept": (_keep(1024, 1), 1024),
    "everything kept, a prefix asked for": (_keep(4096, 1), 128),
    "1/12 kept, out_capacity below the kept count": (_keep(16384, 12), 1024),
    "1/12 kept, out_capacity above the kept count": (_keep(4096, 12), 512),
    "out_capacity equal to the kept count": (np.arange(4096) % 8 == 3, 512),
    "out_capacity above cap": (_keep(1024, 2), 2048),
    "the last row alone": (np.arange(2048) == 2047, 16),
    "cap 8": (_keep(8, 2), 16),
    "cap 64": (_keep(64, 3), 16),
    "cap 1000, no power of two": (_keep(1000, 12), 128),
    "cap 3000, over 1024 and no multiple of it": (_keep(3000, 2), 2048),
    "1/12 kept, a 1024th of the rows asked for": (_keep(16384, 12), 16),
    "a 1024th of the rows asked for, fewer kept": (np.arange(16384) % 2000 == 1999, 16),
    "a 1024th of the rows asked for, nothing kept": (_keep(8192, 0), 8),
    "a 1024th of the rows asked for, the last row alone": (np.arange(8192) == 8191, 8),
}


@pytest.mark.parametrize("case", list(FIRST_KEPT))
def test_first_kept_equals_flatnonzero(case):
    """Entry j is the position of the j-th kept row; past the last kept row, the capacity's last row."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import kernels as K
    keep, out_capacity = FIRST_KEPT[case]
    kept = np.flatnonzero(keep)[:out_capacity]
    want = np.full(out_capacity, len(keep) - 1, np.int32)
    want[:len(kept)] = kept
    got = np.asarray(K.first_kept(jnp.asarray(keep), out_capacity))
    assert got.dtype == np.int32 and np.array_equal(got, want)


def _pandas_inner(probe, build, keys, build_keys):
    left, right = pd.DataFrame(probe), pd.DataFrame(build)
    left = left.dropna(subset=list(keys))
    right = right.dropna(subset=list(build_keys))
    merged = left.merge(right, left_on=list(keys), right_on=list(build_keys))
    return sorted((tuple(None if pd.isna(v) else (int(v) if isinstance(v, (float, np.integer)) else v) for v in row)
                   for row in merged.itertuples(index=False)), key=repr)


REFUSALS = {
    "a duplicate build key": (PROBE, {"dk": [10, 12, 12, 20], "name": ["a", "b", "c", "d"]}, ("k",), ("dk",)),
    "a sparse range over the bound": (PROBE, {"dk": [10, 12, 5000], "name": ["a", "b", "c"]}, ("k",), ("dk",)),
    "two key columns": ({"k": [1, 2, 3, 2], "k2": [1, 1, 2, 2], "v": [1, 2, 3, 4]},
                        {"dk": [1, 2, 3], "dk2": [1, 2, 2], "name": ["a", "b", "c"]}, ("k", "k2"), ("dk", "dk2")),
    "a string key": ({"k": ["a", "b", None, "d", "b"], "v": [1, 2, 3, 4, 5]},
                     {"dk": ["b", "d", "e"], "name": ["B", "D", "E"]}, ("k",), ("dk",)),
}


@pytest.mark.parametrize("case", list(REFUSALS))
def test_what_the_lookup_refuses_takes_the_general_path(case):
    probe, build, keys, build_keys = REFUSALS[case]
    # a 1024-row batch bucket: a key span of 5000 is over the bound
    conf = SrtConf({"srt.sql.batchSizeRows": 1024})
    got, counters = _collect(_join(probe, build, J.INNER, keys, build_keys), conf)
    assert counters["hashJoinBatches"] == 2 and "lookupJoinBatches" not in counters
    assert got == _pandas_inner(probe, build, keys, build_keys) and len(got) > 0


def test_output_capacity_follows_the_matches():
    """The first pair measures, later pairs run at the measured bucket, an overflow relaunches at the
    bucket of what it needs, and the answer is the reference's."""
    build = {"dk": list(range(100)), "name": [f"n{i}" for i in range(100)]}
    # three probe batches of 1000 rows (capacity 1024): 10, 12 and 500 of them inside the dimension
    keys = []
    for inside in (10, 12, 500):
        keys += [i % 100 for i in range(inside)] + [1000 + i for i in range(1000 - inside)]
    probe = {"k": keys, "v": list(range(3000))}
    node = BroadcastHashJoinExec(_scan(probe, nbatches=3), _scan(build), [col("k")], [col("dk")],
                                 join_type=J.INNER)
    ctx = ExecContext()
    stream, seen = node.execute(ctx), []
    counters = ctx.metrics_for(node.exec_id)
    for expected_capacity, relaunches in ((16, 0), (16, 0), (512, 1)):
        batch = next(stream)
        seen.extend(zip(*(batch_to_pydict(batch)[n] for n in ("k", "v", "dk", "name"))))
        assert batch.capacity == expected_capacity == counters["joinOutCapacity"].value
        assert counters.get("joinCapacityRelaunches", J.Metric("x")).value == relaunches
    assert next(stream, None) is None
    assert node._cap_hint == 512 and counters["lookupJoinBatches"].value == 3
    assert sorted(seen, key=repr) == _pandas_inner(probe, build, ("k",), ("dk",)) and len(seen) == 522
    # the next run of the same (cached) plan starts where this one ended: no relaunch
    again = ExecContext()
    assert sum(int(b.num_rows) for b in node.execute(again)) == 522
    assert "joinCapacityRelaunches" not in again.metrics_for(node.exec_id)


def test_a_refused_lookup_costs_one_launch_and_one_read():
    """Key range, table and slots taken come from one program and one host read: a build the lookup then
    refuses (a duplicate key) has paid exactly that over the general path."""
    probe, build, keys, build_keys = REFUSALS["a duplicate build key"]
    _, tried = _collect(_join(probe, build, J.INNER, keys, build_keys))
    _, general = _collect(_join(probe, build, J.INNER, keys, build_keys, lookup=False))
    assert tried["hashJoinBatches"] == general["hashJoinBatches"] == 2
    assert tried["joinReadbacks"] == general["joinReadbacks"] + 1


def test_a_sub_partitioned_build_keeps_the_general_path():
    """The buckets of a build too large for one batch are joined as before: no lookup is tried on them,
    dense unique integer keys or not."""
    build = {"dk": list(range(64)), "name": [f"n{i}" for i in range(64)]}
    probe = {"k": [i % 80 for i in range(200)], "v": list(range(200))}
    conf = SrtConf({"srt.sql.join.subPartitionRows": 16})
    got, counters = _collect(_join(probe, build, J.INNER), conf)
    assert counters["hashJoinBatches"] > 0 and "lookupJoinBatches" not in counters
    assert got == _pandas_inner(probe, build, ("k",), ("dk",)) and len(got) == 168
