"""The general hash join (exec/join.py's HASH path: ``K.build_hash_index`` + ``K.join_gather_maps``) end to
end through ``TpuSession``, shuffled and broadcast, unfused and fused with the aggregate behind it, against a
pandas merge; the counters it keeps (``hashJoinProbeTime`` / ``ProbeRows`` / ``Candidates`` / ``Matches``) and
the program names that tell it from the lookup.

Tables are seeded and shaped like TPC-H's orders and lineitem: dbgen's sparse order keys (the first 8 of every
32), so a build's keys span four times its rows and more than the lookup takes under a 1024-row batch bucket;
NULL keys on both sides; a build with duplicate keys, one of them hot enough that a pair needs more rows than
its probe holds and relaunches. The star-shaped join (dense unique keys) keeps the lookup and its names.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import jit_registry
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.expr import col
from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.plan import TpuSession

N_KEYS = 1000
HOT = 9  # sparse key of order 8: in the duplicate build 40 times, in the fact table one row in ten


def sparse(index):
    return index // 8 * 32 + index % 8 + 1


def _tables(seed=20261015):
    rng = np.random.default_rng(seed)
    n = 3000
    k = sparse(rng.integers(0, N_KEYS, n))
    k[rng.random(n) < 0.1] = HOT
    fact = pd.DataFrame({"k": pd.array(k, dtype="Int64"), "v": rng.uniform(1.0, 100.0, n)})
    fact.loc[rng.random(n) < 0.02, "k"] = pd.NA
    unique = np.sort(rng.choice(N_KEYS, 600, replace=False))
    dim_sparse = pd.DataFrame({"dk": pd.array(sparse(unique), dtype="Int64"), "g": rng.integers(0, 7, 600)})
    dim_sparse.loc[[3, 300], "dk"] = pd.NA
    drawn = np.concatenate([sparse(rng.integers(0, N_KEYS, 360)), np.full(40, HOT)])
    dim_dup = pd.DataFrame({"dk": pd.array(rng.permutation(drawn), dtype="Int64"), "g": rng.integers(0, 7, 400)})
    dim_dup.loc[rng.random(400) < 0.02, "dk"] = pd.NA
    dense = pd.DataFrame({"dk": pd.array(np.arange(1, 201), dtype="Int64"), "g": rng.integers(0, 7, 200)})
    fact_dense = pd.DataFrame({"k": pd.array(rng.integers(1, 251, n), dtype="Int64"), "v": rng.uniform(1, 100, n)})
    return {"fact": fact, "dim_sparse": dim_sparse, "dim_dup": dim_dup, "dim_dense": dense, "fact_dense": fact_dense}


def _merge(tables, fact, dim):
    left = tables[fact].dropna(subset=["k"]).astype({"k": "int64"})
    right = tables[dim].dropna(subset=["dk"]).astype({"dk": "int64"})
    return left.merge(right, left_on="k", right_on="dk")  # a NULL key joins nothing


@pytest.fixture(scope="module")
def parquet(tmp_path_factory):
    tables = _tables()
    root = tmp_path_factory.mktemp("hash_join")
    for name, frame in tables.items():
        (root / name).mkdir()
        for i, rows in enumerate(np.array_split(np.arange(len(frame)), 3 if name.startswith("fact") else 1)):
            pq.write_table(pa.Table.from_pandas(frame.iloc[rows], preserve_index=False),
                           str(root / name / f"{name}-{i}.parquet"))
    return tables, root


def _run(parquet, fact, dim, shape, strategy):
    """One query over ``fact`` joined to ``dim``: the joined rows (``rows``) or a group-by behind the join, which
    the planner fuses into the pair (``agg``); the build ``shuffled`` or ``broadcast``. Returns the answer, the
    query's ``phases``, its plan and the labels of the programs it launched."""
    tables, root = parquet
    conf = {"srt.sql.reader.batchSizeRows": 1024, "srt.sql.batchSizeRows": 1024, "srt.shuffle.partitions": 2}
    if strategy == "shuffled":
        conf["srt.sql.broadcastRowThreshold"] = 10
    session = TpuSession(SrtConf(conf))
    f, d = (session.read.parquet(str(root / name)) for name in (fact, dim))
    joined = f.join(d, on=([col("k")], [col("dk")]))
    df = joined.group_by("g").agg(Sum(col("v")).alias("s")) if shape == "agg" else joined
    launched = []
    real = jit_registry._dispatch

    def dispatch(span, runner, args, kwargs):
        launched.append(span)
        return real(span, runner, args, kwargs)

    jit_registry._dispatch = dispatch
    try:
        rows = df.collect()
    finally:
        jit_registry._dispatch = real
    last = session._last_execution
    return rows, dict(last["phases"]), last["physical"].tree_string(), {s[len("launch."):] for s in launched}


def _answer(rows, shape):
    got = pd.DataFrame(rows)
    if shape == "agg":
        return got.sort_values("g").reset_index(drop=True)
    return got[["k", "v", "dk", "g"]].sort_values(["k", "v", "g"]).reset_index(drop=True)


def _reference(tables, fact, dim, shape):
    merged = _merge(tables, fact, dim)
    if shape == "agg":
        return merged.groupby("g", as_index=False)["v"].sum().rename(columns={"v": "s"}), len(merged)
    return merged[["k", "v", "dk", "g"]].sort_values(["k", "v", "g"]).reset_index(drop=True), len(merged)


HASH_PROGRAMS = {"_hash_join_run_builder", "_fused_hash_join_builder"}
LOOKUP_PROGRAMS = {"_join_run_builder", "_fused_join_builder"}

#: (build, shape, strategy): both strategies, both shapes, both builds; the duplicate build relaunches
CASES = [("dim_sparse", "rows", "shuffled"), ("dim_dup", "agg", "shuffled"),
         ("dim_sparse", "agg", "broadcast"), ("dim_dup", "rows", "broadcast")]


@pytest.mark.parametrize("dim,shape,strategy", CASES)
def test_hash_join_equals_pandas_merge(parquet, dim, shape, strategy):
    tables, _ = parquet
    rows, phases, plan, launched = _run(parquet, "fact", dim, shape, strategy)
    want, matched = _reference(tables, "fact", dim, shape)
    got = _answer(rows, shape)
    assert len(got) == len(want) > 0
    if shape == "agg":
        assert got["g"].tolist() == want["g"].tolist()
        np.testing.assert_allclose(got["s"].to_numpy(float), want["s"].to_numpy(float), rtol=1e-12)
    else:
        for name in ("k", "dk", "g"):
            assert got[name].astype("int64").tolist() == want[name].astype("int64").tolist()
        np.testing.assert_array_equal(got["v"].to_numpy(float), want["v"].to_numpy(float))
    assert ("ShuffledHashJoin" if strategy == "shuffled" else "BroadcastHashJoin") in plan
    assert ("FusedHashJoin" in plan) == (shape == "agg")
    # every pair took the general path, and says what it did
    assert phases["hash_join_batches"] > 0 and phases["lookup_join_batches"] == 0
    assert phases["hash_join_matches"] == matched
    assert phases["hash_join_candidates"] >= phases["hash_join_matches"]
    assert phases["hash_join_probe_rows"] > 0 and phases["hash_join_probe_ns"] > 0
    if dim == "dim_dup":  # the hot key: a pair needs more rows than its probe holds
        assert phases["join_capacity_relaunches"] > 0
    # under names no lookup program has
    assert "_hash_index_builder" in launched and launched & HASH_PROGRAMS
    assert not launched & LOOKUP_PROGRAMS


def test_star_shaped_join_keeps_the_lookup_and_its_names(parquet):
    """Dense unique dimension keys: every pair a lookup, through the programs the star cell's join readers
    count, and none of the hash join's counters move."""
    tables, _ = parquet
    rows, phases, plan, launched = _run(parquet, "fact_dense", "dim_dense", "agg", "broadcast")
    want, _ = _reference(tables, "fact_dense", "dim_dense", "agg")
    got = _answer(rows, "agg")
    assert got["g"].tolist() == want["g"].tolist()
    np.testing.assert_allclose(got["s"].to_numpy(float), want["s"].to_numpy(float), rtol=1e-12)
    assert "FusedHashJoin[BroadcastHashJoin" in plan
    assert phases["lookup_join_batches"] > 0 and phases["hash_join_batches"] == 0
    assert phases["hash_join_probe_ns"] == phases["hash_join_probe_rows"] == 0
    assert phases["hash_join_candidates"] == phases["hash_join_matches"] == 0
    assert {"_lookup_table_builder", "_fused_join_builder"} <= launched
    assert not launched & (HASH_PROGRAMS | {"_hash_index_builder"})


# --- the two mechanisms under the hash join: the index's bucketed search and the exchange's cut ---

def _column(values, valid):
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.vector import ColumnVector
    return ColumnVector(jnp.asarray(np.asarray(values, np.int64)), jnp.asarray(np.asarray(valid)), dt.INT64)


@pytest.mark.parametrize("case", ["distinct", "duplicates", "dead and null rows"])
def test_bucketed_search_equals_searchsorted_over_the_live_hashes(case):
    """``_probe_hash_index`` finds, for every probe hash, the slots of the build's equal live, non-null
    hashes: what ``searchsorted`` left and right give over the sorted index, sentinels left out."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import kernels as K
    rng = np.random.default_rng(7)
    cap = 256
    keys = sparse(rng.integers(0, 4000, cap))
    if case == "duplicates":
        keys[:60] = 9  # one key 60 times: its bucket takes six rounds
    valid = np.ones(cap, bool)
    live = np.ones(cap, bool)
    if case == "dead and null rows":
        valid[rng.random(cap) < 0.1] = False
        live[200:] = False
    index = K.build_hash_index([_column(keys, valid)], jnp.asarray(live))
    probe = np.concatenate([keys[:100], sparse(rng.integers(0, 4000, 412))])
    probe_valid = rng.random(512) > 0.05
    ph = K._join_key_hash([_column(probe, probe_valid)], int(np.iinfo(np.int64).max) - 3)
    lo, hi = (np.asarray(x) for x in K._probe_hash_index(index, ph))
    real = np.asarray(index[0])[:int(index[2][-1])]
    ph = np.asarray(ph)
    assert np.array_equal(lo, np.searchsorted(real, ph, side="left"))
    assert np.array_equal(hi, np.searchsorted(real, ph, side="right"))
    assert (hi > lo).sum() > 50 and int(index[2][-1]) == int((valid & live).sum())


@pytest.mark.parametrize("rows", [[70, 0, 3, 55, 0, 0, 1, 120], [250, 1, 0, 0, 0, 0, 0, 5]])
def test_cut_equals_the_partition_program_repacked(rows):
    """``cut_batch`` at the capacities ``compact_for_transfer`` gives each partition's rows: the slices of
    ``partition_batch`` repacked, live rows and shapes (a string column's byte buffer included), so the bytes
    an exchange recorded when it held the batch are the bytes its cut slices measure."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.vector import batch_from_pydict
    from spark_rapids_tpu.exec.exchange import _partition_slices
    from spark_rapids_tpu.memory.spill import batch_nbytes
    from spark_rapids_tpu.ops import kernels as K
    from spark_rapids_tpu.parallel.partition import cut_batch, partition_batch
    from spark_rapids_tpu.plan.host_table import batch_to_table, to_pydict
    pids = np.concatenate([np.full(n, p, np.int32) for p, n in enumerate(rows)])
    np.random.default_rng(3).shuffle(pids)
    n = len(pids)
    batch = batch_from_pydict({"k": list(range(n)), "s": [("x" * (i % 5)) if i % 7 else None for i in range(n)],
                               "v": [i * 0.5 for i in range(n)]}, capacity=256)
    part_ids = jnp.asarray(np.concatenate([pids, np.zeros(256 - n, np.int32)]))
    caps = tuple(K.transfer_capacity(r, 256) if r else 0 for r in rows)
    cut = jax.jit(lambda b, ids: cut_batch(b, ids, 8, caps))(batch, part_ids)
    old = _partition_slices(partition_batch(batch, part_ids, 8), 8)
    for r, got, want in zip(rows, cut, old):
        if not r:
            assert got is None
            continue
        want = K.compact_for_transfer(want, num_rows=r)
        assert got.capacity == want.capacity and batch_nbytes(got) == batch_nbytes(want)
        assert [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(got.columns)] == \
            [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(want.columns)]
        assert to_pydict(batch_to_table(got)) == to_pydict(batch_to_table(want))
