"""The string-keyed grouped aggregate TPC-H Q1 forces (exec/fused.py, ops/kernels.py): the filter in front of
a fused aggregate as the aggregate's mask, a handful of groups found by comparison rounds, the hash claim
behind them, and the split between the grouped Pallas lane and the XLA branch.

Q1 itself runs through the normal path (``TpuSession`` -> ``read.parquet`` -> DataFrame -> planner) at the
rehearsal configuration ``benchmarks/configs/tpch_tiny_pricing.json`` against the benchmark's pandas
reference, which imports nothing of the engine. The lane's kernel runs in interpret mode, float64, where a
test forces it (``SRT_PALLAS_GROUPED_FORCE``), so equality with the XLA path is exact to rounding.
"""

import json
import os

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar.vector import batch_from_pydict, batch_to_pydict
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.exec.aggregate import LANE_COUNTERS
from spark_rapids_tpu.expr import aggregates as Agg
from spark_rapids_tpu.expr import col
from spark_rapids_tpu.ops import kernels as K
from spark_rapids_tpu.plan import TpuSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLOATS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge", "avg_qty", "avg_price", "avg_disc")


def _counters(session) -> dict:
    totals = {}
    for metrics in session._last_execution["ctx"].metrics.values():
        for name in tuple(LANE_COUNTERS) + ("aggMaskedFilterBatches",):
            if name in metrics:
                totals[name] = totals.get(name, 0) + metrics[name].value
    return totals


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    from benchmarks.harness import datagen
    with open(os.path.join(ROOT, "benchmarks", "configs", "tpch_tiny_pricing.json")) as f:
        config = json.load(f)
    paths, _ = datagen.generate(config, ["lineitem"], 2**31 + 5, str(tmp_path_factory.mktemp("pricing")))
    return config, paths


@pytest.mark.parametrize("lane", ["xla", "pallas"])
def test_q1_through_the_normal_path_equals_the_pandas_reference(lineitem, lane, monkeypatch):
    from benchmarks.configs import tpch
    config, paths = lineitem
    if lane == "pallas":
        monkeypatch.setenv("SRT_PALLAS_GROUPED_FORCE", "1")
    session = TpuSession(SrtConf({}))
    frames = {"lineitem": session.read.parquet(paths["lineitem"])}
    got = pd.DataFrame(tpch.make_query(session, frames, "q1", "dataframe")())
    want = tpch.reference("q1", paths).sort_values(config["queries"]["q1"]["keys"]).reset_index(drop=True)
    assert len(want) == 6
    for name in ("l_returnflag", "l_linestatus", "count_order"):  # keys in ORDER BY's order, counts exact
        assert got[name].tolist() == want[name].tolist()
    for name in FLOATS:
        np.testing.assert_allclose(got[name].to_numpy(float), want[name].to_numpy(float),
                                   rtol=config["float_limits"]["float32"])
    plan = session._last_execution["physical"].tree_string()
    assert "FusedPipeline[FilterExec -> HashAggregateExec]" in plan and ("(pallas)" in plan) == (lane == "pallas")
    counters, phases = _counters(session), session._last_execution["phases"]
    # 40,000 rows reach the fused chain in one batch, whose filter is the aggregate's mask
    assert counters["aggMaskedFilterBatches"] == phases["agg_masked_filter_batches"] == 1
    if lane == "pallas":  # six groups: the rounds find them, the lane adds them up
        assert counters["pallasBatches"] == phases["pallas_batches"] == 1
        assert counters["groupsResolvedDirect"] == phases["groups_direct_batches"] == 1
        assert counters["groupsHashClaimed"] == phases["groups_hash_claim_batches"] == 0
    else:
        assert phases["pallas_batches"] == 0 and "pallasBatches" not in counters


@pytest.fixture(scope="module")
def three_batches(tmp_path_factory):
    """Five 1800-row files under a 2048-row reader batch: three batches. The filter (v > 0) keeps nothing
    of the second; the third holds null keys only in part of its rows, and null values."""
    rng = np.random.default_rng(31)
    root = tmp_path_factory.mktemp("masked")
    frames = []
    for i in range(5):
        n = 1800
        g = np.array(["A", "N", "R", "AA"], dtype=object)[rng.integers(0, 4, n)]
        s = np.array(["O", "F"], dtype=object)[rng.integers(0, 2, n)]
        v = rng.uniform(0.5, 50.0, n)
        w = rng.uniform(0.0, 1.0, n)
        if i in (2, 3):
            v = -v
        if i == 4:
            g[rng.random(n) < 0.3] = None
            v[rng.random(n) < 0.1] = np.nan
        frame = pd.DataFrame({"g": g, "s": s, "v": v, "w": w})
        frames.append(frame)
        pq.write_table(pa.Table.from_pandas(frame, preserve_index=False), str(root / f"t-{i}.parquet"))
    return str(root), pd.concat(frames, ignore_index=True)


#: the native decoder hands over every row (pyarrow's scan would drop what a pushed filter refuses, and with
#: it the batch the filter empties), strings as the Arrow buffers it wrote
SIZES = {"srt.sql.reader.batchSizeRows": 2048, "srt.sql.batchSizeRows": 2048,
         "srt.sql.format.parquet.nativeDecode.enabled": True}


def _masked_query(session, path):
    return (session.read.parquet(path).filter(col("v") > 0.0).group_by("g", "s")
            .agg(Agg.Sum(col("v") * col("w")).alias("sv"), Agg.Average(col("w")).alias("aw"),
                 Agg.CountStar().alias("n"), Agg.Count(col("v")).alias("nv")))


def test_the_masked_filter_equals_the_compacting_chain(three_batches, monkeypatch):
    monkeypatch.setenv("SRT_PALLAS_GROUPED_FORCE", "1")
    path, frame = three_batches
    answers = {}
    for fused in (True, False):
        session = TpuSession(SrtConf({**SIZES, "srt.exec.fusion.enabled": fused}))
        rows = pd.DataFrame(_masked_query(session, path).collect())
        answers[fused] = rows.sort_values(["g", "s"], na_position="first").reset_index(drop=True)
        counters = _counters(session)
        plan = session._last_execution["physical"].tree_string()
        if fused:
            assert "FusedPipeline[FilterExec -> HashAggregateExec] (pallas)" in plan
            # three batches carried their filter as a mask; the one it emptied emitted no partial and
            # launched no kernel; the other two held 8 and 10 groups: rounds, then hash claim
            assert counters["aggMaskedFilterBatches"] == 3 and counters["pallasBatches"] == 2
            assert counters["groupsResolvedDirect"] == 1 and counters["groupsHashClaimed"] == 1
        else:
            assert "FusedPipeline" not in plan and "aggMaskedFilterBatches" not in counters
    masked, compacted = answers[True], answers[False]
    assert len(masked) == len(compacted) == 10  # 8 groups and the two whose g is null
    for name in ("g", "s", "n", "nv"):
        assert masked[name].tolist() == compacted[name].tolist()
    for name in ("sv", "aw"):
        np.testing.assert_allclose(masked[name].to_numpy(float), compacted[name].to_numpy(float), rtol=1e-12)
    kept = frame[frame["v"] > 0.0]
    want = kept.assign(p=kept["v"] * kept["w"]).groupby(["g", "s"], dropna=False).agg(
        sv=("p", "sum"), n=("w", "size")).reset_index().sort_values(["g", "s"], na_position="first")
    assert masked["n"].tolist() == want["n"].tolist()
    np.testing.assert_allclose(masked["sv"].to_numpy(float), want["sv"].to_numpy(float), rtol=1e-12)


def test_a_first_row_survives_the_mask_in_stream_order(three_batches):
    """Order-sensitive aggregates see the rows' own positions: a chain that masks advances the stream's row
    offset by the rows that entered it, so FIRST over three batches is the first kept row of the stream."""
    path, frame = three_batches
    session = TpuSession(SrtConf(SIZES))
    rows = (session.read.parquet(path).filter(col("w") > 0.5).group_by("s")
            .agg(Agg.First(col("w")).alias("first_w"), Agg.Last(col("w")).alias("last_w")).collect())
    assert _counters(session)["aggMaskedFilterBatches"] == 3
    kept = frame[frame["w"] > 0.5]
    for r in rows:
        w = kept[kept["s"] == r["s"]]["w"]
        assert r["first_w"] == w.iloc[0] and r["last_w"] == w.iloc[-1]


CAP = 4096


def _keys(kind: str, groups: int, rng) -> list:
    ids = rng.integers(0, groups, CAP - 96)
    ids[:groups] = np.arange(groups)  # every group at least once
    if kind == "int":
        return [int(i) * 7 - 3 for i in ids]
    # strings of several lengths; group 0 is the null key
    return [None if i == 0 else f"k{i}" * (1 + i % 3) for i in ids]


@jax.jit
def _lane(batch, live):
    kb, states, flags = K.group_aggregate_pallas(
        batch, [batch.column("k")], [batch.column("v"), None], [Agg.Sum(None), Agg.CountStar()], live=live)
    return kb, states, flags


@pytest.mark.parametrize("kind", ["int", "string"])
@pytest.mark.parametrize("groups,lane,direct", [(1, 1, 1), (6, 1, 1), (K.DIRECT_GROUP_ROUNDS + 1, 1, 0),
                                                (1024, 1, 0), (1025, 0, 0)])
def test_groups_are_found_by_what_the_batch_holds(kind, groups, lane, direct):
    """1 and 6 groups resolve in the comparison rounds, one past their bound goes to the hash claim, 1024
    groups still take the lane and 1025 the XLA branch; every path gives numpy's answer under a mask."""
    rng = np.random.default_rng(groups)
    keys = _keys(kind, groups, rng)
    values = rng.uniform(-5.0, 5.0, len(keys))
    batch = batch_from_pydict({"k": keys, "v": values.tolist()}, capacity=CAP)
    live = np.zeros(CAP, bool)
    live[:len(keys)] = rng.random(len(keys)) < 0.9
    live[:groups] = True
    kb, states, flags = _lane(batch, jax.numpy.asarray(live))
    assert np.asarray(flags).tolist() == [lane, direct]
    n = int(kb.num_rows)
    got_keys = batch_to_pydict(kb)["k0"][:n]
    sums, counts = np.asarray(states[0]["sum"])[:n], np.asarray(states[1]["count"])[:n]
    want = pd.DataFrame({"k": pd.Series(keys, dtype=object), "v": values})[live[:len(keys)]] \
        .groupby("k", dropna=False)["v"].agg(["sum", "size"])
    assert n == groups == len(want) and len(set(got_keys)) == n
    for k, s, c in zip(got_keys, sums, counts):
        row = want.loc[[np.nan if k is None else k]].iloc[0] if k is None else want.loc[k]
        assert c == row["size"] and s == pytest.approx(row["sum"], rel=1e-9, abs=1e-9)


def test_fixed_width_string_keys_are_read_without_the_gather():
    """``_string_key_bytes`` is ``padded()`` whatever branch builds it: CHAR(1) and CHAR(3) columns from the
    reshape, a column with a null or a second length from the gather."""
    rng = np.random.default_rng(2)
    for values in ([["A", "N", "R"][i] for i in rng.integers(0, 3, 900)],
                   [["abc", "xyz"][i] for i in rng.integers(0, 2, 900)],
                   [["A", None, "R"][i] for i in rng.integers(0, 3, 900)],
                   [["A", "NN"][i] for i in rng.integers(0, 2, 900)]):
        column = batch_from_pydict({"k": values}, capacity=1024).column("k")
        assert np.array_equal(np.asarray(jax.jit(K._string_key_bytes)(column)), np.asarray(column.padded()))


def test_a_small_grouped_aggregate_keeps_the_xla_branch(monkeypatch):
    """The star cell's shape: a group-by over a few hundred rows stays under the lane's 1024-row gate even
    where the plan chose the lane, with the answers of a plan that did not."""
    rng = np.random.default_rng(8)
    data = {"g": [f"brand#{i}" for i in rng.integers(0, 40, 300)], "v": rng.uniform(1, 500, 300).tolist()}
    answers = {}
    for forced in ("1", "0"):
        monkeypatch.setenv("SRT_PALLAS_GROUPED_FORCE", forced)
        session = TpuSession(SrtConf({}))
        rows = (session.create_dataframe(dict(data)).group_by("g")
                .agg(Agg.Sum(col("v")).alias("sv"), Agg.CountStar().alias("n")).collect())
        answers[forced] = sorted((r["g"], r["n"], r["sv"]) for r in rows)
        counters = _counters(session)
        assert counters.get("pallasBatches", 0) == 0 and counters.get("groupsResolvedDirect", 0) == 0
    assert answers["1"] == answers["0"] and len(answers["1"]) == 40
