"""The shipping cell (TPC-H Q3): its rehearsal end to end at a tiny size, the configuration's tables against the
q6 cell's, the least bytes the hash join must read, each new reader on a synthetic run, and the refusal of an
engine without the hash join's counters."""

import filecmp
import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import run
from benchmarks.harness import datagen

from .conftest import ROOT, load_config

READERS = os.path.join(ROOT, "benchmarks", "layer_metrics")
CELL = "tpch_sf1_shipping.q3_power"
MINE = ["hash_join_device_ms", "hash_join_hbm_roofline_pct", "hash_join_probe_ms"]


def reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", os.path.join(READERS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_cell_end_to_end_at_a_tiny_size(capsys):
    rc = run.main(["--workload", "tpch_tiny_shipping.q3_power", "--seed", str(2**31 + 23), "--seconds", "1.5",
                   "--trace", "0", "--rehearse"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["compared"]["wrong_rows"] == {"value": 0, "limit": 0}
    assert result["compared"]["rows_compared"]["value"] == 10 * result["attempted"]
    assert result["compared"]["max_rel_err_float64"]["value"] <= 1e-9
    assert result["run"]["compiles_in_window"] == 0 and result["attempted"] >= 3
    assert result["run"]["lanes"] == {"q3": ["float64"]}
    assert set(result["metrics"]) >= {"rehearsal.rows_per_s", "rehearsal.setup_s"}


def test_the_tiny_cell_takes_the_hash_path(capsys, monkeypatch):
    """The tiny configuration's engine thresholds are cut with its rows, so its orders-lineitem join is shuffled
    and every pair of it is a hash pair, as at SF1."""
    from spark_rapids_tpu.obs.registry import registry
    rc = run.main(["--workload", "tpch_tiny_shipping.q3_power", "--seed", "7", "--seconds", "0.5", "--trace", "0",
                   "--rehearse"])
    assert rc == 0
    phases = registry().queries()[-1]["phases"]
    assert phases["hash_join_batches"] == 8 and phases["lookup_join_batches"] == 1  # the customer join: a lookup
    assert phases["hash_join_candidates"] >= phases["hash_join_matches"] > 0
    assert phases["hash_join_probe_ns"] > 0 and phases["exchange_split_batches"] > 0


def test_the_configuration_is_the_q6_cells_tables_and_limits(tmp_path):
    new, old = load_config("tpch_sf1_shipping"), load_config("tpch_sf1")
    assert list(new["tables"]) == ["lineitem", "orders", "customer"]
    assert new["tables"] == old["tables"]  # same specs, same order: same bytes a seed
    for key in ("float_limits", "guarantees", "chunk_rows", "engine_conf", "lineitem_rows"):
        assert new[key] == old[key]
    q3 = new["queries"]["q3"]
    assert list(new["queries"]) == ["q3"] and q3["precision"] == "float64" and q3["limit"] == 10
    assert q3["keys"] == ["l_orderkey", "o_orderdate", "o_shippriority"]
    assert q3["order_by"] == [["revenue", "desc"], ["o_orderdate", "asc"]]
    assert new["reduced"] == [] and new["family"] == "tpch_shipping" and len(new["source"]) <= 200
    # byte for byte for one seed: customer whole, the first and the last chunk of every column of the others
    seed = 2**31 + 5
    for name, cfg in (("new", new), ("old", old)):
        datagen.generate_table("customer", cfg["tables"]["customer"], seed, str(tmp_path / name), cfg["chunk_rows"])
    assert filecmp.cmp(tmp_path / "new" / "customer-00000.parquet", tmp_path / "old" / "customer-00000.parquet",
                       shallow=False)
    chunk = new["chunk_rows"]
    for table in ("lineitem", "orders"):
        rows = new["tables"][table]["rows"]
        for index in (0, -(-rows // chunk) - 1):
            start = index * chunk
            for a, b in zip(new["tables"][table]["columns"], old["tables"][table]["columns"]):
                n = min(chunk, rows - start)
                assert datagen.generate_column(a, seed, table, index, start, n).equals(
                    datagen.generate_column(b, seed, table, index, start, n))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    declared = {c["name"]: c for c in benchmark["configs"]}["tpch_sf1_shipping"]
    assert declared["source"] == new["source"] and declared["file"] == "benchmarks/configs/tpch_sf1_shipping.json"
    assert declared["reduced"] == []
    cells = {w["name"]: w for w in benchmark["workloads"]}
    assert cells[CELL]["config"] == "tpch_sf1_shipping" and cells[CELL]["chips"] == 1
    assert [m["name"] for m in benchmark["per_layer"] if m.get("workloads") == [CELL]] == MINE
    assert all(os.path.exists(os.path.join(READERS, f"{name}.py")) for name in MINE)


def test_hash_join_bytes_by_hand():
    from benchmarks.layer_metrics.hash_join_record import hash_join_bytes
    # probe: lineitem shipped after 1995-03-15 (1,357 of 2,526 days), l_orderkey + l_extendedprice + l_discount;
    # build: orders placed before it (1,168 of 2,526 days) by a BUILDING customer (1 of 5), o_orderkey +
    # o_orderdate + o_shippriority
    by_hand = 6001215 * 1357 / 2526 * (8 + 8 + 8) + 1500000 * 1168 / 2526 / 5 * (8 + 4 + 4)
    assert hash_join_bytes(load_config("tpch_sf1_shipping")) == pytest.approx(by_hand, rel=1e-12)
    assert 79.5e6 < by_hand < 79.7e6 and 0.097 < by_hand / 819e9 * 1e3 < 0.098


def synthetic_run(op_seconds, queries=2):
    trace = SimpleNamespace(queries=queries, op_seconds=op_seconds, busy_s=1.0)
    return SimpleNamespace(trace=trace, config=load_config("tpch_sf1_shipping"), cell={"queries": ["q3"]},
                           device_kind="TPU v5 lite", records=[object()] * queries)


def test_device_readers_sum_the_hash_programs_and_not_the_lookups():
    run_ = synthetic_run({"jit__fused_hash_join_builder/%fusion.1": 0.050, "jit__hash_join_run_builder/%gather": 0.010,
                          "jit__hash_index_builder/%sort": 0.004, "jit__bloom_probe_builder/%f": 0.006,
                          "jit__bloom_build_builder/%g": 0.002, "jit__bucket_split_builder/%h": 0.002,
                          "jit__chunk_slice_builder/%i": 0.002,
                          # the lookup's programs, the exchange's and the merge: not the hash join's
                          "jit__fused_join_builder/%fusion.2": 0.5, "jit__join_run_builder/%x": 0.5,
                          "jit__lookup_table_builder/%y": 0.5, "jit__lookup_count_builder/%z": 0.5,
                          "jit__hash_partition_builder/%p": 0.5, "jit__fused_merge_builder/%m": 0.5,
                          # a loop and a branch hold operations the trace also lists: counted once
                          "jit__fused_hash_join_builder/%while.11 = (u32[]) while(%tuple)": 0.040,
                          "jit__hash_index_builder/%conditional.2 = conditional(%p)": 0.100})
    assert reader("hash_join_device_ms")(run_) == pytest.approx(38.0)
    least_ms = (6001215 * 1357 / 2526 * 24 + 1500000 * 1168 / 2526 / 5 * 16) / 819e9 * 1e3
    assert reader("hash_join_hbm_roofline_pct")(run_) == pytest.approx(100 * least_ms / 38.0)
    assert 0 < reader("hash_join_hbm_roofline_pct")(run_) < 100


def test_device_readers_without_a_trace_or_a_hash_pair():
    no_trace = SimpleNamespace(trace=None, records=[])
    assert reader("hash_join_device_ms")(no_trace) is None
    assert reader("hash_join_hbm_roofline_pct")(no_trace) is None
    lookups_only = synthetic_run({"jit__fused_join_builder/%f": 0.1, "jit__lookup_table_builder/%t": 0.1})
    assert reader("hash_join_device_ms")(lookups_only) == 0.0
    assert reader("hash_join_hbm_roofline_pct")(lookups_only) is None


def test_probe_reader_reads_the_phase(monkeypatch):
    from spark_rapids_tpu.obs import registry as engine_registry
    held = [{"phases": {"hash_join_probe_ns": n}} for n in (30e6, 36e6, 33e6)]
    monkeypatch.setattr(engine_registry, "registry", lambda: SimpleNamespace(queries=lambda: list(held)))
    assert reader("hash_join_probe_ms")(SimpleNamespace(records=[object()] * 3)) == pytest.approx(33.0)
    held[:] = [{"phases": {"parse_ns": 1}}]  # a parent commit: no such phase
    assert reader("hash_join_probe_ms")(SimpleNamespace(records=[object()])) is None


def test_an_engine_without_the_hash_join_counters_fails_at_once(monkeypatch, capsys):
    from benchmarks.configs import tpch_shipping
    from spark_rapids_tpu.exec import join
    assert tpch_shipping.missing_counters() == []
    kept = {k: v for k, v in join.JOIN_COUNTERS.items() if k not in tpch_shipping.REQUIRED_JOIN_COUNTERS}
    monkeypatch.setattr(join, "JOIN_COUNTERS", kept)  # a parent commit's engine
    assert tpch_shipping.missing_counters() == list(tpch_shipping.REQUIRED_JOIN_COUNTERS)
    with pytest.raises(RuntimeError, match="hashJoinProbeTime"):
        tpch_shipping.make_query(None, {}, "q3", "dataframe")
    # the whole run: it stops before the first query
    with pytest.raises(RuntimeError, match="hashJoinMatches"):
        run.main(["--workload", "tpch_tiny_shipping.q3_power", "--seed", "3", "--seconds", "1", "--trace", "0",
                  "--rehearse"])
    monkeypatch.delattr(join, "JOIN_COUNTERS")
    assert tpch_shipping.missing_counters() == list(tpch_shipping.REQUIRED_JOIN_COUNTERS)
