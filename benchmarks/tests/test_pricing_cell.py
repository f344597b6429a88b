"""The pricing cell (TPC-H Q1): its rehearsal end to end at a tiny size, the configuration's files against
the q6 cell's, the least bytes the query must read, and each new reader on a synthetic run."""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import run
from benchmarks.harness import datagen

from .conftest import ROOT, load_config

READERS = os.path.join(ROOT, "benchmarks", "layer_metrics")
CELL = "tpch_sf1_pricing.q1_power"


def reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", os.path.join(READERS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_cell_end_to_end_at_a_tiny_size(capsys):
    rc = run.main(["--workload", "tpch_tiny_pricing.q1_power", "--seed", str(2**31 + 17), "--seconds", "1.5",
                   "--trace", "0", "--rehearse"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["compared"]["wrong_rows"] == {"value": 0, "limit": 0}
    assert result["compared"]["rows_compared"]["value"] == 6 * result["attempted"]
    assert result["compared"]["max_rel_err_float32"]["value"] <= 1e-5
    assert result["run"]["compiles_in_window"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) >= {"rehearsal.rows_per_s", "rehearsal.setup_s"}


def test_the_configuration_is_the_q6_cells_lineitem_and_limits():
    new, old = load_config("tpch_sf1_pricing"), load_config("tpch_sf1")
    assert new["tables"] == {"lineitem": old["tables"]["lineitem"]}  # same specs, same order: same bytes a seed
    assert new["queries"] == {"q1": old["queries"]["q1"]}
    for key in ("float_limits", "guarantees", "chunk_rows", "engine_conf"):
        assert new[key] == old[key]
    assert new["reduced"] == [] and new["family"] == "tpch_pricing" and len(new["source"]) <= 200
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    declared = {c["name"]: c for c in benchmark["configs"]}["tpch_sf1_pricing"]
    assert declared["source"] == new["source"] and declared["file"] == "benchmarks/configs/tpch_sf1_pricing.json"
    cells = {w["name"]: w for w in benchmark["workloads"]}
    assert cells[CELL]["config"] == "tpch_sf1_pricing" and cells[CELL]["chips"] == 1
    mine = [m["name"] for m in benchmark["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == ["groupby_device_ms", "groupby_hbm_roofline_pct", "grouped_lane_batches_per_query"]
    assert all(os.path.exists(os.path.join(READERS, f"{name}.py")) for name in mine)


def test_logical_bytes_from_the_configuration_alone():
    # l_shipdate 4 + two CHAR(1) flags + four FLOAT64 columns = 38 B a row
    assert datagen.logical_bytes(load_config("tpch_sf1_pricing"), "q1") == 6001215 * 38 == 228046170
    assert datagen.fact_rows(load_config("tpch_sf1_pricing"), "q1") == 6001215


def synthetic_run(op_seconds, queries=2):
    trace = SimpleNamespace(queries=queries, op_seconds=op_seconds, busy_s=1.0)
    return SimpleNamespace(trace=trace, config=load_config("tpch_sf1_pricing"), cell={"queries": ["q1"]},
                           device_kind="TPU v5 lite", records=[object()] * queries)


def test_device_readers_sum_the_grouped_aggregates_programs():
    run_ = synthetic_run({"jit__fused_program_builder/%fusion.1": 0.060, "jit__fused_program_builder/%closed_call.5": 0.040,
                          "jit_HashAggregateExec._update_pallas/%x": 0.020, "jit_repack_to/%gather": 0.2,
                          "jit__fused_merge_builder/%y": 0.006, "jit_HashAggregateExec._merge_finalize/%z": 0.5,
                          # a loop and a branch hold operations the trace also lists: counted once, as those
                          "jit__fused_program_builder/%while.11 = (u32[]) while(%tuple)": 0.040,
                          "jit__fused_program_builder/%conditional.2 = conditional(%p)": 0.100})
    assert reader("groupby_device_ms")(run_) == pytest.approx(60.0)
    least_ms = 228046170 / 819e9 * 1e3
    assert reader("groupby_hbm_roofline_pct")(run_) == pytest.approx(100 * least_ms / 60.0)
    assert 0 < reader("groupby_hbm_roofline_pct")(run_) < 100


def test_device_readers_without_a_trace_or_a_grouped_aggregate():
    no_trace = SimpleNamespace(trace=None, records=[])
    assert reader("groupby_device_ms")(no_trace) is None and reader("groupby_hbm_roofline_pct")(no_trace) is None
    none_ran = synthetic_run({"jit_HashAggregateExec._pallas_stream/%f": 0.1})
    assert reader("groupby_device_ms")(none_ran) == 0.0 and reader("groupby_hbm_roofline_pct")(none_ran) is None


def test_lane_reader_reads_the_counter(monkeypatch):
    from spark_rapids_tpu.obs import registry as engine_registry
    held = [{"phases": {"pallas_batches": n}} for n in (6, 6, 3)]
    monkeypatch.setattr(engine_registry, "registry", lambda: SimpleNamespace(queries=lambda: list(held)))
    assert reader("grouped_lane_batches_per_query")(SimpleNamespace(records=[object()] * 3)) == pytest.approx(5.0)
    held[:] = [{"phases": {"parse_ns": 1}}]  # a parent commit: no such phase
    assert reader("grouped_lane_batches_per_query")(SimpleNamespace(records=[object()])) is None


def test_an_engine_without_the_grouped_counters_fails_at_once(monkeypatch):
    from benchmarks.configs import tpch_pricing
    from spark_rapids_tpu.exec import aggregate
    assert tpch_pricing.missing_counters() == []
    monkeypatch.delattr(aggregate, "LANE_COUNTERS")  # a parent commit's engine
    assert tpch_pricing.missing_counters() == list(tpch_pricing.REQUIRED_LANE_COUNTERS)
    with pytest.raises(RuntimeError, match="groupsResolvedDirect"):
        tpch_pricing.make_query(None, {}, "q1", "dataframe")
