"""The tpcds family and the join cell's readers: the cell end to end at a tiny size, the check that keeps an
engine without the join counters out of it, the least bytes a query's joins must read, and each new reader
on a synthetic run."""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from benchmarks import run
from benchmarks.configs import tpcds
from benchmarks.layer_metrics import join_record

from .conftest import ROOT, load_config

READERS = os.path.join(ROOT, "benchmarks", "layer_metrics")


def reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", os.path.join(READERS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_cell_end_to_end_at_a_tiny_size(capsys):
    rc = run.main(["--workload", "tpcds_tiny.star_power", "--seed", str(2**31 + 11), "--seconds", "1.5",
                   "--trace", "0", "--rehearse"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["compared"]["wrong_rows"] == {"value": 0, "limit": 0}
    assert result["run"]["compiles_in_window"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) >= {"rehearsal.rows_per_s", "rehearsal.setup_s"}


def test_an_engine_without_the_join_counters_does_not_run_the_cell(monkeypatch):
    from spark_rapids_tpu.exec import join
    kept = dict(join.JOIN_COUNTERS)
    assert tpcds.missing_join_counters() == []
    monkeypatch.setattr(join, "JOIN_COUNTERS", {k: v for k, v in kept.items() if k != "joinReadbacks"})
    with pytest.raises(RuntimeError, match="joinReadbacks"):
        tpcds.make_query(None, {}, "q3", "sql")
    monkeypatch.delattr(join, "JOIN_COUNTERS")  # the parent commit: no such symbol
    assert tpcds.missing_join_counters() == list(tpcds.REQUIRED_JOIN_COUNTERS)


def test_the_configuration_carries_the_parked_files_sizes_and_limits():
    new, parked = load_config("tpcds_sf1"), load_config("nds_sf1")
    assert new["tables"] == parked["tables"] and new["float_limits"] == parked["float_limits"]
    assert new["guarantees"] == parked["guarantees"] and new["reduced"] == ["fact_keys_follow_seed"]
    for qid, query in parked["queries"].items():
        carried = {k: v for k, v in new["queries"][qid].items() if k != "dimension_filters"}
        if qid == "q42":
            # the source's template has i_manager_id = 1, which the parked text dropped: the item scan reads
            # the column, ~490 rows pass the joins, and the aggregate runs under the grouped Pallas lane's
            # 1024-row gate, in float64
            assert carried.pop("precision") == "float64" and query["precision"] == "float32"
            assert carried["scans"]["item"] == query["scans"]["item"] + ["i_manager_id"]
            assert new["queries"][qid]["dimension_filters"]["item"] == {"i_manager_id": 1}
            carried["scans"], carried["precision"] = query["scans"], query["precision"]
        assert carried == query
    assert [new["tables"][t]["rows"] for t in ("store_sales", "date_dim", "item")] == [2880404, 73049, 18000]


def test_join_bytes_from_the_configuration_alone():
    config = load_config("tpcds_sf1")
    fact = 2880404 * 24
    # q42 keeps a month of one year of date_dim (24 B a row) and a hundredth of item (8 + 8 + 5.9 + 8 B a row)
    assert join_record.join_bytes(config, "q42") == pytest.approx(
        fact + 73049 / 12 * (365.25 / 73049) * 24 + 180 * 29.9)
    # q3: a twelfth of date_dim, a thousandth of item (8 + 8 + 7.82 + 8 B a row)
    assert join_record.join_bytes(config, "q3") == pytest.approx(fact + 73049 / 12 * 24 + 18 * 31.82)
    assert all(join_record.join_bytes(config, q) < fact * 1.01 for q in ("q3", "q42", "q52"))


def synthetic_run(op_seconds, queries=3):
    trace = SimpleNamespace(queries=queries, op_seconds=op_seconds, busy_s=1.0)
    config = load_config("tpcds_sf1")
    cell = {"queries": ["q3", "q42", "q52"]}
    return SimpleNamespace(trace=trace, config=config, cell=cell, device_kind="TPU v5 lite", records=[object()] * queries)


def test_device_readers_sum_the_join_and_sort_programs():
    run_ = synthetic_run({"jit__join_run_builder/%fusion.1": 0.030, "jit__fused_join_builder/%gather": 0.060,
                          "jit__lookup_table_builder/%scatter": 0.003, "jit_TopNExec._topn/%sort": 0.0015,
                          "jit_HashAggregateExec._merge_finalize/%x": 0.5, "jit__fused_merge_builder/%y": 0.006,
                          # a loop and a branch hold operations the trace also lists: counted once, as those
                          "jit__join_run_builder/%while.4 = (u32[]) while(%tuple)": 0.030,
                          "jit__fused_join_builder/%conditional.2 = conditional(%p)": 0.010})
    assert reader("join_device_ms")(run_) == pytest.approx(31.0)
    assert reader("sort_device_ms")(run_) == pytest.approx(0.5)
    assert reader("agg_merge_device_ms")(run_) == pytest.approx(2.0)
    least_ms = sum(join_record.join_bytes(run_.config, q) for q in run_.cell["queries"]) / 3 / 819e9 * 1e3
    assert reader("join_hbm_roofline_pct")(run_) == pytest.approx(100 * least_ms / 31.0)
    assert 0 < reader("join_hbm_roofline_pct")(run_) < 100


def test_device_readers_without_a_trace_or_a_join():
    no_trace = SimpleNamespace(trace=None, records=[])
    assert all(reader(n)(no_trace) is None
               for n in ("join_device_ms", "sort_device_ms", "join_hbm_roofline_pct", "agg_merge_device_ms"))
    no_join = synthetic_run({"jit_HashAggregateExec._pallas_stream/%f": 0.1})
    assert reader("join_device_ms")(no_join) == 0.0 and reader("join_hbm_roofline_pct")(no_join) is None
    assert reader("agg_merge_device_ms")(no_join) == 0.0


def test_readbacks_reader_reads_the_counter_not_nanoseconds(monkeypatch):
    from spark_rapids_tpu.obs import registry as engine_registry
    held = [{"phases": {"join_readbacks": n}} for n in (12, 12, 15)]
    monkeypatch.setattr(engine_registry, "registry", lambda: SimpleNamespace(queries=lambda: list(held)))
    assert reader("join_readbacks_per_query")(SimpleNamespace(records=[object()] * 3)) == pytest.approx(13.0)
    held[:] = [{"phases": {"parse_ns": 1}}]  # a parent commit: no such phase
    assert reader("join_readbacks_per_query")(SimpleNamespace(records=[object()])) is None
