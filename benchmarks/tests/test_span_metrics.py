"""The readers of the engine's own phases and of per-operator device time, each on a synthetic run: what
they average over, what they return where there is nothing to read, and that the files ``run.py`` loads
by name are there for every metric ``BENCHMARK.json`` lists."""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from benchmarks.layer_metrics import engine_record

from .conftest import ROOT

READERS = os.path.join(ROOT, "benchmarks", "layer_metrics")
PHASES = {"parse_ns": 1_000_000, "plan_ns": 2_000_000, "execute_ns": 900_000_000, "fetch_ns": 3_000_000,
          "scan_decode_ns": 700_000_000, "scan_wait_ns": 40_000_000, "scan_upload_ns": 120_000_000,
          "prefetch_wait_ns": 60_000_000, "dispatch_ns": 5_000_000, "launches": 22}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", os.path.join(READERS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def scaled(factor):
    return {k: v * factor for k, v in PHASES.items()}


@pytest.fixture
def engine_queries(monkeypatch):
    """Stands in for the engine's record: the tests say what ``registry().queries()`` holds."""
    from spark_rapids_tpu.obs import registry as engine_registry
    held = []
    monkeypatch.setattr(engine_registry, "registry", lambda: SimpleNamespace(queries=lambda: list(held)))
    return held


def run_of(queries, trace=None):
    return SimpleNamespace(records=[object()] * queries, trace=trace)


@pytest.mark.parametrize("name, expected", [
    ("planner_ms", 3.0), ("result_fetch_ms", 3.0), ("scan_decode_ms", 700.0), ("scan_wait_ms", 100.0),
    ("launch_host_ms", 5.0)])
def test_phase_readers_average_the_windows_queries(engine_queries, name, expected):
    # two warm-up queries ten times as slow, then the window's three: only the newest three count
    engine_queries.extend({"phases": scaled(10)} for _ in range(2))
    engine_queries.extend({"phases": scaled(f)} for f in (1, 2, 3))
    assert reader(name)(run_of(3)) == pytest.approx(expected * 2)


def test_phase_readers_take_what_the_engine_kept_when_the_window_was_longer(engine_queries):
    engine_queries.extend({"phases": scaled(f)} for f in (1, 3))
    assert reader("planner_ms")(run_of(100)) == pytest.approx(6.0)


@pytest.mark.parametrize("held", [[], [{"wall_ns": 5}], [{"phases": {"parse_ns": 1}}]],
                         ids=["no records", "an engine without phases", "a phase missing"])
def test_phase_readers_return_nothing_where_there_is_nothing_to_read(engine_queries, held):
    engine_queries.extend(held)
    assert reader("planner_ms")(run_of(3)) is None
    assert reader("scan_wait_ms")(run_of(0)) is None


def test_device_readers_sum_an_operators_programs_over_the_traced_queries():
    trace = SimpleNamespace(queries=2, op_seconds={
        "jit_FilterExec._filter/%fusion.1 = f32[8]": 0.5, "jit_FilterExec._filter/%fusion.2 = f32[8]": 0.25,
        "jit_HashAggregateExec._pallas_stream/%custom-call.1": 0.004,
        "jit_HashAggregateExec._merge_finalize/%fusion.3": 0.002,
        "jit__fused_program_builder/%fusion.9 = FilterExec": 9.0,  # a fused program is no operator's own
        "jit_run/%fusion.4": 7.0, "%copy.1": 3.0})
    assert reader("filter_device_ms")(run_of(2, trace)) == pytest.approx(375.0)
    assert reader("aggregate_device_ms")(run_of(2, trace)) == pytest.approx(3.0)


def test_device_readers_read_zero_without_a_matching_program_and_nothing_without_a_trace():
    parent = SimpleNamespace(queries=3, op_seconds={"jit__filter/%fusion.1": 1.0, "jit_run/%fusion.2": 0.1})
    assert reader("filter_device_ms")(run_of(3, parent)) == 0.0
    assert reader("aggregate_device_ms")(run_of(3, parent)) == 0.0
    assert reader("filter_device_ms")(run_of(3, None)) is None
    assert reader("aggregate_device_ms")(run_of(3, SimpleNamespace(queries=0, op_seconds={}))) is None


def test_every_listed_metric_has_a_reader_and_every_operator_its_programs():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"]]
    assert len(listed) == 14
    for name in listed:
        assert callable(reader(name))
    assert set(engine_record.OPERATOR_PROGRAMS) == {"filter", "aggregate"}
