"""The readers of PR 35's counters (device reads, the pulling thread's split of its execution wall, a launch's
time off a CPU), each on a synthetic run: what they average over, what they return where the engine records
nothing (a parent commit), and that ``BENCHMARK.json`` lists each for every cell."""

import importlib.util
import json
import os
from types import SimpleNamespace

import pytest

from .conftest import ROOT

READERS = os.path.join(ROOT, "benchmarks", "layer_metrics")
NAMES = ("readback_wait_ms", "readbacks_per_query", "consumer_scan_wait_ms", "consumer_host_ms",
         "launch_offcpu_ms")
PHASES = {"execute_ns": 500_000_000, "readback_ns": 120_000_000, "readbacks": 48,
          "consumer_scan_wait_ns": 90_000_000, "consumer_semaphore_wait_ns": 0,
          "consumer_dispatch_ns": 150_000_000, "consumer_readback_ns": 100_000_000,
          "consumer_host_ns": 160_000_000, "dispatch_ns": 170_000_000, "dispatch_cpu_ns": 110_000_000}


def reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", os.path.join(READERS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture
def engine_queries(monkeypatch):
    """Stands in for the engine's record: the tests say what ``registry().queries()`` holds."""
    from spark_rapids_tpu.obs import registry as engine_registry
    held = []
    monkeypatch.setattr(engine_registry, "registry", lambda: SimpleNamespace(queries=lambda: list(held)))
    return held


def run_of(queries):
    return SimpleNamespace(records=[object()] * queries, trace=None)


@pytest.mark.parametrize("name, expected", [
    ("readback_wait_ms", 120.0), ("readbacks_per_query", 48.0), ("consumer_scan_wait_ms", 90.0),
    ("consumer_host_ms", 160.0), ("launch_offcpu_ms", 60.0)])
def test_readers_average_the_windows_queries(engine_queries, name, expected):
    # a warm-up query ten times as slow, then the window's two: only the newest two count
    engine_queries.append({"phases": {k: v * 10 for k, v in PHASES.items()}})
    engine_queries.extend({"phases": {k: v * f for k, v in PHASES.items()}} for f in (1, 3))
    assert reader(name)(run_of(2)) == pytest.approx(expected * 2)


@pytest.mark.parametrize("name", NAMES)
def test_readers_return_nothing_on_an_engine_without_the_counters(engine_queries, name):
    # a parent commit's record: the older phases, none of this PR's
    engine_queries.append({"phases": {"execute_ns": 1, "dispatch_ns": 1, "launches": 1}})
    assert reader(name)(run_of(1)) is None


def test_the_consumers_phases_partition_its_execution():
    assert sum(PHASES[k] for k in ("consumer_scan_wait_ns", "consumer_semaphore_wait_ns", "consumer_dispatch_ns",
                                   "consumer_readback_ns", "consumer_host_ns")) == PHASES["execute_ns"]


def test_every_cell_reports_the_five_and_each_has_its_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NAMES:
        metric = per_layer[name]
        assert "workloads" not in metric and metric["moves"] == "rows_per_s"
        assert metric["source"] == "program_counter"
        assert callable(reader(name))
