"""The trace reduction, on a trace recorded on the chip: three Q6 queries of ``tpch_sf1.q6_power`` on one
TPU v5 lite (PR 23's first chip call), 766 KB."""

import os

import numpy as np
import pytest

from benchmarks.harness import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "q6_sf1.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return xplane.load(TRACE)


@pytest.fixture(scope="module")
def reduced(planes):
    return xplane.reduce(planes)


def sweep(starts, ends):
    """Covered length by counting depth over sorted endpoints: the plain way, to hold the fast one to."""
    points = sorted([(s, 1) for s in starts] + [(e, -1) for e in ends])
    depth, covered, since = 0, 0.0, None
    for at, step in points:
        if depth == 0 and step == 1:
            since = at
        depth += step
        if depth == 0:
            covered += at - since
    return covered


def test_union_merges_nested_touching_and_disjoint_intervals():
    starts, ends = np.array([0.0, 1.0, 2.0, 10.0, 20.0, 21.0]), np.array([5.0, 2.0, 7.0, 12.0, 30.0, 22.0])
    merged_starts, merged_ends = xplane.union(starts, ends)
    assert merged_starts.tolist() == [0.0, 10.0, 20.0] and merged_ends.tolist() == [7.0, 12.0, 30.0]
    assert (merged_ends - merged_starts).sum() == sweep(starts, ends) == 19.0
    assert xplane.union(np.empty(0), np.empty(0))[0].size == 0


def test_window_is_the_span_of_the_traced_queries(planes, reduced):
    assert reduced.queries == 3 and reduced.devices == 1
    host = next(p for p in planes if p.name == xplane.HOST_PLANE)
    spans = [(s, e) for names, starts, ends in host.lines.values()
             for n, s, e in zip(names, starts, ends) if n == "bench.query.q6"]
    assert len(spans) == 3
    assert reduced.window_s == pytest.approx((max(e for _, e in spans) - min(s for s, _ in spans)) / 1e9)
    assert reduced.window_s == pytest.approx(4.344195115)


def test_busy_is_the_union_of_device_op_intervals(planes, reduced):
    device = next(p for p in planes if p.name.startswith(xplane.DEVICE_PLANE))
    names, starts, ends = device.lines[xplane.OPS_LINE]
    assert len(names) == 1911
    assert reduced.busy_s == pytest.approx(sweep(starts, ends) / 1e9, rel=1e-7)  # the ops lie in the window
    assert reduced.busy_s == pytest.approx(3.823962405)
    assert 100 * (1 - reduced.busy_s / reduced.window_s) == pytest.approx(11.975353, abs=1e-5)  # idle share
    assert reduced.launches == 66  # 22 program executions a query


def test_every_idle_second_is_attributed_to_a_host_range(reduced):
    assert sum(reduced.gap_seconds.values()) + reduced.busy_s == pytest.approx(reduced.window_s)
    by_range = dict(reduced.idle_gaps)
    assert reduced.idle_gaps[0][0] == "FileSourceScanExec#1"  # the device waits longest for the scan
    assert by_range["FileSourceScanExec#1"] == pytest.approx(0.400128349)
    assert len(reduced.idle_gaps) <= 10 and len(reduced.device_ops) == 10
    assert all(len(name) <= xplane.NAME_CHARS for name, _ in reduced.device_ops)
    # an operation is named behind the program it ran in
    assert reduced.device_ops[0][0].startswith("jit__filter/%fusion.10 = s32[1048576]")
    assert all(name.startswith("jit_") for name, _ in reduced.device_ops)
    assert reduced.device_ops[0][1] == pytest.approx(0.559307072)


def test_a_trace_without_query_spans_is_refused(planes):
    devices_only = [p for p in planes if not p.name.startswith(xplane.HOST_PLANE)]
    with pytest.raises(ValueError, match="bench.query"):
        xplane.reduce(devices_only)
