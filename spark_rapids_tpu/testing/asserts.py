"""Differential asserts: run the same plan on TPU and on the CPU oracle
and require identical results.

Rebuild of integration_tests asserts.py (SURVEY §4):
- assert_tpu_cpu_equal(_df): assert_gpu_and_cpu_are_equal_collect
- assert_falls_back_to_cpu: assert_gpu_fallback_collect (capture that an
  op was tagged off the TPU, and that results still match through the
  fallback path)
- assert_runs_on_tpu: asserts NO node fell back (catches silent
  fallback regressions).
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import numpy as np

from ..columnar import dtypes as dt
from ..conf import SQL_ENABLED, SrtConf, active_conf
from ..exec.base import ExecContext, TpuExec
from ..plan import cpu_exec, overrides
from ..plan.host_table import (HostTable, batch_to_table, concat_tables,
                               empty_like, to_pydict)
from ..plan.logical import LogicalPlan
from ..plan.session import DataFrame


def _run_tpu(plan: LogicalPlan, conf: SrtConf) -> HostTable:
    physical = overrides.apply_overrides(plan, conf)
    ctx = ExecContext(conf)
    if isinstance(physical, TpuExec):
        tables = [batch_to_table(b) for b in physical.execute(ctx)
                  if int(b.num_rows) > 0]
        return concat_tables(tables) if tables else empty_like(plan.schema)
    return physical.evaluate(ctx)


def _canonical_rows(table: HostTable, sort: bool):
    data = to_pydict(table)
    names = list(data.keys())
    rows = [tuple(data[k][i] for k in names)
            for i in range(table.num_rows)]
    if sort:
        rows.sort(key=_row_key)
    return names, rows


def _row_key(row):
    out = []
    for v in row:
        if v is None:
            out.append((0, ""))
        elif isinstance(v, float) and math.isnan(v):
            out.append((2, "nan"))
        else:
            out.append((1, str(v)))
    return out


def _values_equal(a, b, approx: float) -> bool:
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, float) or isinstance(b, float):
        fa, fb = float(a), float(b)
        if math.isnan(fa) or math.isnan(fb):
            return math.isnan(fa) and math.isnan(fb)
        if math.isinf(fa) or math.isinf(fb):
            return fa == fb
        if approx > 0:
            tol = approx * max(abs(fa), abs(fb), 1e-300)
            return abs(fa - fb) <= max(tol, 1e-12)
        return fa == fb
    return a == b


def assert_tables_equal(cpu: HostTable, tpu: HostTable,
                        ignore_order: bool = True,
                        approx_float: float = 1e-6) -> None:
    assert [n for n, _ in cpu.schema()] == [n for n, _ in tpu.schema()], \
        f"schema names differ: {cpu.schema()} vs {tpu.schema()}"
    cpu_names, cpu_rows = _canonical_rows(cpu, ignore_order)
    _, tpu_rows = _canonical_rows(tpu, ignore_order)
    assert len(cpu_rows) == len(tpu_rows), \
        (f"row count differs: cpu={len(cpu_rows)} tpu={len(tpu_rows)}\n"
         f"cpu={cpu_rows[:10]}\ntpu={tpu_rows[:10]}")
    for i, (cr, tr) in enumerate(zip(cpu_rows, tpu_rows)):
        for j, (cv, tv) in enumerate(zip(cr, tr)):
            assert _values_equal(cv, tv, approx_float), \
                (f"row {i} col {cpu_names[j]}: cpu={cv!r} tpu={tv!r}\n"
                 f"cpu row={cr}\ntpu row={tr}")


def assert_tpu_cpu_equal_df(df: DataFrame, ignore_order: bool = True,
                            approx_float: float = 1e-6,
                            conf: Optional[SrtConf] = None) -> None:
    """Run df's plan on both engines and diff results."""
    conf = conf or active_conf()
    cpu = cpu_exec.execute_cpu(df.plan)
    tpu = _run_tpu(df.plan, conf)
    assert_tables_equal(cpu, tpu, ignore_order, approx_float)


def assert_tpu_cpu_equal(build_df: Callable[..., DataFrame], *args,
                         ignore_order: bool = True,
                         approx_float: float = 1e-6, **kw) -> None:
    assert_tpu_cpu_equal_df(build_df(*args, **kw),
                            ignore_order=ignore_order,
                            approx_float=approx_float)


def assert_falls_back_to_cpu(df: DataFrame, expected_reason: str = ""
                             ) -> None:
    """Assert at least one node was tagged off the TPU (optionally with a
    matching reason) AND that results still agree through the fallback."""
    meta = overrides.tag_only(df.plan)
    reasons = _collect_reasons(meta)
    assert reasons, "expected a CPU fallback but whole plan is TPU-ready"
    if expected_reason:
        assert any(expected_reason in r for r in reasons), \
            f"no fallback reason matched {expected_reason!r}: {reasons}"
    assert_tpu_cpu_equal_df(df)


def assert_runs_on_tpu(df: DataFrame) -> None:
    """Assert NO node fell back (the reference's main regression guard)."""
    meta = overrides.tag_only(df.plan)
    reasons = _collect_reasons(meta)
    assert not reasons, f"unexpected CPU fallback: {reasons}"
    assert_tpu_cpu_equal_df(df)


def _collect_reasons(meta) -> list:
    out = list(meta.reasons)
    for c in meta.child_plans:
        out.extend(_collect_reasons(c))
    return out


def assert_consumer_partition(phases: dict) -> None:
    """A query record's ``phases``: the pulling thread's four waits and
    launches and its own host time add up to ``execute_ns`` exactly, each
    at least 0 and each wait within the same wait over all the query's
    threads (plan/session.py ``CONSUMER_PHASES``)."""
    from ..plan.session import CONSUMER_PHASES
    parts = [phases[k] for k in CONSUMER_PHASES] + [phases["consumer_host_ns"]]
    assert all(p >= 0 for p in parts), phases
    assert sum(parts) == phases["execute_ns"], phases
    for mine, every in (("consumer_scan_wait_ns", "prefetch_wait_ns"),
                        ("consumer_semaphore_wait_ns", "semaphore_wait_ns"),
                        ("consumer_dispatch_ns", "dispatch_ns"),
                        ("consumer_readback_ns", "readback_ns")):
        assert phases[mine] <= phases[every], (mine, phases)
    assert 0 <= phases["dispatch_cpu_ns"] <= phases["dispatch_ns"], phases
