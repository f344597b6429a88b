"""Out-of-core sort: bounded-memory k-way merge of spilled runs
(VERDICT r3 #3; GpuSortExec.scala:242 contract).

The partition is larger than the configured device row budget; the sort
must (a) produce globally sorted output across multiple batches, (b)
keep peak device rows under the budget, (c) survive injected RetryOOM
through the merge loop (RmmSparkRetrySuiteBase pattern)."""

import numpy as np
import pytest

from spark_rapids_tpu.columnar.vector import batch_from_pydict
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.exec.base import ExecContext, TpuExec
from spark_rapids_tpu.exec.sort import SortExec, SortOrder
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.memory.budget import reset_task_context, task_context


class _SourceExec(TpuExec):
    def __init__(self, batches, schema):
        super().__init__()
        self._batches = batches
        self._schema = schema

    @property
    def output_schema(self):
        return self._schema

    def do_execute(self, ctx):
        yield from self._batches


def _make_batches(n_batches=8, rows=4096, seed=0):
    rng = np.random.default_rng(seed)
    batches = []
    vals = []
    for i in range(n_batches):
        v = rng.integers(-10_000, 10_000, rows)
        t = rng.random(rows)
        batches.append(batch_from_pydict(
            {"v": v.tolist(), "t": t.tolist()}))
        vals.append(v)
    return batches, np.concatenate(vals)


def _run_sort(batches, schema, budget_rows, descending=False):
    conf = SrtConf({"srt.sql.sort.oocRowBudget": budget_rows})
    src = _SourceExec(batches, schema)
    node = SortExec(src, [SortOrder(col("v"), ascending=not descending)],
                    global_sort=True)
    ctx = ExecContext(conf)
    out = []
    for b in node.execute(ctx):
        d, m = b.column("v").to_numpy(int(b.num_rows))
        out.append(d)
    metrics = ctx.metrics.get(node.exec_id, {})
    peak = metrics.get("sortOocPeakRows")
    return np.concatenate(out) if out else np.array([]), \
        (peak.value if peak else 0)


def test_ooc_sort_correct_and_bounded():
    reset_task_context()
    batches, all_vals = _make_batches(n_batches=10, rows=4096)
    schema = batches[0].schema()
    budget = 8192   # total is 40960 rows: forces the OOC path
    got, peak = _run_sort(batches, schema, budget)
    assert got.shape[0] == all_vals.shape[0]
    np.testing.assert_array_equal(got, np.sort(all_vals))
    assert peak > 0, "OOC path must have engaged"
    assert peak <= budget, f"device residency {peak} exceeded {budget}"


def test_ooc_sort_descending():
    reset_task_context()
    batches, all_vals = _make_batches(n_batches=6, rows=2048, seed=3)
    schema = batches[0].schema()
    got, peak = _run_sort(batches, schema, 4096, descending=True)
    np.testing.assert_array_equal(got, np.sort(all_vals)[::-1])
    assert 0 < peak <= 4096


def test_ooc_sort_survives_injected_retry_oom():
    reset_task_context()
    batches, all_vals = _make_batches(n_batches=6, rows=2048, seed=7)
    schema = batches[0].schema()
    # fire a RetryOOM a few allocations into the merge loop
    task_context().force_retry_oom(num_allocs_before=20)
    got, peak = _run_sort(batches, schema, 4096)
    np.testing.assert_array_equal(got, np.sort(all_vals))
    assert task_context().retry_count >= 1, \
        "the injected OOM must have gone through the retry path"


# slow: ~15 s; test_ooc_sort_correct_and_bounded keeps the OOC path in tier 1
@pytest.mark.slow
def test_ooc_sort_cascade_many_runs():
    """k runs far above budget/(2*256): the cascade pre-merge keeps
    the residency bound instead of letting carry grow to k*256."""
    reset_task_context()
    batches, all_vals = _make_batches(n_batches=12, rows=700, seed=5)
    schema = batches[0].schema()
    got, peak = _run_sort(batches, schema, 1024)
    np.testing.assert_array_equal(got, np.sort(all_vals))
    assert 0 < peak <= 2048, f"cascade must bound residency, got {peak}"


def test_in_core_path_unchanged():
    reset_task_context()
    batches, all_vals = _make_batches(n_batches=3, rows=512)
    schema = batches[0].schema()
    got, peak = _run_sort(batches, schema, 1 << 22)
    np.testing.assert_array_equal(got, np.sort(all_vals))
    assert peak == 0, "small partitions must take the in-core path"
