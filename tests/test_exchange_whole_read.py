"""A single-process shuffle exchange that its reader takes as one group is
not split: the map side holds each batch whole (with the rows and bytes
every reduce partition would receive), a reader of every partition at
once streams the held batches, and any other read cuts them first into
the slices the split path makes (exec/exchange.py, docs/ADAPTIVE.md).

Each case compares the whole read with the split path of the same
query, or checks that the split path still is today's.
"""

import numpy as np
import pytest

from spark_rapids_tpu import jit_registry
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.exec.base import ExecContext
from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
from spark_rapids_tpu.expr.aggregates import Count, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.jit_registry import read_int
from spark_rapids_tpu.memory.spill import batch_nbytes
from spark_rapids_tpu.parallel.shuffle_manager import shuffle_manager
from spark_rapids_tpu.plan import TpuSession, overrides
from spark_rapids_tpu.plan.adaptive import stage_groups
from spark_rapids_tpu.plan.host_table import batch_to_table, to_pydict
from spark_rapids_tpu.testing import assert_tpu_cpu_equal_df

BASE = {"srt.shuffle.partitions": 8,
        "srt.sql.broadcastRowThreshold": 1}  # joins shuffle
NO_COALESCE = {"srt.sql.adaptive.coalescePartitions.enabled": False}


def _session(**extra):
    return TpuSession(SrtConf({**BASE, **extra}))


def _frame(s, seed, n=160):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 40, n).tolist()
    return s.create_dataframe({"k": k, "s": [f"g{x % 7}" * (x % 3)
                                             for x in k],
                               "v": rng.uniform(0, 10, n).tolist()})


def _facts(s):
    """Two map batches: the union of two frames."""
    return _frame(s, 1).union(_frame(s, 2))


def _exchanges(physical):
    out, todo = [], [physical]
    while todo:
        node = todo.pop()
        if isinstance(node, ShuffleExchangeExec):
            out.append(node)
        todo.extend(node.children)
    return out


def _run(df, monkeypatch, physical=None, ctx=None):
    """(output batches as row dicts, metrics summed over the plan, the
    labels of every program launched)."""
    labels = []
    real = jit_registry._dispatch

    def spy(span, runner, args, kwargs):
        labels.append(span)
        return real(span, runner, args, kwargs)
    monkeypatch.setattr(jit_registry, "_dispatch", spy)
    try:
        physical = physical or overrides.apply_overrides(
            df.plan, df.session.conf)
        ctx = ctx or ExecContext(df.session.conf)
        batches = [to_pydict(batch_to_table(b))
                   for b in physical.execute(ctx)
                   if read_int(b.num_rows) > 0]
    finally:
        monkeypatch.setattr(jit_registry, "_dispatch", real)
    metrics = {}
    for em in ctx.metrics.values():
        for name, metric in em.items():
            metrics[name] = metrics.get(name, 0) + metric.value
    return batches, metrics, labels


def _rows(batches, ordered=False):
    rows = [tuple(r) for b in batches for r in zip(*b.values())]
    return rows if ordered else sorted(rows, key=repr)


def _grouped(s):
    return _facts(s).group_by(col("k"), col("s")).agg(
        Sum(col("v")).alias("sv"), Count(col("v")).alias("n"))


def _case_aggregate(monkeypatch):
    """A FINAL aggregate whose AQE grouping is one group reads the held
    map batches: no partition program, and the split path's answer to
    the bit. The split path cuts each held batch with the program that
    gathers every slice straight to its capacity."""
    whole, m, labels = _run(_grouped(_session()), monkeypatch)
    assert m["shuffleWholeBatches"] == 2 and m["shuffleSplitBatches"] == 0
    assert m["adaptiveCoalescedPartitions"] == 7
    assert "launch._hash_partition_builder" not in labels
    split, m2, labels2 = _run(_grouped(_session(**NO_COALESCE)),
                              monkeypatch)
    assert m2["shuffleSplitBatches"] == 2
    assert m2.get("shuffleWholeBatches", 0) == 0
    assert "launch._hash_cut_builder" in labels2
    assert "launch._hash_partition_builder" not in labels2
    assert _rows(whole) == _rows(split)


def _case_global_sort(monkeypatch):
    """A global sort whose rows fit one batch: one range partition, no
    sample and no range program, one sort, one answer batch; the
    sampled split (rows over one batch) sorts the same rows the same."""
    q = _facts(_session()).sort("v")
    whole, m, labels = _run(q, monkeypatch)
    assert len(whole) == 1
    assert not any("_range_partition_builder" in x for x in labels)
    assert m["shuffleWholeBatches"] >= 1
    split_q = _facts(_session(**{"srt.sql.batchSizeRows": 64})).sort("v")
    split, m2, labels2 = _run(split_q, monkeypatch)
    assert "launch._range_partition_builder" in labels2
    assert len(split) > 1 and m2["shuffleSplitBatches"] >= 1
    assert _rows(whole, ordered=True) == _rows(split, ordered=True)


def _case_one_partition(monkeypatch):
    """A one-partition exchange (a global aggregate's) writes its map
    batches as they are: no round-robin program."""
    q = _facts(_session()).agg(Sum(col("v")).alias("sv"),
                               Count(col("v")).alias("n"))
    _, m, labels = _run(q, monkeypatch)
    assert m["shuffleWholeBatches"] >= 1 and m["shuffleSplitBatches"] == 0
    assert "launch._rr_partition_builder" not in labels
    assert_tpu_cpu_equal_df(q)


def _case_low_min_rows(monkeypatch):
    """With a low row floor AQE makes several groups: the held batches
    are cut into the slices the split path writes, whose rows and bytes
    are the statistics recorded when they were held, so the grouping
    and its event are the split path's; the answer is the whole read's."""
    import spark_rapids_tpu.obs.events as ev
    s = _session(**{
        "srt.sql.adaptive.coalescePartitions.minPartitionRows": 20})
    q = _grouped(s)
    physical = overrides.apply_overrides(q.plan, s.conf)
    ctx = ExecContext(s.conf)
    ex, = [e for e in _exchanges(physical) if e.key_exprs]
    rows, nbytes = ex.materialized_stats(ctx)
    emitted = []
    monkeypatch.setattr(ev, "emit", lambda name, **f: emitted.append(
        dict(f, event=name)))
    groups = stage_groups(ctx, ex)
    mgr = ex.manager or shuffle_manager()
    ex._split_held(ctx, mgr)
    got_rows, got_bytes = [0] * 8, [0] * 8
    for r in range(8):
        for b in mgr.read_partition(ex.shuffle_id, r):
            got_rows[r] += read_int(b.num_rows)
            got_bytes[r] += batch_nbytes(b)
    assert (got_rows, got_bytes) == (rows, nbytes)
    assert 1 < len(groups) < 8
    assert groups == ShuffleExchangeExec.coalesce_groups(
        got_rows, 20, got_bytes, 8 << 20)
    change, = [e for e in emitted if e["event"] == "AdaptivePlanChanged"]
    assert change["partitions_after"] == len(groups)
    assert change["total_rows"] == sum(got_rows)
    assert change["total_bytes"] == sum(got_bytes)
    split, m, _ = _run(q, monkeypatch, physical, ctx)
    assert m["shuffleSplitBatches"] == 2
    whole, _, _ = _run(_grouped(_session()), monkeypatch)
    assert _rows(split) == _rows(whole)


def _case_shuffled_join(monkeypatch):
    """A shuffled join reads its two exchanges partition by partition:
    both are cut with the same hash, so a key lands in the same
    partition on both sides; coalesced into one group, both are read
    whole."""
    s = _session(**NO_COALESCE)
    left, right = _facts(s), _frame(s, 3, n=120)
    q = left.join(right, ([col("k")], [col("k")]), how="inner")
    physical = overrides.apply_overrides(q.plan, s.conf)
    ctx = ExecContext(s.conf)
    sides = _exchanges(physical)
    assert len(sides) == 2
    keys = []
    for ex in sides:
        ex._write(ctx)
        mgr = ex.manager or shuffle_manager()
        ex._split_held(ctx, mgr)
        keys.append([{k for b in mgr.read_partition(ex.shuffle_id, r)
                      for k in to_pydict(batch_to_table(b))["k"]}
                     for r in range(8)])
    for i in range(8):
        for j in range(8):
            if i != j:
                assert not keys[0][i] & keys[1][j]
    split, m, _ = _run(q, monkeypatch, physical, ctx)
    assert m["shuffleSplitBatches"] == 3
    assert_tpu_cpu_equal_df(q)
    coalesced = _session()
    q2 = _facts(coalesced).join(_frame(coalesced, 3, n=120),
                                ([col("k")], [col("k")]), how="inner")
    whole, m2, labels = _run(q2, monkeypatch)
    assert m2["shuffleWholeBatches"] == 3 and m2["shuffleSplitBatches"] == 0
    assert "launch._hash_partition_builder" not in labels
    assert _rows(whole) == _rows(split)


CASES = {"aggregate": _case_aggregate, "global_sort": _case_global_sort,
         "one_partition": _case_one_partition,
         "low_min_rows": _case_low_min_rows,
         "shuffled_join": _case_shuffled_join}


@pytest.mark.parametrize("case", sorted(CASES))
def test_exchange_whole_read(case, monkeypatch):
    CASES[case](monkeypatch)
