"""Bloom filter kernels + runtime join pre-filtering (ops/bloom.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.vector import ColumnVector
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.exec.base import ExecContext
from spark_rapids_tpu.expr import col
from spark_rapids_tpu.ops import bloom as B
from spark_rapids_tpu.plan import TpuSession, overrides


def _col(vals, valid=None):
    a = np.asarray(vals, np.int64)
    v = np.ones(len(a), bool) if valid is None else np.asarray(valid)
    return ColumnVector(jnp.asarray(a), jnp.asarray(v), dt.INT64)


def test_no_false_negatives_and_low_fp():
    rng = np.random.default_rng(0)
    keys = rng.choice(1 << 40, size=5000, replace=False)
    build, probe_hit, probe_miss = keys[:2000], keys[:1000], keys[2000:]
    nb = B.choose_num_bits(len(build))
    bits = B.build_bloom([_col(build)], jnp.ones(len(build), bool), nb)
    hits = np.asarray(B.might_contain(bits, [_col(probe_hit)]))
    assert hits.all()  # bloom filters never produce false negatives
    misses = np.asarray(B.might_contain(bits, [_col(probe_miss)]))
    assert misses.mean() < 0.05  # ~10 bits/key, 6 hashes -> <1% expected


def test_null_and_dead_rows_excluded():
    bits = B.build_bloom([_col([1, 2, 3], [True, False, True])],
                         jnp.asarray([True, True, False]),
                         B.MIN_BITS)
    # only key 1 is live+non-null
    out = np.asarray(B.might_contain(
        bits, [_col([1, 2, 3, 0], [True, True, True, False])]))
    assert out[0]
    assert not out[3]  # null probe key -> False from the kernel


def test_might_contain_expression():
    from spark_rapids_tpu.expr.hashing import BloomFilterMightContain
    session = TpuSession()
    bits = B.build_bloom([_col([10, 20])], jnp.ones(2, bool), B.MIN_BITS)
    df = session.create_dataframe({"k": [10, 20, 30, None]})
    out = df.select(BloomFilterMightContain(col("k"), np.asarray(bits))
                    .alias("m")).to_pydict()
    assert out["m"][0] is True and out["m"][1] is True
    assert out["m"][3] is None  # null input -> null (Spark contract)


def _join_counts(conf):
    session = TpuSession(conf)
    rng = np.random.default_rng(1)
    n = 20_000
    probe = {"k": rng.integers(0, 100_000, n).tolist(),
             "v": rng.uniform(0, 1, n).tolist()}
    # key 7 twice: a build side with a duplicate key stays on the hash
    # path (a unique integer key would be a lookup table, which has no
    # use for a bloom filter in front of it: exec/join.py)
    build = {"k": list(range(50)) + [7],
             "name": [f"x{i}" for i in range(51)]}
    left = session.create_dataframe(probe)
    right = session.create_dataframe(build)
    q = left.join(right, "k")
    physical = overrides.apply_overrides(q.plan, conf)
    ctx = ExecContext(conf)
    rows = sum(int(b.num_rows) for b in physical.execute(ctx))
    dropped = sum(ms["bloomFilteredRows"].value
                  for ms in ctx.metrics.values()
                  if "bloomFilteredRows" in ms)
    return rows, dropped


def test_join_results_identical_with_bloom():
    on = SrtConf({"srt.sql.join.bloomFilter.enabled": True,
                  "srt.sql.join.bloomFilter.minProbeRows": 1,
                  "srt.sql.broadcastRowThreshold": 1})
    off = SrtConf({"srt.sql.join.bloomFilter.enabled": False,
                   "srt.sql.broadcastRowThreshold": 1})
    rows_on, dropped_on = _join_counts(on)
    rows_off, dropped_off = _join_counts(off)
    assert rows_on == rows_off
    assert dropped_on > 0 and dropped_off == 0
