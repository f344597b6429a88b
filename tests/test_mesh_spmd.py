"""SPMD stage-native mesh execution: one compiled program per query
stage (plan/mesh_executor.py stage DAG mode), partition-rule
PartitionSpec mapping, sharding-constraint (device-resident) exchanges,
shared stage programs in the jit registry, per-stage join-growth retry
that never re-executes leaves, and clean fallback to serialized
execution — all on the 8-device virtual CPU mesh tests/conftest.py
configures."""

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from spark_rapids_tpu import jit_registry
from spark_rapids_tpu import parallel as par
from spark_rapids_tpu.columnar.vector import batch_to_pydict
from spark_rapids_tpu.conf import SrtConf
from spark_rapids_tpu.expr.aggregates import Average, CountStar, Sum
from spark_rapids_tpu.expr.core import Alias, col
from spark_rapids_tpu.plan import overrides
from spark_rapids_tpu.plan.mesh_executor import (MeshQueryExecutor,
                                                 run_on_mesh,
                                                 run_on_mesh_or_fallback)
from spark_rapids_tpu.plan.partition_rules import (default_rules,
                                                   is_replicated,
                                                   match_partition_rules,
                                                   parse_rules, rule_path,
                                                   spec_signature)
from spark_rapids_tpu.plan.session import TpuSession
from spark_rapids_tpu.robustness import faults

N = 8
MOD = "spark_rapids_tpu.plan.mesh_executor"


@pytest.fixture(scope="module")
def mesh():
    return par.data_mesh(N)


def _conf(**kw):
    base = {"srt.shuffle.partitions": N}
    base.update({k.replace("_", "."): v for k, v in kw.items()})
    return SrtConf(base)


def _rows(batches):
    out = []
    for b in batches:
        d = batch_to_pydict(b)
        names = list(d)
        out.extend(tuple(d[n][i] for n in names)
                   for i in range(len(d[names[0]])))
    return out


def _assert_same(mesh_batches, df, ordered=False):
    got = _rows(mesh_batches)
    want = [tuple(r.values()) for r in df.collect()]
    if not ordered:
        got, want = sorted(got, key=repr), sorted(want, key=repr)
    assert len(got) == len(want), (len(got), len(want))
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for a, b in zip(g, w):
            if isinstance(a, float) and isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-9, abs=1e-9)
            else:
                assert a == b, (g, w)


def _exchanges(node, acc=None):
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    acc = [] if acc is None else acc
    if isinstance(node, ShuffleExchangeExec):
        acc.append(node)
    for c in getattr(node, "children", []):
        _exchanges(c, acc)
    return acc


def _metric_total(ex, phys, name):
    total = 0
    for x in _exchanges(phys):
        m = ex.last_ctx.metrics_for(x.exec_id).get(name)
        if m is not None:
            total += m.value
    return total


# ---------------------------------------------------------------------------
# partition rules: declarative plan-path -> PartitionSpec mapping
# ---------------------------------------------------------------------------

def test_partition_rules_default_table():
    rules = default_rules("data")
    # broadcast subtrees replicate; everything else rides the data axis
    assert is_replicated(match_partition_rules(
        rules, "ShuffledHashJoinExec/BroadcastExchangeExec"))
    assert is_replicated(match_partition_rules(
        rules, "JoinExec/BroadcastExchangeExec/ProjectExec"))
    assert match_partition_rules(
        rules, "SortExec/ShuffleExchangeExec") == P("data")
    assert match_partition_rules(rules, "BatchScanExec") == P("data")


def test_partition_rules_user_rules_take_precedence():
    rules = parse_rules(
        ".*BroadcastExchangeExec=data;.*FilterExec$=replicated", "data")
    # user rule overrides the builtin broadcast-replication
    assert match_partition_rules(
        rules, "JoinExec/BroadcastExchangeExec") == P("data")
    assert is_replicated(match_partition_rules(rules, "Scan/FilterExec"))
    # non-matching paths still fall through to the defaults
    assert match_partition_rules(rules, "ProjectExec") == P("data")


def test_partition_rules_malformed_raises():
    with pytest.raises(ValueError):
        parse_rules("no-equals-clause", "data")
    with pytest.raises(ValueError):
        parse_rules(".*=banana", "data")


def test_rule_path_and_spec_signature():
    class FakeScanExec:
        pass
    assert rule_path("", FakeScanExec()) == "FakeScanExec"
    assert rule_path("A/B", FakeScanExec()) == "A/B/FakeScanExec"
    assert spec_signature(P("data")) == ("data",)
    assert spec_signature(P()) == ()
    assert spec_signature(P("data", None)) == ("data", "*")


def test_partition_rules_flow_into_executor(mesh):
    """srt.mesh.partitionRules remaps broadcast subtrees onto the data
    axis: the executor then lowers the broadcast as an in-program
    all_gather instead of a replicated host input — results identical
    either way."""
    conf = _conf(srt_sql_broadcastRowThreshold=8)
    s = TpuSession(conf)
    fact = s.create_dataframe({"k": [i % 6 for i in range(200)],
                               "v": [float(i) for i in range(200)]})
    dim = s.create_dataframe({"k": list(range(6)),
                              "name": [f"d{i}" for i in range(6)]})
    df = fact.join(dim, "k")
    phys = overrides.apply_overrides(df.plan, conf)
    assert "BroadcastExchange" in phys.tree_string()
    ex = MeshQueryExecutor(mesh, conf)
    _assert_same(ex.run(phys), df)
    phys2 = overrides.apply_overrides(df.plan, conf)
    conf2 = _conf(srt_sql_broadcastRowThreshold=8,
                  **{"srt.mesh.partitionRules":
                     ".*BroadcastExchangeExec=data"})
    _assert_same(MeshQueryExecutor(mesh, conf2).run(phys2), df)


# ---------------------------------------------------------------------------
# stage DAG mode: per-stage programs, bit-identity, byte accounting
# ---------------------------------------------------------------------------

def _grouped_agg_df(s, n_rows=500, seed=0):
    rng = np.random.default_rng(seed)
    return s.create_dataframe({
        "k": rng.integers(0, 17, n_rows).tolist(),
        "v": rng.uniform(-5, 5, n_rows).tolist(),
    }).group_by("k").agg(Alias(Sum(col("v")), "s"),
                         Alias(Average(col("v")), "a"),
                         Alias(CountStar(), "c"))


def test_stage_dag_grouped_agg_and_byte_accounting(mesh):
    conf = _conf()
    s = TpuSession(conf)
    df = _grouped_agg_df(s)
    phys = overrides.apply_overrides(df.plan, conf)
    ex = MeshQueryExecutor(mesh, conf)
    _assert_same(ex.run(phys), df)
    # partial->exchange->final splits into (at least) two programs
    assert len(ex.stage_records) >= 2, ex.stage_records
    # nothing serialized at stage boundaries: every boundary byte is a
    # bypass of the shuffle write path, and the written counter stays 0
    assert ex.shuffle_bytes_bypassed > 0
    bypassed = _metric_total(ex, phys, "shuffleBytesBypassed")
    written = _metric_total(ex, phys, "shuffleBytesWritten")
    assert bypassed == ex.shuffle_bytes_bypassed
    assert written == 0
    assert bypassed > written


def test_stage_mode_matches_whole_plan_mode(mesh):
    """srt.mesh.stagePrograms.enabled=false is the fallback boundary:
    the legacy single monolithic program — results must be identical."""
    conf_on = _conf()
    conf_off = _conf(**{"srt.mesh.stagePrograms.enabled": False})
    s = TpuSession(conf_on)
    rng = np.random.default_rng(3)
    left = s.create_dataframe({"k": rng.integers(0, 9, 240).tolist(),
                               "v": rng.uniform(0, 9, 240).tolist()})
    right = s.create_dataframe({"k": [i % 9 for i in range(45)],
                                "w": [float(i) for i in range(45)]})
    df = left.join(right, "k").group_by("k").agg(
        Alias(Sum(col("v")), "sv"), Alias(Sum(col("w")), "sw"))
    ex_on = MeshQueryExecutor(mesh, conf_on)
    rows_on = sorted(_rows(ex_on.run(
        overrides.apply_overrides(df.plan, conf_on))), key=repr)
    ex_off = MeshQueryExecutor(mesh, conf_off)
    rows_off = sorted(_rows(ex_off.run(
        overrides.apply_overrides(df.plan, conf_off))), key=repr)
    assert rows_on == rows_off
    assert len(ex_on.stage_records) >= 2
    # whole-plan mode = exactly one program, no stage boundaries
    assert len(ex_off.stage_records) == 1
    assert ex_off.shuffle_bytes_bypassed == 0


def test_resident_exchange_is_identity_handthrough(mesh):
    """Hash-over-identical-keys exchange chains stay device-resident:
    the inner exchange's collective places the rows, the outer one is a
    sharding-constraint identity (generalized MeshColocationBypass) —
    and its bytes count as bypassed but NOT wire."""
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.expr.core import col as c
    conf = _conf()
    s = TpuSession(conf)
    df = s.create_dataframe({"k": [i % 5 for i in range(80)],
                             "v": list(range(80))})
    phys = overrides.apply_overrides(df.plan, conf)
    inner = ShuffleExchangeExec(phys, [c("k")], num_partitions=N)
    outer = ShuffleExchangeExec(inner, [c("k")], num_partitions=N)
    ex = MeshQueryExecutor(mesh, conf)
    got = sorted(_rows(ex.run(outer)))
    want = sorted((k, v) for k, v in zip([i % 5 for i in range(80)],
                                         range(80)))
    assert got == [tuple(r) for r in want]
    assert len(ex.colocated_exchanges) == 1
    assert ex.shuffle_bytes_bypassed > ex.shuffle_bytes_wire > 0


# ---------------------------------------------------------------------------
# shared stage programs: one compile per stage shape, not per query run
# ---------------------------------------------------------------------------

def test_stage_programs_shared_across_runs(mesh):
    conf = _conf()
    s = TpuSession(conf)
    df1 = _grouped_agg_df(s, seed=11)
    phys1 = overrides.apply_overrides(df1.plan, conf)
    before = jit_registry.stats(MOD)
    ex1 = MeshQueryExecutor(mesh, conf)
    rows1 = sorted(_rows(ex1.run(phys1)), key=repr)
    mid = jit_registry.stats(MOD)
    n_programs = len(ex1.stage_records)
    assert n_programs >= 2
    assert mid["misses"] - before["misses"] <= n_programs
    # identical plan shape, fresh plan objects and data values: every
    # stage program is a registry HIT — no new program is registered
    df2 = _grouped_agg_df(s, seed=12)
    phys2 = overrides.apply_overrides(df2.plan, conf)
    ex2 = MeshQueryExecutor(mesh, conf)
    rows2 = ex2.run(phys2)
    after = jit_registry.stats(MOD)
    assert len(ex2.stage_records) == n_programs
    assert after["misses"] == mid["misses"], (before, mid, after)
    assert after["hits"] - mid["hits"] >= n_programs
    assert after["entries"] == mid["entries"]
    assert rows1  # first run produced data too
    _assert_same(rows2, df2)


# ---------------------------------------------------------------------------
# donation policy
# ---------------------------------------------------------------------------

def test_stage_input_donation_policy(mesh):
    conf = _conf()
    s = TpuSession(conf)
    df = _grouped_agg_df(s, seed=21)
    ex = MeshQueryExecutor(mesh, conf)
    _assert_same(ex.run(overrides.apply_overrides(df.plan, conf)), df)
    # the FINAL-merge stage consumes the partial stage's output as its
    # only planned consumer and holds no join: it donates that input
    donated = [i for rec in ex.stage_records for i in rec["donated"]]
    assert donated, ex.stage_records
    # conf kill switch
    conf_off = _conf(**{"srt.mesh.donation.enabled": False})
    df2 = _grouped_agg_df(s, seed=22)
    ex2 = MeshQueryExecutor(mesh, conf_off)
    _assert_same(ex2.run(overrides.apply_overrides(df2.plan, conf_off)),
                 df2)
    assert all(not rec["donated"] for rec in ex2.stage_records)


def test_join_stages_never_donate(mesh):
    """A stage holding a join may overflow and retry against the SAME
    inputs — donation there would read deleted buffers."""
    conf = _conf(srt_sql_broadcastRowThreshold=1)
    s = TpuSession(conf)
    left = s.create_dataframe({"k": [i % 7 for i in range(140)],
                               "v": list(range(140))})
    right = s.create_dataframe({"k": [i % 7 for i in range(35)],
                                "w": list(range(35))})
    df = left.join(right, "k")
    ex = MeshQueryExecutor(mesh, conf)
    _assert_same(ex.run(overrides.apply_overrides(df.plan, conf)), df)
    join_stages = [rec for rec in ex.stage_records if rec["n_inputs"] >= 2]
    assert join_stages, ex.stage_records
    assert all(not rec["donated"] for rec in join_stages)


# ---------------------------------------------------------------------------
# per-stage retry: the q19 fix — overflow re-lowers ONE stage and never
# re-executes leaves
# ---------------------------------------------------------------------------

def test_join_overflow_retries_stage_without_releafing(mesh):
    conf = _conf(srt_sql_broadcastRowThreshold=1)
    s = TpuSession(conf)
    # many-to-many: 40x40 matches per key, guaranteed to overflow the
    # initial growth=1 output capacity
    left = s.create_dataframe({"k": [i % 4 for i in range(160)],
                               "v": list(range(160))})
    right = s.create_dataframe({"k": [i % 4 for i in range(160)],
                                "w": list(range(160))})
    df = left.join(right, "k")
    phys = overrides.apply_overrides(df.plan, conf)
    ex = MeshQueryExecutor(mesh, conf, join_growth=1)
    got = ex.run(phys)
    assert ex.stage_retries >= 1
    # leaves executed exactly once each despite the retries: the retry
    # re-lowers the overflowing stage against its RETAINED inputs (the
    # old whole-plan ladder re-executed every leaf per attempt — the
    # q19 memory bomb)
    assert ex.leaf_executions == 2
    assert sum(len(b) for b in [_rows(got)]) == 160 * 40
    _assert_same(got, df)


def test_join_overflow_past_cap_raises(mesh):
    conf = _conf(srt_sql_broadcastRowThreshold=1)
    s = TpuSession(conf)
    left = s.create_dataframe({"k": [0] * 64, "v": list(range(64))})
    right = s.create_dataframe({"k": [0] * 64, "w": list(range(64))})
    df = left.join(right, "k")
    phys = overrides.apply_overrides(df.plan, conf)
    ex = MeshQueryExecutor(mesh, conf, join_growth=1, max_join_growth=1)
    with pytest.raises(RuntimeError, match="overflowed"):
        ex.run(phys)


# ---------------------------------------------------------------------------
# fallback boundary: seeded fault degrades cleanly to serialized
# ---------------------------------------------------------------------------

def test_mesh_stage_fault_falls_back_to_serialized(mesh):
    conf = _conf()
    s = TpuSession(conf)
    df = _grouped_agg_df(s, seed=31)
    phys = overrides.apply_overrides(df.plan, conf)
    faults.arm_fault_plan("mesh.stage.run:reset@1")
    try:
        out, mode = run_on_mesh_or_fallback(phys, mesh, conf)
    finally:
        faults.disarm_fault_plan()
    assert mode == "serialized"
    _assert_same(out, df)


def test_mesh_no_fault_stays_on_mesh(mesh):
    conf = _conf()
    s = TpuSession(conf)
    df = _grouped_agg_df(s, seed=32)
    phys = overrides.apply_overrides(df.plan, conf)
    out, mode = run_on_mesh_or_fallback(phys, mesh, conf)
    assert mode == "mesh"
    _assert_same(out, df)


# ---------------------------------------------------------------------------
# NDS shapes: bit-identity of staged SPMD vs serialized, incl. the q19
# regression shape
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def nds():
    from spark_rapids_tpu.models.nds import NDS_QUERIES, register_nds
    conf = SrtConf({"srt.shuffle.partitions": N})
    s = TpuSession(conf)
    register_nds(s, "/tmp/nds_spmd_4k", scale_rows=4000)
    return s, conf, NDS_QUERIES


# tier-1 keeps one NDS shape on the virtual mesh (~8-12 s each)
@pytest.mark.parametrize("qid", [
    pytest.param("q3", marks=pytest.mark.slow), "q42",
    pytest.param("q52", marks=pytest.mark.slow)])
def test_nds_stage_identity(mesh, nds, qid):
    s, conf, queries = nds
    df = s.sql(queries[qid])
    phys = overrides.apply_overrides(df.plan, conf)
    ex = MeshQueryExecutor(mesh, conf)
    got = sorted(_rows(ex.run(phys)), key=repr)
    from spark_rapids_tpu.plan.host_table import to_pydict
    single = to_pydict(s.execute(df.plan))
    ks = list(single)
    want = sorted((tuple(single[k][i] for k in ks)
                   for i in range(len(single[ks[0]]) if ks else 0)),
                  key=repr)
    assert len(got) == len(want), (qid, len(got), len(want))
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if isinstance(a, float) and isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-9, abs=1e-9), (g, w)
            else:
                assert a == b, (g, w)
    # the plan really ran as a stage DAG with device-resident
    # boundaries, and nothing was serialized
    assert len(ex.stage_records) >= 2, (qid, ex.stage_records)
    assert ex.shuffle_bytes_bypassed > 0
    assert _metric_total(ex, phys, "shuffleBytesWritten") == 0


# slow: ~20 s for one query on the virtual mesh
@pytest.mark.slow
def test_nds_q19_completes_on_virtual_mesh(mesh):
    """Regression: q19's join-heavy shape aborted (rc=-6 rendezvous /
    48GB cap) under the whole-plan grow-and-retry ladder. The staged
    executor must complete it on the 8-device virtual mesh with
    bounded retries and single leaf execution."""
    from spark_rapids_tpu.models.nds import NDS_QUERIES, register_nds
    conf = SrtConf({"srt.shuffle.partitions": N})
    s = TpuSession(conf)
    register_nds(s, "/tmp/nds_spmd_q19_1k", scale_rows=1000)
    df = s.sql(NDS_QUERIES["q19"])
    phys = overrides.apply_overrides(df.plan, conf)
    ex = MeshQueryExecutor(mesh, conf)
    got = sorted(_rows(ex.run(phys)), key=repr)
    from spark_rapids_tpu.plan.host_table import to_pydict
    single = to_pydict(s.execute(df.plan))
    ks = list(single)
    want = sorted((tuple(single[k][i] for k in ks)
                   for i in range(len(single[ks[0]]) if ks else 0)),
                  key=repr)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if isinstance(a, float) and isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-9, abs=1e-9)
            else:
                assert a == b
    # every leaf host-executed exactly once — no retry ladder releafing
    leaf_count = ex.leaf_executions
    assert leaf_count >= 1
    assert len(ex.stage_records) >= 2
