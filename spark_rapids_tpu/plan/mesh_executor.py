"""Mesh executor: lower planner-produced physical plans to SPMD
programs over a jax.sharding.Mesh — one compiled program PER QUERY
STAGE.

The stage cut is the one plan/adaptive.py already makes for AQE
(everything between shuffle-exchange boundaries, ``stage_dag``); this
module compiles each stage to ONE ``jax.jit``-of-``shard_map`` program
over the device mesh and keeps stage outputs **device-resident**
between programs:

  stage body (child subtree of an exchange)  -> one sharded program
  exchange collective                        -> head of the CONSUMER
                                                stage's program:
    ShuffleExchangeExec(hash keys)   -> partition + lax.all_to_all
    ShuffleExchangeExec(range)       -> in-trace sampled bounds + a2a
    ShuffleExchangeExec(1 partition) -> lax.all_gather (+ shard-0 mask)
    resident exchange                -> identity hand-through pinned by
                                        with_sharding_constraint — the
                                        planner residency rule
                                        (overrides.mesh_resident_exchanges,
                                        the generalized
                                        MeshColocationBypass)
  BroadcastExchangeExec              -> replicated host-materialized
                                        input (partition-rule table) or
                                        in-program all_gather

Stage inputs map to PartitionSpecs through the declarative partition
rules (plan/partition_rules.py): stacked per-shard trees ride the data
axis, broadcast build sides are replicated. Nothing is serialized at a
stage boundary — bytes crossing one are recorded as
``shuffleBytesBypassed`` (they bypassed the serialized shuffle write
path entirely; ``shuffleBytesWritten`` stays 0 on mesh runs), and the
subset that rode an in-program collective also counts as
``shuffleBytesWire``.

A join whose static output capacity overflows retries ONLY its own
stage at doubled growth, re-using the already-materialized stage
inputs — the whole-plan grow-and-retry ladder (which re-lowered the
entire plan and re-executed every leaf per retry, and aborted q19 at
scale with an rc=-6 rendezvous abort: divergent per-device re-traces of
an ever-growing monolithic program) is gone. Stage programs are shared
process-wide by structural shape through jit_registry.shared_stage_jit
(one program per stage shape, not per device or query),
and stages that cannot retry donate their single-consumer inputs.

The reference's equivalent is a p2p shuffle (UCX ActiveMessages,
RapidsShuffleClient.scala:169) feeding the same staged operators; on
TPU the exchange is a compiled collective riding ICI (SURVEY §2.7 "TPU
equivalent" row, §7 hard-part #5).

Leaves (scans, host relations) are executed on the host driver once
per query, split into per-shard slices, and placed with a
``NamedSharding`` over the mesh (parallel/shuffle.py stacked form);
everything above the leaves is traced.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..columnar import dtypes as dt
from ..columnar.vector import (ColumnVector, ColumnarBatch, StringColumn,
                               choose_capacity, column_from_numpy,
                               round_pow2)
from ..conf import (MESH_BROADCAST_REPLICATED, MESH_DONATION,
                    MESH_MAX_JOIN_GROWTH, MESH_PARTITION_RULES,
                    MESH_STAGE_PROGRAMS, SrtConf, active_conf)
from ..exec.aggregate import FINAL, PARTIAL, HashAggregateExec
from ..exec.base import ExecContext, TpuExec
from ..exec.basic import (BatchScanExec, CoalesceBatchesExec, ExpandExec,
                          FilterExec, LocalLimitExec, ProjectExec, UnionExec)
from ..exec.exchange import BroadcastExchangeExec, ShuffleExchangeExec
from ..exec.join import _HashJoinBase
from ..exec.sort import SortExec, TopNExec
from ..obs import events as _events
from ..ops import kernels as K
from ..parallel.mesh import DATA_AXIS, mesh_key, tree_nbytes
from ..parallel.partition import (flatten_partitions, hash_partition_ids,
                                  partition_batch, range_partition_ids,
                                  round_robin_partition_ids,
                                  string_from_padded)
from ..parallel.shuffle import (all_gather_batch, all_to_all_partitions,
                                stack_shards, unstack_shards)
from ..robustness.faults import fault_point
from .partition_rules import (constrain_tree, is_replicated,
                              match_partition_rules, parse_rules, put_tree,
                              rule_path, spec_signature)
from .transitions import HostToDeviceExec


class UnsupportedMeshLowering(Exception):
    """Raised for plan nodes the mesh backend cannot lower (the caller
    falls back to single-process execution)."""


def _mask_to_shard0(batch: ColumnarBatch, axis: str) -> ColumnarBatch:
    keep = lax.axis_index(axis) == 0
    return ColumnarBatch(batch.columns, batch.names,
                         jnp.where(keep, batch.num_rows, 0)
                         .astype(jnp.int32))


def _exchange_kind(node: ShuffleExchangeExec) -> str:
    if node.sort_orders:
        return "range"
    if node.key_exprs:
        return "hash"
    if (node.num_partitions or 1) == 1:
        return "single"
    return "rr"


def _contains_shuffle(node) -> bool:
    if isinstance(node, ShuffleExchangeExec):
        return True
    return any(_contains_shuffle(c) for c in getattr(node, "children", []))


class _ArgSlot:
    """One positional input of a stage program: a host-materialized
    leaf stack or another stage's device-resident output."""

    __slots__ = ("kind", "node", "path", "spec", "key", "index")

    def __init__(self, kind: str, node, path: str, spec: P, index: int):
        self.kind = kind            # "leaf" | "stage"
        self.node = node
        self.path = path
        self.spec = spec
        self.key = (kind, id(node))
        self.index = index


class _StageBuild:
    """Per-attempt lowering state for one stage program: the ordered
    input slots, the traced join-overflow checks, and the structural
    signature (appended branch by branch during lowering) that keys the
    shared-program registry."""

    __slots__ = ("growth", "slots", "slot_by_key", "checks", "sig",
                 "has_join")

    def __init__(self, growth: int):
        self.growth = growth
        self.slots: List[_ArgSlot] = []
        self.slot_by_key: Dict = {}
        self.checks: List = []
        self.sig: List = []
        self.has_join = False


class MeshQueryExecutor:
    """Compiles and runs one physical plan on an n-device mesh, one
    sharded program per query stage (``srt.mesh.stagePrograms.enabled``;
    off = legacy single monolithic program, the fallback boundary)."""

    def __init__(self, mesh: Mesh, conf: Optional[SrtConf] = None,
                 axis: str = DATA_AXIS, join_growth: int = 2,
                 max_join_growth: Optional[int] = None):
        self.mesh = mesh
        self.axis = axis
        self.n = mesh.shape[axis]
        self.conf = conf or active_conf()
        self.join_growth = join_growth
        self._max_growth_override = max_join_growth
        self.rules = parse_rules(
            self.conf.get(MESH_PARTITION_RULES) or "", axis)
        #: exec_ids of exchanges lowered as device-resident identities
        self.colocated_exchanges: List[str] = []
        #: per-stage execution records (tests/observability)
        self.stage_records: List[dict] = []
        self.stage_retries = 0
        #: distinct host leaf materializations (a stage retry must NOT
        #: re-execute leaves — the q19 fix)
        self.leaf_executions = 0
        #: stage-boundary bytes handed through device-resident (never
        #: serialized) / subset that rode an in-program collective
        self.shuffle_bytes_bypassed = 0
        self.shuffle_bytes_wire = 0
        self._registered: set = set()
        self._resident: set = set()
        self._stage_outputs: Dict[int, object] = {}
        self._leaf_cache: Dict = {}
        self._build: Optional[_StageBuild] = None

    # ------------------------------------------------------------------
    # host driver
    # ------------------------------------------------------------------
    def run(self, physical: TpuExec) -> List[ColumnarBatch]:
        """Execute the plan; returns host-ordered result batches (shard
        order is partition order for sorted plans)."""
        from .overrides import mesh_resident_exchanges
        ctx = ExecContext(self.conf)
        #: kept for callers asserting on exchange metrics after the run
        self.last_ctx = ctx
        self._resident = mesh_resident_exchanges(physical, self.conf)
        staged = bool(self.conf.get(MESH_STAGE_PROGRAMS))
        if staged:
            from .adaptive import stage_dag
            stages, self._registered = stage_dag(physical)
        else:
            stages, self._registered = [], set()
        _events.emit("StageSubmitted", mode="mesh", num_shards=self.n,
                     join_growth=self.join_growth,
                     stage_programs=len(stages) + 1)
        for st in stages:
            body = st.exchange.children[0]
            label = f"s{st.order}:{type(self._unwrap(body)).__name__}"
            self._stage_outputs[id(st.exchange)] = \
                self._run_stage(body, ctx, label)
        out = self._run_stage(physical, ctx, "root")
        jax.block_until_ready(jax.tree_util.tree_leaves(out))
        _events.emit("StageCompleted", mode="mesh", num_shards=self.n,
                     overflowed=False, retries=self.stage_retries,
                     bytes_bypassed=self.shuffle_bytes_bypassed,
                     bytes_wire=self.shuffle_bytes_wire)
        return [b for b in unstack_shards(out) if int(b.num_rows) > 0]

    def _unwrap(self, node):
        """Trace through single-box fusion wrappers: a stage program is
        already one XLA computation, so fusion adds nothing here."""
        while True:
            chain = getattr(node, "mesh_chain_root", None)
            if chain is None:
                return node
            node = chain()

    def _is_leaf(self, node) -> bool:
        return isinstance(node, (BatchScanExec, HostToDeviceExec)) or \
            not node.children

    def _run_stage(self, root, ctx: ExecContext, label: str):
        """Compile + run one stage program; returns the stacked,
        device-resident output tree. Join-overflow retries re-lower
        THIS stage only, at doubled growth, against the retained
        inputs."""
        root_u = self._unwrap(root)
        if self._is_leaf(root_u):
            # trivial stage (exchange directly over a scan): the stage
            # output IS the placed leaf stack — no program to compile
            return self._leaf_value(root_u, ctx, P(self.axis))
        if isinstance(root_u, ShuffleExchangeExec) \
                and id(root_u) in self._registered \
                and id(root_u) in self._resident \
                and id(root_u) in self._stage_outputs:
            # plan root is a resident exchange: pure hand-through
            return self._account_stage_input(root_u, ctx)
        growth = self.join_growth
        if self._max_growth_override is not None:
            max_growth = int(self._max_growth_override)
        else:
            try:
                max_growth = int(self.conf.get(MESH_MAX_JOIN_GROWTH))
            except Exception:
                max_growth = 64
        max_growth = max(max_growth, growth)
        args = None
        retries = 0
        while True:
            build = _StageBuild(growth)
            self._build = build
            try:
                fn = self._lower(root, "")
            finally:
                self._build = None
            if args is None:
                args = [self._materialize_slot(s, ctx)
                        for s in build.slots]
            program, record = self._stage_program(build, fn, label)
            # what ``program.lower`` needs to show this stage's HLO
            record["arg_shapes"] = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), args)
            fault_point("mesh.stage.run", label)
            out, ok = program(*args)
            if bool(jnp.all(ok)):
                record["retries"] = retries
                # where the stage's output actually lives: the ids of
                # the devices holding its shards (all of the mesh, if
                # the work was spread)
                record["output_devices"] = sorted(
                    {d.id for x in jax.tree_util.tree_leaves(out)
                     for d in x.devices()})
                self.stage_records.append(record)
                return out
            if growth * 2 > max_growth:
                raise RuntimeError(
                    "mesh join output overflowed its static capacity "
                    f"(stage {label}) at maximum join growth "
                    f"{growth} — results would silently drop rows; "
                    "raise srt.mesh.maxJoinGrowth or repartition finer")
            growth *= 2
            retries += 1
            self.stage_retries += 1
            _events.emit("MeshStageRetry", stage=label,
                         join_growth=growth)

    # ------------------------------------------------------------------
    # stage inputs
    # ------------------------------------------------------------------
    def _slot(self, kind: str, node, path: str, spec: P) -> _ArgSlot:
        b = self._build
        key = (kind, id(node))
        slot = b.slot_by_key.get(key)
        if slot is None:
            slot = _ArgSlot(kind, node, path, spec, len(b.slots))
            b.slots.append(slot)
            b.slot_by_key[key] = slot
        return slot

    def _materialize_slot(self, slot: _ArgSlot, ctx: ExecContext):
        if slot.kind == "stage":
            return self._account_stage_input(slot.node, ctx)
        return self._leaf_value(slot.node, ctx, slot.spec)

    def _account_stage_input(self, node: ShuffleExchangeExec,
                             ctx: ExecContext):
        """Fetch a child stage's device-resident output and account its
        bytes ONCE per consuming stage (retries re-use the fetched
        value and never re-count)."""
        val = self._stage_outputs[id(node)]
        nbytes = tree_nbytes(val)
        resident = id(node) in self._resident
        node.record_mesh_exchange(ctx, nbytes, resident)
        self.shuffle_bytes_bypassed += nbytes
        if resident:
            if node.exec_id not in self.colocated_exchanges:
                self.colocated_exchanges.append(node.exec_id)
            _events.emit("MeshColocationBypass", exec_id=node.exec_id,
                         keys=[repr(e) for e in (node.key_exprs or [])])
        else:
            self.shuffle_bytes_wire += nbytes
        return val

    def _leaf_value(self, leaf, ctx: ExecContext, spec: P):
        """Host-execute a leaf subtree once and place it on the mesh:
        stacked per-shard slices split over the data axis, or one full
        replicated batch (broadcast build sides)."""
        replicated = is_replicated(spec)
        cache_key = (id(leaf), replicated)
        val = self._leaf_cache.get(cache_key)
        if val is not None:
            return val
        batches = self._leaf_batches(leaf, ctx,
                                     1 if replicated else self.n)
        self.leaf_executions += 1
        if replicated:
            val = batches[0]
        else:
            _normalize_strings(batches)
            val = stack_shards(batches)
        val = put_tree(val, self.mesh, spec)
        self._leaf_cache[cache_key] = val
        return val

    def _leaf_batches(self, leaf, ctx: ExecContext,
                      n_splits: int) -> List[ColumnarBatch]:
        """Host-execute a leaf subtree and split its rows into
        ``n_splits`` identically-shaped slices (contiguous split, so
        input order is preserved across the shard sequence)."""
        from .host_table import batch_to_table, concat_tables, to_pydict
        schema = leaf.output_schema
        tables = [batch_to_table(b) for b in leaf.execute(ctx)
                  if int(b.num_rows) > 0]
        if tables:
            table = concat_tables(tables)
            data = to_pydict(table)
            total = table.num_rows
        else:
            data = {n: [] for n, _ in schema}
            total = 0
        per = -(-max(total, 1) // n_splits)
        cap = choose_capacity(max(per, 8))
        names = [n for n, _ in schema]
        out = []
        for s in range(n_splits):
            lo, hi = min(s * per, total), min((s + 1) * per, total)
            chunk = {n: data[n][lo:hi] for n in names}
            out.append(_batch_from_pydict_typed(chunk, schema, cap))
        return out

    # ------------------------------------------------------------------
    # program assembly
    # ------------------------------------------------------------------
    def _stage_program(self, build: _StageBuild, fn: Callable,
                      label: str) -> Tuple[Callable, dict]:
        slots = list(build.slots)
        ax, mesh = self.axis, self.mesh
        donate: Tuple[int, ...] = ()
        if not build.has_join and self.conf.get(MESH_DONATION):
            # joins may overflow and retry against the same inputs, so
            # only join-free stages donate; multi-consumer exchanges
            # (full-outer sharing) are drained again by a later stage
            donate = tuple(
                s.index for s in slots
                if s.kind == "stage"
                and getattr(s.node, "_planned_consumers", 1) <= 1)
        in_specs = tuple(s.spec for s in slots)

        def shard_step(*vals):
            env = {}
            for s, v in zip(slots, vals):
                env[s.key] = v if is_replicated(s.spec) else \
                    jax.tree_util.tree_map(lambda x: x[0], v)
            build.checks = []
            out = fn(env)
            ok = jnp.ones((), jnp.bool_)
            for c in build.checks:
                ok = ok & c
            return jax.tree_util.tree_map(lambda x: x[None], (out, ok))

        def build_program():
            inner = jax.shard_map(shard_step, mesh=mesh,
                                  in_specs=in_specs, out_specs=P(ax),
                                  check_vma=False)

            def staged(*xs):
                # pin every input to its partition-rule sharding: a
                # device-resident stage output is consumed in place,
                # anything placed differently is resharded by XLA
                pinned = tuple(constrain_tree(x, mesh, s.spec)
                               for x, s in zip(xs, slots))
                return inner(*pinned)
            return staged

        key_parts = ["mesh_stage_v1", mesh_key(mesh), ax, build.growth,
                     tuple((s.kind, spec_signature(s.spec))
                           for s in slots),
                     tuple(build.sig)]
        from .. import jit_registry
        program = jit_registry.shared_stage_jit(
            build_program, key_parts, __name__, f"mesh_stage[{label}]",
            donate_argnums=donate)
        record = {
            "label": label,
            "program": program,
            "n_inputs": len(slots),
            "donated": list(donate),
            "growth": build.growth,
            "resident": [s.node.exec_id for s in slots
                         if s.kind == "stage"
                         and id(s.node) in self._resident],
        }
        return program, record

    # ------------------------------------------------------------------
    # lowering
    # ------------------------------------------------------------------
    def _lower(self, node: TpuExec,
               path: str) -> Callable[[Dict], ColumnarBatch]:
        ax, n = self.axis, self.n
        b = self._build
        chain = getattr(node, "mesh_chain_root", None)
        if chain is not None:
            # fusion wrappers: the stage nodes keep their unfused child
            # links, so lowering the terminal recovers the whole chain
            b.sig.append(("fused", type(node).__name__))
            return self._lower(chain(), path)
        path = rule_path(path, node)
        if self._is_leaf(node):
            slot = self._slot("leaf", node, path, P(ax))
            b.sig.append(("leaf", type(node).__name__,
                          list(node.output_schema)))
            key = slot.key
            return lambda env: env[key]

        if isinstance(node, ProjectExec):
            if node._eager:
                raise UnsupportedMeshLowering(
                    "eager projection (uuid/input_file/raise_error)")
            b.sig.append(("project", node.exprs))
            child = self._lower(node.children[0], path)

            def proj_fn(env):
                batch = child(env)
                # context expressions see shard-unique positions:
                # partition_id = shard index, row offsets disjoint
                idx = lax.axis_index(ax)
                return node._project_ctx(
                    batch, idx.astype(jnp.int64) * batch.capacity,
                    idx.astype(jnp.int32))
            return proj_fn

        if isinstance(node, FilterExec):
            b.sig.append(("filter", node.condition))
            child = self._lower(node.children[0], path)
            return lambda env: node._filter(child(env))

        if isinstance(node, CoalesceBatchesExec):
            return self._lower(node.children[0], path)

        from ..exec.pipeline import PrefetchExec
        if isinstance(node, PrefetchExec):
            # host-side pipelining has no meaning inside one traced
            # mesh program: transparent pass-through
            return self._lower(node.children[0], path)

        if isinstance(node, UnionExec):
            b.sig.append(("union", len(node.children)))
            kids = [self._lower(c, path) for c in node.children]

            def union_fn(env):
                batches = [k(env) for k in kids]
                cap = round_pow2(sum(x.capacity for x in batches))
                return K.concat_batches(batches, cap)
            return union_fn

        if isinstance(node, BroadcastExchangeExec):
            return self._lower_broadcast(node, path)

        if isinstance(node, ShuffleExchangeExec):
            return self._lower_shuffle(node, path)

        if isinstance(node, HashAggregateExec):
            return self._lower_agg(node, path)

        if isinstance(node, _HashJoinBase):
            return self._lower_join(node, path)

        if isinstance(node, TopNExec):
            b.sig.append(("topn", node.order, node.limit))
            child = self._lower(node.children[0], path)

            def topn_fn(env):
                local = node._topn(child(env))
                gathered = all_gather_batch(local, n, ax)
                return _mask_to_shard0(node._topn(gathered), ax)
            return topn_fn

        if isinstance(node, SortExec):
            b.sig.append(("sort", node.order))
            child = self._lower(node.children[0], path)
            # child is range-partitioned (planner): local sort per
            # shard; shard order == partition order == global order
            return lambda env: node._sort_one(child(env))

        from ..exec.basic import SampleExec
        if isinstance(node, SampleExec):
            b.sig.append(("sample", node.fraction, node.seed))
            child = self._lower(node.children[0], path)

            def sample_fn(env):
                batch = child(env)
                off = lax.axis_index(ax).astype(jnp.int64) * batch.capacity
                return node._sample(batch, off)
            return sample_fn

        if isinstance(node, ExpandExec):
            from ..exec.basic import _expand_project_builder
            b.sig.append(("expand", node.projections))
            child = self._lower(node.children[0], path)
            # node.projections are already dtype-unified across lists
            # (ExpandExec.__init__ casts divergent slots); build raw
            # un-jitted projectors — the stage trace jits the shard
            out_names = [nm for nm, _ in node.output_schema]
            fns = [_expand_project_builder(p, out_names)
                   for p in node.projections]

            def expand_fn(env):
                batch = child(env)
                outs = [f(batch) for f in fns]
                cap = round_pow2(sum(o.capacity for o in outs))
                return K.concat_batches(outs, cap)
            return expand_fn

        from ..exec.window import BatchedRunningWindowExec, WindowExec
        if isinstance(node, (WindowExec, BatchedRunningWindowExec)):
            return self._lower_window(node, path)

        if isinstance(node, LocalLimitExec):
            b.sig.append(("limit", node.limit))
            child = self._lower(node.children[0], path)

            def limit_fn(env):
                gathered = all_gather_batch(child(env), n, ax)
                return _mask_to_shard0(
                    K.local_limit(gathered, node.limit), ax)
            return limit_fn

        raise UnsupportedMeshLowering(type(node).__name__)

    def _lower_broadcast(self, node: BroadcastExchangeExec, path: str):
        """Broadcast build sides: the partition-rule table maps the
        subtree to replicated placement — host-materialize it once and
        hand every shard the full batch, no collective at all. A
        broadcast subtree that itself contains shuffles (or a user rule
        remapping it to the data axis) lowers per-shard with an
        in-program all_gather instead."""
        ax, n = self.axis, self.n
        b = self._build
        sub = node.children[0]
        spec = match_partition_rules(self.rules, path)
        if (is_replicated(spec)
                and self.conf.get(MESH_BROADCAST_REPLICATED)
                and not _contains_shuffle(sub)):
            slot = self._slot("leaf", sub, path, P())
            b.sig.append(("bcast_replicated",
                          list(sub.output_schema)))
            key = slot.key
            return lambda env: env[key]
        b.sig.append(("bcast_gather",))
        child = self._lower(sub, path)
        return lambda env: all_gather_batch(child(env), n, ax)

    def _lower_shuffle(self, node: ShuffleExchangeExec, path: str):
        b = self._build
        ax = self.axis
        kind = _exchange_kind(node)
        resident = id(node) in self._resident
        if id(node) in self._registered:
            # stage input: the child subtree ran as its own program;
            # its output arrives device-resident
            slot = self._slot("stage", node, path, P(ax))
            b.sig.append(("stage_in", kind, resident,
                          node.key_exprs, node.sort_orders,
                          list(node.output_schema)))
            key = slot.key
            reader = lambda env: env[key]  # noqa: E731
            if resident:
                # sharding-constraint exchange: rows are already on
                # their target shard (planner residency rule); the
                # with_sharding_constraint pin in the program wrapper
                # is the whole exchange
                return reader
            return self._exchange_collective(node, reader)
        # in-program exchange: whole-plan mode, or an exchange nested
        # under a broadcast subtree (not a registered stage)
        child = self._lower(node.children[0], path)
        if resident:
            if node.exec_id not in self.colocated_exchanges:
                self.colocated_exchanges.append(node.exec_id)
                _events.emit("MeshColocationBypass",
                             exec_id=node.exec_id,
                             keys=[repr(e)
                                   for e in (node.key_exprs or [])])
            b.sig.append(("colocated", node.key_exprs))
            return child
        b.sig.append(("exchange", kind, node.key_exprs,
                      node.sort_orders))
        return self._exchange_collective(node, child)

    def _exchange_collective(self, node: ShuffleExchangeExec,
                             child: Callable):
        """The exchange's collective form, applied to the per-shard
        batch ``child`` yields (a stage-input reader or an in-program
        subtree)."""
        ax, n = self.axis, self.n
        if node.sort_orders:
            orders = node.sort_orders

            def range_fn(env):
                batch = child(env)
                bounds = _inline_range_bounds(batch, orders, n, ax)
                keys = [o.expr.eval(batch) for o in orders]
                pids = range_partition_ids(
                    keys, bounds, [o.ascending for o in orders],
                    [o.nulls_first for o in orders])
                pb = partition_batch(batch, pids, n)
                return flatten_partitions(all_to_all_partitions(pb, ax))
            return range_fn
        if node.key_exprs:
            keys = node.key_exprs

            def hash_fn(env):
                batch = child(env)
                kc = [e.eval(batch) for e in keys]
                pids = hash_partition_ids(kc, n)
                pb = partition_batch(batch, pids, n)
                return flatten_partitions(all_to_all_partitions(pb, ax))
            return hash_fn
        if (node.num_partitions or 1) == 1:
            # concentrate everything on shard 0
            return lambda env: _mask_to_shard0(
                all_gather_batch(child(env), n, ax), ax)

        def rr_fn(env):
            batch = child(env)
            pids = round_robin_partition_ids(batch.capacity, n)
            pb = partition_batch(batch, pids, n)
            return flatten_partitions(all_to_all_partitions(pb, ax))
        return rr_fn

    def _lower_agg(self, node: HashAggregateExec, path: str):
        ax, n = self.axis, self.n
        b = self._build
        if node.mode == PARTIAL:
            b.sig.append(("agg_partial", node.group_exprs,
                          node.agg_exprs))
            child = self._lower(node.children[0], path)
            return lambda env: node._update(child(env), jnp.int64(0))
        if node.mode == FINAL:
            # FINAL-merge fusion removed any project prefix from the
            # tree (arm_merge_fusion); re-apply it here, bottom-up,
            # before the merge — the stage trace fuses it all anyway
            prefix = list(reversed(node._merge_fusion or []))
            b.sig.append(("agg_final", node.group_exprs, node.agg_exprs,
                          [p.exprs for p in prefix]))

            def pre(batch):
                for p in prefix:
                    batch = p._project(batch)
                return batch
            ex = node.children[0]
            if (not node.group_exprs and
                    isinstance(ex, ShuffleExchangeExec) and
                    (ex.num_partitions or 1) == 1):
                # global aggregate: gather all partial states, merge on
                # every shard, report from shard 0 only (the merge is
                # replicated — cheap: one row of state per shard)
                if id(ex) in self._registered:
                    slot = self._slot("stage", ex,
                                      rule_path(path, ex), P(ax))
                    b.sig.append(("stage_in", "single", False,
                                  list(ex.output_schema)))
                    key = slot.key
                    inner = lambda env: env[key]  # noqa: E731
                else:
                    inner = self._lower(ex.children[0],
                                        rule_path(path, ex))

                def global_fn(env):
                    gathered = all_gather_batch(inner(env), n, ax)
                    return _mask_to_shard0(
                        node._merge_finalize(pre(gathered)), ax)
                return global_fn
            child = self._lower(ex, path) \
                if isinstance(ex, ShuffleExchangeExec) \
                else self._lower(node.children[0], path)
            return lambda env: node._merge_finalize(pre(child(env)))
        # COMPLETE single-stage: update + merge locally is only correct
        # on one shard — require staged plans on mesh
        raise UnsupportedMeshLowering("complete-mode aggregate")

    def _lower_join(self, node: _HashJoinBase, path: str):
        b = self._build
        b.has_join = True
        b.sig.append(("join", node.join_type, node.build_side,
                      node._probe_key_exprs, node._build_key_exprs))
        left = self._lower(node.children[0], path)
        right = self._lower(node.children[1], path)
        growth = b.growth

        def join_fn(env):
            lb, rb = left(env), right(env)
            probe, build = (lb, rb) if node.build_side == "right" \
                else (rb, lb)
            pk = [e.eval(probe) for e in node._probe_key_exprs]
            bk = [e.eval(build) for e in node._build_key_exprs]
            out_cap = round_pow2(probe.capacity * growth)
            jt = node.join_type
            if jt in ("left_semi", "left_anti"):
                out, total = K.semi_anti_join(
                    probe, bk, pk, build.live_mask(),
                    anti=(jt == "left_anti"),
                    scratch_capacity=out_cap)
            elif jt == "inner":
                out, total = K.inner_join(probe, build, pk, bk, out_cap)
            else:
                out, total = K.left_join(probe, build, pk, bk, out_cap)
            # the kernel reports the TRUE required size; overflow fails
            # the stage (checked host-side), which retries at doubled
            # growth instead of silently dropping matches
            b.checks.append(total <= out_cap)
            return node._reorder_columns(out)
        return join_fn

    def _lower_window(self, node, path: str):
        """Window partitions co-locate via hash all-to-all on the
        partition keys, then the whole-partition segmented-scan kernel
        runs shard-locally (GpuWindowExec's clustered-distribution
        contract on the mesh). The batched-running variant re-uses the
        same kernel here — per shard the data is ONE batch, so the
        carried-state machinery is unnecessary (its sort child is
        skipped: the kernel re-sorts internally)."""
        from ..exec.window import BatchedRunningWindowExec, WindowExec
        ax, n = self.axis, self.n
        b = self._build
        inner = node.children[0]
        if isinstance(node, BatchedRunningWindowExec) and \
                isinstance(inner, SortExec):
            inner = inner.children[0]
        b.sig.append(("window", type(node).__name__, node.window_exprs,
                      node.partition_by))
        child = self._lower(inner, path)
        kernel = WindowExec(inner, node.window_exprs) \
            if isinstance(node, BatchedRunningWindowExec) else node
        if not node.partition_by:
            def global_fn(env):
                g = all_gather_batch(child(env), n, ax)
                return _mask_to_shard0(kernel._compute(g), ax)
            return global_fn
        keys = node.partition_by

        def win_fn(env):
            batch = child(env)
            kc = [e.eval(batch) for e in keys]
            pids = hash_partition_ids(kc, n)
            pb = partition_batch(batch, pids, n)
            local = flatten_partitions(all_to_all_partitions(pb, ax))
            return kernel._compute(local)
        return win_fn


def _inline_range_bounds(batch: ColumnarBatch, orders, n: int, axis: str):
    """Compute shared range bounds inside the trace: all_gather each key
    column, sort the gathered sample with the device comparator, take
    n-1 quantile rows. Every shard computes identical bounds (the
    all_gather is symmetric), which is all correctness needs."""
    keys = [o.expr.eval(batch) for o in orders]
    live = batch.live_mask()
    g_live = lax.all_gather(live, axis, axis=0, tiled=True)
    g_keys = []
    for kc in keys:
        if isinstance(kc, StringColumn):
            padded = lax.all_gather(kc.padded(), axis, axis=0, tiled=True)
            lens = lax.all_gather(kc.lengths(), axis, axis=0, tiled=True)
            valid = lax.all_gather(kc.validity, axis, axis=0, tiled=True)
            g_keys.append(string_from_padded(padded, lens, valid))
        else:
            data = lax.all_gather(kc.data, axis, axis=0, tiled=True)
            valid = lax.all_gather(kc.validity, axis, axis=0, tiled=True)
            g_keys.append(ColumnVector(data, valid, kc.dtype))
    perm = K.sort_indices(g_keys, [o.ascending for o in orders],
                          [o.nulls_first for o in orders], g_live)
    total = jnp.sum(g_live).astype(jnp.int32)
    bounds = []
    cut = jnp.arange(1, n, dtype=jnp.int32)
    cut_pos = jnp.minimum((cut * total) // n,
                          jnp.maximum(total - 1, 0))
    idx = jnp.take(perm, cut_pos)
    for gk in g_keys:
        if isinstance(gk, StringColumn):
            starts = jnp.take(gk.offsets[:-1], idx)
            lens = jnp.take(gk.lengths(), idx)
            w = gk.pad_bucket
            k = jnp.arange(w, dtype=jnp.int32)
            rows = jnp.take(
                gk.chars,
                jnp.clip(starts[:, None] + k[None, :], 0,
                         gk.char_capacity - 1))
            rows = jnp.where(k[None, :] < lens[:, None], rows,
                             jnp.zeros((), jnp.uint8))
            bounds.append(string_from_padded(
                rows, lens, jnp.take(gk.validity, idx)))
        else:
            bounds.append(ColumnVector(jnp.take(gk.data, idx),
                                       jnp.take(gk.validity, idx),
                                       gk.dtype))
    return bounds


def _batch_from_pydict_typed(data: dict, schema, capacity: int
                             ) -> ColumnarBatch:
    names = [n for n, _ in schema]
    cols = []
    for name, dtype in schema:
        arr = np.asarray(data[name], dtype=object)
        mask = np.array([v is not None for v in arr], dtype=bool)
        cols.append(column_from_numpy(arr, capacity, dtype=dtype,
                                      mask=mask))
    n_rows = len(data[names[0]]) if names else 0
    return ColumnarBatch(cols, names, n_rows)


def _normalize_strings(batches: List[ColumnarBatch]) -> None:
    """Pad every shard's string columns to common char capacity and pad
    bucket so the shards stack into one leading-dim pytree."""
    if not batches:
        return
    for ci in range(len(batches[0].columns)):
        cols = [b.columns[ci] for b in batches]
        if not isinstance(cols[0], StringColumn):
            continue
        char_cap = max(c.char_capacity for c in cols)
        pad = max(c.pad_bucket for c in cols)
        for b, c in zip(batches, cols):
            chars = c.chars
            if c.char_capacity < char_cap:
                chars = jnp.concatenate(
                    [chars, jnp.zeros(char_cap - c.char_capacity,
                                      jnp.uint8)])
            b.columns[ci] = StringColumn(c.offsets, chars, c.validity,
                                         pad_bucket=pad)


def run_on_mesh(physical: TpuExec, mesh: Mesh,
                conf: Optional[SrtConf] = None,
                join_growth: int = 2,
                max_join_growth: Optional[int] = None
                ) -> List[ColumnarBatch]:
    """Compile + run one plan over a mesh. Join-overflow handling is
    per stage and internal: only the overflowing stage re-lowers at
    doubled growth (bounded by ``srt.mesh.maxJoinGrowth`` /
    ``max_join_growth``) against its retained inputs — leaves execute
    exactly once per query."""
    return MeshQueryExecutor(mesh, conf, join_growth=join_growth,
                             max_join_growth=max_join_growth) \
        .run(physical)


def run_on_mesh_or_fallback(physical: TpuExec, mesh: Mesh,
                            conf: Optional[SrtConf] = None
                            ) -> Tuple[List[ColumnarBatch], str]:
    """Mesh execution with clean degradation: any mesh-side failure
    (unsupported lowering, stage-program fault, overflow past the
    growth cap) emits a ``MeshFallback`` event, resets the plan's
    stateful nodes, and re-executes serialized single-stream — the
    fallback boundary tools/chaos_check.py seeds faults into. Returns
    (batches, "mesh" | "serialized")."""
    conf = conf or active_conf()
    try:
        return run_on_mesh(physical, mesh, conf), "mesh"
    except (KeyboardInterrupt, SystemExit):
        raise
    except Exception as e:
        _events.emit("MeshFallback",
                     error=f"{type(e).__name__}: {e}")
        physical.reset_for_rerun()
        ctx = ExecContext(conf)
        return list(physical.execute(ctx)), "serialized"
