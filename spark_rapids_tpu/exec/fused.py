"""Fused operator pipelines: one jitted program per linear chain.

The planner (plan/overrides.py, ``_insert_fusion``) collapses
scan -> filter -> project -> partial-aggregate chains into a single
``FusedPipelineExec`` whose per-batch compute is ONE ``jax.jit``
program, registered through ``jit_registry.shared_fn_jit`` so the
traced artifact is shared across partitions and across queries with
structurally identical chains. This is the direct analogue of the
reference keeping whole operator pipelines resident on device — cuDF's
fused filter/project paths and GpuHashAggregateExec running its update
pass directly on the scan output — instead of materializing every
operator boundary to HBM and reading it back.

Three things the fused program buys over the stock per-operator path:

- XLA sees the whole chain in one trace, so filter masks, projection
  arithmetic and the aggregate update fuse into one kernel schedule
  with no intermediate batch round-tripping through HBM;
- the input batch's buffers can be DONATED to the program
  (``donate_argnums``) on non-CPU backends, letting XLA alias them for
  scratch/output instead of allocating fresh device memory;
- one compiled program per distinct chain shape, reused by every
  partition of every query with the same structure (the registry key
  covers the expression trees and schemas, nothing per-instance).

Correctness contract: the fused program is the literal composition of
the same stage functions the unfused operators trace (``FilterExec.
_filter``, ``ProjectExec._project``, ``HashAggregateExec._update``),
so fused output is bit-identical to unfused output per batch —
``tests/test_fusion.py`` proves this on NDS queries and the matcher
refuses any chain whose semantics depend on host-side state (eager
expressions, partition-context expressions).

OOM handling: each input batch runs through the memory framework's
``with_retry`` with the standard halve-by-rows split policy, so a
RetryOOM spills-and-retries and a SplitAndRetryOOM re-enters the fused
program on each half. Retryable OOMs are raised by the python-side
budget/fault layer BEFORE the program launches, so donation (which
consumes the input on launch) composes with retry.
"""

from __future__ import annotations

from typing import Iterator, List

import jax
import jax.numpy as jnp

from ..columnar.vector import ColumnarBatch
from ..jit_registry import shared_fn_jit
from ..jit_registry import stats as _registry_stats
from ..ops import kernels as K
from .aggregate import count_lane_flags
from .base import ExecContext, Metric, NvtxTimer, Schema, TpuExec

#: module-level fusion tally (bench reads this + the registry's
#: per-module stats to report compile reuse across a sweep);
#: joins/final_aggs/sorts are the v2 shapes layered on the v1 chains
FUSION_STATS = {"chains": 0, "stages": 0, "joins": 0, "final_aggs": 0,
                "sorts": 0}

#: HashAggregateExec fields the fused terminal stage reads, in spec
#: order (must stay in sync with the agg spec built in __init__)
_AGG_FIELDS = ("group_exprs", "agg_exprs", "_key_names",
               "_state_schemas", "_result_schema", "_packed_schema")


def fusion_stats() -> dict:
    """Chains/stages fused this process plus the jit-registry share
    charged to this module (hits = compiled-program reuse)."""
    s = dict(FUSION_STATS)
    s["registry"] = _registry_stats(module=__name__)
    return s


def _row_stage_fn(spec):
    """One fused stage as a batch -> batch function. Each stage traces
    under ``jax.named_scope(<the operator it replaces>)``, so inside a
    fused program an op's ``op_name`` still says whose it is. A filter
    here compacts (``K.filter_batch``: the batch that leaves has its
    live rows as a prefix); in a chain that ends in an aggregate it
    does not: see ``_masked_stage_fn``."""
    kind = spec[0]
    if kind == "filter":
        cond = spec[1]

        def filt(batch: ColumnarBatch) -> ColumnarBatch:
            with jax.named_scope("FilterExec"):
                return K.filter_batch(batch, cond.eval(batch))
        return filt
    exprs, names = spec[1], spec[2]

    def proj(batch: ColumnarBatch) -> ColumnarBatch:
        with jax.named_scope("ProjectExec"):
            return ColumnarBatch([e.eval(batch) for e in exprs],
                                 list(names), batch.num_rows)
    return proj


def _masked_stage_fn(spec):
    """A stage of a chain whose terminal is the aggregate, as
    ``(batch, keep) -> (batch, keep)``: a filter evaluates its
    condition over the rows as they stand and ANDs it into ``keep``,
    the mask the aggregate applies anyway (``live``: refused rows land
    on the scratch group with zeroed values) — no gather of every
    column in front of an operator that reads each row once. A
    projection passes ``keep`` through. No batch leaves the program
    with live rows that are not a prefix: the mask ends in the
    aggregate."""
    if spec[0] != "filter":
        proj = _row_stage_fn(spec)
        return lambda batch, keep: (proj(batch), keep)
    cond = spec[1]

    def mask(batch: ColumnarBatch, keep):
        with jax.named_scope("FilterExec"):
            c = cond.eval(batch)
            return batch, keep & c.data & c.validity
    return mask


def _masked_agg_chain(specs):
    """The chain in front of an aggregate terminal and the aggregate, as
    ``run(batch, row_offset) -> (packed, rows_in, flags)``. ``rows_in``
    is what the caller advances its row offset by: the rows that
    entered the chain (the positions order-sensitive aggregates see are
    the rows' own, unfiltered), or 0 when the filters kept none of
    them (the caller then emits no partial, as the unfused aggregate
    never sees an empty batch)."""
    stage_fns = [_masked_stage_fn(s) for s in specs[:-1]]
    masked = any(s[0] == "filter" for s in specs[:-1])
    shell = _agg_shell(specs[-1])
    use_pallas = bool(specs[-1][1])

    def run(batch, row_offset):
        rows, keep = batch.num_rows, batch.live_mask() if masked else None
        for f in stage_fns:
            batch, keep = f(batch, keep)
        packed, flags = _agg_stage(shell, use_pallas, batch, row_offset,
                                   keep)
        if masked:
            rows = jnp.where(jnp.any(keep), rows, 0)
        return packed, rows, flags
    return run


def _agg_stage(shell, use_pallas: bool, batch, row_offset, live):
    """The aggregate that ends a fused chain: (packed, lane flags)."""
    with jax.named_scope("HashAggregateExec"):
        if use_pallas:
            return shell._update_pallas(batch, row_offset, live)
        return shell._update(batch, row_offset, live), \
            jnp.zeros(2, jnp.int32)


def _agg_shell(spec):
    from .aggregate import HashAggregateExec
    shell = object.__new__(HashAggregateExec)
    for name, val in zip(_AGG_FIELDS, spec[3:]):
        setattr(shell, name, list(val))
    shell._pallas_max_cap = int(spec[2])
    return shell


def _fused_program_builder(specs):
    """MODULE-LEVEL builder for shared_fn_jit: the fused per-batch
    program, a pure function of the stage specs.

    Non-aggregate chains: ``run(batch) -> batch``. Aggregate-terminated
    chains: ``run(batch, row_offset) -> (packed, rows_in, flags)``
    (``_masked_agg_chain``): ``rows_in`` advances the caller's
    row_offset and ``flags`` reports the grouped MXU lane's per-batch
    engagement and how the batch's groups were found.
    """
    specs = tuple(specs)
    if specs[-1][0] == "agg":
        return _masked_agg_chain(specs)
    stage_fns = [_row_stage_fn(s) for s in specs]

    def run(batch):
        for f in stage_fns:
            batch = f(batch)
        return batch
    return run


def _fused_join_builder(join_type, probe_keys, build_keys, out_capacity,
                        reorder_n, suffix_specs, mode, table_size=0):
    """MODULE-LEVEL builder for shared_fn_jit: one program running the
    per-pair join (a lookup or the gather-map join: ``mode``, exec/
    join.py), the left/right column reorder, and the probe-side suffix
    chain (filter/project/partial-agg), so the joined batch never
    materializes in HBM between operators. ``aux`` is what the join
    computed from its build side ahead (``BuildSide.aux``).

    Non-aggregate suffixes: ``run(probe, build, *aux) -> (batch,
    total)``. Aggregate-terminated: ``run(probe, build, row_offset,
    *aux) -> (packed, rows_in, flags, total)``. ``total`` is the
    join kernel's true required output size — the host only trusts the
    suffix output when ``total <= out_capacity`` (the capacity-growth
    contract of exec/join.py, unchanged by fusion)."""
    from .join import _join_run_builder
    base = _join_run_builder(join_type, list(probe_keys),
                             list(build_keys), out_capacity, mode,
                             table_size)
    specs = tuple(suffix_specs)
    has_agg = bool(specs) and specs[-1][0] == "agg"
    stage_fns = [] if has_agg else [_row_stage_fn(s) for s in specs]

    def reorder(out: ColumnarBatch) -> ColumnarBatch:
        # kernel output is probe-then-build; plan output is
        # left-then-right (same rule as _HashJoinBase._reorder_columns)
        if reorder_n is None:
            return out
        cols = out.columns[reorder_n:] + out.columns[:reorder_n]
        names = out.names[reorder_n:] + out.names[:reorder_n]
        return ColumnarBatch(cols, names, out.num_rows)

    def join(probe, build, aux):
        with jax.named_scope("HashJoinExec"):
            out, total = base(probe, build, *aux)
            return reorder(out), total

    if not has_agg:
        def run(probe, build, *aux):
            out, total = join(probe, build, aux)
            for f in stage_fns:
                out = f(out)
            return out, total
        return run
    suffix = _masked_agg_chain(specs)

    def run_agg(probe, build, row_offset, *aux):
        out, total = join(probe, build, aux)
        packed, rows_in, flags = suffix(out, row_offset)
        return packed, rows_in, flags, total
    return run_agg


def _fused_merge_builder(prefix_specs, agg_spec, cap):
    """MODULE-LEVEL builder for shared_fn_jit: the FINAL-merge fusion
    program. ``run(*batches)`` concatenates one partition's packed
    partials into ``cap`` slots, applies the projection prefix the
    planner absorbed, and merges+finalizes — one program instead of an
    eager concat followed by a separate merge launch. Each distinct
    batch count is its own cached signature (callers bound it with
    srt.exec.fusion.finalAgg.maxMergeInputs)."""
    stage_fns = [_row_stage_fn(s) for s in tuple(prefix_specs)]
    shell = _agg_shell(agg_spec)

    def run(*batches):
        b = batches[0] if len(batches) == 1 \
            else K.concat_batches(list(batches), cap)
        for f in stage_fns:
            b = f(b)
        with jax.named_scope("HashAggregateExec"):
            return shell._merge_finalize(b)
    return run


def fused_final_merge_fn(agg, projs, cap: int):
    """Shared fused FINAL-merge program for ``agg`` (exec/aggregate.py
    calls this when the planner armed merge fusion). ``projs`` are the
    fused-away ProjectExecs in application order (bottom-up)."""
    prefix_specs = tuple(
        ("project", tuple(p.exprs), tuple(n for n, _ in p.output_schema))
        for p in projs)
    # same spec layout as the v1 "agg" spec so _agg_shell applies
    # (the pallas fields are dead in the merge pass)
    agg_spec = ("agg", False, 0) + tuple(
        tuple(getattr(agg, f)) for f in _AGG_FIELDS)
    return shared_fn_jit(_fused_merge_builder, prefix_specs, agg_spec, cap)


def _schema_row_bytes(schema: Schema) -> int:
    """Estimated device bytes per capacity slot for ``schema`` (data +
    validity lane); variable-width columns counted at a nominal 16B."""
    total = 0
    for _, t in schema:
        phys = getattr(t, "physical", None)
        if phys is None:
            total += 16
        else:
            try:
                total += jnp.dtype(phys).itemsize
            except Exception:
                total += 16
        total += 1  # validity
    return total


class FusedPipelineExec(TpuExec):
    """A planner-fused linear chain executed as one jitted program.

    ``stages`` are the ORIGINAL exec nodes in application order
    (bottom-up: filter before project before partial aggregate); they
    are kept both as the source of the fused program's specs and so
    tree consumers that must see through the fusion (mesh lowering,
    DPP's column-passthrough walk) can reuse the unfused chain — the
    stage nodes still reference their original children.
    """

    _streams_child = True

    def __init__(self, source: TpuExec, stages: List[TpuExec],
                 use_pallas: bool = False, pallas_max_cap: int = 1 << 24,
                 donate: bool = False):
        super().__init__(source)
        from .aggregate import HashAggregateExec
        from .basic import FilterExec, ProjectExec
        self.stages = list(stages)
        terminal = self.stages[-1]
        self._agg = terminal if isinstance(terminal, HashAggregateExec) \
            else None
        self._use_pallas = bool(use_pallas and self._agg is not None)
        self._schema = list(terminal.output_schema)
        specs = []
        for st in self.stages:
            if isinstance(st, FilterExec):
                specs.append(("filter", st.condition))
            elif isinstance(st, ProjectExec):
                specs.append(("project", tuple(st.exprs),
                              tuple(n for n, _ in st.output_schema)))
            else:
                specs.append(("agg", self._use_pallas,
                              int(pallas_max_cap)) +
                             tuple(tuple(getattr(st, f))
                                   for f in _AGG_FIELDS))
        self._specs = tuple(specs)
        # donation is only sound when the source's buffers are
        # single-use (planner gates on file scans) and only effective
        # off-CPU (the CPU backend ignores donations with a warning)
        self.donate = bool(donate) and jax.default_backend() != "cpu"
        jit_kwargs = {"donate_argnums": (0,)} if self.donate else {}
        self._fn = shared_fn_jit(_fused_program_builder, self._specs,
                                 **jit_kwargs)
        # bytes an unfused pipeline would materialize per capacity slot
        # at every internal operator boundary (each non-terminal
        # stage's output batch) — the HBM round-trips fusion removes
        self._saved_bytes_per_slot = sum(
            _schema_row_bytes(st.output_schema)
            for st in self.stages[:-1])
        FUSION_STATS["chains"] += 1
        FUSION_STATS["stages"] += len(self.stages)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    @property
    def output_partitioning(self):
        return self.stages[-1].output_partitioning

    def mesh_chain_root(self) -> TpuExec:
        """The unfused terminal of the wrapped chain. The mesh stage
        executor traces THROUGH fusion wrappers — a stage program is
        already one XLA computation, so the single-box fusion adds
        nothing there; the stage nodes keep their original child links,
        and lowering from the terminal recovers the whole chain."""
        return self.stages[-1]

    def node_description(self) -> str:
        inner = " -> ".join(type(s).__name__ for s in self.stages)
        tags = []
        if self._use_pallas:
            tags.append("pallas")
        if self.donate:
            tags.append("donate")
        tag = f" ({', '.join(tags)})" if tags else ""
        return f"FusedPipeline[{inner}]{tag}"

    # --- per-stage attribution (tracer-gated calibration) ---
    def _calibrate(self, ctx: ExecContext, batch: ColumnarBatch,
                   row_offset: int, metrics) -> bool:
        """Run the first batch stage-by-stage through the operators'
        own jitted functions, timing each with a device sync, and emit
        one ``fused:<Stage>`` span + metric per stage. This is the
        per-stage op-time attribution for the fused program (which is
        opaque to host timers); outputs are discarded — the stream's
        results always come from the fused program. Only runs when the
        span tracer is on, and only once per execution.

        Returns False — and emits no spans or metrics — when the batch
        empties mid-chain: the unfused operators never charge op time
        for stages an emptied batch would not reach (_partial_stream
        and the Project/Filter loops all skip empty inputs), so
        calibrating on it would skew fused-vs-unfused op-time
        comparisons. The caller retries on the next batch."""
        import time as _time
        cur = batch
        for st in self.stages:
            if st is self._agg:
                break
            cur = st._jit(cur)
            if int(cur.num_rows) == 0:
                return False
        parent = None
        for frame in reversed(ctx.timer_stack):
            sp = getattr(frame, "_span", None)
            if sp is not None:
                parent = sp.span_id
                break
        if parent is None:
            parent = ctx.tracer.current_id()
        cur = batch
        off = jnp.int64(row_offset)
        for i, st in enumerate(self.stages):
            name = f"fused:{type(st).__name__}"
            span = ctx.tracer.begin(
                name, kind="operator", parent=parent,
                attrs={"stage": i, "fused_in": self.exec_id,
                       "desc": st.node_description()})
            t0 = _time.perf_counter_ns()
            if st is self._agg:
                cur = st._jit_update(cur, off)
            else:
                cur = st._jit(cur)
            jax.block_until_ready(cur)
            ns = _time.perf_counter_ns() - t0
            ctx.tracer.end(span)
            mname = f"fusedStageTime.{i}.{type(st).__name__}"
            metrics.setdefault(
                mname, Metric(mname, Metric.MODERATE, "ns")).add(ns)
        return True

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        from ..memory.retry import (split_spillable_in_half_by_rows,
                                    with_retry)
        from ..memory.spill import SpillableBatch, SpillPriority
        m = ctx.metrics_for(self.exec_id)
        fused_ops = m.setdefault("fusedOps",
                                 Metric("fusedOps", Metric.ESSENTIAL))
        saved = m.setdefault(
            "fusionBytesSaved",
            Metric("fusionBytesSaved", Metric.ESSENTIAL, "B"))
        fuse_time = m.setdefault("fusedTime",
                                 Metric("fusedTime", Metric.MODERATE,
                                        "ns"))
        fused_ops.set(len(self.stages))
        # batches whose filter went into the aggregate as its mask
        masked = m.setdefault(
            "aggMaskedFilterBatches",
            Metric("aggMaskedFilterBatches", Metric.DEBUG)) \
            if self._agg is not None and any(
                s[0] == "filter" for s in self._specs) else None
        state = {"offset": 0}
        used_flags: List = []
        calibrated = ctx.tracer is None

        def run_one(sb):
            batch = sb.get()
            with ctx.semaphore, NvtxTimer(fuse_time, "fused"):
                if self._agg is not None:
                    out, rows_in, used = self._fn(
                        batch, jnp.int64(state["offset"]))
                    n_in = int(rows_in)
                    state["offset"] += n_in
                    if masked is not None:
                        masked.add(1)
                    if n_in == 0:
                        # the unfused aggregate never sees (and never
                        # emits a partial for) a batch that filtered
                        # down to nothing (_partial_stream skips them)
                        sb.close()
                        return None
                    if self._use_pallas:
                        used_flags.append(used)
                else:
                    out = self._fn(batch)
            saved.add(self._saved_bytes_per_slot * int(batch.capacity))
            sb.close()
            return out

        for batch in self.children[0].execute(ctx):
            if int(batch.num_rows) == 0:
                continue
            if not calibrated:
                calibrated = self._calibrate(ctx, batch,
                                             state["offset"], m)
            sb = SpillableBatch(batch, SpillPriority.ACTIVE_ON_DECK)
            for out in with_retry(
                    sb, run_one,
                    split_policy=split_spillable_in_half_by_rows):
                if out is not None:
                    yield out
        count_lane_flags(m, used_flags)


class FusedHashJoinExec(TpuExec):
    """A planner-fused hash join plus its probe-side suffix chain
    (fusion v2, shape (a): device-side hash-join fusion).

    Wraps the ORIGINAL join node — ``children = [join]``, so every
    tree walk (exchange-consumer counting, the adaptive stage
    collector's parent checks, pipeline insertion) sees the join and
    its exchanges unchanged — and arms it (``join._fusion = self``) so
    the join's per-pair program is swapped for one jitted program
    running build+probe join, column reorder, and the absorbed
    filter/project/partial-agg suffix. Everything ELSE the join does
    stays in the join: broadcast demotion, skew splits,
    sub-partitioning, bloom prefilter, DPP and the capacity-growth
    retry contract all apply unchanged, which is what keeps fusion
    composable with every plan/adaptive.py decision — the decisions
    re-evaluate at execute time, after any adaptive rewrite, never
    before.

    OOM handling mirrors FusedPipelineExec: each probe batch runs
    under ``with_retry`` with the halve-by-rows split policy (sound
    for every supported join type — the probe is the preserved side,
    so probe-row chunks join independently). Donation: the probe batch
    is donated only on a capacity-measured relaunch, where the
    reported total makes the launch provably final and the batch
    provably dead (a first launch may overflow and need the probe
    again).
    """

    _streams_child = True

    def __init__(self, join: TpuExec, suffix: List[TpuExec],
                 use_pallas: bool = False, pallas_max_cap: int = 1 << 24,
                 donate: bool = False):
        super().__init__(join)
        from .aggregate import HashAggregateExec
        from .basic import FilterExec, ProjectExec
        from .join import LEFT_ANTI, LEFT_SEMI
        self.join = join
        self.suffix = list(suffix)
        terminal = self.suffix[-1]
        self._agg = terminal if isinstance(terminal, HashAggregateExec) \
            else None
        self._use_pallas = bool(use_pallas and self._agg is not None)
        self._schema = list(terminal.output_schema)
        specs = []
        for st in self.suffix:
            if isinstance(st, FilterExec):
                specs.append(("filter", st.condition))
            elif isinstance(st, ProjectExec):
                specs.append(("project", tuple(st.exprs),
                              tuple(n for n, _ in st.output_schema)))
            else:
                specs.append(("agg", self._use_pallas,
                              int(pallas_max_cap)) +
                             tuple(tuple(getattr(st, f))
                                   for f in _AGG_FIELDS))
        self._suffix_specs = tuple(specs)
        reorder = not (join.build_side == "right"
                       or join.join_type in (LEFT_SEMI, LEFT_ANTI))
        self._reorder_n = len(join.children[1].output_schema) \
            if reorder else None
        self.donate = bool(donate) and jax.default_backend() != "cpu"
        self._fn_cache = {}
        # bytes an unfused plan would materialize per capacity slot at
        # the join output and every internal suffix boundary
        self._saved_bytes_per_slot = (
            _schema_row_bytes(join.output_schema) +
            sum(_schema_row_bytes(st.output_schema)
                for st in self.suffix[:-1]))
        self._exec_state = None
        join._fusion = self
        FUSION_STATS["chains"] += 1
        FUSION_STATS["stages"] += len(self.suffix) + 1
        FUSION_STATS["joins"] += 1

    @property
    def output_schema(self) -> Schema:
        return self._schema

    @property
    def output_partitioning(self):
        return self.suffix[-1].output_partitioning

    def mesh_chain_root(self) -> TpuExec:
        """Unfused terminal of the join + suffix chain (see
        FusedPipelineExec.mesh_chain_root): the suffix nodes keep their
        child links down to the wrapped join, so lowering the terminal
        suffix stage recovers join and suffix inside one stage trace."""
        return self.suffix[-1]

    def node_description(self) -> str:
        tags = []
        if self._use_pallas:
            tags.append("pallas")
        if self.donate:
            tags.append("donate")
        tag = f" ({', '.join(tags)})" if tags else ""
        return (f"FusedHashJoin[{self.join.node_description()} -> "
                + " -> ".join(type(s).__name__ for s in self.suffix)
                + f"]{tag}")

    def _fused_fn(self, out_cap: int, donate: bool, side):
        key = (out_cap, donate, side.mode, side.table_size)
        fn = self._fn_cache.get(key)
        if fn is None:
            jit_kwargs = {"donate_argnums": (0,)} if donate else {}
            fn = shared_fn_jit(
                _fused_join_builder, self.join.join_type,
                tuple(self.join._probe_key_exprs),
                tuple(self.join._build_key_exprs),
                out_cap, self._reorder_n, self._suffix_specs, side.mode,
                side.table_size, **jit_kwargs)
            self._fn_cache[key] = fn
        return fn

    # --- execute-time hooks the armed join calls back into ---

    def fused_pairs(self, ctx: ExecContext, probe: ColumnarBatch,
                    build: ColumnarBatch, retries: Metric
                    ) -> Iterator[ColumnarBatch]:
        """One probe batch against one build batch through the fused
        program, with per-batch split-and-retry re-entry (the join's
        _join_batches delegates here when armed)."""
        from ..memory.retry import (split_spillable_in_half_by_rows,
                                    with_retry)
        from ..memory.spill import SpillableBatch, SpillPriority
        st = self._exec_state

        def run_one(psb):
            pb = psb.get()
            out = self._run_pair(ctx, pb, build, retries, st)
            psb.close()
            return out

        sb = SpillableBatch(probe, SpillPriority.ACTIVE_ON_DECK)
        for out in with_retry(
                sb, run_one,
                split_policy=split_spillable_in_half_by_rows):
            if out is not None:
                yield out

    def _run_pair(self, ctx: ExecContext, probe: ColumnarBatch,
                  build: ColumnarBatch, retries: Metric, st):
        """The join's ``_join_pair`` with the suffix behind it: the same
        build side, first capacity and overflow contract (all the
        join's), one host read a launch."""
        from ..columnar.vector import choose_capacity
        from ..conf import JOIN_GROWTH_STEPS
        join = self.join
        max_steps = ctx.conf.get(JOIN_GROWTH_STEPS)
        side = join._build_side(ctx, build)
        out_cap = join._pair_capacity(ctx, probe, side)
        measured = False
        total = 0
        for _ in range(max_steps + 1):
            donate = self.donate and measured
            fn = self._fused_fn(out_cap, donate, side)
            with ctx.semaphore, NvtxTimer(st["fuse_time"], "fused-join"):
                if self._agg is not None:
                    out, rows_in, used, total = fn(
                        probe, build, jnp.int64(st["offset"]), *side.aux)
                else:
                    out, total = fn(probe, build, *side.aux)
                    rows_in = out.num_rows
            total, n_in = join._read(ctx, total, rows_in)
            if total <= out_cap:
                join._note_total(side, total)
                join._count_pair(ctx, side, out_cap)
                st["saved"].add(self._saved_bytes_per_slot * out_cap)
                if self._agg is None:
                    return ColumnarBatch(out.columns, out.names, n_in)
                st["offset"] += n_in
                if n_in == 0:
                    # mirror the unfused partial aggregate: no partial
                    # emitted for a pair that filtered down to nothing
                    return None
                if self._use_pallas:
                    st["used"].append(used)
                return out
            if donate:
                # the measured capacity makes a relaunch overflow a
                # kernel contract violation — and the probe is gone
                raise RuntimeError(
                    "fused join under-reported its output size on a "
                    "donated relaunch")
            retries.add(1)
            join._counter(ctx, "joinCapacityRelaunches").add(1)
            out_cap = choose_capacity(total)
            measured = True
        raise RuntimeError(
            f"join expansion {total} exceeded capacity after "
            f"{max_steps} growth steps")

    def suffix_fallback(self, ctx: ExecContext, stream
                        ) -> Iterator[ColumnarBatch]:
        """Empty-build path: the join produced its passthrough /
        null-extend batches eagerly (_empty_result_core), so run the
        suffix through the operators' OWN jitted functions exactly as
        the unfused plan would — same pallas-lane choice, same
        row_offset threading, same empty-batch skips."""
        st = self._exec_state
        grouped_fn = self._agg._grouped_pallas_fn(ctx) \
            if self._use_pallas and self._agg is not None else None
        for batch in stream:
            if int(batch.num_rows) == 0:
                continue
            cur = batch
            emit = True
            for stage in self.suffix:
                if stage is self._agg:
                    n_in = int(cur.num_rows)
                    if n_in == 0:
                        emit = False
                        break
                    with ctx.semaphore:
                        if grouped_fn is not None:
                            cur, used = grouped_fn(
                                cur, jnp.int64(st["offset"]))
                            st["used"].append(used)
                        else:
                            cur = stage._jit_update(
                                cur, jnp.int64(st["offset"]))
                    st["offset"] += n_in
                else:
                    with ctx.semaphore:
                        cur = stage._jit(cur)
            if emit:
                yield cur

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        m = ctx.metrics_for(self.exec_id)
        m.setdefault("fusedOps",
                     Metric("fusedOps", Metric.ESSENTIAL)).set(
            len(self.suffix) + 1)
        self._exec_state = {
            "offset": 0,
            "saved": m.setdefault(
                "fusionBytesSaved",
                Metric("fusionBytesSaved", Metric.ESSENTIAL, "B")),
            "fuse_time": m.setdefault(
                "fusedTime", Metric("fusedTime", Metric.MODERATE, "ns")),
            "used": [],
        }
        try:
            yield from self.children[0].execute(ctx)
        finally:
            st = self._exec_state
            if st is not None:
                count_lane_flags(m, st["used"])
            self._exec_state = None
