"""Window exec: sort-based segmented-scan window computation.

Rebuild of GpuWindowExec.scala (SURVEY §2.4, 2108 LoC). cuDF exposes
rolling/scan window kernels; the TPU formulation sorts the whole input
by (partition keys, order keys) once, derives segment boundaries, and
lowers every window function to vectorized segmented scans / gathers:

  row_number   idx - segment_start + 1
  rank         cummax of order-run starts within the segment
  dense_rank   segmented cumsum of order-run starts
  ntile        closed-form bucket from row_number and partition size
  lead/lag     index-shifted gather masked to the segment
  running agg  segmented associative_scan (sum/min/max/count/avg)
  whole-part.  segment reduce + gather
  sliding ROWS prefix-sum differences (sum/count/avg) or O(w) masked
               min/max for static window widths

Everything runs in ONE jit per (capacity, plan) — there is no per-
function kernel launch loop. Results scatter back to input order, so
the node is order-preserving (stronger than Spark's contract).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtypes as dt
from ..columnar.vector import (Column, ColumnVector, ColumnarBatch,
                               StringColumn, choose_capacity)
from ..expr.aggregates import (AggregateFunction, Average, Count, CountStar,
                               Max, Min, Sum)
from ..expr.core import Expression, make_result
from ..jit_registry import read_int, shared_method_jit
from ..expr.window import (Lag, Lead, DenseRank, NTile, PercentRank, Rank,
                           RowNumber, WindowExpression, WindowFrame)
from ..ops import kernels as K
from .base import ExecContext, Schema, TpuExec


# ---------------------------------------------------------------------------
# segmented primitives (all length-N over the sorted layout)
# ---------------------------------------------------------------------------

def _seg_scan(op, vals, new_seg):
    """Inclusive segmented scan: op-accumulate, restarting where
    new_seg[i] is True (classic segmented-scan monoid lift)."""
    def combine(a, b):
        af, av = a
        bf, bv = b
        return (af | bf, jnp.where(bf, bv, op(av, bv)))
    flags, out = jax.lax.associative_scan(combine, (new_seg, vals))
    return out


def _seg_start_idx(new_seg):
    """For each row, index of its segment's first row (via cummax)."""
    n = new_seg.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    starts = jnp.where(new_seg, idx, 0)
    return jax.lax.associative_scan(jnp.maximum, starts)


def _seg_counts(gid, num_rows, cap):
    """Per-row count of live rows in the row's segment."""
    ones = (jnp.arange(cap) < num_rows).astype(jnp.int64)
    totals = jnp.zeros(cap, jnp.int64).at[gid].add(ones)
    return totals[gid]


def _prev_differs(cols: Sequence[Column]) -> jnp.ndarray:
    """True where row i's keys differ from row i-1 (row 0 = True;
    K._adjacent_equal already yields eq[0] = False)."""
    eq = K._adjacent_equal(cols[0])
    for c in cols[1:]:
        eq = eq & K._adjacent_equal(c)
    return ~eq


def _seg_lower_bound(keys, seg_start, seg_end, query):
    """Per-row first index j in [seg_start_i, seg_end_i] with
    keys[j] >= query_i (vectorized binary search, ~log2(cap) steps)."""
    cap = keys.shape[0]
    lo = seg_start
    hi = seg_end + 1  # exclusive
    steps = max(cap.bit_length(), 1)
    for _ in range(steps):
        mid = (lo + hi) // 2
        ge = jnp.take(keys, jnp.clip(mid, 0, cap - 1)) >= query
        go_left = ge & (lo < hi)
        hi = jnp.where(go_left, mid, hi)
        lo = jnp.where(~ge & (lo < hi), mid + 1, lo)
    return lo


def _seg_upper_bound(keys, seg_start, seg_end, query):
    """Per-row last index j in [seg_start_i, seg_end_i] with
    keys[j] <= query_i (hi_i = lower_bound(> query) - 1)."""
    cap = keys.shape[0]
    lo = seg_start
    hi = seg_end + 1
    steps = max(cap.bit_length(), 1)
    for _ in range(steps):
        mid = (lo + hi) // 2
        gt = jnp.take(keys, jnp.clip(mid, 0, cap - 1)) > query
        go_left = gt & (lo < hi)
        hi = jnp.where(go_left, mid, hi)
        lo = jnp.where(~gt & (lo < hi), mid + 1, lo)
    return lo - 1


def _range_sum(vals, lo_i, hi_i, cap, width_empty):
    """Frame sums via prefix-sum differences, IEEE-safe for floats: a
    +/-inf or NaN anywhere in the partition must only poison frames
    that actually CONTAIN it (a naive cumsum difference yields inf-inf
    = NaN for every frame after the value)."""
    def diff(ps, zero):
        top = ps[jnp.clip(hi_i, 0, cap - 1)]
        bot = jnp.where(lo_i > 0, ps[jnp.clip(lo_i - 1, 0, cap - 1)], zero)
        return top - bot

    if not jnp.issubdtype(vals.dtype, jnp.floating):
        out = diff(jnp.cumsum(vals), jnp.zeros((), vals.dtype))
        return jnp.where(width_empty, 0, out)
    finite = jnp.isfinite(vals)
    base = diff(jnp.cumsum(jnp.where(finite, vals, 0.0)),
                jnp.zeros((), vals.dtype))

    def present(mask):
        return diff(jnp.cumsum(mask.astype(jnp.int32)), 0) > 0
    pos_inf = present(vals == jnp.inf)
    neg_inf = present(vals == -jnp.inf)
    has_nan = present(jnp.isnan(vals))
    out = jnp.where(pos_inf & ~neg_inf, jnp.inf,
                    jnp.where(neg_inf & ~pos_inf, -jnp.inf, base))
    out = jnp.where(has_nan | (pos_inf & neg_inf), jnp.nan, out)
    return jnp.where(width_empty, 0.0, out)


def _range_count(cnt_vals, lo_i, hi_i, cap, width_empty):
    ccnt = jnp.cumsum(cnt_vals)
    top = ccnt[jnp.clip(hi_i, 0, cap - 1)]
    bot = jnp.where(lo_i > 0, ccnt[jnp.clip(lo_i - 1, 0, cap - 1)], 0)
    return jnp.where(width_empty, 0, top - bot)


def _rmq(vals, lo_i, hi_i, cap, op, out_t):
    """Range min/max query via a doubling sparse table: O(cap log cap)
    build, two gathers per query."""
    fill = dt.max_value(out_t) if op is jnp.minimum else dt.min_value(out_t)
    levels = [vals]
    span = 1
    while span < cap:
        prev = levels[-1]
        shifted = jnp.concatenate(
            [prev[span:], jnp.full((span,), fill, prev.dtype)])
        levels.append(op(prev, shifted))
        span *= 2
    table = jnp.stack(levels)                       # (L, cap)
    w = jnp.maximum(hi_i - lo_i + 1, 1)
    kk = jnp.floor(jnp.log2(w.astype(jnp.float64))).astype(jnp.int32)
    pow2 = jnp.left_shift(jnp.int32(1), kk)
    flat = table.reshape(-1)
    a = jnp.take(flat, jnp.clip(kk * cap + lo_i, 0, flat.shape[0] - 1))
    b = jnp.take(flat, jnp.clip(kk * cap + hi_i - pow2 + 1, 0,
                                flat.shape[0] - 1))
    out = op(a, b)
    return jnp.where(hi_i < lo_i, jnp.asarray(fill, vals.dtype), out)


class WindowExec(TpuExec):
    """Computes window columns for expressions sharing one
    (partition_by, order_by) spec; appends them to the child schema."""

    def __init__(self, child: TpuExec,
                 window_exprs: Sequence[Tuple[WindowExpression, str]]):
        super().__init__(child)
        self.window_exprs = list(window_exprs)
        spec = window_exprs[0][0].spec
        self.partition_by = spec.partition_by
        self.order_by = spec.order_fields
        for we, _ in window_exprs[1:]:
            if (repr(we.spec.partition_by) != repr(self.partition_by)
                    or repr(we.spec.order_fields) != repr(self.order_by)):
                raise ValueError(
                    "one WindowExec handles one (partition, order) spec; "
                    "the planner must split differing specs")
        in_schema = child.output_schema
        self._schema = list(in_schema) + [
            (name, we.data_type(in_schema))
            for we, name in self.window_exprs]
        from ..expr.misc import contains_eager
        self._jit = self._compute if contains_eager(
            [we for we, _ in self.window_exprs] + list(self.partition_by)
            + [o.expr for o in self.order_by]) \
            else shared_method_jit(
                self, "_compute",
                ("window_exprs", "partition_by", "order_by", "_schema"))

    @property
    def output_schema(self) -> Schema:
        return self._schema

    # --- the one big kernel ---
    def _compute(self, batch: ColumnarBatch) -> ColumnarBatch:
        cap = batch.capacity
        n = batch.num_rows
        live = batch.live_mask()
        part_cols = [e.eval(batch) for e in self.partition_by]
        order_cols = [o.expr.eval(batch) for o in self.order_by]

        # sort by (partition, order); dead rows sort last
        asc = [True] * len(part_cols) + [o.ascending for o in self.order_by]
        nf = [True] * len(part_cols) + [o.nulls_first for o in self.order_by]
        perm = K.sort_indices(part_cols + order_cols, asc, nf, live)
        sorted_batch = batch.gather(perm, n, unique=True)
        s_part = [c.gather(perm) for c in part_cols]
        s_order = [c.gather(perm) for c in order_cols]

        idx = jnp.arange(cap, dtype=jnp.int32)
        s_live = idx < n
        new_part = _prev_differs(s_part) if s_part else \
            (idx == 0)
        new_part = new_part | (idx == 0)
        gid = jnp.cumsum(new_part.astype(jnp.int32)) - 1
        seg_start = _seg_start_idx(new_part)
        counts = _seg_counts(gid, n, cap)
        new_order = new_part | (_prev_differs(s_order)
                                if s_order else jnp.zeros(cap, jnp.bool_))
        # last row index of each order-key run (RANGE peer semantics):
        # next run's start - 1, via reversed inclusive cummin of starts
        starts_only = jnp.where(new_order, idx, jnp.int32(cap))
        incl_next = jax.lax.associative_scan(
            jnp.minimum, starts_only[::-1])[::-1]
        next_start = jnp.concatenate(
            [incl_next[1:], jnp.full(1, cap, jnp.int32)])
        run_end = jnp.clip(next_start - 1, 0, cap - 1)

        out_cols: List[Column] = []
        for we, _name in self.window_exprs:
            out_cols.append(self._one_function(
                we, sorted_batch, idx, s_live, new_part, new_order, gid,
                seg_start, counts, run_end, cap, n))

        # scatter results back to input order
        inv = jnp.zeros(cap, jnp.int32).at[perm].set(idx)
        restored = [c.gather(inv) for c in out_cols]
        return ColumnarBatch(
            list(batch.columns) + restored,
            [nm for nm, _ in self._schema], n)

    def _one_function(self, we: WindowExpression, sorted_batch, idx,
                      s_live, new_part, new_order, gid, seg_start, counts,
                      run_end, cap, n) -> Column:
        fn = we.func
        live_valid = s_live
        rn = idx - seg_start + 1  # row_number, 1-based

        if isinstance(fn, RowNumber):
            return make_result(rn.astype(jnp.int32), live_valid, dt.INT32)
        if isinstance(fn, (Rank, DenseRank, PercentRank)):
            run_start = jnp.where(new_order, idx, 0)
            rank_idx = jax.lax.associative_scan(jnp.maximum, run_start)
            rank = (rank_idx - seg_start + 1).astype(jnp.int32)
            if isinstance(fn, Rank):
                return make_result(rank, live_valid, dt.INT32)
            if isinstance(fn, DenseRank):
                dr = _seg_scan(jnp.add, new_order.astype(jnp.int32),
                               new_part).astype(jnp.int32)
                return make_result(dr, live_valid, dt.INT32)
            denom = jnp.maximum(counts - 1, 1).astype(jnp.float64)
            pr = jnp.where(counts > 1, (rank - 1).astype(jnp.float64)
                           / denom, 0.0)
            return make_result(pr, live_valid, dt.FLOAT64)
        if isinstance(fn, NTile):
            nt = jnp.int64(fn.n)
            cnt = counts
            q = cnt // nt
            r = cnt % nt
            i0 = (rn - 1).astype(jnp.int64)
            big_span = r * (q + 1)
            in_big = i0 < big_span
            bucket = jnp.where(
                in_big, i0 // jnp.maximum(q + 1, 1),
                r + jnp.where(q > 0, (i0 - big_span) // jnp.maximum(q, 1),
                              i0 - big_span))
            return make_result((bucket + 1).astype(jnp.int32), live_valid,
                               dt.INT32)
        if isinstance(fn, (Lead, Lag)):
            col = fn.children[0].eval(sorted_batch)
            k = fn.offset if isinstance(fn, Lead) and not isinstance(fn, Lag) \
                else -fn.offset
            target = idx + k
            seg_end = seg_start + counts.astype(jnp.int32) - 1
            in_seg = (target >= seg_start) & (target <= seg_end) & \
                (target >= 0) & (target < cap)
            got = col.gather(jnp.clip(target, 0, cap - 1))
            if fn.default is not None:
                from ..expr.core import Literal
                d = Literal(fn.default).eval(sorted_batch)
                if isinstance(got, StringColumn):
                    from ..expr.conditional import _select_strings
                    out = _select_strings(in_seg, got, d)
                    return out.with_validity(
                        jnp.where(in_seg, got.validity, d.validity) & s_live)
                data = jnp.where(in_seg, got.data, d.data.astype(got.data.dtype))
                valid = jnp.where(in_seg, got.validity, d.validity) & s_live
                return make_result(data, valid, got.dtype)
            valid = got.validity & in_seg & s_live
            if isinstance(got, StringColumn):
                return got.with_validity(valid)
            return make_result(got.data, valid, got.dtype)
        if isinstance(fn, AggregateFunction):
            return self._window_aggregate(fn, we.spec.frame, sorted_batch,
                                          idx, s_live, new_part, gid,
                                          seg_start, counts, run_end, cap,
                                          spec=we.spec)
        raise NotImplementedError(type(fn).__name__)

    def _window_aggregate(self, fn: AggregateFunction, frame: WindowFrame,
                          sorted_batch, idx, s_live, new_part, gid,
                          seg_start, counts, run_end, cap,
                          spec=None) -> Column:
        in_schema = sorted_batch.schema()
        if isinstance(fn, CountStar):
            vals = s_live.astype(jnp.int64)
            valid_in = s_live
            out_t = dt.INT64
        else:
            col = fn.children[0].eval(sorted_batch)
            out_t = fn.data_type(in_schema)
            valid_in = col.validity
            if isinstance(fn, (Sum, Average, Count)):
                phys = jnp.float64 if isinstance(fn, Average) or \
                    (isinstance(fn, Sum) and out_t == dt.FLOAT64) else \
                    out_t.physical
                vals = col.data.astype(jnp.float64
                                       if isinstance(fn, Average)
                                       else phys)
                if isinstance(col.dtype, dt.DecimalType) and \
                        isinstance(fn, Average):
                    vals = vals / (10.0 ** col.dtype.scale)
            else:
                vals = col.data

        cnt_vals = valid_in.astype(jnp.int64)
        if isinstance(fn, Count) or isinstance(fn, CountStar):
            agg_vals = cnt_vals
            op = jnp.add
            zero_for_null = 0
        elif isinstance(fn, Sum) or isinstance(fn, Average):
            agg_vals = jnp.where(valid_in, vals, 0)
            op = jnp.add
            zero_for_null = 0
        elif isinstance(fn, Min):
            fill = dt.max_value(out_t)
            agg_vals = jnp.where(valid_in, vals,
                                 jnp.asarray(fill, vals.dtype))
            op = jnp.minimum
        elif isinstance(fn, Max):
            fill = dt.min_value(out_t)
            agg_vals = jnp.where(valid_in, vals,
                                 jnp.asarray(fill, vals.dtype))
            op = jnp.maximum
        else:
            raise NotImplementedError(
                f"window aggregate {type(fn).__name__}")

        if frame.is_unbounded:
            if op is jnp.add:
                total = jnp.zeros(cap, agg_vals.dtype).at[gid].add(agg_vals)
            elif op is jnp.minimum:
                total = jnp.full(cap, jnp.asarray(
                    dt.max_value(out_t), agg_vals.dtype)).at[gid].min(agg_vals)
            else:
                total = jnp.full(cap, jnp.asarray(
                    dt.min_value(out_t), agg_vals.dtype)).at[gid].max(agg_vals)
            acc = total[gid]
            ncnt = jnp.zeros(cap, jnp.int64).at[gid].add(cnt_vals)[gid]
        elif frame.is_running:
            acc = _seg_scan(op, agg_vals, new_part)
            ncnt = _seg_scan(jnp.add, cnt_vals, new_part)
            if not frame.row_based:
                # RANGE running: all peers of the current order key share
                # the value at their run's LAST row (SQL peer semantics)
                acc = jnp.take(acc, run_end)
                ncnt = jnp.take(ncnt, run_end)
        elif frame.row_based:
            return self._sliding(fn, frame, agg_vals, cnt_vals, idx,
                                 seg_start, counts, cap, out_t, op, s_live)
        else:
            return self._range_sliding(fn, frame, spec, sorted_batch,
                                       agg_vals, cnt_vals, seg_start,
                                       counts, cap, out_t, op, s_live)

        return self._finalize_agg(fn, acc, ncnt, s_live, out_t)

    def _range_sliding(self, fn, frame, spec, sorted_batch, agg_vals,
                       cnt_vals, seg_start, counts, cap, out_t, op,
                       s_live):
        """RANGE BETWEEN x PRECEDING AND y FOLLOWING with value offsets
        (GpuWindowExec bounded-range frames): per-row frame bounds are
        binary searches over the partition-sorted order key; add-monoids
        then use prefix-sum differences and min/max a doubling sparse
        table (O(log n) RMQ — the two-kernel trick cuDF's range windows
        use becomes searchsorted + gather here)."""
        of = spec.order_fields[0]
        key_col = of.expr.eval(sorted_batch)
        k = key_col.data.astype(jnp.float64)
        if isinstance(key_col.dtype, dt.DecimalType):
            # decimal lanes are scaled ints; frame offsets are logical
            # values — scale them to the same fixed-point basis
            factor = float(10 ** key_col.dtype.scale)
            frame = WindowFrame(
                None if frame.lo is None else frame.lo * factor,
                None if frame.hi is None else frame.hi * factor,
                row_based=False)
        if not of.ascending:
            k = -k
        # null order keys are their own peer group at the sort's null
        # end: map them to +/-inf so their frames cover exactly the run
        null_end = jnp.where(of.nulls_first, -jnp.inf, jnp.inf)
        k = jnp.where(key_col.validity, k, null_end)
        seg_end = seg_start + counts.astype(jnp.int32) - 1
        lo_val = k + frame.lo if frame.lo is not None else None
        hi_val = k + frame.hi if frame.hi is not None else None
        lo_i = seg_start if lo_val is None else _seg_lower_bound(
            k, seg_start, seg_end, lo_val)
        hi_i = seg_end if hi_val is None else _seg_upper_bound(
            k, seg_start, seg_end, hi_val)
        width_empty = hi_i < lo_i
        if op is jnp.add:
            acc = _range_sum(agg_vals, lo_i, hi_i, cap, width_empty)
        else:
            acc = _rmq(agg_vals, lo_i, hi_i, cap, op, out_t)
        ncnt = _range_count(cnt_vals, lo_i, hi_i, cap, width_empty)
        return self._finalize_agg(fn, acc, ncnt, s_live, out_t)

    def _sliding(self, fn, frame, agg_vals, cnt_vals, idx, seg_start,
                 counts, cap, out_t, op, s_live):
        """ROWS BETWEEN a AND b with integer bounds: prefix-sum
        differences for add-monoids, O(width) masked scan otherwise."""
        lo = frame.lo
        hi = frame.hi
        seg_end = seg_start + counts.astype(jnp.int32) - 1
        lo_i = seg_start if lo is None else \
            jnp.maximum(idx + lo, seg_start)
        hi_i = seg_end if hi is None else \
            jnp.minimum(idx + hi, seg_end)
        width_empty = hi_i < lo_i
        if op is jnp.add:
            acc = _range_sum(agg_vals, lo_i, hi_i, cap, width_empty)
            ncnt = _range_count(cnt_vals, lo_i, hi_i, cap, width_empty)
        else:
            if lo is None or hi is None:
                raise NotImplementedError(
                    "min/max sliding frames need bounded ROWS offsets")
            width = hi - lo + 1
            acc = jnp.take(agg_vals, jnp.clip(lo_i, 0, cap - 1))
            ncnt = jnp.zeros(cap, jnp.int64)
            for off in range(width):
                j = lo_i + off
                ok = (j <= hi_i)
                v = jnp.take(agg_vals, jnp.clip(j, 0, cap - 1))
                acc = jnp.where(ok, op(acc, v), acc)
                ncnt = ncnt + jnp.where(
                    ok, jnp.take(cnt_vals, jnp.clip(j, 0, cap - 1)), 0)
        return self._finalize_agg(fn, acc, ncnt, s_live, out_t)

    def _finalize_agg(self, fn, acc, ncnt, s_live, out_t) -> ColumnVector:
        if isinstance(fn, (Count, CountStar)):
            return make_result(acc.astype(jnp.int64), s_live, dt.INT64)
        has_vals = ncnt > 0
        if isinstance(fn, Average):
            out = acc / jnp.where(has_vals, ncnt, 1).astype(jnp.float64)
            return make_result(out, has_vals & s_live, dt.FLOAT64)
        if isinstance(fn, Sum):
            phys = out_t.physical
            return make_result(acc.astype(phys), has_vals & s_live, out_t)
        return make_result(acc, has_vals & s_live, out_t)

    def required_child_distributions(self):
        """Partitioned windows cluster by the partition keys
        (GpuWindowExec requiredChildDistribution): each reduce
        partition holds whole window partitions, so the exec
        materializes one PARTITION at a time instead of the whole
        input — and the same clustering is what the mesh lowering
        rides."""
        from ..plan.distribution import (ClusteredDistribution,
                                         UnspecifiedDistribution)
        if self.partition_by:
            return [ClusteredDistribution(self.partition_by)]
        return [UnspecifiedDistribution()]

    @property
    def output_partitioning(self):
        return self.children[0].output_partitioning

    # --- streaming shell: one materialization per child partition ---
    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        for part in self.children[0].execute_partitioned(ctx):
            yield from self._window_partition(ctx, part)

    def _window_partition(self, ctx: ExecContext,
                          stream) -> Iterator[ColumnarBatch]:
        from ..memory.retry import with_retry_no_split
        from ..memory.spill import SpillableBatch, SpillPriority
        runs: List[SpillableBatch] = []
        total = 0
        try:
            for b in stream:
                if read_int(b.num_rows) == 0:
                    continue
                total += read_int(b.num_rows)
                runs.append(with_retry_no_split(
                    lambda x=b: SpillableBatch(
                        x, SpillPriority.ACTIVE_ON_DECK)))
            if not runs:
                return
            cap = choose_capacity(total)

            def compute():
                batches = [sb.get() for sb in runs]
                with ctx.semaphore:
                    merged = (batches[0] if len(batches) == 1
                              else K.concat_batches(batches, cap))
                    return self._jit(merged)
            # RetryOOM: spill + re-run (pure over the held spillables)
            yield with_retry_no_split(compute)
        finally:
            for sb in runs:
                sb.close()

    def node_description(self) -> str:
        fns = ", ".join(type(we.func).__name__
                        for we, _ in self.window_exprs)
        return f"Window[{fns}]"


# ---------------------------------------------------------------------------
# batched running windows (GpuRunningWindowExec + BatchedRunningWindowFixer,
# GpuWindowExec.scala:236-292)
# ---------------------------------------------------------------------------

def running_compatible(window_exprs, in_schema) -> bool:
    """True when every expression can stream batch-at-a-time over a
    (partition, order)-sorted child with carried state: rank family, or
    ROWS running (unbounded-preceding..current-row) sum/min/max/count/
    avg over plain numeric inputs. RANGE running is excluded — its peer
    rows share the value at the run's LAST row, which can live in the
    next batch (needs lookahead); decimal inputs carry two-limb states
    the scalar fixer cannot hold."""
    for we, _name in window_exprs:
        fn = we.func
        if isinstance(fn, (RowNumber, Rank, DenseRank)):
            continue
        frame = we.spec.frame
        if isinstance(fn, (Sum, Count, CountStar, Min, Max, Average)) \
                and frame is not None and frame.is_running \
                and frame.row_based:
            if fn.children:
                t = fn.children[0].data_type(in_schema)
                if isinstance(t, dt.DecimalType) or t == dt.STRING \
                        or t.is_nested:
                    return False
            continue
        return False
    return True


class BatchedRunningWindowExec(TpuExec):
    """Running-frame windows over an already (partition, order)-sorted
    stream in O(batch) memory: each batch computes its within-batch
    segmented scans, then the FIRST partition-run is fixed up with
    state carried from the previous batch (rank/row-number bases,
    running accumulator and count), exactly the reference's
    BatchedRunningWindowFixer contract. The planner places a SortExec
    below; output rows stream in sorted order (Spark's window makes no
    ordering promise, and this matches the reference's running path)."""

    def __init__(self, child: TpuExec,
                 window_exprs: Sequence[Tuple[WindowExpression, str]]):
        super().__init__(child)
        self.window_exprs = list(window_exprs)
        spec = window_exprs[0][0].spec
        self.partition_by = spec.partition_by
        self.order_by = spec.order_fields
        in_schema = child.output_schema
        self._schema = list(in_schema) + [
            (name, we.data_type(in_schema))
            for we, name in self.window_exprs]
        self._in_schema = in_schema
        from ..expr.misc import contains_eager
        self._jit = self._compute if contains_eager(
            [we for we, _ in self.window_exprs] + list(self.partition_by)
            + [o.expr for o in self.order_by]) \
            else shared_method_jit(
                self, "_compute",
                ("window_exprs", "partition_by", "order_by", "_schema",
                 "_in_schema"))

    @property
    def output_schema(self) -> Schema:
        return self._schema

    # --- carried state -----------------------------------------------
    def _agg_acc_dtype(self, fn):
        if isinstance(fn, (Count, CountStar)):
            return jnp.int64
        if isinstance(fn, Average):
            return jnp.float64
        t = fn.data_type(self._in_schema)
        return t.physical

    def _zero_state(self):
        """Structure-stable pytree: 1-row tail key columns + per-fn
        scalars. has_tail gates every fixup."""
        def zero_col(e):
            t = e.data_type(self._in_schema)
            if t == dt.STRING:
                return StringColumn(jnp.zeros(2, jnp.int32),
                                    jnp.zeros(8, jnp.uint8),
                                    jnp.zeros(1, jnp.bool_), pad_bucket=8)
            return ColumnVector(jnp.zeros(1, t.physical),
                                jnp.zeros(1, jnp.bool_), t)
        fns = []
        for we, _ in self.window_exprs:
            fn = we.func
            fns.append({
                "acc": jnp.zeros((), self._agg_acc_dtype(fn))
                if isinstance(fn, (Sum, Count, CountStar, Min, Max,
                                   Average)) else jnp.zeros((), jnp.int64),
                "cnt": jnp.zeros((), jnp.int64),
                "rank": jnp.zeros((), jnp.int64),
                "dense": jnp.zeros((), jnp.int64),
            })
        return {
            "has_tail": jnp.zeros((), jnp.bool_),
            "rows": jnp.zeros((), jnp.int64),  # rows so far in partition
            "tail_part": [zero_col(e) for e in self.partition_by],
            "tail_order": [zero_col(o.expr) for o in self.order_by],
            "fns": fns,
        }

    # --- the per-batch kernel ----------------------------------------
    def _compute(self, batch: ColumnarBatch, state):
        cap = batch.capacity
        n = batch.num_rows
        idx = jnp.arange(cap, dtype=jnp.int32)
        s_live = idx < n
        part_cols = [e.eval(batch) for e in self.partition_by]
        order_cols = [o.expr.eval(batch) for o in self.order_by]

        new_part = (_prev_differs(part_cols) if part_cols
                    else jnp.zeros(cap, jnp.bool_)) | (idx == 0)
        gid = jnp.cumsum(new_part.astype(jnp.int32)) - 1
        seg_start = _seg_start_idx(new_part)
        new_order = new_part | (_prev_differs(order_cols)
                                if order_cols else jnp.zeros(cap, jnp.bool_))
        run_start = jax.lax.associative_scan(
            jnp.maximum, jnp.where(new_order, idx, 0))
        rn = (idx - seg_start + 1).astype(jnp.int64)

        zero_i = jnp.zeros(1, jnp.int32)
        def row0_equal(cols, tails):
            if not cols:
                return jnp.ones((), jnp.bool_)
            # grouping equality: a NULL partition key continues the
            # NULL partition (join-style null!=null broke carried
            # state exactly for null keys)
            return K._keys_equal(cols, zero_i, tails, zero_i,
                                 null_safe=True)[0]
        cont = state["has_tail"] & (n > 0) & \
            row0_equal(part_cols, state["tail_part"])
        cont_order = cont & row0_equal(order_cols, state["tail_order"])
        in_seg0 = (gid == 0) & s_live
        prev_rows = jnp.where(cont, state["rows"], 0)

        out_cols: List[Column] = []
        new_fns = []
        last = jnp.clip(n - 1, 0, cap - 1)

        for (we, _name), fst in zip(self.window_exprs, state["fns"]):
            fn = we.func
            if isinstance(fn, RowNumber):
                out = jnp.where(in_seg0, rn + prev_rows, rn)
                out_cols.append(make_result(out.astype(jnp.int32),
                                            s_live, dt.INT32))
                nf = dict(fst)
                new_fns.append(nf)
                continue
            if isinstance(fn, (Rank, DenseRank)):
                if isinstance(fn, Rank):
                    rank = (run_start - seg_start + 1).astype(jnp.int64)
                    # rows continuing the tail's ORDER run keep its rank;
                    # later runs of the continued partition shift by the
                    # carried partition row count
                    in_first_run = run_start == 0
                    fixed = jnp.where(in_first_run & cont_order,
                                      fst["rank"], rank + prev_rows)
                    out = jnp.where(in_seg0 & cont, fixed, rank)
                    out_cols.append(make_result(out.astype(jnp.int32),
                                                s_live, dt.INT32))
                    nf = dict(fst)
                    nf["rank"] = jnp.take(out, last)
                    new_fns.append(nf)
                else:
                    dr = _seg_scan(jnp.add, new_order.astype(jnp.int64),
                                   new_part)
                    fixed = dr + fst["dense"] - \
                        jnp.where(cont_order, 1, 0)
                    out = jnp.where(in_seg0 & cont, fixed, dr)
                    out_cols.append(make_result(out.astype(jnp.int32),
                                                s_live, dt.INT32))
                    nf = dict(fst)
                    nf["dense"] = jnp.take(out, last)
                    new_fns.append(nf)
                continue
            # running aggregates
            out_t = fn.data_type(self._in_schema) \
                if not isinstance(fn, CountStar) else dt.INT64
            if isinstance(fn, CountStar):
                valid_in = s_live
                vals = s_live.astype(jnp.int64)
            else:
                col = fn.children[0].eval(batch)
                valid_in = col.validity
                vals = col.data
            acc_t = self._agg_acc_dtype(fn)
            cnt_vals = (valid_in & s_live).astype(jnp.int64)
            if isinstance(fn, (Count, CountStar)):
                agg_vals = cnt_vals
                op = jnp.add
            elif isinstance(fn, Min):
                op = jnp.minimum
                fill = dt.max_value(out_t)
                agg_vals = jnp.where(valid_in & s_live, vals.astype(acc_t),
                                     jnp.asarray(fill, acc_t))
            elif isinstance(fn, Max):
                op = jnp.maximum
                fill = dt.min_value(out_t)
                agg_vals = jnp.where(valid_in & s_live, vals.astype(acc_t),
                                     jnp.asarray(fill, acc_t))
            else:  # Sum / Average
                op = jnp.add
                agg_vals = jnp.where(valid_in & s_live,
                                     vals.astype(acc_t),
                                     jnp.zeros((), acc_t))
            acc = _seg_scan(op, agg_vals, new_part)
            ncnt = _seg_scan(jnp.add, cnt_vals, new_part)
            prev_acc = fst["acc"]
            prev_cnt = jnp.where(cont, fst["cnt"], 0)
            if op is jnp.add:
                fix = acc + jnp.where(cont, prev_acc,
                                      jnp.zeros((), acc.dtype))
            else:
                fix = jnp.where(cont & (prev_cnt > 0),
                                op(acc, prev_acc), acc)
            acc = jnp.where(in_seg0, fix, acc)
            ncnt = jnp.where(in_seg0, ncnt + prev_cnt, ncnt)
            has_vals = ncnt > 0
            if isinstance(fn, (Count, CountStar)):
                out_cols.append(make_result(acc.astype(jnp.int64),
                                            s_live, dt.INT64))
            elif isinstance(fn, Average):
                avg = acc / jnp.where(has_vals, ncnt, 1).astype(jnp.float64)
                out_cols.append(make_result(avg, has_vals & s_live,
                                            dt.FLOAT64))
            else:
                out_cols.append(make_result(acc.astype(out_t.physical),
                                            has_vals & s_live, out_t))
            nf = dict(fst)
            nf["acc"] = jnp.take(acc, last).astype(acc_t)
            nf["cnt"] = jnp.take(ncnt, last)
            new_fns.append(nf)

        # carried tail = last live row's keys + its row_number
        one_valid = jnp.asarray([True])
        last_arr = jnp.asarray([0], jnp.int32) + last
        new_state = {
            "has_tail": state["has_tail"] | (n > 0),
            "rows": jnp.where(
                n > 0,
                jnp.take(jnp.where(in_seg0, rn + prev_rows, rn), last),
                state["rows"]),
            "tail_part": [c.gather(last_arr, one_valid)
                          for c in part_cols],
            "tail_order": [c.gather(last_arr, one_valid)
                           for c in order_cols],
            "fns": new_fns,
        }
        out = ColumnarBatch(list(batch.columns) + out_cols,
                            [nm for nm, _ in self._schema], n)
        return out, new_state

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        state = self._zero_state()
        for batch in self.children[0].execute(ctx):
            if read_int(batch.num_rows) == 0:
                continue
            with ctx.semaphore:
                out, state = self._jit(batch, state)
            yield out

    def node_description(self) -> str:
        fns = ", ".join(type(we.func).__name__
                        for we, _ in self.window_exprs)
        return f"BatchedRunningWindow[{fns}]"
