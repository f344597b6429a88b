"""Fused filter+aggregate lowering onto the pallas tile_reduce kernel.

A global (no grouping keys) HashAggregateExec whose aggregates are
simple numeric expressions executes here as ONE device program per
input batch, and a FilterExec child is absorbed into that program, so
no filtered intermediate batch is ever materialized. The predicate
goes, whole, to one side of the kernel boundary:

- kernel-safe (``pred_safe``): predicate, projections and partial
  reduction all evaluate in VMEM, each input column crosses HBM once;
- anything else jit can trace (a FLOAT64 comparison on the chip,
  Divide, numeric IN, Least/Greatest anywhere): XLA evaluates it in
  front of the kernel with the engine's ordinary expression code, at
  the columns' own types, and the result rides into the kernel as its
  live mask (``mask_pred``). The same rows pass as through FilterExec,
  without its compaction gather; the kernel reads only the columns the
  aggregates reference.

This is the TPU counterpart of the reference's fused cuDF reduction
path for q6-shaped queries (GpuAggregateExec.scala AggHelper update
pass over a filtered iterator).

Numerics: on TPU the kernel computes in float32 (float64 inputs and
float64 literals are demoted before tracing — Mosaic has no f64), with
per-tile partials combined in emulated float64 outside the kernel; on
CPU (pallas interpret mode, used by the test lane) everything stays
float64, so differential tests check the exact Spark semantics. The
float32 tile arithmetic on TPU is the same class of deviation the
reference ships behind spark.rapids.sql.variableFloatAgg.enabled.

The gate is static and conservative: unsupported aggregate/expression
shapes keep the stock XLA path, and that is a plan decision
(``pallas_eligible`` / ``grouped_eligible`` / the conf switches, shown
in the plan's node description). Once a lane is chosen it either runs
or raises: a Mosaic refusal on the chip is an error, never a quiet
switch of lanes. tests/test_tpu_compile.py asks the chip's compiler for
both kernels at the main path's shapes.
"""

from __future__ import annotations

import copy
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import dtypes as dt
from ..columnar.vector import ColumnVector, ColumnarBatch
from ..expr import aggregates as Agg
from ..expr import arithmetic as A
from ..expr import core as E
from ..expr import predicates as Pr
from ..expr.cast import Cast
from ..jit_registry import read_back
from ..ops import pallas_kernels as PK

_SAFE_NODES = (
    E.ColumnRef, E.Literal, E.Alias, Cast,
    A.Add, A.Subtract, A.Multiply, A.UnaryMinus,
    A.UnaryPositive, A.Abs,
    Pr.EqualTo, Pr.LessThan, Pr.GreaterThan, Pr.LessThanOrEqual,
    Pr.GreaterThanOrEqual, Pr.EqualNullSafe, Pr.And, Pr.Or, Pr.Not,
    Pr.IsNull, Pr.IsNotNull, Pr.IsNaN,
)
# Not here, because the chip's compiler refuses them in a kernel body
# (each was asked, tests/test_tpu_compile.py style): Divide (Spark's
# result is always double and Mosaic has no f64), numeric InSet
# (captures an array constant; string IN is rewritten to
# _PaddedStrPred first), Least/Greatest (a NaN mask of a splat literal
# is laid out replicated and the row masks cannot join it).
_SAFE_DTYPES = (dt.BOOL, dt.INT8, dt.INT16, dt.INT32, dt.DATE,
                dt.FLOAT32, dt.FLOAT64)
_FLOATY = (dt.FLOAT32, dt.FLOAT64)
# min/max must be exact in a float32 lane on TPU: floats are closed
# under min/max, DATE/INT16/INT8 values are < 2^24
_MINMAX_DTYPES = (dt.FLOAT32, dt.FLOAT64, dt.DATE, dt.INT8, dt.INT16)


class _PaddedStrPred(E.Expression):
    """Kernel-side string predicate over the padded byte-lane view —
    the string-predicate kernel family (reference: cuDF string
    comparison kernels feeding filtered reductions). The referenced
    column's W byte planes (W, rows/128, 128) + lengths + validity ride
    the kernel batch's ``str_lanes``; comparison is pure VPU byte
    arithmetic in VMEM, so dim-filter predicates like cd_gender='M'
    fuse into the single-pass reduction."""

    def __init__(self, name: str, choices: Sequence[bytes],
                 prefix: bool = False):
        super().__init__()
        self.name = name
        self.choices = [bytes(c) for c in choices]
        self.prefix = prefix

    def data_type(self, schema) -> dt.DType:
        return dt.BOOL

    def references(self) -> set:
        return {self.name}

    def eval(self, batch) -> ColumnVector:
        chars, lens, valid = batch.str_lanes[self.name]
        w = chars.shape[0]
        hit = jnp.zeros(lens.shape, jnp.bool_)
        for lit in self.choices:
            m = len(lit)
            if m > w:
                continue  # longer than any string in this batch
            eq = jnp.ones(lens.shape, jnp.bool_)
            for j in range(m):  # m is tiny (literal length)
                # python-int scalars: array constants can't be
                # captured inside a pallas kernel trace
                eq = eq & (chars[j].astype(jnp.int32) == lit[j])
            if self.prefix:
                eq = eq & (lens >= m)
            else:
                eq = eq & (lens == m)
            hit = hit | eq
        return ColumnVector(hit, valid, dt.BOOL)

    def __repr__(self):
        op = "startswith" if self.prefix else "in"
        return f"{self.name} {op} {self.choices!r}"


class _PaddedStrNull(E.Expression):
    """IS [NOT] NULL over a kernel-batch string column."""

    def __init__(self, name: str, negated: bool):
        super().__init__()
        self.name = name
        self.negated = negated

    def data_type(self, schema) -> dt.DType:
        return dt.BOOL

    def references(self) -> set:
        return {self.name}

    def eval(self, batch) -> ColumnVector:
        _, _, valid = batch.str_lanes[self.name]
        data = valid if self.negated else ~valid
        return ColumnVector(data, jnp.ones_like(valid), dt.BOOL)


def _rewrite_string_preds(pred: E.Expression, schema):
    """Replace eligible string predicate subtrees (col = 'lit',
    col IN ('a','b'), startswith, IS [NOT] NULL) with kernel-lane
    nodes; returns (rewritten, {string column names}) — or (pred,
    set()) unchanged when nothing matched."""
    from ..expr import strings as S
    schema_d = dict(schema)
    found: set = set()

    def is_str_ref(e):
        return isinstance(e, E.ColumnRef) and \
            schema_d.get(e.name) == dt.STRING

    def rw(e: E.Expression):
        if isinstance(e, Pr.EqualTo):
            l, r = e.children
            if is_str_ref(l) and isinstance(r, E.Literal) and \
                    isinstance(r.value, str):
                found.add(l.name)
                return _PaddedStrPred(l.name, [r.value.encode()])
            if is_str_ref(r) and isinstance(l, E.Literal) and \
                    isinstance(l.value, str):
                found.add(r.name)
                return _PaddedStrPred(r.name, [l.value.encode()])
        if isinstance(e, Pr.InSet) and is_str_ref(e.children[0]) and \
                all(isinstance(v, str) for v in e.values):
            found.add(e.children[0].name)
            return _PaddedStrPred(e.children[0].name,
                                  [v.encode() for v in e.values])
        if isinstance(e, S.StartsWith) and is_str_ref(e.children[0]):
            found.add(e.children[0].name)
            return _PaddedStrPred(e.children[0].name,
                                  [e.prefix.encode()], prefix=True)
        if isinstance(e, (Pr.IsNull, Pr.IsNotNull)) and \
                is_str_ref(e.children[0]):
            found.add(e.children[0].name)
            return _PaddedStrNull(e.children[0].name,
                                  isinstance(e, Pr.IsNotNull))
        if not e.children:
            return e
        out = copy.copy(e)
        out.children = [rw(c) for c in e.children]
        return out

    return rw(pred), found


def _expr_safe(expr: E.Expression, schema, no_f64: bool = False) -> bool:
    """``schema`` is the Schema list ([(name, dtype)]) data_type wants.
    ``no_f64`` additionally rejects any float64-typed subexpression —
    used for TPU filter predicates, where demoting to float32 would
    change which ROWS pass (not just low-order sum bits, the only
    deviation srt.sql.pallas.enabled's contract covers)."""
    if isinstance(expr, (_PaddedStrPred, _PaddedStrNull)):
        return True  # pure byte-lane VPU arithmetic, exact
    if not isinstance(expr, _SAFE_NODES):
        return False
    if isinstance(expr, E.Literal) and expr.value is None:
        return False
    try:
        t = expr.data_type(schema)
        if t not in _SAFE_DTYPES or (no_f64 and t == dt.FLOAT64):
            return False
    except Exception:
        return False
    return all(_expr_safe(c, schema, no_f64) for c in expr.children)


class _KernelLit(E.Literal):
    """A literal inside the kernel: the splat alone, valid on live rows.
    ``Literal.eval`` also zeroes dead lanes with a select over two
    constants, which Mosaic lays out replicated and then cannot relayout
    the live mask into; every kernel consumer masks by validity anyway."""

    def eval(self, batch) -> ColumnVector:
        return ColumnVector(
            jnp.full(batch.capacity, self.physical_value(),
                     self.dtype.physical),
            batch.live_mask(), self.dtype)


def _kernel_expr(expr: E.Expression, demote_f64: bool) -> E.Expression:
    """Rewrite an expression tree for tracing inside the kernel:
    literals become :class:`_KernelLit`, and with ``demote_f64`` (the
    chip: Mosaic has no f64) float64 literals and casts become float32.
    Column data itself is cast outside the kernel; this fixes the
    literals/casts inside the tree so no f64 op is ever traced."""
    if isinstance(expr, E.Literal):
        if demote_f64 and expr.dtype == dt.FLOAT64:
            return _KernelLit(float(np.float32(expr.value)), dt.FLOAT32)
        return _KernelLit(expr.value, expr.dtype)
    if demote_f64 and isinstance(expr, Cast) and expr.to == dt.FLOAT64:
        return Cast(_kernel_expr(expr.children[0], True), dt.FLOAT32,
                    expr.ansi)
    kids = [_kernel_expr(c, demote_f64) for c in expr.children]
    if all(a is b for a, b in zip(kids, expr.children)):
        return expr
    clone = copy.copy(expr)
    clone.children = kids
    return clone


def _collect_refs(exprs, names: set) -> None:
    for e in exprs:
        if isinstance(e, E.ColumnRef):
            names.add(e.name)
        _collect_refs(e.children, names)


class _KernelBatch(ColumnarBatch):
    """Shim batch for tracing expressions inside the kernel: live_mask
    comes from a block input instead of an iota (Mosaic-unfriendly), and
    ``capacity`` is the (rows/128, 128) row-block SHAPE, so literals
    (``jnp.full(capacity, v)``) materialize in the tile's layout."""

    def __init__(self, columns, names, live):
        super().__init__(columns, names, live.size)
        self._live = live

    @property
    def capacity(self):
        return self._live.shape

    def live_mask(self):
        return self._live


class PallasAggPlan:
    """Static lowering of (pred, agg_exprs) onto tile_reduce outputs.
    ``pred`` is traced inside the kernel; ``mask_pred`` by XLA in front
    of it, where its result replaces the batch's live mask."""

    def __init__(self, agg_exprs, input_schema, pred: Optional[E.Expression],
                 mask_pred: Optional[E.Expression] = None):
        assert pred is None or mask_pred is None
        self.input_schema = input_schema
        self.mask_pred = mask_pred
        schema = list(input_schema)
        self.str_names: List[str] = []
        if pred is not None:
            pred, snames = _rewrite_string_preds(pred, schema)
            self.str_names = sorted(snames)
        self.pred = pred
        demote = PK.on_tpu()
        self._prep = lambda e: _kernel_expr(e, demote)
        self.kinds: List[str] = []
        # per agg: list of (state_name, slot_index, state_dtype)
        self.agg_slots: List[List[Tuple[str, int, dt.DType]]] = []
        self._builders: List[Callable] = []
        refs: set = set()
        if pred is not None:
            _collect_refs([pred], refs)
            refs -= set(self.str_names)  # ride str_lanes, not columns
        for fn, _name in agg_exprs:
            in_t = (fn.children[0].data_type(schema)
                    if fn.children else None)
            slots = []
            if isinstance(fn, (Agg.Sum, Agg.Average)):
                slots.append(("sum", self._slot(PK.SUM), dt.FLOAT64))
                slots.append(("count", self._slot(PK.SUM), dt.INT64))
                self._builders.append(self._masked_sum(fn))
            elif isinstance(fn, Agg.CountStar):
                slots.append(("count", self._slot(PK.SUM), dt.INT64))
                self._builders.append(self._count_star())
            elif isinstance(fn, Agg.Count):
                slots.append(("count", self._slot(PK.SUM), dt.INT64))
                self._builders.append(self._count(fn))
            elif isinstance(fn, (Agg.Min, Agg.Max)):
                kind = PK.MAX if fn.largest else PK.MIN
                slots.append((fn._key, self._slot(kind), in_t))
                slots.append(("seen", self._slot(PK.SUM), dt.BOOL))
                is_float = in_t in (dt.FLOAT32, dt.FLOAT64)
                if is_float:
                    # Spark float order puts NaN GREATEST: the kernel
                    # reduces non-NaN lanes only and this count
                    # restores NaN afterwards (any-NaN => max is NaN;
                    # all-NaN => min is NaN) — mirrors
                    # _MinMaxBase._float_reduce
                    slots.append(("_nan", self._slot(PK.SUM),
                                  dt.FLOAT64))
                self._builders.append(self._minmax(fn, kind,
                                                   with_nan=is_float))
            else:
                raise AssertionError(type(fn))
            self.agg_slots.append(slots)
        _collect_refs([fn for fn, _ in agg_exprs], refs)
        self.ref_names = sorted(refs)

    def _slot(self, kind: str) -> int:
        self.kinds.append(kind)
        return len(self.kinds) - 1

    # --- per-aggregate value builders (traced inside the kernel) ---
    def _masked_sum(self, fn):
        expr = self._prep(fn.children[0])

        def build(batch, mask):
            c = expr.eval(batch)
            m = mask & c.validity
            zero = jnp.zeros((), c.data.dtype)
            return [jnp.where(m, c.data, zero), m.astype(jnp.float32)]
        return build

    def _count_star(self):
        def build(batch, mask):
            return [mask.astype(jnp.float32)]
        return build

    def _count(self, fn):
        expr = self._prep(fn.children[0])

        def build(batch, mask):
            c = expr.eval(batch)
            return [(mask & c.validity).astype(jnp.float32)]
        return build

    def _minmax(self, fn, kind, with_nan: bool):
        expr = self._prep(fn.children[0])

        def build(batch, mask):
            c = expr.eval(batch)
            m = mask & c.validity
            fill = jnp.asarray(PK.reduce_identity(kind, c.data.dtype),
                               c.data.dtype)
            if not with_nan:
                return [jnp.where(m, c.data, fill),
                        m.astype(jnp.float32)]
            nan = jnp.isnan(c.data)
            return [jnp.where(m & ~nan, c.data, fill),
                    m.astype(jnp.float32),
                    (m & nan).astype(jnp.float32)]
        return build

    # --- the fused per-batch function (jit this) ---
    def batch_fn(self):
        schema_d = dict(self.input_schema)  # name -> dtype lookup
        names = self.ref_names
        demote = PK.on_tpu()
        pred = self._prep(self.pred) if self.pred is not None else None
        mask_pred = self.mask_pred
        builders = self._builders
        kinds = self.kinds

        def shim_dtype(t: dt.DType) -> dt.DType:
            return dt.FLOAT32 if demote and t == dt.FLOAT64 else t

        col_dtypes = [shim_dtype(schema_d[n]) for n in names]

        str_names = self.str_names

        def run(batch: ColumnarBatch):
            arrays = []
            for n, st in zip(names, col_dtypes):
                c = batch.column(n)
                data = c.data
                if demote and data.dtype == jnp.float64:
                    data = data.astype(jnp.float32)
                arrays.append(data)
                arrays.append(c.validity.astype(jnp.uint8))
            n_scalar = len(arrays)
            for sn in str_names:
                sc = batch.column(sn)
                arrays.append(sc.padded())              # (cap, W) u8
                arrays.append(sc.lengths().astype(jnp.int32))
                arrays.append(sc.validity.astype(jnp.uint8))
            live = batch.live_mask()
            if mask_pred is not None:
                # FilterExec's own tree at the columns' own types
                # (K.filter_batch's keep), so the same rows pass
                c = mask_pred.eval(batch)
                live = live & c.data & c.validity
            arrays.append(live.astype(jnp.uint8))

            def row_fn(blocks):
                cols = []
                for i, (n, st) in enumerate(zip(names, col_dtypes)):
                    cols.append(ColumnVector(blocks[2 * i],
                                             blocks[2 * i + 1] != 0, st))
                live = blocks[-1] != 0
                kb = _KernelBatch(cols, list(names), live)
                kb.str_lanes = {}
                for k, sn in enumerate(str_names):
                    chars = blocks[n_scalar + 3 * k]
                    lens = blocks[n_scalar + 3 * k + 1]
                    valid = blocks[n_scalar + 3 * k + 2] != 0
                    kb.str_lanes[sn] = (chars, lens, valid)
                mask = live
                if pred is not None:
                    pc = pred.eval(kb)
                    mask = mask & pc.data & pc.validity
                vals = []
                for b in builders:
                    vals.extend(b(kb, mask))
                return vals

            from ..conf import PALLAS_TILE_ROWS, active_conf
            return PK.tile_reduce(arrays, row_fn, kinds,
                                  tile_rows=active_conf()
                                  .get(PALLAS_TILE_ROWS))
        return run

    # --- host-side accumulation -> packed agg states ---
    def init_totals(self) -> List[float]:
        return [PK.reduce_identity(k, jnp.float64) if k != PK.SUM else 0.0
                for k in self.kinds]

    def combine(self, totals: List[float], partials) -> None:
        for i, (k, p) in enumerate(zip(self.kinds, partials)):
            v = float(read_back(p)[0])
            if k == PK.SUM:
                totals[i] += v
            elif np.isnan(v) or np.isnan(totals[i]):
                # builders exclude NaN lanes from min/max slots, so a
                # NaN here can only be a true sum overflow artifact —
                # keep the propagate-NaN guard for safety
                totals[i] = float("nan")
            elif k == PK.MIN:
                totals[i] = min(totals[i], v)
            else:
                totals[i] = max(totals[i], v)

    def states(self, totals: List[float], cap: int = 8) -> List[dict]:
        """Accumulated scalars -> per-aggregate state dicts shaped for
        HashAggregateExec._pack (cap-length arrays, group 0 live)."""
        out = []
        for slots in self.agg_slots:
            d = {}
            aux = {sname: totals[idx] for sname, idx, _ in slots}
            if "_nan" in aux:
                # Spark NaN-greatest ordering, deferred from the kernel
                key_name, key_idx, _t = slots[0]
                kkind = self.kinds[key_idx]
                nan_ct, seen_ct = aux["_nan"], aux["seen"]
                if kkind == PK.MAX and nan_ct > 0:
                    totals[key_idx] = float("nan")
                elif kkind == PK.MIN and nan_ct > 0 and \
                        seen_ct - nan_ct <= 0:
                    totals[key_idx] = float("nan")
            for sname, idx, stype in slots:
                if sname == "_nan":
                    continue  # consumed above; not part of the state
                v = totals[idx]
                phys = stype.physical
                if stype == dt.BOOL:
                    arr = np.zeros(cap, bool)
                    arr[0] = v > 0
                else:
                    arr = np.zeros(cap, phys)
                    if np.issubdtype(phys, np.integer) and \
                            not np.isfinite(v):
                        # zero-row min/max of a float-lane reduction:
                        # the +/-inf identity can't enter an int buffer,
                        # and seen=False keeps it from escaping anyway
                        pass
                    else:
                        # real inf/NaN totals must flow through — the
                        # XLA lane returns inf for sum(col with inf)
                        arr[0] = np.asarray(v).astype(phys)
                d[sname] = jnp.asarray(arr)
            out.append(d)
        return out


def grouped_eligible(agg_exec) -> bool:
    """Static gate for the grouped MXU lane (VERDICT r4 #2 — the
    reference's device groupby is THE aggregate path,
    GpuAggregateExec.scala:175): grouping keys present and every
    aggregate sum-decomposable — Sum/Average over floats, Count,
    CountStar. The per-batch <= 1024-group bound is traced (the
    sort-free prelude's num_groups: comparison rounds for a handful of
    groups, hash claim past them), so the decision between the one-hot
    matmul and the XLA scatter path is a lax.cond inside one compiled
    program (ops/kernels.py group_aggregate_pallas). A filter fused in
    front reaches that program as its ``live`` mask, not as a
    compaction (exec/fused.py _masked_agg_chain)."""
    if not agg_exec.group_exprs or agg_exec.mode == "final":
        return False
    schema = list(agg_exec.input_schema)
    for fn, _name in agg_exec.agg_exprs:
        if type(fn) in (Agg.CountStar, Agg.Count):
            continue
        if type(fn) not in (Agg.Sum, Agg.Average):
            return False
        try:
            if fn.children[0].data_type(schema) not in _FLOATY:
                return False
        except Exception:
            return False
    return True


def grouped_lane_on() -> bool:
    """The grouped kernel runs where it is fast: the real chip. The CPU
    interpret lane exists for differential tests (force with
    SRT_PALLAS_GROUPED_FORCE=1) but costs Python dispatch per tile."""
    import os
    return PK.on_tpu() or os.environ.get("SRT_PALLAS_GROUPED_FORCE") == "1"


def pallas_eligible(agg_exec) -> bool:
    """The static gate; False keeps the stock XLA path. (The actual
    PallasAggPlan is built lazily at execute time, once
    ``HashAggregateExec._pallas_filter`` has placed the predicate.)"""
    if agg_exec.group_exprs:
        return False
    schema = list(agg_exec.input_schema)
    for fn, _name in agg_exec.agg_exprs:
        try:
            if isinstance(fn, (Agg.Sum, Agg.Average)):
                if fn.children[0].data_type(schema) not in _FLOATY:
                    return False
            elif isinstance(fn, (Agg.Min, Agg.Max)):
                if fn.children[0].data_type(schema) not in _MINMAX_DTYPES:
                    return False
            elif isinstance(fn, (Agg.CountStar, Agg.Count)):
                pass
            else:
                return False
        except Exception:
            return False
        if not all(_expr_safe(c, schema) for c in fn.children):
            return False
    return True


def pred_safe(pred: E.Expression, input_schema) -> bool:
    """May the kernel itself evaluate this filter predicate? Row
    selection must stay exact: on TPU (where the kernel would demote
    f64 to f32) any float64 subexpression keeps the predicate out of
    the kernel — it becomes the XLA-evaluated mask in front of it
    (``PallasAggPlan.mask_pred``). String predicate subtrees are judged
    AFTER their byte-lane rewrite (the string-predicate kernel family)."""
    rewritten, _ = _rewrite_string_preds(pred, list(input_schema))
    return _expr_safe(rewritten, list(input_schema),
                      no_f64=PK.on_tpu())
