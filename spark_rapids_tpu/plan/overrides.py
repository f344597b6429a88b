"""Rule registry + tag-then-convert driver.

Rebuild of GpuOverrides.scala (SURVEY §2.2, 4668 LoC): a registry of
expression rules and exec rules, the wrap/tag pass (meta.py), and the
conversion of tagged logical trees into mixed TPU/CPU physical trees
with transitions at the seams (GpuTransitionOverrides role).

Where the reference registers ~215 expression rules mapping Catalyst
Expressions to Gpu* implementations, our frontend expressions ARE the
TPU implementations, so an expression rule here carries only the
support metadata: TypeSig + extra plan-time checks. Fallback maps the
expression to the CPU interpreter (cpu_eval.py) instead.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Type

from ..columnar import dtypes as dt
from ..conf import (BROADCAST_THRESHOLD_ROWS, EXCHANGE_ENABLED, EXPLAIN,
                    FUSION_DONATE, FUSION_ENABLED, FUSION_EXCLUDE_EXECS,
                    FUSION_FINAL_AGG, FUSION_JOINS, PALLAS_ENABLED,
                    PALLAS_GROUP_MAX_CAPACITY, PALLAS_GROUPED_ENABLED,
                    PIPELINE_ENABLED, SHUFFLE_PARTITIONS, SQL_ENABLED,
                    SrtConf, active_conf)
from ..exec.aggregate import HashAggregateExec
from ..exec.base import TpuExec
from ..exec.basic import (BatchScanExec, CoalesceBatchesExec, ExpandExec,
                          FilterExec, LocalLimitExec, ProjectExec, RangeExec,
                          UnionExec)
from ..exec.join import ShuffledHashJoinExec
from ..exec.sort import SortExec, SortOrder, TopNExec
from ..expr import aggregates as Agg
from ..expr import arithmetic as A
from ..expr import cast as C
from ..expr import conditional as Cond
from ..expr import core as E
from ..expr import datetime as D
from ..expr import hashing as H
from ..expr import mathfns as M
from ..expr import predicates as P
from ..expr import strings as S
from . import cpu_eval, typechecks as ts
from .logical import (Aggregate, Expand, Filter, Join, Limit, LocalRelation,
                      LogicalPlan, Project, Range, Sample, Sort, Union,
                      Window)
from .meta import ExprMeta, PlanMeta
from .transitions import (CpuPhysical, DeviceToHostBridge, HostToDeviceExec)


class ExprRule:
    """Support metadata for one expression class (GpuOverrides.expr)."""

    def __init__(self, cls: Type, sig: ts.TypeSig,
                 extra_tag: Optional[Callable[[ExprMeta], None]] = None,
                 description: str = ""):
        self.cls = cls
        self.sig = sig
        self.extra_tag = extra_tag
        self.description = description or cls.__doc__ or ""

    def tag(self, meta: ExprMeta) -> None:
        for child in meta.expr.children:
            t = child.data_type(meta.schema)
            reason = self.sig.reason_if_unsupported(
                t, f"{type(meta.expr).__name__} input")
            if reason:
                meta.will_not_work_on_tpu(reason)
        if self.extra_tag is not None:
            self.extra_tag(meta)


class ExecRule:
    """Support metadata for one logical-plan class (GpuOverrides.exec)."""

    def __init__(self, cls: Type,
                 tag_fn: Optional[Callable[[PlanMeta], None]] = None,
                 description: str = ""):
        self.cls = cls
        self.tag_fn = tag_fn
        self.description = description

    def tag(self, meta: PlanMeta) -> None:
        if self.tag_fn is not None:
            self.tag_fn(meta)


_EXPR_RULES: Dict[Type, ExprRule] = {}
_EXEC_RULES: Dict[Type, ExecRule] = {}


def expr_rule_for(cls: Type) -> Optional[ExprRule]:
    return _EXPR_RULES.get(cls)


def exec_rule_for(cls: Type) -> Optional[ExecRule]:
    return _EXEC_RULES.get(cls)


def _expr(cls, sig: ts.TypeSig, extra=None):
    _EXPR_RULES[cls] = ExprRule(cls, sig, extra)


# --- expression rules ------------------------------------------------------

_expr(E.ColumnRef, ts.all_basic_128)
_expr(E.Alias, ts.all_basic_128 + ts.TypeSig(ts.ARRAY, ts.STRUCT))


def _register_pandas_udf_rule():
    # vectorized UDFs stay in device plans: the Project conversion
    # extracts them into ArrowEvalPythonExec (GpuExtractPythonUDFs role)
    from ..udf.pandas_udf import PandasUDF
    _expr(PandasUDF, ts.all_basic)


_register_pandas_udf_rule()


def _register_bloom_rule():
    from ..expr.hashing import BloomFilterMightContain
    _expr(BloomFilterMightContain,
          ts.integral + ts.TypeSig(ts.DATE, ts.TIMESTAMP, ts.STRING))


_register_bloom_rule()


def _register_misc_rules():
    # execution-context expressions (expr/misc.py): leaf exprs, no
    # input types to check; eager-only ones are handled by Project
    from ..expr import misc as MX
    for cls in (MX.MonotonicallyIncreasingID, MX.SparkPartitionID,
                MX.InputFileName, MX.InputFileBlockStart,
                MX.InputFileBlockLength, MX.Uuid, MX.RaiseError,
                MX.Version):
        _expr(cls, ts.all_basic)


_register_misc_rules()


def device_type_ok(t: dt.DType) -> Optional[str]:
    """Recursive device support for a column type (TypeSig nested
    checks): arrays/structs of supported types flow through
    project/filter/generate; maps are CPU-only for now."""
    if isinstance(t, dt.ArrayType):
        return device_type_ok(t.element_type)
    if isinstance(t, dt.StructType):
        for _, ft in t.fields:
            reason = device_type_ok(ft)
            if reason:
                return reason
        return None
    if isinstance(t, dt.MapType):
        for part in (t.key_type, t.value_type):
            reason = device_type_ok(part)
            if reason:
                return reason
        return None
    return ts.all_basic_128.reason_if_unsupported(t, "column")


def _tag_literal(meta: ExprMeta):
    t = meta.expr.data_type(meta.schema)
    reason = ts.all_basic_128.reason_if_unsupported(t, "literal")
    if reason:
        meta.will_not_work_on_tpu(reason)


_expr(E.Literal, ts.all_basic_128, _tag_literal)

for _cls in (A.Add, A.Subtract, A.Multiply, A.Divide):
    _expr(_cls, ts.numeric_all)
# mod/div on decimal128 needs >128-bit scale alignment: CPU fallback
for _cls in (A.IntegralDivide, A.Remainder, A.Pmod):
    _expr(_cls, ts.numeric)
for _cls in (A.UnaryMinus, A.UnaryPositive, A.Abs):
    _expr(_cls, ts.numeric_all)
for _cls in (A.Least, A.Greatest):
    # decimal64 reduces on the int64 physical; strings + decimal128
    # fall back to the CPU lane (the If-fold device lane for strings
    # exists but mis-selects on some null patterns — planner-gated off
    # until debugged; the CPU oracle string lane is the active path)
    _expr(_cls, ts.numeric_no_decimal + ts.TypeSig(
        ts.DATE, ts.TIMESTAMP, ts.BOOLEAN, ts.DECIMAL_64))

for _cls in (P.EqualTo, P.LessThan, P.GreaterThan, P.LessThanOrEqual,
             P.GreaterThanOrEqual, P.EqualNullSafe):
    _expr(_cls, ts.comparable + ts.decimal128)
for _cls in (P.And, P.Or, P.Not):
    _expr(_cls, ts.TypeSig(ts.BOOLEAN))
for _cls in (P.IsNull, P.IsNotNull):
    _expr(_cls, ts.all_basic_128)
_expr(P.IsNaN, ts.fp)
_expr(P.InSet, ts.comparable)

for _cls in (Cond.If, Cond.CaseWhen, Cond.Coalesce, Cond.NullIf, Cond.Nvl,
             Cond.Nvl2):
    _expr(_cls, ts.all_basic)


def _tag_cast(meta: ExprMeta):
    try:
        meta.expr.check_supported(meta.schema)
    except TypeError as e:
        meta.will_not_work_on_tpu(f"cast: {e}")


_expr(C.Cast, ts.all_basic_128, _tag_cast)

for _cls in list(cpu_eval._MATH_FNS) + [M.Log, M.Log2, M.Log10, M.Floor,
                                        M.Ceil, M.Pow, M.Atan2, M.Hypot,
                                        M.Round, M.BRound]:
    _expr(_cls, ts.numeric)

for _cls in (S.Length, S.OctetLength, S.Upper, S.Lower, S.Substring,
             S.Concat, S.StartsWith, S.EndsWith, S.Contains, S.StringTrim,
             S.StringTrimLeft, S.StringTrimRight):
    _expr(_cls, ts.TypeSig(ts.STRING))


def _tag_like(meta: ExprMeta):
    for ch in meta.expr.pattern:
        if ch not in ("%", "_") and len(ch.encode("utf-8")) != 1:
            meta.will_not_work_on_tpu(
                "LIKE: multi-byte pattern literals not supported on TPU")
            return


_expr(S.Like, ts.TypeSig(ts.STRING), _tag_like)

for _cls in (S.Reverse, S.Lpad, S.Rpad, S.InitCap, S.ConcatWs,
             S.StringLocate, S.StringRepeat, S.StringReplace,
             S.StringTranslate):
    _expr(_cls, ts.TypeSig(ts.STRING))


def _tag_rlike(meta: ExprMeta):
    """transpile-or-fallback (RegexParser.transpile contract): patterns
    the NFA engine rejects run on CPU via python re."""
    from ..expr.regex import RegexUnsupported, transpile
    try:
        transpile(meta.expr.pattern)
    except RegexUnsupported as e:
        meta.will_not_work_on_tpu(f"rlike: {e}")


def _tag_regexp_extract(meta: ExprMeta):
    from ..expr.regex import RegexUnsupported, check_submatch_supported
    try:
        check_submatch_supported(meta.expr.pattern, meta.expr.group)
    except RegexUnsupported as e:
        meta.will_not_work_on_tpu(f"regexp_extract: {e}")


def _tag_regexp_replace(meta: ExprMeta):
    from ..expr.regex import RegexUnsupported, check_submatch_supported
    if meta.expr._repl_refs:
        meta.will_not_work_on_tpu(
            "regexp_replace: group references in the replacement run "
            "on CPU")
        return
    try:
        check_submatch_supported(meta.expr.pattern, 0)
    except RegexUnsupported as e:
        meta.will_not_work_on_tpu(f"regexp_replace: {e}")


def _register_regex_rules():
    from ..expr import regex as RX
    _EXPR_RULES[RX.RLike] = ExprRule(RX.RLike, ts.TypeSig(ts.STRING),
                                     _tag_rlike)
    # extract/replace run on device via span finding + greedy segment
    # splits (expr/regex.py submatch machinery); patterns outside that
    # envelope tag to CPU `re` (transpile-or-fallback)
    _EXPR_RULES[RX.RegExpExtract] = ExprRule(
        RX.RegExpExtract, ts.TypeSig(ts.STRING), _tag_regexp_extract)
    _EXPR_RULES[RX.RegExpReplace] = ExprRule(
        RX.RegExpReplace, ts.TypeSig(ts.STRING), _tag_regexp_replace)


_register_regex_rules()

# date fields accept timestamps too (micros -> days in _to_days)
for _cls in (D.Year, D.Month, D.DayOfMonth, D.Quarter, D.DayOfWeek,
             D.WeekDay, D.DayOfYear, D.LastDay):
    _expr(_cls, ts.TypeSig(ts.DATE, ts.TIMESTAMP))
for _cls in (D.Hour, D.Minute, D.Second, D.UnixTimestampToSeconds):
    _expr(_cls, ts.TypeSig(ts.TIMESTAMP))
for _cls in (D.DateAdd, D.DateSub, D.DateDiff):
    _expr(_cls, ts.TypeSig(ts.DATE) + ts.integral)
_expr(D.AddMonths, ts.TypeSig(ts.DATE) + ts.integral)
_expr(D.FromUnixTime, ts.integral)

from ..expr import timezone as TZX  # noqa: E402

for _cls in (TZX.FromUTCTimestamp, TZX.ToUTCTimestamp):
    _expr(_cls, ts.TypeSig(ts.TIMESTAMP))
_expr(D.MakeDate, ts.integral)
_expr(D.TruncDate, ts.TypeSig(ts.DATE, ts.STRING))

from ..expr import json as JX  # noqa: E402

_expr(JX.GetJsonObject, ts.TypeSig(ts.STRING))
# from_json/to_json: CPU engine (no device JSON tokenizer yet) — no
# rule registered routes them to cpu_eval

_expr(H.Murmur3Hash, ts.comparable)
_expr(H.XxHash64, ts.comparable)

from ..expr import bitwise as BW  # noqa: E402

for _cls in (BW.BitwiseAnd, BW.BitwiseOr, BW.BitwiseXor, BW.BitwiseNot,
             BW.BitCount):
    _expr(_cls, ts.integral + ts.TypeSig(ts.BOOLEAN))
for _cls in (BW.ShiftLeft, BW.ShiftRight, BW.ShiftRightUnsigned):
    _expr(_cls, ts.integral)
_expr(BW.InterleaveBits, ts.integral)

# --- collections (arrays/structs) ---
from ..expr import collections as CX  # noqa: E402

_nested_ok = ts.all_basic + ts.TypeSig(ts.ARRAY, ts.STRUCT)


def _primitive_elements(meta: ExprMeta):
    """Lane-kernel exprs need a primitive (non-string) element type."""
    t = meta.expr.children[0].data_type(meta.schema)
    if isinstance(t, dt.ArrayType) and (t.element_type.is_nested or
                                        t.element_type == dt.STRING):
        meta.will_not_work_on_tpu(
            f"{type(meta.expr).__name__}: element type "
            f"{t.element_type} needs lane lowering not yet on TPU")


_expr(CX.CreateArray, ts.numeric + ts.TypeSig(ts.BOOLEAN, ts.DATE,
                                              ts.TIMESTAMP, ts.NULL))
_expr(CX.Size, _nested_ok)
_expr(CX.GetArrayItem, _nested_ok)
_expr(CX.ElementAt, _nested_ok)
_expr(CX.ArrayContains, _nested_ok, _primitive_elements)
_expr(CX.ArrayMin, _nested_ok, _primitive_elements)
_expr(CX.ArrayMax, _nested_ok, _primitive_elements)
_expr(CX.SortArray, _nested_ok, _primitive_elements)
_expr(CX.CreateNamedStruct, ts.all_basic)
_expr(CX.GetStructField, ts.TypeSig(ts.STRUCT))
_expr(CX.ArrayDistinct, _nested_ok, _primitive_elements)
_expr(CX.ArrayUnion, _nested_ok, _primitive_elements)
_expr(CX.ArrayIntersect, _nested_ok, _primitive_elements)
_expr(CX.ArrayExcept, _nested_ok, _primitive_elements)
_expr(CX.ArraysOverlap, _nested_ok, _primitive_elements)
_expr(CX.ArrayRemove, _nested_ok, _primitive_elements)
_expr(CX.ArrayPosition, _nested_ok, _primitive_elements)
_expr(CX.Slice, _nested_ok, _primitive_elements)
_expr(CX.ArrayReverse, _nested_ok, _primitive_elements)


def _tag_array_repeat(meta: ExprMeta):
    from ..expr.core import Literal
    if not isinstance(meta.expr.children[1], Literal):
        meta.will_not_work_on_tpu(
            "array_repeat: non-literal count needs dynamic list "
            "extents (static-shape device lowering); runs on CPU")
    t = meta.expr.children[0].data_type(meta.schema)
    if t.is_nested or t == dt.STRING:
        meta.will_not_work_on_tpu(
            f"array_repeat of {t} needs lane lowering not yet on TPU")


_expr(CX.ArrayRepeat, ts.all_basic + ts.TypeSig(ts.ARRAY),
      _tag_array_repeat)


def _cpu_only_collection(meta: ExprMeta):
    meta.will_not_work_on_tpu(
        f"{type(meta.expr).__name__}: ragged/nested lane lowering not "
        "yet on TPU; runs on the CPU engine")


def _tag_zip_with(meta: ExprMeta):
    # lane evaluation binds the lambda vars as primitive element lanes;
    # the lambda RESULT must be primitive too (the repack builds a
    # flat ColumnVector child)
    for child in meta.expr.children[:2]:
        t = child.data_type(meta.schema)
        et = t.element_type if isinstance(t, dt.ArrayType) else t
        if et.is_nested or et == dt.STRING:
            meta.will_not_work_on_tpu(
                f"zip_with over {et} elements needs non-primitive lane "
                "lowering; runs on CPU")
    out_t = meta.expr.data_type(meta.schema)  # binds lambda var dtypes
    rt = out_t.element_type if isinstance(out_t, dt.ArrayType) else out_t
    if rt.is_nested or rt == dt.STRING:
        meta.will_not_work_on_tpu(
            f"zip_with producing {rt} needs non-primitive lane "
            "lowering; runs on CPU")


_expr(CX.Flatten, ts.all_basic + ts.TypeSig(ts.ARRAY, ts.STRUCT, ts.MAP),
      None)
_expr(CX.ArraysZip,
      ts.all_basic + ts.TypeSig(ts.ARRAY, ts.STRUCT, ts.MAP), None)
_expr(CX.ArrayJoin,
      ts.all_basic + ts.TypeSig(ts.ARRAY, ts.STRUCT, ts.MAP), None)
_expr(CX.ZipWith,
      ts.all_basic + ts.TypeSig(ts.ARRAY, ts.STRUCT, ts.MAP),
      _tag_zip_with)
_expr(CX.MapConcat,
      ts.all_basic + ts.TypeSig(ts.ARRAY, ts.STRUCT, ts.MAP),
      _cpu_only_collection)


# --- higher-order functions + maps ---
from ..expr import higher_order as HO  # noqa: E402

_hof_ok = ts.all_basic + ts.TypeSig(ts.ARRAY, ts.STRUCT, ts.MAP)


def _lambda_primitive_elements(meta: ExprMeta):
    """Lane-lowered lambdas need primitive (non-string, non-nested)
    element/key/value types on device; everything else falls back
    (the reference runs these through cuDF's list lowering —
    higherOrderFunctions.scala TypeSigs gate similarly)."""
    parts = []
    for child in meta.expr.children:
        t = child.data_type(meta.schema)
        if isinstance(t, dt.MapType):
            parts += [t.key_type, t.value_type]
        elif isinstance(t, dt.ArrayType):
            parts.append(t.element_type)
    for p in parts:
        if p.is_nested or p == dt.STRING:
            meta.will_not_work_on_tpu(
                f"{type(meta.expr).__name__}: element type {p} needs "
                "lane lowering not yet on TPU")
    # lambda RESULT must also be a primitive lane type
    from ..expr.higher_order import (ArrayFilter, ArrayTransform,
                                     MapFilter, TransformKeys,
                                     TransformValues)
    if isinstance(meta.expr, (ArrayTransform, TransformKeys,
                              TransformValues)):
        rt = meta.expr.children[1].data_type(meta.schema)
        if rt.is_nested or rt == dt.STRING:
            meta.will_not_work_on_tpu(
                f"{type(meta.expr).__name__}: lambda result type {rt} "
                "needs lane lowering not yet on TPU")


def _no_outer_refs_in_aggregate(meta: ExprMeta):
    from ..expr.higher_order import _outer_refs
    expr: HO.ArrayAggregate = meta.expr
    for body in expr._bodies():
        if _outer_refs(body, expr.lambda_vars):
            meta.will_not_work_on_tpu(
                "aggregate() lambda referencing outer columns runs on "
                "CPU (scan-carried outer state not lowered)")
            return
    _lambda_primitive_elements(meta)


_expr(HO.LambdaVariable, ts.all_basic)
_expr(HO.ArrayTransform, _hof_ok, _lambda_primitive_elements)
_expr(HO.ArrayExists, _hof_ok, _lambda_primitive_elements)
_expr(HO.ArrayForAll, _hof_ok, _lambda_primitive_elements)
_expr(HO.ArrayFilter, _hof_ok, _lambda_primitive_elements)
_expr(HO.ArrayAggregate, _hof_ok, _no_outer_refs_in_aggregate)
_expr(HO.MapKeys, ts.TypeSig(ts.MAP))
_expr(HO.MapValues, ts.TypeSig(ts.MAP))
_expr(HO.MapEntries, ts.TypeSig(ts.MAP))
_expr(HO.GetMapValue, ts.TypeSig(ts.MAP) + ts.all_basic,
      _lambda_primitive_elements)
_expr(HO.MapContainsKey, ts.TypeSig(ts.MAP) + ts.all_basic,
      _lambda_primitive_elements)
_expr(HO.TransformValues, ts.TypeSig(ts.MAP), _lambda_primitive_elements)
_expr(HO.TransformKeys, ts.TypeSig(ts.MAP), _lambda_primitive_elements)
_expr(HO.MapFilter, ts.TypeSig(ts.MAP), _lambda_primitive_elements)
_expr(HO.CreateMap, ts.numeric + ts.TypeSig(ts.BOOLEAN, ts.DATE,
                                            ts.TIMESTAMP))
_expr(HO.MapFromArrays, ts.TypeSig(ts.ARRAY), _lambda_primitive_elements)


def _tag_explode(meta: ExprMeta):
    t = meta.expr.children[0].data_type(meta.schema)
    if not isinstance(t, dt.ArrayType):
        meta.will_not_work_on_tpu(f"explode of {t} not supported on TPU")


_expr(CX.Explode, _nested_ok, _tag_explode)

for _cls in (Agg.First, Agg.Last):
    _expr(_cls, ts.comparable)
# collect_list/set build ListColumn states on device; set dedupe sorts
# elements, so string sets stay on CPU (char-buffer churn)
_expr(Agg.CollectList, ts.numeric + ts.TypeSig(ts.BOOLEAN, ts.DATE,
                                               ts.TIMESTAMP, ts.STRING))
_expr(Agg.CollectSet, ts.numeric + ts.TypeSig(ts.BOOLEAN, ts.DATE,
                                              ts.TIMESTAMP))
for _cls in (Agg.Count, Agg.CountStar):
    _expr(_cls, ts.comparable + ts.decimal128)
# sum/avg on decimal128 run on the two-limb segmented accumulator
# (expr/aggregates.py _Decimal128SumMixin); variance family stays
# double-only like the reference's GpuM2
for _cls in (Agg.Sum, Agg.Average):
    _expr(_cls, ts.numeric_all)
for _cls in (Agg.VariancePop, Agg.VarianceSamp,
             Agg.StddevPop, Agg.StddevSamp):
    _expr(_cls, ts.numeric)
# t-digest sketch states (ListColumn centroids) on device; exact
# Percentile remains CPU-only (not decomposable into bounded states)
_expr(Agg.ApproxPercentile, ts.numeric)
# min/max cover strings via sort-rank selection (expr/aggregates.py
# _string_reduce)
for _cls in (Agg.Min, Agg.Max):
    _expr(_cls, ts.numeric_all + ts.TypeSig(ts.BOOLEAN, ts.DATE,
                                            ts.TIMESTAMP, ts.STRING))


# --- exec rules ------------------------------------------------------------

_TPU_JOIN_TYPES = ("inner", "left_outer", "right_outer", "left_semi",
                   "left_anti", "full_outer", "cross")


def _tag_join(meta: PlanMeta):
    plan: Join = meta.plan
    if plan.join_type not in _TPU_JOIN_TYPES:
        meta.will_not_work_on_tpu(
            f"join type {plan.join_type} not supported on TPU yet")
    if plan.condition is not None and plan.join_type not in ("inner",
                                                            "cross"):
        # residual conditions on outer/semi/anti change match semantics
        # (not merely filter output) — CPU engine handles those
        meta.will_not_work_on_tpu(
            f"join residual condition on {plan.join_type} not supported "
            "on TPU yet")
    if not plan.left_keys and plan.join_type not in ("inner", "cross"):
        meta.will_not_work_on_tpu(
            f"keyless {plan.join_type} join not supported on TPU yet")


def _wide_decimal(t: dt.DType) -> bool:
    return isinstance(t, dt.DecimalType) and t.is_wide


def _tag_agg(meta: PlanMeta):
    plan: Aggregate = meta.plan
    in_schema = plan.children[0].schema
    for e in plan.group_exprs:
        t = e.data_type(in_schema)
        if t.is_nested:
            meta.will_not_work_on_tpu(
                f"group-by key of type {t} not supported on TPU yet")
        if _wide_decimal(t):
            meta.will_not_work_on_tpu(
                "group-by key of type decimal128 not supported on TPU "
                "yet (two-limb sort keys)")


def _tag_file_scan(meta: PlanMeta):
    from ..io.scan import FileScan
    plan: FileScan = meta.plan
    for name, t in plan.schema:
        reason = device_type_ok(t)
        if reason:
            meta.will_not_work_on_tpu(f"scan column {name}: {reason}")


def _no_nested_inputs(what: str):
    """Execs whose kernels concat/partition/sort batches don't take
    nested payload columns yet (the reference gates the same surface
    per-op via TypeSig; GpuHashJoin/GpuSortExec nested support)."""
    def tag(meta: PlanMeta):
        for c in meta.plan.children:
            for name, t in c.schema:
                if t.is_nested:
                    meta.will_not_work_on_tpu(
                        f"{what}: nested column {name} ({t}) not "
                        "supported on TPU yet")
                    return
    return tag


def _tag_sort(meta: PlanMeta):
    _no_nested_inputs("sort")(meta)
    plan = meta.plan
    in_schema = plan.children[0].schema
    for f in plan.order:
        if _wide_decimal(f.expr.data_type(in_schema)):
            meta.will_not_work_on_tpu(
                "sort key of type decimal128 not supported on TPU yet "
                "(two-limb sort keys)")
            return


def _tag_window(meta: PlanMeta):
    from ..expr.window import (Lag, Lead, DenseRank, NTile, PercentRank,
                               Rank, RowNumber)
    plan: Window = meta.plan
    in_schema = plan.children[0].schema
    supported_rank = (RowNumber, Rank, DenseRank, PercentRank, NTile,
                      Lead, Lag)
    spec0 = plan.window_exprs[0][0].spec if plan.window_exprs else None
    if spec0 is not None:
        key_exprs = list(spec0.partition_by) + \
            [o.expr for o in spec0.order_fields]
        for e in key_exprs:
            if _wide_decimal(e.data_type(in_schema)):
                meta.will_not_work_on_tpu(
                    "window partition/order key of type decimal128 not "
                    "supported on TPU yet")
                return
    for we, name in plan.window_exprs:
        fn = we.func
        if isinstance(fn, supported_rank):
            continue
        if isinstance(fn, (Agg.Sum, Agg.Count, Agg.CountStar, Agg.Average)):
            out_t = fn.data_type(in_schema) \
                if not isinstance(fn, Agg.CountStar) else dt.INT64
            in_wide = any(_wide_decimal(c.data_type(in_schema))
                          for c in fn.children)
            if in_wide or _wide_decimal(out_t):
                meta.will_not_work_on_tpu(
                    f"window {name}: decimal128 aggregation windows "
                    "not on TPU yet")
                continue
        elif isinstance(fn, (Agg.Min, Agg.Max)):
            t0 = fn.children[0].data_type(in_schema) if fn.children else None
            if t0 == dt.STRING:
                meta.will_not_work_on_tpu(
                    f"window {name}: string min/max not on TPU yet")
                continue
            if t0 is not None and _wide_decimal(t0):
                meta.will_not_work_on_tpu(
                    f"window {name}: decimal128 aggregation windows "
                    "not on TPU yet")
                continue
        else:
            meta.will_not_work_on_tpu(
                f"window function {type(fn).__name__} not on TPU yet")
            continue
        frame = we.spec.frame
        if frame is not None and not frame.row_based and not (
                frame.is_running or frame.is_unbounded):
            # bounded RANGE frames: one numeric/date/timestamp order key
            # (binary-searchable values; exec/window.py _range_sliding)
            ofs = we.spec.order_fields
            kt = ofs[0].expr.data_type(in_schema) if len(ofs) == 1 else None
            key_ok = (kt is not None and not _wide_decimal(kt) and (
                kt.is_numeric or
                isinstance(kt, (dt.DateType, dt.TimestampType))))
            if not key_ok:
                meta.will_not_work_on_tpu(
                    f"window {name}: RANGE frames need a single "
                    "numeric/date order key on TPU")
        if frame is not None and frame.row_based and \
                isinstance(fn, (Agg.Min, Agg.Max)) and \
                not (frame.is_running or frame.is_unbounded) and \
                (frame.lo is None or frame.hi is None):
            meta.will_not_work_on_tpu(
                f"window {name}: min/max sliding frames need bounded "
                "ROWS offsets")


def _tag_join_all(meta: PlanMeta):
    _tag_join(meta)
    _no_nested_inputs("join")(meta)
    plan: Join = meta.plan
    lschema = plan.children[0].schema
    rschema = plan.children[1].schema
    for e in plan.left_keys:
        if _wide_decimal(e.data_type(lschema)):
            meta.will_not_work_on_tpu(
                "join key of type decimal128 not supported on TPU yet "
                "(two-limb hash keys)")
            return
    for e in plan.right_keys:
        if _wide_decimal(e.data_type(rschema)):
            meta.will_not_work_on_tpu(
                "join key of type decimal128 not supported on TPU yet "
                "(two-limb hash keys)")
            return


def _register_exec_rules():
    from ..cache import CachedRelation
    from ..io.scan import FileScan
    from .logical import Generate
    _EXEC_RULES[CachedRelation] = ExecRule(CachedRelation)
    _EXEC_RULES.update({
        LocalRelation: ExecRule(LocalRelation),
        Range: ExecRule(Range),
        Project: ExecRule(Project),
        Filter: ExecRule(Filter),
        Limit: ExecRule(Limit),
        Union: ExecRule(Union, _no_nested_inputs("union")),
        Expand: ExecRule(Expand, _no_nested_inputs("expand")),
        Sort: ExecRule(Sort, _tag_sort),
        Sample: ExecRule(Sample),
        Aggregate: ExecRule(Aggregate, _tag_agg),
        Join: ExecRule(Join, _tag_join_all),
        Window: ExecRule(Window, _tag_window),
        FileScan: ExecRule(FileScan, _tag_file_scan),
        Generate: ExecRule(Generate),
    })


_register_exec_rules()


# --- conversion ------------------------------------------------------------

def _build_tpu_exec(plan: LogicalPlan, children: List[TpuExec],
                    conf: SrtConf) -> TpuExec:
    from ..cache import CachedRelation
    from ..io.scan import FileScan, FileSourceScanExec
    if isinstance(plan, CachedRelation):
        return BatchScanExec(plan.batches(), plan.schema)
    if isinstance(plan, FileScan):
        return FileSourceScanExec(plan)
    if isinstance(plan, (LocalRelation, Range)) :
        # host-resident leaves enter the device through the transition
        return HostToDeviceExec(CpuPhysical(plan, []))
    if isinstance(plan, Sample):
        from ..exec.basic import SampleExec
        return SampleExec(children[0], plan.fraction, plan.seed)
    if isinstance(plan, Project):
        from ..udf.pandas_udf import extract_pandas_udfs
        exprs, pyudfs = extract_pandas_udfs(plan.exprs)
        if pyudfs:
            # GpuExtractPythonUDFs role: UDFs evaluate in a pooled
            # python worker between the child and the projection
            from ..exec.python_exec import ArrowEvalPythonExec
            return ProjectExec(
                ArrowEvalPythonExec(children[0], pyudfs), exprs)
        return ProjectExec(children[0], plan.exprs)
    if isinstance(plan, Filter):
        return FilterExec(children[0], plan.condition)
    if isinstance(plan, Limit):
        return LocalLimitExec(children[0], plan.n)
    if isinstance(plan, Union):
        return UnionExec(*children)
    if isinstance(plan, Expand):
        return ExpandExec(children[0], plan.projections, plan.names)
    if isinstance(plan, Sort):
        return SortExec(children[0],
                        [SortOrder(o.expr, o.ascending, o.nulls_first)
                         for o in plan.order],
                        global_sort=plan.is_global)
    if isinstance(plan, Aggregate):
        # staged (GpuAggregateExec partial -> exchange -> final); the
        # ensure_distribution pass places the exchange between them.
        # collect_list/set carry ListColumn states the exchange
        # partitioner doesn't pack yet -> single-stage COMPLETE
        from ..exec.aggregate import COMPLETE, FINAL, PARTIAL

        def _single_stage(fn) -> bool:
            # list states shuffle via the packed child-plane layout
            # (parallel/partition.py), but only for PRIMITIVE elements;
            # string/nested-element collects stay single-stage
            if isinstance(fn, (Agg.CollectList, Agg.ApproxPercentile)):
                if isinstance(fn, Agg.ApproxPercentile):
                    return False
                t = fn.children[0].data_type(plan.children[0].schema)
                return t == dt.STRING or t.is_nested or \
                    (isinstance(t, dt.DecimalType) and t.is_wide)
            return False
        if any(_single_stage(fn) for fn, _ in plan.agg_exprs):
            return HashAggregateExec(children[0], plan.group_exprs,
                                     plan.agg_exprs, mode=COMPLETE)
        partial = HashAggregateExec(children[0], plan.group_exprs,
                                    plan.agg_exprs, mode=PARTIAL)
        return HashAggregateExec(partial, plan.group_exprs, plan.agg_exprs,
                                 mode=FINAL,
                                 input_schema=plan.children[0].schema)
    if isinstance(plan, Window):
        from ..conf import WINDOW_BATCHED_RUNNING
        from ..exec.window import (BatchedRunningWindowExec, WindowExec,
                                   running_compatible)
        in_schema = plan.children[0].schema
        if conf.get(WINDOW_BATCHED_RUNNING) and \
                running_compatible(plan.window_exprs, in_schema):
            # running-only windows stream batch-at-a-time over a sorted
            # child with carried state (GpuRunningWindowExec role)
            spec = plan.window_exprs[0][0].spec
            orders = ([SortOrder(e, True, True)
                       for e in spec.partition_by] +
                      [SortOrder(o.expr, o.ascending, o.nulls_first)
                       for o in spec.order_fields])
            sorted_child = SortExec(children[0], orders)
            return BatchedRunningWindowExec(sorted_child,
                                            plan.window_exprs)
        return WindowExec(children[0], plan.window_exprs)
    from .logical import Generate
    if isinstance(plan, Generate):
        from ..exec.generate import GenerateExec
        return GenerateExec(children[0], plan.generator,
                            plan.element_name, plan.pos_name)
    if isinstance(plan, Join):
        return _build_join(plan, children, conf)
    raise NotImplementedError(type(plan).__name__)


def _coerce_join_keys(plan: Join):
    """Join keys must share a dtype across sides: the partitioner hashes
    key *values*, and murmur3 is width-sensitive (Spark's analyzer
    inserts these casts before planning)."""
    from ..expr.conditional import _common_type
    ls, rs = plan.children[0].schema, plan.children[1].schema
    lk, rk = [], []
    for l, r in zip(plan.left_keys, plan.right_keys):
        lt, rt = l.data_type(ls), r.data_type(rs)
        if lt == rt:
            lk.append(l)
            rk.append(r)
            continue
        ct = _common_type([lt, rt])
        lk.append(l if lt == ct else C.Cast(l, ct))
        rk.append(r if rt == ct else C.Cast(r, ct))
    return lk, rk


def _join_cls(plan: Join, build: str, conf: SrtConf):
    """Broadcast when the build side's estimated rows are small
    (spark.sql.autoBroadcastJoinThreshold role)."""
    from .cost import estimate_rows
    build_plan = plan.children[1] if build == "right" else plan.children[0]
    if estimate_rows(build_plan) <= conf.get(BROADCAST_THRESHOLD_ROWS):
        from ..exec.join import BroadcastHashJoinExec
        return BroadcastHashJoinExec
    return ShuffledHashJoinExec


def _build_join(plan: Join, children: List[TpuExec],
                conf: SrtConf) -> TpuExec:
    from ..exec.nested_loop_join import (BroadcastNestedLoopJoinExec,
                                         CartesianProductExec)
    from .cost import estimate_rows
    left, right = children
    if not plan.left_keys:
        # keyless: cartesian / conditioned nested loop
        if plan.condition is None:
            return CartesianProductExec(left, right)
        return BroadcastNestedLoopJoinExec(left, right, plan.condition,
                                           "inner")
    left_keys, right_keys = _coerce_join_keys(plan)
    if plan.join_type == "full_outer":
        # full outer = left_outer(L,R) UNION null-extended anti(R,L)
        # (both pieces are device-supported; the Union concatenates)
        lo = ShuffledHashJoinExec(left, right, left_keys, right_keys,
                                  join_type="left_outer",
                                  build_side="right")
        anti = ShuffledHashJoinExec(right, left, right_keys, left_keys,
                                    join_type="left_anti",
                                    build_side="right")
        left_schema = plan.children[0].schema
        null_left = [E.Literal(None, t) for _, t in left_schema]
        exprs = ([E.Alias(e, n) for e, (n, _) in
                  zip(null_left, left_schema)] +
                 [E.Alias(E.col(n), n)
                  for n, _ in plan.children[1].schema])
        extended = ProjectExec(anti, exprs)
        return UnionExec(lo, extended)
    build = "left" if plan.join_type == "right_outer" else "right"
    if plan.join_type == "inner" and \
            estimate_rows(plan.children[0]) < estimate_rows(plan.children[1]):
        # an inner join is symmetric: build the side estimated smaller,
        # whichever way the query names the tables (FROM date_dim JOIN
        # store_sales builds the dimension, not the fact table)
        build = "left"
    cls = _join_cls(plan, build, conf)
    joined = cls(left, right, left_keys, right_keys,
                 join_type=plan.join_type, build_side=build)
    if plan.condition is not None and plan.join_type == "inner":
        # residual condition = post-join filter (sound for inner)
        return FilterExec(joined, plan.condition)
    return joined


def _to_physical(meta: PlanMeta, conf: SrtConf):
    # TopN fusion: Limit(Sort) both replaceable -> TopNExec
    if (isinstance(meta.plan, Limit) and len(meta.child_plans) == 1
            and isinstance(meta.child_plans[0].plan, Sort)
            and meta.can_this_be_replaced
            and meta.child_plans[0].can_this_be_replaced
            and conf.get(SQL_ENABLED)):
        sort_meta = meta.child_plans[0]
        grandkids = [_to_physical(c, conf)
                     for c in sort_meta.child_plans]
        dev = [c if isinstance(c, TpuExec) else HostToDeviceExec(c)
               for c in grandkids]
        order = [SortOrder(o.expr, o.ascending, o.nulls_first)
                 for o in sort_meta.plan.order]
        return TopNExec(dev[0], order, meta.plan.n)
    children = [_to_physical(c, conf) for c in meta.child_plans]
    if meta.can_this_be_replaced and conf.get(SQL_ENABLED):
        dev = [c if isinstance(c, TpuExec) else HostToDeviceExec(c)
               for c in children]
        return _build_tpu_exec(meta.plan, dev, conf)
    host = [c if not isinstance(c, TpuExec) else DeviceToHostBridge(c)
            for c in children]
    return CpuPhysical(meta.plan, host)


# --- EnsureRequirements: place exchanges ----------------------------------

def _pin_partitioning(node: TpuExec) -> None:
    """Disable partition-count-changing AQE transforms in ``node`` and
    every descendant down to (and including) the first exchange — a
    partition-wise parent depends on the advertised layout."""
    from ..exec.exchange import ShuffleExchangeExec
    node.preserve_partitioning = True
    if isinstance(node, ShuffleExchangeExec):
        return
    for c in node.children:
        _pin_partitioning(c)


def ensure_distribution(node: TpuExec, conf: SrtConf) -> TpuExec:
    """Insert shuffle/broadcast exchanges wherever a child's output
    partitioning does not satisfy its parent's required distribution
    (Spark EnsureRequirements; reference stages are glued the same way —
    GpuShuffleExchangeExecBase between partial and final aggregates,
    co-partitioning for GpuShuffledHashJoinExec, GpuRangePartitioner
    under global sort)."""
    from ..exec.exchange import BroadcastExchangeExec, ShuffleExchangeExec
    from .distribution import (AllTuples, BroadcastDistribution,
                               ClusteredDistribution, OrderedDistribution)
    # recurse into device children (and through host islands)
    node.children = [ensure_distribution(c, conf) for c in node.children]
    if hasattr(node, "cpu_child"):
        node.cpu_child = _ensure_physical(node.cpu_child, conf)
    if not conf.get(EXCHANGE_ENABLED):
        return node
    reqs = node.required_child_distributions()
    n_parts = conf.get(SHUFFLE_PARTITIONS)
    clustered = [r for r in reqs if isinstance(r, ClusteredDistribution)]
    if len(clustered) > 1:
        # co-partitioning (join): all clustered children must agree on
        # the partition count, so pin it in the requirement
        for r in clustered:
            r.num_partitions = n_parts
    out_children = []
    for child, req in zip(node.children, reqs):
        if child.output_partitioning.satisfies(req):
            # the parent will consume this child partition-wise WITHOUT
            # a re-exchange: AQE transforms inside the child (partition
            # coalescing, adaptive broadcast) must not change its
            # partition count/grouping
            if isinstance(req, ClusteredDistribution):
                _pin_partitioning(child)
            out_children.append(child)
        elif isinstance(req, BroadcastDistribution):
            out_children.append(BroadcastExchangeExec(child))
        elif isinstance(req, AllTuples):
            out_children.append(ShuffleExchangeExec(child, [],
                                                    num_partitions=1))
        elif isinstance(req, ClusteredDistribution):
            out_children.append(ShuffleExchangeExec(
                child, req.exprs, num_partitions=n_parts))
        elif isinstance(req, OrderedDistribution):
            if n_parts > 1:
                out_children.append(ShuffleExchangeExec(
                    child, [], num_partitions=n_parts,
                    sort_orders=req.sort_orders))
            else:
                out_children.append(child)
        else:
            out_children.append(child)
    node.children = out_children
    return node


def _ensure_physical(physical, conf: SrtConf):
    """Walk a mixed host/device physical tree applying
    ensure_distribution to every device island."""
    if isinstance(physical, TpuExec):
        return ensure_distribution(physical, conf)
    if isinstance(physical, DeviceToHostBridge):
        physical.tpu = ensure_distribution(physical.tpu, conf)
        physical.children = [physical.tpu]
        return physical
    if isinstance(physical, CpuPhysical):
        physical.children = [_ensure_physical(c, conf)
                             for c in physical.children]
        return physical
    return physical


def push_down_filters(plan: LogicalPlan) -> None:
    """Filter-over-scan pushdown (ParquetFilters role): the scan prunes
    row groups/files with the translatable conjuncts; the Filter node
    stays, so device-side semantics are unchanged."""
    from ..io.scan import FileScan
    for i, c in enumerate(plan.children):
        push_down_filters(c)
        if isinstance(plan, Filter) and isinstance(c, FileScan) \
                and c.pushed_filter is None:
            plan.children[i] = c.with_pushed_filter(plan.condition)


def prune_scan_columns(plan: LogicalPlan) -> None:
    """ColumnPruning (Spark's rule of the same name): narrow each
    FileScan's schema to the columns referenced between it and the
    nearest column-REPLACING ancestor (Project/Aggregate/Expand). A q6
    over a 16-column lineitem then decodes 4 columns instead of 16 —
    on the host-decode scan path this is the single largest I/O lever.
    Scans are replaced by narrowed COPIES (they're shared across
    DataFrames). CachedRelation prunes the same way — a projection over
    df.cache() decompresses only the referenced column blocks
    (ParquetCachedBatchSerializer selectedAttributes role)."""
    from ..cache import CachedRelation
    from ..io.scan import FileScan

    def node_refs(node: LogicalPlan) -> set:
        refs = set()
        for e in node.expressions():
            refs |= e.references()
        return refs

    def walk(node: LogicalPlan, required) -> None:
        # required: set of column names the PARENT needs from this
        # node's output; None = everything (no boundary seen yet)
        for i, c in enumerate(node.children):
            creq = _child_required(node, c, required)
            if isinstance(c, (FileScan, CachedRelation)):
                if creq is None:
                    continue
                keep = [(n, t) for n, t in c.schema if n in creq]
                if not keep:
                    # count(*)-style: keep one spine column (narrowest)
                    keep = [min(c.schema, key=lambda nt:
                                8 if nt[1].is_nested else
                                4 if nt[1] == dt.STRING else 1)]
                if len(keep) < len(c.schema):
                    node.children[i] = c.with_schema(keep)
                continue
            walk(c, creq)

    def _child_required(node, child, required):
        from .logical import (Aggregate, Expand, Generate, Project,
                              Union, Window)
        if isinstance(node, (Project, Aggregate, Expand)):
            # boundary: output is fully determined by the expressions
            return node_refs(node)
        if isinstance(node, Union):
            # positional semantics: never narrow below a union
            return None
        if required is None:
            return None
        if isinstance(node, (Window, Generate)):
            gen = {n for n, _ in node.schema} - \
                  {n for n, _ in child.schema}
            return (required - gen) | node_refs(node)
        return required | node_refs(node)

    walk(plan, None)


def _force_perfile_for_input_file(plan: LogicalPlan) -> None:
    """InputFileBlockRule (GpuOverrides.scala InputFileBlockRule role):
    input_file_name()/input_file_block_* need a single source file per
    batch, so scans below such expressions must not use the coalescing
    (file-mixing) reader. Marks every FileScan in the subtree."""
    from ..expr.misc import contains_input_file
    from ..io.scan import FileScan

    def mark(node: LogicalPlan) -> None:
        if isinstance(node, FileScan):
            node.options["_reader_override"] = "PERFILE"
        for c in node.children:
            mark(c)

    def walk(node: LogicalPlan) -> None:
        exprs = [e for e, _ in node.expressions_with_schemas()]
        if contains_input_file(exprs):
            mark(node)
        for c in node.children:
            walk(c)

    walk(plan)


def apply_overrides(plan: LogicalPlan, conf: Optional[SrtConf] = None):
    """wrap -> tag -> convert (GpuOverrides.applyWithContext equivalent).

    Returns the physical root: a TpuExec (device result) or a
    CpuPhysical/DeviceToHostBridge (host result).
    """
    conf = conf or active_conf()
    push_down_filters(plan)
    prune_scan_columns(plan)
    _force_perfile_for_input_file(plan)
    meta = PlanMeta(plan)
    meta.tag_for_tpu()
    from .cost import apply_cost_model
    apply_cost_model(meta, conf)
    mode = conf.get(EXPLAIN)
    if mode == "ALL":
        print("\n".join(meta.explain_lines()))
    elif mode == "NOT_ON_TPU":
        lines = meta.explain_lines(only_not_on_tpu=True)
        if lines:
            print("\n".join(lines))
    root = _ensure_physical(_to_physical(meta, conf), conf)
    _count_exchange_consumers(root)
    root = _insert_fusion(root, conf)
    root = _insert_pipeline(plan, root, conf)
    _tag_push(root, conf)
    return root


def _insert_fusion(root, conf: SrtConf):
    """Operator-fusion pass (exec/fused.py): collapse linear
    scan -> filter -> project -> partial-aggregate chains (and their
    filter/project-only prefixes) into one FusedPipelineExec whose
    per-batch compute is a single shared-jit program, so intermediate
    batches never materialize between operators and XLA schedules the
    whole chain as one program.

    Matching is top-down from each chain terminal (a PARTIAL
    HashAggregateExec, else the topmost Filter/Project): consecutive
    Filter/Project stages are absorbed downward until the chain bottoms
    out at a scan; a chain shorter than two stages, or whose ultimate
    source is not a scan, stays unfused. A no-op CoalesceBatchesExec
    (target_rows=None — re-batches to the session default without
    changing boundaries' semantics) does not break the match: it stays
    in place as (part of) the fused node's source subtree and the
    matcher looks through it when checking for the scan.

    Opt-outs: ``srt.exec.fusion.enabled`` kills the pass;
    ``srt.exec.fusion.excludeExecs`` breaks chains at the named
    classes; stages with eager or partition-context expressions never
    fuse (``expr/misc.py::fusion_blocked``); a terminal aggregate eligible for
    the global-agg pallas lane stays unfused: that lane absorbs its
    direct Filter child itself, predicate into the kernel or as a mask
    in front of it (``HashAggregateExec._pallas_filter``).
    When the grouped pallas lane is fully enabled the fused program
    uses ``_update_pallas`` as its terminal stage instead of the stock
    update — pallas_agg as a fusable terminal.

    Fusion v2 extends the same matcher beyond linear scan chains:

    - **hash-join fusion** (``srt.exec.fusion.joins``): a chain whose
      ultimate source is a hash join wraps the join in a
      FusedHashJoinExec — build+probe and the suffix compile into one
      program per probe batch, while the join node keeps ALL of its
      orchestration (adaptive demotion/skew splits, sub-partitioning,
      bloom, DPP, growth retries). The matcher then keeps walking the
      join's children, so scan chains on the exchanges' map sides
      still fuse. Fusion arms at execute time through the join's
      ``_fusion`` hook, which is what lets plan/adaptive.py decisions
      re-evaluate after adaptive rewrites, never before.
    - **FINAL-aggregate fusion** (``srt.exec.fusion.finalAgg``): a
      FINAL HashAggregateExec whose child chain reaches its shuffle
      exchange through only no-op coalesces and fusable projects is
      armed (``arm_merge_fusion``) so the per-partition concat +
      projection prefix + merge+finalize runs as one program.
    - sort-prefix fusion (``srt.exec.fusion.sort``) needs no planner
      work — exec/sort.py self-arms from the conf at execute time."""
    if not conf.get(FUSION_ENABLED):
        return root
    from ..exec import pallas_agg
    from ..exec.aggregate import FINAL, PARTIAL
    from ..exec.fused import FusedHashJoinExec, FusedPipelineExec
    from ..exec.join import _HashJoinBase
    from ..expr.misc import fusion_blocked
    from ..io.scan import FileSourceScanExec
    excludes = {s.strip() for s in
                conf.get(FUSION_EXCLUDE_EXECS).split(",") if s.strip()}
    pallas_on = conf.get(PALLAS_ENABLED)
    grouped_conf = pallas_on and conf.get(PALLAS_GROUPED_ENABLED)
    donate_conf = conf.get(FUSION_DONATE)
    max_cap = conf.get(PALLAS_GROUP_MAX_CAPACITY)
    join_conf = conf.get(FUSION_JOINS)
    final_conf = conf.get(FUSION_FINAL_AGG)

    def stage_ok(n) -> bool:
        if type(n).__name__ in excludes:
            return False
        if isinstance(n, FilterExec):
            return not fusion_blocked([n.condition])
        if isinstance(n, ProjectExec):
            return not fusion_blocked(n.exprs)
        return False

    def agg_ok(a) -> bool:
        if type(a).__name__ in excludes or a.mode != PARTIAL or a._eager:
            return False
        if fusion_blocked(list(a.group_exprs) +
                          [fn for fn, _ in a.agg_exprs]):
            return False
        # the global-aggregate pallas lane absorbs the agg's direct
        # Filter child (_pallas_filter); fusing would steal it
        if a._pallas_gate and pallas_on:
            return False
        return True

    def through_noop_coalesce(n):
        while isinstance(n, CoalesceBatchesExec) and n.target_rows is None:
            n = n.children[0]
        return n

    def join_ok(j) -> bool:
        # a post-join condition or eager key expressions need the
        # unfused host-side evaluation; an already-armed join never
        # re-arms (idempotency)
        return (type(j).__name__ not in excludes
                and j.condition is None
                and j._fusion is None
                and not j._eager_keys())

    def try_fuse(n):
        stages = []
        cur = n
        if isinstance(cur, HashAggregateExec):
            if not agg_ok(cur):
                return n
            stages.append(cur)
            cur = cur.children[0]
        while stage_ok(cur):
            stages.append(cur)
            cur = cur.children[0]
        if not stages:
            return n
        src = through_noop_coalesce(cur)
        stages.reverse()  # application order, bottom-up
        terminal = stages[-1]
        use_pallas = bool(
            isinstance(terminal, HashAggregateExec) and grouped_conf
            and terminal._pallas_grouped_gate
            and pallas_agg.grouped_lane_on())
        if len(stages) >= 2 and isinstance(src, (BatchScanExec,
                                                 FileSourceScanExec)):
            # donation is sound only when the source's buffers are
            # single-use: file scans decode fresh arrays per run;
            # BatchScanExec re-yields the same in-memory arrays on
            # re-runs
            donate = bool(donate_conf
                          and isinstance(src, FileSourceScanExec))
            return FusedPipelineExec(cur, stages, use_pallas=use_pallas,
                                     pallas_max_cap=max_cap,
                                     donate=donate)
        if join_conf and isinstance(src, _HashJoinBase) and join_ok(src):
            # a single suffix stage is already worth it (join+stage is
            # two operators in one program); the no-op coalesce between
            # join and suffix (if any) is dropped — the fused program
            # consumes join pairs directly and re-batching boundaries
            # carry no semantics the suffix observes
            return FusedHashJoinExec(src, stages, use_pallas=use_pallas,
                                     pallas_max_cap=max_cap,
                                     donate=donate_conf)
        return n

    def try_fuse_final(a) -> None:
        if not final_conf or type(a).__name__ in excludes or a._eager \
                or a._merge_fusion is not None:
            return
        if fusion_blocked(list(a.group_exprs) +
                          [fn for fn, _ in a.agg_exprs]):
            return
        from ..exec.exchange import ShuffleExchangeExec
        projs = []
        cur = through_noop_coalesce(a.children[0])
        while isinstance(cur, ProjectExec) and stage_ok(cur):
            projs.append(cur)
            cur = through_noop_coalesce(cur.children[0])
        if not isinstance(cur, ShuffleExchangeExec):
            return
        # arm the fused concat+prefix+merge program and rewire the agg
        # straight onto its exchange (the absorbed coalesce/projects
        # run inside the fused program; projs stay in top-down order)
        a.arm_merge_fusion(projs)
        a.children[0] = cur

    def walk(n):
        if isinstance(n, (HashAggregateExec, FilterExec, ProjectExec)):
            fused = try_fuse(n)
            if fused is not n:
                if isinstance(fused, FusedHashJoinExec):
                    # keep walking below the join — the exchanges' map
                    # sides hold fusable scan chains of their own
                    kids = fused.join.children
                    for i, c in enumerate(kids):
                        kids[i] = walk(c)
                # below a fused scan chain only scan-ish sources remain
                # (scan, or no-op coalesce over scan) — nothing fusable
                return fused
        if isinstance(n, HashAggregateExec) and n.mode == FINAL:
            try_fuse_final(n)
        kids = getattr(n, "children", None)
        if kids:
            for i, c in enumerate(kids):
                kids[i] = walk(c)
        return n

    return walk(root)


def _plan_is_pipeline_safe(plan: LogicalPlan) -> bool:
    """Partition-context expressions — spark_partition_id(),
    monotonically_increasing_id(), input_file_*() — read state the
    consuming thread mutates while iterating (``ctx.partition_id``,
    the input-file TLS), which a background producer running ahead
    would race. Plans holding any of them run synchronously."""
    from ..expr.misc import (InputFileName, MonotonicallyIncreasingID,
                             SparkPartitionID, _InputFileBlock)
    ctx_types = (InputFileName, _InputFileBlock, SparkPartitionID,
                 MonotonicallyIncreasingID)

    def expr_has(e) -> bool:
        if isinstance(e, ctx_types):
            return True
        return any(expr_has(c) for c in e.children)

    def walk(node) -> bool:
        if any(expr_has(e) for e in node.expressions()):
            return False
        return all(walk(c) for c in node.children)

    return walk(plan)


def _insert_pipeline(plan: LogicalPlan, root, conf: SrtConf):
    """Pipelining pass (exec/pipeline.py): wrap every eligible
    FileSourceScanExec in a PrefetchExec (decode overlaps compute) and
    tag exchange instances ``_pipeline_ok`` so their read side / the
    broadcast build drains through a background producer. Exchanges
    are TAGGED rather than wrapped: AQE transforms locate them with
    direct-child isinstance checks that an interposed node would break.
    Scans already forced to the PERFILE reader by an input_file_name()
    ancestor stay synchronous (the expression reads per-batch TLS the
    producer thread would own), and whole plans with partition-context
    expressions opt out via ``_plan_is_pipeline_safe``."""
    if not conf.get(PIPELINE_ENABLED) or not _plan_is_pipeline_safe(plan):
        return root
    from ..exec.exchange import BroadcastExchangeExec, ShuffleExchangeExec
    from ..exec.pipeline import PrefetchExec
    from ..io.scan import FileSourceScanExec

    def walk(n):
        kids = getattr(n, "children", None)
        if kids:
            for i, c in enumerate(kids):
                kids[i] = walk(c)
        if isinstance(n, (ShuffleExchangeExec, BroadcastExchangeExec)):
            n._pipeline_ok = True
        elif isinstance(n, FileSourceScanExec) and \
                n.scan.options.get("_reader_override") != "PERFILE":
            return PrefetchExec(n)
        return n

    return walk(root)


def _tag_push(root, conf: SrtConf) -> None:
    """Push-based-shuffle pass: tag every planned ShuffleExchangeExec
    ``_push_ok`` so its map phase eagerly pushes blocks to the owning
    reducers' endpoints (exec/exchange.py ``_push_route``). Tagged, not
    wrapped, for the same reason as ``_pipeline_ok`` — AQE locates
    exchanges by direct isinstance checks. Range (sort_orders)
    exchanges are tagged too: their partition ownership follows the
    same contiguous arithmetic. Hand-built plans that skip the planner
    opt in by setting the attribute themselves."""
    from ..conf import SHUFFLE_PUSH_ENABLED
    if not conf.get(SHUFFLE_PUSH_ENABLED):
        return
    from ..exec.exchange import ShuffleExchangeExec

    def walk(n) -> None:
        if isinstance(n, ShuffleExchangeExec):
            n._push_ok = True
        for c in getattr(n, "children", []):
            walk(c)

    walk(root)


def _count_exchange_consumers(root) -> None:
    """Count, per ShuffleExchangeExec INSTANCE, how many tree edges
    drain it. Full expansion, no dedup: a subtree shared by the two
    halves of a full-outer union (``_build_join``) really is drained
    twice per run. The exchange frees its shuffle blocks only after
    that many full drains (exec/exchange.py ``_release``)."""
    from ..exec.exchange import ShuffleExchangeExec
    counts: Dict[int, int] = {}
    insts: Dict[int, object] = {}

    def walk(n) -> None:
        if isinstance(n, ShuffleExchangeExec):
            counts[id(n)] = counts.get(id(n), 0) + 1
            insts[id(n)] = n
        for c in getattr(n, "children", []):
            walk(c)

    walk(root)
    for k, x in insts.items():
        x._planned_consumers = counts[k]


def mesh_resident_exchanges(root, conf: Optional[SrtConf] = None) -> set:
    """Planner residency rule for the mesh lane: the set of
    ``ShuffleExchangeExec`` ids (``id(node)``) whose collective is the
    identity on the mesh, because the child's advertised partitioning
    already satisfies the exchange's target placement
    (distribution.mesh_placement_satisfied). The mesh stage executor
    lowers these as device-resident hand-throughs pinned with
    ``with_sharding_constraint`` — whole stage DAGs stay on device
    until a true repartition forces an in-program ``all_to_all``.

    This is the generalization of the old ``_hash_colocated`` special
    case (hash-over-hash only) to range-over-range and
    single-over-single, promoted from the lowering into the planner so
    the decision is visible (MeshResidencyPlanned event) before any
    program compiles. Gated by ``srt.mesh.residency.enabled`` and the
    push-shuffle locality confs the single-box bypass honors — the
    placement contract is the same one.
    """
    from ..conf import (MESH_RESIDENCY, SHUFFLE_PUSH_ENABLED,
                        SHUFFLE_PUSH_LOCAL_BYPASS, active_conf)
    from ..exec.exchange import ShuffleExchangeExec
    from ..obs import events as _events
    from .distribution import mesh_placement_satisfied
    conf = conf or active_conf()
    if not (conf.get(MESH_RESIDENCY) and conf.get(SHUFFLE_PUSH_ENABLED)
            and conf.get(SHUFFLE_PUSH_LOCAL_BYPASS)):
        return set()
    resident: set = set()

    def walk(n) -> None:
        if isinstance(n, ShuffleExchangeExec) and id(n) not in resident:
            child = n.children[0]
            if mesh_placement_satisfied(child.output_partitioning, n):
                resident.add(id(n))
        for c in getattr(n, "children", []):
            walk(c)

    walk(root)
    if resident:
        _events.emit("MeshResidencyPlanned", count=len(resident))
    return resident


def tag_only(plan: LogicalPlan,
             conf: Optional[SrtConf] = None) -> PlanMeta:
    """Tagging pass without conversion (explain-only mode — the
    reference's spark.rapids.sql.mode=explainOnly). Applies the cost
    model too when a conf enables it, so explain output matches what
    apply_overrides would actually plan."""
    meta = PlanMeta(plan)
    meta.tag_for_tpu()
    from .cost import apply_cost_model
    apply_cost_model(meta, conf or active_conf())
    return meta


# --- supported-ops doc-gen (TypeChecks.scala doc generation) ---------------

def generate_supported_ops_doc() -> str:
    """Reference-style per-op support matrices (TypeChecks doc-gen ->
    docs/supported_ops.md): one row per expression, one column per type
    tag. The cells come straight from each registered rule's TypeSig —
    the SAME object the tagging pass enforces at plan time, so the doc
    cannot over-promise relative to the planner."""
    tags = ts.ALL_TAGS
    short = {ts.BOOLEAN: "BOOL", ts.BYTE: "I8", ts.SHORT: "I16",
             ts.INT: "I32", ts.LONG: "I64", ts.FLOAT: "F32",
             ts.DOUBLE: "F64", ts.STRING: "STR", ts.DATE: "DATE",
             ts.TIMESTAMP: "TS", ts.DECIMAL_64: "DEC64",
             ts.DECIMAL_128: "DEC128", ts.NULL: "NULL",
             ts.ARRAY: "ARR", ts.STRUCT: "STRUCT", ts.MAP: "MAP"}
    header = "| Expression | " + " | ".join(short[t] for t in tags) + " |"
    sep = "|---" * (len(tags) + 1) + "|"
    lines = [
        "# Supported ops on TPU", "",
        "Generated from the expression/exec rule registries "
        "(`spark_rapids_tpu/plan/overrides.py`) — do not edit. The "
        "matrices render the exact TypeSig objects the tagging pass "
        "enforces, so plan-time behavior and this document cannot "
        "diverge.", "",
        "`S` = supported input type on device; `NS` = the containing "
        "operator falls back to the CPU engine for that input type.",
        "", "## Expressions", "", header, sep]
    for cls in sorted(_EXPR_RULES, key=lambda c: c.__name__):
        rule = _EXPR_RULES[cls]
        cells = [" S " if t in rule.sig.tags else "NS" for t in tags]
        lines.append(f"| {cls.__name__} | " + " | ".join(cells) + " |")
    lines += [
        "", "## Operators", "",
        "Column types flowing THROUGH an operator follow "
        "`device_type_ok` (all basic types + decimal128; arrays and "
        "structs of those through project/filter/generate; maps on "
        "CPU). Operator-specific key restrictions are tagged at plan "
        "time (e.g. no nested/decimal128 group-by or join keys).", "",
        "| Operator | Notes |", "|---|---|"]
    for cls in sorted(_EXEC_RULES, key=lambda c: c.__name__):
        rule = _EXEC_RULES[cls]
        desc = (rule.description or (cls.__doc__ or "").strip()
                .split("\n")[0])
        lines.append(f"| {cls.__name__} | {desc} |")
    return "\n".join(lines) + "\n"
