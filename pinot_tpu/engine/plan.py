"""Per-segment plan: QueryContext + segment metadata -> kernel spec + params.

Re-design of the reference's plan maker + predicate evaluators
(``InstancePlanMakerImplV2.makeSegmentPlanNode:227``,
``operator/filter/predicate/*``): the *spec* is a hashable structural
description of the computation (filter tree shape, predicate strategies,
aggregation set, group-by layout) that keys the kernel cache; the *params*
are the runtime values (dictId intervals, LUTs, literals, group strides)
passed as device arrays so queries differing only in literals reuse the
compiled kernel.

Predicate translation exploits sorted dictionaries: EQ/RANGE become dictId
compares, IN/REGEXP become a boolean LUT over the dictionary gathered on
device (the vectorized analogue of dictId-set predicate evaluators).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.engine.aggregates import AggDef, agg_value_expr, resolve_agg
from pinot_tpu.engine.errors import QueryError, UnsupportedQueryError
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.expressions import (
    Expr,
    FilterNode,
    FilterOp,
    Function,
    Identifier,
    Literal,
    Predicate,
    PredicateType,
)
from pinot_tpu.segment.immutable import DataSource, ImmutableSegment
from pinot_tpu.spi.data import DataType

# group-by scatter limit: beyond this the composed key space is too large for
# dense device arrays and execution falls back to the host path
# (the reference's analogue knob: numGroupsLimit, InstancePlanMakerImplV2.java:67)
MAX_DEVICE_GROUPS = 1 << 21

_I32_MAX = np.iinfo(np.int32).max

_ARITH_OPS = {"plus", "minus", "times", "divide", "mod", "floordiv"}

# Epoch-arithmetic transforms compile to exact device integer ops (the
# device equivalents of the reference's vectorized datetime transform
# functions, operator/transform/function/DateTimeConversionTransformFunction
# et al. — fixed-width units only; calendar units stay host-evaluated).
# Unit widths come from the host function registry so the oracle and the
# device rewrite share one source of truth.
from pinot_tpu.query.functions import TIME_UNIT_MS as _UNIT_MS
from pinot_tpu.query.functions import TRUNC_UNIT_MS as _TRUNC_MS

_TIME_DIV = {
    "toepochseconds": _UNIT_MS["SECONDS"],
    "toepochminutes": _UNIT_MS["MINUTES"],
    "toepochhours": _UNIT_MS["HOURS"],
    "toepochdays": _UNIT_MS["DAYS"]}
_TIME_MUL = {
    "fromepochseconds": _UNIT_MS["SECONDS"],
    "fromepochminutes": _UNIT_MS["MINUTES"],
    "fromepochhours": _UNIT_MS["HOURS"],
    "fromepochdays": _UNIT_MS["DAYS"]}


def _device_transform_rewrite(e: Function) -> Optional[Expr]:
    """Time transform -> equivalent plus/minus/times/mod/floordiv tree, or
    None when the function isn't device-expressible. Rewrites happen at
    PLAN time only, so response column names keep the user's expression."""
    n = e.name
    if n in _TIME_DIV and len(e.args) == 1:
        return Function("floordiv", (e.args[0], Literal(_TIME_DIV[n])))
    if n in _TIME_MUL and len(e.args) == 1:
        return Function("times", (e.args[0], Literal(_TIME_MUL[n])))
    if (n == "datetrunc" and len(e.args) == 2
            and isinstance(e.args[0], Literal)):
        q = _TRUNC_MS.get(str(e.args[0].value).lower())
        if q == 1:
            return e.args[1]
        if q:
            # trunc(v, q) = v - (v mod q): exact for negatives too (floor
            # semantics match the host datetrunc's floordiv-multiply)
            return Function("minus", (e.args[1],
                                      Function("mod",
                                               (e.args[1], Literal(q)))))
        return None
    if (n == "timeconvert" and len(e.args) == 3
            and all(isinstance(a, Literal) for a in e.args[1:])):
        ma = _UNIT_MS.get(str(e.args[1].value).upper())
        mb = _UNIT_MS.get(str(e.args[2].value).upper())
        if ma is None or mb is None:
            return None
        inner: Expr = e.args[0] if ma == 1 else \
            Function("times", (e.args[0], Literal(ma)))
        return inner if mb == 1 else \
            Function("floordiv", (inner, Literal(mb)))
    return None


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


@dataclass
class SegmentPlan:
    """The executable plan for one (query, segment) pair."""

    spec: Tuple              # hashable kernel-cache key (incl. static sizes)
    params: List[np.ndarray]  # runtime arrays, kernel consumes in order
    columns: List[str]       # columns to stage
    # (strategy, column | gexpr base) per group expr (decode reads these)
    group_defs: List[Tuple[str, Any]]
    group_cards: List[int]   # per group col: size of its key space
    group_strides: Optional[np.ndarray]  # row-major key strides (decode uses)
    num_groups: int          # padded total group count (0 = not group-by)
    agg_defs: List[AggDef]
    # per group col: key-space offset the kernel subtracts (0 unless the
    # column is graw/gexpr or its dictId range was filter-narrowed)
    group_bases: List[int] = field(default_factory=list)


class PlanError(UnsupportedQueryError):
    """Query shape the device kernels don't cover -> host fallback.

    Every PlanError carries a machine-readable ``reason_code`` for the
    path-decision ledger (common/tracing.py): pass ``reason=`` at the
    raise site or rely on the message classifier — either way a decline
    is never ``unknown`` (the bench gates on that)."""

    def __init__(self, message: str, reason: Optional[str] = None):
        super().__init__(message)
        self._reason = reason

    @property
    def reason_code(self) -> str:
        if self._reason is not None:
            return self._reason
        from pinot_tpu.common.tracing import classify_decline

        self._reason = classify_decline(str(self))
        return self._reason


# --------------------------------------------------------------------------
# star-tree node plan: the pre-aggregation rung of the device ladder
# --------------------------------------------------------------------------

# pseudo-column namespace for star-tree node arrays: the kernel spec reads
# these keys out of the staged node-column tree (engine/staging.py
# startree_nodes), never a segment forward index
def startree_dim_key(col: str) -> str:
    return f"stdim:{col}"


def startree_metric_key(fn: str, col: str) -> str:
    return f"stmetric:{fn}__{col}"


@dataclass
class StarTreePlan:
    """Executable device plan over one star-tree's node arrays.

    The spec is a regular kernel spec (same ops, same param protocol, same
    cache) whose capacity is the padded SELECTED-record count — the kernel
    aggregates a gathered node slice, so the dense/hash group-by rungs and
    the packed-output machinery apply unchanged. ``agg_map`` records how
    the rewritten pre-agg leaves reassemble into the ORIGINAL aggregation
    states (count -> sum of the count column, avg -> sum+count pair)."""

    spec: Tuple
    params: List[np.ndarray]
    columns: List[str]            # pseudo node-column keys the kernel reads
    group_cols: List[str]         # real dimension names (key decode)
    group_cards: List[int]
    group_bases: List[int]
    group_strides: Optional[np.ndarray]
    num_groups: int
    agg_map: List[Tuple[str, List[int]]]  # (base, rewritten leaf indexes)


def plan_star_tree(ctx, segment, tree, matches: Dict[str, Any],
                   num_selected: int) -> StarTreePlan:
    """Star-tree device eligibility + spec build. ``matches`` carries the
    per-dimension dictId matches ``startree_exec.resolve_matches`` already
    translated (the fit check in ``pick_star_tree`` has passed). Reuses the
    PR-1 dictId-narrowing idea: a predicated group dimension's key range
    shrinks to its match bounds, so selective Q2.x shapes land on the dense
    rung outright. Raises PlanError when the node slice can't ride the
    device kernels (the host walker serves instead)."""
    from pinot_tpu.engine.startree_exec import _pairs_needed
    from pinot_tpu.segment.startree import match_bounds

    aggs = [resolve_agg(f) for f in ctx.aggregations]
    params: List[np.ndarray] = []
    columns: List[str] = []

    group_cols: List[str] = []
    group_specs: List[Tuple] = []
    group_cards: List[int] = []
    group_bases: List[int] = []
    num_groups = 0
    if ctx.group_by:
        for e in ctx.group_by:
            # pick_star_tree guarantees Identifier group exprs on tree dims
            col = e.name
            cm = segment.metadata.column(col)
            lo, hi = 0, cm.cardinality - 1
            if col in matches:
                mlo, mhi = match_bounds(matches[col])
                lo, hi = max(lo, mlo), min(hi, mhi)
                if lo > hi:
                    lo, hi = 0, 0  # unsatisfiable: 1-slot key space
            group_cols.append(col)
            group_cards.append(hi - lo + 1)
            group_bases.append(lo)
            key = startree_dim_key(col)
            group_specs.append(("gdict", key))
            if key not in columns:
                columns.append(key)
        total = 1
        for c in group_cards:
            total *= c
            if total > MAX_DEVICE_GROUPS:
                raise PlanError("star-tree group key space too large "
                                "-> host walker")
        num_groups = _next_pow2(total)
        strides = np.ones(len(group_cards), dtype=np.int32)
        for i in range(len(group_cards) - 2, -1, -1):
            strides[i] = strides[i + 1] * group_cards[i + 1]
        params.append(strides)
        params.append(np.asarray(group_bases, dtype=np.int64))
    else:
        strides = None

    # rewrite aggregations onto the pre-aggregated metric columns: COUNT
    # becomes SUM over the count column, AVG splits into SUM+COUNT leaves
    # reassembled at decode (ref: StarTreeGroupByExecutor reading
    # AggregationFunctionColumnPair columns instead of raw values)
    agg_specs: List[Tuple] = []
    agg_map: List[Tuple[str, List[int]]] = []

    def leaf(fn: str, col: str) -> int:
        key = startree_metric_key(fn, col)
        acc = "i64" if fn == "count" else "f64"
        op = "sum" if fn in ("count", "sum") else fn
        agg_specs.append((op, False, ("col", key, False), acc))
        if key not in columns:
            columns.append(key)
        return len(agg_specs) - 1

    for agg, fn in zip(aggs, ctx.aggregations):
        pairs = _pairs_needed(agg, fn)
        if pairs is None:  # pick_star_tree admitted it; stay defensive
            raise PlanError(f"aggregation {agg.name} has no pre-agg pairs")
        if agg.base == "avg":
            (sfn, scol), (cfn, ccol) = pairs
            agg_map.append(("avg", [leaf(sfn, scol), leaf(cfn, ccol)]))
        else:
            (pfn, pcol), = pairs
            agg_map.append((agg.base, [leaf(pfn, pcol)]))

    capacity = max(128, _next_pow2(max(1, num_selected)))
    spec = (("true",), tuple(agg_specs), tuple(group_specs), num_groups,
            capacity)
    expected = expected_param_count(spec)
    if len(params) != expected:
        raise AssertionError(
            f"star-tree param pack/unpack drift: packed {len(params)} but "
            f"the spec consumes {expected} (spec={spec[:3]!r})")
    return StarTreePlan(spec=spec, params=params, columns=columns,
                        group_cols=group_cols, group_cards=group_cards,
                        group_bases=group_bases, group_strides=strides,
                        num_groups=num_groups, agg_map=agg_map)


def plan_segment(ctx: QueryContext, segment: ImmutableSegment) -> SegmentPlan:
    if getattr(segment, "is_mutable", False):
        # consuming segments are host-resident (unsorted dictionaries, live
        # append) — served by the host engine until sealed (SURVEY.md §7)
        raise PlanError("mutable segment -> host path")
    params: List[np.ndarray] = []
    columns: List[str] = []

    filter_spec = _compile_filter(ctx.filter, segment, params, columns)
    # collected BEFORE the validdocs placeholder shifts the param slots
    dict_ranges = (_conjunctive_dict_ranges(filter_spec, params)
                   if ctx.group_by else {})

    if getattr(segment, "valid_doc_ids", None) is not None:
        # upsert-managed: AND a point-in-time snapshot of the live valid-doc
        # bitmap into the filter (the validDocIds contract,
        # ref: IndexSegment.getValidDocIds ANDed into every filter). The
        # param rides FIRST, before the filter's params, as a PLACEHOLDER:
        # the executor substitutes the version-cached device mask (or a
        # fresh host snapshot for unversioned bitmaps) at run time, so the
        # O(capacity) copy isn't paid when the cache will win anyway.
        params.insert(0, None)
        filter_spec = ("and", (("validdocs",), filter_spec))

    agg_defs = [resolve_agg(f) for f in ctx.aggregations]

    group_specs: List[Tuple] = []
    group_defs: List[Tuple[str, Any]] = []
    group_cards: List[int] = []
    group_bases: List[int] = []
    pending_gexpr: List[Tuple[int, Expr]] = []
    num_groups = 0
    if ctx.group_by:
        for e in ctx.group_by:
            strat, payload, card, base = _group_strategy(e, segment,
                                                         dict_ranges)
            group_cards.append(card)
            group_bases.append(base)
            if strat == "gexpr":
                # compiled AFTER strides/bases so the kernel's param-cursor
                # order (strides, bases, then key-expression literals)
                # matches the order the params list is built in
                group_specs.append(None)
                group_defs.append((strat, base))  # decode adds base back
                pending_gexpr.append((len(group_specs) - 1, e))
            else:
                group_specs.append((strat, payload))
                group_defs.append((strat, payload))
                if payload not in columns:
                    columns.append(payload)
        total = 1
        for c in group_cards:
            total *= c
            if total > MAX_DEVICE_GROUPS:
                raise PlanError(
                    f"group key space {total}+ exceeds device limit")
        num_groups = _next_pow2(total)
        # strides (row-major over group columns) + value-base offsets;
        # the executor's key decode reuses these exact strides
        strides = np.ones(len(group_cards), dtype=np.int32)
        for i in range(len(group_cards) - 2, -1, -1):
            strides[i] = strides[i + 1] * group_cards[i + 1]
        params.append(strides)
        params.append(np.asarray(group_bases, dtype=np.int64))
        for idx, e in pending_gexpr:
            group_specs[idx] = (
                "gexpr", _compile_value(e, segment, params, columns))
        grouped = True
    else:
        strides = None
        grouped = False

    agg_specs: List[Tuple] = []
    for agg, fn in zip(agg_defs, ctx.aggregations):
        ok = agg.device_grouped if grouped else agg.device_scalar
        if not ok:
            raise PlanError(f"aggregation {agg.name} not device-supported "
                            f"{'grouped' if grouped else 'scalar'}")
        vexpr = agg_value_expr(fn)
        if agg.base == "distinctcounthll" and not agg.mv:
            # device HLL: per-dictId (bucket, rank) LUTs precomputed from
            # the dictionary's hashes; register update = masked scatter-max
            # (ref: DistinctCountHLLAggregationFunction; utils/hll.py)
            from pinot_tpu.utils.hll import DEFAULT_LOG2M

            if not isinstance(vexpr, Identifier) or vexpr.name.startswith("$"):
                raise PlanError("DISTINCTCOUNTHLL argument must be a column")
            cm = segment.metadata.column(vexpr.name)
            if not (cm.has_dictionary and cm.single_value):
                raise PlanError("DISTINCTCOUNTHLL needs an SV dict column")
            m = 1 << DEFAULT_LOG2M
            if num_groups and (num_groups + 1) * m > (1 << 23):
                raise PlanError("grouped HLL register space too large")
            d = segment.data_source(vexpr.name).dictionary
            bucket, rank = d.hll_register_luts(DEFAULT_LOG2M)
            params.append(bucket)
            params.append(rank)
            agg_specs.append(("distinctcounthll", vexpr.name, DEFAULT_LOG2M))
            if vexpr.name not in columns:
                columns.append(vexpr.name)
            continue
        if agg.base == "distinctcount" and not agg.mv:
            # checked before value compilation: the presence-bitmap kernel
            # reads dictIds directly, so non-numeric (string) columns are
            # fine here even though they have no device value expression
            if not isinstance(vexpr, Identifier) or vexpr.name.startswith("$"):
                raise PlanError("DISTINCTCOUNT argument must be a column")
            cm = segment.metadata.column(vexpr.name)
            if not cm.has_dictionary:
                raise PlanError("DISTINCTCOUNT on raw column -> host")
            if not cm.single_value:
                raise PlanError("DISTINCTCOUNT on MV column -> host")
            if cm.cardinality > (1 << 20):
                # the presence vector is [cardinality]: past ~1M ids the
                # D2H outweighs the scan (use DISTINCTCOUNTHLL there, like
                # the reference recommends at scale)
                raise PlanError("DISTINCTCOUNT cardinality too large -> host")
            agg_specs.append(("distinctcount", vexpr.name, cm.cardinality))
            if vexpr.name not in columns:
                columns.append(vexpr.name)
            continue
        fanout = 1
        if vexpr is None:
            vspec = None
        elif agg.mv:
            if not isinstance(vexpr, Identifier) or vexpr.name.startswith("$"):
                raise PlanError("MV aggregation argument must be a column")
            cm = segment.metadata.column(vexpr.name)
            if cm.single_value or not cm.data_type.is_numeric:
                raise PlanError(f"{agg.name} needs a numeric MV column")
            vspec = ("colmv", vexpr.name)
            fanout = max(1, cm.max_num_multi_values)
            if vexpr.name not in columns:
                columns.append(vexpr.name)
        else:
            vspec = _compile_value(vexpr, segment, params, columns)
        acc = _acc_dtype(agg.base, vexpr, segment, fanout)
        agg_specs.append((agg.base, agg.mv, vspec, acc))

    spec = (filter_spec, tuple(agg_specs), tuple(group_specs), num_groups,
            segment.padded_capacity)
    expected = expected_param_count(spec)
    if len(params) != expected:
        raise AssertionError(
            f"param pack/unpack drift: packed {len(params)} params but the "
            f"spec consumes {expected} — plan.py and the kernel param "
            f"tables disagree (spec={spec[:3]!r})")
    return SegmentPlan(spec=spec, params=params, columns=columns,
                       group_defs=group_defs, group_cards=group_cards,
                       group_strides=strides, num_groups=num_groups,
                       agg_defs=agg_defs, group_bases=group_bases)


# --------------------------------------------------------------------------
# accumulator narrowing (v5e-shaped kernels: f64/i64 are emulated on TPU, so
# capacity-sized accumulation runs in i32/f32 whenever column stats bound the
# values; partials are widened to i64/f64 at kernel output for exact
# cross-segment merging)
# --------------------------------------------------------------------------

def _value_kind(e: Expr, segment: ImmutableSegment):
    """('int', max_abs|None) when the expression is integral on device,
    ('float', None) otherwise. Integer bounds propagate through
    plus/minus/times/mod/floordiv (and the epoch-transform rewrites) so
    expression aggregations like ``sum(lo_extendedprice * lo_discount)``
    or ``sum(toEpochDays(ts))`` accumulate EXACTLY in i32/i64 instead of
    drifting in f32; true division stays float."""
    if isinstance(e, Literal):
        if isinstance(e.value, bool) or isinstance(e.value, int):
            return ("int", abs(int(e.value)))
        return ("float", None)
    if isinstance(e, Identifier):
        cm = segment.metadata.column(e.name)
        if cm.data_type.is_integral:
            if cm.min_value is None or cm.max_value is None:
                return ("int", None)
            return ("int", max(abs(int(cm.min_value)),
                               abs(int(cm.max_value))))
        return ("float", None)
    if isinstance(e, Function):
        rewritten = _device_transform_rewrite(e)
        if rewritten is not None:
            return _value_kind(rewritten, segment)
        if (e.name in ("plus", "minus", "times", "mod", "floordiv")
                and len(e.args) == 2):
            kinds = [_value_kind(a, segment) for a in e.args]
            if all(k[0] == "int" for k in kinds):
                (_, la), (_, ra) = kinds
                if e.name == "mod":
                    # |a mod b| < |b| under floor semantics
                    return ("int", ra)
                if e.name == "floordiv":
                    # |a // b| <= |a| for integral |b| >= 1
                    return ("int", la)
                if la is None or ra is None:
                    return ("int", None)
                return ("int", la * ra if e.name == "times" else la + ra)
    return ("float", None)


def _acc_dtype(base: str, vexpr: Optional[Expr], segment: ImmutableSegment,
               fanout: int = 1) -> str:
    """``fanout`` is the MV entries-per-doc bound (1 for SV): MV sums/counts
    accumulate up to capacity*fanout terms, not capacity."""
    if vexpr is None:  # count(*): docs per segment always fit i32
        return "i32"
    if base == "count":
        # count(col) counts docs (SV) or total MV entries (fanout > 1)
        return ("i32" if segment.padded_capacity * fanout <= _I32_MAX
                else "i64")
    kind, max_abs = _value_kind(vexpr, segment)
    if kind == "float":
        return "f32"
    if base in ("min", "max", "minmaxrange"):
        return "i32" if (max_abs is not None and max_abs <= _I32_MAX) else "i64"
    # sum/avg: the whole-segment sum must fit the accumulator exactly
    if (max_abs is not None
            and max_abs * segment.padded_capacity * fanout <= _I32_MAX):
        return "i32"
    return "i64"


# --------------------------------------------------------------------------
# filter-aware dictId narrowing: predicates in the filter's top-level AND
# conjunction bound the dictIds any LIVE doc can carry in those columns, so
# a group column under such a predicate needs only the narrowed key range —
# the composed key space of selective queries (SSB Q3.3/Q3.4/Q4.3 shape)
# drops below the sparse threshold and takes the dense (often Pallas-
# eligible) rung outright. The reference narrows the same way by feeding
# filtered dictId sets to DictionaryBasedGroupKeyGenerator (SURVEY §2.4).
# --------------------------------------------------------------------------

# params consumed per compiled filter op (must mirror kernels._emit_filter)
_FILTER_PARAMS = {
    "true": 0, "false": 0, "validdocs": 1, "isnull": 0, "isnotnull": 0,
    "eq": 1, "neq": 1, "range": 1, "lut": 1,
    "mv_eq": 1, "mv_neq": 1, "mv_range": 1, "mv_lut": 1,
    "veq": 1, "vneq": 1, "vrange": 2, "vin": 1, "vnotin": 1,
}

# params consumed per compiled value op (must mirror kernels._emit_value;
# "fn" is structural — its args carry the params, like and/or/not in the
# filter tree). "colmv" is absent deliberately: MV values never route
# through _emit_value (the MV branch reads dense mv + counts, 0 params).
_VALUE_PARAMS = {"lit": 1, "col": 0, "fn": 0}


def _count_value_params(vspec: Optional[Tuple]) -> int:
    if vspec is None or vspec[0] == "colmv":
        return 0
    n = _VALUE_PARAMS[vspec[0]]
    if vspec[0] == "fn":
        n += sum(_count_value_params(a) for a in vspec[2])
    return n


def expected_param_count(spec: Tuple) -> int:
    """Number of runtime params the kernel-side cursor consumes for
    ``spec`` — the pack-time half of the runtime protocol mirror (the
    consume-time half is ``_ParamCursor.finish()``). Walks the spec with
    the same per-op tables the static protocol lint verifies both sides
    against, so a dynamically-built spec that drifts fails loudly here
    instead of silently mis-keying results."""
    filter_spec, agg_specs, group_specs, _num_groups, _cap = spec

    def walk_filter(node: Tuple) -> int:
        op = node[0]
        if op in ("and", "or", "not"):
            return sum(walk_filter(c) for c in node[1])
        return _FILTER_PARAMS[op]

    n = walk_filter(filter_spec)
    if group_specs:
        n += 2  # the strides + bases arrays, in that order
        for gspec in group_specs:
            if gspec[0] == "gexpr":
                n += _count_value_params(gspec[1])
    for aspec in agg_specs:
        if aspec[0] == "distinctcounthll":
            n += 2  # per-dictId (bucket, rank) register LUTs
        elif aspec[0] != "distinctcount":
            n += _count_value_params(aspec[2])
    return n


def narrow_plan_groups(plan: SegmentPlan,
                       ranges: List[Tuple[int, int]]) -> SegmentPlan:
    """Rebuild a group-by plan with each group column's key range narrowed
    to the OBSERVED dictId bounds ``ranges`` (inclusive, raw dictIds — the
    pallas group-range probe's output). Exact: the bounds are min/max over
    the very rows the filter matches, so no live doc composes a key outside
    the narrowed space. The narrowed plan keeps the spec shape (and the
    params list length/order — only the strides/bases arrays are replaced
    in place), so kernels, pack/unpack, and the group decode apply
    unchanged; ``_narrowed_from`` carries the original spec for the
    executor's per-shape blocklists."""
    assert plan.group_cards and len(ranges) == len(plan.group_cards)
    cards: List[int] = []
    bases: List[int] = []
    for (lo, hi), card, base in zip(ranges, plan.group_cards,
                                    plan.group_bases):
        lo = max(base, int(lo))
        hi = min(base + card - 1, int(hi))
        if lo > hi:            # no matched rows touched this column
            lo = hi = base
        cards.append(hi - lo + 1)
        bases.append(lo)
    total = 1
    for c in cards:
        total *= c
    num_groups = _next_pow2(total)
    strides = np.ones(len(cards), dtype=np.int32)
    for i in range(len(cards) - 2, -1, -1):
        strides[i] = strides[i + 1] * cards[i + 1]

    filter_spec, agg_specs, group_specs, _old, capacity = plan.spec
    spec = (filter_spec, agg_specs, group_specs, num_groups, capacity)

    def walk_filter(node: Tuple) -> int:
        op = node[0]
        if op in ("and", "or", "not"):
            return sum(walk_filter(c) for c in node[1])
        return _FILTER_PARAMS[op]

    n_filter = walk_filter(filter_spec)
    params = list(plan.params)
    params[n_filter] = strides
    params[n_filter + 1] = np.asarray(bases, dtype=np.int64)
    narrowed = SegmentPlan(
        spec=spec, params=params, columns=list(plan.columns),
        group_defs=list(plan.group_defs), group_cards=cards,
        group_strides=strides, num_groups=num_groups,
        agg_defs=plan.agg_defs, group_bases=bases)
    narrowed._narrowed_from = getattr(plan, "_narrowed_from", plan.spec)
    return narrowed


def _conjunctive_dict_ranges(filter_spec: Tuple,
                             params: List[np.ndarray]
                             ) -> Dict[str, Tuple[int, int]]:
    """column -> (lo, hi) inclusive dictId bounds implied for every doc the
    filter can match, collected only along pure-AND paths from the root
    (predicates under OR/NOT prove nothing). Repeated predicates meet
    (intersect); an empty meet means the filter matches nothing."""
    ranges: Dict[str, Tuple[int, int]] = {}

    def meet(col: str, lo: int, hi: int) -> None:
        cur = ranges.get(col)
        ranges[col] = ((max(cur[0], lo), min(cur[1], hi))
                       if cur else (lo, hi))

    def walk(node: Tuple, i: int, conj: bool) -> int:
        op = node[0]
        if op == "and":
            for c in node[1]:
                i = walk(c, i, conj)
            return i
        if op in ("or", "not"):
            for c in node[1]:
                i = walk(c, i, False)
            return i
        if conj:
            if op == "eq":
                did = int(params[i])
                meet(node[1], did, did)
            elif op == "range":
                iv = np.asarray(params[i])
                meet(node[1], int(iv[0]), int(iv[1]))
            elif op == "lut":
                idx = np.nonzero(np.asarray(params[i]))[0]
                if idx.size:
                    meet(node[1], int(idx[0]), int(idx[-1]))
                else:
                    meet(node[1], 1, 0)  # matches nothing
        return i + _FILTER_PARAMS[op]

    walk(filter_spec, 0, True)
    return ranges


# --------------------------------------------------------------------------
# group-by strategies
# --------------------------------------------------------------------------

def _value_bounds(e: Expr, segment: ImmutableSegment
                  ) -> Optional[Tuple[int, int]]:
    """(lo, hi) integer bounds of a device-compilable expression via
    interval arithmetic over column stats, or None when unbounded /
    non-integral. Feeds the 'gexpr' group strategy: a bounded integral
    expression's value space is a dense key range, exactly like a raw int
    column's (ref: the value-based group key generators,
    NoDictionarySingleColumnGroupKeyGenerator)."""
    if isinstance(e, Literal):
        if isinstance(e.value, bool) or not isinstance(e.value, int):
            return None
        return (e.value, e.value)
    if isinstance(e, Identifier):
        if e.name.startswith("$"):
            return None
        cm = segment.metadata.column(e.name)
        if (not cm.single_value or not cm.data_type.is_integral
                or cm.min_value is None or cm.max_value is None):
            return None
        return (int(cm.min_value), int(cm.max_value))
    if isinstance(e, Function):
        rw = _device_transform_rewrite(e)
        if rw is not None:
            return _value_bounds(rw, segment)
        if e.name not in ("plus", "minus", "times", "mod", "floordiv") \
                or len(e.args) != 2:
            return None
        a = _value_bounds(e.args[0], segment)
        b = _value_bounds(e.args[1], segment)
        if a is None or b is None:
            return None
        (alo, ahi), (blo, bhi) = a, b
        if e.name == "plus":
            return (alo + blo, ahi + bhi)
        if e.name == "minus":
            return (alo - bhi, ahi - blo)
        if e.name == "times":
            corners = (alo * blo, alo * bhi, ahi * blo, ahi * bhi)
            return (min(corners), max(corners))
        # mod / floordiv: positive-constant divisor only (floor semantics)
        if blo != bhi or blo <= 0:
            return None
        if e.name == "mod":
            return (0, blo - 1)
        return (alo // blo, ahi // blo)
    return None


def _group_strategy(e: Expr, segment: ImmutableSegment,
                    dict_ranges: Optional[Dict[str, Tuple[int, int]]] = None
                    ) -> Tuple[str, Any, int, int]:
    """-> (strategy, payload, cardinality, base). Payload is the column
    name for gdict/graw; for 'gexpr' the EXPRESSION (compiled to a device
    value spec after strides/bases take their param slots).
    ``dict_ranges`` carries the filter-narrowed dictId bounds per column."""
    if isinstance(e, Identifier):
        if e.name.startswith("$"):
            raise PlanError("group-by on virtual column -> host path")
        cm = segment.metadata.column(e.name)
        if not cm.single_value:
            raise PlanError("group-by on MV column -> host path")
        if cm.has_dictionary:
            # key = dictId - narrowed base
            # (ref: DictionaryBasedGroupKeyGenerator.java:62)
            lo, hi = (dict_ranges or {}).get(e.name, (0, cm.cardinality - 1))
            lo = max(0, lo)
            hi = min(cm.cardinality - 1, hi)
            if lo > hi:
                # the conjunction is unsatisfiable for this column: no doc
                # survives the filter, a 1-slot key space is enough
                lo, hi = 0, 0
            return ("gdict", e.name, hi - lo + 1, lo)
        if cm.data_type.is_integral:
            lo, hi = int(cm.min_value), int(cm.max_value)
            span = hi - lo + 1
            if span > MAX_DEVICE_GROUPS:
                raise PlanError("raw int group-by span too large")
            # key = value - min (value-space; psum-able across segments
            # that share the base -- used by the sharded combine path)
            return ("graw", e.name, span, lo)
        raise PlanError("group-by on raw float column -> host path")
    # bounded integral EXPRESSION (time buckets: GROUP BY toEpochDays(ts),
    # dateTrunc('hour', ts), ...): key = expr value - lo
    bounds = _value_bounds(e, segment)
    if bounds is None:
        raise PlanError(f"group-by expression {e} -> host path")
    lo, hi = bounds
    span = hi - lo + 1
    if span <= 0 or span > MAX_DEVICE_GROUPS:
        raise PlanError("group-by expression span too large -> host path")
    return ("gexpr", e, span, lo)


# --------------------------------------------------------------------------
# filter compilation
# --------------------------------------------------------------------------

def _compile_filter(node: Optional[FilterNode], segment: ImmutableSegment,
                    params: List[np.ndarray], columns: List[str]) -> Tuple:
    if node is None:
        return ("true",)
    return _compile_node(node, segment, params, columns)


def _compile_node(node: FilterNode, segment: ImmutableSegment,
                  params: List[np.ndarray], columns: List[str]) -> Tuple:
    if node.op is FilterOp.AND:
        return ("and", tuple(_compile_node(c, segment, params, columns)
                             for c in node.children))
    if node.op is FilterOp.OR:
        return ("or", tuple(_compile_node(c, segment, params, columns)
                            for c in node.children))
    if node.op is FilterOp.NOT:
        return ("not", (_compile_node(node.children[0], segment, params, columns),))
    return _compile_predicate(node.predicate, segment, params, columns)


def _conv(ds: DataSource, v: Any) -> Any:
    try:
        return ds.metadata.data_type.convert(v)
    except (ValueError, TypeError) as e:
        raise QueryError(f"cannot convert {v!r} for column {ds.name!r}: {e}")


def _compile_predicate(pred: Predicate, segment: ImmutableSegment,
                       params: List[np.ndarray], columns: List[str]) -> Tuple:
    t = pred.type

    if t in (PredicateType.IS_NULL, PredicateType.IS_NOT_NULL):
        cols = pred.lhs.columns()
        if not cols:
            raise QueryError(f"predicate references no column: {pred}")
        col = cols[0]
        cm = segment.metadata.column(col)
        if not cm.has_nulls:
            return ("false",) if t is PredicateType.IS_NULL else ("true",)
        if col not in columns:
            columns.append(col)
        return ("isnull", col) if t is PredicateType.IS_NULL else ("isnotnull", col)

    if not isinstance(pred.lhs, Identifier):
        raise PlanError(f"expression predicate {pred.lhs} -> host path")

    col = pred.lhs.name
    if col.startswith("$"):
        raise PlanError("virtual column predicate -> host path")
    ds = segment.data_source(col)
    cm = ds.metadata
    if col not in columns:
        columns.append(col)
    mvp = "" if cm.single_value else "mv_"

    if cm.has_dictionary:
        d = ds.dictionary
        card = cm.cardinality
        # Exclusive predicates on MV columns require ALL values to satisfy
        # (ref: BaseDictionaryBasedPredicateEvaluator.applyMV isExclusive):
        # compile the inclusive form and negate the per-doc result.
        if not cm.single_value and t in (PredicateType.NOT_EQ,
                                         PredicateType.NOT_IN):
            from dataclasses import replace
            inner_t = (PredicateType.EQ if t is PredicateType.NOT_EQ
                       else PredicateType.IN)
            inner = _compile_predicate(replace(pred, type=inner_t), segment,
                                       params, columns)
            return ("not", (inner,))
        if t in (PredicateType.EQ, PredicateType.NOT_EQ):
            did = d.index_of(_conv(ds, pred.value))
            params.append(np.int32(did))
            return (mvp + ("eq" if t is PredicateType.EQ else "neq"), col)
        if t is PredicateType.RANGE:
            lo = _conv(ds, pred.lower) if pred.lower is not None else None
            hi = _conv(ds, pred.upper) if pred.upper is not None else None
            try:
                a, b = d.range_to_dict_id_interval(lo, hi,
                                                   pred.lower_inclusive,
                                                   pred.upper_inclusive)
            except TypeError:
                # unsorted (mutable) dictionary: ids are arrival-ordered,
                # so a contiguous interval doesn't exist — value-scan to a
                # dictId LUT instead (same kernel op as IN)
                ids = d.matching_range_ids(lo, hi, pred.lower_inclusive,
                                           pred.upper_inclusive)
                lut = np.zeros(d.cardinality, dtype=bool)
                lut[ids] = True
                params.append(lut)
                return (mvp + "lut", col, card)
            params.append(np.array([a, b], dtype=np.int32))
            return (mvp + "range", col)
        if t in (PredicateType.IN, PredicateType.NOT_IN,
                 PredicateType.REGEXP_LIKE, PredicateType.TEXT_MATCH,
                 PredicateType.JSON_MATCH):
            if t is PredicateType.JSON_MATCH and not cm.single_value:
                raise PlanError("JSON_MATCH on MV column is unsupported")
            lut = _build_lut(ds, pred)
            params.append(lut)
            return (mvp + "lut", col, card)
        raise PlanError(f"predicate {t} -> host path")

    # RAW column
    if not cm.single_value:
        raise PlanError("raw MV column predicate -> host path")
    if t in (PredicateType.EQ, PredicateType.NOT_EQ):
        v = _conv(ds, pred.value)
        dt = _raw_np_dtype(cm)
        if cm.data_type.is_integral:
            info = np.iinfo(dt)
            if not (info.min <= int(v) <= info.max):
                # literal outside the staged dtype's range can't match any
                # stored value (all values fit the narrowed dtype)
                return ("false",) if t is PredicateType.EQ else ("true",)
        params.append(np.asarray(v, dtype=dt))
        return ("veq" if t is PredicateType.EQ else "vneq", col)
    if t is PredicateType.RANGE:
        bounds = _raw_bounds(cm, ds, pred)
        if bounds is None:  # range provably empty for the staged dtype
            return ("false",)
        lo, hi, lo_inc, hi_inc = bounds
        params.append(lo)
        params.append(hi)
        return ("vrange", col, lo_inc, hi_inc)
    if t in (PredicateType.IN, PredicateType.NOT_IN):
        dt = _raw_np_dtype(cm)
        conv = [_conv(ds, v) for v in pred.values]
        if cm.data_type.is_integral:
            info = np.iinfo(dt)
            conv = [v for v in conv if info.min <= int(v) <= info.max]
        vals = np.array(conv, dtype=dt)
        if vals.size == 0:
            return ("false",) if t is PredicateType.IN else ("true",)
        params.append(vals)
        return ("vin" if t is PredicateType.IN else "vnotin", col, len(vals))
    raise PlanError(f"predicate {t} on raw column -> host path")


def _raw_np_dtype(cm) -> np.dtype:
    """Param dtype matching the staged raw forward array (no promotion)."""
    from pinot_tpu.engine.staging import staged_int_dtype

    return (staged_int_dtype(cm) if cm.data_type.is_integral
            else np.dtype(np.float64))


def _raw_bounds(cm, ds: DataSource, pred: Predicate):
    """(lo, hi, lo_inclusive, hi_inclusive) in the staged dtype, or None if
    the range is provably empty. A literal outside the narrowed dtype's range
    either makes the bound unrestrictive (replace with an inclusive dtype
    extreme — every stored value fits the dtype) or the range empty."""
    dt = _raw_np_dtype(cm)
    lo_inc, hi_inc = pred.lower_inclusive, pred.upper_inclusive
    if cm.data_type.is_integral:
        info = np.iinfo(dt)
        if pred.lower is None:
            lo, lo_inc = info.min, True
        else:
            lv = int(_conv(ds, pred.lower))
            if lv > info.max:
                return None          # x >/>= lv is impossible
            if lv < info.min:
                lo, lo_inc = info.min, True   # bound unrestrictive
            else:
                lo = lv
        if pred.upper is None:
            hi, hi_inc = info.max, True
        else:
            uv = int(_conv(ds, pred.upper))
            if uv < info.min:
                return None          # x </<= uv is impossible
            if uv > info.max:
                hi, hi_inc = info.max, True   # bound unrestrictive
            else:
                hi = uv
        return (np.asarray(lo, dtype=dt), np.asarray(hi, dtype=dt),
                lo_inc, hi_inc)
    lo = np.float64(_conv(ds, pred.lower)) if pred.lower is not None \
        else np.float64(float("-inf"))
    hi = np.float64(_conv(ds, pred.upper)) if pred.upper is not None \
        else np.float64(float("inf"))
    return lo, hi, lo_inc, hi_inc


def _build_lut(ds: DataSource, pred: Predicate) -> np.ndarray:
    """Boolean dictId lookup table (the vectorized dictId-set evaluator)."""
    d = ds.dictionary
    card = d.cardinality
    t = pred.type
    lut = np.zeros(card, dtype=bool)
    if t in (PredicateType.IN, PredicateType.NOT_IN):
        for v in pred.values:
            i = d.index_of(_conv(ds, v))
            if i >= 0:
                lut[i] = True
        if t is PredicateType.NOT_IN:
            lut = ~lut
        return lut
    if t is PredicateType.REGEXP_LIKE:
        try:
            rx = re.compile(str(pred.value))
        except re.error as e:
            raise QueryError(f"bad regex {pred.value!r}: {e}")
        reader = getattr(ds, "fst_index", None)
        if reader is not None:
            # FST prefix narrowing: verify the regexp only inside the
            # trie-resolved dictId interval (ref: FSTBasedRegexpPredicateEvaluator)
            lut[reader.matching_ids(str(pred.value))] = True
            return lut
        for i, v in enumerate(d.get_values(range(card))):
            if rx.search(str(v)):
                lut[i] = True
        return lut
    if t is PredicateType.JSON_MATCH:
        # parse each DISTINCT value once; the doc mask is then a dictId
        # gather on device (JSON_MATCH rides the TPU scan like IN/REGEXP)
        from pinot_tpu.segment.jsonindex import (
            match_json_value,
            parse_match_filter,
        )

        try:
            ast = parse_match_filter(str(pred.value))
        except ValueError as e:
            raise QueryError(f"bad JSON_MATCH filter: {e}")
        for i, v in enumerate(d.get_values(range(card))):
            if match_json_value(v, ast):
                lut[i] = True
        return lut
    # TEXT_MATCH: tokenized index when present (dictId postings -> LUT,
    # so the query rides the device scan); the index-less decay evaluates
    # the SAME dialect per distinct value
    from pinot_tpu.segment.textindex import match_text_value, parse_text_query

    try:
        reader = getattr(ds, "text_index", None)
        if reader is not None:
            lut[reader.matching_ids(str(pred.value))] = True
            return lut
        ast = parse_text_query(str(pred.value))
    except ValueError as e:
        raise QueryError(f"bad TEXT_MATCH query: {e}")
    for i, v in enumerate(d.get_values(range(card))):
        if match_text_value(v, ast):
            lut[i] = True
    return lut


# --------------------------------------------------------------------------
# value-expression compilation
# --------------------------------------------------------------------------

def _compile_value(e: Expr, segment: ImmutableSegment,
                   params: List[np.ndarray], columns: List[str]) -> Tuple:
    if isinstance(e, Literal):
        if not isinstance(e.value, (int, float, bool)) or e.value is None:
            raise PlanError(f"non-numeric literal {e} in value expression")
        params.append(np.float64(e.value))
        return ("lit",)
    if isinstance(e, Identifier):
        if e.name.startswith("$"):
            raise PlanError("virtual column in value expression -> host")
        cm = segment.metadata.column(e.name)
        if not cm.single_value:
            raise PlanError(f"MV column {e.name} in value expression")
        if not cm.data_type.is_numeric:
            raise PlanError(f"non-numeric column {e.name} in value expression")
        if e.name not in columns:
            columns.append(e.name)
        return ("col", e.name, cm.has_dictionary)
    if isinstance(e, Function):
        if e.name not in _ARITH_OPS:
            rewritten = _device_transform_rewrite(e)
            if rewritten is None:
                raise PlanError(f"transform {e.name} -> host path")
            return _compile_value(rewritten, segment, params, columns)
        args = tuple(_compile_value(a, segment, params, columns) for a in e.args)
        return ("fn", e.name, args)
    raise PlanError(f"cannot compile value expression {e}")
