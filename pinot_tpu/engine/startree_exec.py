"""Star-tree query execution: fit check + pre-aggregated record aggregation.

Re-design of ``pinot-core/.../startree/StarTreeUtils.java:47``
(``isFitForStarTree`` + predicate-map extraction), the node walk
(``StarTreeFilterOperator.java:87``) and the pre-agg aggregation
(``StarTreeGroupByExecutor.java:43``); selection logic mirrors
``AggregationGroupByOrderByPlanNode.java:66-87``.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Set, Tuple

import numpy as np

from pinot_tpu.engine.aggregates import AggDef, agg_value_expr
from pinot_tpu.engine.results import AggResult, GroupByResult, QueryStats
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.expressions import (
    FilterNode,
    FilterOp,
    Function,
    Identifier,
    Predicate,
    PredicateType,
    canonical_arith_key,
)
from pinot_tpu.segment.startree import STAR, DictIdRange, StarTree

# cap on MATERIALIZED dictId sets: a predicate matching more ids than this
# never builds a python set. Contiguous runs (every RANGE over a sorted
# dictionary) decline to a DictIdRange slice check instead; only
# non-contiguous overflows (NOT_IN over a huge dictionary) bail to the scan
_MAX_RANGE_IDS = 100_000


def _flatten_and(node: Optional[FilterNode]) -> Optional[List[Predicate]]:
    """Filter -> flat AND-ed predicate list, or None when the shape doesn't
    fit (OR/NOT — the reference also bails to the normal path there)."""
    if node is None:
        return []
    if node.op is FilterOp.PREDICATE:
        return [node.predicate]
    if node.op is not FilterOp.AND:
        return None
    out: List[Predicate] = []
    for c in node.children:
        sub = _flatten_and(c)
        if sub is None:
            return None
        out.extend(sub)
    return out


def _agg_pair(agg: AggDef, fn: Function) -> Optional[Tuple[str, str]]:
    """AggDef -> (function, column) pair stored in tree records. The
    column half may be a canonical EXPRESSION key (``(a*b)``) — derived
    pre-agg pairs over +/-/* arithmetic, ref: the StarTreeV2 builder's
    derived-column function-column pairs."""
    if agg.mv:
        return None
    vexpr = agg_value_expr(fn)
    if agg.base == "count" and vexpr is None:
        return ("count", "*")
    if agg.base in ("sum", "min", "max") and vexpr is not None:
        key = canonical_arith_key(vexpr)
        if key is not None:
            return (agg.base, key)
    return None


def _pairs_needed(agg: AggDef, fn: Function) -> Optional[List[Tuple[str, str]]]:
    """Pairs the tree must store to answer this aggregation (AVG = SUM+COUNT,
    ref: AggregationFunctionColumnPair resolution)."""
    p = _agg_pair(agg, fn)
    if p is not None:
        return [p]
    vexpr = agg_value_expr(fn)
    if agg.base == "avg" and not agg.mv and vexpr is not None:
        key = canonical_arith_key(vexpr)
        if key is not None:
            return [("sum", key), ("count", "*")]
    return None


def _pair_column(fn: Function) -> str:
    """Aggregation argument -> stored pair column key ('*' for COUNT(*),
    a column name, or the canonical expression key)."""
    vexpr = agg_value_expr(fn)
    if vexpr is None:
        return "*"
    key = canonical_arith_key(vexpr)
    return key if key is not None else "*"


class StarTreePick(NamedTuple):
    """``pick_star_tree``'s result: the chosen tree, its index in
    ``segment.star_trees`` (rides the decision ledger + QueryStats), and
    the flattened AND-ed predicate list."""

    tree: StarTree
    index: int
    preds: List[Predicate]


# Specificity rank of the per-tree decline reasons: how deep in the fit
# checks a tree got before failing. With multiple trees, the MOST-specific
# reason across trees reaches the ledger — a tree missing only a function
# pair was one config line from serving; a tree whose split order lacks the
# group columns never stood a chance, and reporting the latter when the
# former exists would misdirect the operator.
_REASON_RANK = {
    "startree_group_off_split_order": 0,
    "startree_filter_non_dimension": 1,
    "startree_predicate_type_unsupported": 2,
    "startree_agg_not_pairable": 3,
    "startree_expression_agg_no_pair": 4,
    "startree_missing_function_pair": 5,
}


def _pred_match_estimate(segment, pred: Predicate, card: int) -> int:
    """Estimated count of dictIds a predicate matches — a plan-time proxy
    (never materializes id sets; tree selection must stay cheap)."""
    t = pred.type
    if t is PredicateType.EQ:
        return 1
    if t is PredicateType.IN:
        return min(card, len(pred.values))
    if t is PredicateType.NOT_EQ:
        return max(1, card - 1)
    if t is PredicateType.NOT_IN:
        return max(1, card - len(pred.values))
    if t is PredicateType.RANGE:
        try:
            d = segment.data_source(pred.lhs.name).dictionary
            if d is not None:
                a, b = d.range_to_dict_id_interval(
                    pred.lower, pred.upper, pred.lower_inclusive,
                    pred.upper_inclusive)
                return max(0, int(b) - int(a) + 1)
        except (ValueError, TypeError, KeyError):
            pass
        return max(1, card // 3)
    return card


def _estimate_records(tree: StarTree, preds: List[Predicate],
                      group_cols: List[str], segment) -> float:
    """Records-read estimate for a FITTING tree — the selection cost
    proxy: walk the split order; a predicated dim narrows to its match
    estimate, a grouped dim fans out to its cardinality, a free dim
    descends the star child (×1) unless star creation was skipped
    (×cardinality). Capped at the tree's record count (a leaf-heavy tree
    can never read more than it stores)."""
    by_col: Dict[str, int] = {}
    for p in preds:
        col = p.lhs.name
        card = segment.metadata.column(col).cardinality
        est = _pred_match_estimate(segment, p, card)
        by_col[col] = min(by_col.get(col, card), est)
    grouped = set(group_cols)
    est = 1.0
    for d in tree.config.dimensions_split_order:
        if d in by_col:
            est *= max(1, by_col[d])
        elif d in grouped or d in tree.config.skip_star_creation:
            est *= max(1, segment.metadata.column(d).cardinality)
    return min(est, float(tree.num_records))


def pick_star_tree(ctx: QueryContext, aggs: List[AggDef],
                   segment, on_decline=None) -> Optional[StarTreePick]:
    """Ref: StarTreeUtils.isFitForStarTree + StarTreeIndexConfig
    multi-tree resolution — the CHEAPEST tree satisfying the query (every
    fitting tree scored by :func:`_estimate_records`; the lower index
    breaks ties), or None. ``on_decline`` (if given) receives a
    machine-readable reason code when the segment HAS trees but none
    fits — the path-decision ledger's hook (a segment without trees is
    not a decline). With multiple trees the reported reason is the
    most-specific across trees (``_REASON_RANK``)."""

    def decline(reason: str):
        if on_decline is not None:
            on_decline(reason)
        return None

    trees = getattr(segment, "star_trees", None)
    if not trees or not ctx.is_aggregation:
        return None  # no trees / non-agg shape: not a decline (docstring)
    if getattr(segment, "valid_doc_ids", None) is not None:
        # pre-agg records ignore upsert invalidation
        return decline("startree_upsert_valid_docs")
    preds = _flatten_and(ctx.filter)
    if preds is None:
        return decline("startree_filter_or_not_shape")
    group_cols: List[str] = []
    for e in ctx.group_by:
        if not isinstance(e, Identifier):
            return decline("startree_group_expression")
        group_cols.append(e.name)

    # needed pairs are a property of the QUERY, not the tree: resolve once
    needed: List[Tuple[str, str]] = []
    for agg, fn in zip(aggs, ctx.aggregations):
        ps = _pairs_needed(agg, fn)
        if ps is None:
            # not pair-able by ANY tree: non-arith expression aggs
            # (sum(a/b), transforms) vs un-mergeable/MV aggregations
            return decline("startree_expression_agg_no_pair"
                           if isinstance(agg_value_expr(fn), Function)
                           else "startree_agg_not_pairable")
        needed.extend(ps)

    reason: Optional[str] = None

    def note(r: str) -> None:
        nonlocal reason
        if reason is None or (_REASON_RANK.get(r, 0)
                              > _REASON_RANK.get(reason, 0)):
            reason = r

    fitting: List[Tuple[float, int, StarTree]] = []
    for ti, tree in enumerate(trees):
        dims = set(tree.config.dimensions_split_order)
        if any(c not in dims for c in group_cols):
            note("startree_group_off_split_order")
            continue
        ok = True
        for p in preds:
            if not isinstance(p.lhs, Identifier) or p.lhs.name not in dims:
                note("startree_filter_non_dimension")
                ok = False
                break
            if p.type not in (PredicateType.EQ, PredicateType.IN,
                              PredicateType.NOT_EQ, PredicateType.NOT_IN,
                              PredicateType.RANGE):
                note("startree_predicate_type_unsupported")
                ok = False
                break
        if not ok:
            continue
        missing = [c for f, c in needed if not tree.has_pair(f, c)]
        if missing:
            # the Q1.x ledger code when a derived pair is absent (the
            # ROADMAP coverage gap); plain column pairs keep their own
            note("startree_expression_agg_no_pair"
                 if any(c.startswith("(") for c in missing)
                 else "startree_missing_function_pair")
            continue
        fitting.append((_estimate_records(tree, preds, group_cols, segment),
                        ti, tree))
    if not fitting:
        return decline(reason or "startree_no_fitting_tree")
    _est, ti, tree = min(fitting, key=lambda t: (t[0], t[1]))
    return StarTreePick(tree, ti, preds)


def _matching_ids(segment, pred: Predicate):
    """Predicate -> dictId match over the dimension's dictionary (reuses
    the host predicate evaluators): a set when small enough to materialize,
    a :class:`DictIdRange` when the ids are contiguous but over the cap
    (the RANGE shape), a reason STRING when neither fits (scan path
    serves; the string feeds the decision ledger)."""
    from pinot_tpu.engine.host_eval import _matching_dict_ids

    ds = segment.data_source(pred.lhs.name)
    if ds.dictionary is None:
        return "startree_raw_dimension"
    ids = _matching_dict_ids(ds, pred)
    if len(ids) > _MAX_RANGE_IDS:
        if int(ids[-1]) - int(ids[0]) + 1 == len(ids):
            return DictIdRange(int(ids[0]), int(ids[-1]))
        # non-contiguous overflow (NOT_IN over a huge dictionary): the
        # RANGE shape declines to a slice check, this cannot
        return "startree_dictid_overflow_noncontiguous"
    return set(int(i) for i in ids)


def _intersect(a, b):
    """Meet of two dictId matches (set | DictIdRange)."""
    if isinstance(a, DictIdRange) and isinstance(b, DictIdRange):
        return DictIdRange(max(a.lo, b.lo), min(a.hi, b.hi))
    if isinstance(a, DictIdRange):
        return {v for v in b if v in a}
    if isinstance(b, DictIdRange):
        return {v for v in a if v in b}
    return a & b


def resolve_matches(segment, preds: List[Predicate], on_decline=None
                    ) -> Optional[Dict[str, Any]]:
    """AND-ed predicates -> per-dimension dictId match (set | DictIdRange),
    or None when a predicate cannot be translated (the caller falls back to
    the scan path; ``on_decline`` receives the reason code). Shared by the
    host walker and the device rung."""
    matches: Dict[str, Any] = {}
    for p in preds:
        ids = _matching_ids(segment, p)
        if isinstance(ids, str):
            if on_decline is not None:
                on_decline(ids)
            return None
        col = p.lhs.name
        matches[col] = ids if col not in matches \
            else _intersect(matches[col], ids)
    return matches


def execute_star_tree(ctx: QueryContext, aggs: List[AggDef], segment,
                      tree: StarTree, preds: List[Predicate],
                      stats: Optional[QueryStats] = None):
    """-> AggResult or GroupByResult built from pre-aggregated records."""
    matches = resolve_matches(segment, preds)
    if matches is None:
        return None
    return execute_with_matches(ctx, aggs, segment, tree, matches, stats)


def execute_with_matches(ctx: QueryContext, aggs: List[AggDef], segment,
                         tree: StarTree, matches: Dict[str, Any],
                         stats: Optional[QueryStats] = None,
                         idx: Optional[np.ndarray] = None):
    """Host (numpy) aggregation over the tree-walk-selected records
    (``idx``: the caller's walk; walked here when omitted)."""
    group_cols = [e.name for e in ctx.group_by]
    if idx is None:
        idx = tree.select_records(matches, group_cols)

    if stats is not None:
        stats.num_segments_processed += 1
        stats.total_docs += segment.num_docs
        stats.num_docs_scanned += int(idx.shape[0])
        stats.num_segments_matched += 1 if idx.shape[0] else 0

    if not ctx.is_group_by:
        return AggResult([_scalar_state(tree, agg, fn, idx)
                          for agg, fn in zip(aggs, ctx.aggregations)])

    gb = GroupByResult()
    if idx.shape[0] == 0:
        return gb
    from pinot_tpu.engine.groupkeys import compose_group_keys

    dim_pos = {d: i for i, d in enumerate(tree.config.dimensions_split_order)}
    key_ids = [np.asarray(tree.dims[idx, dim_pos[c]]) for c in group_cols]
    cards = [int(k.max()) + 1 if k.size else 1 for k in key_ids]
    uniq, gid, decode_codes = compose_group_keys(key_ids, cards)

    # decode dictIds through the segment dictionaries
    codes = [decode_codes(int(u)) for u in uniq]
    keys = list(zip(*(
        segment.data_source(c).dictionary.get_values([k[j] for k in codes])
        for j, c in enumerate(group_cols))))
    n = len(uniq)
    states_per_agg = [
        _grouped_states(tree, agg, fn, idx, gid, n)
        for agg, fn in zip(aggs, ctx.aggregations)]
    for g, key in enumerate(keys):
        gb.groups[key] = [states_per_agg[a][g] for a in range(len(aggs))]
    return gb


def _metric(tree: StarTree, fn: str, col: str, idx: np.ndarray) -> np.ndarray:
    return np.asarray(tree.metrics[f"{fn}__{col}"][idx])


def _scalar_state(tree: StarTree, agg: AggDef, fn: Function,
                  idx: np.ndarray) -> Any:
    col = _pair_column(fn)
    if agg.base == "count":
        return int(_metric(tree, "count", "*", idx).sum())
    if idx.shape[0] == 0:
        return {"sum": 0.0, "min": float("inf"), "max": float("-inf"),
                "avg": (0.0, 0)}[agg.base]
    if agg.base == "sum":
        return float(_metric(tree, "sum", col, idx).sum())
    if agg.base == "min":
        return float(_metric(tree, "min", col, idx).min())
    if agg.base == "max":
        return float(_metric(tree, "max", col, idx).max())
    if agg.base == "avg":
        return (float(_metric(tree, "sum", col, idx).sum()),
                int(_metric(tree, "count", "*", idx).sum()))
    raise AssertionError(agg.base)


def _grouped_states(tree: StarTree, agg: AggDef, fn: Function,
                    idx: np.ndarray, gid: np.ndarray, n: int) -> List[Any]:
    col = _pair_column(fn)
    if agg.base == "count":
        out = np.zeros(n, dtype=np.int64)
        np.add.at(out, gid, _metric(tree, "count", "*", idx))
        return [int(v) for v in out]
    if agg.base == "sum":
        out = np.zeros(n)
        np.add.at(out, gid, _metric(tree, "sum", col, idx))
        return [float(v) for v in out]
    if agg.base == "min":
        out = np.full(n, np.inf)
        np.minimum.at(out, gid, _metric(tree, "min", col, idx))
        return [float(v) for v in out]
    if agg.base == "max":
        out = np.full(n, -np.inf)
        np.maximum.at(out, gid, _metric(tree, "max", col, idx))
        return [float(v) for v in out]
    if agg.base == "avg":
        s = np.zeros(n)
        c = np.zeros(n, dtype=np.int64)
        np.add.at(s, gid, _metric(tree, "sum", col, idx))
        np.add.at(c, gid, _metric(tree, "count", "*", idx))
        return [(float(a), int(b)) for a, b in zip(s, c)]
    raise AssertionError(agg.base)
