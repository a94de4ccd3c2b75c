"""Host (numpy) evaluation of filters and expressions over a segment.

This is the CPU execution path, used for (a) selection queries (data movement,
not compute — the device adds nothing), (b) consuming/mutable segments that
are not yet device-staged (mirroring the reference, where the realtime tail
is served from the mutable segment), and (c) as the oracle the device kernels
are tested against.

Predicate semantics follow the reference's filter operators
(``operator/filter/*``): on multi-value columns a predicate matches a doc if
ANY value matches (ref: MV doc-id iterators).
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

import numpy as np

from pinot_tpu.engine.errors import QueryError, UnsupportedQueryError
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


# --------------------------------------------------------------------------
# Filter evaluation -> boolean doc mask
# --------------------------------------------------------------------------

def eval_filter(segment: ImmutableSegment, node: Optional[FilterNode]) -> np.ndarray:
    n = segment.num_docs
    if node is None:
        mask = np.ones(n, dtype=bool)
    else:
        mask = _eval_node(segment, node)
    valid = getattr(segment, "valid_doc_ids", None)
    if valid is not None:
        # upsert: only the live doc per primary key is visible
        # (ref: IndexSegment.getValidDocIds AND-ed into every filter)
        mask = mask & np.asarray(valid[:n])
    return mask


def _eval_node(segment: ImmutableSegment, node: FilterNode) -> np.ndarray:
    if node.op is FilterOp.AND:
        out = _eval_node(segment, node.children[0])
        for c in node.children[1:]:
            out = out & _eval_node(segment, c)
        return out
    if node.op is FilterOp.OR:
        out = _eval_node(segment, node.children[0])
        for c in node.children[1:]:
            out = out | _eval_node(segment, c)
        return out
    if node.op is FilterOp.NOT:
        return ~_eval_node(segment, node.children[0])
    return eval_predicate(segment, node.predicate)


def _matching_dict_ids(ds: DataSource, pred: Predicate) -> np.ndarray:
    """Predicate -> sorted array of matching dictIds (the host analogue of
    the reference's dictionary-based predicate evaluators,
    ``operator/filter/predicate/*``)."""
    d = ds.dictionary
    card = d.cardinality
    t = pred.type
    dt = ds.metadata.data_type

    def conv(v):
        try:
            return dt.convert(v)
        except (ValueError, TypeError) as e:
            raise QueryError(f"cannot convert {v!r} for column "
                             f"{ds.name!r} ({dt.label}): {e}")

    if t is PredicateType.EQ:
        i = d.index_of(conv(pred.value))
        return np.array([i] if i >= 0 else [], dtype=np.int64)
    if t is PredicateType.NOT_EQ:
        i = d.index_of(conv(pred.value))
        ids = np.arange(card, dtype=np.int64)
        return ids[ids != i] if i >= 0 else ids
    if t is PredicateType.IN:
        ids = sorted({d.index_of(conv(v)) for v in pred.values} - {-1})
        return np.array(ids, dtype=np.int64)
    if t is PredicateType.NOT_IN:
        hit = {d.index_of(conv(v)) for v in pred.values} - {-1}
        return np.array([i for i in range(card) if i not in hit], dtype=np.int64)
    if t is PredicateType.RANGE:
        lo = conv(pred.lower) if pred.lower is not None else None
        hi = conv(pred.upper) if pred.upper is not None else None
        if hasattr(d, "matching_range_ids"):
            # unsorted (mutable) dictionary: value scan, not dictId interval
            return d.matching_range_ids(lo, hi, pred.lower_inclusive,
                                        pred.upper_inclusive)
        a, b = d.range_to_dict_id_interval(lo, hi, pred.lower_inclusive,
                                           pred.upper_inclusive)
        return np.arange(max(a, 0), min(b, card - 1) + 1, dtype=np.int64)
    if t is PredicateType.REGEXP_LIKE:
        try:
            rx = re.compile(str(pred.value))
        except re.error as e:
            raise QueryError(f"bad regex {pred.value!r}: {e}")
        reader = getattr(ds, "fst_index", None)
        if reader is not None:
            return reader.matching_ids(str(pred.value))
        return np.array([i for i, v in enumerate(d.get_values(range(card)))
                         if rx.search(str(v))], dtype=np.int64)
    if t is PredicateType.TEXT_MATCH:
        from pinot_tpu.segment.textindex import (
            match_text_value,
            parse_text_query,
        )

        try:
            reader = getattr(ds, "text_index", None)
            if reader is not None:
                # tokenized inverted index -> dictId postings
                # (ref: TextMatchFilterOperator over TextIndexReader)
                return reader.matching_ids(str(pred.value))
            # index-less decay: SAME query dialect, evaluated per distinct
            # value (results must not depend on whether the index exists)
            ast = parse_text_query(str(pred.value))
        except ValueError as e:
            raise QueryError(f"bad TEXT_MATCH query: {e}")
        return np.array([i for i, v in enumerate(d.get_values(range(card)))
                         if match_text_value(v, ast)], dtype=np.int64)
    raise UnsupportedQueryError(f"predicate {t} not supported on "
                                f"dictionary column {ds.name!r}")


def eval_predicate(segment: ImmutableSegment, pred: Predicate) -> np.ndarray:
    n = segment.num_docs
    # IS_NULL / IS_NOT_NULL read the null bitmap regardless of encoding
    if pred.type in (PredicateType.IS_NULL, PredicateType.IS_NOT_NULL):
        col = _predicate_column(pred)
        ds = segment.data_source(col)
        nb = ds.null_bitmap
        isnull = (np.asarray(nb[:n]) if nb is not None
                  else np.zeros(n, dtype=bool))
        return isnull if pred.type is PredicateType.IS_NULL else ~isnull

    if not isinstance(pred.lhs, Identifier):
        # expression predicate: evaluate values then compare
        return _eval_expr_predicate(segment, pred)

    if pred.lhs.name.startswith("$"):
        vals = _virtual_column_values(segment, pred.lhs.name, n)
        dt = (DataType.LONG if vals.dtype.kind == "i" else DataType.STRING)
        return _compare_values(vals, pred, dt)

    ds = segment.data_source(pred.lhs.name)
    cm = ds.metadata

    if pred.type is PredicateType.JSON_MATCH:
        return _eval_json_match(ds, pred, n)

    # RANGE over a range-indexed RAW column: binary search + slice instead
    # of a full compare scan (ref: RangeIndexBasedFilterOperator)
    if (pred.type is PredicateType.RANGE and not cm.has_dictionary
            and cm.single_value
            and getattr(ds, "range_order", None) is not None):
        return _range_index_mask(ds, pred, n)

    # Exclusive predicates on MV columns: ALL values must satisfy
    # (ref: BaseDictionaryBasedPredicateEvaluator.applyMV isExclusive) —
    # evaluate the inclusive form and negate.
    if not cm.single_value and pred.type in (PredicateType.NOT_EQ,
                                             PredicateType.NOT_IN):
        from dataclasses import replace
        inner_t = (PredicateType.EQ if pred.type is PredicateType.NOT_EQ
                   else PredicateType.IN)
        return ~eval_predicate(segment, replace(pred, type=inner_t))

    if cm.has_dictionary:
        ids = _matching_dict_ids(ds, pred)
        if cm.single_value:
            if len(ids) == 0:
                return np.zeros(n, dtype=bool)
            if cm.has_inverted_index and len(ids) <= max(4, cm.cardinality // 8):
                # posting lists beat a full scan for selective predicates
                # (ref: BitmapBasedFilterOperator vs ScanBasedFilterOperator
                # selection in FilterOperatorUtils)
                mask = np.zeros(n, dtype=bool)
                for i in ids:
                    mask[ds.doc_ids_for_dict_id(int(i))] = True
                return mask
            fwd = np.asarray(ds.forward_index[:n])
            if len(ids) == int(ids[-1] - ids[0]) + 1:  # contiguous interval
                return (fwd >= ids[0]) & (fwd <= ids[-1])
            return np.isin(fwd, ids)
        offsets = np.asarray(ds.mv_offsets)
        flat = np.asarray(ds.forward_index)
        if len(ids) == 0:
            return np.zeros(n, dtype=bool)
        hit = np.isin(flat, ids)
        # any per row: reduceat over CSR offsets (empty rows -> False)
        return _any_per_row(hit, offsets, n)

    # RAW column: compare values directly
    vals = np.asarray(ds.forward_index[:n])
    return _compare_values(vals, pred, cm.data_type)


def _eval_json_match(ds: DataSource, pred: Predicate, n: int) -> np.ndarray:
    """JSON_MATCH: posting lists when the column carries a JSON index,
    else parse-per-distinct-value over the dictionary (or per doc on raw)
    (ref: JsonMatchFilterOperator vs the index-less decay)."""
    from pinot_tpu.segment.jsonindex import match_json_value, parse_match_filter

    cm = ds.metadata
    if not cm.single_value:
        raise UnsupportedQueryError(
            f"JSON_MATCH on multi-value column {ds.name!r}")
    try:
        reader = getattr(ds, "json_index", None)
        if reader is not None:
            return np.asarray(reader.match(str(pred.value))[:n])
        ast = parse_match_filter(str(pred.value))
    except ValueError as e:
        raise QueryError(f"bad JSON_MATCH filter: {e}")
    if cm.has_dictionary:
        d = ds.dictionary
        lut = np.fromiter(
            (match_json_value(v, ast)
             for v in d.get_values(range(cm.cardinality))), dtype=bool,
            count=cm.cardinality)
        return lut[np.asarray(ds.forward_index[:n])]
    vals = ds.forward_index[:n]
    return np.fromiter((match_json_value(v, ast) for v in vals),
                       dtype=bool, count=n)


def _range_index_mask(ds: DataSource, pred: Predicate, n: int) -> np.ndarray:
    order = np.asarray(ds.range_order)
    sorted_vals = ds.range_sorted_values  # gathered once, cached
    dt = ds.metadata.data_type
    lo_i = 0
    hi_i = n
    if pred.lower is not None:
        v = dt.convert(pred.lower)
        side = "left" if pred.lower_inclusive else "right"
        lo_i = int(np.searchsorted(sorted_vals, v, side=side))
    if pred.upper is not None:
        v = dt.convert(pred.upper)
        side = "right" if pred.upper_inclusive else "left"
        hi_i = int(np.searchsorted(sorted_vals, v, side=side))
    mask = np.zeros(n, dtype=bool)
    if hi_i > lo_i:
        mask[order[lo_i:hi_i]] = True
    return mask


def _any_per_row(flat_hits: np.ndarray, offsets: np.ndarray, n: int) -> np.ndarray:
    counts = np.diff(offsets)
    rows = np.repeat(np.arange(n), counts)  # row index of each flat entry
    out = np.zeros(n, dtype=bool)
    out[rows[flat_hits]] = True
    return out


def _compare_values(vals: np.ndarray, pred: Predicate, dt: DataType) -> np.ndarray:
    t = pred.type

    def conv(v):
        try:
            return dt.convert(v)
        except (ValueError, TypeError) as e:
            raise QueryError(f"cannot convert {v!r} to {dt.label}: {e}")

    if t is PredicateType.EQ:
        return vals == conv(pred.value)
    if t is PredicateType.NOT_EQ:
        return vals != conv(pred.value)
    if t is PredicateType.IN:
        return np.isin(vals, [conv(v) for v in pred.values])
    if t is PredicateType.NOT_IN:
        return ~np.isin(vals, [conv(v) for v in pred.values])
    if t is PredicateType.RANGE:
        mask = np.ones(vals.shape, dtype=bool)
        if pred.lower is not None:
            lo = conv(pred.lower)
            mask &= (vals >= lo) if pred.lower_inclusive else (vals > lo)
        if pred.upper is not None:
            hi = conv(pred.upper)
            mask &= (vals <= hi) if pred.upper_inclusive else (vals < hi)
        return mask
    raise UnsupportedQueryError(f"predicate {t} not supported on raw column")


def _virtual_column_values(segment: ImmutableSegment, name: str,
                           n: int) -> np.ndarray:
    """Auto-columns every segment serves (ref: segment/virtualcolumn/* —
    DocIdVirtualColumnProvider etc.)."""
    if name == "$docId":
        return np.arange(n, dtype=np.int64)
    if name == "$segmentName":
        return np.full(n, segment.segment_name, dtype=object)
    if name == "$hostName":
        import socket

        return np.full(n, socket.gethostname(), dtype=object)
    raise UnsupportedQueryError(f"unknown virtual column {name!r}")


VIRTUAL_COLUMNS = {"$docId": "LONG", "$segmentName": "STRING",
                   "$hostName": "STRING"}


def _eval_expr_predicate(segment: ImmutableSegment, pred: Predicate) -> np.ndarray:
    geo_mask = _try_geo_index(segment, pred)
    if geo_mask is not None:
        return geo_mask
    vals = eval_expr_values(segment, pred.lhs)
    dt = (DataType.DOUBLE if np.issubdtype(np.asarray(vals).dtype, np.floating)
          else DataType.LONG)
    if np.asarray(vals).dtype == object:
        dt = DataType.STRING
    return _compare_values(np.asarray(vals), pred, dt)


def _try_geo_index(segment: ImmutableSegment,
                   pred: Predicate) -> Optional[np.ndarray]:
    """``stdistance(geoCol, 'POINT...') < r`` with a geo-indexed column:
    cell-disk prefilter + exact haversine on candidates only
    (ref: H3IndexFilterOperator). Returns None when the shape doesn't fit."""
    lhs = pred.lhs
    if not (isinstance(lhs, Function) and lhs.name in ("stdistance", "st_distance")
            and pred.type is PredicateType.RANGE
            and pred.upper is not None and pred.lower is None
            and len(lhs.args) == 2):
        return None
    col_arg, lit_arg = lhs.args
    if isinstance(col_arg, Literal) and isinstance(lit_arg, Identifier):
        col_arg, lit_arg = lit_arg, col_arg
    if not (isinstance(col_arg, Identifier) and isinstance(lit_arg, Literal)):
        return None
    if col_arg.name.startswith("$") \
            or col_arg.name not in segment.metadata.columns:
        return None
    ds = segment.data_source(col_arg.name)
    reader = getattr(ds, "geo_index", None)
    if reader is None:
        return None
    from pinot_tpu.utils import geo

    try:
        center = geo.parse_ewkt(lit_arg.value)
    except ValueError:
        return None
    if not center.geography:
        # planar (euclidean) distance: the index's haversine candidates
        # would disagree with the scalar semantics — decline
        return None
    if center.kind != "POINT":
        return None
    n = segment.num_docs
    ids = reader.ids_within(center.x, center.y, float(pred.upper),
                            inclusive=pred.upper_inclusive)
    if ids.size == 0:
        return np.zeros(n, dtype=bool)
    fwd = np.asarray(ds.forward_index[:n])
    return np.isin(fwd, ids)


def _predicate_column(pred: Predicate) -> str:
    cols = pred.lhs.columns()
    if not cols:
        raise QueryError(f"predicate references no column: {pred}")
    return cols[0]


# --------------------------------------------------------------------------
# Expression evaluation -> value arrays
# --------------------------------------------------------------------------

_ARITH = {
    "plus": np.add,
    "minus": np.subtract,
    "times": np.multiply,
    "divide": np.true_divide,
    "mod": np.mod,
}

# scalar transform functions usable host-side (subset of the reference's 42
# transform functions, operator/transform/function/*)
_UNARY = {
    "abs": np.abs,
    "ceil": np.ceil,
    "floor": np.floor,
    "exp": np.exp,
    "ln": np.log,
    "sqrt": np.sqrt,
}


def eval_expr_values(segment: ImmutableSegment, expr: Expr,
                     doc_ids: Optional[np.ndarray] = None) -> np.ndarray:
    """Evaluate an expression to per-doc values (numeric -> float/int arrays,
    strings -> object arrays). SV only; MV columns are handled by the MV
    aggregation functions."""
    n = segment.num_docs

    if isinstance(expr, Literal):
        return np.full(n if doc_ids is None else len(doc_ids), expr.value)

    if isinstance(expr, Identifier):
        if expr.name.startswith("$"):
            vals = _virtual_column_values(segment, expr.name, n)
            return vals if doc_ids is None else vals[doc_ids]
        ds = segment.data_source(expr.name)
        cm = ds.metadata
        if not cm.single_value:
            raise UnsupportedQueryError(
                f"multi-value column {expr.name!r} in expression position")
        fwd = np.asarray(ds.forward_index[:n])
        if doc_ids is not None:
            fwd = fwd[doc_ids]
        if not cm.has_dictionary:
            return fwd
        if cm.data_type.is_numeric:
            return np.asarray(ds.dictionary.device_values())[fwd]
        return np.array(ds.dictionary.get_values(fwd), dtype=object)

    if isinstance(expr, Function):
        name = expr.name
        if name in _ARITH:
            a = _to_float(eval_expr_values(segment, expr.args[0], doc_ids))
            b = _to_float(eval_expr_values(segment, expr.args[1], doc_ids))
            return _ARITH[name](a, b)
        if name in _UNARY:
            a = _to_float(eval_expr_values(segment, expr.args[0], doc_ids))
            return _UNARY[name](a)
        # scalar-registry fallback: any registered function evaluates
        # row-wise over the argument arrays (ref: the TransformFunction ->
        # ScalarFunction reflection bridge, FunctionInvoker)
        from pinot_tpu.query import functions as fnreg

        fn = fnreg.lookup(name)
        if fn is not None:
            arg_arrays = [eval_expr_values(segment, a, doc_ids)
                          for a in expr.args]
            n_rows = (len(arg_arrays[0]) if arg_arrays
                      else (n if doc_ids is None else len(doc_ids)))
            out = [fn(*(arr[i] for arr in arg_arrays))
                   for i in range(n_rows)]
            arr = np.asarray(out)
            return arr if arr.dtype != object or not out \
                else np.asarray(out, dtype=object)
        raise UnsupportedQueryError(f"transform function {name!r} not supported")

    raise UnsupportedQueryError(f"cannot evaluate expression {expr}")


def _to_float(a: np.ndarray) -> np.ndarray:
    if a.dtype == object:
        raise QueryError("arithmetic on non-numeric column")
    return a.astype(np.float64) if not np.issubdtype(a.dtype, np.floating) else a


def read_values(segment: ImmutableSegment, column: str,
                doc_ids: np.ndarray) -> List[Any]:
    """Gather output values for selection results (host path)."""
    if column.startswith("$"):
        vals = _virtual_column_values(segment, column, segment.num_docs)
        return [v.item() if hasattr(v, "item") else v
                for v in vals[doc_ids]]
    ds = segment.data_source(column)
    cm = ds.metadata
    if cm.single_value:
        fwd = np.asarray(ds.forward_index)[doc_ids]
        if not cm.has_dictionary:
            return [cm.data_type.convert(v) for v in fwd]
        return ds.dictionary.get_values(fwd)
    offsets = np.asarray(ds.mv_offsets)
    flat = np.asarray(ds.forward_index)
    d = ds.dictionary
    out = []
    for i in doc_ids:
        ids = flat[offsets[i]:offsets[i + 1]]
        out.append(d.get_values(ids))
    return out
