"""Index rung: selective conjunctive filters served by a device docId gather.

The re-design of the reference's index-based filter operators
(``BitmapBasedFilterOperator`` over ``BitmapInvertedIndexReader.java:34``,
``SortedIndexBasedFilterOperator`` over the sorted forward index,
``RangeIndexBasedFilterOperator`` over ``BitSlicedRangeIndexReader``) for the
gather-then-kernel shape PR-6 proved out for star-tree node slices:

1. HOST resolves the matching docIds — sorted-postings decode + union for
   EQ/IN over inverted columns, binary search over the sorted forward index
   or the range-index permutation, ``np.intersect1d`` across the AND
   conjuncts, shortest list first. A conjunct whose own match count is far
   over the candidates already left (or that has no index at all, beside
   one that has) is PROBED instead: evaluated on the forward index of the
   candidates alone, the reference's scan-based filter operator running
   under an index-based one in an AND. All vectorized numpy; no per-doc
   Python.
2. The docIds pad to a power-of-two capacity and ride to the device as ONE
   compact int32 array; the SAME jitted gather kernel the star-tree rung
   uses (``startree_device.build_startree_kernel``) gathers the staged
   group/value columns down to the slice and runs ``build_kernel_body``
   over the gathered block — dense/hash/sort rung selection, packed-output
   framing, and group decode all apply unchanged, so results are
   bit-identical to the full scan with ``num_docs_scanned`` = matched rows.
3. Rung selection is cost-based and runs BEFORE any posting list is
   decoded: exact per-predicate match counts come from the inverted
   index's doc-count offsets (``offsets[id+1]-offsets[id]``), from binary
   search over the sorted forward index, or from the range permutation's
   interval width. Estimates over ``SELECTIVITY_THRESHOLD`` of the table
   decline to the scan rungs — a broad filter gathers most of the table
   and the scan kernel wins.

Every outcome lands in the decision ledger under the ``index`` point
(``tracing.INDEX_DECISION_REASONS``); the gathered idx arrays are
residency-accounted and lease-pinned on the segment's resident
(``StagedSegment.index_slice``) so eviction/spill semantics compose
unchanged.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import numpy as np

from pinot_tpu.common.tracing import maybe_span, record_decision
from pinot_tpu.engine.aggregates import AggDef
from pinot_tpu.engine.plan import (
    PlanError,
    SegmentPlan,
    _next_pow2,
    expected_param_count,
)
from pinot_tpu.engine.results import QueryStats
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.expressions import Identifier, Predicate, PredicateType
from pinot_tpu.segment.dictionary import needle_for

# fraction of the table above which an estimated match count declines to the
# scan rungs: past this the gather reads most of the table anyway and the
# scan kernel's streaming access pattern wins (the FilterOperatorUtils
# bitmap-vs-scan selection heuristic, recast as a device rung gate)
SELECTIVITY_THRESHOLD = 0.05

# cap on per-dictId python-level iterations (posting-list decodes / interval
# slices). Contiguous dictId runs never hit this — they resolve as one
# interval; only scattered huge id sets bail, and those are broad filters
# the threshold gate should have declined anyway.
_MAX_ID_LISTS = 1024

_MIN_CAPACITY = 128

# a conjunct whose match count is over this many times the candidates left
# is probed on their forward index: resolving it decodes and sorts its
# whole match list to keep a sliver of it, probing reads one value a
# candidate
_PROBE_OVER = 8

_EMPTY = np.empty(0, dtype=np.int64)


def build_gather_kernel(spec):
    """Jitted ``fn(cols, idx, params, num_docs) -> packed f64 vector``:
    gathers each staged column's ROW-shaped arrays (fwd/mv/mvcount/null)
    down to the ``idx`` slice and runs the standard kernel body over the
    gathered block. ``dictvals`` stays un-gathered — it is dictId-shaped
    (the body indexes it BY the gathered fwd dictIds), which is exactly why
    the star-tree gather kernel (fwd-only trees) can't serve here."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu.engine.kernels import (
        build_kernel_body,
        pack_outputs,
        sparse_mode,
    )

    body = build_kernel_body(spec, sparse_k=sparse_mode(spec))

    def index_gather_agg(cols, idx, params, num_docs):
        gathered = {name: {k: (v if k == "dictvals" else v[idx])
                           for k, v in tree.items()}
                    for name, tree in cols.items()}
        return pack_outputs(body(gathered, params, num_docs, jnp.int32(0)),
                            spec)

    return jax.jit(index_gather_agg)


def _decline(stats: Optional[QueryStats], reason: str) -> None:
    record_decision(stats, "index", "scan", "index_gather", reason)


def _chose(stats: Optional[QueryStats], reason: str) -> None:
    record_decision(stats, "index", "index_gather", "scan", reason)


class _Decline(Exception):
    """Internal control flow: predicate routing hit an ineligible shape."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class _Route:
    """One predicate's index path: an exact match-count estimate computed
    WITHOUT decoding postings, a resolver producing the sorted unique
    int64 docId array when the cost gate passes (None: the column has no
    index, the conjunct can only be probed), and a probe giving the
    conjunct's mask over a docId array (None: a multi-value column)."""

    __slots__ = ("estimate", "resolve", "probe")

    def __init__(self, estimate: int,
                 resolve: Optional[Callable[[], np.ndarray]],
                 probe: Optional[Callable[[np.ndarray], np.ndarray]] = None):
        self.estimate = estimate
        self.resolve = resolve
        self.probe = probe


def _dict_probe(ds, ids: np.ndarray) -> Callable[[np.ndarray], np.ndarray]:
    """A single-value dictionary column's conjunct on the forward index
    of some docs: their dictIds against the matching ones."""
    def probe(idx: np.ndarray) -> np.ndarray:
        if ids.size == 0:
            return np.zeros(idx.size, dtype=bool)
        got = np.asarray(ds.forward_index)[idx]
        if int(ids[-1] - ids[0]) + 1 == ids.size:   # contiguous interval
            return (got >= ids[0]) & (got <= ids[-1])
        return np.isin(got, ids)

    return probe


def _raw_probe(ds, cm, pred: Predicate) -> Callable[[np.ndarray], np.ndarray]:
    """A single-value RAW column's conjunct on the values of some docs."""
    def probe(idx: np.ndarray) -> np.ndarray:
        from pinot_tpu.engine.host_eval import _compare_values

        return _compare_values(np.asarray(ds.forward_index)[idx], pred,
                               cm.data_type)

    return probe


def _postings_route(ds, cm, ids: np.ndarray) -> _Route:
    """EQ/IN/RANGE over an inverted-indexed dictionary column: match count
    from the doc-count offsets, docIds from varint posting decode + union."""
    if ids.size > _MAX_ID_LISTS:
        raise _Decline("index_selectivity_over_threshold")
    offsets = np.asarray(ds.inverted_index[0])
    est = int((offsets[ids + 1] - offsets[ids]).sum()) if ids.size else 0
    multi_value = not cm.single_value

    def resolve() -> np.ndarray:
        if ids.size == 0:
            return _EMPTY
        parts = [ds.doc_ids_for_dict_id(int(i)) for i in ids]
        docs = parts[0] if len(parts) == 1 else np.concatenate(parts)
        docs = docs.astype(np.int64, copy=False)
        if multi_value:
            # an MV doc may repeat a value within a row and postings of
            # different dictIds share docs — union, not concatenation
            return np.unique(docs)
        return docs if len(parts) == 1 else np.sort(docs)

    return _Route(est, resolve,
                  None if multi_value else _dict_probe(ds, ids))


def _sorted_route(ds, ids: np.ndarray, num_docs: int) -> _Route:
    """Sorted dictionary column: dictIds map to contiguous docId runs, so
    matches are binary searches over the forward index — the sorted-column
    analogue of SortedIndexReader's docId ranges."""
    probe = _dict_probe(ds, ids)
    if ids.size == 0:
        return _Route(0, lambda: _EMPTY, probe)
    fwd = np.asarray(ds.forward_index[:num_docs])
    # needles of the column's dtype: dictIds fit it, and numpy copies the
    # column to search it with any other
    needles = ids.astype(fwd.dtype, copy=False)
    if int(ids[-1] - ids[0]) + 1 == ids.size:  # contiguous dictId interval
        lo = int(np.searchsorted(fwd, needles[0], side="left"))
        hi = int(np.searchsorted(fwd, needles[-1], side="right"))
        return _Route(hi - lo, lambda: np.arange(lo, hi, dtype=np.int64),
                      probe)
    if ids.size > _MAX_ID_LISTS:
        raise _Decline("index_selectivity_over_threshold")
    los = np.searchsorted(fwd, needles, side="left")
    his = np.searchsorted(fwd, needles, side="right")
    est = int((his - los).sum())

    def resolve() -> np.ndarray:
        parts = [np.arange(lo, hi, dtype=np.int64)
                 for lo, hi in zip(los.tolist(), his.tolist()) if hi > lo]
        if not parts:
            return _EMPTY
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    return _Route(est, resolve, probe)


def _range_route(ds, cm, pred: Predicate, num_docs: int) -> _Route:
    """RANGE (or EQ, as a degenerate [v, v] range) over a range-indexed RAW
    column: binary search on the values-in-sorted-order array, slice of the
    sorted-order permutation (the host mask path's ``_range_index_mask``,
    producing docIds instead of a mask)."""
    sorted_vals = ds.range_sorted_values
    dt = cm.data_type
    lo_i, hi_i = 0, num_docs
    if pred.type is PredicateType.EQ:
        v = needle_for(sorted_vals, dt.convert(pred.value))
        lo_i = int(np.searchsorted(sorted_vals, v, side="left"))
        hi_i = int(np.searchsorted(sorted_vals, v, side="right"))
    else:
        if pred.lower is not None:
            v = needle_for(sorted_vals, dt.convert(pred.lower))
            side = "left" if pred.lower_inclusive else "right"
            lo_i = int(np.searchsorted(sorted_vals, v, side=side))
        if pred.upper is not None:
            v = needle_for(sorted_vals, dt.convert(pred.upper))
            side = "right" if pred.upper_inclusive else "left"
            hi_i = int(np.searchsorted(sorted_vals, v, side=side))
    est = max(0, hi_i - lo_i)
    order = ds.range_order

    def resolve() -> np.ndarray:
        if hi_i <= lo_i:
            return _EMPTY
        return np.sort(np.asarray(order[lo_i:hi_i]).astype(np.int64))

    return _Route(est, resolve, _raw_probe(ds, cm, pred))


def _pred_route(segment, pred: Predicate, num_docs: int) -> _Route:
    """Predicate -> index route, or raise _Decline with the ledger code."""
    lhs = pred.lhs
    if not isinstance(lhs, Identifier) or lhs.name.startswith("$"):
        raise _Decline("index_filter_shape")
    if pred.type not in (PredicateType.EQ, PredicateType.IN,
                         PredicateType.RANGE):
        raise _Decline("index_pred_type_unsupported")
    ds = segment.data_source(lhs.name)
    cm = ds.metadata
    if cm.has_dictionary:
        from pinot_tpu.engine.host_eval import _matching_dict_ids

        ids = _matching_dict_ids(ds, pred)
        if cm.single_value and cm.is_sorted:
            return _sorted_route(ds, ids, num_docs)
        if cm.has_inverted_index:
            return _postings_route(ds, cm, ids)
        if cm.single_value:     # no index: probed beside a conjunct with one
            return _Route(num_docs, None, _dict_probe(ds, ids))
        raise _Decline("index_missing_index")
    if (cm.single_value
            and pred.type in (PredicateType.EQ, PredicateType.RANGE)
            and getattr(ds, "range_order", None) is not None):
        return _range_route(ds, cm, pred, num_docs)
    if cm.single_value:
        return _Route(num_docs, None, _raw_probe(ds, cm, pred))
    raise _Decline("index_missing_index")


def _gated_routes(segment, preds: List[Predicate], num_docs: int,
                  threshold: int) -> Optional[List[_Route]]:
    """The conjuncts' routes in the order they are applied, or None past
    the cost gate; raises _Decline for ineligible shapes. The gate runs
    on exact per-predicate counts BEFORE any posting list decodes, and on
    the conjuncts that have an index: one of them has to start the
    candidates. A conjunct without one never starts them."""
    routes = [_pred_route(segment, p, num_docs) for p in preds]
    indexed = [r.estimate for r in routes if r.resolve is not None]
    if not indexed:
        raise _Decline("index_missing_index")
    if min(indexed) > threshold:
        return None
    routes.sort(key=lambda r: (r.resolve is None, r.estimate))
    return routes


def resolve_doc_ids(segment, preds: List[Predicate], num_docs: int,
                    threshold: int, trace: Optional[dict] = None
                    ) -> Optional[np.ndarray]:
    """Conjunction -> sorted unique int64 docIds, or None past the cost
    gate (raises _Decline for ineligible shapes). Resolution starts from
    the most selective conjunct's docIds, so the working set never exceeds
    its match count; each further conjunct is probed on the forward index
    of what is left where its own count is over ``_PROBE_OVER`` times that
    (or it has no index), else resolved and intersected. Both give the
    same docIds. ``trace`` (a traced query's) takes ``candidates``,
    ``resolved`` and ``probed``."""
    routes = _gated_routes(segment, preds, num_docs, threshold)
    if routes is None:
        return None
    idx = routes[0].resolve()
    candidates, resolved, probed = int(idx.size), 1, 0
    for r in routes[1:]:
        if idx.size == 0:
            break
        if r.probe is not None and (
                r.resolve is None or r.estimate > _PROBE_OVER * idx.size):
            idx = idx[r.probe(idx)]
            probed += 1
        else:
            idx = np.intersect1d(idx, r.resolve(), assume_unique=True)
            resolved += 1
    if trace is not None:
        trace.update(candidates=candidates, resolved=resolved, probed=probed)
    return idx


def gather_plan(full: SegmentPlan, n: int) -> SegmentPlan:
    """The gathered-block plan derived from the scan plan: the filter spec
    collapses to ``("true",)`` (every gathered row satisfied it on the
    host), capacity re-sizes to the idx array's power-of-two pad, and the
    filter's leading params drop — ``plan_segment`` packs params in filter
    -> group -> agg order, so the tail is exactly the group strides/bases
    (KEEPING any filter-narrowed dictId bases: gathered rows satisfy the
    very conjuncts the narrowing came from) plus the agg params."""
    spec = full.spec
    stripped = (("true",), spec[1], spec[2], spec[3],
                max(_MIN_CAPACITY, _next_pow2(max(1, n))))
    n_filter = expected_param_count(spec) \
        - expected_param_count((("true",),) + spec[1:])
    return SegmentPlan(
        spec=stripped,
        params=list(full.params[n_filter:]),
        columns=_spec_columns(stripped, full.columns),
        group_defs=full.group_defs,
        group_cards=full.group_cards,
        group_strides=full.group_strides,
        num_groups=full.num_groups,
        agg_defs=full.agg_defs,
        group_bases=full.group_bases)


def _spec_columns(spec, candidates: List[str]) -> List[str]:
    """Columns the stripped spec still references (filter-only columns must
    not stage: the gather kernel never reads them)."""
    names = set()

    def walk(node):
        if isinstance(node, tuple):
            for x in node:
                walk(x)
        elif isinstance(node, str):
            names.add(node)

    walk((spec[1], spec[2]))
    return [c for c in candidates if c in names]


def batch_index_eligible(executor, ctx: QueryContext, segments) -> bool:
    """Should a multi-segment query leave the sharded combine for the
    per-segment ladder so the index rung can serve it? True when the
    conjunctive filter routes through indexes AND the selectivity estimate
    is under threshold on EVERY segment — estimates only (postings offsets
    arithmetic, searchsorted bounds), no postings decode, so the check
    costs microseconds per segment. ``all`` (not ``any``, unlike the
    star-tree fit check): a segment over threshold would pay a full
    per-segment scan that the sharded combine amortizes across the mesh,
    so one ineligible segment keeps the batch on the combine."""
    if str(ctx.options.get("useIndexRung", "true")).lower() == "false":
        return False
    if ctx.filter is None:
        return False
    from pinot_tpu.engine.startree_exec import _flatten_and

    preds = _flatten_and(ctx.filter)
    if not preds:
        return False
    for segment in segments:
        if getattr(segment, "valid_doc_ids", None) is not None:
            return False
        num_docs = segment.num_docs
        threshold = max(1, int(num_docs * SELECTIVITY_THRESHOLD))
        try:
            if _gated_routes(segment, preds, num_docs, threshold) is None:
                return False
        except _Decline:
            return False
    return True


def try_index_rung(executor, ctx: QueryContext, aggs: List[AggDef],
                   segment, stats: QueryStats,
                   grouped: bool) -> Optional[Any]:
    """AggResult / GroupByResult served by the docId-gather rung, or None
    (scan rungs serve; the reason is in the ledger for every decline on an
    index-candidate shape)."""
    if ctx.options.get("useIndexRung", "true").lower() == "false":
        return None  # operator opt-out, not a decline
    if ctx.filter is None:
        return None  # no filter: nothing selective to index — not a decline
    from pinot_tpu.engine.startree_exec import _flatten_and

    preds = _flatten_and(ctx.filter)
    if not preds:
        if preds is None:  # OR/NOT shape: indexes don't compose here (yet)
            _decline(stats, "index_filter_shape")
        return None  # constant-true filter ([]): nothing selective to
        #              index — not a decline
    if getattr(segment, "valid_doc_ids", None) is not None:
        # upsert: the valid-doc bitmap ANDs every filter and postings don't
        # see it — the scan kernel's validdocs param path serves
        _decline(stats, "index_upsert_valid_docs")
        return None

    num_docs = segment.num_docs
    threshold = max(1, int(num_docs * SELECTIVITY_THRESHOLD))
    try:
        with maybe_span(stats, "IndexRoute",
                        segment=segment.segment_name) as sp:
            if sp is None:
                idx = resolve_doc_ids(segment, preds, num_docs, threshold)
            else:       # traced: what the resolution did
                idx = resolve_doc_ids(segment, preds, num_docs, threshold,
                                      sp.attrs)
                if idx is not None:
                    sp.attrs["matched"] = int(idx.size)
    except _Decline as d:
        _decline(stats, d.reason)
        return None
    if idx is None:
        _decline(stats, "index_selectivity_over_threshold")
        return None
    n = int(idx.size)

    try:
        plan = gather_plan(executor._plan_for(ctx, segment, stats), n)
    except PlanError:
        # the scan branch re-plans, re-raises, and ledgers the specific
        # plan-decline code; here only the rung outcome is recorded
        _decline(stats, "index_plan_error")
        return None

    from pinot_tpu.engine.executor import filter_fingerprint

    lease = executor._lease_of(stats)
    staged = executor.residency.stage(segment, lease=lease)
    capacity = plan.spec[4]

    def build_idx() -> np.ndarray:
        padded = np.zeros(capacity, dtype=np.int32)
        padded[:n] = idx.astype(np.int32, copy=False)
        return padded

    try:
        idx_dev = staged.index_slice((filter_fingerprint(ctx), capacity),
                                     build_idx)
        executor.residency.account(segment.segment_name, lease)

        def launch():
            from pinot_tpu.engine.kernels import fetch_outputs, unpack_outputs

            with maybe_span(stats, "Dispatch"):
                cols = {name: staged.column(name).tree()
                        for name in plan.columns}
                kernel = executor._index_kernel(plan.spec)
                packed = kernel(cols, idx_dev, tuple(plan.params),
                                np.int32(n))
            # may raise PlanError
            return unpack_outputs(fetch_outputs(stats, packed), plan.spec)

        # per-segment coalescing: concurrent identical dashboard queries —
        # the SAME compiled ctx over the same resident — share one gather
        # launch + D2H (host docId resolution above stays per-caller)
        with maybe_span(stats, "Kernel", kernel="index_gather",
                        segment=segment.segment_name, records=n,
                        capacity=capacity):
            out, _ = executor._kernel_flight.do(
                ("index", id(ctx), segment.segment_name, id(staged)),
                launch)
    except PlanError:
        _decline(stats, "index_plan_error")
        return None
    except Exception:
        # staging/launch failure must not fail the query: the scan rungs
        # still serve it — mirror the mutable tier's containment
        _decline(stats, "index_exec_failed")
        return None

    stats.num_segments_processed += 1
    stats.total_docs += num_docs
    stats.num_docs_scanned += n
    if n:
        stats.num_segments_matched += 1
    _chose(stats, "index_served")

    from pinot_tpu.engine.executor import (
        decode_grouped_result,
        decode_scalar_result,
    )

    if grouped:
        return decode_grouped_result(plan, segment, out)
    return decode_scalar_result(plan, segment, out)
