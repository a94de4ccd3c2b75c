"""Broker reduce: merge per-server DataTables into the final ResultTable.

Re-design of ``pinot-core/.../query/reduce/BrokerReduceService.java:44``
(``reduceOnDataTable:49`` dispatching by query type) +
``GroupByDataTableReducer.java:66`` (IndexedTable merge, HAVING,
post-aggregation) / ``AggregationDataTableReducer`` /
``SelectionDataTableReducer`` / ``DistinctDataTableReducer``.

Two execution paths share one accumulator surface:

- **vectorized** (the default): per-server tables fold AS THEY ARRIVE
  (``ReduceAccumulator.add`` — reduce overlaps the stragglers' network
  wait), keeping the wire's typed column buffers as numpy arrays the
  whole way. Group-by merges via ONE stable ``np.lexsort`` + boundary
  ``reduceat`` pass (engine/results.py ``lexsort_runs``/
  ``fold_grouped_runs``); selection merges the servers' pre-trimmed
  ORDER-BY blocks with a vectorized k-way lexsort and boxes ONLY the
  offset+limit output rows; distinct dedups via vectorized run detection
  over the concatenated key columns. Numeric columns never box a cell.
- **row path** (``vectorized=False`` or the ``vectorizedReduce=false``
  query option): the original per-row reducers, kept verbatim as the
  bit-parity oracle. Any shape the vectorized path cannot prove exact
  (object-typed keys, mixed column kinds across servers, NaN order keys,
  i64 sums near overflow) falls back here — recorded on the decision
  ledger under the ``reduce`` point.

On top of the vectorized path sits the **device** group-by route
(``BrokerReduceService(device_reduce=True)`` or the ``deviceReduce``
query option; off by default): when broker and servers share the
process (embedded cluster — tables never crossed a wire), the
concatenated (keys, states) block merges ON DEVICE through
``parallel/reduce_device.py`` — composite-key segment scatter + psum
over the broker mesh — and only the host finalization (insertion-order
restore, trim, ORDER BY, output boxing) runs on CPU. Shapes the device
fold cannot prove exact decline to the vectorized host path with a
``reduce:device->host:<reason>`` ledger record, giving the full ladder
device -> vectorized host -> row oracle.
"""

from __future__ import annotations

import time

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.common.bounds import I64_FOLD_BOUND
from pinot_tpu.common.datatable import Column, DataTable, ResponseType
from pinot_tpu.engine.aggregates import AggDef, resolve_agg
from pinot_tpu.engine.errors import QueryError
from pinot_tpu.engine.host_engine import _lexsort
from pinot_tpu.engine.results import (
    _VEC_STATE_FOLDS,
    AggResult,
    DataSchema,
    GroupByResult,
    QueryStats,
    ResultTable,
    _eval_scalar_filter,
    _result_schema,
    _Reversible,
    fold_grouped_runs,
    lexsort_runs,
    reduce_aggregation,
    reduce_group_by,
)
from pinot_tpu.query.context import QueryContext
from pinot_tpu.spi.config import CommonConstants

# vec state bases -> device segment/collective op (exactly the
# _VEC_STATE_FOLDS bases: count states fold by addition)
_DEVICE_OPS = {"count": "sum", "sum": "sum", "min": "min", "max": "max"}


class MixedResponseTypeError(QueryError):
    """Servers answered one scatter with DIFFERENT response types — a
    merge across them would be silently wrong-shaped (ref: the reference
    trusts DataTable data schemas to agree; here the mismatch is loud)."""


def _selection_key_spec(ctx: QueryContext, schema: DataSchema,
                        num_hidden: int) -> Tuple[List[int], List[bool]]:
    """Resolve ORDER BY expressions to column indices over a selection
    schema (visible by name/alias, order-by-only keys in the hidden
    tail). ONE resolver for the row-path oracle and the vectorized
    merge — the two paths cannot drift on key lookup."""
    names = schema.column_names
    visible_n = len(names) - num_hidden
    # aliased select expressions: ORDER BY references the expression,
    # the schema shows the alias — map through select_expressions
    alias_of: Dict[str, int] = {}
    if visible_n == len(ctx.select_expressions):
        for i, e in enumerate(ctx.select_expressions):
            alias_of.setdefault(str(e), i)
    key_idx: List[int] = []
    for ob in ctx.order_by:
        key = str(ob.expr)
        if key in names:
            key_idx.append(names.index(key))
        elif key in alias_of:
            key_idx.append(alias_of[key])
        else:
            hidden_names = names[visible_n:]
            if key not in hidden_names:
                raise QueryError(
                    f"ORDER BY {key} not found in selection schema")
            key_idx.append(visible_n + hidden_names.index(key))
    return key_idx, [ob.ascending for ob in ctx.order_by]


def _sortable_arrays(cols: List[np.ndarray]) -> List[np.ndarray]:
    """Rank-encode string arrays so ``lexsort_runs`` compares integers;
    numeric arrays pass through (NaN semantics preserved)."""
    out = []
    for a in cols:
        if a.dtype.kind in ("U", "S", "O"):
            _, codes = np.unique(a, return_inverse=True)
            a = codes
        out.append(a)
    return out


class ReduceAccumulator:
    """Streaming reduce state: ``add()`` one DataTable per arrival (the
    gather loop calls it the moment a server answers), ``finish()`` runs
    the final merge/trim/HAVING/post-agg pass. Fold timings land in
    ``fold_spans`` — the Reduce span's per-table split."""

    def __init__(self, service: "BrokerReduceService", ctx: QueryContext):
        self._svc = service
        self.ctx = ctx
        self.stats = QueryStats()
        self.exceptions: List[str] = []
        self.tables: List[DataTable] = []
        self.fold_spans: List[Dict[str, Any]] = []
        # a traced query's broker sets its root's perf_counter start: the
        # folds then carry their offset from it and the thread's CPU time
        self.trace_origin: Optional[float] = None
        self.rtype: Optional[ResponseType] = None
        self._mixed: Optional[MixedResponseTypeError] = None
        self.vectorized = service.vectorized and ctx.options.get(
            "vectorizedReduce", "true").lower() != "false"
        dev_opt = ctx.options.get("deviceReduce")
        self.device_route = self.vectorized and (
            dev_opt.lower() == "true" if dev_opt is not None
            else service.device_reduce)
        self._served_device = False
        self._wire_decoded = False
        self._fallback: Optional[str] = None
        self._aggs: List[AggDef] = [resolve_agg(f)
                                    for f in ctx.aggregations]
        # aggregation
        self._agg_merged: Optional[AggResult] = None
        # group-by
        self._gb_types: Dict[str, str] = {}
        self._gb_key_kinds: Optional[List[int]] = None
        self._gb_state_vec: Optional[List[bool]] = None
        self._gb_state_kinds: Optional[List[int]] = None
        self._gb_keys: List[List[np.ndarray]] = []
        self._gb_states: List[List[Any]] = []
        self._gb_i64_bound = 0
        # selection / distinct
        self._schema: Optional[DataSchema] = None
        self._num_hidden = 0
        self._col_kinds: Optional[List[int]] = None
        self._row_cols: List[List[Column]] = []
        self._row_counts: List[int] = []
        self._all_sorted = True

    # -- arrival fold --------------------------------------------------------
    def add(self, table: DataTable, instance: Optional[str] = None) -> None:
        t0 = time.perf_counter()
        c0 = time.thread_time() if self.trace_origin is not None else 0.0
        self.stats.merge(table.stats)
        self.exceptions.extend(table.exceptions)
        if table.exceptions:
            return
        if self.rtype is None:
            self.rtype = table.response_type
        elif table.response_type is not self.rtype:
            if self._mixed is None:
                self._mixed = MixedResponseTypeError(
                    f"servers disagree on response type: "
                    f"{self.rtype.value} vs {table.response_type.value} — "
                    f"refusing a wrong-shaped merge")
            return
        self.tables.append(table)
        if table.wire_decoded:
            # crossed a process boundary: the device route's premise
            # (states already resident, no D2H paid) does not hold
            self._wire_decoded = True
        if self.vectorized and self._fallback is None:
            self._fold(table)
        span = {"name": "Fold", "rows": table.num_rows(),
                "ms": round((time.perf_counter() - t0) * 1e3, 3)}
        if self.trace_origin is not None:
            import threading

            span.update(
                startMs=round((t0 - self.trace_origin) * 1e3, 3),
                cpuMs=round((time.thread_time() - c0) * 1e3, 3),
                thread=threading.current_thread().name)
        if instance is not None:
            span["instance"] = instance
        self.fold_spans.append(span)

    def _decline(self, reason: str) -> None:
        from pinot_tpu.common.tracing import record_decision

        self._fallback = reason
        record_decision(self.stats, "reduce", "row_path", "vectorized",
                        reason)

    def _decline_device(self, reason: str) -> None:
        """Device merge cannot serve this shape: fall back ONE rung (to
        the vectorized host path, not the oracle) and say why."""
        from pinot_tpu.common.tracing import record_decision

        self.device_route = False
        record_decision(self.stats, "reduce", "host", "device", reason)

    def _fold(self, table: DataTable) -> None:
        rtype = table.response_type
        if rtype is ResponseType.AGGREGATION:
            part = AggResult(table.agg_states())
            if self._agg_merged is None:
                self._agg_merged = part
            else:
                self._agg_merged.merge(part, self._aggs)
            return
        if rtype is ResponseType.GROUP_BY:
            self._fold_group_by(table)
            return
        self._fold_rows(table)

    def _fold_group_by(self, table: DataTable) -> None:
        self._gb_types.update(table.schema_types())
        if table.num_rows() == 0:
            return  # nothing to merge (empty wire columns carry no
            #         kind): not a decline
        key_cols, agg_cols = table.group_columns()
        kinds = [c.kind for c in key_cols]
        if any(not (c.is_numeric or c.is_string) for c in key_cols):
            return self._decline("reduce_group_key_not_sortable")
        if self._gb_key_kinds is None:
            self._gb_key_kinds = kinds
            self._gb_state_vec = [
                a.base in _VEC_STATE_FOLDS and c.is_numeric
                for a, c in zip(self._aggs, agg_cols)]
            self._gb_state_kinds = [c.kind for c in agg_cols]
        elif kinds != self._gb_key_kinds:
            return self._decline("reduce_column_kind_mismatch")
        states: List[Any] = []
        for vec, agg, col, want in zip(self._gb_state_vec, self._aggs,
                                       agg_cols, self._gb_state_kinds):
            if vec:
                if col.kind != want:
                    # i64 on one server, f64 on another: the oracle's
                    # exact-int-then-float arithmetic is the contract
                    return self._decline("reduce_column_kind_mismatch")
                arr = col.array()
                if arr.dtype.kind == "i" and agg.base in ("count", "sum"):
                    self._gb_i64_bound += max(
                        abs(int(arr.max())), abs(int(arr.min())))
                elif arr.dtype.kind == "f" \
                        and agg.base in ("min", "max") \
                        and bool(np.isnan(arr).any()):
                    # np.minimum propagates NaN; python min() does not —
                    # only the oracle's semantics are the contract
                    return self._decline("reduce_nan_numeric_state")
                states.append(("vec", arr))
            else:
                states.append(("obj", col.tolist()))
        self._gb_keys.append([c.array() for c in key_cols])
        self._gb_states.append(states)

    def _fold_rows(self, table: DataTable) -> None:
        """SELECTION / DISTINCT arrival: keep the typed columns, box
        nothing. Kind consistency across servers is the exactness guard
        (the oracle would coerce, e.g. int and float keys comparing
        equal — a mix falls back to it)."""
        if self._schema is None:
            self._schema = table.data_schema()
        self._num_hidden = max(self._num_hidden, table.num_hidden)
        self._all_sorted = self._all_sorted and table.selection_sorted
        if table.num_rows() == 0:
            return  # empty arrival: not a decline
        cols = table.columns()
        kinds = [c.kind for c in cols]
        if self._col_kinds is None:
            self._col_kinds = kinds
        elif kinds != self._col_kinds:
            return self._decline("reduce_column_kind_mismatch")
        if self.rtype is ResponseType.DISTINCT \
                and any(not (c.is_numeric or c.is_string) for c in cols):
            return self._decline("reduce_distinct_key_not_sortable")
        self._row_cols.append(cols)
        self._row_counts.append(table.num_rows())

    # -- final pass ----------------------------------------------------------
    def finish(self) -> Tuple[ResultTable, QueryStats, List[str]]:
        if not self.tables:
            raise QueryError("; ".join(self.exceptions)
                             or "no server responses")
        if self._mixed is not None:
            raise self._mixed
        svc, ctx = self._svc, self.ctx
        if not self.vectorized or self._fallback is not None:
            table = svc._reduce_rows(ctx, self.rtype, self.tables,
                                     self.stats)
            self.stats.reduce_path = "oracle"
            return table, self.stats, self.exceptions
        if self.rtype is ResponseType.AGGREGATION:
            table = reduce_aggregation(ctx, self._aggs, self._agg_merged)
        elif self.rtype is ResponseType.GROUP_BY:
            table = self._finish_group_by()
        elif self.rtype is ResponseType.SELECTION:
            table = self._finish_selection()
        else:
            table = self._finish_distinct()
        if self._fallback is not None:
            # a finish-time guard tripped (NaN order key, i64 bound):
            # rerun the retained tables through the oracle
            table = svc._reduce_rows(ctx, self.rtype, self.tables,
                                     self.stats)
            self.stats.reduce_path = "oracle"
        else:
            self.stats.reduce_path = ("device" if self._served_device
                                      else "vectorized")
        return table, self.stats, self.exceptions

    def _finish_group_by(self) -> Optional[ResultTable]:
        ctx, aggs = self.ctx, self._aggs
        if self._gb_i64_bound >= I64_FOLD_BOUND:
            if self.device_route:
                self._decline_device("reduce_device_i64_sum_bound")
            self._decline("reduce_i64_sum_bound")
            return None
        if not self._gb_keys:
            merged = GroupByResult()
            if merged.trim(self._svc.num_groups_limit):
                self.stats.num_groups_limit_reached = True
            return reduce_group_by(ctx, aggs, merged, self._gb_types)
        arity = len(self._gb_keys[0])
        key_concat = [
            np.concatenate([t[k] for t in self._gb_keys])
            for k in range(arity)]
        n = int(key_concat[0].shape[0])
        entries = []
        for a in range(len(aggs)):
            parts = [t[a] for t in self._gb_states]
            if self._gb_state_vec[a]:
                entries.append(
                    ("vec", np.concatenate([p[1] for p in parts])))
            else:
                flat: List[Any] = []
                for p in parts:
                    flat.extend(p[1])
                entries.append(("obj", flat))
        merged = self._device_group_by(key_concat, entries, n) \
            if self.device_route else None
        if merged is not None:
            # device contract == host contract: per group (any fixed
            # enumeration), earliest input index + exactly-folded state;
            # the stable argsort below restores insertion order either way
            first_idx, folded = merged
            self._served_device = True
        else:
            order, starts = lexsort_runs(_sortable_arrays(key_concat))
            folded = fold_grouped_runs(order, starts, n, entries, aggs)
            first_idx = order[starts]
        # restore the oracle's dict-insertion order: groups appear in
        # first-occurrence order of the concatenated input (stable
        # lexsort -> each run's first sorted element IS its earliest)
        perm = np.argsort(first_idx, kind="stable")
        if len(perm) > self._svc.num_groups_limit:
            # the oracle trims the merged dict to its first
            # num_groups_limit INSERTION-ordered entries — same cut
            perm = perm[: self._svc.num_groups_limit]
            self.stats.num_groups_limit_reached = True

        table = self._finalize_group_by_vectorized(
            key_concat, first_idx, perm, folded)
        if table is not None:
            return table

        # shape outside the vectorized finalization (HAVING, post-agg
        # arithmetic, unsortable finals): build the merged GroupByResult
        # and run the UNCHANGED trim/HAVING/post-agg pass — the merge
        # itself stayed array-native
        boxed_keys = [_box_indexed(key_concat[k], first_idx)
                      for k in range(arity)]
        groups: Dict[Tuple, List[Any]] = {}
        for j in perm:
            j = int(j)
            key = tuple(bk[j] for bk in boxed_keys)
            groups[key] = [_box_state(folded[a][j],
                                      self._gb_state_vec[a])
                           for a in range(len(aggs))]
        return reduce_group_by(ctx, aggs, GroupByResult(groups),
                               self._gb_types)

    def _device_group_by(self, key_concat, entries, n
                         ) -> Optional[Tuple[np.ndarray, List[np.ndarray]]]:
        """Try the on-device merge -> ``(first_idx, folded)``, or None
        after a ``reduce:device->host:<reason>`` ledger record. Every
        guard here is an EXACTNESS proof obligation: only folds whose
        result is order-independent bit-for-bit may leave the host."""
        from pinot_tpu.parallel import reduce_device as rdev

        if self._wire_decoded:
            # decoded wire tables already paid D2H + serialization —
            # the host lexsort is the natural frame for them
            self._decline_device("reduce_device_cross_process")
            return None
        if any(kind != "vec" for kind, _ in entries):
            self._decline_device("reduce_device_obj_state")
            return None
        mesh = rdev.broker_mesh()
        if mesh is None:
            self._decline_device("reduce_device_mesh_unavailable")
            return None
        if n > rdev.MAX_MERGE_ROWS:
            self._decline_device("reduce_device_rows_over_capacity")
            return None
        for a in key_concat:
            if a.dtype.kind == "f" and bool(np.isnan(a).any()):
                # NaN != NaN breaks the composite-key group identity
                self._decline_device("reduce_device_nan_key")
                return None
        comp, space = rdev.encode_composite_keys(key_concat)
        if comp is None:
            self._decline_device("reduce_device_key_space_overflow")
            return None
        ops: List[str] = []
        vals: List[np.ndarray] = []
        for agg, (_, arr) in zip(self._aggs, entries):
            if agg.base == "sum" and arr.dtype.kind == "f" \
                    and not rdev.f64_sum_exact(arr):
                # f64 addition is order-dependent; the psum order is not
                # the reduceat order, so only provably-exact sums go
                self._decline_device("reduce_device_f64_sum_order")
                return None
            ops.append(_DEVICE_OPS[agg.base])
            vals.append(arr)
        try:
            return rdev.device_group_merge(mesh, comp, space, vals, ops)
        except Exception:
            self._decline_device("reduce_device_kernel_error")
            return None

    def _finalize_group_by_vectorized(self, key_concat, first_idx, perm,
                                      folded) -> Optional[ResultTable]:
        """Array-native HAVING-free finalization: when every SELECT
        expression is a group key or an aggregation (no post-agg
        arithmetic) the final columns build straight from the folded
        arrays, ORDER BY runs as one more stable lexsort, and only the
        offset..offset+limit OUTPUT rows ever box. Returns None when the
        shape needs the row-path ``reduce_group_by`` (semantics there are
        the contract — this is purely the fast lane)."""
        ctx, aggs = self.ctx, self._aggs
        if ctx.having is not None:
            return None
        key_of = {str(g): k for k, g in enumerate(ctx.group_by)}
        agg_of = {str(fn): a for a, fn in enumerate(ctx.aggregations)}

        final_cache: Dict[str, Any] = {}

        def final_column(name: str):
            """Final values for a key/agg column over ``perm`` order —
            an ndarray for vectorized finals, a boxed list otherwise."""
            if name in final_cache:
                return final_cache[name]
            if name in key_of:
                out = key_concat[key_of[name]][first_idx[perm]]
            else:
                a = agg_of[name]
                agg = aggs[a]
                if self._gb_state_vec[a]:
                    arr = folded[a][perm]
                    # mirror _FINAL: count -> int, sum/min/max -> float
                    out = (arr.astype(np.int64) if agg.base == "count"
                           else arr.astype(np.float64))
                else:
                    states = folded[a]
                    out = [agg.finalize(states[int(j)]) for j in perm]
            final_cache[name] = out
            return out

        for e in ctx.select_expressions:
            if str(e) not in key_of and str(e) not in agg_of:
                return None  # post-aggregation arithmetic -> row path
        for ob in ctx.order_by:
            if str(ob.expr) not in key_of and str(ob.expr) not in agg_of:
                return None

        ngroups = len(perm)
        if ctx.order_by and ngroups:
            sort_cols = []
            for ob in ctx.order_by:
                col = final_column(str(ob.expr))
                arr = np.asarray(col) if not isinstance(col, np.ndarray) \
                    else col
                if arr.dtype == object:
                    return None  # non-uniform finals: oracle comparisons
                if arr.dtype.kind == "f" and bool(np.isnan(arr).any()):
                    return None
                sort_cols.append(arr)
            window = _lexsort(sort_cols,
                              [ob.ascending for ob in ctx.order_by])
            window = window[ctx.offset: ctx.offset + ctx.limit]
        else:
            lo = min(ctx.offset, ngroups)
            hi = min(ctx.offset + ctx.limit, ngroups)
            window = np.arange(lo, hi, dtype=np.int64)

        out_cols = []
        for e in ctx.select_expressions:
            col = final_column(str(e))
            if isinstance(col, np.ndarray):
                taken = col[window]
                if taken.dtype.kind in ("U", "S", "O"):
                    out_cols.append([str(v) for v in taken])
                else:
                    out_cols.append(taken.tolist())
            else:
                out_cols.append([col[int(j)] for j in window])
        rows = [[c[i] for c in out_cols] for i in range(len(window))]
        names, types = _result_schema(ctx, aggs, self._gb_types)
        return ResultTable(DataSchema(names, types), rows)

    def _selected_rows(self, sel: np.ndarray, visible: int
                       ) -> List[List[Any]]:
        """Box ONLY the chosen global row indices (output order = sel
        order), gathering per table through ``Column.take_boxed``."""
        bounds = np.concatenate(
            (np.zeros(1, np.int64),
             np.cumsum(self._row_counts))).astype(np.int64)
        rows: List[Optional[List[Any]]] = [None] * len(sel)
        tno = np.searchsorted(bounds, sel, side="right") - 1
        for ti, cols in enumerate(self._row_cols):
            pos = np.flatnonzero(tno == ti)
            if pos.size == 0:
                continue
            local = sel[pos] - bounds[ti]
            cells = [c.take_boxed(local) for c in cols[:visible]]
            for j, p in enumerate(pos):
                rows[int(p)] = [c[j] for c in cells]
        return rows  # type: ignore[return-value]

    def _finish_selection(self) -> Optional[ResultTable]:
        ctx = self.ctx
        schema = self._schema
        if schema is None:  # every ok table was empty AND schema-less
            schema = self.tables[0].data_schema()
        num_hidden = self._num_hidden
        total = int(sum(self._row_counts))
        visible = len(schema.column_names) - num_hidden
        out_schema = schema if not num_hidden else DataSchema(
            schema.column_names[:visible], schema.column_types[:visible])

        if not ctx.order_by or total == 0:
            lo = min(ctx.offset, total)
            hi = min(ctx.offset + ctx.limit, total)
            sel = np.arange(lo, hi, dtype=np.int64)
            return ResultTable(out_schema,
                               self._selected_rows(sel, visible))

        # resolve ORDER BY -> column indices (shared with the oracle)
        key_idx, directions = _selection_key_spec(ctx, schema, num_hidden)
        if any(not (self._row_cols[0][i].is_numeric
                    or self._row_cols[0][i].is_string)
               for i in key_idx):
            self._decline("reduce_order_key_not_sortable")
            return None
        if len(self._row_cols) == 1 and self._all_sorted:
            # single pre-sorted block (ref: SelectionOperatorUtils — the
            # one-server case): the trim window IS the answer
            lo = min(ctx.offset, total)
            hi = min(ctx.offset + ctx.limit, total)
            sel = np.arange(lo, hi, dtype=np.int64)
            return ResultTable(out_schema,
                               self._selected_rows(sel, visible))
        key_cols = [
            np.concatenate([cols[i].array() for cols in self._row_cols])
            for i in key_idx]
        for a in key_cols:
            if a.dtype.kind == "f" and bool(np.isnan(a).any()):
                # python-sort NaN comparisons are order-dependent; only
                # the oracle's (ill-defined but historical) order counts
                self._decline("reduce_nan_order_key")
                return None
        order = _lexsort(key_cols, directions)
        sel = order[ctx.offset: ctx.offset + ctx.limit].astype(np.int64)
        return ResultTable(out_schema, self._selected_rows(sel, visible))

    def _finish_distinct(self) -> Optional[ResultTable]:
        ctx = self.ctx
        schema = self._schema
        if schema is None:
            schema = self.tables[0].data_schema()
        names = schema.column_names
        rows: List[List[Any]] = []
        if self._row_cols:
            cols_concat = [
                np.concatenate([cols[i].array()
                                for cols in self._row_cols])
                for i in range(len(names))]
            order, starts = lexsort_runs(_sortable_arrays(cols_concat))
            first_idx = order[starts]
            first_idx.sort()  # first-occurrence (insertion) order
            rows = self._selected_rows(first_idx.astype(np.int64),
                                       len(names))
        if ctx.having is not None:
            rows = [r for r in rows
                    if _eval_scalar_filter(ctx.having,
                                           dict(zip(names, r)))]
        if ctx.order_by:
            idx_of = {n: i for i, n in enumerate(names)}

            def sort_key(row):
                parts = []
                for ob in ctx.order_by:
                    i = idx_of.get(str(ob.expr))
                    if i is None:
                        raise QueryError(
                            f"ORDER BY {ob.expr} not in DISTINCT list")
                    parts.append(_Reversible(row[i], ob.ascending))
                return tuple(parts)

            rows.sort(key=sort_key)
        return ResultTable(schema,
                           rows[ctx.offset: ctx.offset + ctx.limit])


def _box_indexed(arr: np.ndarray, idx: np.ndarray) -> list:
    """Box the selected key cells (one per OUTPUT group, never per row)."""
    taken = arr[idx]
    if taken.dtype.kind in ("U", "S", "O"):
        return [str(v) for v in taken]
    return taken.tolist()


def _box_state(v: Any, vec: bool) -> Any:
    return v.item() if vec else v


class BrokerReduceService:
    """Ref: BrokerReduceService.java:44."""

    def __init__(self, num_groups_limit: int =
                 CommonConstants.DEFAULT_NUM_GROUPS_LIMIT,
                 vectorized: bool = True,
                 device_reduce: bool =
                 CommonConstants.DEFAULT_BROKER_DEVICE_REDUCE):
        self.num_groups_limit = num_groups_limit
        self.vectorized = vectorized
        self.device_reduce = device_reduce

    def accumulator(self, ctx: QueryContext) -> ReduceAccumulator:
        """Streaming entry: the gather loop folds tables as they arrive
        (reduce-as-arrivals), then calls ``finish()``."""
        return ReduceAccumulator(self, ctx)

    def reduce(self, ctx: QueryContext, tables: List[DataTable]
               ) -> Tuple[ResultTable, QueryStats, List[str]]:
        """-> (result, merged stats, per-server error messages). A partial
        failure still reduces the successful servers' tables, but the errors
        MUST reach the response so the caller can tell a partial result from
        a complete one (ref: partial-results + exceptions behavior,
        SingleConnectionBrokerRequestHandler.java:134-141)."""
        acc = self.accumulator(ctx)
        for t in tables:
            acc.add(t)
        return acc.finish()

    # -- row-path reducers (the bit-parity oracle) ---------------------------
    def _reduce_rows(self, ctx: QueryContext, rtype: ResponseType,
                     ok: List[DataTable], stats: QueryStats) -> ResultTable:
        if rtype is ResponseType.AGGREGATION:
            return self._reduce_aggregation(ctx, ok)
        if rtype is ResponseType.GROUP_BY:
            return self._reduce_group_by(ctx, ok, stats)
        if rtype is ResponseType.SELECTION:
            return self._reduce_selection(ctx, ok)
        return self._reduce_distinct(ctx, ok)

    def _reduce_aggregation(self, ctx: QueryContext,
                            tables: List[DataTable]) -> ResultTable:
        aggs = [resolve_agg(f) for f in ctx.aggregations]
        merged: AggResult = None
        for t in tables:
            part = AggResult(t.agg_states())
            if merged is None:
                merged = part
            else:
                merged.merge(part, aggs)
        return reduce_aggregation(ctx, aggs, merged)

    def _reduce_group_by(self, ctx: QueryContext, tables: List[DataTable],
                         stats: QueryStats) -> ResultTable:
        aggs = [resolve_agg(f) for f in ctx.aggregations]
        merged = GroupByResult()
        schema_types: Dict[str, str] = {}
        for t in tables:
            schema_types.update(t.schema_types())
            merged.merge(GroupByResult(t.group_by_groups()), aggs)
        if merged.trim(self.num_groups_limit):
            stats.num_groups_limit_reached = True
        return reduce_group_by(ctx, aggs, merged, schema_types)

    def _reduce_selection(self, ctx: QueryContext,
                          tables: List[DataTable]) -> ResultTable:
        schema = tables[0].data_schema()
        num_hidden = max(t.num_hidden for t in tables)
        rows: List[List[Any]] = []
        for t in tables:
            rows.extend(t.rows())

        if ctx.order_by and rows:
            # hidden trailing columns hold the order-by expression values;
            # visible order-by columns are found by name
            key_idx, directions = _selection_key_spec(ctx, schema,
                                                      num_hidden)

            def sort_key(row):
                return tuple(_Reversible(row[i], asc)
                             for i, asc in zip(key_idx, directions))

            rows.sort(key=sort_key)

        rows = rows[ctx.offset: ctx.offset + ctx.limit]
        if num_hidden:
            visible = len(schema.column_names) - num_hidden
            schema = DataSchema(schema.column_names[:visible],
                                schema.column_types[:visible])
            rows = [r[:visible] for r in rows]
        return ResultTable(schema, rows)

    def _reduce_distinct(self, ctx: QueryContext,
                         tables: List[DataTable]) -> ResultTable:
        schema = tables[0].data_schema()
        seen: Dict[Tuple, List[Any]] = {}
        for t in tables:
            for r in t.rows():
                key = tuple(tuple(v) if isinstance(v, list) else v for v in r)
                if key not in seen:
                    seen[key] = r
        rows = list(seen.values())
        names = schema.column_names
        if ctx.having is not None:
            rows = [r for r in rows
                    if _eval_scalar_filter(ctx.having, dict(zip(names, r)))]
        if ctx.order_by:
            idx_of = {n: i for i, n in enumerate(names)}

            def sort_key(row):
                parts = []
                for ob in ctx.order_by:
                    i = idx_of.get(str(ob.expr))
                    if i is None:
                        raise QueryError(
                            f"ORDER BY {ob.expr} not in DISTINCT list")
                    parts.append(_Reversible(row[i], ob.ascending))
                return tuple(parts)

            rows.sort(key=sort_key)
        return ResultTable(schema, rows[ctx.offset: ctx.offset + ctx.limit])
