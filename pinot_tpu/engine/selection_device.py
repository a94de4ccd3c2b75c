"""Device path for ordered selection: filter + top-k on the accelerator.

The reference's hot realtime shape — ``SELECT cols FROM t WHERE ...
ORDER BY ts DESC LIMIT 10`` (``SelectionOrderByOperator.java``) — runs the
filter scan AND the order-by selection on device: the boolean mask and a
stable lexicographic ``lax.sort`` over the order keys (docIds ride as the
payload, so ties keep doc order — the host's stable-sort tie semantics
exactly) produce the per-segment top-k doc ids; only k ids cross the wire,
and the k rows materialize from the host-side column files (row
materialization is O(k · columns), never O(capacity)).

Eligibility (everything else falls back to the numpy host path):
- every ORDER BY expression is a non-null numeric/dict SV column
  (dictionary columns sort by dictId — the dictionary is sorted, so
  dictId order IS value order);
- the filter compiles for the device (plan._compile_filter);
- offset+limit bounded (top-k stays a small D2H);
- immutable, non-upsert segments.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu.engine import host_engine
from pinot_tpu.engine.kernels import _ParamCursor, _emit_filter
from pinot_tpu.engine.plan import PlanError, _compile_filter
from pinot_tpu.engine.results import DataSchema, QueryStats, ResultTable
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.expressions import Identifier
from pinot_tpu.segment.immutable import ImmutableSegment

# top-k cap: past this the dense sort + D2H stops beating the host path
MAX_DEVICE_SELECTION_K = 8192
# LRU bound on compiled top-k kernels (k rides in the cache key)
_KERNEL_CACHE_CAP = 256
_I32 = np.iinfo(np.int32)


def _order_columns(ctx: QueryContext,
                   segment: ImmutableSegment) -> Optional[List[str]]:
    import math

    cols = []
    for ob in ctx.order_by:
        e = ob.expr
        if not isinstance(e, Identifier) or e.name.startswith("$"):
            return None
        cm = segment.metadata.column(e.name)
        if not cm.single_value or cm.has_nulls:
            return None
        if not (cm.has_dictionary or cm.data_type.is_numeric):
            return None
        if not cm.has_dictionary:
            from pinot_tpu.engine.staging import staged_int_dtype

            if cm.data_type.is_integral:
                if staged_int_dtype(cm) != np.dtype(np.int32):
                    return None  # the sort keys are 32-bit
                # filtered-out rows park at INT32_MAX in the leading key:
                # a value that transforms onto it (max ascending, min
                # through ``~`` descending) would tie with them
                if cm.min_value is None or cm.max_value is None:
                    return None
                if (int(cm.max_value) >= _I32.max if ob.ascending
                        else int(cm.min_value) <= _I32.min):
                    return None
            else:
                # the kernel parks filtered-out rows at +inf: a raw float
                # column containing ±inf/NaN would collide with (or sort
                # past) the sentinel — stats must PROVE finiteness
                try:
                    if (cm.min_value is None or cm.max_value is None
                            or not math.isfinite(float(cm.min_value))
                            or not math.isfinite(float(cm.max_value))):
                        return None
                except (TypeError, ValueError):
                    return None
        cols.append(e.name)
    return cols


def _build_kernel(filter_spec, directions: Tuple[bool, ...], capacity: int,
                  k: int):
    """jitted fn(cols, params, num_docs, keys) -> (docids[k], n_matched).
    Keys sort lexicographically in their staged dtype (i32 dictIds / raw
    ints, f64 raw floats) with the docIds as the payload of a STABLE sort
    — a total order identical to the host's stable lexsort. Integer keys
    never widen: a 64-bit comparator is emulated on the TPU, and XLA
    takes minutes to compile a multi-key sort over it."""

    def selection_topk(cols, params, num_docs, keys):
        pc = _ParamCursor(params)
        mask = _emit_filter(filter_spec, cols, pc, capacity)
        pc.finish()  # selection params are exactly the filter params
        iota = jnp.arange(capacity, dtype=jnp.int32)
        mask = mask & (iota < num_docs)
        operands = []
        for key, asc in zip(keys, directions):
            if jnp.issubdtype(key.dtype, jnp.integer):
                # ~v reverses the order with no overflow at the i32 edges
                operands.append(key if asc else ~key)
            else:
                operands.append(key if asc else -key)
        # filtered-out rows park past every matched row (_order_columns
        # proves no matched key reaches the sentinel)
        lead = operands[0]
        park = (_I32.max if jnp.issubdtype(lead.dtype, jnp.integer)
                else jnp.inf)
        operands[0] = jnp.where(mask, lead, jnp.asarray(park, lead.dtype))
        sorted_ops = jax.lax.sort(tuple(operands) + (iota,),
                                  num_keys=len(operands), is_stable=True)
        return sorted_ops[-1][:k], mask.sum(dtype=jnp.int32)

    return jax.jit(selection_topk)


def device_selection(ctx: QueryContext, segments: List[ImmutableSegment],
                     staging, kernel_cache: Dict,
                     stats: Optional[QueryStats]) -> Optional[ResultTable]:
    """The ordered-selection branch of host_engine.execute_selection with
    the per-segment scan+sort on device; returns None when ineligible."""
    need = ctx.offset + ctx.limit
    if not ctx.order_by or need <= 0 or need > MAX_DEVICE_SELECTION_K:
        return None

    schema = segments[0].metadata.schema
    select = host_engine._expand_select(ctx, schema)
    names = host_engine._select_names(ctx, select)
    types = [host_engine._column_type(segments[0], e) for e in select]

    # phase 1: verify EVERY segment is eligible before any kernel runs or
    # stats mutate — a mid-loop fallback would otherwise double-count the
    # already-processed segments when the host path re-tracks them all
    plans: List[Tuple[ImmutableSegment, List[str], Tuple, List[Any],
                      List[str]]] = []
    for seg in segments:
        if getattr(seg, "is_mutable", False) \
                or getattr(seg, "valid_doc_ids", None) is not None:
            return None
        order_cols = _order_columns(ctx, seg)
        if order_cols is None:
            return None
        try:
            params: List[Any] = []
            columns: List[str] = []
            filter_spec = _compile_filter(ctx.filter, seg, params, columns)
        except PlanError:
            return None
        plans.append((seg, order_cols, filter_spec, params, columns))

    picked: List[Tuple[ImmutableSegment, np.ndarray]] = []
    lease = getattr(stats, "_staging_lease", None)
    for seg, order_cols, filter_spec, params, columns in plans:
        staged = staging.stage(seg, lease=lease)
        cols = {name: staged.column(name).tree() for name in columns}
        keys = [staged.column(c).tree()["fwd"] for c in order_cols]
        k = min(need, seg.padded_capacity)
        ckey = (filter_spec, tuple(ob.ascending for ob in ctx.order_by),
                seg.padded_capacity, k,
                tuple(sorted((n, tuple(sorted(t))) for n, t in
                             ((nm, cols[nm].keys()) for nm in cols))))
        kern = kernel_cache.get(ckey)
        if kern is None:
            kern = _build_kernel(
                filter_spec, tuple(ob.ascending for ob in ctx.order_by),
                seg.padded_capacity, k)
            kernel_cache[ckey] = kern
            while len(kernel_cache) > _KERNEL_CACHE_CAP:
                kernel_cache.popitem(last=False)
        elif hasattr(kernel_cache, "move_to_end"):
            kernel_cache.move_to_end(ckey)
        docids_dev, n = kern(cols, tuple(params), jnp.int32(seg.num_docs),
                             keys)
        n = int(n)
        if stats is not None:
            stats.num_segments_processed += 1
            stats.total_docs += seg.num_docs
            stats.num_docs_scanned += n
            stats.num_segments_matched += 1 if n else 0
        if n == 0:
            continue
        picked.append((seg, np.asarray(docids_dev)[:min(n, k)]))

    if not picked:
        return ResultTable(DataSchema(names, types), [])

    # merge the per-segment top-k candidates exactly like the host path:
    # stable lexsort over (keys...) in segment order == global ordering
    key_cols: List[np.ndarray] = []
    for ki, ob in enumerate(ctx.order_by):
        key_cols.append(np.concatenate(
            [host_engine._order_key_array(seg, ob.expr, d)
             for seg, d in picked]))
    order = host_engine._lexsort(key_cols,
                                 [ob.ascending for ob in ctx.order_by])
    order = order[ctx.offset: ctx.offset + ctx.limit]

    bounds = np.cumsum([0] + [len(d) for _, d in picked])
    rows: List[List[Any]] = [None] * len(order)  # type: ignore[list-item]
    for si, (seg, docids) in enumerate(picked):
        local = [(oi, int(gi - bounds[si])) for oi, gi in enumerate(order)
                 if bounds[si] <= gi < bounds[si + 1]]
        if not local:
            continue
        ids = np.asarray([docids[li] for _, li in local])
        cols_v = [host_engine._select_values(seg, e, ids) for e in select]
        for row_i, (oi, _li) in enumerate(local):
            rows[oi] = [c[row_i] for c in cols_v]
    return ResultTable(DataSchema(names, types), rows)
