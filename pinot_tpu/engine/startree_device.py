"""Star-tree device rung: pre-aggregated node slices through the kernels.

The device promotion of ``engine/startree_exec.py``'s host walker
(re-design of ``StarTreeFilterOperator.java:87`` +
``StarTreeGroupByExecutor.java:43``): the *tree walk* stays host-side — a
few nodes descended, then binary searches inside the sorted leaves, over R
pre-aggregated records (R << num_docs) — but the aggregation runs on device
through the SAME group-by kernel ladder the forward-index scan uses:

1. ``resolve_matches`` + ``StarTree.select_records`` pick the answering
   record indices, in ascending order (a few hundred to a few thousand
   for the SSB Q2.x shape — vs a 3M-doc scan): the walk descends the
   matching children, cuts every leaf it reaches to the matching
   sub-ranges by ``searchsorted`` on the leaf's own sort order, and reads
   and masks only the dimensions the search cannot decide.
2. The indices pad to a power-of-two capacity and ride to the device as
   ONE small int32 array; the jitted kernel gathers the staged node
   columns (``StagedSegment.startree_nodes`` — byte-accounted, pinned,
   evictable residents like any column) down to the selected slice and
   runs ``build_kernel_body`` over it — dense scatter for narrowed key
   spaces, the hash/sort rungs past the sparse threshold, identical
   packed-output framing, one D2H fetch.
3. Decode reassembles the ORIGINAL aggregation states from the rewritten
   pre-agg leaves (``StarTreePlan.agg_map``: count = sum of the count
   column, avg = sum+count pair), so ``GroupByResult``/``AggResult``
   merging — the CombineOperator analogue — applies unchanged.

Queries the node plan can't serve (key space past MAX_DEVICE_GROUPS)
raise PlanError and the host walker serves; queries the TREE can't serve
never reach here (``pick_star_tree`` gates both paths).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.common.tracing import maybe_span
from pinot_tpu.engine.aggregates import AggDef
from pinot_tpu.engine.plan import PlanError, StarTreePlan, plan_star_tree
from pinot_tpu.engine.results import AggResult, GroupByResult, QueryStats
from pinot_tpu.query.context import QueryContext

POS_INF = float("inf")
NEG_INF = float("-inf")


def build_startree_kernel(spec: Tuple):
    """Jitted ``fn(cols, idx, params, num_docs) -> packed f64 vector``:
    gathers each staged node column down to the ``idx`` slice (padding
    gathers row 0; the kernel's ``doc < num_docs`` mask drops it) and runs
    the standard kernel body — the node table IS a segment to the kernel."""
    import jax
    import jax.numpy as jnp

    from pinot_tpu.engine.kernels import (
        build_kernel_body,
        pack_outputs,
        sparse_mode,
    )

    body = build_kernel_body(spec, sparse_k=sparse_mode(spec))

    def startree_agg(cols, idx, params, num_docs):
        gathered = {name: {k: v[idx] for k, v in tree.items()}
                    for name, tree in cols.items()}
        return pack_outputs(body(gathered, params, num_docs, jnp.int32(0)),
                            spec)

    return jax.jit(startree_agg)


def _empty_states(aggs: List[AggDef]) -> List[Any]:
    """Zero-match scalar states, matching the scan path's conventions."""
    out: List[Any] = []
    for agg in aggs:
        out.append({"count": 0, "sum": 0.0, "min": POS_INF,
                    "max": NEG_INF, "avg": (0.0, 0)}[agg.base])
    return out


def _leaf_states(base: str, leaves: List[np.ndarray], gidx) -> List[Any]:
    """One original aggregation's per-group states from its rewritten
    pre-agg leaves (``gidx`` = live group indexes into dense leaves)."""
    if base == "count":
        arr = np.asarray(leaves[0])[gidx]
        return [int(v) for v in arr]
    if base in ("sum", "min", "max"):
        arr = np.asarray(leaves[0])[gidx]
        return [float(v) for v in arr]
    if base == "avg":
        s = np.asarray(leaves[0])[gidx]
        c = np.asarray(leaves[1])[gidx]
        return [(float(a), int(b)) for a, b in zip(s, c)]
    raise AssertionError(base)


def _decode_grouped(plan: StarTreePlan, segment,
                    out: Dict[str, Any]) -> GroupByResult:
    """Kernel output -> GroupByResult keyed on dictionary VALUES, using the
    plan's own strides/bases (the narrowed-gdict decode contract shared
    with ``executor.decode_grouped_result``)."""
    presence = np.asarray(out["presence"])
    gidx = np.nonzero(presence)[0]
    result = GroupByResult()
    if gidx.size == 0:
        return result
    strides = plan.group_strides.astype(np.int64)
    key_cols: List[List[Any]] = []
    for i, col in enumerate(plan.group_cols):
        dids = (gidx // strides[i]) % plan.group_cards[i]
        d = segment.data_source(col).dictionary
        key_cols.append(d.get_values(dids + plan.group_bases[i]))
    keys = list(zip(*key_cols))

    states_per_agg = [
        _leaf_states(base, [out[f"agg{j}"] for j in leaf_idx], gidx)
        for base, leaf_idx in plan.agg_map]
    for gi, key in enumerate(keys):
        result.groups[key] = [states_per_agg[ai][gi]
                              for ai in range(len(plan.agg_map))]
    return result


def _decode_scalar(plan: StarTreePlan, out: Dict[str, Any]) -> AggResult:
    states: List[Any] = []
    for base, leaf_idx in plan.agg_map:
        leaves = [out[f"agg{j}"] for j in leaf_idx]
        if base == "count":
            states.append(int(leaves[0]))
        elif base in ("sum", "min", "max"):
            states.append(float(leaves[0]))
        else:  # avg
            states.append((float(leaves[0]), int(leaves[1])))
    return AggResult(states)


def execute_star_tree_device(executor, ctx: QueryContext,
                             aggs: List[AggDef], segment, tree,
                             matches: Dict[str, Any],
                             stats: QueryStats,
                             tree_index: Optional[int] = None,
                             idx: Optional[np.ndarray] = None
                             ) -> Optional[Any]:
    """-> AggResult / GroupByResult served from device-resident node
    arrays, or raises PlanError (host walker serves). ``executor`` provides
    the residency manager (staging + lease pinning) and the star-tree
    kernel cache. ``tree_index`` is the pick's index into
    ``segment.star_trees`` (derived by identity when omitted); ``idx`` the
    records the caller's walk selected (walked here when omitted)."""
    import jax.numpy as jnp

    from pinot_tpu.engine.kernels import fetch_outputs, unpack_outputs

    if tree_index is None:
        tree_index = segment.star_trees.index(tree)
    if idx is None:
        idx = tree.select_records(matches, [e.name for e in ctx.group_by])
    n = int(idx.shape[0])

    with maybe_span(stats, "Plan", cacheHit=False):
        plan = plan_star_tree(ctx, segment, tree, matches, n)

    if n == 0:
        # nothing selected: skip the launch, emit the scan path's empty
        # shapes (stats still count the segment as processed, zero scanned)
        stats.num_segments_processed += 1
        stats.total_docs += segment.num_docs
        if ctx.is_group_by:
            return GroupByResult()
        return AggResult(_empty_states(aggs))

    # stage the node arrays through the residency manager: the segment
    # resident is pinned by this query's lease, so the arrays cannot be
    # evicted out from under the launch
    with maybe_span(stats, "Stage", segment=segment.segment_name):
        staged = executor.residency.stage(segment,
                                          lease=executor._lease_of(stats))

    def launch():
        with maybe_span(stats, "Dispatch"):
            nodes = staged.startree_nodes(tree_index)
            cols = {key: {"fwd": nodes[key]} for key in plan.columns}
            capacity = plan.spec[-1]
            padded = np.zeros(capacity, dtype=np.int32)
            padded[:n] = idx.astype(np.int32)
            kernel = executor._startree_kernel(plan.spec)
            packed = kernel(cols, jnp.asarray(padded), tuple(plan.params),
                            np.int32(n))
        # may raise PlanError
        return unpack_outputs(fetch_outputs(stats, packed), plan.spec)

    # per-segment coalescing contract (engine/executor._kernel_flight):
    # concurrent identical dashboard queries — the SAME compiled ctx object
    # over the same staged tree — share one node-slice launch + D2H. The
    # walk/plan above stays per-caller (host work, query-private stats).
    with maybe_span(stats, "Kernel", kernel="startree_device",
                    segment=segment.segment_name, records=n):
        out, _ = executor._kernel_flight.do(
            ("startree", id(ctx), segment.segment_name, tree_index,
             id(staged)),
            launch)

    stats.num_segments_processed += 1
    stats.total_docs += segment.num_docs
    stats.num_docs_scanned += n
    stats.num_segments_matched += 1
    with maybe_span(stats, "Decode"):
        if not ctx.is_group_by:
            return _decode_scalar(plan, out)
        return _decode_grouped(plan, segment, out)
