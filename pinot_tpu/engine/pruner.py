"""Server-side segment pruning: skip segments a filter provably excludes.

Re-design of the reference's ``SegmentPrunerService.java`` +
``ColumnValueSegmentPruner.java``: before planning/staging, each acquired
segment's column metadata is tested against the query's filter tree —
min/max bounds for EQ/RANGE/IN, partition membership for EQ, bloom filters
for EQ/IN. A segment prunes only when the filter is PROVABLY empty on it:
AND prunes if any conjunct proves empty, OR only if all branches do, NOT
and unhandled predicates are conservatively kept.

On the TPU serving path pruning is worth more than on the reference: a
pruned segment never joins the device batch, never pays dictionary
unification, and never burns HBM bandwidth in the dense scan.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.expressions import (
    FilterNode,
    FilterOp,
    Identifier,
    Predicate,
    PredicateType,
)
from pinot_tpu.utils.partition import get_partition_function


# what proved a segment empty, as the ``Prune`` span counts it
BY_PARTITION, BY_BOUNDS, BY_BLOOM = "byPartition", "byBounds", "byBloom"


def prune_segments(ctx: QueryContext, segments: List,
                   stats=None, why: Optional[Dict[str, int]] = None) -> List:
    """Segments the query may still match (ref:
    SegmentPrunerService.prune called at ServerQueryExecutorV1Impl:277).
    ``why`` (a traced query's) takes the count of segments each kind of
    proof excluded."""
    if ctx.filter is None:
        return segments
    kept = []
    for s in segments:
        proof = _proof_of_empty(ctx.filter, s)
        if proof is None:
            kept.append(s)
        elif why is not None:
            why[proof] = why.get(proof, 0) + 1
    if stats is not None:
        stats.num_segments_pruned += len(segments) - len(kept)
    return kept


def _proof_of_empty(node: FilterNode, seg) -> Optional[str]:
    """What proves the filter empty on the segment (the first proof found),
    or None: it may match."""
    if node.op is FilterOp.AND:
        for c in node.children:
            proof = _proof_of_empty(c, seg)
            if proof is not None:
                return proof
        return None
    if node.op is FilterOp.OR:
        proof = None
        for c in node.children:
            proof = _proof_of_empty(c, seg)
            if proof is None:
                return None
        return proof
    if node.op is FilterOp.NOT:
        return None  # negations are not provable from min/max
    return _predicate_proof(node.predicate, seg)


def _value_proof(seg, cm, v) -> Optional[str]:
    if not _within_bounds(cm, v):
        return BY_BOUNDS
    if not _partition_may_contain(cm, v):
        return BY_PARTITION
    if not _bloom_may_contain(seg, cm, v):
        return BY_BLOOM
    return None


def _predicate_proof(pred: Predicate, seg) -> Optional[str]:
    if not isinstance(pred.lhs, Identifier):
        return None
    cm = seg.metadata.columns.get(pred.lhs.name)
    if cm is None or not cm.single_value:
        return None
    t = pred.type

    def conv(v) -> Optional[Any]:
        from pinot_tpu.spi.data import DataType

        try:
            v = cm.data_type.convert(v)
        except (TypeError, ValueError):
            return None
        if cm.data_type is DataType.FLOAT:
            # stored values are float32: the probe must see the same
            # precision or bounds/bloom checks compare f64 0.1 against
            # f64(f32(0.1)) and false-prune
            import numpy as np

            v = float(np.float32(v))
        return v

    if t is PredicateType.EQ:
        v = conv(pred.value)
        if v is None:
            return None
        return _value_proof(seg, cm, v)
    if t is PredicateType.IN:
        vals = [conv(x) for x in pred.values]
        vals = [v for v in vals if v is not None]
        if not vals:
            return None
        proof = None
        for v in vals:
            proof = _value_proof(seg, cm, v)
            if proof is None:
                return None
        return proof
    if t is PredicateType.RANGE:
        return None if _range_overlaps(cm, pred, conv) else BY_BOUNDS
    return None


def _within_bounds(cm, v) -> bool:
    if cm.min_value is None or cm.max_value is None or cm.has_nulls:
        return True
    try:
        return cm.min_value <= v <= cm.max_value
    except TypeError:
        return True


def _partition_may_contain(cm, v) -> bool:
    """Ref: the partition branch of ColumnValueSegmentPruner (and the
    broker's PartitionSegmentPruner — same metadata)."""
    if not cm.partition_function or not cm.partitions:
        return True
    fn = get_partition_function(cm.partition_function, cm.num_partitions)
    return fn.partition(v) in cm.partitions


def _bloom_may_contain(seg, cm, v) -> bool:
    if not cm.has_bloom_filter:
        return True
    bf = seg.data_source(cm.name).bloom_filter
    # v already round-tripped through the stored precision (see conv);
    # the build side hashed the f64 widening of the stored f32 values
    return bf is None or bf.might_contain(v)


def _range_overlaps(cm, pred: Predicate, conv) -> bool:
    if cm.min_value is None or cm.max_value is None or cm.has_nulls:
        return True
    lo = conv(pred.lower) if pred.lower is not None else None
    hi = conv(pred.upper) if pred.upper is not None else None
    try:
        if lo is not None:
            if pred.lower_inclusive:
                if cm.max_value < lo:
                    return False
            elif cm.max_value <= lo:
                return False
        if hi is not None:
            if pred.upper_inclusive:
                if cm.min_value > hi:
                    return False
            elif cm.min_value >= hi:
                return False
    except TypeError:
        return True
    return True
