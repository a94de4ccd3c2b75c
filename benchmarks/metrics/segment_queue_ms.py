"""Engine and parallel executor, host side: how long a query's segments
waited for a worker of the segment pool: the longest ``SegmentQueue`` of a
query, median. 0 where the segments ran inline on the query's thread (they
waited for nothing); nothing where the query had no per-segment path."""

from benchmarks.lib import spans


def read(ctx):
    def one(root):
        found = spans.servers(root)
        if not any(spans.named(s, "SegmentGroupBy", "SegmentAggregate")
                   for s in found):
            return None
        return max((float(q["ms"]) for s in found
                    for q in spans.named(s, "SegmentQueue")), default=0.0)

    return spans.per_query(ctx["records"], one)
