"""Parallel launcher: how long a query's launch waited in the ordered
dispatcher's queue, behind the launches of other requests (``queueMs`` of
its ``ShardedCombine`` spans, summed), median over the window's queries
that took the sharded path. Nothing where none did."""

from benchmarks.lib.stats import find, median, ms, roots


def read(ctx):
    waits = []
    for _, root in roots(ctx["records"]):
        combine = [s for s in find(root, "ShardedCombine") if "queueMs" in s]
        if combine:
            waits.append(ms(combine, "queueMs"))
    return median(waits)
