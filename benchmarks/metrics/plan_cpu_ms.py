"""Engine and parallel executor, host side: CPU time of planning (the
star-tree plan, the segment plan, or in the sharded path plan and bind),
summed over a query, mean over the window's queries."""

from benchmarks.lib import spans


def read(ctx):
    return spans.cpu_of(ctx["records"], "Plan")
