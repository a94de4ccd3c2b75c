"""Engine and parallel executor, host side: CPU time of the star-tree walk
(pick a tree, resolve the predicates, select the records), summed over a
query's segments, mean over the window's queries."""

from benchmarks.lib import spans


def read(ctx):
    return spans.cpu_of(ctx["records"], "StarTreeWalk")
