"""Engine and parallel executor, host side: CPU time of the index rung's
docId resolution (the ``IndexRoute`` span: binary search on the sorted
key, posting lists, probes on the candidates' forward index), summed over
a query's segments, mean over the window's queries."""

from benchmarks.lib import spans


def read(ctx):
    return spans.cpu_of(ctx["records"], "IndexRoute")
