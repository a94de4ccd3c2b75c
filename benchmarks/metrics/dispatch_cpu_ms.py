"""Parallel launcher: CPU time of the host side of a query's launches
(build the arguments, the jit call until it returns), summed, mean over the
window's queries."""

from benchmarks.lib import spans


def read(ctx):
    return spans.cpu_of(ctx["records"], "Dispatch")
