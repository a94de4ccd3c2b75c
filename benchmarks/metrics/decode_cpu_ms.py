"""Engine and parallel executor, host side: CPU time from the kernel's
output to the server's wire form (``Decode``, ``CombineSegments``,
``Serialize``), summed over a query, mean over the
window's queries."""

from benchmarks.lib import spans


def read(ctx):
    return spans.cpu_of(ctx["records"], "Decode", "CombineSegments",
                        "Serialize")
