"""Broker: the root span less the servers' part of it (what is left is
compile, routing, scatter and gather, reduce), median."""

from benchmarks.lib.stats import find, median, ms, roots


def read(ctx):
    return median([float(root["ms"]) - ms(find(root, "ServerQuery"))
                   for _, root in roots(ctx["records"])])
