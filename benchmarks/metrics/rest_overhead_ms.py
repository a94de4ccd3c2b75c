"""Transport (REST): client latency less the broker's root span, median."""

from benchmarks.lib.stats import median, roots


def read(ctx):
    return median([rec["latency_ms"] - float(root["ms"])
                   for rec, root in roots(ctx["records"])])
