"""Service, by SSB flight: client latency of the Q3.x requests, median."""

from benchmarks.lib.stats import median


def read(ctx):
    return median([r["latency_ms"] for r in ctx["records"]
                   if r["group"] == "q3"])
