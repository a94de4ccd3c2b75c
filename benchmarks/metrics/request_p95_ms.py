"""Service, whole path: client latency of all the window's requests, 95th
percentile (nearest rank), in the cells whose two clients leave the tail
to which two strings meet (PERF.md section 2): the same number as
``latency_p95_ms``, with no bound."""

from benchmarks.lib.stats import percentile


def read(ctx):
    return percentile([r["latency_ms"] for r in ctx["records"]], 95.0)
