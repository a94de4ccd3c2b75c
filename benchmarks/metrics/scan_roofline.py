"""Kernels: the least time the chip needs for the queries answered in the
traced span (``lib/work.py``: unpruned rows times packed column bits over
the memory bandwidth) over the device-op time they took. Bound by bytes.
Nothing to read where the table has star-trees: its queries scan no rows."""

from benchmarks.lib import work


def read(ctx):
    dev = ctx["device"]
    trees = ctx["config"]["tableIndexConfig"].get("starTreeIndexConfigs")
    if trees or not dev or not ctx["in_trace"] or dev["op_seconds"] <= 0:
        return None
    least = sum(work.scan_least_seconds(
        ctx["table_mod"], ctx["cycle"][rec["index"]],
        ctx["config"]["segments"], ctx["rows"], ctx["peak"])
        for rec in ctx["in_trace"])
    return 100.0 * least / dev["op_seconds"]
