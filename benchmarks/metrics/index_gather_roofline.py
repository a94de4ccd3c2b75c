"""Kernels: the least time the chip needs for the index-rung gathers of
the queries answered in the traced span (``lib/work_index.py``: each
launch's ``capacity`` docIds and one staged entry a docId of every column
the query sums or groups by, over the memory bandwidth) over the device
time of ``jit_index_gather_agg``. Bound by bytes. The program's time is
the traced span's device-op time less the ranked operations of other
programs (the list holds the ten largest, so operations of others below
the tenth place count as the gather's: the share is a floor). Nothing to
read where no query took the rung."""

from benchmarks.lib import spans, work_index

PROGRAM = "jit_index_gather_agg"


def read(ctx):
    dev = ctx["device"]
    if not dev or not ctx["in_trace"] or dev["op_seconds"] <= 0:
        return None
    least, launches = 0.0, 0
    for rec, root in spans.roots(ctx["in_trace"]):
        query = ctx["cycle"][rec["index"]]
        for srv in spans.servers(root):
            for k in spans.named(srv, "Kernel"):
                if k.get("kernel") == "index_gather" and "capacity" in k:
                    least += work_index.gather_least_bytes(query,
                                                           k["capacity"])
                    launches += 1
    others = sum(s for op, s in dev["device_ops"]
                 if not op.startswith(PROGRAM))
    seconds = dev["op_seconds"] - others
    if not launches or seconds <= 0:
        return None
    return 100.0 * least / float(ctx["peak"]["hbm_bytes_per_s"]) / seconds
