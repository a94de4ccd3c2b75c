"""Kernels, on a mesh: the least bytes the queries answered in the traced
span have to read (``lib/work.py``: unpruned rows times packed column bits)
over what the mesh's chips move in the device-op time they took (the
config's ``mesh`` devices times one chip's memory bandwidth; the trace's
``op_seconds`` is already a mean over chips). The same work whatever kernel
serves it. Nothing to read for a config without a ``mesh``."""

from benchmarks.lib import work


def read(ctx):
    dev, mesh = ctx["device"], ctx["config"].get("mesh")
    trees = ctx["config"]["tableIndexConfig"].get("starTreeIndexConfigs")
    if (trees or not mesh or not dev or not ctx["in_trace"]
            or dev["op_seconds"] <= 0):
        return None
    least_bytes = sum(work.scan_least_bytes(
        ctx["table_mod"], ctx["cycle"][rec["index"]],
        ctx["config"]["segments"], ctx["rows"]) for rec in ctx["in_trace"])
    mesh_bytes_per_s = (mesh["seg"] * mesh["doc"]
                        * float(ctx["peak"]["hbm_bytes_per_s"]))
    return 100.0 * least_bytes / mesh_bytes_per_s / dev["op_seconds"]
