"""Kernels: the copy of a query's outputs from the device to host arrays:
union of its ``D2H`` intervals, median."""

from benchmarks.lib import spans


def read(ctx):
    return spans.wall_union_of(ctx["records"], "D2H")
