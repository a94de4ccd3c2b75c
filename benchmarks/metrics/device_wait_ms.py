"""Kernels: how long a query waited on the device with nothing else to do
(``block_until_ready``): union of its ``DeviceWait`` intervals, median."""

from benchmarks.lib import spans


def read(ctx):
    return spans.wall_union_of(ctx["records"], spans.DEVICE_WAIT)
