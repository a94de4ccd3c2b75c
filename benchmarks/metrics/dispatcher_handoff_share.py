"""Parallel launcher: the share of the dispatcher thread's time over the
window spent handing off: counters and futures after the device is ready,
the drain and grouping, the step to the next group or wait
(``/debug/launches`` ``clock.handingOffMs``, after less before, over the
five states' sum; ``lib/launch_clock.py``)."""

from benchmarks.lib import launch_clock


def read(ctx):
    return launch_clock.share(ctx, "handingOffMs")
