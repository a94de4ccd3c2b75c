"""Parallel launcher: the share of the dispatcher thread's time over the
window spent waiting with nothing queued, where the host upstream sets the
pace (``/debug/launches`` ``clock.emptyMs``, after less before, over the
five states' sum; ``lib/launch_clock.py``)."""

from benchmarks.lib import launch_clock


def read(ctx):
    return launch_clock.share(ctx, "emptyMs")
