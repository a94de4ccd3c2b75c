"""Parallel launcher: the share of the dispatcher thread's time over the
window spent in ``block_until_ready`` on a group's launches
(``/debug/launches`` ``clock.deviceWaitMs``, after less before, over the
five states' sum; ``lib/launch_clock.py``)."""

from benchmarks.lib import launch_clock


def read(ctx):
    return launch_clock.share(ctx, "deviceWaitMs")
