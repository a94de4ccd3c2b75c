"""Parallel launcher: the share of the dispatcher thread's time over the
window spent in a group's jit calls, from the group's start until the last
call returns (``/debug/launches`` ``clock.dispatchingMs``, after less
before, over the five states' sum; ``lib/launch_clock.py``)."""

from benchmarks.lib import launch_clock


def read(ctx):
    return launch_clock.share(ctx, "dispatchingMs")
