"""Parallel launcher: the share of the dispatcher thread's time over the
window spent being woken: from the submit that found it waiting until it
holds the drained queue (``/debug/launches`` ``clock.wakingMs``, after less
before, over the five states' sum; ``lib/launch_clock.py``)."""

from benchmarks.lib import launch_clock


def read(ctx):
    return launch_clock.share(ctx, "wakingMs")
