"""Device: the share of the traced span in which no request had a launch
under way: not covered by any ``Dispatch`` start to ``DeviceWait`` end of
any request (the roots' ``startEpochMs`` put every record's spans on the
wall clock the trace's marks are tied to). Beside ``device_idle_share`` it
says how much of the idle device the host never gave work to."""

from benchmarks.lib import spans
from benchmarks.lib.stats import roots


def read(ctx):
    dev = ctx["device"]
    if not dev or dev["wall_end"] <= dev["wall_begin"]:
        return None
    lo, hi = dev["wall_begin"] * 1e3, dev["wall_end"] * 1e3
    launches = [iv for _, root in roots(ctx["records"])
                for iv in spans.launch_intervals(root)]
    if not launches:
        return None
    covered = spans.length((max(a, lo), min(b, hi)) for a, b in launches
                           if min(b, hi) > max(a, lo))
    return 100.0 * (1.0 - covered / (hi - lo))
