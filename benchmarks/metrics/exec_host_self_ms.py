"""Engine and parallel executor, host side: the server's execution span
less its queue waits and less its wait on the device, median.

The device wait is the sharded combine's ``workMs`` (launch + D2H) or,
on the per-segment ladder, the Kernel spans of the segment that waited
longest (segments may run side by side and spans carry no start time).
"""

from benchmarks.lib.stats import find, median, ms, roots


def read(ctx):
    out = []
    for _, root in roots(ctx["records"]):
        servers = find(root, "ServerQuery")
        if not servers:
            continue
        combine = find(root, "ShardedCombine")
        waits = ms(find(root, "Admission") + find(root, "SchedulerQueue")
                   + combine, "queueMs")
        if combine:
            device = ms(combine, "workMs")
        else:
            segments = (find(root, "SegmentGroupBy")
                        + find(root, "SegmentAggregate"))
            device = max((ms(find(s, "Kernel")) for s in segments),
                         default=0.0)
        out.append(ms(servers) - waits - device)
    return median(out)
