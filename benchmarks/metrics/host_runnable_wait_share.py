"""Engine and parallel executor, host side: the share of the host phases'
wall time in which their thread was not on a CPU (waiting for the
interpreter lock, for another lock, for the operating system): over the
spans under ``ServerQuery`` that are neither the device wait nor a queue,
1 - (sum of self CPU) / (sum of self wall), over all queries of the
window; a span's own queue wait (``queueMs``) is taken off its wall."""

from benchmarks.lib import spans
from benchmarks.lib.stats import roots


def read(ctx):
    cpu = wall = 0.0
    for _, root in roots(ctx["records"]):
        at = spans.place(root)
        for srv in spans.servers(root):
            for s in spans.walk(srv):
                if s["name"] == spans.DEVICE_WAIT \
                        or s["name"] in spans.QUEUE_SPANS:
                    continue
                cpu += spans.self_cpu_ms(s)
                wall += max(spans.self_wall_ms(s, at)
                            - float(s.get("queueMs") or 0.0), 0.0)
    if wall <= 0:
        return None
    return 100.0 * max(1.0 - cpu / wall, 0.0)
