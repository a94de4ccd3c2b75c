"""Engine and parallel executor, host side: what no named span covers of
the server's execution: ``ServerQuery``'s length less the union of its
children's intervals, median. The target is under 5% of ``ServerQuery``."""

from benchmarks.lib import spans


def read(ctx):
    def one(root):
        at = spans.place(root)
        found = spans.servers(root)
        return sum(spans.self_wall_ms(s, at) for s in found) if found else None

    return spans.per_query(ctx["records"], one)
