"""Engine and parallel executor, host side: wall time of the server's
segment pruning (the ``Prune`` span: partition membership, min/max and
bloom over every segment the request names), a query, median. Nothing to
read from a program without the span."""

from benchmarks.lib import spans
from benchmarks.lib.stats import ms


def read(ctx):
    def one(root):
        found = [s for srv in spans.servers(root)
                 for s in spans.named(srv, "Prune")]
        return ms(found) if found else None

    return spans.per_query(ctx["records"], one)
