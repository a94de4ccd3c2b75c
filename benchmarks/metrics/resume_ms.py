"""Parallel launcher: how long a query's thread took to run again once
the dispatcher had set its launch's future (``Resume`` spans under
``ShardedCombine``, summed a query), median over the window's queries that
have one. The thread is runnable there with nothing to do but take the
interpreter lock. None from a program without the span."""

from benchmarks.lib import spans
from benchmarks.lib.stats import ms


def read(ctx):
    def one(root):
        found = [s for srv in spans.servers(root)
                 for s in spans.named(srv, "Resume")]
        return ms(found) if found else None

    return spans.per_query(ctx["records"], one)
