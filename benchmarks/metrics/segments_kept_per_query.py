"""Engine and parallel executor, host side: segments a query still has to
look at after pruning (``kept`` of the ``Prune`` span), mean over the
window's queries. A count."""

from benchmarks.lib import spans


def read(ctx):
    def one(root):
        found = [s for srv in spans.servers(root)
                 for s in spans.named(srv, "Prune") if "kept" in s]
        return float(sum(s["kept"] for s in found)) if found else None

    return spans.per_query(ctx["records"], one, spans.mean)
