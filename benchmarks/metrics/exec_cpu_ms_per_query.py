"""Engine and parallel executor, host side: CPU time the server's threads
spent on a query: every span under ``ServerQuery`` counted once (its
``cpuMs`` less its same-thread children's), summed, mean over the window's
queries (the thread clock may tick coarsely: ``lib/spans.cpu_of``). Against
the wall clock a query costs the process (1000 / queries a second) it says
how much of that one interpreter lock can explain."""

from benchmarks.lib import spans


def read(ctx):
    def one(root):
        found = spans.servers(root)
        if not found:
            return None
        return sum(spans.self_cpu_ms(s) for srv in found
                   for s in spans.walk(srv))

    return spans.per_query(ctx["records"], one, spans.mean)
