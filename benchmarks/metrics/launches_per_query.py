"""Parallel launcher: programs launched on the device in the traced span
(events of the trace's ``XLA Modules`` line, mean over chips) for each
query answered inside it; a count. The program's own ``/debug/launches``
counts only what goes through the sharded combine's dispatcher, and reads
0 where the star-tree ladder launches segment by segment."""


def read(ctx):
    if not ctx["device"] or not ctx["in_trace"]:
        return None
    return ctx["device"]["launches"] / len(ctx["in_trace"])
