"""Kernels: device-op time in the traced span (summed durations, mean
over chips) for each query answered inside it."""


def read(ctx):
    if not ctx["device"] or not ctx["in_trace"]:
        return None
    return ctx["device"]["op_seconds"] * 1e3 / len(ctx["in_trace"])
