"""Parallel combine (ICI): of the chips' device-op time in the traced span,
the share spent in collectives: moving partials between chips, or waiting
in the merge for a slower chip. Read from the trace's ranked list of device
operations (``device_ops``: seconds summed over chips, the ten largest), so
a collective below the tenth place is not counted: a floor. Nothing to
read for a config without a ``mesh``.

``COLLECTIVES``: HLO names as the v5e trace's ``XLA Ops`` line has them.
Read by hand off the four-chip host's trace (PERF.md section 5): the
combine's psum / pmin / pmax are ``all-reduce``, ``all-reduce.1``,
``all-reduce.5`` under ``jit_pallas_scan_sharded`` and
``jit_pallas_probe_sharded``, and nothing else of the list occurs today;
the others are XLA's names for what a later combine may use, and an
asynchronous pair's ``-start`` / ``-done`` share their operation's prefix."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(op: str) -> bool:
    """``jit_pallas_scan_sharded/all-reduce-start.3`` is; a fusion is not."""
    return op.rsplit("/", 1)[-1].startswith(COLLECTIVES)


def read(ctx):
    dev, mesh = ctx["device"], ctx["config"].get("mesh")
    if not mesh or not dev or dev["op_seconds"] <= 0:
        return None
    devices = mesh["seg"] * mesh["doc"]
    seconds = sum(s for op, s in dev["device_ops"] if is_collective(op))
    return 100.0 * seconds / (dev["op_seconds"] * devices)
