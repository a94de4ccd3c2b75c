"""Load generator: how late the open loop's requests left, against the
moment each was due at the cell's rate, median over the window. Under a
millisecond the window stood as an open loop; above it the generator's
connections were all busy and the offered load was the system's own pace.
Nothing to read under a closed loop."""

from benchmarks.lib.stats import median


def read(ctx):
    return median([r["late_ms"] for r in ctx["records"] if "late_ms" in r])
