"""From a profiler trace (``.xplane.pb``) to device numbers.

Which planes and lines are the device's (looked at by hand on a v5e
trace, PERF.md "Layers"): a plane named ``/device:TPU:<n>`` is one chip;
its line ``XLA Ops`` holds one event per executed HLO operation (named by
the operation's whole HLO text), and ``XLA Modules`` one per launched
program (``jit_kernel(<fingerprint>)``). ``Async XLA Ops`` repeats the
copies from start to done and is not read. Host threads are lines of
``/host:CPU``. Event times are nanoseconds on one clock for all planes.

The harness writes two ``TraceAnnotation`` marks on the host
(``bench_window_begin`` / ``bench_window_end``) and notes the wall clock at
each; they bound the traced span and tie the trace's clock to the
client's.
"""

from __future__ import annotations

import bisect

from typing import Any, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_BEGIN = "bench_window_begin"
MARK_END = "bench_window_end"

Interval = Tuple[float, float]


def load(path: str) -> Dict[str, Any]:
    """Device-op and program events per chip and the two marks, in
    seconds on the trace's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    modules: Dict[str, List[Tuple[str, float, float]]] = {}
    marks: Dict[str, float] = {}
    layout: List[str] = []
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            layout.append(f"{plane.name} | {line.name} | {len(events)}")
            if (plane.name.startswith(DEVICE_PLANE)
                    and line.name in (OPS_LINE, MODULES_LINE)):
                into = devices if line.name == OPS_LINE else modules
                into[plane.name] = [
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9) for e in events]
            elif plane.name.startswith("/host:"):
                for e in events:
                    if e.name in (MARK_BEGIN, MARK_END):
                        marks[e.name] = e.start_ns * 1e-9
    return {"devices": devices, "modules": modules, "marks": marks,
            "layout": layout}


def short_name(op: str, programs: Sequence[Tuple[str, float, float]],
               starts: Sequence[float], at: float) -> str:
    """``jit_kernel/fusion.6`` from the operation's HLO text and the
    program that was running when it started."""
    name = op.split(" = ")[0].lstrip("%")
    i = bisect.bisect_right(starts, at) - 1
    if i >= 0 and at <= programs[i][2]:
        return programs[i][0].split("(")[0] + "/" + name
    return name


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] between merged busy intervals."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def in_flight_name(records: Sequence[Dict[str, Any]], at: float) -> str:
    """What the host had in hand at wall time ``at``: the flight groups of
    the requests sent and not yet answered."""
    groups = sorted({r["group"] for r in records
                     if r["sent_wall"] <= at < r["done_wall"]})
    return ("in_flight_host_side:" + "_".join(groups) if groups
            else "no_request_in_flight")


def reduce(trace: Dict[str, Any], wall_begin: float, wall_end: float,
           records: Sequence[Dict[str, Any]], top: int = 10
           ) -> Optional[Dict[str, Any]]:
    """Busy seconds (union of device-op intervals, mean over chips), the
    span's length, programs launched, per-op sums and the idle gaps by
    what was in flight.
    ``records`` carry ``sent_wall`` / ``done_wall`` / ``group``. Nothing
    on a device line gives None."""
    if not trace["devices"]:
        return None
    marks = trace["marks"]
    if MARK_BEGIN in marks and MARK_END in marks:
        lo, hi = marks[MARK_BEGIN], marks[MARK_END]
    else:                        # no marks: the span the device ops cover
        lo = min(e[1] for ev in trace["devices"].values() for e in ev)
        hi = max(e[2] for ev in trace["devices"].values() for e in ev)
    to_wall = wall_begin - lo
    window = hi - lo
    busy_total = 0.0
    launches = 0
    op_s: Dict[str, float] = {}
    gap_s: Dict[str, float] = {}
    for plane, events in trace["devices"].items():
        programs = sorted(trace.get("modules", {}).get(plane, ()),
                          key=lambda e: e[1])
        starts = [e[1] for e in programs]
        launches += sum(lo <= a < hi for a in starts)
        inside = [(short_name(n, programs, starts, a), max(a, lo),
                   min(b, hi)) for n, a, b in events
                  if min(b, hi) > max(a, lo)]
        busy = union([(a, b) for _, a, b in inside])
        busy_total += sum(b - a for a, b in busy)
        for name, a, b in inside:
            op_s[name] = op_s.get(name, 0.0) + (b - a)
        for a, b in gaps(busy, lo, hi):
            name = in_flight_name(records, (a + b) / 2.0 + to_wall)
            gap_s[name] = gap_s.get(name, 0.0) + (b - a)
    chips = len(trace["devices"])
    rank = lambda d: [[k, v] for k, v in  # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_total / chips, "window_s": window,
            "op_seconds": sum(op_s.values()) / chips,
            "launches": launches / chips,
            "wall_begin": lo + to_wall, "wall_end": hi + to_wall,
            "device_ops": rank(op_s), "idle_gaps": rank(gap_s)}
