"""Arithmetic on span trees that carry a clock.

A span of a program with the clock is the wire form ``lib/stats.py``
describes plus ``startMs`` (its start as an offset from its root's),
``cpuMs`` (CPU time of the thread that ran it, as fine as the machine's
thread clock ticks; 0 for a pure wait) and ``thread``; a root (``BrokerQuery``, ``ServerQuery``) also carries
``startEpochMs``, its start on the wall clock in ms, so every span of a
tree, whichever process recorded it, can be laid on the client's clock. A
span's self time is its ``ms`` less the union of its children's intervals:
children may have run side by side on other threads.

A program without the clock gives trees without ``startMs``; every
function here then finds nothing and the readers return None.
"""

from __future__ import annotations

from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    Iterator,
    List,
    Optional,
    Sequence,
    Tuple,
)

from benchmarks.lib.stats import find, median, ms, roots
from benchmarks.lib.trace_reduce import union

Interval = Tuple[float, float]
Span = Dict[str, Any]

# spans that are nothing but a wait in a queue (queueMs == ms)
QUEUE_SPANS = ("Admission", "SchedulerQueue", "SegmentQueue")
DEVICE_WAIT = "DeviceWait"


def has_clock(span: Span) -> bool:
    return "startMs" in span


def walk(span: Span) -> Iterator[Span]:
    yield span
    for child in span.get("children", ()):
        yield from walk(child)


def place(root: Span) -> Dict[int, Interval]:
    """``id(span) -> (start, end)`` in wall-clock ms for every span of
    the tree. A span with ``startEpochMs`` stands on the wall clock by
    itself and is the base of the spans below it."""
    out: Dict[int, Interval] = {}

    def put(span: Span, base: float) -> None:
        if "startEpochMs" in span:
            base = start = float(span["startEpochMs"])
        else:
            start = base + float(span.get("startMs", 0.0))
        out[id(span)] = (start, start + float(span["ms"]))
        for child in span.get("children", ()):
            put(child, base)

    put(root, 0.0)
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(list(intervals)))


def self_wall_ms(span: Span, at: Dict[int, Interval]) -> float:
    """``ms`` less the union of the children's intervals, each clipped to
    the span's own."""
    lo, hi = at[id(span)]
    kids = [(max(a, lo), min(b, hi)) for a, b in
            (at[id(c)] for c in span.get("children", ()))]
    return max((hi - lo) - length(k for k in kids if k[1] > k[0]), 0.0)


def self_cpu_ms(span: Span) -> float:
    """``cpuMs`` less the children's that ran on the span's own thread (a
    thread's CPU clock counts them inside the span; a child on another
    thread is counted by itself alone)."""
    same = sum(float(c.get("cpuMs") or 0.0)
               for c in span.get("children", ())
               if c.get("thread") == span.get("thread"))
    return max(float(span.get("cpuMs") or 0.0) - same, 0.0)


def named(span: Span, *names: str) -> List[Span]:
    return [s for name in names for s in find(span, name)]


def servers(root: Span) -> List[Span]:
    """The ``ServerQuery`` trees of a broker root that carry the clock."""
    return [s for s in find(root, "ServerQuery") if has_clock(s)]


def per_query(records: Sequence[Dict[str, Any]],
              fn: Callable[[Span], Optional[float]],
              reduce: Callable[[Sequence[float]], Optional[float]] = median
              ) -> Optional[float]:
    """``reduce`` (the median) over the traced responses of ``fn(broker
    root)``; a query for which ``fn`` finds nothing to read is left out."""
    values = [fn(root) for _, root in roots(records)
              if has_clock(root)]
    return reduce([v for v in values if v is not None])


def mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def cpu_of(records: Sequence[Dict[str, Any]], *names: str
           ) -> Optional[float]:
    """Sum of ``cpuMs`` over a query's server-side spans of these names,
    mean over the queries that have one; None where none has. The mean
    and not the median: where the thread's CPU clock ticks coarsely (10
    ms on the chip's machine) one query's sum is a multiple of the tick,
    and only the mean over a window's hundreds of queries resolves
    finer."""
    def one(root: Span) -> Optional[float]:
        spans = [s for srv in servers(root) for s in named(srv, *names)]
        return ms(spans, "cpuMs") if spans else None

    return per_query(records, one, mean)


def wall_union_of(records: Sequence[Dict[str, Any]], name: str
                  ) -> Optional[float]:
    """Union of the intervals of a query's server-side spans of this
    name, in ms, median; None where no query has one."""
    def one(root: Span) -> Optional[float]:
        at = place(root)
        spans = [s for srv in servers(root) for s in find(srv, name)]
        return length(at[id(s)] for s in spans) if spans else None

    return per_query(records, one)


def launch_intervals(root: Span) -> List[Interval]:
    """One ``(Dispatch.start, DeviceWait.end)`` in wall-clock ms for every
    launch of the query: the time in which the device was handed work or
    owed an answer."""
    at = place(root)
    out = []
    for srv in servers(root):
        for parent in walk(srv):
            kids = parent.get("children", ())
            starts = [at[id(c)][0] for c in kids if c["name"] == "Dispatch"]
            ends = [at[id(c)][1] for c in kids if c["name"] == DEVICE_WAIT]
            if starts and ends:
                out.append((min(starts), max(ends)))
    return out
