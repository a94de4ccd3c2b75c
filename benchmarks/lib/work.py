"""The least work a query asks of the chip, from the data and the query.

Reads only the benchmark's own table module and the query as data (the
``where`` / ``value`` / ``group_by`` of the traffic file), never the engine:
it counts the same work whatever kernel serves it.

A scan's least bytes: every row of the segments the query's time predicate
does not prune, times the dictionary-packed width of the columns the query
names, ``ceil(log2(cardinality))`` bits each. Its least time is those
bytes over the chip's memory bandwidth; a scan has no arithmetic that
could bound it first.
"""

from __future__ import annotations

import math

from typing import Any, Dict, List


def packed_bits(table_mod, column: str) -> int:
    return max(1, math.ceil(math.log2(table_mod.CARDINALITY[column])))


def columns_named(query: Dict[str, Any]) -> List[str]:
    named = [w[0] for w in query["where"]]
    named += [v for v in query["value"] if v not in ("*", "-")]
    named += query["group_by"]
    return sorted(set(named))


def _holds(value: int, op: str, lits: List[Any]) -> bool:
    if op == "=":
        return value == int(lits[0])
    if op == "in":
        return value in [int(v) for v in lits]
    if op == "<":
        return value < int(lits[0])
    if op == "between":
        return int(lits[0]) <= value <= int(lits[1])
    raise ValueError(f"unknown comparison {op!r}")


def segments_kept(table_mod, query: Dict[str, Any],
                  num_segments: int) -> List[int]:
    """Segments with at least one month that every time predicate of the
    query admits (segments are time-bounded; any other predicate prunes
    nothing)."""
    kept = []
    for i in range(num_segments):
        def admits(month: int) -> bool:
            for column, op, *lits in query["where"]:
                if column == "d_yearmonthnum":
                    ok = _holds(month, op, lits)
                elif column == "d_year":
                    ok = _holds(month // 100, op, lits)
                else:
                    continue
                if not ok:
                    return False
            return True
        if any(admits(m) for m in table_mod.segment_months(i, num_segments)):
            kept.append(i)
    return kept


def scan_least_bytes(table_mod, query: Dict[str, Any], num_segments: int,
                     rows: int) -> float:
    sizes = table_mod.segment_sizes(num_segments, rows)
    scanned = sum(sizes[i] for i in segments_kept(table_mod, query,
                                                  num_segments))
    bits = sum(packed_bits(table_mod, c) for c in columns_named(query))
    return scanned * bits / 8.0


def scan_least_seconds(table_mod, query: Dict[str, Any], num_segments: int,
                       rows: int, peak: Dict[str, Any]) -> float:
    return (scan_least_bytes(table_mod, query, num_segments, rows)
            / float(peak["hbm_bytes_per_s"]))
