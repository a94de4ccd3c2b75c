"""Arithmetic every reader shares: order statistics and span-tree walks.

A span is the program's wire form: ``{"name", "ms", "queueMs"?, "workMs"?,
"children"?, ...attributes}``. Spans carry no start time, so a parent's
self time is its ``ms`` less what the named children took.
"""

from __future__ import annotations

import math

from typing import Any, Dict, Iterable, List, Optional, Sequence


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest rank: the smallest value with at least ``q`` percent of the
    sample at or below it. Nothing to read gives nothing."""
    if not values:
        return None
    xs = sorted(values)
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def find(node: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    found = [node] if node.get("name") == name else []
    for child in node.get("children", ()):
        found += find(child, name)
    return found


def roots(records: Iterable[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """(record, broker root span) for every traced response that has one."""
    out = []
    for rec in records:
        raw = rec.get("raw") or {}
        spans = (raw.get("traceInfo") or {}).get("spans") or []
        if rec.get("ok") and spans:
            out.append((rec, spans[0]))
    return out


def ms(spans: Iterable[Dict[str, Any]], key: str = "ms") -> float:
    return sum(float(s.get(key) or 0.0) for s in spans)
