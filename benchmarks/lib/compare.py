"""The comparison that decides ``correct``.

Every response of the window is held to the deployment's guarantees: it
answered in full (HTTP 200, no exception, every server that was asked
answered, not partial) and its rows are the oracle's, exactly. Every SSB
measure is an integer, so the limit on the largest difference is 0.
Grouped rows match by key; the response's own order has to satisfy the
query's ORDER BY (ties may fall either way).
"""

from __future__ import annotations

import json

from typing import Any, Dict, List, Optional, Sequence, Tuple


def check_response(status: int, body: str
                   ) -> Tuple[Optional[Dict[str, Any]], Optional[str]]:
    """(parsed response, None) where the guarantees hold, else (None or
    the parsed response, what broke)."""
    if status != 200:
        return None, f"HTTP {status}"
    try:
        raw = json.loads(body)
    except ValueError:
        return None, "body is not JSON"
    if raw.get("exceptions"):
        return raw, f"exceptions: {str(raw['exceptions'])[:200]}"
    if (raw.get("numServersResponded") != raw.get("numServersQueried")
            or raw.get("partialResult")):
        return raw, "partial response"
    if "resultTable" not in raw:
        return raw, "no resultTable"
    return raw, None


def _ordered(rows: Sequence[Sequence[Any]], order: str) -> bool:
    if order == "last_key_then_value_desc":
        keys = [(r[-2], -float(r[-1])) for r in rows]
    else:
        keys = [tuple(r[:-1]) for r in rows]
    return all(a <= b for a, b in zip(keys, keys[1:]))


def rows_gap(got: Sequence[Sequence[Any]], want: Sequence[Sequence[Any]],
             order: str) -> Tuple[bool, float]:
    """(rows agree in keys, count and order; the largest absolute
    difference of a sum). A missing or extra group reads as infinite."""
    a = {tuple(str(x) for x in r[:-1]): float(r[-1]) for r in got}
    b = {tuple(str(x) for x in r[:-1]): float(r[-1]) for r in want}
    if len(got) != len(want) or set(a) != set(b):
        return False, float("inf")
    gap = max((abs(a[k] - b[k]) for k in a), default=0.0)
    return _ordered(got, order), gap


def compare(records: List[Dict[str, Any]], cycle: List[Dict[str, Any]],
            want: Dict[str, list], limit_abs: float = 0.0
            ) -> Dict[str, Any]:
    """All records of one client run against the oracle. Marks each record
    ``ok`` in place and returns the numbers compared."""
    failed: List[str] = []
    wrong: List[str] = []
    worst = 0.0
    for rec in records:
        q = cycle[rec["index"]]
        raw, why = check_response(rec["status"], rec["body"])
        rec["raw"] = raw
        if why is not None:
            rec["ok"] = False
            failed.append(f"{q['flight']}#{q['id']}: "
                          f"{rec.get('error') or why}")
            continue
        same, gap = rows_gap(raw["resultTable"]["rows"],
                             want[str(q["id"])], q["order"])
        worst = max(worst, gap)
        rec["ok"] = same and gap <= limit_abs
        if not rec["ok"]:
            wrong.append(f"{q['flight']}#{q['id']}: gap {gap}, "
                         f"keys/order {'ok' if same else 'differ'}")
    return {"responses_compared": len(records) - len(failed),
            "responses_failed": len(failed),
            "responses_wrong": len(wrong),
            "max_abs_diff": worst,
            "first_failed": failed[:3], "first_wrong": wrong[:3]}
