"""The launch dispatcher's own clock, read as counters.

``/debug/launches`` ``clock`` (``parallel/launcher.py``) charges every
second of the dispatcher thread's life to one of five states, cumulative:
``emptyMs``, ``wakingMs``, ``dispatchingMs``, ``deviceWaitMs``,
``handingOffMs``. A share is one state's growth over the window (after
less before) over the growth of the five together, so it needs no window
length and counts a group once however many requests rode it. A program
without the clock serves no ``clock``; every share is then None.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

STATES = ("emptyMs", "wakingMs", "dispatchingMs", "deviceWaitMs",
          "handingOffMs")


def share(ctx: Dict[str, Any], state: str) -> Optional[float]:
    """``state``'s growth over the window in percent of all five's."""
    before = ctx["before"]["launches"].get("clock")
    after = ctx["after"]["launches"].get("clock")
    if not before or not after:
        return None
    grew = {s: float(after[s]) - float(before[s]) for s in STATES}
    total = sum(grew.values())
    if total <= 0:
        return None
    return 100.0 * grew[state] / total
