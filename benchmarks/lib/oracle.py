"""The plain reference: numpy over the generated rows, none of the engine.

A query arrives as data (``where`` / ``value`` / ``group_by`` / ``order``
from the traffic file, never as SQL): a conjunction of comparisons, one
summed expression, group keys. A text literal selects the codes of the
domain's members that satisfy the comparison as text.
Sums are exact: every measure is an integer and float64 holds their sums.

Its cost is not rows x strings. ``Reference`` holds, for one table, what the
strings of a cycle share:

- a conjunct's mask over the whole table is computed once and kept for the
  strings that repeat it (SSB's variants share most of theirs), as far as
  ``MASK_CACHE_BYTES`` reach;
- a conjunct ``=`` / ``in`` on an integer column that takes more than
  ``INDEX_MIN_DISTINCT`` distinct values in this table (a member's key; no
  column of a ``STRING_DOMAINS`` entry) narrows by an index of the
  reference's own, built once a column: the stable argsort and the column
  sorted. The query's candidate rows are two ``searchsorted`` a literal,
  sorted ascending, and the other conjuncts are applied to those rows
  alone. Ascending rows keep the float32 control's ``np.add.at`` order, so
  ``want`` and ``control`` are the lists the full mask gives. None of the
  13 SSB families has such a conjunct (``d_year`` 7 values, ``lo_discount``
  11, ``lo_quantity`` 50, ``d_weeknuminyear`` 53, ``d_yearmonthnum`` 84):
  on SSB every query takes the full mask, as it always did.

Run as a script it is the oracle child of a benchmark run: it makes the
table from the seed, answers the cell's cycle and writes the answers as JSON.
numpy and the standard library only, so it never touches the chip.

A hybrid table (``hybrid_answers``) is answered in two parts. The static
part is ``want`` as above over the offline rows at or below the time
boundary, the largest offline time less one unit (upstream's
``TimeBoundaryManager``). The stream part is the rows the producer will
have appended by the end of the run, regenerated from the table module's
``stream_codes``: those of the members the cycle pins that lie past the
boundary, with their partition offsets, written beside the JSON as
``<out>.npz`` (the parent answers a response at the prefix of the stream
it could have read), and for each window's end each partition's count and
metric sums over all its rows.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

Row = Tuple[Any, ...]

# an integer column with more distinct values than this is a key worth an
# index; every filter column of the 13 SSB families has at most 84
INDEX_MIN_DISTINCT = 4096
# whole-table masks kept for the strings that repeat a conjunct
MASK_CACHE_BYTES = 1 << 30


def _conjunct(table_mod, column: str, op: str, lits: list, col: np.ndarray
              ) -> np.ndarray:
    """One comparison over ``col`` (the whole column or some rows of it),
    on codes: a text literal selects the codes of the domain's members that
    satisfy the comparison as text."""
    domain = table_mod.STRING_DOMAINS.get(column)
    if op not in ("=", "in", "<", "between"):
        raise ValueError(f"unknown comparison {op!r}")
    if domain is not None:
        test = {"=": lambda v: v == lits[0], "in": lambda v: v in lits,
                "<": lambda v: v < lits[0],
                "between": lambda v: lits[0] <= v <= lits[1]}[op]
        return np.isin(col, [i for i, v in enumerate(domain) if test(v)])
    if op == "<":
        return col < int(lits[0])
    if op == "between":
        return (col >= int(lits[0])) & (col <= int(lits[1]))
    return np.isin(col, [int(v) for v in lits])


def _mask(table_mod, cols: Dict[str, np.ndarray], where: List[list],
          idx: Optional[np.ndarray] = None) -> Optional[np.ndarray]:
    """The conjunction over the whole table, or over the rows ``idx``
    alone. No conjunct gives None: every row."""
    mask = None
    for column, op, *lits in where:
        col = cols[column] if idx is None else cols[column][idx]
        m = _conjunct(table_mod, column, op, lits, col)
        mask = m if mask is None else mask & m
    return mask


class Reference:
    """One table's rows and what the strings of a cycle share over them:
    the masks of conjuncts that repeat, the index of a key column. The
    counters are for the tests and for the set-up line."""

    def __init__(self, table_mod, cols: Dict[str, np.ndarray],
                 cycle: Optional[List[Dict[str, Any]]] = None):
        self.table_mod, self.cols = table_mod, cols
        self.repeats = Counter(tuple(c) for q in cycle or ()
                               for c in q["where"])
        self.masks: Dict[tuple, np.ndarray] = {}
        self.sorted: Dict[str, Optional[Tuple[np.ndarray, np.ndarray]]] = {}
        self.narrowed = 0       # queries that took their rows by an index
        self.masks_shared = 0   # conjunct masks found instead of computed

    def index(self, column: str
              ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """``(order, column[order])`` for a key column, None for any
        other. The range of an integer column bounds its distinct values,
        so no SSB column is ever sorted to find out."""
        if column not in self.sorted:
            col, found = self.cols[column], None
            if (column not in self.table_mod.STRING_DOMAINS
                    and col.dtype.kind in "iu" and len(col)
                    and int(col.max()) - int(col.min()) >= INDEX_MIN_DISTINCT):
                order = np.argsort(col, kind="stable")
                ordered = col[order]
                steps = np.count_nonzero(ordered[1:] != ordered[:-1])
                if steps + 1 > INDEX_MIN_DISTINCT:
                    found = (order, ordered)
            self.sorted[column] = found
        return self.sorted[column]

    def _narrow(self, where: List[list]) -> Optional[np.ndarray]:
        """The rows of the first conjunct an index serves, ascending, with
        the other conjuncts applied to them; None where no conjunct is
        one."""
        for n, (column, op, *lits) in enumerate(where):
            found = self.index(column) if op in ("=", "in") else None
            if found is None:
                continue
            order, ordered = found
            info = np.iinfo(ordered.dtype)
            # needles of the column's own dtype: numpy copies the column
            # to search it with any other
            needles = np.unique(np.asarray(
                [v for v in map(int, lits) if info.min <= v <= info.max],
                dtype=ordered.dtype))
            lo = np.searchsorted(ordered, needles, "left")
            hi = np.searchsorted(ordered, needles, "right")
            idx = np.sort(np.concatenate(
                [order[a:b] for a, b in zip(lo, hi)] or [order[:0]]))
            rest = _mask(self.table_mod, self.cols,
                         where[:n] + where[n + 1:], idx)
            self.narrowed += 1
            return idx if rest is None else idx[rest]
        return None

    def _shared_mask(self, conjunct: list) -> np.ndarray:
        key = tuple(conjunct)
        if key in self.masks:
            self.masks_shared += 1
            return self.masks[key]
        column, op, *lits = conjunct
        m = _conjunct(self.table_mod, column, op, lits, self.cols[column])
        if self.repeats[key] > 1:
            self.masks[key] = m
            kept = sum(k.nbytes for k in self.masks.values())
            while kept > MASK_CACHE_BYTES:
                kept -= self.masks.pop(next(iter(self.masks))).nbytes
        return m

    def rows(self, where: List[list]) -> np.ndarray:
        """The rows the conjunction keeps, ascending."""
        idx = self._narrow(where)
        if idx is not None:
            return idx
        mask = None
        for conjunct in where:
            m = self._shared_mask(conjunct)
            mask = m if mask is None else mask & m
        return np.flatnonzero(mask)


def _value(cols: Dict[str, np.ndarray], value: list, idx: np.ndarray,
           dtype) -> np.ndarray:
    if len(value) == 1:
        return cols[value[0]][idx].astype(dtype)
    op, a, b = value
    x, y = cols[a][idx].astype(dtype), cols[b][idx].astype(dtype)
    return x * y if op == "*" else x - y


def answer(table_mod, cols: Dict[str, np.ndarray], query: Dict[str, Any],
           dtype=np.float64, idx: Optional[np.ndarray] = None) -> List[Row]:
    """Rows shaped like the engine's resultTable: group keys as the engine
    prints them, then the sum. ``dtype`` float32 is the control: the sum
    accumulated in the precision below the deployment's exact answers.
    ``idx``: the rows the conjunction keeps, ascending, where the caller
    has them; else the full mask finds them."""
    if idx is None:
        idx = np.flatnonzero(_mask(table_mod, cols, query["where"]))
    vals = _value(cols, query["value"], idx, dtype)
    keys = query["group_by"]
    if not keys:
        return [(float(vals.sum(dtype=dtype)),)]
    combined = np.zeros(len(idx), dtype=np.int64)
    sizes = []
    for k in keys:
        col = cols[k][idx].astype(np.int64)
        base = 0 if k in table_mod.STRING_DOMAINS else int(col.min(initial=0))
        size = int(col.max(initial=0)) - base + 1
        combined = combined * size + (col - base)
        sizes.append((k, base, size))
    uniq, inverse = np.unique(combined, return_inverse=True)
    if dtype == np.float64:
        sums = np.bincount(inverse, weights=vals, minlength=len(uniq))
    else:
        sums = np.zeros(len(uniq), dtype=dtype)
        np.add.at(sums, inverse, vals)
    rows = []
    for g, s in zip(uniq.tolist(), sums.tolist()):
        parts = []
        for k, base, size in reversed(sizes):
            g, r = divmod(g, size)
            domain = table_mod.STRING_DOMAINS.get(k)
            parts.append(domain[r] if domain is not None else r + base)
        rows.append(tuple(reversed(parts)) + (float(s),))
    if query["order"] == "last_key_then_value_desc":
        rows.sort(key=lambda r: (r[-2], -r[-1]))
    else:
        rows.sort(key=lambda r: r[:-1])
    return rows


def answers(table_mod, cols: Dict[str, np.ndarray],
            cycle: List[Dict[str, Any]], control: bool) -> Dict[str, Any]:
    """``want`` (and the float32 ``control``) for every string of the
    cycle, by its id."""
    ref = Reference(table_mod, cols, cycle)
    ids = [str(q["id"]) for q in cycle]     # the answers in cycle order
    out: Dict[str, Any] = {"want": dict.fromkeys(ids)}
    if control:
        out["control"] = dict.fromkeys(ids)
    # strings that share conjuncts stand together: their masks stay found
    for q in sorted(cycle, key=lambda q: (q["flight"], q["sql"])):
        idx = ref.rows(q["where"])
        out["want"][str(q["id"])] = answer(table_mod, cols, q, idx=idx)
        if control:
            out["control"][str(q["id"])] = answer(table_mod, cols, q,
                                                  np.float32, idx=idx)
    out["oracle"] = {"strings": len(cycle), "narrowed": ref.narrowed,
                     "masks_shared": ref.masks_shared}
    return out


def hybrid_answers(table_mod, cols: Dict[str, np.ndarray],
                   cycle: List[Dict[str, Any]], control: bool, seed: int,
                   hybrid: Dict[str, Any]) -> Dict[str, Any]:
    """``want`` (and ``control``) over the offline rows at or below the
    boundary; ``stream``, the arrays of the members' rows past it, sorted
    by member and offset; ``totals``, a partition's count and metric sums
    at each window's end."""
    tc, parts = hybrid["time_column"], hybrid["partitions"]
    boundary = int(cols[tc].max()) - 1
    keep = np.flatnonzero(cols[tc] <= boundary)
    out = answers(table_mod, {k: v[keep] for k, v in cols.items()}, cycle,
                  control)
    members = np.asarray(hybrid["members"], dtype=np.int64)
    chunks, totals = [], []
    for p in range(parts):
        end = hybrid["appended"][-1][p]
        codes = table_mod.stream_codes(p, 0, end, seed, parts)
        totals.append([[n] + [int(codes[m][:n].sum(dtype=np.int64))
                              for m in hybrid["metrics"]]
                       for n in (ends[p] for ends in hybrid["appended"])])
        rows = np.flatnonzero(np.isin(codes[hybrid["partition_column"]],
                                      members) & (codes[tc] > boundary))
        chunk = {k: v[rows] for k, v in codes.items()}
        chunk["offset"] = rows.astype(np.int64)
        chunks.append(chunk)
    stream = {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}
    order = np.lexsort((stream["offset"], stream[hybrid["partition_column"]]))
    out["stream"] = {k: v[order] for k, v in stream.items()}
    out["hybrid"] = {"boundary": boundary, "offline_rows": int(len(keep)),
                     "stream_rows": int(len(order)),
                     "totals": [list(w) for w in zip(*totals)]}
    return out


def answers_for(table: str, num_segments: int, rows: int, seed: int,
                cycle: List[Dict[str, Any]], control: bool,
                hybrid: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    t0 = time.perf_counter()
    table_mod = importlib.import_module(f"benchmarks.tables.{table}")
    cols = table_mod.table_codes(num_segments, rows, seed)
    made = time.perf_counter() - t0
    out = (answers(table_mod, cols, cycle, control) if hybrid is None else
           hybrid_answers(table_mod, cols, cycle, control, seed, hybrid))
    out["oracle"].update(table_s=made, seconds=time.perf_counter() - t0)
    return out


def main(argv: List[str]) -> int:
    """``oracle.py <job.json> <out.json>``: the job is a dict of
    ``answers_for``'s arguments, written by the parent."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(argv[0]) as f:
        job = json.load(f)
    out = answers_for(**job)
    if "stream" in out:
        with open(argv[1] + ".npz.tmp", "wb") as f:
            np.savez(f, **out.pop("stream"))
        os.replace(argv[1] + ".npz.tmp", argv[1] + ".npz")
    with open(argv[1] + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(argv[1] + ".tmp", argv[1])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
