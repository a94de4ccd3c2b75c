"""The plain reference: numpy over the generated rows, none of the engine.

A query arrives as data (``where`` / ``value`` / ``group_by`` / ``order``
from the traffic file, never as SQL): a conjunction of comparisons, one
summed expression, group keys. A text literal selects the codes of the
domain's members that satisfy the comparison as text.
Sums are exact: every measure is an integer and float64 holds their sums.

Run as a script it is the oracle child of a benchmark run: it makes the
table from the seed, answers the cell's cycle and writes the answers as JSON.
numpy and the standard library only, so it never touches the chip.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

from typing import Any, Dict, List, Tuple

import numpy as np

Row = Tuple[Any, ...]


def _mask(table_mod, cols: Dict[str, np.ndarray], where: List[list]
          ) -> np.ndarray:
    """The conjunction, on codes: a text literal selects the codes of the
    domain's members that satisfy the comparison as text."""
    mask = None
    for column, op, *lits in where:
        col = cols[column]
        domain = table_mod.STRING_DOMAINS.get(column)
        if op not in ("=", "in", "<", "between"):
            raise ValueError(f"unknown comparison {op!r}")
        if domain is not None:
            test = {"=": lambda v: v == lits[0], "in": lambda v: v in lits,
                    "<": lambda v: v < lits[0],
                    "between": lambda v: lits[0] <= v <= lits[1]}[op]
            m = np.isin(col, [i for i, v in enumerate(domain) if test(v)])
        elif op == "<":
            m = col < int(lits[0])
        elif op == "between":
            m = (col >= int(lits[0])) & (col <= int(lits[1]))
        else:
            m = np.isin(col, [int(v) for v in lits])
        mask = m if mask is None else mask & m
    return mask


def _value(cols: Dict[str, np.ndarray], value: list, idx: np.ndarray,
           dtype) -> np.ndarray:
    if len(value) == 1:
        return cols[value[0]][idx].astype(dtype)
    op, a, b = value
    x, y = cols[a][idx].astype(dtype), cols[b][idx].astype(dtype)
    return x * y if op == "*" else x - y


def answer(table_mod, cols: Dict[str, np.ndarray], query: Dict[str, Any],
           dtype=np.float64) -> List[Row]:
    """Rows shaped like the engine's resultTable: group keys as the engine
    prints them, then the sum. ``dtype`` float32 is the control: the sum
    accumulated in the precision below the deployment's exact answers."""
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


def answers_for(table: str, num_segments: int, rows: int, seed: int,
                cycle: List[Dict[str, Any]], control: bool
                ) -> Dict[str, Any]:
    table_mod = importlib.import_module(f"benchmarks.tables.{table}")
    cols = table_mod.table_codes(num_segments, rows, seed)
    out: Dict[str, Any] = {"want": {str(q["id"]): answer(table_mod, cols, q)
                                    for q in cycle}}
    if control:
        out["control"] = {str(q["id"]): answer(table_mod, cols, q, np.float32)
                          for q in cycle}
    return out


def main(argv: List[str]) -> int:
    """``oracle.py <job.json> <out.json>``: the job is a dict of
    ``answers_for``'s arguments, written by the parent."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(argv[0]) as f:
        job = json.load(f)
    out = answers_for(**job)
    with open(argv[1] + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(argv[1] + ".tmp", argv[1])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
