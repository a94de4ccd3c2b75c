"""A table of the tests' own for the reference's index path: a member's
key that is sorted and partitioned over the segments (about 100 rows a
key), a text column, a day column and two integer measures wide enough
that a float32 sum of a member's rows is inexact. It has the
interface of ``benchmarks/tables/*`` as far as ``lib/oracle.py`` uses it.
"""

from typing import Dict, List

import numpy as np

TAGS = [f"tag{i:02d}" for i in range(16)]
STRING_DOMAINS: Dict[str, List[str]] = {"tag": TAGS}
DAYS = 365
ROWS_A_KEY = 100


def num_keys(rows: int) -> int:
    return max(rows // ROWS_A_KEY, 1)


def table_codes(num_segments: int, rows: int,
                seed: int) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    per = -(-rows // num_segments)
    keys = num_keys(rows)
    key = np.concatenate([
        np.sort(rng.integers(keys * i // num_segments,
                             max(keys * (i + 1) // num_segments,
                                 keys * i // num_segments + 1),
                             min(per, rows - i * per)))
        for i in range(num_segments) if rows - i * per > 0])
    return {"member": key.astype(np.int32),
            "tag": rng.integers(0, len(TAGS), rows).astype(np.int8),
            "day": rng.integers(0, DAYS, rows).astype(np.int16),
            "amount": rng.integers(1, 2 ** 30, rows).astype(np.int32),
            "cost": rng.integers(1, 2 ** 29, rows).astype(np.int32)}


def queries(rows: int, count: int, seed: int) -> List[Dict]:
    """Seeded lookups, as data: key alone, key with a day range, with a
    text ``in``, all three, a key no row has, a key list."""
    rng = np.random.default_rng(seed)
    keys = num_keys(rows)
    out = []
    for i in range(count):
        kind = i % 6
        member = int(rng.integers(0, keys))
        lo = int(rng.integers(0, DAYS - 60))
        day = ["day", "between", lo, lo + int(rng.integers(1, 60))]
        tags = ["tag", "in"] + [TAGS[j] for j in
                                rng.choice(len(TAGS), 4, replace=False)]
        where = [["member", "=", member]]
        if kind == 1:
            where.append(day)
        elif kind == 2:
            where.insert(0, tags)       # the key need not come first
        elif kind == 3:
            where += [day, tags]
        elif kind == 4:
            where = [["member", "=", keys + 7 + i], day]    # no such member
        elif kind == 5:
            where = [["member", "in"] + [int(v) for v in
                                         rng.integers(0, keys, 5)], tags]
        grouped = i % 2 == 1
        out.append({"id": i, "flight": f"L{kind}", "sql": f"lookup {i}",
                    "where": where,
                    "value": ["-", "amount", "cost"] if grouped
                    else ["amount"],
                    "group_by": ["tag", "day"] if grouped else [],
                    "order": "keys"})
    return out
