"""SSB flat ``lineorder`` rows from a seed: the benchmark's own generator.

A copy of the draw order of ``pinot_tpu/tools/ssb.py`` (dbgen's value
distributions on the denormalised table), kept here so that a later PR
cannot change the yardstick's data. It draws dictionary CODES first and
only the segment builder's children turn them into strings: the oracle
works on narrow integer codes and never holds the table as text.

numpy only. Nothing of the program is imported here.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS_BY_REGION = [
    ["ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE"],
    ["ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES"],
    ["CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM"],
    ["FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM"],
    ["EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA"],
]
# generation order: nation g = region * 5 + pick; city = g * 10 + digit
NATIONS = [n for row in NATIONS_BY_REGION for n in row]
CITIES = [f"{n[:9]:<9}{c}" for n in NATIONS for c in range(10)]
MFGRS = [f"MFGR#{m}" for m in range(1, 6)]
CATEGORIES = [f"MFGR#{m}{c}" for m in range(1, 6) for c in range(1, 6)]
BRANDS = [f"{cat}{b:02d}" for cat in CATEGORIES for b in range(1, 41)]
MONTHS = [y * 100 + m for y in range(1992, 1999) for m in range(1, 13)]

# string columns -> the list a generated code indexes
STRING_DOMAINS: Dict[str, List[str]] = {
    "c_region": REGIONS, "c_nation": NATIONS, "c_city": CITIES,
    "s_region": REGIONS, "s_nation": NATIONS, "s_city": CITIES,
    "p_mfgr": MFGRS, "p_category": CATEGORIES, "p_brand1": BRANDS,
}
# distinct values each column can take (lib/work.py packs a column into
# ceil(log2(cardinality)) bits); the integer measures count their range
CARDINALITY: Dict[str, int] = {
    "lo_quantity": 50, "lo_discount": 11,
    "lo_extendedprice": 50 * 110_999, "lo_revenue": 50 * 110_999,
    "lo_supplycost": 66_600 - 540,
    "d_year": 7, "d_yearmonthnum": 84, "d_weeknuminyear": 53,
    "c_region": 5, "c_nation": 25, "c_city": 250,
    "s_region": 5, "s_nation": 25, "s_city": 250,
    "p_mfgr": 5, "p_category": 25, "p_brand1": 1000,
}


def segment_sizes(num_segments: int, rows: int) -> List[int]:
    per = -(-rows // num_segments)
    sizes, left = [], rows
    while left > 0 and len(sizes) < num_segments:
        sizes.append(min(per, left))
        left -= sizes[-1]
    return sizes


def segment_months(i: int, num_segments: int) -> List[int]:
    """The contiguous month window of segment ``i`` (84 months split in
    order: segments are time-bounded as a Pinot table's are)."""
    per = -(-len(MONTHS) // num_segments)
    if per * (num_segments - 1) >= len(MONTHS):
        raise ValueError(f"{num_segments} segments of {per} months leave "
                         f"some without a month of the {len(MONTHS)}")
    return MONTHS[i * per:(i + 1) * per]


def segment_codes(i: int, num_segments: int, n: int,
                  seed: int) -> Dict[str, np.ndarray]:
    """Segment ``i``'s rows: integers as values, strings as codes into
    ``STRING_DOMAINS``. Seeded per segment, so builders run in parallel."""
    rng = np.random.default_rng(seed * 1_000_003 + i)
    quantity = rng.integers(1, 51, n).astype(np.int64)
    discount = rng.integers(0, 11, n).astype(np.int64)
    price = rng.integers(905, 111_000, n)
    extended = (quantity * price).astype(np.int64)
    revenue = (extended * (100 - discount) // 100).astype(np.int64)
    supplycost = rng.integers(540, 66_600, n).astype(np.int64)
    week = rng.integers(1, 54, n).astype(np.int64)
    out = {"lo_quantity": quantity.astype(np.int8),
           "lo_discount": discount.astype(np.int8),
           "lo_extendedprice": extended.astype(np.int32),
           "lo_revenue": revenue.astype(np.int32),
           "lo_supplycost": supplycost.astype(np.int32),
           "d_weeknuminyear": week.astype(np.int8)}
    for side in "cs":
        region = rng.integers(0, 5, n)
        nation = region * 5 + rng.integers(0, 5, n)
        city = nation * 10 + rng.integers(0, 10, n)
        out[f"{side}_region"] = region.astype(np.int8)
        out[f"{side}_nation"] = nation.astype(np.int8)
        out[f"{side}_city"] = city.astype(np.int16)
    mfgr = rng.integers(1, 6, n) - 1
    category = mfgr * 5 + rng.integers(1, 6, n) - 1
    brand = category * 40 + rng.integers(1, 41, n) - 1
    out["p_mfgr"] = mfgr.astype(np.int8)
    out["p_category"] = category.astype(np.int8)
    out["p_brand1"] = brand.astype(np.int16)
    months = np.asarray(segment_months(i, num_segments))
    ym = months[rng.integers(0, len(months), n)]
    out["d_yearmonthnum"] = ym.astype(np.int32)
    out["d_year"] = (ym // 100).astype(np.int16)
    return out


def decode(codes: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Codes -> the columnar frame a segment builder takes (int64 and
    text)."""
    return {name: (np.asarray(STRING_DOMAINS[name])[col]
                   if name in STRING_DOMAINS else col.astype(np.int64))
            for name, col in codes.items()}


def table_codes(num_segments: int, rows: int,
                seed: int) -> Dict[str, np.ndarray]:
    """The whole table as codes, segment after segment (the oracle's
    input: about 40 B/row)."""
    parts = [segment_codes(i, num_segments, n, seed)
             for i, n in enumerate(segment_sizes(num_segments, rows))]
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
