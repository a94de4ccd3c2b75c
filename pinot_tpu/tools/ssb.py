"""Star Schema Benchmark (SSB) — generator + query suite on a flat table.

The reference benchmarks Pinot with TPC-H/SSB-derived data through
``contrib/pinot-druid-benchmark`` (README.md:1-60: dbgen-generated lineitem,
response-time + throughput runners). SSB's own dbgen emits a ``lineorder``
fact table joined to date/customer/supplier/part dimensions; OLAP stores
(and the Pinot/Druid comparisons) run it **denormalized** — one flat table
with the dimension attributes the 13 queries touch. This module generates
that flat table directly with dbgen-faithful value distributions
(uniform quantity 1..50, discount 0..10, ~25 nations in 5 regions, 1000
brands in 25 categories under 5 mfgrs, 7 order years 1992-1998) scaled by
``sf`` (SF 1 = 6,000,000 lineorder rows).

Queries Q1.1-Q4.3 are the standard SSB flights rewritten against the flat
schema (d_* / c_* / s_* / p_* columns live on the fact row).
"""

from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema

ROWS_PER_SF = 6_000_000

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# 5 nations per region (dbgen has 25 total); names chosen to match the
# query constants (UNITED STATES in AMERICA, UNITED KINGDOM in EUROPE)
NATIONS = {
    "AFRICA": ["ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE"],
    "AMERICA": ["ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES"],
    "ASIA": ["CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM"],
    "EUROPE": ["FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM"],
    "MIDDLE EAST": ["EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA"],
}


def ssb_schema() -> Schema:
    D, M = FieldType.DIMENSION, FieldType.METRIC
    I, S = DataType.INT, DataType.STRING
    return Schema("ssb_lineorder", [
        FieldSpec("lo_quantity", I, D),
        FieldSpec("lo_discount", I, D),
        FieldSpec("lo_extendedprice", I, M),
        FieldSpec("lo_revenue", I, M),
        FieldSpec("lo_supplycost", I, M),
        FieldSpec("d_year", I, D),
        FieldSpec("d_yearmonthnum", I, D),
        FieldSpec("d_weeknuminyear", I, D),
        FieldSpec("c_region", S, D),
        FieldSpec("c_nation", S, D),
        FieldSpec("c_city", S, D),
        FieldSpec("s_region", S, D),
        FieldSpec("s_nation", S, D),
        FieldSpec("s_city", S, D),
        FieldSpec("p_mfgr", S, D),
        FieldSpec("p_category", S, D),
        FieldSpec("p_brand1", S, D),
    ])


def _geo(rng: np.random.Generator, n: int):
    """(region, nation, city) columns with dbgen's nested structure:
    10 cities per nation, named '<nation[:9]>N' like dbgen ('UNITED KI1')."""
    region_idx = rng.integers(0, len(REGIONS), n)
    nation_pick = rng.integers(0, 5, n)
    city_pick = rng.integers(0, 10, n)
    regions = np.array(REGIONS)[region_idx]
    nation_table = np.array([NATIONS[r] for r in REGIONS])  # [5, 5]
    nations = nation_table[region_idx, nation_pick]
    city_table = np.array(
        [[f"{nat[:9]:<9}{c}" for c in range(10)]
         for r in REGIONS for nat in NATIONS[r]])           # [25, 10]
    nation_flat_idx = region_idx * 5 + nation_pick
    cities = city_table[nation_flat_idx, city_pick]
    return regions, nations, cities


def _flat_columns(rng: np.random.Generator, n: int) -> Dict[str, np.ndarray]:
    """Every flat column except d_year/d_yearmonthnum (callers draw those:
    globally uniform, or restricted to a segment's time window)."""
    quantity = rng.integers(1, 51, n).astype(np.int64)
    discount = rng.integers(0, 11, n).astype(np.int64)
    # dbgen: extendedprice = quantity * part price (905..~111k cents)
    price = rng.integers(905, 111_000, n)
    extended = (quantity * price).astype(np.int64)
    revenue = (extended * (100 - discount) // 100).astype(np.int64)
    supplycost = rng.integers(540, 66_600, n).astype(np.int64)
    week = rng.integers(1, 54, n).astype(np.int64)

    c_region, c_nation, c_city = _geo(rng, n)
    s_region, s_nation, s_city = _geo(rng, n)

    mfgr_i = rng.integers(1, 6, n)
    cat_i = rng.integers(1, 6, n)
    brand_i = rng.integers(1, 41, n)
    p_mfgr = np.array([f"MFGR#{i}" for i in range(1, 6)])[mfgr_i - 1]
    p_category = np.array(
        [f"MFGR#{m}{c}" for m in range(1, 6) for c in range(1, 6)]
    )[(mfgr_i - 1) * 5 + (cat_i - 1)]
    p_brand1 = np.array(
        [f"MFGR#{m}{c}{b:02d}" for m in range(1, 6) for c in range(1, 6)
         for b in range(1, 41)]
    )[((mfgr_i - 1) * 5 + (cat_i - 1)) * 40 + (brand_i - 1)]

    return {
        "lo_quantity": quantity, "lo_discount": discount,
        "lo_extendedprice": extended, "lo_revenue": revenue,
        "lo_supplycost": supplycost,
        "d_weeknuminyear": week,
        "c_region": c_region, "c_nation": c_nation, "c_city": c_city,
        "s_region": s_region, "s_nation": s_nation, "s_city": s_city,
        "p_mfgr": p_mfgr, "p_category": p_category, "p_brand1": p_brand1,
    }


def generate_flat(sf: float, seed: int = 42,
                  rows: int = 0) -> Dict[str, np.ndarray]:
    """Flattened lineorder columns, ``rows or int(sf * ROWS_PER_SF)`` rows."""
    n = rows or int(sf * ROWS_PER_SF)
    rng = np.random.default_rng(seed)
    cols = _flat_columns(rng, n)
    year = rng.integers(1992, 1999, n).astype(np.int64)
    month = rng.integers(1, 13, n).astype(np.int64)
    cols["d_year"] = year
    cols["d_yearmonthnum"] = year * 100 + month
    return cols


_ALL_MONTHS = [y * 100 + m for y in range(1992, 1999) for m in range(1, 13)]


def _segment_months(i: int, num_segments: int) -> List[int]:
    """Contiguous d_yearmonthnum window for segment ``i`` (84 months split
    across segments — real Pinot segments are time-bounded, and the window
    keeps the Q1.x time filters exercising the server min/max pruner)."""
    per = -(-len(_ALL_MONTHS) // num_segments)
    return _ALL_MONTHS[i * per:(i + 1) * per] or [_ALL_MONTHS[-1]]


def generate_segment_frame(i: int, num_segments: int, n: int,
                           seed: int = 42) -> Dict[str, np.ndarray]:
    """Segment ``i``'s flat rows: dbgen-faithful value distributions with
    d_yearmonthnum drawn from the segment's contiguous month window.
    Segments are INDEPENDENTLY generatable (seeded per segment), which is
    what makes the parallel builder embarrassingly parallel — no global
    sort, no cross-process data movement (ref: per-segment independence of
    SegmentIndexCreationDriverImpl.java:81)."""
    rng = np.random.default_rng(seed * 1_000_003 + i)
    cols = _flat_columns(rng, n)
    months = np.asarray(_segment_months(i, num_segments))
    ym = months[rng.integers(0, len(months), n)]
    cols["d_yearmonthnum"] = ym.astype(np.int64)
    cols["d_year"] = (ym // 100).astype(np.int64)
    return cols


def segment_sizes(num_segments: int, rows: int) -> List[int]:
    """Rows per segment: ``ceil(rows / num_segments)`` each until the rows
    run out (trailing empty segments are dropped)."""
    per = -(-rows // num_segments)
    sizes = []
    left = rows
    while left > 0 and len(sizes) < num_segments:
        sizes.append(min(per, left))
        left -= sizes[-1]
    return sizes


def generate_segment_frames(num_segments: int, rows: int, seed: int = 42):
    """Segment by segment, EXACTLY the rows ``build_segments(num_segments,
    rows, seed)`` indexes — for an oracle that cannot hold the whole table
    as raw strings."""
    for i, n in enumerate(segment_sizes(num_segments, rows)):
        yield generate_segment_frame(i, num_segments, n, seed)


def generate_table(num_segments: int, rows: int,
                   seed: int = 42) -> Dict[str, np.ndarray]:
    """``generate_segment_frames`` concatenated (for the pandas oracle /
    external baseline side of parity checks)."""
    frames = list(generate_segment_frames(num_segments, rows, seed))
    return {k: np.concatenate([f[k] for f in frames]) for k in frames[0]}


PARTITION_COLUMN = "d_year"
NUM_YEARS = 7  # dbgen's 1992..1998


def generate_partitioned_frame(i: int, num_segments: int, n: int,
                               seed: int = 42) -> Dict[str, np.ndarray]:
    """Segment ``i``'s rows holding EXACTLY ONE ``d_year`` value
    (1992 + i mod 7) — the partition-aligned segment layout the broker's
    partition pruner feeds on: a ``d_year`` eq/range predicate then skips
    every server holding no matching segment (ref: Kafka-partitioned
    streams landing one partition per LLC segment)."""
    rng = np.random.default_rng(seed * 2_000_003 + i)
    cols = _flat_columns(rng, n)
    year = 1992 + (i % NUM_YEARS)
    cols["d_year"] = np.full(n, year, dtype=np.int64)
    cols["d_yearmonthnum"] = (year * 100
                              + rng.integers(1, 13, n)).astype(np.int64)
    return cols


def ssb_indexing_config(star_tree: bool = True, num_partitions: int = 0,
                        partition_column: str = PARTITION_COLUMN):
    """Default lineorder indexing: the MULTI-TREE star-tree set that puts
    every SSB flight on a sub-scan rung (ref: StarTreeIndexConfig
    multi-tree resolution; plan-time selection picks the cheapest fitting
    tree per query):

    - tree 0 — the PR-6 primary (Q2.x): category/brand drill-down under
      the region filters, revenue/supplycost pre-aggs, plus the Q1.x
      derived pair so the pair namespace is exercised on the primary too.
    - tree 1 — Q1.x: the ``sum(lo_extendedprice * lo_discount)`` derived
      pair (expression pre-aggregation) over the time/discount/quantity
      filter dims the flight predicates touch.
    - tree 2 — Q3.x: the geo drill-down (region -> nation -> city, both
      sides) with d_yearmonthnum for the Q3.4 month filter.
    - tree 3 — Q4.1/Q4.2: profit (``sum(lo_revenue - lo_supplycost)``
      derived pair) by customer nation / supplier nation × category.
    - tree 4 — Q4.3: profit by supplier city × brand under the
      s_nation/category filters (splitting Q4 across two trees keeps
      record counts bounded: nation×city×brand in ONE split order would
      dedup nothing at SSB scale).

    Keeping one tree per flight family bounds each tree's record count by
    its own dim-tuple space — the cost model the cheapest-tree selection
    scores against. ``num_partitions`` > 0 adds a Modulo
    segment-partition config on ``partition_column`` so the builder
    records per-segment partition metadata (the broker pruner's input);
    ``star_tree=False`` drops the trees (mesh-parity tests want every
    query on the sharded combine)."""
    from pinot_tpu.spi.table import (
        IndexingConfig,
        SegmentPartitionConfig,
        StarTreeIndexConfig,
    )

    trees = [
        StarTreeIndexConfig(
            dimensions_split_order=["d_year", "c_region", "s_region",
                                    "p_category", "p_brand1"],
            function_column_pairs=["SUM__lo_revenue", "SUM__lo_supplycost",
                                   "SUM__lo_extendedprice*lo_discount",
                                   "COUNT__*"],
            max_leaf_records=10_000),
        StarTreeIndexConfig(
            dimensions_split_order=["d_year", "d_yearmonthnum",
                                    "d_weeknuminyear", "lo_discount",
                                    "lo_quantity"],
            function_column_pairs=["SUM__lo_extendedprice*lo_discount",
                                   "SUM__lo_revenue", "COUNT__*"],
            max_leaf_records=10_000),
        StarTreeIndexConfig(
            dimensions_split_order=["d_year", "d_yearmonthnum", "c_region",
                                    "s_region", "c_nation", "s_nation",
                                    "c_city", "s_city"],
            function_column_pairs=["SUM__lo_revenue", "COUNT__*"],
            max_leaf_records=10_000),
        StarTreeIndexConfig(
            dimensions_split_order=["d_year", "c_region", "s_region",
                                    "p_mfgr", "c_nation", "s_nation",
                                    "p_category"],
            function_column_pairs=["SUM__lo_revenue-lo_supplycost",
                                   "COUNT__*"],
            max_leaf_records=10_000),
        StarTreeIndexConfig(
            dimensions_split_order=["d_year", "s_nation", "p_category",
                                    "s_city", "p_brand1"],
            function_column_pairs=["SUM__lo_revenue-lo_supplycost",
                                   "COUNT__*"],
            max_leaf_records=10_000),
    ] if star_tree else []
    spc = SegmentPartitionConfig(column_partition_map={
        partition_column: {"functionName": "Modulo",
                           "numPartitions": num_partitions},
    }) if num_partitions > 0 else None
    return IndexingConfig(star_tree_index_configs=trees,
                          segment_partition_config=spc)


def _build_one(i: int, num_segments: int, n: int, seed: int,
               out_dir: str, partitioned: bool = False,
               star_tree: bool = True) -> str:
    """Worker: generate + build one segment (process-pool entry point)."""
    from pinot_tpu.segment import SegmentBuilder

    if partitioned:
        frame = generate_partitioned_frame(i, num_segments, n, seed)
        name = f"ssb_part_{i}"
        cfg = ssb_indexing_config(star_tree=star_tree,
                                  num_partitions=num_segments)
    else:
        frame = generate_segment_frame(i, num_segments, n, seed)
        name = f"ssb_{i}"
        cfg = ssb_indexing_config(star_tree=star_tree)
    SegmentBuilder(ssb_schema(), name, indexing_config=cfg).build(frame,
                                                                  out_dir)
    return name


def build_segments(sf: float, out_dir: str, num_segments: int = 8,
                   seed: int = 42, rows: int = 0,
                   workers: int = 0, partitioned: bool = False,
                   star_tree: bool = True) -> List:
    """Build + load ``num_segments`` SSB segments. ``workers`` > 1 builds
    segments in a spawn process pool (per-column creators are independent in
    the reference too — SegmentIndexCreationDriverImpl.java:81); 0 picks
    min(num_segments, cpu_count). ``partitioned`` builds the
    one-``d_year``-per-segment layout with Modulo partition metadata
    (broker partition pruning); ``star_tree=False`` skips tree build."""
    from pinot_tpu.segment import load_segment

    n = rows or int(sf * ROWS_PER_SF)
    jobs = [(i, num_segments, take, seed, out_dir, partitioned, star_tree)
            for i, take in enumerate(segment_sizes(num_segments, n))]

    if not workers:
        workers = min(len(jobs), os.cpu_count() or 1)
    if workers > 1 and len(jobs) > 1:
        import multiprocessing as mp

        # SPAWN, not fork: the bench worker calls this with a live JAX
        # runtime whose threads/locks a forked child would inherit
        # mid-flight; the builder itself is numpy-only either way
        with mp.get_context("spawn").Pool(workers) as pool:
            names = pool.starmap(_build_one, jobs)
    else:
        names = [_build_one(*j) for j in jobs]
    return [load_segment(os.path.join(out_dir, nm)) for nm in names]


# The 13 SSB flights on the flat schema (constants follow the spec;
# selectivities match dbgen's).
QUERIES: Dict[str, str] = {
    "Q1.1": "SELECT sum(lo_extendedprice * lo_discount) FROM ssb_lineorder "
            "WHERE d_year = 1993 AND lo_discount BETWEEN 1 AND 3 "
            "AND lo_quantity < 25",
    "Q1.2": "SELECT sum(lo_extendedprice * lo_discount) FROM ssb_lineorder "
            "WHERE d_yearmonthnum = 199401 AND lo_discount BETWEEN 4 AND 6 "
            "AND lo_quantity BETWEEN 26 AND 35",
    "Q1.3": "SELECT sum(lo_extendedprice * lo_discount) FROM ssb_lineorder "
            "WHERE d_weeknuminyear = 6 AND d_year = 1994 "
            "AND lo_discount BETWEEN 5 AND 7 "
            "AND lo_quantity BETWEEN 26 AND 35",
    "Q2.1": "SELECT d_year, p_brand1, sum(lo_revenue) FROM ssb_lineorder "
            "WHERE p_category = 'MFGR#12' AND s_region = 'AMERICA' "
            "GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1",
    "Q2.2": "SELECT d_year, p_brand1, sum(lo_revenue) FROM ssb_lineorder "
            "WHERE p_brand1 BETWEEN 'MFGR#2221' AND 'MFGR#2228' "
            "AND s_region = 'ASIA' "
            "GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1",
    "Q2.3": "SELECT d_year, p_brand1, sum(lo_revenue) FROM ssb_lineorder "
            "WHERE p_brand1 = 'MFGR#2239' AND s_region = 'EUROPE' "
            "GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1",
    "Q3.1": "SELECT c_nation, s_nation, d_year, sum(lo_revenue) "
            "FROM ssb_lineorder "
            "WHERE c_region = 'ASIA' AND s_region = 'ASIA' "
            "AND d_year BETWEEN 1992 AND 1997 "
            "GROUP BY c_nation, s_nation, d_year "
            "ORDER BY d_year ASC, sum(lo_revenue) DESC",
    "Q3.2": "SELECT c_city, s_city, d_year, sum(lo_revenue) "
            "FROM ssb_lineorder "
            "WHERE c_nation = 'UNITED STATES' AND s_nation = 'UNITED STATES' "
            "AND d_year BETWEEN 1992 AND 1997 "
            "GROUP BY c_city, s_city, d_year "
            "ORDER BY d_year ASC, sum(lo_revenue) DESC",
    "Q3.3": "SELECT c_city, s_city, d_year, sum(lo_revenue) "
            "FROM ssb_lineorder "
            "WHERE c_city IN ('UNITED KI1', 'UNITED KI5') "
            "AND s_city IN ('UNITED KI1', 'UNITED KI5') "
            "AND d_year BETWEEN 1992 AND 1997 "
            "GROUP BY c_city, s_city, d_year "
            "ORDER BY d_year ASC, sum(lo_revenue) DESC",
    "Q3.4": "SELECT c_city, s_city, d_year, sum(lo_revenue) "
            "FROM ssb_lineorder "
            "WHERE c_city IN ('UNITED KI1', 'UNITED KI5') "
            "AND s_city IN ('UNITED KI1', 'UNITED KI5') "
            "AND d_yearmonthnum = 199712 "
            "GROUP BY c_city, s_city, d_year "
            "ORDER BY d_year ASC, sum(lo_revenue) DESC",
    "Q4.1": "SELECT d_year, c_nation, sum(lo_revenue - lo_supplycost) "
            "FROM ssb_lineorder "
            "WHERE c_region = 'AMERICA' AND s_region = 'AMERICA' "
            "AND p_mfgr IN ('MFGR#1', 'MFGR#2') "
            "GROUP BY d_year, c_nation ORDER BY d_year, c_nation",
    "Q4.2": "SELECT d_year, s_nation, p_category, "
            "sum(lo_revenue - lo_supplycost) FROM ssb_lineorder "
            "WHERE c_region = 'AMERICA' AND s_region = 'AMERICA' "
            "AND p_mfgr IN ('MFGR#1', 'MFGR#2') "
            "AND d_year IN (1997, 1998) "
            "GROUP BY d_year, s_nation, p_category "
            "ORDER BY d_year, s_nation, p_category",
    "Q4.3": "SELECT d_year, s_city, p_brand1, "
            "sum(lo_revenue - lo_supplycost) FROM ssb_lineorder "
            "WHERE s_nation = 'UNITED STATES' AND d_year IN (1997, 1998) "
            "AND p_category = 'MFGR#14' "
            "GROUP BY d_year, s_city, p_brand1 "
            "ORDER BY d_year, s_city, p_brand1",
}


def pandas_answer(cols: Dict[str, np.ndarray], qid: str):
    """Oracle for parity tests (pandas over the generated columns)."""
    import pandas as pd

    df = pd.DataFrame(cols)
    if qid == "Q1.1":
        m = ((df.d_year == 1993) & df.lo_discount.between(1, 3)
             & (df.lo_quantity < 25))
        return int((df.lo_extendedprice[m] * df.lo_discount[m]).sum())
    if qid == "Q1.2":
        m = ((df.d_yearmonthnum == 199401) & df.lo_discount.between(4, 6)
             & df.lo_quantity.between(26, 35))
        return int((df.lo_extendedprice[m] * df.lo_discount[m]).sum())
    if qid == "Q1.3":
        m = ((df.d_weeknuminyear == 6) & (df.d_year == 1994)
             & df.lo_discount.between(5, 7) & df.lo_quantity.between(26, 35))
        return int((df.lo_extendedprice[m] * df.lo_discount[m]).sum())
    raise ValueError(f"no pandas oracle for {qid}")
