"""``lib/work.py``: the least bytes of the 13 spec queries against a hand
count from the schema's cardinalities; pruned segments are not counted."""

import pytest

from benchmarks.lib import schedule, work
from benchmarks.tables import ssb_flat

ROWS, SEGMENTS = 24_000_000, 8

# bits: quantity 6 (50), discount 4 (11), extendedprice 23 (5,549,950),
# revenue 23, supplycost 17 (66,060), year 3 (7), yearmonth 7 (84),
# week 6 (53), region 3 (5), nation 5 (25), city 8 (250), mfgr 3,
# category 5, brand 10 (1000)
# 84 months in 8 segments of 11 (the last has 7): 1992 lies in segments
# 0-1, 1993 in 1-2, 1994 in 2-3, 1997 in 5-6, 1998 in 6-7; 199401 in 2,
# 199712 in 6. Rows a segment: 3,000,000.
HAND = {   # flight -> (segments scanned, bits a row)
    "Q1.1": (2, 3 + 4 + 6 + 23),
    "Q1.2": (1, 7 + 4 + 6 + 23),
    "Q1.3": (2, 6 + 3 + 4 + 6 + 23),
    "Q2.1": (8, 5 + 3 + 23 + 3 + 10),
    "Q2.2": (8, 10 + 3 + 23 + 3),
    "Q2.3": (8, 10 + 3 + 23 + 3),
    "Q3.1": (7, 3 + 3 + 3 + 23 + 5 + 5),
    "Q3.2": (7, 5 + 5 + 3 + 23 + 8 + 8),
    "Q3.3": (7, 8 + 8 + 3 + 23),
    "Q3.4": (1, 8 + 8 + 7 + 23 + 3),
    "Q4.1": (8, 3 + 3 + 3 + 23 + 17 + 3 + 5),
    "Q4.2": (3, 3 + 3 + 3 + 3 + 23 + 17 + 5 + 5),
    "Q4.3": (3, 5 + 3 + 5 + 23 + 17 + 8 + 10),
}


@pytest.fixture(scope="module")
def spec():
    return {q["flight"]: q for q in schedule.spec_queries(
        schedule.load_traffic("flights_c2"))}


@pytest.mark.parametrize("flight", sorted(HAND))
def test_least_bytes_equal_the_hand_count(spec, flight):
    segments, bits = HAND[flight]
    assert (work.scan_least_bytes(ssb_flat, spec[flight], SEGMENTS, ROWS)
            == segments * 3_000_000 * bits / 8.0)


def test_pruned_segments_are_not_counted(spec):
    q = spec["Q1.2"]                      # d_yearmonthnum = 199401
    assert work.segments_kept(ssb_flat, q, SEGMENTS) == [2]
    assert work.segments_kept(ssb_flat, spec["Q2.1"], SEGMENTS) == list(
        range(8))
    assert work.segments_kept(ssb_flat, spec["Q3.1"], SEGMENTS) == list(
        range(7))                         # 1992..1997 leaves out 1998's last


def test_quarterly_segments_prune_by_quarter(spec):
    """Another count of segments (72M rows in 28 were probed on the chip):
    three months each, so Q1.2's month lies in one and Q1.1's year in
    four, and the rows split 27 to 1."""
    assert work.segments_kept(ssb_flat, spec["Q1.2"], 28) == [8]
    assert work.segments_kept(ssb_flat, spec["Q1.1"], 28) == [4, 5, 6, 7]
    sizes = ssb_flat.segment_sizes(28, 72_000_000)
    assert sizes == [2_571_429] * 27 + [2_571_417]
    assert (work.scan_least_bytes(ssb_flat, spec["Q1.1"], 28, 72_000_000)
            == 4 * 2_571_429 * (3 + 4 + 6 + 23) / 8.0)


def test_segments_without_a_month_are_refused():
    assert len(ssb_flat.segment_months(27, 28)) == 3
    with pytest.raises(ValueError, match="without a month"):
        ssb_flat.segment_months(0, 24)       # 21 of 4 months take all 84


def test_least_seconds_is_bytes_over_bandwidth(spec):
    peak = {"hbm_bytes_per_s": 819e9}
    b = work.scan_least_bytes(ssb_flat, spec["Q2.1"], SEGMENTS, ROWS)
    assert work.scan_least_seconds(ssb_flat, spec["Q2.1"], SEGMENTS, ROWS,
                                   peak) == b / 819e9
