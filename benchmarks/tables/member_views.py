"""``member_views`` rows from a seed: who viewed whose profile, when.

The table of Pinot's "Who viewed my profile" use case (Im et al.,
SIGMOD'18): one row a profile view, keyed by the viewee (``member_id``),
partitioned on that key (``Modulo``, ``PARTITIONS``) and physically sorted
on it inside every segment, with the viewer's attributes as dimensions. The
paper gives no column list and no cardinalities; every one here is assumed
(``configs/userfacing.json`` says so).

Layout. Segment ``i`` holds partition ``i % PARTITIONS`` and time slice
``i // PARTITIONS`` of the ``DAYS``-day span (96 segments: 48 partitions x
two halves of 45 days; 48 or fewer: one slice, all 90 days). A partition's
members are the hottest of its ids: a fixed permutation of all ``MEMBERS``
ids is the popularity rank (``rank_order``), a table of any size holds the
lowest-ranked members of each partition it has, and ``member_domain`` draws
the traffic's keys by Zipf over the same ranks, so a toy table still holds
most of the members a query names. A member's rows in a segment are a
seeded heavy-tailed share of the segment (log-normal weights, mean ~50 a
time slice at full size), capped so that no member holds over
``MEMBER_SHARE_CAP`` of a segment.

The contract of ``tables/ssb_flat.py``: ``segment_sizes``, ``segment_codes``
seeded a segment, ``decode``, ``table_codes``, ``STRING_DOMAINS``,
``CARDINALITY``; codes first, strings only in the builders. numpy only.
Nothing of the program is imported here. For a hybrid table besides
(``lib/hybrid.py``): ``stream_codes``, a stream partition's rows by offset,
and ``STREAM_BLOCK``.
"""

from __future__ import annotations

import functools

from typing import Dict, List, Tuple

import numpy as np

MEMBERS = 960_000
PARTITIONS = 48                 # member_id % 48: upstream's Modulo
MEMBERS_A_PARTITION = MEMBERS // PARTITIONS
DAYS = 90
DAY0 = 19_631                   # 2023-10-01 in days since the epoch
ROWS_A_MEMBER = 100             # mean over the whole span, at any size
MEMBER_SHARE_CAP = 0.05         # the index rung's SELECTIVITY_THRESHOLD
RANK_SEED = 35_001              # the popularity order, no seed moves it
DOMAIN_SEED = 35_002            # the traffic's draws over it
DOMAIN_SIZE = 65_536
ZIPF_EXPONENT = 1.0
WINDOW_SPANS = (6, 29, 89)      # b - a of ``day BETWEEN a AND b``
WINDOWS_A_SPAN = 128
# the stream of a hybrid table: the day before the time boundary (which
# the broker hides: the offline half serves it), the offline half's last
# day (the realtime half serves it) and the day after it, today
STREAM_DAYS = (DAY0 + DAYS - 2, DAY0 + DAYS)
STREAM_SEED = 35_003
STREAM_BLOCK = 4096

INDUSTRIES = [f"industry_{k:03d}" for k in range(148)]
REGIONS = [f"region_{k:02d}" for k in range(64)]
SENIORITIES = ["cxo", "director", "entry", "manager", "owner", "partner",
               "senior", "training", "unpaid", "vp"]
COMPANY_SIZES = ["1", "10001+", "1001-5000", "11-50", "2-10", "201-500",
                 "5001-10000", "501-1000", "51-200"]
SOURCES = ["external", "feed", "messaging", "profile_browse", "search"]

STRING_DOMAINS: Dict[str, List[str]] = {
    "viewer_industry": INDUSTRIES, "viewer_region": REGIONS,
    "viewer_seniority": SENIORITIES, "viewer_company_size": COMPANY_SIZES,
    "source": SOURCES,
}
DWELL_MS = (1_000, 86_400_000)      # a second to a day, log-uniform
CARDINALITY: Dict[str, int] = {
    "member_id": MEMBERS, "day": DAYS,
    "viewer_industry": 148, "viewer_region": 64, "viewer_seniority": 10,
    "viewer_company_size": 9, "source": 5,
    "views": 20, "dwell_ms": DWELL_MS[1] - DWELL_MS[0] + 1,
}


def segment_sizes(num_segments: int, rows: int) -> List[int]:
    per = -(-rows // num_segments)
    sizes, left = [], rows
    while left > 0 and len(sizes) < num_segments:
        sizes.append(min(per, left))
        left -= sizes[-1]
    return sizes


def partition_of(member_id: int) -> int:
    """Upstream's ``ModuloPartitionFunction``; the program is not asked."""
    return int(member_id) % PARTITIONS


def time_slices(num_segments: int) -> int:
    return -(-num_segments // PARTITIONS)


def segment_days(i: int, num_segments: int) -> range:
    """The days segment ``i`` holds: its slice of the span, in order."""
    slices = time_slices(num_segments)
    per = -(-DAYS // slices)
    s = i // PARTITIONS
    return range(DAY0 + s * per, DAY0 + min(DAYS, (s + 1) * per))


@functools.lru_cache(maxsize=1)
def rank_order() -> np.ndarray:
    """Member ids from the hottest down: ``rank_order()[r]`` is the member
    of popularity rank ``r``."""
    return np.random.default_rng(RANK_SEED).permutation(MEMBERS)


def partition_members(p: int, count: int) -> np.ndarray:
    """The ``count`` hottest members of partition ``p``, ascending."""
    if not 0 < count <= MEMBERS_A_PARTITION:
        raise ValueError(f"a partition has {MEMBERS_A_PARTITION} members, "
                         f"not {count}")
    order = rank_order()
    return np.sort(order[order % PARTITIONS == p][:count])


def members_of(n: int, num_segments: int) -> int:
    """Members a partition holds where its segments have ``n`` rows."""
    return max(1, min(MEMBERS_A_PARTITION,
                      n * time_slices(num_segments) // ROWS_A_MEMBER))


def _skewed(rng, size: int, n: int) -> np.ndarray:
    """Codes 0..size-1, the low ones more often (weights 1 / (k + 4))."""
    w = 1.0 / (np.arange(size) + 4.0)
    return rng.choice(size, n, p=w / w.sum())


def segment_codes(i: int, num_segments: int, n: int,
                  seed: int) -> Dict[str, np.ndarray]:
    """Segment ``i``'s rows, ascending on ``member_id``: integers as
    values, strings as codes into ``STRING_DOMAINS``. Seeded per segment,
    so builders run in parallel."""
    rng = np.random.default_rng(seed * 1_000_003 + i)
    members = partition_members(i % PARTITIONS, members_of(n, num_segments))
    weight = rng.lognormal(0.0, 1.5, len(members))
    if len(members) * MEMBER_SHARE_CAP >= 2.0:
        for _ in range(16):         # no member over the cap, with room
            weight = np.minimum(weight, 0.6 * MEMBER_SHARE_CAP * weight.sum())
    counts = rng.multinomial(n, weight / weight.sum())
    days = segment_days(i, num_segments)
    return {
        "member_id": np.repeat(members, counts).astype(np.int32),
        "day": rng.integers(days.start, days.stop, n).astype(np.int16),
        **_view_columns(rng, n),
    }


def _view_columns(rng, n: int) -> Dict[str, np.ndarray]:
    """A view's other columns: the viewer's attributes and the measures."""
    return {
        "viewer_industry": _skewed(rng, 148, n).astype(np.int16),
        "viewer_region": _skewed(rng, 64, n).astype(np.int8),
        "viewer_seniority": rng.integers(0, 10, n).astype(np.int8),
        "viewer_company_size": rng.integers(0, 9, n).astype(np.int8),
        "source": _skewed(rng, 5, n).astype(np.int8),
        "views": rng.integers(1, 21, n).astype(np.int8),
        "dwell_ms": np.exp(rng.uniform(np.log(DWELL_MS[0]),
                                       np.log(DWELL_MS[1] + 1), n)
                           ).astype(np.int64).clip(*DWELL_MS
                                                   ).astype(np.int32),
    }


@functools.lru_cache(maxsize=64)
def _stream_members(partition: int, partitions: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Stream partition ``partition``'s members in popularity order, and
    the cumulative Zipf weight of each (exponent ``ZIPF_EXPONENT`` over
    the global rank, as ``member_domain`` draws the traffic's keys),
    capped as a segment's are: no member over ``MEMBER_SHARE_CAP`` of the
    rows a seal makes a segment of."""
    order = rank_order()
    ranks = np.flatnonzero(order % partitions == partition)
    weight = 1.0 / (ranks + 1.0) ** ZIPF_EXPONENT
    for _ in range(16):
        weight = np.minimum(weight, 0.6 * MEMBER_SHARE_CAP * weight.sum())
    cum = np.cumsum(weight)
    return order[ranks], cum / cum[-1]


def stream_codes(partition: int, start: int, n: int, seed: int,
                 partitions: int) -> Dict[str, np.ndarray]:
    """Stream partition ``partition``'s rows at offsets ``[start, start +
    n)``, as ``segment_codes`` gives a segment's: the realtime half of a
    hybrid ``member_views``. A row's viewee is drawn by Zipf over the
    members whose key falls in the partition (``member_id % partitions``:
    upstream's ``Modulo`` keying of the producer), so the members the
    traffic asks for most are viewed most; its day is one of
    ``STREAM_DAYS``. Seeded a block of ``STREAM_BLOCK`` offsets, so the
    producer and the oracle regenerate the same rows however they cut
    the offsets."""
    if not 0 <= partition < partitions or start < 0 or n < 0:
        raise ValueError(f"no rows {start}+{n} in partition {partition} "
                         f"of {partitions}")
    members, cum = _stream_members(partition, partitions)
    first = start // STREAM_BLOCK
    last = max(first, (start + n - 1) // STREAM_BLOCK)
    parts = []
    for block in range(first, last + 1):
        rng = np.random.default_rng([seed, STREAM_SEED, partitions,
                                     partition, block])
        m = STREAM_BLOCK
        pick = np.minimum(np.searchsorted(cum, rng.random(m), "right"),
                          len(members) - 1)
        parts.append({
            "member_id": members[pick].astype(np.int32),
            "day": rng.integers(STREAM_DAYS[0], STREAM_DAYS[1] + 1,
                                m).astype(np.int16),
            **_view_columns(rng, m),
        })
    lo = start - first * STREAM_BLOCK
    return {k: np.concatenate([p[k] for p in parts])[lo:lo + n]
            for k in parts[0]}


def decode(codes: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Codes -> the columnar frame a segment builder takes (int64 and
    text)."""
    return {name: (np.asarray(STRING_DOMAINS[name])[col]
                   if name in STRING_DOMAINS else col.astype(np.int64))
            for name, col in codes.items()}


def table_codes(num_segments: int, rows: int,
                seed: int) -> Dict[str, np.ndarray]:
    """The whole table as codes, segment after segment (the oracle's
    input: 17 B/row)."""
    parts = [segment_codes(i, num_segments, n, seed)
             for i, n in enumerate(segment_sizes(num_segments, rows))]
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


# --------------------------------------------------------------------------
# the traffic's domains, committed as data in traffic/families_member_views
# --------------------------------------------------------------------------

def member_domain() -> List[int]:
    """``DOMAIN_SIZE`` draws of a member by Zipf (exponent
    ``ZIPF_EXPONENT``) over the popularity ranks of all ``MEMBERS``: the
    list ``lib/schedule._draw`` picks ``{u}`` from, in which hot keys
    recur."""
    rng = np.random.default_rng(DOMAIN_SEED)
    w = 1.0 / np.arange(1, MEMBERS + 1, dtype=np.float64) ** ZIPF_EXPONENT
    ranks = rng.choice(MEMBERS, DOMAIN_SIZE, p=w / w.sum())
    return rank_order()[ranks].tolist()


def window_domain() -> List[List[int]]:
    """``[a, b]`` day windows inside the span, ``WINDOWS_A_SPAN`` of each
    length of ``WINDOW_SPANS``, ``a`` uniform."""
    rng = np.random.default_rng(DOMAIN_SEED + 1)
    out = []
    for span in WINDOW_SPANS:
        for a in rng.integers(0, DAYS - span, WINDOWS_A_SPAN).tolist():
            out.append([DAY0 + a, DAY0 + a + span])
    return out


def region_domains() -> List[List[str]]:
    """The 64 regions in four quarters: one of each makes the four
    distinct values of ``viewer_region IN (...)``."""
    return [REGIONS[q * 16:(q + 1) * 16] for q in range(4)]
