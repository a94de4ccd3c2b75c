"""The ``ssb_scan_sf12`` deployment at toy size through the served path
(PR 37).

``benchmarks/tables/ssb_flat.py`` in 28 segments of three months, a few
thousand rows each, built by the program's segment builder under the table
config of ``benchmarks/configs/ssb_scan_sf12.json``, served by the embedded
cluster over REST, and every answer of ``flights_c2``'s 104 strings held to
``benchmarks/lib/oracle.py`` exactly. What the cell asks of the chip at 72M
rows: a string that keeps one segment takes the per-segment path, every
other the sharded combine over a batch of its own subset, and nothing is
streamed through the residency budget in slices.
"""

import json
import os
import urllib.request

import pytest

from benchmarks.lib import compare, oracle, schedule, serve, work
from benchmarks.tables import ssb_flat
from pinot_tpu.common.tracing import LEDGER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEGMENTS, SEED = 28, 2 ** 31 + 37
ROWS = SEGMENTS * 3_000


def cell_config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ssb_scan_sf12.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    config = dict(cell_config(), rows=ROWS)
    out = str(tmp_path_factory.mktemp("ssb_scan_sf12"))
    dirs = [os.path.join(out, serve._build_one(
        "ssb_flat", config["schema"], config["tableIndexConfig"], i,
        SEGMENTS, n, SEED, out))
        for i, n in enumerate(ssb_flat.segment_sizes(SEGMENTS, ROWS))]
    cycle = schedule.build_cycle(schedule.load_traffic("flights_c2"), SEED)
    want = oracle.answers(ssb_flat, ssb_flat.table_codes(SEGMENTS, ROWS,
                                                         SEED),
                          cycle, control=False)["want"]
    mark = LEDGER.snapshot()
    served = serve.Served(config, dirs, os.path.join(out, "work"))
    try:
        served.wait_staged()
        yield served, cycle, want, mark
    finally:
        served.close()


def ask_all(served, cycle):
    records = []
    for q in cycle:
        req = urllib.request.Request(
            served.urls["broker"] + "/query/sql",
            data=json.dumps({"sql": q["sql"] + " OPTION(trace=true)"}
                            ).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            records.append({"index": q["id"], "status": r.status,
                            "body": r.read().decode()})
    return records


def assert_exact(records, cycle, want):
    numbers = compare.compare(records, cycle, want)
    assert numbers["responses_failed"] == 0, numbers["first_failed"]
    assert numbers["responses_wrong"] == 0, numbers["first_wrong"]
    assert numbers["max_abs_diff"] == 0.0


def find(span, name):
    out = [span] if span.get("name") == name else []
    for c in span.get("children", ()):
        out += find(c, name)
    return out


def kept_names(q):
    return frozenset(f"seg_{i}" for i in
                     work.segments_kept(ssb_flat, q, SEGMENTS))


def test_every_string_is_exact_on_the_route_its_subset_takes(deployment):
    served, cycle, want, mark = deployment
    assert len({q["sql"] for q in cycle}) == len(cycle) == 104
    records = ask_all(served, cycle)
    assert_exact(records, cycle, want)
    assert served.ledger_breaches(
        cell_config()["forbidden_decision_reasons"], "cpu") == []

    sizes = set()
    for rec in records:
        q = cycle[rec["index"]]
        (root,) = json.loads(rec["body"])["traceInfo"]["spans"]
        (prune,) = find(root, "Prune")
        assert prune["segments"] == SEGMENTS
        assert prune["kept"] == len(kept_names(q))
        sizes.add(prune["kept"])
        (route,) = find(root, "Route")
        assert route["path"] == ("per_segment" if prune["kept"] == 1
                                 else "sharded"), q["sql"]
        assert not find(root, "Slice")
        assert not any(lease.get("sliced") for lease in find(root, "Lease"))
    # a quarter, a year, two years, six years, all seven
    assert sizes == {1, 4, 8, 24, 28}


def test_each_distinct_subset_is_a_batch_of_its_own(deployment):
    served, cycle, want, _ = deployment
    ask_all(served, cycle)
    subsets = {kept_names(q) for q in cycle if len(kept_names(q)) > 1}
    batches = served.server.executor._batches
    assert {frozenset(key) for key in batches} == subsets
    assert len(batches) == len(subsets)


def test_at_the_chips_share_of_the_budget_nothing_is_evicted_or_sliced(
        deployment):
    """On the chip this table holds ~75% of the residency budget. With the
    budget set so that what the cycle staged is 75% of it on the fullest
    device, a second pass over the cycle reads only what is resident:
    no miss, no eviction, no sliced or spilled query, and the answers
    exact."""
    served, cycle, want, _ = deployment
    residency = served.server.executor.residency
    ask_all(served, cycle)          # every batch staged
    mem = served.debug("server", "/debug/memory")
    fullest = max(d["stagedBytes"] for d in mem["devices"])
    residency.set_budget_bytes(int(fullest / 0.75))
    try:
        before = served.debug("server", "/debug/memory")
        assert_exact(ask_all(served, cycle), cycle, want)
        after = served.debug("server", "/debug/memory")
    finally:
        residency.set_budget_bytes(None)
    moved = {k: after["counters"][k] - before["counters"][k]
             for k in ("misses", "evictions", "slicedQueries", "spills",
                       "demotions")}
    assert moved == dict.fromkeys(moved, 0)
    assert after["counters"]["hits"] > before["counters"]["hits"]
    assert after["stagedSegments"].keys() == before["stagedSegments"].keys()
