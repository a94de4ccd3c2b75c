"""The ``userfacing`` deployment at toy size through the served path (PR 35).

``benchmarks/tables/member_views.py`` at 200,000 rows in 8 partitioned,
sorted segments, built by the program's segment builder under the table
config of ``benchmarks/configs/userfacing.json``, served by the embedded
cluster over REST, and every answer of a seeded cycle of the four families
held to ``benchmarks/lib/oracle.py`` exactly. Every string has to be served
by the index rung, and pruning has to leave at most two segments a query:
what the benchmark's cell asks of the chip at 96M rows.
"""

import json
import os
import urllib.request

import pytest

from benchmarks.lib import compare, oracle, schedule, serve
from benchmarks.tables import member_views as mv
from pinot_tpu.common.tracing import LEDGER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, SEGMENTS, SEED = 200_000, 8, 2 ** 31 + 35
SERVED = "index:scan->index_gather:index_served"


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    config = dict(cell_config(), rows=ROWS, segments=SEGMENTS)
    out = tmp_path_factory.mktemp("member_views")
    sizes = mv.segment_sizes(SEGMENTS, ROWS)
    dirs = [os.path.join(str(out), serve._build_one(
        "member_views", config["schema"], config["tableIndexConfig"], i,
        SEGMENTS, n, SEED, str(out))) for i, n in enumerate(sizes)]
    # the cell's four families over the members this toy table holds (the
    # committed domain names members of all 48 partitions)
    traffic = schedule.load_traffic("zipf_open_r80")
    held = set()
    for p in range(SEGMENTS):
        held.update(mv.partition_members(
            p, mv.members_of(sizes[p], SEGMENTS)).tolist())
    traffic["domains"]["members"] = [
        m for m in traffic["domains"]["members"] if m in held]
    traffic["variants_per_flight"] = 12
    cycle = schedule.build_cycle(traffic, SEED)
    want = oracle.answers(mv, mv.table_codes(SEGMENTS, ROWS, SEED), cycle,
                          control=False)
    mark = LEDGER.snapshot()
    served = serve.Served(config, dirs, str(out / "work"))
    try:
        yield served, cycle, want, mark
    finally:
        served.close()


def ask(served, sql):
    req = urllib.request.Request(
        served.urls["broker"] + "/query/sql",
        data=json.dumps({"sql": sql}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return r.status, r.read().decode()


def find(span, name):
    out = [span] if span.get("name") == name else []
    for c in span.get("children", ()):
        out += find(c, name)
    return out


def test_the_four_families_are_exact_index_served_and_pruned(deployment):
    served, cycle, want, mark = deployment
    assert len(cycle) == 48 and len({q["flight"] for q in cycle}) == 4
    assert len(set(q["sql"] for q in cycle)) == 48
    records = []
    for q in cycle:
        status, body = ask(served, q["sql"] + " OPTION(trace=true)")
        records.append({"index": q["id"], "status": status, "body": body})
    numbers = compare.compare(records, cycle, want["want"])
    assert numbers["responses_failed"] == 0, numbers["first_failed"]
    assert numbers["responses_wrong"] == 0, numbers["first_wrong"]
    assert numbers["max_abs_diff"] == 0.0
    # the mix holds strings with rows and strings without
    rows = [len(want["want"][str(q["id"])]) for q in cycle
            if q["group_by"]]
    assert any(rows) and not all(rows)

    # the device served every string, by the index rung; nothing the
    # deployment forbids is in the ledger
    assert served.ledger_breaches(
        cell_config()["forbidden_decision_reasons"], "cpu") == []
    delta = LEDGER.delta(mark)
    assert delta.get(SERVED, 0) >= len(cycle)
    assert not [k for k in delta if k.startswith("index:index_gather->")]

    for rec in records:
        (root,) = rec["raw"]["traceInfo"]["spans"]
        prunes = find(root, "Prune")
        assert len(prunes) == 1
        (prune,) = prunes
        assert prune["segments"] == SEGMENTS
        assert 1 <= prune["kept"] <= 2
        assert (prune.get("byPartition", 0) + prune.get("byBounds", 0)
                + prune["kept"]) >= SEGMENTS
        routes = find(root, "IndexRoute")
        assert 1 <= len(routes) <= 2
        for route in routes:
            # the sorted key gives the member's rows; the day range (no
            # index) is probed on them, the regions as a rule too
            conjuncts = len(cycle[rec["index"]]["where"])
            assert 1 <= route["resolved"] <= conjuncts - 1
            assert route["resolved"] + route["probed"] <= conjuncts
            assert route["probed"] >= 1 or route["candidates"] == 0
        kernels = [k for k in find(root, "Kernel")
                   if k.get("kernel") == "index_gather"]
        assert len(kernels) == len(routes)
        assert all(k["capacity"] >= 128 for k in kernels)


def cell_config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "userfacing.json")) as f:
        return json.load(f)


def test_the_servers_debug_endpoint_carries_the_stall_watch(deployment):
    served = deployment[0]
    watch = served.debug("server", "/debug/scheduler")["stallWatch"]
    assert watch["thresholdMs"] == 50.0 and watch["inflight"] == 0
    assert {"stalls", "stallMsTotal", "stallMsMax", "gc", "where",
            "residencyInStalls", "last"} <= set(watch)


def test_a_segment_holds_one_partition_sorted(deployment):
    served = deployment[0]
    seg = served.server.data_manager.get(served.table)
    acquired = seg.acquire_segments(None)
    try:
        assert len(acquired) == SEGMENTS
        for holder in acquired:
            cm = holder.segment.metadata.columns["member_id"]
            i = int(holder.segment.segment_name.rsplit("_", 1)[1])
            assert cm.is_sorted
            assert (cm.partition_function, cm.num_partitions,
                    cm.partitions) == ("Modulo", mv.PARTITIONS,
                                       [i % mv.PARTITIONS])
    finally:
        seg.release_segments(acquired)
