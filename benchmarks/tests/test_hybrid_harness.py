"""A hybrid table through ``run.run`` on the CPU (``hybrid_toy``, each
drive in a process of its own): a toy ``member_views`` whose REALTIME
half two stream partitions feed from the benchmark's own producer while
the window queries it. The drive is
correct; each fault ``correct`` or a guarantee has to catch fails the run;
the producer is JAX-free and serves what the oracle regenerates; and a
config without ``realtime`` builds what it built before hybrid tables
came in (digests pinned from the tree before them)."""

import hashlib
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.lib import hybrid, oracle, schedule
from benchmarks.tables import member_views as mv
from benchmarks.tests import hybrid_toy

# A seal holds its partition's consumer while it builds the segment: on a
# CPU 0.75-0.93 s for the toy's 3,000 rows alone, and under load past 2 s
# (two drives of seven read 13 and 7 responses stale at 2 s, missing rows
# appended just after a seal). The CPU drives take 5 s; the chip's, 2 s.
FRESHNESS_S = 5.0


def drive(tmp, fault=None, **config) -> dict:
    """The toy cell through ``run.run`` in a process of its own (the
    drives build clusters and compile as a benchmark run does, and none
    of it stays in the test process), ``fault`` planted; what it saw."""
    config.setdefault("freshness_s", FRESHNESS_S)
    job = {"data_root": str(tmp), "fault": fault, "config": config}
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.tests.hybrid_toy",
         json.dumps(job)], cwd=bench.ROOT, capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_hybrid")


@pytest.fixture(scope="module")
def sound(data_root):
    return drive(data_root)


# -- the drive ----------------------------------------------------------------

def test_the_toy_hybrid_drive_is_correct(sound):
    line = sound["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 50
    compared = line["compared"]
    assert list(compared)[-1] == "responses_compared"
    assert compared["responses_compared"]["value"] == line["attempted"]
    for name in ("max_abs_diff", "responses_wrong", "responses_stale",
                 "host_served_decisions", "keeps_up_drained_lag_rows",
                 "rows_exact_partitions_off"):
        assert compared[name]["value"] == 0, name
    assert compared["keeps_up_lag_rows"]["limit"] == 1_000 * FRESHNESS_S
    assert compared["seals_in_window"]["value"] >= 2
    assert isinstance(line["compiled_in_window"], int)
    assert list(line)[-1] == "compared"
    win = sound["windows"][-1]
    # rows were appended while the window queried: 6 s at 1,000 rows/s
    assert sum(win["ingest_end"]["appended"]) == sum(
        hybrid_toy.toy_config()["realtime"]["catch_up_rows"]) + 6_000
    # the stream moved answers inside the window: some responses had rows
    # of their member arrive between lo and hi, some read a prefix past 0
    prefixes = win["prefixes"]
    assert len(prefixes) == line["attempted"] and all(prefixes)
    assert any(p["k_hi"] > p["k_lo"] for p in prefixes)
    assert any(p["k"] for p in prefixes)
    assert all(p["k_lo"] <= p["k"] <= p["k_hi"] for p in prefixes)


def test_an_altered_answer_fails_the_hybrid_run(data_root):
    seen = drive(data_root, "altered")
    # the harness's own count-and-sum queries go through the same reduce:
    # rows_exact fails the run, after the window's answers were judged
    line = seen["line"]
    assert line["correct"] is False and line["phase"] == "compare"
    assert "rows_exact_partitions_off" in line["failed_run"]
    verdict = seen["windows"][-1]["verdict"]
    assert verdict["correct"] is False
    assert verdict["compared"]["responses_wrong"]["value"] > 0


def test_a_stale_answer_fails_the_hybrid_run(data_root):
    """Every response of the window answers from the stream as it stood
    three seconds past the freshness limit before it was sent."""
    line = drive(data_root, "stale")["line"]
    assert line["correct"] is False
    assert line["compared"]["responses_stale"]["value"] > 0
    assert line["compared"]["responses_wrong"]["value"] == 0


def test_a_consumer_held_back_fails_keeps_up(data_root):
    """From the window on, the consumers read nothing."""
    line = drive(data_root, "held_back", drain_s=2.0, freshness_s=2.0)[
        "line"]
    assert line["correct"] is False and line["phase"] == "compare"
    assert "keeps_up_lag_rows" in line["failed_run"]
    assert "keeps_up_drained_lag_rows" in line["failed_run"]


def test_a_row_dropped_at_the_seal_fails_rows_exact(data_root):
    line = drive(data_root, "dropped")["line"]
    assert line["correct"] is False and line["phase"] == "compare"
    assert "rows_exact_partitions_off" in line["failed_run"]
    assert "keeps_up" not in line["failed_run"]


# -- the producer and the oracle ----------------------------------------------

def get(url: str):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read().decode())


def test_the_producer_never_loads_jax_and_serves_the_oracles_rows(tmp_path):
    config = hybrid_toy.toy_config(rate=2_000.0, catch_up=(300, 4_500))
    rt = config["realtime"]
    seed = 2 ** 31 + 99
    child = bench.Child("producer.py", hybrid.producer_job(config, seed),
                        str(tmp_path), "producer")
    try:
        producer = hybrid.Producer(child.ready(60.0))
        topic = f"{producer.url}/topics/{config['table_name']}"
        meta = get(f"{topic}/metadata")
        assert meta == {"numPartitions": 2, "earliest": [0, 0],
                        "latest": [300, 4_500]}
        producer.live(time.time() + 0.2, 0.5)
        deadline = time.time() + 30
        while producer.log()["lives_done"] < 1:
            assert time.time() < deadline
            time.sleep(0.05)
        state = producer.log()
        assert state["appended"] == hybrid.appended_after(config, 1_000)
        assert len(state["late_ms"]) >= 40      # a tick every 10 ms
        served = {}
        for p in range(2):
            got = []
            while len(got) < state["appended"][p]:
                batch = get(f"{topic}/fetch?partition={p}"
                            f"&offset={len(got)}&max=5000")
                assert [m["offset"] for m in batch["messages"]] == list(
                    range(len(got), batch["nextOffset"]))
                got += [m["payload"] for m in batch["messages"]]
            served[p] = got
            want = mv.decode(mv.stream_codes(p, 0, len(got), seed, 2))
            assert got == [dict(zip(want, row)) for row in
                           zip(*[v.tolist() for v in want.values()])]
            assert {r["member_id"] % 2 for r in got} == {p}
        producer.stop()
        out = child.join(60.0)      # exit 0: it never imported JAX
        assert out["appended"] == state["appended"]
    finally:
        child.kill()
    # the oracle regenerates the same rows: a member's past the boundary
    cycle = schedule.build_cycle(hybrid_toy.toy_traffic(variants=8), seed)
    pins = hybrid.check(config, cycle)
    job = hybrid.oracle_job(config, pins, 1.0, 1)
    job["appended"] = [state["appended"]]
    out = oracle.answers_for("member_views", config["segments"], 8_000,
                             seed, cycle, False, hybrid=job)
    boundary = out["hybrid"]["boundary"]
    assert boundary == mv.DAY0 + mv.DAYS - 2
    stream = hybrid.Stream(out["stream"], "member_id")
    for member in set(pins.values()):
        cols, offsets = stream.of(member)
        rows = served[member % rt["partitions"]]
        mine = [i for i, r in enumerate(rows) if r["member_id"] == member
                and r["day"] > boundary]
        assert offsets.tolist() == mine
        assert cols["views"].tolist() == [rows[i]["views"] for i in mine]
    totals = out["hybrid"]["totals"][0]
    for p in range(2):
        assert totals[p] == [len(served[p]),
                             sum(r["views"] for r in served[p]),
                             sum(r["dwell_ms"] for r in served[p])]


def test_a_string_that_pins_no_one_partition_is_refused():
    config = hybrid_toy.toy_config()
    q = {"id": 0, "flight": "f", "where": [["member_id", "in", 1, 2]]}
    with pytest.raises(hybrid.ConfigRefused, match="does not pin one"):
        hybrid.check(config, [q])
    with pytest.raises(hybrid.ConfigRefused, match="does not pin one"):
        hybrid.check(config, [dict(q, where=[["day", "=", 5]])])
    assert hybrid.check(config, [dict(q, where=[["member_id", "=", 7]])]) \
        == {0: 7}
    del config["realtime"]["drain_s"]
    with pytest.raises(hybrid.ConfigRefused, match="drain_s"):
        hybrid.check(config, [])


def test_a_response_is_judged_at_the_prefix_it_could_read():
    """Hand-made: one member's three stream rows at offsets 2, 5 and 9 of
    partition 1, appended in batches at t = 10, 20, 30."""
    config = hybrid_toy.toy_config()
    q = {"id": 0, "flight": "t", "where": [["member_id", "=", 7]],
         "value": ["views"], "group_by": [], "order": "keys"}
    arrays = {"member_id": np.array([7, 7, 7]), "views": np.array([1, 2, 4]),
              "day": np.full(3, mv.STREAM_DAYS[1]),
              "offset": np.array([2, 5, 9])}
    stream = hybrid.Stream(arrays, "member_id")
    log = hybrid.Log([[10.0, 1, 3], [20.0, 1, 6], [30.0, 1, 10]], 2)
    assert [log.at(1, t) for t in (5, 10, 25, 99)] == [0, 3, 6, 10]

    def rec(total, sent, done):
        return {"index": 0, "status": 200, "sent_epoch": sent,
                "done_epoch": done, "body": json.dumps({
                    "exceptions": [], "numServersQueried": 1,
                    "numServersResponded": 1, "partialResult": False,
                    "resultTable": {"rows": [[total]]}})}

    # the limit is 2 s: lo is what stood 2 s before the request left
    records = [rec(103.0, 23.0, 24.0),      # rows at 2 and 5: right
               rec(100.0, 11.0, 16.0),      # the row at 2 missing: right
               rec(101.0, 40.0, 41.0),      # 5 and 9 missing: stale
               rec(107.0, 15.0, 16.0),      # 9 not appended yet: wrong
               rec(104.5, 23.0, 24.0)]      # no prefix at all: wrong
    out = hybrid.compare_window(records, [q], {"0": [[100.0]]}, stream,
                                {0: 7}, log, config, mv)
    assert [r["ok"] for r in records] == [True, True, False, False, False]
    assert (out["responses_stale"], out["responses_wrong"]) == (1, 2)
    assert out["max_abs_diff"] == 1.5
    assert [r["prefix"]["k"] for r in records] == [2, 0, 1, 3, None]


# -- a config without realtime builds what it built before --------------------

PINNED = {"jobs": "1404417f37a05aad", "oracle": "aa36cc892c881e11",
          "verdict": "528c8e46b868f65b"}


def digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()
                          ).hexdigest()[:16]


class FakeServed:
    broker_port = 4321

    @staticmethod
    def ledger_breaches(forbidden, platform):
        return (["index:index_gather->scan:index_exec_failed"]
                if "index_exec_failed" in forbidden else [])

    @staticmethod
    def compiled_between(before, after):
        return sorted(set(after["cache_entries"])
                      - set(before["cache_entries"]))


PIN_SEED = 2 ** 31 + 777


def cells():
    with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def test_the_client_jobs_of_the_cells_are_as_before():
    out = {}
    for cell in cells():
        c = bench.load_cell(cell)
        assert "realtime" not in c["config"]
        cycle = schedule.build_cycle(c["traffic"], PIN_SEED)
        sqls = [q["sql"] for q in cycle]
        out[cell] = [
            bench.own_job(FakeServed(), cycle, sqls, c["traffic"], 45.0),
            bench.client_job(FakeServed(), sqls, runner="script", clients=1,
                             plans=[bench.walk_order(cycle)],
                             lockstep=False)]
    assert digest(out) == PINNED["jobs"]


class _Stop(Exception):
    pass


def test_the_oracle_inputs_of_the_cells_are_as_before(tmp_path,
                                                      monkeypatch):
    from benchmarks.lib import serve

    seen = {}

    class Capture:
        def __init__(self, script, job, work, tag):
            seen[tag] = (script, job)

        def kill(self):
            pass

    def no_device(*a, **kw):
        raise _Stop()

    monkeypatch.setattr(bench, "Child", Capture)
    monkeypatch.setattr(serve, "device_info", no_device)
    out = {}
    for cell in cells():
        seen.clear()
        with pytest.raises(_Stop):
            bench.set_up(cell, PIN_SEED, "cpu", 40_000, str(tmp_path), True)
        assert list(seen) == ["oracle"]     # no producer child
        out[cell] = seen["oracle"]
    assert digest(out) == PINNED["oracle"]


def test_the_judge_verdict_without_realtime_is_as_before():
    cell = bench.load_cell("userfacing.zipf_open_r80")
    cycle = schedule.build_cycle(cell["traffic"], PIN_SEED)[:4]

    def body(rows, **kw):
        return json.dumps(dict({
            "exceptions": [], "numServersQueried": 1,
            "numServersResponded": 1, "partialResult": False,
            "resultTable": {"rows": rows}}, **kw))

    want = {str(q["id"]): ([[5.0]] if not q["group_by"]
                           else [["a", 2.0], ["b", 3.0]]) for q in cycle}
    records = [{"index": q["id"], "status": 200,
                "body": body(want[str(q["id"])])} for q in cycle]
    records += [
        {"index": cycle[0]["id"], "status": 200,
         "body": body([[6.0]] if not cycle[0]["group_by"] else [["a", 2.0]])},
        {"index": cycle[1]["id"], "status": 500, "body": "", "error": "x"},
        {"index": cycle[2]["id"], "status": 200,
         "body": body(want[str(cycle[2]["id"])], numServersResponded=0)}]

    def counters(spills, hits, cache):
        return {"memory": {"counters": {"spills": spills}},
                "broker": {"singleFlight": {"hits": hits}},
                "cache_entries": cache}

    setup = {"config": cell["config"], "cycle": cycle, "want": want,
             "counters_at_start": counters(1, 0, [])}
    win = {"records": records, "before": counters(1, 3, ["a"]),
           "after": counters(2, 5, ["a", "b"])}
    verdict = bench.judge(FakeServed(), setup, win, "cpu")
    assert verdict["correct"] is False and verdict["attempted"] == 7
    assert "responses_stale" not in verdict["compared"]
    assert digest(verdict) == PINNED["verdict"]
