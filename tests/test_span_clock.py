"""The span tree's clock: start offsets, thread CPU time, request ids.

What ``common/tracing.py`` and the instrumented layers guarantee beyond
``tests/test_trace.py``:

- every closed span carries ``startMs`` (offset from its root's start),
  ``cpuMs`` (CPU time of the thread that ran it) and ``thread``; both roots
  carry ``startEpochMs`` and the one ``requestId`` of the request;
- children lie inside their parent's interval, so a span's self time is
  its ``ms`` less the union of its children's intervals, also where the
  children ran side by side in the segment pool;
- the host phases have names on both paths (star-tree ladder, sharded
  combine), and the device wait is a span of its own with no CPU time;
- an untraced query creates no recorder, reads no thread clock and enters
  no profiler annotation;
- the benchmark's readers (``benchmarks/metrics/``) return the numbers
  worked out by hand from a recorded tree, and None without the clock.
"""

import copy
import json
import os
import threading
import time

import numpy as np
import pytest

from pinot_tpu.common import tracing
from pinot_tpu.common.tracing import SpanRecorder, flatten_spans
from pinot_tpu.engine import QueryStats
from pinot_tpu.parallel import ShardedQueryExecutor
from pinot_tpu.query import compile_query
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.spi.table import (
    IndexingConfig,
    StarTreeIndexConfig,
    TableConfig,
)

pytestmark = pytest.mark.trace

RNG = np.random.default_rng(23)
N = 1024
NUM_SEGMENTS = 3
TREE_SQL = ("SELECT region, sum(qty), count(*) FROM sales_st "
            "GROUP BY region ORDER BY region")
SCAN_SQL = "SELECT year, sum(qty) FROM sales_st GROUP BY year ORDER BY year"
CLOCK_KEYS = ("startMs", "cpuMs", "thread")
EPS_MS = 0.01       # rounding of the wire form (3 decimals a number)


def _schema():
    return Schema("sales_st", [
        FieldSpec("region", DataType.STRING),
        FieldSpec("kind", DataType.STRING),
        FieldSpec("year", DataType.INT),
        FieldSpec("qty", DataType.LONG, FieldType.METRIC),
    ])


def _indexing():
    return IndexingConfig(star_tree_index_configs=[StarTreeIndexConfig(
        dimensions_split_order=["region", "kind"],
        function_column_pairs=["SUM__qty", "COUNT__*"],
        max_leaf_records=100)])


def _rows():
    return {
        "region": [["east", "west"][j] for j in RNG.integers(0, 2, N)],
        "kind": [["a", "b", "c"][j] for j in RNG.integers(0, 3, N)],
        "year": (2015 + RNG.integers(0, 5, N)).tolist(),
        "qty": RNG.integers(1, 50, N).tolist(),
    }


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    out = tmp_path_factory.mktemp("clock_segs")
    built = []
    for i in range(NUM_SEGMENTS):
        SegmentBuilder(_schema(), f"sales_st_{i}",
                       indexing_config=_indexing()).build(_rows(), str(out))
        built.append(load_segment(str(out / f"sales_st_{i}")))
    return built


@pytest.fixture(scope="module")
def executor():
    ex = ShardedQueryExecutor()
    yield ex
    ex.close()


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    from pinot_tpu.tools.cluster import EmbeddedCluster

    c = EmbeddedCluster(num_servers=1,
                        data_dir=str(tmp_path_factory.mktemp("clock_cl")))
    try:
        c.create_table(TableConfig("sales_st", indexing_config=_indexing()),
                       _schema())
        for i in range(2):
            c.ingest_rows("sales_st_OFFLINE", _schema(), _rows(),
                          segment_name=f"sales_st_{i}")
        assert c.wait_for_ev_converged("sales_st_OFFLINE")
        yield c
    finally:
        c.shutdown()


def _traced(executor, segs, sql):
    rt, stats = executor.execute(
        compile_query(sql + " OPTION(trace=true)"), segs)
    assert len(stats.spans) == 1
    return stats.spans[0]


def _walk(span):
    yield span
    for child in span.get("children", ()):
        yield from _walk(child)


def _names(span):
    return {s["name"] for s in _walk(span)}


def _interval(span):
    return span["startMs"], span["startMs"] + span["ms"]


def _union_ms(intervals):
    total, at = 0.0, float("-inf")
    for a, b in sorted(intervals):
        a = max(a, at)
        if b > a:
            total += b - a
            at = b
    return total


# --------------------------------------------------------------------------
# what a span records
# --------------------------------------------------------------------------

class TestWhatASpanRecords:
    @pytest.mark.parametrize("sql", [TREE_SQL, SCAN_SQL],
                             ids=["startree", "sharded"])
    def test_every_span_has_start_cpu_and_thread(self, executor, segs, sql):
        root = _traced(executor, segs, sql)
        for span in _walk(root):
            for key in CLOCK_KEYS:
                assert key in span, (span["name"], key)
            assert span["cpuMs"] >= 0 and span["thread"]
        assert root["startMs"] == 0.0
        assert abs(root["startEpochMs"] - time.time() * 1e3) < 60e3
        # only the root stands on the wall clock by itself
        assert [s["name"] for s in _walk(root)
                if "startEpochMs" in s] == ["ServerQuery"]

    @pytest.mark.parametrize("sql", [TREE_SQL, SCAN_SQL],
                             ids=["startree", "sharded"])
    def test_children_lie_inside_their_parent(self, executor, segs, sql):
        root = _traced(executor, segs, sql)
        for parent in _walk(root):
            lo, hi = _interval(parent)
            for child in parent.get("children", ()):
                a, b = _interval(child)
                assert a >= lo - EPS_MS and b <= hi + EPS_MS, \
                    (parent["name"], child["name"], (lo, hi), (a, b))

    def test_pure_waits_carry_no_cpu_time(self, executor, segs):
        root = _traced(executor, segs, TREE_SQL)
        waits = [s for s in _walk(root)
                 if s["name"] in ("Admission", "SegmentQueue")]
        assert {s["name"] for s in waits} == {"Admission", "SegmentQueue"}
        for s in waits:
            assert s["cpuMs"] == 0.0 and s["queueMs"] == s["ms"]

    def test_request_id_and_wall_clock_on_both_roots(self, cluster):
        resp = cluster.query(TREE_SQL + " OPTION(trace=true)")
        assert not resp.exceptions, resp.exceptions
        broker = resp.to_dict()["traceInfo"]["spans"][0]
        assert broker["name"] == "BrokerQuery"
        servers = [s for s in _walk(broker) if s["name"] == "ServerQuery"]
        assert len(servers) == 1
        assert broker["requestId"] and \
            servers[0]["requestId"] == broker["requestId"]
        # the server's root on the broker's clock: inside ScatterGather
        gather = next(s for s in broker["children"]
                      if s["name"] == "ScatterGather")
        lo, hi = _interval(gather)
        a, b = _interval(servers[0])
        assert a == pytest.approx(
            servers[0]["startEpochMs"] - broker["startEpochMs"], abs=EPS_MS)
        assert lo - 0.5 <= a and b <= hi + 0.5, ((lo, hi), (a, b))
        for span in _walk(broker):
            for key in CLOCK_KEYS:
                assert key in span, (span["name"], key)

    def test_a_clients_request_id_wins(self, cluster):
        resp = cluster.query(TREE_SQL + " OPTION(trace=true, requestId=r42)")
        broker = resp.to_dict()["traceInfo"]["spans"][0]
        assert {s["requestId"] for s in _walk(broker)
                if "requestId" in s} == {"r42"}

    def test_the_broker_gives_every_query_a_request_id(self, cluster):
        server = next(iter(cluster.servers.values()))
        cluster.query(SCAN_SQL)
        cluster.query(SCAN_SQL)
        done = server.executor.queries.snapshot()["completed"][-2:]
        ids = [d.get("requestId") for d in done]
        assert all(ids) and ids[0] != ids[1], ids

    def test_scheduler_wait_moves_the_root_back(self):
        stats = QueryStats()
        rec = tracing.start_trace(stats, request_id="q1")
        root = rec.span_begin("ServerQuery")
        with rec.span("Lease"):
            pass
        rec.span_end(root)
        before = copy.deepcopy(stats.spans[0])
        tracing.attach_root_child(stats, "SchedulerQueue", wall_ms=5.0,
                                  queue_ms=5.0, front=True)
        after = stats.spans[0]
        assert after["ms"] == pytest.approx(before["ms"] + 5.0, abs=EPS_MS)
        assert after["startEpochMs"] == pytest.approx(
            before["startEpochMs"] - 5.0, abs=EPS_MS)
        queue, lease = after["children"]
        assert (queue["name"], queue["startMs"], queue["cpuMs"]) \
            == ("SchedulerQueue", 0.0, 0.0)
        assert lease["startMs"] == pytest.approx(
            before["children"][0]["startMs"] + 5.0, abs=EPS_MS)


# --------------------------------------------------------------------------
# self time is arithmetic on intervals
# --------------------------------------------------------------------------

class TestSelfTimeFromIntervals:
    def test_pool_segments_overlap_and_self_time_is_not_negative(
            self, executor, segs):
        """Segments of one query run side by side in the pool: their
        ``ms`` summed may pass the root's, the union of their intervals
        cannot."""
        overlapped = False
        for _ in range(8):
            root = _traced(executor, segs, TREE_SQL)
            kids = [_interval(c) for c in root["children"]]
            union = _union_ms(kids)
            assert union <= root["ms"] + EPS_MS
            assert root["ms"] - union >= -EPS_MS
            seg = sorted(_interval(c) for c in root["children"]
                         if c["name"] == "SegmentGroupBy")
            assert len(seg) == NUM_SEGMENTS
            threads = {c["thread"] for c in root["children"]
                       if c["name"] == "SegmentGroupBy"}
            assert root["thread"] not in threads
            if any(b > a2 for (_, b), (a2, _) in zip(seg, seg[1:])):
                overlapped = True
                break
        assert overlapped, "no two segments ran side by side in 8 queries"

    def test_worker_spans_stand_on_the_querys_clock(self, executor, segs):
        root = _traced(executor, segs, TREE_SQL)
        queues = [c for c in root["children"] if c["name"] == "SegmentQueue"]
        assert len(queues) == NUM_SEGMENTS
        # all were submitted at once, each ends where its segment begins
        assert len({q["startMs"] for q in queues}) == 1
        for q in queues:
            seg = next(c for c in root["children"]
                       if c["name"] == "SegmentGroupBy"
                       and c["segment"] == q["segment"])
            assert q["thread"] == seg["thread"]
            assert q["startMs"] + q["ms"] <= seg["startMs"] + EPS_MS


# --------------------------------------------------------------------------
# named spans where the host time goes
# --------------------------------------------------------------------------

STARTREE_SPANS = {"Route", "SegmentQueue", "StarTreeWalk", "Plan", "Stage",
                  "Kernel", "Dispatch", "DeviceWait", "D2H", "Decode",
                  "CombineSegments", "Release"}
SHARDED_SPANS = {"Route", "Plan", "Stage", "ShardedCombine", "Dispatch",
                 "DeviceWait", "HandOff", "Resume", "D2H", "Decode",
                 "Release"}


class TestNamedSpans:
    @pytest.mark.parametrize("name", sorted(STARTREE_SPANS))
    def test_on_the_star_tree_path(self, executor, segs, name):
        root = _traced(executor, segs, TREE_SQL)
        assert name in _names(root), sorted(_names(root))

    @pytest.mark.parametrize("name", sorted(SHARDED_SPANS))
    def test_on_the_sharded_path(self, executor, segs, name):
        root = _traced(executor, segs, SCAN_SQL)
        assert name in _names(root), sorted(_names(root))

    def test_parents_and_attributes(self, executor, segs):
        tree = _traced(executor, segs, TREE_SQL)
        assert next(s for s in tree["children"]
                    if s["name"] == "Route")["path"] == "startree"
        for seg in (c for c in tree["children"]
                    if c["name"] == "SegmentGroupBy"):
            inner = {c["name"]: c for c in seg["children"]}
            assert {"StarTreeWalk", "Plan", "Stage", "Kernel",
                    "Decode"} <= set(inner)
            assert inner["StarTreeWalk"]["tree"] == 0
            assert inner["StarTreeWalk"]["records"] \
                == inner["Kernel"]["records"]
            assert [c["name"] for c in inner["Kernel"]["children"]] \
                == ["Dispatch", "DeviceWait", "D2H"]
            assert inner["Kernel"]["children"][2]["bytes"] > 0
        scan = _traced(executor, segs, SCAN_SQL)
        kids = {c["name"]: c for c in scan["children"]}
        assert kids["Route"]["path"] == "sharded"
        assert kids["Plan"]["cacheHit"] in (True, False)
        combine = kids["ShardedCombine"]
        launch = {c["name"]: c for c in combine["children"]}
        assert set(launch) == {"Dispatch", "DeviceWait", "HandOff", "Resume",
                               "D2H"}
        # the launcher's dispatcher thread stamped its two phases
        assert launch["Dispatch"]["thread"].startswith("combine-launch")
        assert launch["DeviceWait"]["thread"] == launch["Dispatch"]["thread"]
        assert launch["D2H"]["thread"] == combine["thread"]
        assert launch["DeviceWait"]["startMs"] == pytest.approx(
            launch["Dispatch"]["startMs"] + launch["Dispatch"]["ms"],
            abs=EPS_MS)

    def test_the_launch_hands_off_then_the_query_resumes(self, executor,
                                                         segs):
        """Under ``ShardedCombine``: ``DeviceWait``, then the dispatcher's
        ``HandOff`` until it set the future, then the query thread's
        ``Resume``, each starting where the one before ended."""
        root = _traced(executor, segs, SCAN_SQL)
        combine = next(c for c in root["children"]
                       if c["name"] == "ShardedCombine")
        names = [c["name"] for c in combine["children"]]
        i = names.index("DeviceWait")
        assert names[i:i + 3] == ["DeviceWait", "HandOff", "Resume"], names
        wait, hand, resume = combine["children"][i:i + 3]
        assert hand["thread"] == wait["thread"]
        assert hand["thread"].startswith("combine-launch")
        assert resume["thread"] == combine["thread"]
        assert resume["cpuMs"] == 0.0
        for a, b in ((wait, hand), (hand, resume)):
            assert b["startMs"] == pytest.approx(a["startMs"] + a["ms"],
                                                 abs=2 * EPS_MS)

    def test_a_wake_is_on_the_clock_not_in_the_span_tree(self, executor,
                                                         segs):
        """A query whose submit wakes the waiting dispatcher counts one
        wake on ``/debug/launches`` ``clock``, inside its combine's
        ``queueMs``, and its launch has the same spans as one that found
        the dispatcher busy, which counts no wake."""
        from pinot_tpu.parallel.launcher import LaunchKernel

        launcher = executor.launcher

        def launch_names(root):
            combine = next(c for c in root["children"]
                           if c["name"] == "ShardedCombine")
            return [c["name"] for c in combine["children"]]

        def wait_idle():
            deadline = time.monotonic() + 30
            while launcher.snapshot()["queued"] or not launcher._waiting:
                assert time.monotonic() < deadline
                time.sleep(0.001)

        _traced(executor, segs, SCAN_SQL)       # the thread is there
        wait_idle()
        before = launcher.stats_snapshot()["clock"]
        idle = _traced(executor, segs, SCAN_SQL)
        wait_idle()
        woken = launcher.stats_snapshot()["clock"]
        assert woken["wakes"] == before["wakes"] + 1
        combine = next(c for c in idle["children"]
                       if c["name"] == "ShardedCombine")
        assert woken["wakingMs"] - before["wakingMs"] \
            <= combine["queueMs"] + EPS_MS
        names = launch_names(idle)
        assert names[0] == "Dispatch", names
        # busy: a launch holds the dispatcher while the query submits
        gate, entered = threading.Event(), threading.Event()

        def hold(params, num_docs):
            entered.set()
            gate.wait(30)
            return params

        blocker = executor.launcher.submit(LaunchKernel(("hold",), hold),
                                           0, 0)
        assert entered.wait(30)
        got = []
        t = threading.Thread(target=lambda: got.append(
            _traced(executor, segs, SCAN_SQL)), daemon=True)
        t.start()
        deadline = time.monotonic() + 30
        while not launcher.snapshot()["queued"]:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        gate.set()
        blocker.result(30)
        t.join(60)
        wait_idle()
        # the blocker woke the dispatcher; the query that queued behind
        # it did not
        assert launcher.stats_snapshot()["clock"]["wakes"] \
            == woken["wakes"] + 1
        assert got and launch_names(got[0]) == names

    def test_the_walk_span_says_what_the_walk_did(self, executor, segs):
        """``nodes`` read, ``emitted`` records of the leaf ranges reached,
        ``gathered`` still read and masked after the search inside the
        leaves, ``selectMs`` the wall time of ``select_records`` alone."""
        tree = _traced(executor, segs, TREE_SQL)
        walks = [c for seg in tree["children"]
                 if seg["name"] == "SegmentGroupBy"
                 for c in seg["children"] if c["name"] == "StarTreeWalk"]
        assert len(walks) == NUM_SEGMENTS
        for w in walks:
            assert {"tree", "records", "nodes", "emitted", "gathered",
                    "selectMs"} <= set(w)
            assert w["nodes"] >= 1
            assert 0 <= w["gathered"] <= w["emitted"]
            assert 0 < w["records"] <= w["emitted"]
            assert 0 <= w["selectMs"] <= w["ms"] + EPS_MS

    def test_an_untraced_walk_is_handed_no_counter(self, executor, segs,
                                                   monkeypatch):
        """``select_records`` fills its counters through an out-parameter
        that only a traced query hands in; untraced it gets none. Either
        way it reads no clock itself: ``selectMs`` is taken around it, by
        the traced caller."""
        from pinot_tpu.segment.startree import StarTree

        handed, reads = [], []
        real = StarTree.select_records
        real_clocks = {name: getattr(time, name) for name
                       in ("perf_counter", "thread_time", "monotonic")}

        def spy(self, matches, group_by, walk=None):
            # the segments walk side by side on pool workers, each opening
            # and closing its own spans: a read counts on its own thread
            me = threading.get_ident()
            handed.append(walk)
            before = len(reads)
            out = real(self, matches, group_by, walk)
            mine = [r for r in reads[before:] if r[1] == me]
            assert not mine, mine
            return out

        def counted(name):
            def clock():
                reads.append((name, threading.get_ident()))
                return real_clocks[name]()
            return clock

        executor.execute(compile_query(TREE_SQL), segs)     # warm
        monkeypatch.setattr(StarTree, "select_records", spy)
        for name in real_clocks:
            monkeypatch.setattr(time, name, counted(name))
        executor.execute(compile_query(TREE_SQL), segs)
        assert handed == [None] * NUM_SEGMENTS
        del handed[:]
        _traced(executor, segs, TREE_SQL)
        assert len(handed) == NUM_SEGMENTS
        assert all(set(w) == {"nodes", "emitted", "gathered"}
                   for w in handed)

    def test_served_queries_serialize_under_the_server_root(self, cluster):
        resp = cluster.query(TREE_SQL + " OPTION(trace=true)")
        broker = resp.to_dict()["traceInfo"]["spans"][0]
        server = next(s for s in _walk(broker) if s["name"] == "ServerQuery")
        names = [c["name"] for c in server["children"]]
        assert names[:2] == ["SchedulerQueue", "Admission"]
        assert names[-2:] == ["Serialize", "Release"]
        # the folds lie where their time does: inside the gather
        gather = next(s for s in broker["children"]
                      if s["name"] == "ScatterGather")
        assert "Fold" in [c["name"] for c in gather["children"]]

    def test_device_wait_has_no_cpu_time_on_a_blocking_wait(self):
        """The wait for the device is the one span in which the thread is
        owed work and does none."""
        from pinot_tpu.engine.kernels import fetch_outputs

        class Slow:
            def block_until_ready(self):
                time.sleep(0.05)
                return self

            def __array__(self, dtype=None, copy=None):
                return np.arange(4.0)

        stats = QueryStats()
        rec = tracing.start_trace(stats)
        with rec.span("Kernel"):
            host = fetch_outputs(stats, Slow())
        assert host.tolist() == [0.0, 1.0, 2.0, 3.0]
        wait, d2h = stats.spans[0]["children"]
        assert (wait["name"], d2h["name"]) == ("DeviceWait", "D2H")
        assert wait["ms"] >= 50.0 and wait["cpuMs"] < 5.0
        assert d2h["bytes"] == 32
        # untraced: the same array, no span
        assert fetch_outputs(QueryStats(), Slow()).tolist() == host.tolist()

    def test_grpc_serialize_and_deserialize(self):
        """Over the wire the framing is the root's last child and the
        decode a span of its own beside the server's tree."""
        from pinot_tpu.common.datatable import DataTable
        from pinot_tpu.transport.grpc_transport import _from_wire, _to_wire

        stats = QueryStats()
        rec = tracing.start_trace(stats, request_id="q9")
        with rec.span("ServerQuery"):
            pass
        dt = DataTable.for_group_by({("east",): [3.0, 2]}, {}, stats)
        back = _from_wire(_to_wire(dt), traced=True)
        server, decode = back.stats.spans
        assert server["children"][-1]["name"] == "Serialize"
        assert server["children"][-1]["bytes"] > 0
        assert decode["name"] == "Deserialize" and "startEpochMs" in decode
        plain = _from_wire(_to_wire(DataTable.for_aggregation(
            [1.0], QueryStats())), traced=False)
        assert plain.stats.spans == []


# --------------------------------------------------------------------------
# one clock with the device trace; the off path
# --------------------------------------------------------------------------

class TestAnnotationsAndTheOffPath:
    def test_one_annotation_a_recorded_span(self, executor, segs,
                                            monkeypatch):
        entered = []
        real = tracing.annotate

        def counting(name, request_id):
            entered.append((name, request_id))
            return real(name, request_id)

        monkeypatch.setattr(tracing, "annotate", counting)
        rt, stats = executor.execute(compile_query(
            SCAN_SQL + " OPTION(trace=true, requestId=r7)"), segs)
        spans = list(_walk(stats.spans[0]))
        # a wait that was over when it was recorded gets none (Admission,
        # and the query thread's Resume); the launcher's dispatcher enters
        # its three phases on its own thread
        recorded = [s["name"] for s in spans
                    if s["name"] not in ("Admission", "Resume")]
        assert sorted(n for n, _ in entered) == sorted(recorded)
        assert {r for _, r in entered} == {"r7"}

    def test_annotation_is_a_jax_trace_annotation(self):
        import jax

        ann = tracing.annotate("Probe", "r1")
        assert isinstance(ann, jax.profiler.TraceAnnotation)
        ann.__exit__(None, None, None)

    def test_untraced_query_pays_no_recorder_clock_or_annotation(
            self, cluster, monkeypatch):
        calls = {"recorder": 0, "thread_time": 0, "annotate": 0}
        real_init = SpanRecorder.__init__
        real_clock = time.thread_time

        def init(self, *a, **k):
            calls["recorder"] += 1
            real_init(self, *a, **k)

        def clock():
            calls["thread_time"] += 1
            return real_clock()

        def annotate(name, request_id):
            calls["annotate"] += 1

        cluster.query(TREE_SQL)     # warm both paths first
        cluster.query(SCAN_SQL)
        monkeypatch.setattr(SpanRecorder, "__init__", init)
        monkeypatch.setattr(time, "thread_time", clock)
        monkeypatch.setattr(tracing, "annotate", annotate)
        for sql in (TREE_SQL, SCAN_SQL):
            resp = cluster.query(sql)
            assert not resp.exceptions and "traceInfo" not in resp.to_dict()
        assert calls == {"recorder": 0, "thread_time": 0, "annotate": 0}
        # and the traced query does pay them
        cluster.query(SCAN_SQL + " OPTION(trace=true)")
        assert min(calls.values()) > 0, calls


# --------------------------------------------------------------------------
# one source for the flat view
# --------------------------------------------------------------------------

class TestFlatView:
    def test_the_recorder_keeps_no_second_list(self, executor, segs):
        rt, stats = executor.execute(
            compile_query(TREE_SQL + " OPTION(trace=true)"), segs)
        assert stats.trace == []
        flat = flatten_spans(stats.spans)
        assert len(flat) == len(list(_walk(stats.spans[0])))
        assert flat[0]["operator"] == "ServerQuery"
        assert {"SegmentGroupBy", "Kernel", "StarTreeWalk"} \
            <= {e["operator"] for e in flat}

    def test_entries_come_from_the_tree_instance_tagged(self, cluster):
        resp = cluster.query(TREE_SQL + " OPTION(trace=true)")
        info = resp.to_dict()["traceInfo"]
        server = next(s for s in _walk(info["spans"][0])
                      if s["name"] == "ServerQuery")
        entries = info["entries"]
        assert [e["operator"] for e in entries] \
            == [s["name"] for s in _walk(server)]
        assert {e["instance"] for e in entries} == {"server_0"}
        assert all("ms" in e and "startMs" in e for e in entries)


# --------------------------------------------------------------------------
# the benchmark's readers on a recorded tree
# --------------------------------------------------------------------------

HERE = os.path.dirname(os.path.abspath(__file__))

# Worked out by hand from tests/span_trees_cpu.json (two responses: the
# first took the star-tree ladder, the second the sharded combine). A
# median of two values is the lower (``lib/stats.percentile``'s nearest
# rank); CPU sums are means over the queries that have the span.
# ServerQuery 1 is 4.134 ms; its children cover [0, .038] [.108, .121]
# [.146, .177] [.194, 3.94] (two queue waits and two segments, overlapping)
# [3.984, 3.995] [4.003, 4.022] [4.029, 4.128] = 3.957, which leaves 0.177.
# ServerQuery 2 is 1.631 ms and its children cover 1.364: 0.267 is left.
BY_HAND = {
    "server_unattributed_ms": 0.177,
    # the threads' top spans: .38 + .92 + 2.507 | .775 + .099, halved
    "exec_cpu_ms_per_query": (3.807 + 0.874) / 2,
    # self CPU 3.755 + .874 over self wall 6.303 + 1.248, waits left out
    "host_runnable_wait_share": 100.0 * (1.0 - 4.629 / 7.551),
    "segment_queue_ms": 0.825,              # the longer of .056 and .825
    "startree_walk_cpu_ms": 0.249,          # .142 + .107; none in the second
    "plan_cpu_ms": (0.085 + 0.014) / 2,     # .043 + .042 | .014
    "dispatch_cpu_ms": (2.372 + 0.099) / 2,     # .391 + 1.981 | .099
    "device_wait_ms": 0.262,    # [3.495, 3.841] holds the other: .346 | .262
    "d2h_ms": 0.018,                        # .012 + .006 | .09
    # .072 + .033 + .01 + .019 | .038 + .02
    "decode_cpu_ms": (0.134 + 0.058) / 2,
    # a 10 ms span from 1 ms before ServerQuery 1: its two launches cover
    # [.592, 3.841], the second response lies outside
    "idle_nothing_launched_share": 100.0 * (1.0 - 3.249 / 10.0),
    "residency_hit_share": 100.0 * 100 / (100 + 25),
}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "span_trees_cpu.json")) as f:
        records = json.load(f)["records"]
    server = next(s for s in _walk(records[0]["raw"]["traceInfo"]["spans"][0])
                  if s["name"] == "ServerQuery")
    begin = (server["startEpochMs"] - 1.0) / 1e3
    return {"records": records,
            "device": {"wall_begin": begin, "wall_end": begin + 0.010},
            "before": {"memory": {"counters": {"hits": 40, "misses": 5}}},
            "after": {"memory": {"counters": {"hits": 140, "misses": 30}}}}


def _reader(name):
    from benchmarks import run

    return run.metric_reader(name)


def _without_clock(ctx):
    bare = copy.deepcopy(ctx)
    for rec in bare["records"]:
        for span in _walk(rec["raw"]["traceInfo"]["spans"][0]):
            for key in CLOCK_KEYS + ("startEpochMs", "requestId"):
                span.pop(key, None)
    for side in ("before", "after"):
        bare[side]["memory"]["counters"].update(hits=0, misses=0)
    return bare


class TestReaders:
    def test_every_new_metric_has_an_entry_and_a_reader(self):
        with open(os.path.join(os.path.dirname(HERE),
                               "BENCHMARK.json")) as f:
            entries = {m["name"]: m for m in json.load(f)["per_layer"]}
        for name in BY_HAND:
            assert entries[name]["workloads"], name
            assert callable(_reader(name))

    @pytest.mark.parametrize("name", sorted(BY_HAND))
    def test_reads_the_number_worked_out_by_hand(self, recorded, name):
        # the wire form rounds to 3 decimals; a share sums many spans
        slack = 1e-2 if name.endswith("_share") else 2e-3
        assert _reader(name)(recorded) == pytest.approx(BY_HAND[name],
                                                        abs=slack)

    @pytest.mark.parametrize("name", sorted(BY_HAND))
    def test_reads_nothing_without_the_clock(self, recorded, name):
        assert _reader(name)(_without_clock(recorded)) is None

    def test_old_readers_read_the_same_names(self, recorded):
        """The tree kept its shape: the readers that were there find
        their spans and numbers where they did."""
        assert _reader("sched_wait_ms")(recorded) == pytest.approx(0.038)
        assert _reader("broker_self_ms")(recorded) == pytest.approx(
            2.208 - 1.631)
        # ServerQuery less its queue waits less the combine's work
        assert _reader("exec_host_self_ms")(recorded) == pytest.approx(
            1.631 - (0.054 + 0.002 + 0.065) - 0.914)

    def test_segment_queue_reads_zero_where_segments_ran_inline(
            self, recorded):
        ctx = copy.deepcopy(recorded)
        for span in _walk(ctx["records"][0]["raw"]["traceInfo"]["spans"][0]):
            span["children"] = [c for c in span.get("children", ())
                                if c["name"] != "SegmentQueue"]
        assert _reader("segment_queue_ms")(ctx) == 0.0


# --------------------------------------------------------------------------
# the launcher's readers: the dispatcher's clock and the query's Resume
# --------------------------------------------------------------------------

LAUNCHER_CELLS = ["ssb_scan.flights_c2", "ssb_scan.flights_c8v",
                  "ssb_scan_x4.flights_c2", "ssb_scan_sf12.flights_c2"]


def _clock_ctx(before, after):
    keys = ("emptyMs", "wakingMs", "dispatchingMs", "deviceWaitMs",
            "handingOffMs")
    return {side: {"launches": {"requests": 0,
                                "clock": dict(zip(keys, vals),
                                              wakes=0, groups=0)}}
            for side, vals in (("before", before), ("after", after))}


# before (100, 1, 50, 300, 9); after (1100, 4, 2050, 5300, 1509): the
# window grew 1000 + 3 + 2000 + 5000 + 1500 = 9503 ms of the thread
CLOCK_BY_HAND = {
    "dispatcher_empty_share": 100.0 * 1000 / 9503,
    "dispatcher_wake_share": 100.0 * 3 / 9503,
    "dispatcher_dispatch_share": 100.0 * 2000 / 9503,
    "dispatcher_device_wait_share": 100.0 * 5000 / 9503,
    "dispatcher_handoff_share": 100.0 * 1500 / 9503,
}


def _server(resumes):
    """A broker root over one server's tree with one ``ShardedCombine`` a
    value of ``resumes`` (``None``: a combine without ``Resume``)."""
    def leaf(name, start, ms):
        return {"name": name, "ms": ms, "startMs": start, "cpuMs": 0.0,
                "thread": "t"}

    combines = []
    for i, r in enumerate(resumes):
        kids = [leaf("Dispatch", 1.0 + 10 * i, 2.0),
                leaf("DeviceWait", 3.0 + 10 * i, 4.0),
                leaf("HandOff", 7.0 + 10 * i, 0.1)]
        if r is not None:
            kids.append(leaf("Resume", 7.1 + 10 * i, r))
        combines.append(dict(leaf("ShardedCombine", 10 * i, 9.0),
                             children=kids))
    server = dict(leaf("ServerQuery", 0.0, 30.0), startEpochMs=1e12 + 1,
                  children=combines)
    root = dict(leaf("BrokerQuery", 0.0, 40.0), startEpochMs=1e12,
                children=[server])
    return {"ok": True, "raw": {"traceInfo": {"spans": [root]}}}


class TestLauncherReaders:
    def test_each_has_an_entry_in_the_launcher_cells(self):
        with open(os.path.join(os.path.dirname(HERE),
                               "BENCHMARK.json")) as f:
            entries = {m["name"]: m for m in json.load(f)["per_layer"]}
        for name in list(CLOCK_BY_HAND) + ["resume_ms"]:
            assert entries[name]["workloads"] == LAUNCHER_CELLS, name
            assert entries[name]["layer"] == "parallel launcher"
            assert callable(_reader(name))

    @pytest.mark.parametrize("name", sorted(CLOCK_BY_HAND))
    def test_a_share_reads_the_number_worked_out_by_hand(self, name):
        ctx = _clock_ctx((100, 1, 50, 300, 9), (1100, 4, 2050, 5300, 1509))
        assert _reader(name)(ctx) == pytest.approx(CLOCK_BY_HAND[name])

    def test_the_five_shares_sum_to_a_hundred(self):
        ctx = _clock_ctx((0, 0, 0, 0, 0), (7, 1, 3, 11, 2))
        assert sum(_reader(n)(ctx) for n in CLOCK_BY_HAND) \
            == pytest.approx(100.0)

    @pytest.mark.parametrize("name", sorted(CLOCK_BY_HAND))
    def test_a_share_reads_nothing_without_the_clock(self, name):
        ctx = _clock_ctx((0,) * 5, (1,) * 5)
        for side in ("before", "after"):
            bare = copy.deepcopy(ctx)
            del bare[side]["launches"]["clock"]     # a parent's snapshot
            assert _reader(name)(bare) is None
        # a window in which the clock did not move
        assert _reader(name)(_clock_ctx((5,) * 5, (5,) * 5)) is None

    def test_resume_reads_the_number_worked_out_by_hand(self):
        # query 1: two launches, 0.3 + 0.2; query 2: 0.9; query 3 has
        # none and is left out: the median of (0.5, 0.9) is the lower
        records = [_server([0.3, 0.2]), _server([0.9]), _server([None])]
        assert _reader("resume_ms")({"records": records}) \
            == pytest.approx(0.5)
        assert _reader("resume_ms")({"records": records[1:]}) \
            == pytest.approx(0.9)

    def test_resume_reads_nothing_without_the_span(self, recorded):
        # the recorded trees are a parent's: no Resume anywhere
        assert _reader("resume_ms")(recorded) is None
        assert _reader("resume_ms")({"records": [_server([None])]}) is None
