"""Query lifecycle tracing: span trees, decision ledger, slow-query log.

The contracts the subsystem guarantees (common/tracing.py + the
instrumented execution layers):

- a traced query returns ONE hierarchical span tree covering the full
  lifecycle (admission -> lease -> launch -> kernel -> combine), with
  explicit queue-vs-work attribution wherever a queue exists;
- span trees ride the DataTable wire and re-parent under the broker root
  at reduce, instance-tagged BEFORE re-parenting; the legacy flat
  ``traceInfo["entries"]`` view is preserved;
- exception edges close every open span — a dying query never leaves a
  dangling tree;
- the untraced path allocates NO span objects;
- every decline of a faster rung lands in ``QueryStats.decisions`` with
  a stable, non-``unknown`` reason code (the Q1.x expression-agg and
  Q3.x off-split-order shapes pinned here);
- the query registry backs ``/debug/queries`` and the slow-query log
  retains full span trees for over-threshold queries even when
  trace/sampling missed them.
"""

import json
import threading

import numpy as np
import pytest

from pinot_tpu.common.datatable import DataTable
from pinot_tpu.common.tracing import (
    DecisionLedger,
    SpanRecorder,
    build_broker_root,
    classify_decline,
    flatten_spans,
    parse_decision_key,
)
from pinot_tpu.engine import QueryStats, ServerQueryExecutor
from pinot_tpu.parallel import ShardedQueryExecutor
from pinot_tpu.query import compile_query
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.spi.config import CommonConstants, PinotConfiguration

pytestmark = pytest.mark.trace

RNG = np.random.default_rng(11)
N = 1024
NUM_SEGMENTS = 3

GROUP_SQL = ("SELECT region, sum(qty), count(*) FROM sales "
             "GROUP BY region ORDER BY region")
TRACED_SQL = GROUP_SQL + " OPTION(trace=true)"


def _schema():
    return Schema("sales", [
        FieldSpec("region", DataType.STRING),
        FieldSpec("qty", DataType.LONG, FieldType.METRIC),
    ])


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace_segs")
    regions = ["east", "west", "north", "south"]
    built = []
    for i in range(NUM_SEGMENTS):
        b = SegmentBuilder(_schema(), f"sales_{i}")
        b.build({
            "region": [regions[j] for j in RNG.integers(0, 4, N)],
            "qty": RNG.integers(1, 50, N).tolist(),
        }, str(out))
        built.append(load_segment(str(out / f"sales_{i}")))
    return built


@pytest.fixture(scope="module")
def st_segs(tmp_path_factory):
    """Segments carrying a star-tree over (region, kind) — the decline
    shapes (expression agg, off-split-order group) need trees to
    decline."""
    from pinot_tpu.spi.table import IndexingConfig, StarTreeIndexConfig

    out = tmp_path_factory.mktemp("trace_st_segs")
    cfg = IndexingConfig(star_tree_index_configs=[StarTreeIndexConfig(
        dimensions_split_order=["region", "kind"],
        function_column_pairs=["SUM__qty", "COUNT__*"],
        max_leaf_records=100)])
    schema = Schema("sales_st", [
        FieldSpec("region", DataType.STRING),
        FieldSpec("kind", DataType.STRING),
        FieldSpec("year", DataType.INT),
        FieldSpec("qty", DataType.LONG, FieldType.METRIC),
        FieldSpec("price", DataType.DOUBLE, FieldType.METRIC),
    ])
    built = []
    for i in range(2):
        b = SegmentBuilder(schema, f"sales_st_{i}", indexing_config=cfg)
        b.build({
            "region": [["east", "west"][j] for j in RNG.integers(0, 2, N)],
            "kind": [["a", "b", "c"][j] for j in RNG.integers(0, 3, N)],
            "year": (2015 + RNG.integers(0, 5, N)).tolist(),
            "qty": RNG.integers(1, 50, N).tolist(),
            "price": np.round(RNG.normal(100.0, 10.0, N), 2).tolist(),
        }, str(out))
        built.append(load_segment(str(out / f"sales_st_{i}")))
    return built


def _names(children):
    return [c["name"] for c in children]


def _find(children, name):
    for c in children:
        if c["name"] == name:
            return c
    return None


# --------------------------------------------------------------------------
# span-tree shape
# --------------------------------------------------------------------------

class TestSpanTreeShape:
    def test_per_segment_group_by_nesting(self, segs):
        """admission -> lease -> per-segment (stage, kernel) nesting under
        one ServerQuery root."""
        ex = ServerQueryExecutor()
        rt, stats = ex.execute(compile_query(TRACED_SQL), segs)
        assert len(stats.spans) == 1
        root = stats.spans[0]
        assert root["name"] == "ServerQuery"
        kids = _names(root["children"])
        assert kids[0] == "Admission"
        assert "Lease" in kids
        seg_spans = [c for c in root["children"]
                     if c["name"] == "SegmentGroupBy"]
        assert len(seg_spans) == NUM_SEGMENTS
        for sp in seg_spans:
            assert sp["path"] in ("device", "host")
            inner = _names(sp.get("children", []))
            assert "Kernel" in inner
        # explicit queue-vs-work split at the admission level
        adm = _find(root["children"], "Admission")
        assert "queueMs" in adm and "workMs" in adm
        # the children's intervals lie inside the root's: segments run
        # side by side in the pool, so their ``ms`` may sum past the
        # root's, the union of their intervals cannot
        covered, at = 0.0, 0.0
        for a, b in sorted((c["startMs"], c["startMs"] + c["ms"])
                           for c in root["children"]):
            a = max(a, at)
            if b > a:
                covered += b - a
                at = b
        assert covered <= root["ms"] + 0.01
        # the flat view is derived FROM the tree, not kept beside it
        assert stats.trace == []
        ops = {e["operator"] for e in flatten_spans(stats.spans)}
        assert {"ServerQuery", "SegmentGroupBy", "Kernel"} <= ops

    def test_sharded_combine_queue_attribution(self, segs):
        """The launch-dispatcher level carries the queue-vs-work split
        (queueMs = dispatcher queue wait, workMs = launch + D2H)."""
        ex = ShardedQueryExecutor()
        rt, stats = ex.execute(compile_query(TRACED_SQL), segs)
        root = stats.spans[0]
        sc = _find(root["children"], "ShardedCombine")
        assert sc is not None, _names(root["children"])
        assert "queueMs" in sc and "workMs" in sc
        assert sc["kernel"] in ("jnp", "pallas")
        assert sc["segments"] == NUM_SEGMENTS
        # nesting order: Admission -> Lease -> ShardedCombine
        kids = _names(root["children"])
        assert kids.index("Admission") < kids.index("Lease") \
            < kids.index("ShardedCombine")

    def test_off_path_zero_allocation(self, segs):
        """An untraced query allocates no recorder, no spans, no flat
        entries — the off path pays one getattr per site."""
        ex = ServerQueryExecutor()
        rt, stats = ex.execute(compile_query(GROUP_SQL), segs)
        assert getattr(stats, "_recorder", None) is None
        assert stats.spans == []
        assert stats.trace == []

    def test_sample_rate_records_without_option(self, segs):
        """pinot.server.query.trace.sample=1.0: every query records and
        SHIPS its tree exactly as if trace=true had been set."""
        cfg = PinotConfiguration(
            {CommonConstants.TRACE_SAMPLE_KEY: "1.0"}, use_env=False)
        ex = ServerQueryExecutor(config=cfg)
        rt, stats = ex.execute(compile_query(GROUP_SQL), segs)
        assert stats.spans and stats.spans[0]["name"] == "ServerQuery"


# --------------------------------------------------------------------------
# wire + reduce re-parenting
# --------------------------------------------------------------------------

class TestWire:
    def _stats_with_tree(self):
        st = QueryStats(num_docs_scanned=7)
        st.spans.append({"name": "ServerQuery", "ms": 5.0, "children": [
            {"name": "Kernel", "ms": 4.0, "kernel": "jnp"}]})
        st.decisions["pallas:pallas_kernel->jnp_kernel:pallas_distinct_agg"] = 2
        st.trace.append({"operator": "Kernel", "ms": 4.0})
        return st

    def test_binary_wire_round_trip(self):
        dt = DataTable.for_aggregation([1.0], self._stats_with_tree())
        back = DataTable.from_bytes(dt.to_bytes())
        assert back.stats.spans == dt.stats.spans
        assert back.stats.decisions == dt.stats.decisions
        assert back.stats.trace == dt.stats.trace

    def test_legacy_json_wire_round_trip(self):
        dt = DataTable.for_aggregation([1.0], self._stats_with_tree())
        back = DataTable.from_bytes(dt.to_json_bytes())
        assert back.stats.spans == dt.stats.spans
        assert back.stats.decisions == dt.stats.decisions

    def test_reduce_merges_and_broker_root_reparents(self):
        """_tag_trace attributes per instance BEFORE reduce; the broker
        root adopts every server tree under ScatterGather."""
        from pinot_tpu.broker.broker import _tag_trace
        from pinot_tpu.broker.reduce import BrokerReduceService

        dts = []
        for i in range(2):
            dt = DataTable.for_aggregation([float(i)],
                                           self._stats_with_tree())
            _tag_trace(dt, f"server_{i}")
            dts.append(dt)
        ctx = compile_query("SELECT sum(qty) FROM sales")
        table, stats, errors = BrokerReduceService().reduce(ctx, dts)
        assert len(stats.spans) == 2
        assert {s["instance"] for s in stats.spans} \
            == {"server_0", "server_1"}
        # decisions summed across servers
        assert stats.decisions[
            "pallas:pallas_kernel->jnp_kernel:pallas_distinct_agg"] == 4
        root = build_broker_root(
            {"COMPILATION": 1.0, "SCATTER_GATHER": 12.0, "REDUCE": 0.5},
            stats.spans, 14.0, admission_wait_ms=0.2)
        assert root["name"] == "BrokerQuery"
        sg = _find(root["children"], "ScatterGather")
        assert _names(sg["children"]) == ["ServerQuery", "ServerQuery"]
        adm = _find(root["children"], "Admission")
        assert adm["queueMs"] == 0.2

    def test_cluster_trace_end_to_end(self, segs, tmp_path):
        """Full wire path: broker root whose children account >= 90% of
        measured wall time, server trees instance-tagged, scheduler-queue
        attribution present, legacy entries preserved."""
        from pinot_tpu.spi.table import TableConfig
        from pinot_tpu.tools.cluster import EmbeddedCluster

        c = EmbeddedCluster(num_servers=2, data_dir=str(tmp_path))
        try:
            c.create_table(TableConfig("sales"), _schema())
            regions = ["east", "west"]
            for i in range(2):
                c.ingest_rows("sales_OFFLINE", _schema(), {
                    "region": [regions[j]
                               for j in RNG.integers(0, 2, 512)],
                    "qty": RNG.integers(1, 50, 512).tolist(),
                }, segment_name=f"sales_{i}")
            assert c.wait_for_ev_converged("sales_OFFLINE")
            best = 0.0
            for _ in range(5):
                resp = c.query(TRACED_SQL)
                assert not resp.exceptions, resp.exceptions
                ti = resp.to_dict()["traceInfo"]
                root = ti["spans"][0]
                assert root["name"] == "BrokerQuery"
                covered = sum(ch["ms"] for ch in root["children"])
                best = max(best, covered / root["ms"])
                if best >= 0.9:
                    break
            assert best >= 0.9, f"broker-root children cover {best:.2%}"
            sg = _find(root["children"], "ScatterGather")
            server_roots = [s for s in sg["children"]
                            if s["name"] == "ServerQuery"]
            assert server_roots
            assert all("instance" in s for s in server_roots)
            # scheduler-level queue attribution inside each server tree
            for s in server_roots:
                q = _find(s["children"], "SchedulerQueue")
                assert q is not None and "queueMs" in q
            # legacy flat entries preserved, instance-tagged
            entries = ti["entries"]
            assert entries and all("operator" in e and "ms" in e
                                   for e in entries)
            assert all("instance" in e for e in entries)
            # scheduler wait totals surfaced for ops
            snap = list(c.servers.values())[0].scheduler.stats_snapshot()
            assert "queueWaitMsTotal" in snap
            # untraced responses stay untraced
            resp2 = c.query(GROUP_SQL)
            assert "traceInfo" not in resp2.to_dict()
        finally:
            c.shutdown()


# --------------------------------------------------------------------------
# exception edges + slow-query log + registry
# --------------------------------------------------------------------------

class TestRegistry:
    def test_exception_edge_closes_spans(self, segs, monkeypatch):
        """A query dying mid-execution still produces a CLOSED tree (the
        registry's completed entry carries the error; the slow log keeps
        the tree)."""
        from pinot_tpu.engine import executor as executor_mod

        cfg = PinotConfiguration(
            {CommonConstants.SLOW_THRESHOLD_MS_KEY: "0.0001"},
            use_env=False)
        ex = ServerQueryExecutor(use_device=False, config=cfg)

        def boom(*a, **k):
            raise RuntimeError("kernel exploded")

        monkeypatch.setattr(executor_mod.host_engine,
                            "host_group_by_segment", boom)
        with pytest.raises(RuntimeError):
            ex.execute(compile_query(GROUP_SQL), segs)
        snap = ex.queries.snapshot()
        assert snap["running"] == []
        done = snap["completed"][-1]
        assert "kernel exploded" in done["error"]
        slow = snap["slow"][-1]
        root = slow["spans"][0]
        assert root["name"] == "ServerQuery"
        assert root["ms"] >= 0  # closed: wall time measured

    def test_slow_log_retains_tree_when_untraced(self, segs):
        """The slow log keeps the FULL span tree for over-threshold
        queries even though the response ships untraced."""
        cfg = PinotConfiguration(
            {CommonConstants.SLOW_THRESHOLD_MS_KEY: "0.0001"},
            use_env=False)
        ex = ServerQueryExecutor(config=cfg)
        rt, stats = ex.execute(compile_query(GROUP_SQL), segs)
        # response payload: untraced (no spans shipped)
        assert stats.spans == []
        assert stats.trace == []
        slow = ex.queries.snapshot()["slow"][-1]
        assert slow["spans"][0]["name"] == "ServerQuery"
        assert _names(slow["spans"][0]["children"])

    def test_registry_ring_and_request_id(self, segs):
        ex = ServerQueryExecutor()
        sql = GROUP_SQL + " OPTION(requestId=dash42)"
        ex.execute(compile_query(sql), segs)
        done = ex.queries.snapshot()["completed"][-1]
        assert done["requestId"] == "dash42"
        assert done["table"] == "sales"
        assert done["elapsedMs"] > 0


# --------------------------------------------------------------------------
# decision ledger
# --------------------------------------------------------------------------

class TestDecisionLedger:
    def test_q1_shape_expression_agg_decline_is_stable(self, st_segs):
        """The Q1.x shape: an expression aggregation has no pre-agg pair,
        so the star-tree declines with a stable reason — twice."""
        ex = ServerQueryExecutor()
        ctx = compile_query("SELECT region, sum(qty * price) FROM sales_st "
                            "GROUP BY region ORDER BY region")
        keys = []
        for _ in range(2):
            rt, stats = ex.execute(ctx, st_segs)
            keys.append({k for k in stats.decisions
                         if k.startswith("startree:")})
        assert keys[0] == keys[1]
        assert any("startree_expression_agg_no_pair" in k
                   for k in keys[0]), keys

    def test_q3_shape_off_split_order_decline(self, st_segs):
        """The Q3.x shape: a group column off the split order declines
        the tree with the off-split-order reason."""
        ex = ServerQueryExecutor()
        rt, stats = ex.execute(
            compile_query("SELECT year, sum(qty) FROM sales_st "
                          "GROUP BY year ORDER BY year"), st_segs)
        assert any("startree_group_off_split_order" in k
                   for k in stats.decisions), stats.decisions

    def test_pallas_declines_are_classified(self, segs):
        """Every pallas decline carries a non-unknown reason code (the
        bench loud-fails otherwise)."""
        ex = ServerQueryExecutor(use_pallas=True)
        rt, stats = ex.execute(
            compile_query("SELECT distinctcount(region) FROM sales"), segs)
        pallas = {k: v for k, v in stats.decisions.items()
                  if parse_decision_key(k)[0] == "pallas"}
        assert pallas, stats.decisions
        assert all(parse_decision_key(k)[3] != "unknown" for k in pallas)
        assert any("pallas_distinct_agg" in k for k in pallas), pallas

    def test_residency_spill_decision(self, segs):
        """An over-budget unsliceable working set records WHY it fell to
        the host engine."""
        ex = ServerQueryExecutor(hbm_budget_bytes=1)
        rt, stats = ex.execute(compile_query(GROUP_SQL), segs)
        spill = [k for k in stats.decisions
                 if parse_decision_key(k)[0] == "residency"]
        assert spill, stats.decisions
        assert parse_decision_key(spill[0])[3] \
            == "single_segment_over_budget"

    def test_decisions_merge_and_response_surface(self, segs):
        """Decisions sum at merge and surface on the broker response."""
        a = QueryStats()
        b = QueryStats()
        a.decisions["plan:device_kernel->host_engine:mutable_segment"] = 1
        b.decisions["plan:device_kernel->host_engine:mutable_segment"] = 2
        a.merge(b)
        assert a.decisions[
            "plan:device_kernel->host_engine:mutable_segment"] == 3
        from pinot_tpu.common.response import BrokerResponse

        resp = BrokerResponse(stats=a)
        assert resp.to_dict()["decisions"] == a.decisions

    def test_classifier_never_unknown_for_real_messages(self):
        for msg in (
                "mutable segment -> host path",
                "group key space 4194304+ exceeds device limit",
                "aggregation percentile not device-supported grouped",
                "transform regexpextract -> host path",
                "lut with too many runs",
                "int expr bound exceeds i32",
                "some brand new decline nobody classified yet"):
            assert classify_decline(msg) != "unknown", msg
        # digits are stripped so runtime values never fork the code
        assert classify_decline("group key space 123+ exceeds device limit") \
            == classify_decline("group key space 999+ exceeds device limit")

    def test_ledger_histogram_and_metrics(self):
        from pinot_tpu.spi.metrics import MetricsRegistry

        led = DecisionLedger()
        reg = MetricsRegistry(role="server")
        led.bind_metrics(reg)
        led.record("pallas", "jnp_kernel", "pallas_kernel",
                   "pallas_distinct_agg")
        led.record("pallas", "jnp_kernel", "pallas_kernel",
                   "pallas_distinct_agg")
        snap = led.snapshot()
        assert snap[
            "pallas:pallas_kernel->jnp_kernel:pallas_distinct_agg"] == 2
        assert led.reason_histogram()["pallas_distinct_agg"] == 2
        text = reg.export_prometheus()
        # ONE labeled family, not N name-mangled counters: every decline
        # cell is a (point, reason) label pair under one TYPE header
        assert "# TYPE pinot_server_decision_declined_total counter" in text
        assert ('pinot_server_decision_declined_total{point="pallas",'
                'reason="pallas_distinct_agg"} 2') in text
        # delta: the bench's per-suite view
        mark = led.snapshot()
        led.record("plan", "host_engine", "device_kernel",
                   "mutable_segment")
        delta = led.delta(mark)
        assert list(delta.values()) == [1]


# --------------------------------------------------------------------------
# recorder unit behavior
# --------------------------------------------------------------------------

class TestRecorder:
    def test_context_manager_closes_on_raise(self):
        rec = SpanRecorder()
        with pytest.raises(ValueError):
            with rec.span("outer"):
                with rec.span("inner"):
                    raise ValueError("boom")
        assert rec.open_depth == 0
        assert rec.spans[0]["name"] == "outer"
        assert rec.spans[0]["children"][0]["name"] == "inner"

    def test_abandoned_child_swept_by_parent_close(self):
        rec = SpanRecorder()
        outer = rec.span_begin("outer")
        rec.span_begin("abandoned")
        rec.span_end(outer)
        assert rec.open_depth == 0
        assert _names(rec.spans[0]["children"]) == ["abandoned"]

    def test_double_close_is_noop(self):
        rec = SpanRecorder()
        sp = rec.span_begin("x")
        rec.span_end(sp)
        assert rec.span_end(sp) is None
        assert len(rec.spans) == 1


# --------------------------------------------------------------------------
# trace-while-querying hammer
# --------------------------------------------------------------------------

def test_trace_hammer(segs):
    """4 threads, traced + untraced queries interleaved on one sharded
    executor: results stay bit-identical, every traced tree is closed and
    rooted, untraced stats stay span-free."""
    ex = ShardedQueryExecutor()
    oracle, _ = ex.execute(compile_query(GROUP_SQL), segs)
    errors = []

    def pump(i):
        try:
            for j in range(6):
                traced = (i + j) % 2 == 0
                ctx = compile_query(TRACED_SQL if traced else GROUP_SQL)
                rt, stats = ex.execute(ctx, segs)
                assert rt.rows == oracle.rows
                if traced:
                    assert stats.spans[0]["name"] == "ServerQuery"
                    rec = getattr(stats, "_recorder", None)
                    assert rec is None or rec.open_depth == 0
                else:
                    assert stats.spans == []
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=pump, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errors, errors


def test_spans_json_serializable(segs):
    ex = ShardedQueryExecutor()
    rt, stats = ex.execute(compile_query(TRACED_SQL), segs)
    json.dumps(stats.spans)
    json.dumps(stats.decisions)
