"""Upsert engine: key->location semantics, valid-doc masking, and the
full-cluster upsert flow (ref: PartitionUpsertMetadataManager /
UpsertTableIntegrationTest)."""

import numpy as np
import pandas as pd
import pytest

from pinot_tpu.common.tracing import flatten_spans
from pinot_tpu.engine import ServerQueryExecutor
from pinot_tpu.ingestion import MemoryStream
from pinot_tpu.query import compile_query
from pinot_tpu.segment import MutableSegment, SegmentBuilder, load_segment
from pinot_tpu.segment.upsert import (
    PartitionUpsertMetadataManager,
    attach_valid_docs,
)
from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.spi.table import (
    SegmentsValidationConfig,
    StreamIngestionConfig,
    TableConfig,
    TableType,
    UpsertConfig,
    UpsertMode,
)
from pinot_tpu.tools import EmbeddedCluster


def make_schema():
    return Schema("users", [
        FieldSpec("uid", DataType.STRING),
        FieldSpec("status", DataType.STRING),
        FieldSpec("score", DataType.LONG, FieldType.METRIC),
        FieldSpec("ts", DataType.LONG, FieldType.DATE_TIME),
    ], primary_key_columns=["uid"])


def build_seg(tmp_path, name, rows):
    cols = {k: [r[k] for r in rows] for k in rows[0]}
    SegmentBuilder(make_schema(), name).build(cols, str(tmp_path))
    return load_segment(f"{tmp_path}/{name}")


class TestPartitionUpsertManager:
    def test_newer_segment_invalidates_older(self, tmp_path):
        pm = PartitionUpsertMetadataManager(["uid"], "ts")
        s1 = build_seg(tmp_path, "s1", [
            {"uid": "a", "status": "new", "score": 1, "ts": 100},
            {"uid": "b", "status": "new", "score": 2, "ts": 100},
        ])
        v1 = pm.add_segment(s1)
        s2 = build_seg(tmp_path, "s2", [
            {"uid": "a", "status": "upd", "score": 10, "ts": 200},
        ])
        v2 = pm.add_segment(s2)
        assert list(v1) == [False, True]   # 'a' superseded
        assert list(v2) == [True]
        assert pm.num_keys == 2

    def test_older_arrival_is_dropped(self, tmp_path):
        pm = PartitionUpsertMetadataManager(["uid"], "ts")
        s1 = build_seg(tmp_path, "s1", [
            {"uid": "a", "status": "new", "score": 1, "ts": 300}])
        v1 = pm.add_segment(s1)
        s2 = build_seg(tmp_path, "s2", [
            {"uid": "a", "status": "old", "score": 0, "ts": 100}])
        v2 = pm.add_segment(s2)
        assert list(v1) == [True]
        assert list(v2) == [False]  # late, older record never visible

    def test_query_sees_latest_only(self, tmp_path):
        pm = PartitionUpsertMetadataManager(["uid"], "ts")
        s1 = build_seg(tmp_path, "s1", [
            {"uid": "a", "status": "new", "score": 1, "ts": 100},
            {"uid": "b", "status": "new", "score": 2, "ts": 100},
        ])
        s2 = build_seg(tmp_path, "s2", [
            {"uid": "a", "status": "upd", "score": 10, "ts": 200},
        ])
        attach_valid_docs(s1, pm.add_segment(s1))
        attach_valid_docs(s2, pm.add_segment(s2))
        ex = ServerQueryExecutor()
        t, _ = ex.execute(compile_query(
            "SELECT count(*), sum(score) FROM users"), [s1, s2])
        assert t.rows[0] == [2, 12.0]  # a=10 (latest), b=2
        t2, _ = ex.execute(compile_query(
            "SELECT status, count(*) FROM users GROUP BY status ORDER BY status"),
            [s1, s2])
        assert [(r[0], r[1]) for r in t2.rows] == [("new", 1), ("upd", 1)]

    def test_remove_segment_clears_keys(self, tmp_path):
        pm = PartitionUpsertMetadataManager(["uid"], "ts")
        s1 = build_seg(tmp_path, "s1", [
            {"uid": "a", "status": "x", "score": 1, "ts": 100}])
        pm.add_segment(s1)
        pm.remove_segment("s1")
        assert pm.num_keys == 0


class TestUpsertCluster:
    def test_realtime_upsert_e2e(self, tmp_path):
        """Stream the same keys repeatedly: queries must see exactly one row
        per key with the latest value, across consuming + sealed segments."""
        MemoryStream.create("upsert_topic", 1)
        cluster = EmbeddedCluster(num_servers=1, data_dir=str(tmp_path))
        schema = make_schema()
        cfg = TableConfig(
            "users", TableType.REALTIME,
            validation_config=SegmentsValidationConfig(time_column_name="ts"),
            stream_config=StreamIngestionConfig(
                stream_type="memory", topic="upsert_topic",
                segment_flush_threshold_rows=60),
            upsert_config=UpsertConfig(mode=UpsertMode.FULL))
        cluster.create_table(cfg, schema)

        stream = MemoryStream.get("upsert_topic")
        rng = np.random.default_rng(3)
        latest = {}
        ts = 1000
        for _ in range(150):
            uid = f"u{int(rng.integers(0, 20))}"
            score = int(rng.integers(0, 100))
            ts += 1
            latest[uid] = (score, ts)
            stream.produce({"uid": uid, "status": "s", "score": score,
                            "ts": ts}, partition=0)

        assert cluster.wait_for_docs("users", len(latest), timeout_s=20)
        import time
        deadline = time.time() + 10
        while time.time() < deadline:
            rows = cluster.query_rows("SELECT count(*), sum(score) FROM users")
            if rows[0][0] == len(latest) and \
                    rows[0][1] == float(sum(s for s, _ in latest.values())):
                break
            time.sleep(0.1)
        assert rows[0][0] == len(latest), (rows, len(latest))
        assert rows[0][1] == float(sum(s for s, _ in latest.values()))

        # per-key check through the broker
        rows = cluster.query_rows(
            "SELECT uid, max(score) FROM users GROUP BY uid ORDER BY uid LIMIT 100")
        got = {r[0]: r[1] for r in rows}
        assert got == {k: float(s) for k, (s, _) in latest.items()}
        cluster.shutdown()
        MemoryStream.delete("upsert_topic")


class TestUpsertDevicePath:
    def test_device_serves_upsert_with_parity(self, tmp_path):
        """Sealed upsert segments ride the device kernels with the
        valid-doc snapshot ANDed into the filter (plan.py 'validdocs')."""
        rng = np.random.default_rng(13)
        n = 3000
        rows = [{"uid": f"u{i % 900}", "status": ["a", "b"][i % 2],
                 "score": int(rng.integers(0, 100)), "ts": i}
                for i in range(n)]
        seg = build_seg(tmp_path, "up_0", rows)
        pm = PartitionUpsertMetadataManager(["uid"], "ts")
        attach_valid_docs(seg, pm.add_segment(seg))
        assert seg.valid_doc_ids is not None

        dev = ServerQueryExecutor(use_device=True)
        host = ServerQueryExecutor(use_device=False)
        for sql in ("SELECT count(*) FROM users",
                    "SELECT sum(score) FROM users WHERE status = 'a'",
                    "SELECT status, count(*), max(score) FROM users "
                    "GROUP BY status ORDER BY status"):
            traced = compile_query(sql + " OPTION(trace=true)")
            drt, dstats = dev.execute(traced, [seg])
            hrt, _ = host.execute(compile_query(sql), [seg])
            assert drt.rows == hrt.rows, sql
            # the DEVICE kernels must have served (a silent PlanError
            # fallback to host would make this parity vacuous)
            flat = flatten_spans(dstats.spans)
            paths = {t.get("path") for t in flat}
            assert "device" in paths, (sql, flat)
        # only the live doc per key is visible
        t, _ = dev.execute(compile_query("SELECT count(*) FROM users"),
                           [seg])
        assert t.rows[0][0] == 900

    def test_snapshot_tracks_new_invalidation(self, tmp_path):
        """A doc invalidated between two queries disappears from the
        second (plans snapshot the bitmap per execution)."""
        rows = [{"uid": f"u{i}", "status": "a", "score": i, "ts": i}
                for i in range(100)]
        seg = build_seg(tmp_path, "up_1", rows)
        pm = PartitionUpsertMetadataManager(["uid"], "ts")
        attach_valid_docs(seg, pm.add_segment(seg))
        dev = ServerQueryExecutor(use_device=True)
        q = compile_query("SELECT count(*) FROM users")
        assert dev.execute(q, [seg])[0].rows[0][0] == 100
        # a newer segment claims u5: the old doc goes invalid in place
        seg2 = build_seg(tmp_path, "up_2",
                         [{"uid": "u5", "status": "a", "score": 1,
                           "ts": 1000}])
        attach_valid_docs(seg2, pm.add_segment(seg2))
        assert dev.execute(q, [seg])[0].rows[0][0] == 99


class TestMutableUpsertDevicePath:
    """PR 17: CONSUMING segments ride the device kernels too — the
    watermark snapshot captures the upsert bitmap at the same instant as
    the doc count, and the kernel's validdocs placeholder is filled from
    that snapshot (mutable_staging._valid_locked)."""

    pytestmark = pytest.mark.realtime_tier

    def _consuming(self, n_rows, n_keys, seed=7):
        from pinot_tpu.server.data_manager import _LiveValidDocs

        seg = MutableSegment(make_schema(), "mut_up_0", capacity=65536)
        pm = PartitionUpsertMetadataManager(["uid"], "ts")
        attach_valid_docs(seg, _LiveValidDocs(pm, seg.segment_name))
        rng = np.random.default_rng(seed)
        latest = {}
        for i in range(n_rows):
            row = {"uid": f"u{int(rng.integers(0, n_keys))}",
                   "status": ["a", "b"][int(rng.integers(0, 2))],
                   "score": int(rng.integers(0, 100)), "ts": i}
            seg.index(row)
            pm.add_record(seg.segment_name, seg.num_docs - 1,
                          pm.key_of_row(row), row["ts"])
            latest[row["uid"]] = row
        return seg, pm, latest

    def test_consuming_upsert_device_host_parity(self):
        """Writes quiesced: device and host must agree bit-for-bit on a
        consuming upsert segment, and the device rung must actually have
        served (a silent host fallback would make parity vacuous)."""
        seg, _, latest = self._consuming(2000, 300)
        dev = ServerQueryExecutor(use_device=True)
        host = ServerQueryExecutor(use_device=False)
        for sql in ("SELECT status, count(*), sum(score), max(score) "
                    "FROM users GROUP BY status",
                    "SELECT uid, max(ts) FROM users "
                    "WHERE status = 'a' GROUP BY uid LIMIT 500"):
            drt, dstats = dev.execute(compile_query(sql), [seg])
            hrt, _ = host.execute(compile_query(sql), [seg])
            assert sorted(map(repr, drt.rows)) == \
                sorted(map(repr, hrt.rows)), sql
            assert dstats.group_by_rung == "mutable_device", \
                (sql, dstats.group_by_rung)
        # exactly one live doc per key survives the mask
        t, _ = dev.execute(compile_query("SELECT count(*) FROM users"),
                           [seg])
        assert t.rows[0][0] == len(latest)

    def test_invalidation_between_queries_same_watermark(self):
        """A key re-ingested between two queries flips its old doc's bit:
        the version-keyed device mask cache must NOT serve the stale
        bitmap (same watermark, different validdocs)."""
        from pinot_tpu.server.data_manager import _LiveValidDocs

        seg = MutableSegment(make_schema(), "mut_up_1", capacity=65536)
        pm = PartitionUpsertMetadataManager(["uid"], "ts")
        attach_valid_docs(seg, _LiveValidDocs(pm, seg.segment_name))
        for i in range(50):  # 50 unique keys, no dups yet
            row = {"uid": f"u{i}", "status": "a", "score": i, "ts": i}
            seg.index(row)
            pm.add_record(seg.segment_name, i, pm.key_of_row(row), i)
        dev = ServerQueryExecutor(use_device=True)
        q = compile_query("SELECT count(*), sum(score) FROM users")
        t0, _ = dev.execute(q, [seg])
        assert t0.rows[0][0] == 50
        # newer record for u5: old doc invalidated, count stays 50
        row = {"uid": "u5", "status": "a", "score": 1, "ts": 10_000}
        seg.index(row)
        pm.add_record(seg.segment_name, seg.num_docs - 1,
                      pm.key_of_row(row), row["ts"])
        t1, _ = dev.execute(q, [seg])
        host = ServerQueryExecutor(use_device=False)
        t1h, _ = host.execute(q, [seg])
        assert t1.rows == t1h.rows
        assert t1.rows[0][0] == 50


def test_plan_cache_respects_late_bitmap_attach(tmp_path):
    """A valid-doc bitmap attached AFTER a query cached the plan must
    invalidate it (the no-validdocs plan would count invalidated docs)."""
    rows = [{"uid": f"u{i % 50}", "status": "a", "score": i, "ts": i}
            for i in range(200)]
    seg = build_seg(tmp_path, "pc_0", rows)
    ex = ServerQueryExecutor(use_device=True)
    q = compile_query("SELECT count(*) FROM users")
    assert ex.execute(q, [seg])[0].rows[0][0] == 200  # plan cached, no bitmap
    pm = PartitionUpsertMetadataManager(["uid"], "ts")
    attach_valid_docs(seg, pm.add_segment(seg))
    assert ex.execute(q, [seg])[0].rows[0][0] == 50  # fresh plan sees it
