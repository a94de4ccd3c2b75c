"""gRPC query transport: broker <-> server over the network.

Re-design of the reference's query RPC layer (Netty + thrift
``InstanceRequest`` at ``transport/QueryServer.java:46`` /
``ServerChannels.java:55``, and the gRPC alternative
``transport/grpc/GrpcQueryServer.java:45`` with
``pinot-common/src/main/proto/server.proto``): a single unary method
carrying a JSON-framed InstanceRequest (compiled QueryContext + table +
segment list) and returning DataTable bytes. Generic bytes-in/bytes-out
method handlers keep the wire layer free of generated stubs (no
grpcio-tools in the image); the payload framing is the versioned contract.

Multi-host note: this is the DCN leg of the design (SURVEY.md §2.12) —
broker scatter/gather rides gRPC across hosts, while the intra-host
multi-chip combine rides ICI collectives inside the sharded executor.
"""

from __future__ import annotations

import json
import logging

from concurrent import futures
from typing import List, Optional

import grpc

from pinot_tpu.common.datatable import DataTable
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.serde import context_from_dict, context_to_dict

log = logging.getLogger(__name__)

_SERVICE = "pinot_tpu.QueryServer"
_METHOD_EXECUTE = f"/{_SERVICE}/Execute"
_METHOD_EXECUTE_STREAMING = f"/{_SERVICE}/ExecuteStreaming"


def _encode_request(ctx: QueryContext, table: str,
                    segments: Optional[List[str]]) -> bytes:
    return json.dumps({
        "version": 1,
        "context": context_to_dict(ctx),
        "table": table,
        "segments": segments,
    }).encode("utf-8")


def _decode_request(raw: bytes):
    d = json.loads(raw.decode("utf-8"))
    return context_from_dict(d["context"]), d["table"], d.get("segments")


def _to_wire(dt: DataTable) -> bytes:
    """``to_bytes``; a traced table's framing is timed and lands in its
    tree as the root's last child, ``Serialize`` (the stats section is
    framed after the payload, so the span rides the bytes it measured)."""
    if not dt.stats.spans:
        return dt.to_bytes()
    import time

    from pinot_tpu.common.tracing import attach_root_child

    t0, c0 = time.perf_counter(), time.thread_time()
    payload = dt.payload_buffers()
    nbytes = sum(memoryview(p).nbytes for p in payload)
    attach_root_child(dt.stats, "Serialize",
                      wall_ms=(time.perf_counter() - t0) * 1e3,
                      cpu_ms=(time.thread_time() - c0) * 1e3, bytes=nbytes)
    return b"".join(dt.to_buffers(payload))


def _from_wire(raw: bytes, traced: bool) -> DataTable:
    """``from_bytes``; for a query that asked for its trace the decode is
    timed and rides beside the server's tree as a ``Deserialize`` span on
    the wall clock (the broker lays both under ScatterGather)."""
    if not traced:
        return DataTable.from_bytes(raw)
    import threading
    import time

    epoch_ms, t0, c0 = time.time() * 1e3, time.perf_counter(), \
        time.thread_time()
    dt = DataTable.from_bytes(raw)
    if dt.stats.spans:
        dt.stats.spans.append({
            "name": "Deserialize",
            "ms": round((time.perf_counter() - t0) * 1e3, 3),
            "startMs": 0.0, "startEpochMs": round(epoch_ms, 3),
            "cpuMs": round((time.thread_time() - c0) * 1e3, 3),
            "thread": threading.current_thread().name,
            "bytes": len(raw)})
    return dt


class GrpcQueryServer:
    """Network front of one ServerInstance
    (ref: GrpcQueryServer.java:45 submit:84). ``Execute`` is the unary
    whole-result method; ``ExecuteStreaming`` streams per-segment blocks
    for selection queries (ref: the streaming operators under
    ``operator/streaming/*`` feeding GrpcQueryServer) so the broker can
    short-circuit LIMIT without waiting for every segment."""

    def __init__(self, server_instance, port: int = 0, max_workers: int = 8):
        self._instance = server_instance
        self._grpc = grpc.server(
            futures.ThreadPoolExecutor(max_workers=max_workers))
        handler = grpc.method_handlers_generic_handler(_SERVICE, {
            "Execute": grpc.unary_unary_rpc_method_handler(
                self._execute,
                request_deserializer=None,
                response_serializer=None),
            "ExecuteStreaming": grpc.unary_stream_rpc_method_handler(
                self._execute_streaming,
                request_deserializer=None,
                response_serializer=None),
        })
        self._grpc.add_generic_rpc_handlers((handler,))
        self.port = self._grpc.add_insecure_port(f"[::]:{port}")

    def _execute(self, request: bytes, context) -> bytes:
        try:
            ctx, table, segments = _decode_request(request)
            table_result = self._instance.execute_query(ctx, table, segments)
        except Exception as e:  # errors travel in the DataTable
            log.debug("grpc execute failed", exc_info=True)
            table_result = DataTable.for_exception(repr(e))
        return _to_wire(table_result)

    def _execute_streaming(self, request: bytes, context):
        """Yield one DataTable per block: selection queries stream a block
        PER SEGMENT (each block carries its own stats — unlike the
        reference's trailing-metadata framing, StreamingResponseUtils);
        other query shapes degrade to a single block (their combine is a
        reduction — there is nothing incremental to ship)."""
        try:
            ctx, table, segments = _decode_request(request)
            if not ctx.is_selection:
                yield _to_wire(self._instance.execute_query(
                    ctx, table, segments))
                return
            for block in self._instance.execute_query_streaming(
                    ctx, table, segments):
                yield block.to_bytes()
        except Exception as e:  # noqa: BLE001 — errors travel in-band
            log.debug("grpc streaming execute failed", exc_info=True)
            yield DataTable.for_exception(repr(e)).to_bytes()

    def start(self) -> None:
        self._grpc.start()

    def stop(self, grace: float = 5.0) -> None:
        self._grpc.stop(grace)


class GrpcServerStub:
    """Broker-side remote server handle — same ``execute_query`` surface as
    an in-process ServerInstance, so it registers with
    BrokerRequestHandler.register_server unchanged
    (ref: ServerChannels per-server connection + GrpcQueryClient.java:27)."""

    def __init__(self, address: str, timeout_s: float = 60.0):
        self.address = address
        self._channel = grpc.insecure_channel(address)
        self._call = self._channel.unary_unary(
            _METHOD_EXECUTE, request_serializer=None,
            response_deserializer=None)
        self._call_streaming = self._channel.unary_stream(
            _METHOD_EXECUTE_STREAMING, request_serializer=None,
            response_deserializer=None)
        self.timeout_s = timeout_s

    def execute_query(self, ctx: QueryContext, table: str,
                      segments: Optional[List[str]] = None) -> DataTable:
        try:
            raw = self._call(_encode_request(ctx, table, segments),
                             timeout=self.timeout_s)
            return _from_wire(raw, ctx.trace_enabled)
        except grpc.RpcError as e:
            return DataTable.for_exception(
                f"rpc to {self.address} failed: {e.code().name}")

    def execute_query_streaming(self, ctx: QueryContext, table: str,
                                segments: Optional[List[str]] = None):
        """Yield DataTable blocks as the server produces them
        (ref: GrpcQueryClient.submit returning a response iterator)."""
        try:
            for raw in self._call_streaming(
                    _encode_request(ctx, table, segments),
                    timeout=self.timeout_s):
                yield DataTable.from_bytes(raw)
        except grpc.RpcError as e:
            yield DataTable.for_exception(
                f"rpc to {self.address} failed: {e.code().name}")

    def close(self) -> None:
        self._channel.close()
