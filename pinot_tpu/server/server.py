"""Server instance: segment hosting + instance-level query execution.

Re-design of ``pinot-server/.../starter/helix/BaseServerStarter.java:117`` +
``ServerInstance.java:53`` + the state-model transitions
(``SegmentOnlineOfflineStateModelFactory.java:53,76``): the server watches
the cluster store's IdealState, reconciles its assigned segments
(OFFLINE->ONLINE = load; OFFLINE->CONSUMING = start stream consumer;
CONSUMING->ONLINE = seal/swap), reports ExternalView states, and answers
instance query requests through the scheduler -> executor pipeline
(ref: InstanceRequestHandler.channelRead0:90 ->
QueryScheduler.processQueryAndSerialize:147 ->
ServerQueryExecutorV1Impl.processQuery:119).
"""

from __future__ import annotations

import logging
import os
import threading
import time

from typing import Any, Dict, List, Optional

from pinot_tpu.common.datatable import DataTable
from pinot_tpu.controller.state import (
    CONSUMING,
    ONLINE,
    ClusterStateStore,
    InstanceInfo,
)
from pinot_tpu.engine.executor import ServerQueryExecutor
from pinot_tpu.ingestion.realtime import (
    ConsumerState,
    RealtimeSegmentDataManager,
    SegmentCompletionProtocol,
)
from pinot_tpu.ingestion.stream import StreamOffset
from pinot_tpu.parallel.executor import ShardedQueryExecutor
from pinot_tpu.query.context import QueryContext
from pinot_tpu.server.data_manager import (
    InstanceDataManager,
    RealtimeTableDataManager,
)
from pinot_tpu.server.scheduler import QueryScheduler, make_scheduler
from pinot_tpu.spi.table import TableType, table_type_from_name

log = logging.getLogger(__name__)


class ServerInstance:
    """One query server (ref: ServerInstance.java:53). In-process transport:
    the broker calls ``execute_query`` directly (the embedded-cluster mode,
    ref: ClusterTest single-JVM multi-instance); the gRPC service wraps the
    same entry point for multi-process deployments."""

    def __init__(self, instance_id: str, store: ClusterStateStore,
                 completion_protocol: Optional[SegmentCompletionProtocol] = None,
                 executor: Optional[ServerQueryExecutor] = None,
                 scheduler: Optional[QueryScheduler] = None,
                 segment_dir: str = "/tmp/pinot_tpu_server",
                 consumer_tick_s: float = 0.02,
                 config=None):
        from pinot_tpu.spi.metrics import MetricsRegistry

        self.instance_id = instance_id
        self.store = store
        self.completion_protocol = completion_protocol
        # the sharded executor over this process's devices IS the serving
        # path: one stacked launch per multi-segment scan (a 1x1 mesh on a
        # one-chip host), star-tree / index / single-segment queries
        # handed back to the per-segment ladder it subclasses
        self.executor = executor or ShardedQueryExecutor(config=config)
        # runner pool sized by pinot.server.query.runner.threads (pqr);
        # policy from pinot.server.query.scheduler.policy — default SEWF
        # (shortest-expected-work-first with anti-starvation aging)
        from pinot_tpu.spi.config import CommonConstants

        policy = (config.get_str(CommonConstants.SCHEDULER_POLICY_KEY,
                                 CommonConstants.DEFAULT_SCHEDULER_POLICY)
                  if config is not None
                  else CommonConstants.DEFAULT_SCHEDULER_POLICY)
        self.scheduler = scheduler or make_scheduler(policy, config=config)
        self.metrics = MetricsRegistry(role="server")
        # segment lifecycle -> HBM residency: adds prefetch, removals evict
        self.data_manager = InstanceDataManager(listener=self)
        residency = getattr(self.executor, "residency", None)
        # gaps with requests in flight and none done (/debug/scheduler)
        from pinot_tpu.server.stall import StallWatch

        self.stall_watch = StallWatch(residency=residency)
        if residency is not None:
            residency.bind_metrics(self.metrics)
        # launch-coalescing meters/gauges (sharded executors only)
        launcher = getattr(self.executor, "launcher", None)
        if launcher is not None:
            launcher.bind_metrics(self.metrics)
        # admission-gate meters/gauges (server/admission.py)
        admission = getattr(self.executor, "admission", None)
        if admission is not None:
            admission.bind_metrics(self.metrics)
        # path-decision ledger -> /metrics: every decline of a faster
        # rung becomes a cell of the labeled decision_declined_total family
        from pinot_tpu.common.tracing import LEDGER

        LEDGER.bind_metrics(self.metrics)
        # continuous telemetry: export the histogram/SLO families on this
        # server's /metrics, give the flight recorder this instance's
        # scheduler/memory state, and ring-track the scheduler queue depth
        from pinot_tpu.common.telemetry import TELEMETRY

        TELEMETRY.configure(config)
        self.metrics.bind_telemetry(TELEMETRY)
        TELEMETRY.recorder.register_provider("scheduler",
                                             self.scheduler_debug)
        TELEMETRY.track_gauge(
            f"scheduler.queue_depth.{instance_id}",
            lambda: float(self.scheduler.queue_depth()))
        self.segment_dir = segment_dir
        self.consumer_tick_s = consumer_tick_s
        self._started = False
        self._queries_enabled = False
        self._reconcile_lock = threading.RLock()
        self._upsert_managers: Dict[str, object] = {}  # guarded-by: _reconcile_lock

    # -- lifecycle (ref: BaseServerStarter.start) ---------------------------
    def start(self, heartbeat_interval_s: float = 0.0) -> None:
        from pinot_tpu.spi.environment import get_environment_provider

        # a RESTART must not wipe operator-set tenant tags (PUT updateTags):
        # re-registration carries the stored tags forward
        prior = self.store.get_instance(self.instance_id)
        self.store.register_instance(
            InstanceInfo(self.instance_id, "SERVER", port=0,
                         tags=(prior.tags if prior is not None
                               else ["DefaultTenant"]),
                         failure_domain=get_environment_provider()
                         .failure_domain()))
        # replay current assignments, then watch for changes (the Helix
        # participant registration + state-transition replay)
        self.store.watch("idealstate/", self._on_ideal_state_change)
        self.store.watch("reloadrequests/", self._on_reload_request)
        for path in self.store.children("idealstate"):
            table = path.split("/", 1)[1]
            self._reconcile_table(table)
        self._started = True
        self._queries_enabled = True
        self.stall_watch.start()
        if heartbeat_interval_s > 0:
            # the ephemeral-znode keepalive: the controller's liveness
            # check marks us dead when these stop
            self._hb_stop = threading.Event()

            def beat():
                while not self._hb_stop.wait(heartbeat_interval_s):
                    try:
                        self.store.touch_instance(self.instance_id)
                    except Exception:
                        log.exception("[%s] heartbeat failed",
                                      self.instance_id)

            self.store.touch_instance(self.instance_id)
            self._hb_thread = threading.Thread(
                target=beat, daemon=True,
                name=f"heartbeat-{self.instance_id}")
            self._hb_thread.start()

    def shutdown(self) -> None:
        """Ref: shutdown = disable queries, drain, unregister."""
        self._queries_enabled = False
        hb = getattr(self, "_hb_stop", None)
        if hb is not None:
            hb.set()
            # join BEFORE marking dead: an in-flight touch_instance would
            # resurrect the instance (touch sets alive=True)
            self._hb_thread.join(timeout=5)
        self.scheduler.shutdown()
        self.stall_watch.stop()
        self.data_manager.shutdown()
        close = getattr(self.executor, "close", None)
        if close is not None:
            close()
        residency = getattr(self.executor, "residency", None)
        if residency is not None:
            residency.close()
        self.store.set_instance_alive(self.instance_id, False)

    # -- segment lifecycle -> HBM residency (data-manager listener) ----------
    def segment_added(self, table: str, segment) -> None:
        """Prefetch hook: stage new/reloaded immutable segments in the
        background so the table's first query pays no H2D (residency skips
        mutable segments and stops at the budget instead of evicting).
        When the added segment is the sealed replacement of a consuming
        one, the mutable resident's chunks are dead weight — evict them
        (in-flight queries keep their snapshot via python refs)."""
        residency = getattr(self.executor, "residency", None)
        if residency is None:
            return
        if not getattr(segment, "is_mutable", False):
            from pinot_tpu.engine.mutable_staging import resident_name

            residency.evict(resident_name(segment.segment_name))
        residency.prefetch(segment)

    def segment_removed(self, table: str, segment_name: str) -> None:
        """Eviction hook: an unassigned segment's HBM must be reclaimed —
        refcounts protect in-flight readers, the residency entry must go."""
        evict = getattr(self.executor, "evict_segment", None)
        if evict is not None:
            evict(segment_name)

    def _upsert_manager_for_locked(self, table: str):
        """TableUpsertMetadataManager for upsert-enabled realtime tables
        (ref: TableUpsertMetadataManager creation in RealtimeTableDataManager)."""
        if table in self._upsert_managers:
            return self._upsert_managers[table]
        from pinot_tpu.spi.table import UpsertMode

        cfg = self.store.get_table_config(table)
        if cfg is None:
            # config not visible yet: decide on a later reconcile instead of
            # caching a permanent 'no upsert'
            return None
        mgr = None
        if cfg.upsert_config is not None \
                and cfg.upsert_config.mode is not UpsertMode.NONE:
            schema = self.store.get_schema(cfg.table_name)
            if schema is None:
                return None  # schema lag: retry on the next reconcile
            if schema.primary_key_columns:
                from pinot_tpu.segment.upsert import TableUpsertMetadataManager

                cmp_col = (cfg.upsert_config.comparison_column
                           or cfg.validation_config.time_column_name)
                mgr = TableUpsertMetadataManager(
                    schema.primary_key_columns, cmp_col,
                    cfg.upsert_config.mode)
        self._upsert_managers[table] = mgr
        return mgr

    # -- state transitions ---------------------------------------------------
    def _on_ideal_state_change(self, path: str, value) -> None:
        if not self._started:
            return
        table = path.split("/", 1)[1]
        try:
            self._reconcile_table(table)
        except Exception:
            log.exception("[%s] reconcile failed for %s",
                          self.instance_id, table)

    def _reconcile_table(self, table: str) -> None:
        with self._reconcile_lock:
            self._reconcile_table_locked(table)

    def _reconcile_table_locked(self, table: str) -> None:
        ideal = self.store.get_ideal_state(table)
        realtime = table_type_from_name(table) is TableType.REALTIME
        tdm = self.data_manager.get_or_create(
            table, realtime=realtime,
            upsert_manager=self._upsert_manager_for_locked(table) if realtime
            else None)

        my_segments = {seg: states[self.instance_id]
                       for seg, states in ideal.items()
                       if self.instance_id in states}

        # drop segments no longer assigned to me
        for seg in tdm.segment_names():
            if seg not in my_segments:
                tdm.remove_segment(seg)
                self.store.report_instance_state(table, seg,
                                                 self.instance_id, "OFFLINE")

        for seg, target in my_segments.items():
            if target == ONLINE:
                self._ensure_online(table, tdm, seg)
            elif target == CONSUMING:
                self._ensure_consuming(table, tdm, seg)

    def _ensure_online(self, table: str, tdm, seg: str) -> None:
        if isinstance(tdm, RealtimeTableDataManager):
            mgr = tdm.consuming_manager(seg)
            if mgr is not None:
                # CONSUMING -> ONLINE flip arrived before the local consumer
                # finished; its terminal callback completes the swap
                return
        if tdm.has_segment(seg):
            return
        md = self.store.get_segment_metadata(table, seg)
        if md is None or not md.download_url:
            log.warning("[%s] no download url for %s/%s",
                        self.instance_id, table, seg)
            return
        # deep-store resolution through the PinotFS registry (ref:
        # downloadSegmentFromDeepStore, BaseTableDataManager.java:388) —
        # local URIs serve in place, remote schemes materialize under the
        # server's segment dir
        from pinot_tpu.spi.filesystem import fetch_segment

        try:
            local = fetch_segment(md.download_url,
                                  os.path.join(self.segment_dir, table))
        except Exception:
            log.exception("[%s] deep-store fetch failed for %s/%s (%s)",
                          self.instance_id, table, seg, md.download_url)
            return
        if isinstance(tdm, RealtimeTableDataManager):
            # upsert tables must register downloaded keys (on_sealed handles
            # both the upsert and plain realtime cases)
            tdm.on_sealed(seg, local, partition=md.partition)
        else:
            tdm.add_segment_from_dir(local)
        self.store.report_instance_state(table, seg, self.instance_id, ONLINE)

    def _ensure_consuming(self, table: str, tdm, seg: str) -> None:
        assert isinstance(tdm, RealtimeTableDataManager), table
        if tdm.consuming_manager(seg) is not None or tdm.has_segment(seg):
            return
        cfg = self.store.get_table_config(table)
        schema = self.store.get_schema(cfg.table_name)
        md = self.store.get_segment_metadata(table, seg)
        if cfg is None or schema is None or md is None:
            log.warning("[%s] missing config for consuming %s/%s",
                        self.instance_id, table, seg)
            return
        start = StreamOffset.parse(md.start_offset or "0")

        mgr = RealtimeSegmentDataManager(
            seg, cfg, schema, partition=md.partition or 0,
            start_offset=start, protocol=self.completion_protocol,
            instance_id=self.instance_id,
            output_dir=f"{self.segment_dir}/{self.instance_id}/{table}",
            on_terminal=lambda m, t=table, td=tdm: self._on_consumer_done(
                t, td, m))
        tdm.add_consuming(mgr)
        self.store.report_instance_state(table, seg, self.instance_id,
                                         CONSUMING)
        mgr.start(tick_seconds=self.consumer_tick_s)

    def _on_consumer_done(self, table: str, tdm, mgr) -> None:
        """Terminal consumer states (ref: CONSUMING->ONLINE transition +
        the KEEP/DISCARD commit-protocol outcomes)."""
        seg = mgr.segment_name
        if tdm.consuming_manager(seg) is not mgr:
            # unassigned (or replaced) while finishing: do not resurrect
            return
        try:
            if mgr.state is ConsumerState.COMMITTED:
                tdm.on_sealed(seg, mgr._committed_dir)
            elif mgr.state is ConsumerState.RETAINING:
                # KEEP: build locally at the committed offset, swap in place
                md, seg_dir = mgr.build_segment()
                tdm.on_sealed(seg, seg_dir)
            elif mgr.state is ConsumerState.DISCARDED:
                zk = self.store.get_segment_metadata(table, seg)
                if zk and zk.download_url:
                    # same PinotFS resolution as _ensure_online (http(s)
                    # deep stores must materialize locally here too)
                    from pinot_tpu.spi.filesystem import fetch_segment

                    local = fetch_segment(
                        zk.download_url,
                        os.path.join(self.segment_dir, table))
                    tdm.on_sealed(seg, local)
                else:
                    # winner's metadata not visible yet: drop the consumer
                    # entry so a later reconcile can download it ONLINE
                    tdm.drop_consumer(seg)
                    tdm.remove_segment(seg)
                    return
            else:  # ERROR
                log.error("[%s] consumer for %s ended in %s",
                          self.instance_id, seg, mgr.state)
                return
            self.store.report_instance_state(table, seg, self.instance_id,
                                             ONLINE)
            # pick up the successor CONSUMING segment promptly
            self._reconcile_table(table)
        except Exception:
            log.exception("[%s] seal handling failed for %s",
                          self.instance_id, seg)

    # -- reload (ref: SegmentMessageHandlerFactory refresh/reload) ----------
    def _on_reload_request(self, path: str, _value) -> None:
        table = path.split("/", 1)[-1]
        tdm = self.data_manager.get(table)
        if tdm is None:
            return
        cfg = self.store.get_table_config(table)
        if cfg is None:
            return
        from pinot_tpu.segment.preprocessor import reload_segment

        acquired = tdm.acquire_segments(None)
        try:
            for holder in acquired:
                seg = holder.segment
                if getattr(seg, "is_mutable", False):
                    continue  # consuming segments rebuild indexes at seal
                try:
                    added = reload_segment(tdm, seg, cfg.indexing_config)
                    if added:
                        log.info("[%s] reloaded %s/%s: %s",
                                 self.instance_id, table,
                                 seg.segment_name, added)
                except Exception:
                    log.exception("[%s] reload failed for %s/%s",
                                  self.instance_id, table, seg.segment_name)
        finally:
            tdm.release_segments(acquired)

    # -- query path (ref: InstanceRequestHandler.channelRead0:90) -----------
    def execute_query(self, ctx: QueryContext, table: str,
                      segment_names: Optional[List[str]] = None) -> DataTable:
        if not self._queries_enabled:
            return DataTable.for_exception(
                f"server {self.instance_id} is shut down")
        submit_t = time.perf_counter()
        self.stall_watch.begin()
        try:
            # the shape key feeds the SEWF policy's per-shape latency
            # EWMAs: same table + same SQL text = same expected work
            future = self.scheduler.submit(
                lambda: self._execute(ctx, table, segment_names, submit_t),
                table=table, shape=(table, ctx.sql))
            return future.result()
        finally:
            self.stall_watch.end()

    def _execute(self, ctx: QueryContext, table: str,
                 segment_names: Optional[List[str]],
                 submit_t: float) -> DataTable:
        from pinot_tpu.spi.metrics import ServerMeter, ServerQueryPhase

        wait_ms = (time.perf_counter() - submit_t) * 1e3
        self.metrics.timer(ServerQueryPhase.SCHEDULER_WAIT).update_ms(wait_ms)
        self.metrics.meter(ServerMeter.QUERIES).mark()
        tdm = self.data_manager.get(table)
        if tdm is None:
            self.metrics.meter(ServerMeter.QUERY_EXCEPTIONS).mark()
            return DataTable.for_exception(
                f"table {table} not hosted on {self.instance_id}")
        acquired = tdm.acquire_segments(segment_names)
        t0 = time.perf_counter()
        try:
            segments = [s.segment for s in acquired]
            if not segments:
                self.metrics.meter(ServerMeter.QUERY_EXCEPTIONS).mark()
                return DataTable.for_exception(
                    f"no segments of {table} on {self.instance_id}")
            dt = self.executor.execute_instance(ctx, segments)
            exec_ms = (time.perf_counter() - t0) * 1e3
            # phase timings travel in the DataTable stats (ref: the
            # TimerContext values at ServerQueryExecutorV1Impl:122-303)
            dt.stats.add_phase_ms(ServerQueryPhase.SCHEDULER_WAIT, wait_ms)
            dt.stats.add_phase_ms(ServerQueryPhase.QUERY_EXECUTION, exec_ms)
            if dt.stats.spans:
                # scheduler-queue wait happened before the executor's
                # span tree opened; retroactively attribute it as the
                # root's FIRST child (pure queue time) so the tree
                # accounts the full server-side lifecycle
                from pinot_tpu.common.tracing import attach_root_child

                attach_root_child(dt.stats, "SchedulerQueue",
                                  wall_ms=wait_ms, queue_ms=wait_ms,
                                  front=True)
            self.metrics.timer(
                ServerQueryPhase.QUERY_EXECUTION).update_ms(exec_ms)
            self.metrics.meter(ServerMeter.DOCS_SCANNED).mark(
                dt.stats.num_docs_scanned)
            self.metrics.meter(ServerMeter.SEGMENTS_PRUNED).mark(
                dt.stats.num_segments_pruned)
            return dt
        except Exception as e:  # query errors travel in the DataTable
            log.debug("[%s] query failed", self.instance_id, exc_info=True)
            self.metrics.meter(ServerMeter.QUERY_EXCEPTIONS).mark()
            return DataTable.for_exception(str(e))
        finally:
            tdm.release_segments(acquired)

    def execute_query_streaming(self, ctx: QueryContext, table: str,
                                segment_names: Optional[List[str]] = None):
        """Selection queries stream one DataTable block PER SEGMENT (ref:
        StreamingSelectionOnlyOperator feeding GrpcQueryServer.submit) so
        the broker can stop pulling once LIMIT rows arrived. Generator of
        DataTables; non-selection shapes yield the single combined block."""
        if not self._queries_enabled:
            yield DataTable.for_exception(
                f"server {self.instance_id} is shut down")
            return
        if not ctx.is_selection:
            yield self.execute_query(ctx, table, segment_names)
            return
        tdm = self.data_manager.get(table)
        if tdm is None:
            yield DataTable.for_exception(
                f"table {table} not hosted on {self.instance_id}")
            return
        acquired = tdm.acquire_segments(segment_names)
        try:
            if not acquired:
                yield DataTable.for_exception(
                    f"no segments of {table} on {self.instance_id}")
                return
            # prune ONCE across the acquired set: the per-segment
            # execute_instance would otherwise keep-one-fallback every
            # prunable segment into a scan
            from pinot_tpu.engine.pruner import prune_segments

            kept = prune_segments(
                ctx, [h.segment for h in acquired]) or \
                [acquired[0].segment]
            for segment in kept:
                yield self.executor.execute_instance(ctx, [segment])
        except Exception as e:  # noqa: BLE001 — errors travel in-band
            log.debug("[%s] streaming query failed", self.instance_id,
                      exc_info=True)
            yield DataTable.for_exception(str(e))
        finally:
            tdm.release_segments(acquired)

    # -- admin (ref: TablesResource) ----------------------------------------
    def hosted_tables(self) -> List[str]:
        return self.data_manager.table_names()

    def hosted_segments(self, table: str) -> List[str]:
        tdm = self.data_manager.get(table)
        return tdm.segment_names() if tdm else []

    def table_size(self, table: str) -> Dict[str, Any]:
        """On-disk bytes per hosted segment (ref: TableSizeResource);
        segments that vanish mid-walk are omitted, not reported as 0."""
        tdm = self.data_manager.get(table)
        if tdm is None:
            return {"tableName": table, "segments": {}, "totalBytes": 0}
        sizes: Dict[str, int] = {}
        for name in tdm.segment_names():
            seg = None
            acquired = tdm.acquire_segments([name])
            if not acquired:
                continue  # deleted concurrently: omit (ref: missing segs)
            try:
                seg_dir = getattr(acquired[0].segment, "segment_dir", None)
                total = 0
                if seg_dir and os.path.isdir(seg_dir):
                    for root, _dirs, files in os.walk(seg_dir):
                        total += sum(
                            os.path.getsize(os.path.join(root, f))
                            for f in files)
                sizes[name] = total
            finally:
                tdm.release_segments(acquired)
        return {"tableName": table, "segments": sizes,
                "totalBytes": sum(sizes.values())}

    def evict_staged(self, segment_name: str) -> Dict[str, Any]:
        """Admin force-eviction of one staged resident (REST
        ``POST /debug/memory/evict/<name>``); reports what remains."""
        evict = getattr(self.executor, "evict_segment", None)
        if evict is not None:
            evict(segment_name)
        residency = getattr(self.executor, "residency", None)
        return {"evicted": segment_name,
                "stagedBytes": (residency.staged_bytes()
                                if residency is not None else 0)}

    def demote_staged(self, name: str) -> Dict[str, Any]:
        """Admin force-demotion of one resident to the host-RAM tier
        (REST ``POST /debug/memory/demote/<name>``): its device arrays
        D2H-snapshot into the host tier and the next query promotes them
        with a plain H2D instead of rebuilding. Refused (demoted=False)
        when the resident is pinned by an in-flight query."""
        residency = getattr(self.executor, "residency", None)
        if residency is None:
            return {"demoted": False, "reason": "no residency manager"}
        ok = residency.demote(name)
        return {"demoted": bool(ok), "name": name,
                "stagedBytes": residency.staged_bytes(),
                "hostBytes": residency.host_bytes()}

    def launch_debug(self) -> Dict[str, Any]:
        """Launch-coalescing state for ``GET /debug/launches``: requests vs
        device launches, coalesced counts, queue waits, the dispatcher's
        ``clock`` (where its thread's time went) and the live dispatcher
        queue depth (empty for host-only executors)."""
        launcher = getattr(self.executor, "launcher", None)
        if launcher is None:
            return {"enabled": False}
        out: Dict[str, Any] = {"enabled": True}
        out.update(launcher.snapshot())
        return out

    def scheduler_debug(self) -> Dict[str, Any]:
        """Scheduler-tier state for ``GET /debug/scheduler``: dispatch
        policy + queue depth, admission bounds/counters, and the
        per-segment kernel single-flight counters — the millions-of-users
        ops view."""
        out: Dict[str, Any] = {"scheduler": self.scheduler.stats_snapshot(),
                               "stallWatch": self.stall_watch.snapshot()}
        admission = getattr(self.executor, "admission", None)
        if admission is not None:
            out["admission"] = admission.snapshot()
        flight = getattr(self.executor, "_kernel_flight", None)
        if flight is not None:
            out["kernelFlight"] = flight.snapshot()
        qflight = getattr(self.executor, "_query_flight", None)
        if qflight is not None:
            out["queryFlight"] = qflight.snapshot()
        return out

    def queries_debug(self) -> Dict[str, Any]:
        """``GET /debug/queries``: currently-running queries (id, sql,
        phase, elapsed, pins held), the completed ring buffer, and the
        slow-query log — full span trees retained for over-threshold
        queries even when trace/sampling missed them
        (``pinot.server.query.slow.threshold.ms``)."""
        registry = getattr(self.executor, "queries", None)
        if registry is None:
            return {"enabled": False}
        out: Dict[str, Any] = {"instance": self.instance_id}
        out.update(registry.snapshot())
        return out

    def telemetry_debug(self) -> Dict[str, Any]:
        """``GET /debug/telemetry``: the continuous-telemetry view —
        windowed (table, phase) latency histograms with sliding AND
        lifetime quantiles, plus the gauge-history rings (staged/host
        bytes, queue depths, arrival EWMA, rejection counters)."""
        from pinot_tpu.common.telemetry import TELEMETRY

        return TELEMETRY.snapshot()

    def slo_debug(self) -> Dict[str, Any]:
        """``GET /debug/slo``: per-table latency/error objectives + the
        short/long-window burn rates."""
        from pinot_tpu.common.telemetry import TELEMETRY

        return TELEMETRY.slo_snapshot()

    def freshness_debug(self) -> Dict[str, Any]:
        """``GET /debug/freshness``: per-table ingest-to-queryable
        histograms (each sample: one row's append -> first covering
        watermark) + the freshness objective/burn when configured."""
        from pinot_tpu.common.telemetry import TELEMETRY

        return TELEMETRY.freshness_snapshot()

    def flightrecorder_debug(self) -> Dict[str, Any]:
        """``GET /debug/flightrecorder``: the black box — frozen bundle
        index, the last post-mortem bundle, live ring occupancy, and the
        anomaly-event totals."""
        from pinot_tpu.common.telemetry import TELEMETRY

        return TELEMETRY.recorder.snapshot()

    def pallas_debug(self) -> Dict[str, Any]:
        """``GET /debug/pallas``: the per-shape blocklist (spec + the
        reason each shape declines with — ``pallas_shape_blocked`` for
        runtime lowering failures, ``pallas_preflight_<rule>`` for
        preflight-seeded predictions) plus the last preflight verdict
        table run against this executor (tools/preflight.py),
        ``launches``: fused-scan launches by the accumulate they took
        (``single``: at most 128 groups; ``two_level``: more; ``scalar``:
        no one-hot at all, every group-range probe among them), and
        ``mxu``: those that built a one-hot, by MXU contraction (``bf16``:
        integer rows alone, one bf16 pass; ``fp32``: float-sum rows took
        an fp32 contraction besides). A chip
        that fell over mid-round keeps its lessons visible here — and,
        with ``pinot.server.query.pallas.blocklist.path`` set, across
        restarts."""
        bl = getattr(self.executor, "_pallas_blocked", None)
        out: Dict[str, Any] = {
            "blocklist": bl.snapshot() if hasattr(bl, "snapshot") else [],
            "blockedShapes": len(bl) if bl is not None else 0,
        }
        path = getattr(bl, "_path", None)
        if path:
            out["blocklistPath"] = path
        verdicts = getattr(self.executor, "preflight_verdicts", None)
        out["preflight"] = verdicts if verdicts is not None else {
            "run": False}
        launches = getattr(self.executor, "pallas_launches", None)
        out["launches"] = launches() if launches is not None else {}
        mxu = getattr(self.executor, "pallas_mxu", None)
        out["mxu"] = mxu() if mxu is not None else {}
        return out

    def memory_debug(self) -> Dict[str, Any]:
        """Bytes-accurate HBM residency + native mmap accounting
        (ref: MmapDebugResource). Per resident: device bytes, pin count,
        staged column/packed/value array counts; plus the budget, fleet
        total/peak, and the hit/miss/eviction/spill counters.
        ``dictionaries``: of the hosted immutable segments' string
        dictionaries that a reader has opened, how many hold their values
        in process memory (``materialised``, with ``hostBytes``) and how
        many are too large for that and stay on the mapped blob."""
        from pinot_tpu import native

        out: Dict[str, Any] = {"stagedSegments": {}}
        residency = getattr(self.executor, "residency", None)
        if residency is not None:
            out.update(residency.snapshot())
        out["nativeMmapBuffers"] = native.mmap_buffer_count()
        out["dictionaries"] = self._dictionary_debug()
        return out

    def _dictionary_debug(self) -> Dict[str, int]:
        out = {"materialised": 0, "blobBacked": 0, "hostBytes": 0}
        for table in self.data_manager.table_names():
            tdm = self.data_manager.get(table)
            if tdm is None:
                continue  # dropped since table_names()
            acquired = tdm.acquire_segments()
            try:
                for sdm in acquired:
                    loaded = getattr(sdm.segment,
                                     "loaded_string_dictionaries", None)
                    for d in loaded() if loaded else ():
                        out["materialised"] += d.host_bytes > 0
                        out["blobBacked"] += d.blob_backed
                        out["hostBytes"] += d.host_bytes
            finally:
                tdm.release_segments(acquired)
        return out
