"""Broker request handler: SQL front door + scatter/gather.

Re-design of ``pinot-broker/.../requesthandler/BaseBrokerRequestHandler.java:176``:
parse SQL -> resolve the table (offline / realtime / hybrid with the time
boundary, ``:2002``) -> routing tables -> scatter per-server instance
requests -> gather DataTables -> BrokerReduceService -> BrokerResponse
(ref: SingleConnectionBrokerRequestHandler.java:82-146).

Transport: an in-process server registry (the embedded-cluster mode, ref:
ClusterTest single-JVM). Multi-host deployments register gRPC stubs that
expose the same ``execute_query`` signature.
"""

from __future__ import annotations

import logging
import time

from concurrent.futures import TimeoutError as FutureTimeout
from dataclasses import replace
from typing import Dict, List, Optional, Tuple

from pinot_tpu.broker.reduce import BrokerReduceService
from pinot_tpu.broker.routing import RoutingManager
from pinot_tpu.common.datatable import DataTable
from pinot_tpu.common.response import BrokerResponse
from pinot_tpu.controller.state import ClusterStateStore
from pinot_tpu.engine.errors import QueryError, QueryRejectedError
from pinot_tpu.engine.results import QueryStats
from pinot_tpu.query import SqlParseError, compile_query
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.expressions import (
    FilterNode,
    FilterOp,
    Identifier,
    Predicate,
    PredicateType,
)
from pinot_tpu.spi.config import CommonConstants
from pinot_tpu.spi.table import TableType, table_name_with_type

log = logging.getLogger(__name__)

# ref: QueryException codes
SQL_PARSING_ERROR = 150
TABLE_DOES_NOT_EXIST_ERROR = 190
BROKER_REQUEST_SEND_ERROR = 425
SERVER_NOT_RESPONDING_ERROR = 427
QUERY_EXECUTION_ERROR = 200
ACCESS_DENIED_ERROR = 180
TOO_MANY_REQUESTS_ERROR = 429


class AccessDeniedError(QueryError):
    """A subquery (or other nested execution) was denied by access control;
    carries the denial through QueryError-shaped handling so the outer
    response keeps errorCode 180 (-> HTTP 403)."""


class BrokerRequestHandler:
    """Ref: BaseBrokerRequestHandler.java:176."""

    def __init__(self, store: ClusterStateStore,
                 routing: Optional[RoutingManager] = None,
                 scatter_workers: int = 16,
                 query_timeout_s: float = 30.0,
                 coalesce: bool = True,
                 device_reduce: Optional[bool] = None):
        from pinot_tpu.spi.metrics import MetricsRegistry

        if device_reduce is None:
            # operator knob (pinot.broker.reduce.device.enabled): an
            # explicit constructor argument — the embedded cluster's and
            # the bench's path — wins over the environment
            from pinot_tpu.spi.config import PinotConfiguration

            device_reduce = PinotConfiguration().get_bool(
                CommonConstants.BROKER_DEVICE_REDUCE_KEY,
                CommonConstants.DEFAULT_BROKER_DEVICE_REDUCE)
        self.store = store
        self.routing = routing or RoutingManager(store)
        self.reduce_service = BrokerReduceService(
            device_reduce=device_reduce)
        self._servers: Dict[str, object] = {}
        from pinot_tpu.server.scheduler import _DaemonPool

        from pinot_tpu.broker.quota import QueryQuotaManager

        self._pool = _DaemonPool(scatter_workers, "scatter")
        self.query_timeout_s = query_timeout_s
        self.metrics = MetricsRegistry(role="broker")
        import threading as _threading

        self._subq_local = _threading.local()
        self.quota = QueryQuotaManager(
            store,
            num_brokers_fn=lambda: max(
                len(store.instances("BROKER", only_alive=True)), 1))
        # single admission gate for the front door: the per-table QPS
        # quota rides it (reason="quota" rejections), and operators can
        # bound broker concurrency through configure() — the server-side
        # executor gate bounds execution below.
        from pinot_tpu.server.admission import AdmissionGate

        self.admission = AdmissionGate(max_concurrent=-1, quota=self.quota,
                                       name="broker-admission")
        # single-flight coalescing: concurrent IDENTICAL dashboard queries
        # (same normalized SQL + principal + cluster-state generation)
        # share one compile/scatter/gather/reduce, before any fan-out
        from pinot_tpu.common.singleflight import SingleFlight

        self.coalesce = coalesce
        self._flights = SingleFlight()
        # the broker's own request ids (next() on a count is atomic)
        import itertools
        import os

        self._request_ids = itertools.count(1)
        self._request_prefix = f"b{os.getpid():x}"
        self._leading = _threading.local()
        # continuous telemetry: the broker front door records per-table
        # windowed latency/error (the SLO tracker's input) and exposes
        # the process telemetry families on this registry's /metrics
        from pinot_tpu.common.telemetry import TELEMETRY

        TELEMETRY.configure()
        self.metrics.bind_telemetry(TELEMETRY)
        TELEMETRY.recorder.register_provider(
            "brokerScheduler", self.scheduler_snapshot)

    # -- transport registry --------------------------------------------------
    def register_server(self, instance_id: str, server) -> None:
        """``server`` exposes execute_query(ctx, table, segments)->DataTable
        (a ServerInstance, or a gRPC stub with the same surface)."""
        self._servers[instance_id] = server

    # -- entry (ref: handleSQLRequest:203) -----------------------------------
    def handle_sql(self, sql: str, principal=None,
                   access_control=None) -> BrokerResponse:
        """Front door. Concurrent IDENTICAL queries — same normalized SQL,
        same principal, same cluster-state generation — single-flight: one
        leader runs the full compile/authorize/scatter/gather/reduce and
        every concurrent duplicate receives the same BrokerResponse (the
        dashboard-fanout case: N browser tabs refreshing one chart cost
        ONE execution). A store mutation (segment push, table config)
        bumps the generation, so later arrivals never join a flight whose
        answer predates the change. Coalescing is skipped for
        time-dependent SQL (``now()``)."""
        key = self._flight_key(sql, principal, access_control)
        led = getattr(self._leading, "keys", None)
        if led is None:
            led = self._leading.keys = set()
        if key is None or key in led:
            # non-coalescable, or a re-entrant subquery on the leader's own
            # thread (joining our own flight would deadlock)
            return self._handle_sql(sql, principal, access_control)

        def lead():
            led.add(key)
            try:
                return self._handle_sql(sql, principal, access_control)
            finally:
                led.discard(key)

        resp, coalesced = self._flights.do(key, lead)
        if coalesced:
            from pinot_tpu.spi.metrics import BrokerMeter

            self.metrics.meter(BrokerMeter.QUERIES).mark()
            self.metrics.meter(BrokerMeter.QUERIES_COALESCED).mark()
        return resp

    def _flight_key(self, sql: str, principal, access_control):
        """None = don't coalesce. The key carries the cluster-state
        VERSION as the table generation: any store mutation invalidates
        joinability (conservatively — a whole-store counter, not per
        table, trading a few missed coalesces for zero staleness)."""
        if not self.coalesce or not isinstance(sql, str):
            return None
        norm = " ".join(sql.split())
        if not norm or "now(" in norm.lower():
            return None  # time-dependent: two calls are NOT identical work
        pkey = getattr(principal, "name", None) if principal is not None \
            else None
        return (norm, pkey,
                id(access_control) if access_control is not None else None,
                self.store.version)

    def scheduler_snapshot(self) -> Dict[str, object]:
        """Broker half of ``/debug/scheduler``: single-flight coalescing
        counters + the front-door admission gate."""
        return {"singleFlight": self._flights.snapshot(),
                "admission": self.admission.snapshot()}

    # -- continuous telemetry (process-wide center; broker-side routes) ------
    def telemetry_snapshot(self) -> Dict[str, object]:
        """``GET /debug/telemetry``: windowed (table, phase) histograms
        with sliding AND lifetime quantiles + the gauge-history rings."""
        from pinot_tpu.common.telemetry import TELEMETRY

        return TELEMETRY.snapshot()

    def slo_snapshot(self) -> Dict[str, object]:
        """``GET /debug/slo``: per-table objectives + multi-window burn."""
        from pinot_tpu.common.telemetry import TELEMETRY

        return TELEMETRY.slo_snapshot()

    def flightrecorder_snapshot(self) -> Dict[str, object]:
        """``GET /debug/flightrecorder``: bundle index + last bundle."""
        from pinot_tpu.common.telemetry import TELEMETRY

        return TELEMETRY.recorder.snapshot()

    def freshness_snapshot(self) -> Dict[str, object]:
        """``GET /debug/freshness``: per-table ingest-to-queryable
        histograms + freshness-objective burn."""
        from pinot_tpu.common.telemetry import TELEMETRY

        return TELEMETRY.freshness_snapshot()

    def _handle_sql(self, sql: str, principal=None,
                    access_control=None) -> BrokerResponse:
        """``access_control``/``principal`` enable per-table authorization
        on the PARSED query (ref: BaseBrokerRequestHandler.handleRequest
        authorizing on the compiled request, not the raw SQL — a regex over
        the SQL text is spoofable via string literals). Subquery rewrites
        re-enter with the same principal so inner queries are checked too."""
        from pinot_tpu.spi.metrics import BrokerMeter, BrokerQueryPhase

        start = time.perf_counter()
        self.metrics.meter(BrokerMeter.QUERIES).mark()
        response = BrokerResponse()
        tel_table: List[str] = [""]  # resolved after compile, read by finish
        # a query that asks for its trace also gets each phase's start and
        # the broker thread's CPU time in it (the clock of the span tree);
        # "trace" in the text is a hint that costs an untraced query one
        # substring test, ctx.trace_enabled decides after the compile
        clock = _PhaseClock(start) \
            if isinstance(sql, str) and "trace" in sql else None

        def phase(name: str, t0: float) -> float:
            """Record a broker phase (ref: BrokerQueryPhase timers at
            SingleConnectionBrokerRequestHandler.java:90-123)."""
            now = time.perf_counter()
            ms = (now - t0) * 1e3
            response.phase_times_ms[name] = \
                response.phase_times_ms.get(name, 0.0) + ms
            self.metrics.timer(name).update_ms(ms)
            if clock is not None:
                clock.note(name, t0)
            return now

        def finish(resp: BrokerResponse) -> BrokerResponse:
            # exactly one exceptions_total tick per failed query, whatever
            # the failure mode (parse / no table / unavailable / reduce)
            if resp.has_exceptions:
                self.metrics.meter(BrokerMeter.EXCEPTIONS).mark()
            # every front-door outcome lands in the per-table windowed
            # latency histogram + the SLO error-budget counters — the
            # continuous (sliding-percentile) view of broker latency
            from pinot_tpu.common.telemetry import TELEMETRY

            TELEMETRY.note_broker_query(
                tel_table[0], (time.perf_counter() - start) * 1e3,
                resp.has_exceptions)
            return resp

        try:
            ctx = compile_query(sql)
        except SqlParseError as e:
            response.add_exception(SQL_PARSING_ERROR, str(e))
            return finish(response)
        tel_table[0] = ctx.table_name or ""
        # every query that came through the broker has a request id: the
        # client's OPTION(requestId=...) or the broker's own, carried to
        # the servers in the context and onto both roots of the span tree
        ctx.options.setdefault(
            "requestId", f"{self._request_prefix}-{next(self._request_ids)}")
        if not ctx.trace_enabled:
            clock = None
        t = phase(BrokerQueryPhase.COMPILATION, start)

        if access_control is not None:
            from pinot_tpu.spi.auth import READ

            # ctx.table_name is never None (the grammar requires FROM), so
            # the parsed table — not a spoofable raw-SQL regex — is what
            # gets authorized
            if not access_control.has_access(principal, ctx.table_name,
                                             READ):
                response.add_exception(
                    ACCESS_DENIED_ERROR,
                    f"Permission denied for table {ctx.table_name!r}")
                return finish(response)

        try:
            physical = self._resolve_tables(ctx.table_name)
        except QueryError as e:
            response.add_exception(TABLE_DOES_NOT_EXIST_ERROR, str(e))
            return finish(response)

        if ctx.explain:
            # EXPLAIN PLAN FOR: logical operator tree, no execution — but
            # AFTER table resolution, so explaining a nonexistent table
            # errors like the real query would (ref: ExplainPlanDataTableReducer)
            from pinot_tpu.engine.results import DataSchema, ResultTable
            from pinot_tpu.query.explain import EXPLAIN_COLUMNS, explain_rows

            names, types = EXPLAIN_COLUMNS
            response.result_table = ResultTable(DataSchema(names, types),
                                                explain_rows(ctx))
            response.time_used_ms = (time.perf_counter() - start) * 1e3
            return finish(response)

        try:
            # strip gapfill(...) BEFORE scatter: servers execute the plain
            # bucket group-by; the reducer fills the gaps (ref:
            # GapfillProcessor dispatched from BrokerReduceService.java:44)
            from pinot_tpu.broker.gapfill import extract_gapfill

            ctx, gapfill_spec = extract_gapfill(ctx)
        except QueryError as e:
            response.add_exception(QUERY_EXECUTION_ERROR, str(e))
            return finish(response)

        # admission FIRST — per-table QPS quota + broker concurrency bound
        # ride ONE gate: a throttled/rejected request must not get to
        # trigger subquery execution work (ref: queryquota acquire before
        # routing). Tickets release in the finally below; rejection is the
        # typed retriable error, surfaced as a 429-coded exception.
        tickets: List[object] = []
        if clock is not None:
            clock.note("ADMISSION", time.perf_counter())
        try:
            for table in physical:
                t_adm = self.admission.admit(table)
                tickets.append(t_adm)
        except QueryRejectedError as e:
            for t_adm in tickets:
                self.admission.release(t_adm)
            self.metrics.meter(BrokerMeter.QUERIES_REJECTED).mark()
            response.add_exception(
                TOO_MANY_REQUESTS_ERROR,
                f"{e} (retriable; queueDepth={e.queue_depth})")
            return finish(response)
        admit_wait_ms = sum(getattr(t_adm, "wait_ms", 0.0)
                            for t_adm in tickets)
        try:
            return self._scatter_reduce(ctx, physical, gapfill_spec,
                                        response, phase, finish, start,
                                        principal, access_control,
                                        admit_wait_ms=admit_wait_ms,
                                        clock=clock)
        finally:
            for t_adm in tickets:
                self.admission.release(t_adm)

    def _scatter_reduce(self, ctx, physical, gapfill_spec, response,
                        phase, finish, start, principal, access_control,
                        admit_wait_ms: float = 0.0,
                        clock: Optional["_PhaseClock"] = None
                        ) -> BrokerResponse:
        """Post-admission half of the front door: subquery rewrite ->
        hybrid split -> routing -> scatter/gather -> reduce.
        ``admit_wait_ms`` is the front-door admission-gate queue wait —
        the broker-level queue span in the trace tree."""
        from pinot_tpu.spi.metrics import BrokerMeter, BrokerQueryPhase

        try:
            ctx = self._rewrite_subqueries(ctx, principal=principal,
                                           access_control=access_control)
        except AccessDeniedError as e:
            response.add_exception(ACCESS_DENIED_ERROR, str(e))
            return finish(response)
        except QueryError as e:
            response.add_exception(QUERY_EXECUTION_ERROR, str(e))
            return finish(response)

        tables: List[DataTable] = []
        servers_queried = set()
        servers_responded = set()
        # broker-side stats carrier: routing + gather decisions recorded
        # here merge into the reduced stats so the response's decision
        # ledger explains why each server was or wasn't scattered to
        broker_stats = QueryStats()
        # reduce-as-arrivals: every gathered DataTable folds into the
        # merge state the moment it lands, so the reduce work overlaps
        # the stragglers' network wait; finish() below runs only the
        # final trim/HAVING/post-agg pass
        acc = self.reduce_service.accumulator(ctx)
        if clock is not None:
            acc.trace_origin = start
        for table, sub_ctx in self._split_hybrid(ctx, physical,
                                                 stats=broker_stats):
            t = time.perf_counter()
            route = self.routing.route(table, sub_ctx, stats=broker_stats)
            routing, unavailable = route.routing, route.unavailable
            t = phase(BrokerQueryPhase.ROUTING, t)
            if unavailable:
                self.metrics.meter(BrokerMeter.NO_SERVING_HOST).mark(
                    len(unavailable))
                response.add_exception(
                    SERVER_NOT_RESPONDING_ERROR,
                    f"{len(unavailable)} segments of {table} unavailable: "
                    f"{unavailable[:5]}")
            if not routing:
                continue
            if self._use_streaming(sub_ctx, routing):
                gathered, queried, responded = \
                    self._scatter_gather_streaming(table, sub_ctx, routing,
                                                   broker_stats, acc)
            else:
                gathered, queried, responded = self._scatter_gather(
                    table, sub_ctx, routing, broker_stats, acc)
            phase(BrokerQueryPhase.SCATTER_GATHER, t)
            tables.extend(gathered)
            servers_queried |= queried
            servers_responded |= responded

        response.num_servers_queried = len(servers_queried)
        response.num_servers_responded = len(servers_responded)
        broker_stats.num_servers_queried = len(servers_queried)
        broker_stats.num_servers_responded = len(servers_responded)
        if not tables:
            # an existing-but-empty table answers with an empty result
            response.stats = broker_stats
            response.time_used_ms = (time.perf_counter() - start) * 1e3
            return finish(response)

        t = time.perf_counter()
        try:
            table, stats, server_errors = acc.finish()
            if gapfill_spec is not None:
                from pinot_tpu.broker.gapfill import apply_gapfill

                table = apply_gapfill(ctx, table, gapfill_spec)
            response.result_table = table
            # fold the broker-side routing/gather ledger + scatter
            # accounting into the reduced stats: numServersQueried /
            # numServersResponded ride the stats (and thus the wire /
            # QueryStats merges) so a partial result is LOUD everywhere
            # the stats travel, not just on the top-level response
            stats.merge(broker_stats)
            response.stats = stats
            traced_stats = stats if stats.spans else None
            for msg in server_errors:
                # partial result: the table stands, but the caller sees it
                response.add_exception(SERVER_NOT_RESPONDING_ERROR, msg)
        except QueryError as e:
            traced_stats = None
            response.stats = broker_stats
            response.add_exception(QUERY_EXECUTION_ERROR, str(e))
        phase(BrokerQueryPhase.REDUCE, t)
        response.time_used_ms = (time.perf_counter() - start) * 1e3
        if traced_stats is not None:
            # ref: trace JSON attached to response metadata
            # (ServerQueryExecutorV1Impl.java:221-226). The flat
            # "entries" view is derived from the servers' trees (one
            # entry a span, instance-tagged); "spans" is the broker root
            # with the measured broker phases as children and every
            # server's tree — instance-tagged at gather, see _tag_trace —
            # re-parented under ScatterGather. Assembled AFTER the REDUCE
            # phase timer so the root's children account the full broker
            # wall time.
            from pinot_tpu.common.tracing import (
                build_broker_root,
                flatten_spans,
            )

            entries = traced_stats.trace + flatten_spans(traced_stats.spans)
            root = build_broker_root(
                response.phase_times_ms, traced_stats.spans,
                response.time_used_ms, admission_wait_ms=admit_wait_ms,
                reduce_folds=acc.fold_spans,
                phase_start_ms=clock.start_ms if clock else None,
                phase_cpu_ms=clock.cpu_by_phase() if clock else None,
                # the root's start on the wall clock: now, less its age
                start_epoch_ms=time.time() * 1e3
                - (time.perf_counter() - start) * 1e3,
                request_id=ctx.request_id)
            response.trace_info = {"entries": entries, "spans": [root]}
        return finish(response)

    # -- table resolution + hybrid split -------------------------------------
    # -- IN_SUBQUERY (IdSet semijoin) ---------------------------------------
    MAX_SUBQUERY_DEPTH = 3

    def _rewrite_subqueries(self, ctx: QueryContext, principal=None,
                            access_control=None) -> QueryContext:
        """``inSubquery(col, '<sql>')`` predicates: pre-execute the inner
        query (typically ``SELECT idset(col) FROM ...``), then rewrite to
        ``inIdSet(col, <serialized set>)`` so servers evaluate a plain
        membership transform (ref: the broker-side IN_SUBQUERY rewrite +
        server IdSet resolution, ServerQueryExecutorV1Impl.java:404-441)."""
        from dataclasses import replace

        from pinot_tpu.query.expressions import (
            FilterNode,
            Function,
            Literal,
        )

        if ctx.filter is None:
            return ctx

        def walk(node: FilterNode) -> FilterNode:
            if node.predicate is not None:
                p = node.predicate
                lhs = p.lhs
                if (isinstance(lhs, Function)
                        and lhs.name in ("insubquery", "in_subquery")):
                    if len(lhs.args) != 2 \
                            or not isinstance(lhs.args[1], Literal):
                        raise QueryError(
                            "inSubquery(column, 'sql literal') expected")
                    inner_sql = str(lhs.args[1].value)
                    tl = self._subq_local
                    tl.depth = getattr(tl, "depth", 0) + 1
                    try:
                        if tl.depth > self.MAX_SUBQUERY_DEPTH:
                            raise QueryError("IN_SUBQUERY nesting too deep")
                        # inner queries carry the OUTER principal: a
                        # table-scoped caller must not semijoin/probe
                        # other tables through the rewrite
                        inner = self.handle_sql(
                            inner_sql, principal=principal,
                            access_control=access_control)
                    finally:
                        tl.depth -= 1
                    if any(e.get("errorCode") == ACCESS_DENIED_ERROR
                           for e in inner.exceptions):
                        # the denial must keep its identity end to end so
                        # the REST layer returns 403, same as a direct query
                        raise AccessDeniedError(
                            f"IN_SUBQUERY inner query denied: "
                            f"{inner.exceptions[0].get('message')}")
                    if inner.has_exceptions or inner.result_table is None \
                            or not inner.result_table.rows:
                        raise QueryError(
                            f"IN_SUBQUERY inner query failed: "
                            f"{inner.exceptions[:1] or 'empty result'}")
                    if (len(inner.result_table.rows) != 1
                            or len(inner.result_table.rows[0]) != 1):
                        raise QueryError(
                            "IN_SUBQUERY inner query must return exactly "
                            "one IDSET() value (no GROUP BY)")
                    idset = inner.result_table.rows[0][0]
                    if not isinstance(idset, str):
                        raise QueryError(
                            "IN_SUBQUERY inner query must produce IDSET()")
                    new_lhs = Function("inidset",
                                       (lhs.args[0], Literal(idset)))
                    return FilterNode.pred(replace(p, lhs=new_lhs))
                return node
            kids = tuple(walk(c) for c in node.children)
            if all(a is b for a, b in zip(kids, node.children)):
                return node  # untouched subtree: no rebuild on the hot path
            return FilterNode(node.op, children=kids, predicate=None)

        new_filter = walk(ctx.filter)
        if new_filter is ctx.filter:
            return ctx
        return replace(ctx, filter=new_filter)

    def _resolve_tables(self, raw_name: str) -> List[str]:
        """'myTable' -> its physical tables; explicit _OFFLINE/_REALTIME
        names pass through (ref: table resolution via TableCache)."""
        known = set(self.store.table_names())
        if raw_name in known:
            return [raw_name]
        out = [table_name_with_type(raw_name, t)
               for t in (TableType.OFFLINE, TableType.REALTIME)
               if table_name_with_type(raw_name, t) in known]
        if not out:
            raise QueryError(f"table {raw_name!r} does not exist")
        return out

    @staticmethod
    def _hybrid_route(stats, reason: str, chosen: str,
                      declined: str) -> None:
        """Time-boundary routing outcome onto the decision ledger (the
        'hybrid' ReasonNamespace scans the first string literal)."""
        from pinot_tpu.common.tracing import record_decision

        record_decision(stats, "hybrid", chosen, declined, reason)

    def _split_hybrid(self, ctx: QueryContext, physical: List[str],
                      stats: Optional[QueryStats] = None
                      ) -> List[Tuple[str, QueryContext]]:
        """Hybrid tables get the time-boundary split
        (ref: BaseBrokerRequestHandler attachTimeBoundary :2002); every
        outcome lands on the decision ledger."""
        if len(physical) < 2:
            self._hybrid_route(stats, "hybrid_single_table", "direct",
                               "time_split")
            return [(physical[0], ctx)]
        offline = next(t for t in physical if t.endswith("_OFFLINE"))
        realtime = next(t for t in physical if t.endswith("_REALTIME"))
        cfg = self.store.get_table_config(offline)
        tc = cfg.validation_config.time_column_name if cfg else None
        boundary = self.routing.time_boundary.get_boundary(offline)
        if tc is None:
            # no time column: the split predicate can't be expressed
            self._hybrid_route(stats, "hybrid_no_time_column",
                               "realtime_all", "time_split")
            return [(realtime, ctx)]
        if boundary is None:
            # no boundary yet: realtime serves everything
            self._hybrid_route(stats, "hybrid_no_boundary",
                               "realtime_all", "time_split")
            return [(realtime, ctx)]
        self._hybrid_route(stats, "hybrid_time_split", "time_split",
                           "realtime_all")
        off_pred = FilterNode(
            FilterOp.PREDICATE,
            predicate=Predicate(PredicateType.RANGE, Identifier(tc),
                                upper=boundary, upper_inclusive=True))
        rt_pred = FilterNode(
            FilterOp.PREDICATE,
            predicate=Predicate(PredicateType.RANGE, Identifier(tc),
                                lower=boundary, lower_inclusive=False))
        return [
            (offline, replace(ctx, filter=_and(ctx.filter, off_pred))),
            (realtime, replace(ctx, filter=_and(ctx.filter, rt_pred))),
        ]

    # -- streaming scatter/gather (ref: GrpcBrokerRequestHandler +
    # StreamingReduceService): selection-only queries pull per-segment
    # blocks from ALL servers concurrently and stop the moment
    # offset+limit rows arrived — the wire analogue of
    # SelectionOnlyCombineOperator's early exit.
    def _scatter_gather_streaming(self, table: str, ctx: QueryContext,
                                  routing: Dict[str, List[str]],
                                  broker_stats: Optional[QueryStats] = None,
                                  acc=None):
        import threading

        from pinot_tpu.common.tracing import record_decision

        need = ctx.offset + ctx.limit
        queried, responded = set(), set()
        enough = threading.Event()
        lock = threading.Lock()
        have = [0]

        def pull(server, segments) -> List[DataTable]:
            out: List[DataTable] = []
            for block in server.execute_query_streaming(ctx, table,
                                                        segments):
                out.append(block)
                if not block.exceptions:
                    with lock:
                        have[0] += block.num_rows()
                        if have[0] >= need:
                            enough.set()
                if enough.is_set():
                    break
            return out

        futures = {}
        for instance_id, segments in routing.items():
            queried.add(instance_id)
            server = self._servers.get(instance_id)
            if server is None:
                futures[instance_id] = None
                continue
            futures[instance_id] = self._pool.submit(
                lambda srv=server, segs=segments: pull(srv, segs))

        gathered: List[DataTable] = []

        def took(dt: DataTable, instance_id: str) -> None:
            gathered.append(dt)
            if acc is not None:
                acc.add(dt, instance=instance_id)

        deadline = time.monotonic() + self.query_timeout_s
        for instance_id, fut in self._as_arrivals(futures, deadline):
            if fut is None:
                took(DataTable.for_exception(
                    f"server {instance_id} is not connected"), instance_id)
                record_decision(broker_stats, "gather", "partial_result",
                                "full_result", "server_not_connected")
                continue
            try:
                if isinstance(fut, FutureTimeout):
                    raise fut
                ok = False
                for dt in fut.result(timeout=0.001):
                    _tag_trace(dt, instance_id)
                    took(dt, instance_id)
                    ok = ok or not dt.exceptions
                # responded = returned at least one USABLE block; a server
                # that only errored is down for accounting purposes
                if ok:
                    responded.add(instance_id)
                else:
                    record_decision(broker_stats, "gather", "partial_result",
                                    "full_result", "server_error")
            except FutureTimeout:
                enough.set()  # stop the straggler's pull loop
                took(DataTable.for_exception(
                    f"server {instance_id} timed out after "
                    f"{self.query_timeout_s}s"), instance_id)
                record_decision(broker_stats, "gather", "partial_result",
                                "full_result", "server_timeout")
            except Exception as e:  # noqa: BLE001
                took(DataTable.for_exception(
                    f"server {instance_id} failed: {e!r}"), instance_id)
                record_decision(broker_stats, "gather", "partial_result",
                                "full_result", "server_error")
        return gathered, queried, responded

    @staticmethod
    def _as_arrivals(futures: Dict[str, object], deadline: float):
        """Yield ``(instance_id, future)`` in COMPLETION order (the
        reduce-as-arrivals contract: a fast server's table folds while
        the stragglers are still on the wire). Not-connected entries
        (None) yield first; a future still pending at the deadline
        yields a ``FutureTimeout`` instance in its place."""
        from concurrent.futures import as_completed

        pending = {}
        for instance_id, fut in futures.items():
            if fut is None:
                yield instance_id, None
            else:
                pending[fut] = instance_id
        if not pending:
            return
        try:
            for fut in as_completed(
                    pending, timeout=max(deadline - time.monotonic(),
                                         0.001)):
                yield pending.pop(fut), fut
        except FutureTimeout as e:
            for fut, instance_id in pending.items():
                yield instance_id, (fut if fut.done() else e)

    def _use_streaming(self, ctx: QueryContext,
                       routing: Dict[str, List[str]]) -> bool:
        return (ctx.is_selection and not ctx.order_by
                and not ctx.distinct
                and all(hasattr(self._servers.get(i), "execute_query_streaming")
                        for i in routing))

    # -- scatter/gather (ref: QueryRouter.submitQuery:85) --------------------
    def _scatter_gather(self, table: str, ctx: QueryContext,
                        routing: Dict[str, List[str]],
                        broker_stats: Optional[QueryStats] = None,
                        acc=None):
        """Per-server failure handling: a down / not-connected / timed-out
        server yields a partial result — its error travels as an exception
        DataTable, it is NOT counted as responded, and the reason lands on
        the decision ledger — never a hung or silently-wrong answer.

        Tables are processed in COMPLETION order and folded into ``acc``
        (the reduce accumulator) as they land — the broker reduces the
        fast servers' answers while the stragglers are still running."""
        from pinot_tpu.common.tracing import record_decision

        queried, responded = set(), set()
        futures = {}
        for instance_id, segments in routing.items():
            server = self._servers.get(instance_id)
            queried.add(instance_id)
            if server is None:
                futures[instance_id] = None
                continue
            futures[instance_id] = self._pool.submit(
                lambda srv=server, segs=segments:
                srv.execute_query(ctx, table, segs))
        gathered: List[DataTable] = []

        def took(dt: DataTable, instance_id: str) -> None:
            gathered.append(dt)
            if acc is not None:
                acc.add(dt, instance=instance_id)

        deadline = time.monotonic() + self.query_timeout_s
        for instance_id, fut in self._as_arrivals(futures, deadline):
            if fut is None:
                took(DataTable.for_exception(
                    f"server {instance_id} is not connected"), instance_id)
                record_decision(broker_stats, "gather", "partial_result",
                                "full_result", "server_not_connected")
                continue
            try:
                if isinstance(fut, FutureTimeout):
                    raise fut
                dt = fut.result(timeout=0.001)
                _tag_trace(dt, instance_id)
                took(dt, instance_id)
                # responded = came back with a USABLE DataTable; a server
                # that answered with only an error (shut down mid-scatter,
                # table not hosted) is accounted as a gather failure
                if dt.exceptions:
                    record_decision(broker_stats, "gather", "partial_result",
                                    "full_result", "server_error")
                else:
                    responded.add(instance_id)
            except FutureTimeout:
                took(DataTable.for_exception(
                    f"server {instance_id} timed out after "
                    f"{self.query_timeout_s}s"), instance_id)
                record_decision(broker_stats, "gather", "partial_result",
                                "full_result", "server_timeout")
            except Exception as e:
                took(DataTable.for_exception(
                    f"server {instance_id} failed: {e!r}"), instance_id)
                record_decision(broker_stats, "gather", "partial_result",
                                "full_result", "server_error")
        return gathered, queried, responded

    def shutdown(self) -> None:
        self._pool.stop()


class _PhaseClock:
    """A traced query's broker phases on the span tree's clock: each
    phase's first start as an offset from the root's, and the broker
    thread's CPU time in it (``build_broker_root`` reads both)."""

    __slots__ = ("origin", "cpu0", "cpu_at", "start_ms", "cpu_ms")

    def __init__(self, origin: float):
        self.origin = origin
        self.cpu0 = self.cpu_at = time.thread_time()
        self.start_ms: Dict[str, float] = {}
        self.cpu_ms: Dict[str, float] = {}

    def note(self, name: str, t0: float) -> None:
        """A phase that began at ``t0`` ends now (the admission wait is
        noted as it begins: a wait has no CPU time)."""
        self.start_ms.setdefault(name, (t0 - self.origin) * 1e3)
        now = time.thread_time()
        if name != "ADMISSION":
            self.cpu_ms[name] = self.cpu_ms.get(name, 0.0) \
                + (now - self.cpu_at) * 1e3
        self.cpu_at = now

    def cpu_by_phase(self) -> Dict[str, float]:
        """CPU ms a phase, and ``TOTAL``: all of the root's so far."""
        return dict(self.cpu_ms,
                    TOTAL=(time.thread_time() - self.cpu0) * 1e3)


def _and(a: Optional[FilterNode], b: FilterNode) -> FilterNode:
    if a is None:
        return b
    return FilterNode(FilterOp.AND, children=(a, b))


def _tag_trace(dt: DataTable, instance_id: str) -> None:
    """Attribute trace entries AND span-tree roots to their server BEFORE
    the reduce merges/re-parents them (the reference keys traceInfo per
    instance) — after the broker root adopts every server's trees, the
    per-server origin is only recoverable from these tags."""
    for e in dt.stats.trace:
        e.setdefault("instance", instance_id)
    for root in dt.stats.spans:
        root.setdefault("instance", instance_id)
