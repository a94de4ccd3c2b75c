"""Segment query executor: per-segment execution + instance-level combine.

Re-design of ``ServerQueryExecutorV1Impl.java:75`` +
``BaseCombineOperator.java:55``: dispatches each query to the device kernels
(aggregation/group-by), the host paths (selection/distinct/fallback), or the
metadata fast paths (ref: MetadataBasedAggregationOperator /
DictionaryBasedAggregationOperator, AggregationPlanNode.java:172-181), then
merges per-segment partials and reduces to a ResultTable.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.engine import host_engine
from pinot_tpu.engine.aggregates import AggDef, agg_value_expr, resolve_agg
from pinot_tpu.engine.errors import QueryError
from pinot_tpu.engine.kernels import KernelCache
from pinot_tpu.engine.plan import PlanError, SegmentPlan, plan_segment
from pinot_tpu.engine.results import (
    AggResult,
    GroupByResult,
    QueryStats,
    ResultTable,
    reduce_aggregation,
    reduce_group_by,
)
from pinot_tpu.common.tracing import (
    QueryRegistry,
    maybe_span,
    record_decision,
    start_trace,
    stats_tracer,
)
from pinot_tpu.engine.residency import ResidencyManager
from pinot_tpu.query.context import QueryContext
from pinot_tpu.query.expressions import Identifier
from pinot_tpu.segment.immutable import ImmutableSegment
from pinot_tpu.spi.config import CommonConstants


def grouped_rung(spec: Tuple, out: Dict[str, Any]) -> str:
    """Which group-by rung of the device cardinality ladder served this
    kernel output: 'dense' | 'compact' (dense scatter, compact D2H) |
    'hash' | 'sort' (the sparse rungs; 'sort' means the hash table
    overflowed and the sort fallback ran)."""
    from pinot_tpu.engine.kernels import compact_mode, sparse_mode

    if sparse_mode(spec):
        return "sort" if out.get("rung") else "hash"
    return "compact" if compact_mode(spec) else "dense"


def filter_fingerprint(ctx: QueryContext) -> str:
    """Digest of the filter tree, memoized per ctx — cache keys must
    distinguish same-SQL contexts whose filters were rewritten (hybrid
    time boundary, IN_SUBQUERY idsets)."""
    fp = getattr(ctx, "_filter_fp", None)
    if fp is None:
        import hashlib

        fp = hashlib.blake2b(str(ctx.filter).encode("utf-8"),
                             digest_size=16).hexdigest()
        ctx._filter_fp = fp
    return fp


def _segment_tracer(ctx: QueryContext, stats: QueryStats, op: str, seg):
    """``done(result, path)`` pass-through that records a per-segment SPAN
    when the query is traced (ref: TraceContext.java:46 — operator timings
    attach to the request's trace tree); the legacy flat entry is emitted
    from the span at close. Untraced queries get the zero-allocation
    pass-through."""
    rec = stats_tracer(stats)
    if rec is None:
        return lambda result, path: result
    sp = rec.span_begin(op, segment=seg.segment_name)

    def done(result, path):
        # the closure owns the span close (graftlint spanpair contract);
        # an error path that skips done() is swept closed when the parent
        # (or the root, at query teardown) ends
        rec.span_end(sp, path=path)
        return result

    return done


class ServerQueryExecutor:
    """One per server instance; owns the staging + kernel caches."""

    def __init__(self, use_device: bool = True,
                 num_groups_limit: int = CommonConstants.DEFAULT_NUM_GROUPS_LIMIT,
                 use_pallas: Optional[bool] = None,
                 hbm_budget_bytes=None, host_budget_bytes=None, config=None):
        from pinot_tpu.engine import ensure_compile_cache, ensure_x64
        from pinot_tpu.engine.pallas_kernels import PallasKernelCache
        from pinot_tpu.engine.residency import AUTO

        ensure_x64()
        ensure_compile_cache()
        self.config = config
        # HBM residency manager: budget/pins/cost-aware eviction with a
        # host-RAM spill tier + sliced/spill admission for every
        # device-resident array this executor stages. ``hbm_budget_bytes``
        # / ``host_budget_bytes``: None = resolve from the config keys
        # (pinot.server.query.hbm.budget.bytes /
        # pinot.server.query.hostram.budget.bytes) then the backend device
        # memory / psutil; <= 0 forces uncapped.
        self.residency = ResidencyManager(
            budget_bytes=AUTO if hbm_budget_bytes is None else hbm_budget_bytes,
            host_budget_bytes=(AUTO if host_budget_bytes is None
                               else host_budget_bytes),
            config=config)
        # legacy alias (pre-residency name); same object
        self.staging = self.residency
        self.kernels = KernelCache()
        # (sql, segment) -> (segment identity, SegmentPlan): the per-segment
        # analogue of the sharded executor's query cache — repeat queries
        # skip predicate translation / LUT builds. Safe because params no
        # longer embed mutable state: the upsert validdocs placeholder is
        # filled per run — immutable segments from staged.valid_mask(),
        # consuming segments from the watermark snapshot's device mask
        # (mutable_staging._serve). LRU-bounded; mutable segments bypass
        # the cache entirely (their plans are watermark-specific).
        import threading
        from collections import OrderedDict

        self._plan_cache: "OrderedDict" = OrderedDict()
        self._plan_cache_cap = 512
        self._plan_cache_lock = threading.Lock()
        self.pallas_kernels = PallasKernelCache()
        self.use_device = use_device
        # pallas kernels compile for real TPUs; on the CPU backend they run
        # only in (slow) interpret mode, so auto-enable on TPU and leave
        # interpret mode to tests that opt in explicitly
        self.use_pallas = use_pallas
        # plan.spec values whose pallas kernel failed to lower/run on this
        # backend — or that the kernel preflight predicted would — take
        # the jnp path; everything else keeps the fused kernel. Created
        # below once the config (persistence path) is resolved.
        # ordered-selection top-k kernels (engine/selection_device.py);
        # LRU-capped like the sibling caches (k rides in the key, so
        # unbounded LIMIT variety must not pin kernels forever)
        self._selection_kernels: "OrderedDict" = OrderedDict()
        # star-tree node-slice kernels (engine/startree_device.py): spec ->
        # jitted gather+aggregate fn. The spec's capacity is the pow2-padded
        # selected-record count, so variety is bounded; LRU-capped anyway
        self._startree_kernels: "OrderedDict" = OrderedDict()  # guarded-by: _startree_kernel_lock
        self._startree_kernel_lock = threading.Lock()
        self.num_groups_limit = num_groups_limit
        # segment fan-out width: pinot.server.query.worker.threads (the
        # reference's pqw pool size); default preserves the old hardcoded
        # min(cpu, 8). The pool itself is persistent and lazily built —
        # per-query ThreadPoolExecutor spawn/teardown was pure overhead on
        # the serving path.
        import os

        from pinot_tpu.spi.config import (
            CommonConstants as _CC,
            PinotConfiguration,
        )

        cfg = config if config is not None else PinotConfiguration()
        self.worker_threads = max(1, cfg.get_int(
            _CC.WORKER_THREADS_KEY, min(os.cpu_count() or 1, 8)))
        # pallas LUT interval-run cap (the "ivs" fallback bound)
        self._pallas_lut_runs = max(1, cfg.get_int(
            _CC.PALLAS_LUT_MAX_RUNS_KEY, _CC.DEFAULT_PALLAS_LUT_MAX_RUNS))
        # per-shape pallas blocklist (reason-carrying, optionally
        # persisted): runtime lowering failures + preflight-seeded shapes
        from pinot_tpu.engine.pallas_blocklist import PallasBlocklist

        self._pallas_blocked = PallasBlocklist(
            path=cfg.get(_CC.PALLAS_BLOCKLIST_PATH_KEY))
        # last kernel-preflight verdict table run against this executor
        # (tools/preflight.attach_verdicts); surfaced on GET /debug/pallas
        self.preflight_verdicts: Optional[dict] = None
        # fused-scan launches by accumulate form, group-range probes under
        # ``scalar`` (GET /debug/pallas ``launches``), and those that built
        # a one-hot by MXU contraction (``mxu``)
        self._pallas_launches = {  # guarded-by: _pallas_launches_lock
            "single": 0, "two_level": 0, "scalar": 0}
        self._pallas_mxu = {  # guarded-by: _pallas_launches_lock
            "bf16": 0, "fp32": 0}
        self._pallas_launches_lock = threading.Lock()
        self._segment_pool = None
        self._segment_pool_lock = threading.Lock()
        # request-tier admission: bounded concurrency + bounded queue in
        # front of execution; past the bound queries are REJECTED with a
        # typed retriable error instead of convoying (server/admission.py).
        # Lazy import: pinot_tpu.server pulls this module back in.
        from pinot_tpu.server.admission import AdmissionGate

        self.admission = AdmissionGate.from_config(cfg)
        # query lifecycle tracing (common/tracing.py): spans are recorded
        # when the request asks (trace=true), the sample rate hits, or a
        # slow-query threshold is configured (the registry then retains
        # over-threshold trees sampling missed). The registry also backs
        # /debug/queries (running set + completed ring).
        self.trace_sample = cfg.get_float(
            _CC.TRACE_SAMPLE_KEY, _CC.DEFAULT_TRACE_SAMPLE)
        self.queries = QueryRegistry(slow_threshold_ms=cfg.get_float(
            _CC.SLOW_THRESHOLD_MS_KEY, _CC.DEFAULT_SLOW_THRESHOLD_MS))
        # continuous telemetry (common/telemetry.py): apply config
        # (sampler resolution, SLO objectives, flight-recorder knobs) to
        # the process-wide center and register this executor's state as
        # flight-recorder bundle providers — a frozen bundle carries the
        # residency + admission snapshots of the LAST executor built
        # (one per process everywhere outside multi-instance tests)
        from pinot_tpu.common.telemetry import TELEMETRY

        TELEMETRY.configure(cfg)
        TELEMETRY.recorder.register_provider("residency",
                                             self.residency.snapshot)
        TELEMETRY.recorder.register_provider("admission",
                                             self.admission.snapshot)
        # backend selection is itself a path decision: a CPU default
        # backend is why no pallas kernel can compile — record it ONCE so
        # the ledger explains the whole pallas story, not just per-plan
        # declines
        import jax as _jax

        if _jax.default_backend() == "cpu":
            record_decision(None, "backend", "cpu", "tpu",
                            "cpu_default_backend")
        # per-segment half of the launch-coalescing contract: concurrent
        # identical kernel launches (same cached plan + same staged
        # resident) share one device program + one D2H fetch
        from pinot_tpu.common.singleflight import SingleFlight

        self._kernel_flight = SingleFlight()
        # whole-query single-flight for the direct execute() surface (the
        # embedded / bench path — the broker front door has its own): a
        # concurrent identical query (same compiled ctx object, same
        # segment objects) rides the leader's full execution instead of
        # paying its own serialized device programs
        self._query_flight = SingleFlight()

    def _pallas_mode(self) -> Optional[bool]:
        """None = disabled; True/False = enabled (interpret or compiled)."""
        import jax

        backend = jax.default_backend()
        if self.use_pallas is None:
            # auto: compiled pallas only on TPU-like backends (the kernels
            # use pltpu memory spaces and cannot lower on GPU)
            return False if backend not in ("cpu", "gpu", "cuda", "rocm") \
                else None
        if not self.use_pallas:
            return None
        if backend in ("gpu", "cuda", "rocm"):
            return None  # pltpu memory spaces cannot lower on GPU
        return backend == "cpu"  # interpret on CPU

    def _note_pallas_launch(self, spec, count: bool = True
                            ) -> Dict[str, Any]:
        """The span attributes that say which accumulate and which MXU
        contraction the fused scan of ``spec`` (its PallasSpec) takes
        (``groups`` is the kernel's ``num_groups_padded``); counts the
        launch under those names unless the caller shared another
        query's."""
        from pinot_tpu.engine.pallas_kernels import (
            spec_accumulate_kind,
            spec_mxu_kind,
        )

        kind = spec_accumulate_kind(spec)
        mxu = spec_mxu_kind(spec)
        if count:
            self._count_pallas_launch(kind, mxu)
        took = {"groups": spec.num_groups_padded, "accumulate": kind}
        if mxu is not None:
            took["mxu"] = mxu
        return took

    def _count_pallas_launch(self, kind: str,
                             mxu: Optional[str] = None) -> None:
        with self._pallas_launches_lock:
            self._pallas_launches[kind] += 1
            if mxu is not None:
                self._pallas_mxu[mxu] += 1

    def pallas_launches(self) -> Dict[str, int]:
        with self._pallas_launches_lock:
            return dict(self._pallas_launches)

    def pallas_mxu(self) -> Dict[str, int]:
        with self._pallas_launches_lock:
            return dict(self._pallas_mxu)

    # -- public ------------------------------------------------------------
    def execute_instance(self, ctx: QueryContext,
                         segments: List[ImmutableSegment]):
        """Instance-level execution returning a mergeable DataTable — the
        scatter/gather server half (ref: InstanceResponseOperator wrapping
        combine output into a serialized DataTable). The broker merges
        DataTables from all servers and reduces (BrokerReduceService).
        Admission-gated: past the bounded queue this raises a typed
        retriable QueryRejectedError BEFORE any lease/pin is taken."""
        ticket = self.admission.admit(ctx.table_name or "")
        try:
            return self._execute_instance_admitted(
                ctx, segments, admit_wait_ms=ticket.wait_ms)
        finally:
            self.admission.release(ticket)

    # -- tracing bookends ----------------------------------------------------
    def _open_query(self, ctx: QueryContext, segments,
                    admit_wait_ms: float = 0.0):
        """Create the query's stats + registry token and, when the query
        is traced (trace=true / sample hit / slow-log force), its span
        recorder and root span. The admission-gate queue wait — measured
        before stats existed — lands as the first child with full queue
        attribution."""
        stats = QueryStats(num_segments_queried=len(segments))
        stats._tel_table = ctx.table_name or ""  # telemetry attribution
        requested = ctx.trace_enabled
        if not requested and self.trace_sample > 0:
            import random

            requested = random.random() < self.trace_sample
        if requested or self.queries.force_trace:
            rec = start_trace(stats, request_id=ctx.request_id)
            stats._trace_requested = requested
            root = rec.span_begin("ServerQuery", table=ctx.table_name)
            stats._root_span = root  # closed by _close_query's close_all
            # the admission wait ended as the root opened: the root
            # starts where the wait did, so the wait lies inside it
            rec.backdate_root(admit_wait_ms)
            rec.add_completed("Admission", wall_ms=admit_wait_ms,
                              queue_ms=admit_wait_ms, start=root.t0)
        token = self.queries.begin(ctx, stats)
        stats._registry_token = token  # phase updates from inner layers
        return stats, token

    def _close_query(self, stats: QueryStats, token, error=None) -> None:
        """Query teardown: close every open span (exception edges leave
        the tree closed, never dangling), finish the registry entry (the
        slow log snapshots over-threshold trees here), and — when the
        recording was slow-log-forced rather than requested — strip the
        spans/entries off the wire payload."""
        rec = stats_tracer(stats)
        if rec is not None:
            rec.close_all()
        self.queries.end(token, error=error)
        if rec is not None and not getattr(stats, "_trace_requested", False):
            # forced recording: the slow log copied what it needed; the
            # response must look exactly like an untraced one
            stats.spans.clear()
            stats._recorder = None

    def _execute_instance_admitted(self, ctx: QueryContext,
                                   segments: List[ImmutableSegment],
                                   admit_wait_ms: float = 0.0):
        import time as _time

        from pinot_tpu.common.telemetry import observe_ms

        t0 = _time.perf_counter()
        stats, token = self._open_query(ctx, segments, admit_wait_ms)
        error = None
        try:
            return self._execute_instance_traced(ctx, segments, stats)
        except BaseException as e:
            error = e
            raise
        finally:
            self._close_query(stats, token, error=error)
            observe_ms(ctx.table_name, "server_exec",
                       (_time.perf_counter() - t0) * 1e3)

    def _execute_instance_traced(self, ctx: QueryContext,
                                 segments: List[ImmutableSegment],
                                 stats: QueryStats):
        from dataclasses import replace

        from pinot_tpu.common.datatable import DataTable

        if not segments:
            raise QueryError(f"no segments for table {ctx.table_name!r}")
        self._validate_columns(ctx, segments[0])
        segments = self._prune(ctx, segments, stats)
        lease = self._begin_lease(ctx, segments, stats)
        try:
            if ctx.distinct:
                # HAVING is broker-side (it sees the global distinct set);
                # ORDER BY stays server-side so each server ships its true
                # top rows — order-by keys are always in the distinct select
                # list, so a per-server sorted prefix of offset+limit rows
                # is sufficient
                if ctx.having is not None:
                    sub = replace(ctx, order_by=[], having=None,
                                  limit=self.num_groups_limit, offset=0)
                else:
                    sub = replace(ctx, having=None,
                                  limit=ctx.offset + ctx.limit, offset=0)
                record_decision(stats, "plan", "host_engine",
                                "device_kernel", "distinct_host_only")
                table = host_engine.execute_distinct(sub, segments, stats)
                if len(table.rows) >= self.num_groups_limit:
                    stats.num_groups_limit_reached = True
                return DataTable.for_distinct(table.schema, table.rows, stats)

            if ctx.is_selection:
                if not ctx.order_by:
                    sub = replace(ctx, limit=ctx.offset + ctx.limit, offset=0)
                    table = host_engine.execute_selection(sub, segments, stats)
                    return DataTable.for_selection(table.schema, table.rows,
                                                   stats)
                # ordered: append order-by expressions as hidden trailing
                # columns so the broker can merge-sort without re-reading
                # segments (ref: SelectionOrderByOperator rows carry
                # order-by columns)
                present = {str(e) for e in ctx.select_expressions}
                hidden = [ob.expr for ob in ctx.order_by
                          if str(ob.expr) not in present]
                sub = replace(
                    ctx,
                    select_expressions=list(ctx.select_expressions) + hidden,
                    aliases=list(ctx.aliases) + [None] * len(hidden),
                    limit=ctx.offset + ctx.limit, offset=0)
                table = self._selection(sub, segments, stats)
                # server-side ORDER-BY trim: the block ships at most
                # offset+limit rows ALREADY in query order — flagged so
                # the broker merge treats it as a pre-sorted block
                # (ref: SelectionOperatorUtils sorted-block contract)
                return DataTable.for_selection(table.schema, table.rows,
                                               stats, num_hidden=len(hidden),
                                               sorted_rows=True)

            aggs = [resolve_agg(f) for f in ctx.aggregations]
            if ctx.is_group_by:
                merged = self._execute_group_by(ctx, aggs, segments, stats)
                # the result to its wire form (every key and state encoded)
                with maybe_span(stats, "Serialize",
                                rows=len(merged.groups)):
                    if merged.trim(self.num_groups_limit):
                        stats.num_groups_limit_reached = True
                    return DataTable.for_group_by(
                        merged.groups, self._schema_types(segments[0]),
                        stats)
            merged_agg = self._execute_aggregation(ctx, aggs, segments, stats)
            with maybe_span(stats, "Serialize", rows=1):
                return DataTable.for_aggregation(merged_agg.states, stats)
        finally:
            # unpin, re-measure the residents, re-enforce the budget
            with maybe_span(stats, "Release"):
                self.residency.end_query(lease, stats)

    def execute(self, ctx: QueryContext,
                segments: List[ImmutableSegment]) -> Tuple[ResultTable, QueryStats]:
        ticket = self.admission.admit(ctx.table_name or "")
        try:
            # whole-query single-flight: the identical-dashboard-query
            # case pays ONE execution; followers share the leader's
            # (ResultTable, QueryStats) — bit-identical by construction.
            # Admission stays per caller (a coalesced request is still a
            # request; its slot releases when the shared flight resolves).
            out, _ = self._query_flight.do(
                self._query_flight_key(ctx, segments),
                lambda: self._execute_admitted(
                    ctx, segments, admit_wait_ms=ticket.wait_ms))
            return out
        finally:
            self.admission.release(ticket)

    @staticmethod
    def _query_flight_key(ctx: QueryContext, segments) -> Optional[Tuple]:
        """None = not shareable. Keyed on OBJECT identity of the compiled
        ctx and every segment: a reloaded segment (new object) or a
        re-compiled ctx never joins a stale flight, and the leader's own
        references keep the ids stable for the flight's lifetime. Mutable
        (consuming) and upsert-managed segments are excluded — their
        contents advance between two otherwise-identical executions."""
        for s in segments:
            if getattr(s, "valid_doc_ids", None) is not None \
                    or getattr(s, "is_mutable", False):
                return None
        return (id(ctx), tuple(id(s) for s in segments))

    def _execute_admitted(self, ctx: QueryContext,
                          segments: List[ImmutableSegment],
                          admit_wait_ms: float = 0.0
                          ) -> Tuple[ResultTable, QueryStats]:
        import time as _time

        from pinot_tpu.common.telemetry import observe_ms

        t0 = _time.perf_counter()
        stats, token = self._open_query(ctx, segments, admit_wait_ms)
        error = None
        try:
            return self._execute_traced(ctx, segments, stats)
        except BaseException as e:
            error = e
            raise
        finally:
            self._close_query(stats, token, error=error)
            observe_ms(ctx.table_name, "server_exec",
                       (_time.perf_counter() - t0) * 1e3)

    def _execute_traced(self, ctx: QueryContext,
                        segments: List[ImmutableSegment],
                        stats: QueryStats
                        ) -> Tuple[ResultTable, QueryStats]:
        if not segments:
            raise QueryError(f"no segments for table {ctx.table_name!r}")
        self._validate_columns(ctx, segments[0])
        segments = self._prune(ctx, segments, stats)
        lease = self._begin_lease(ctx, segments, stats)
        try:
            if ctx.distinct:
                record_decision(stats, "plan", "host_engine",
                                "device_kernel", "distinct_host_only")
                return (host_engine.execute_distinct(ctx, segments, stats),
                        stats)
            if ctx.is_selection:
                return self._selection(ctx, segments, stats), stats

            aggs = [resolve_agg(f) for f in ctx.aggregations]
            if ctx.is_group_by:
                merged = self._execute_group_by(ctx, aggs, segments, stats)
                if merged.trim(self.num_groups_limit):
                    stats.num_groups_limit_reached = True
                schema_types = self._schema_types(segments[0])
                return reduce_group_by(ctx, aggs, merged, schema_types), stats

            merged_agg = self._execute_aggregation(ctx, aggs, segments, stats)
            return reduce_aggregation(ctx, aggs, merged_agg), stats
        finally:
            # unpin, re-measure the residents, re-enforce the budget
            with maybe_span(stats, "Release"):
                self.residency.end_query(lease, stats)

    def _begin_lease(self, ctx: QueryContext,
                     segments: List[ImmutableSegment], stats: QueryStats):
        """Open the residency lease for this query: admission decides
        device vs sliced-device vs host-spill, the lease pins every
        resident the query stages until ``end_query`` (a sliced lease
        releases pins at slice boundaries instead). Only aggregation /
        group-by shapes are sliceable — their partials merge with the
        existing combine merges; selection/distinct keep the old
        fit-or-spill admission. Host-only executors skip the protocol
        entirely (they stage nothing)."""
        token = getattr(stats, "_registry_token", None)
        if token is not None:
            token["phase"] = "staging"
        if not self.use_device:
            record_decision(stats, "backend", "host_engine", "device",
                            "device_disabled")
            return None
        sliceable = not ctx.distinct and not ctx.is_selection
        with maybe_span(stats, "Lease", segments=len(segments)) as sp:
            lease = self.residency.begin_query(
                segments, ctx.referenced_columns(), sliceable=sliceable,
                devices=self._stage_devices(ctx, segments))
            if sp is not None:
                sp.attrs.update(sliced=lease.sliced, spilled=lease.spilled,
                                reason=lease.admit_reason)
        if not lease.device_allowed:
            record_decision(stats, "residency", "host_engine", "device",
                            lease.admit_reason)
        elif lease.sliced:
            record_decision(stats, "residency", "sliced_device",
                            "resident_device", lease.admit_reason)
        stats._staging_lease = lease
        return lease

    def _stage_devices(self, ctx: QueryContext,
                       segments: List[ImmutableSegment]) -> int:
        """Devices this executor spreads what a query stages over: one."""
        return 1

    @staticmethod
    def _lease_of(stats: QueryStats):
        return getattr(stats, "_staging_lease", None)

    def _device_admitted(self, stats: QueryStats) -> bool:
        """False when admission spilled this query to the host engine."""
        lease = self._lease_of(stats)
        return lease is None or lease.device_allowed

    def evict_segment(self, segment_name: str) -> None:
        """Drop a segment's device arrays (unassignment / reload hook)."""
        self.residency.evict(segment_name)

    def _prune(self, ctx: QueryContext, segments: List[ImmutableSegment],
               stats: QueryStats) -> List[ImmutableSegment]:
        """Server-side pruning before planning/staging (ref:
        SegmentPrunerService at ServerQueryExecutorV1Impl:277). At least
        one segment is kept so result-shape machinery (schema derivation,
        identity aggregation states) runs unchanged — a provably-empty
        scan of one segment is cheap and exact."""
        import time as _time

        from pinot_tpu.engine.pruner import prune_segments
        from pinot_tpu.spi.metrics import ServerQueryPhase

        t0 = _time.perf_counter()
        with maybe_span(stats, "Prune", segments=len(segments)) as sp:
            if sp is None:
                kept = prune_segments(ctx, segments, stats)
            else:       # traced: how many each kind of proof excluded
                why: Dict[str, int] = {}
                kept = prune_segments(ctx, segments, stats, why)
                sp.attrs.update(kept=len(kept), **why)
        stats.add_phase_ms(ServerQueryPhase.SEGMENT_PRUNING,
                           (_time.perf_counter() - t0) * 1e3)
        if not kept:
            kept = segments[:1]
            stats.num_segments_pruned -= 1
        # totalDocs covers ALL acquired segments (ref: the reference adds
        # pruned segments' docs to numTotalDocs); processed segments add
        # theirs during execution
        kept_names = {s.segment_name for s in kept}
        stats.total_docs += sum(s.num_docs for s in segments
                                if s.segment_name not in kept_names)
        return kept

    # -- aggregation (no group-by) ----------------------------------------
    def _execute_aggregation(self, ctx: QueryContext, aggs: List[AggDef],
                             segments: List[ImmutableSegment],
                             stats: QueryStats) -> AggResult:
        parts = self._map_segments(
            lambda seg, st: self._segment_aggregation(ctx, aggs, seg, st),
            segments, stats)
        merged: Optional[AggResult] = None
        with maybe_span(stats, "CombineSegments", segments=len(parts)):
            for part in parts:
                if merged is None:
                    merged = part
                else:
                    merged.merge(part, aggs)
        return merged

    def _map_segments(self, fn, segments: List[ImmutableSegment],
                      stats: QueryStats) -> List[Any]:
        """Per-segment execution on the persistent worker pool (ref: the
        reference's combine runs segment plans on a sized executor pool,
        BaseCombineOperator.java:55 + the pqw server pool). The numpy-heavy
        host families (sketch builds, sorts, percentiles) release the GIL,
        so segments overlap on multi-core servers; each task gets a private
        QueryStats merged in-order afterwards (QueryStats mutation is not
        thread-safe). Sized by pinot.server.query.worker.threads; the pool
        is shared across concurrent queries, so the thread count is a
        server-level bound instead of multiplying per in-flight query.

        A SLICED lease serializes the fan-out instead: each segment is a
        budget slice — stage, execute, then unpin + demote-to-host before
        the next segment stages — so a working set far over the HBM budget
        still rides the device kernels one segment at a time."""
        token = getattr(stats, "_registry_token", None)
        if token is not None:
            token["phase"] = "executing"
        lease = self._lease_of(stats)
        if lease is not None and lease.sliced:
            parts = []
            for seg in segments:
                parts.append(fn(seg, stats))
                self.residency.release_slice(lease)
            return parts
        if self.worker_threads <= 1 or len(segments) <= 1:
            return [fn(seg, stats) for seg in segments]
        pool = self._worker_pool()
        parent = stats_tracer(stats)
        locals_ = [QueryStats() for _ in segments]
        for st in locals_:  # the pin set must ride into worker threads
            st._staging_lease = lease
            st._tel_table = getattr(stats, "_tel_table", "")
            if parent is not None:
                # recorders are thread-confined: each worker records into
                # its private stats, on the query's clock; merge() below
                # re-parents the finished spans under the caller's open
                # span
                start_trace(st, parent=parent)
        if parent is not None:
            import time as _time

            t_submit = _time.perf_counter()
            segment_fn = fn

            def picked_up(seg, st):
                # submit until a worker picks the task up: a pure wait
                waited = (_time.perf_counter() - t_submit) * 1e3
                stats_tracer(st).add_completed(
                    "SegmentQueue", wall_ms=waited, queue_ms=waited,
                    start=t_submit, segment=seg.segment_name)
                return segment_fn(seg, st)

            fn = picked_up
        parts = pool.map(fn, segments, locals_)
        for st in locals_:
            rec = stats_tracer(st)
            if rec is not None:
                rec.close_all()
            stats.merge(st)
        return parts

    def _worker_pool(self):
        """Lazily-built persistent segment-fanout pool (daemon threads;
        spawn once per executor, not once per query)."""
        pool = self._segment_pool
        if pool is None:
            from pinot_tpu.server.scheduler import WorkerPool

            with self._segment_pool_lock:
                pool = self._segment_pool
                if pool is None:
                    pool = WorkerPool(self.worker_threads, name="pqw")
                    self._segment_pool = pool
        return pool

    def close(self) -> None:
        """Drain the worker pool (server shutdown hook). Safe to reuse the
        executor afterwards: the pool rebuilds lazily on the next fan-out."""
        with self._segment_pool_lock:
            pool, self._segment_pool = self._segment_pool, None
        if pool is not None:
            pool.stop()

    def _segment_aggregation(self, ctx: QueryContext, aggs: List[AggDef],
                             seg: ImmutableSegment,
                             stats: QueryStats) -> AggResult:
        done = _segment_tracer(ctx, stats, "SegmentAggregate", seg)

        fast = self._metadata_fast_path(ctx, aggs, seg, stats)
        if fast is not None:
            return done(fast, "metadata")
        st = self._try_star_tree(ctx, aggs, seg, stats)
        if st is not None:
            result, rung = st
            return done(result, rung)
        if self.use_device and self._device_admitted(stats):
            if getattr(seg, "is_mutable", False):
                from pinot_tpu.engine import mutable_staging

                res = mutable_staging.serve_aggregation(self, ctx, aggs,
                                                        seg, stats)
                if res is not None:
                    return done(res, "mutable_device")
            else:
                from pinot_tpu.engine import index_exec

                ix = index_exec.try_index_rung(self, ctx, aggs, seg, stats,
                                               grouped=False)
                if ix is not None:
                    return done(ix, "index")
                try:
                    plan = self._plan_for(ctx, seg, stats)
                    return done(self._run_device_scalar(plan, seg, stats),
                                "device")
                except PlanError as e:
                    record_decision(stats, "plan", "host_engine",
                                    "device_kernel", e.reason_code)
        with maybe_span(stats, "HostScan", segment=seg.segment_name):
            return done(host_engine.host_aggregate_segment(ctx, aggs, seg,
                                                           stats), "host")

    def _selection(self, ctx: QueryContext,
                   segments: List[ImmutableSegment],
                   stats: QueryStats) -> ResultTable:
        """Selection with the ordered top-k scan on device when eligible
        (engine/selection_device.py); host numpy path otherwise."""
        if self.use_device and ctx.order_by and self._device_admitted(stats):
            from pinot_tpu.engine.selection_device import device_selection

            table = device_selection(ctx, segments, self.residency,
                                     self._selection_kernels, stats)
            if table is not None:
                return table
            record_decision(stats, "selection", "host_engine",
                            "device_topk", "selection_not_device_eligible")
        with maybe_span(stats, "HostSelection"):
            return host_engine.execute_selection(ctx, segments, stats)

    def _star_tree_pick(self, ctx: QueryContext, aggs: List[AggDef],
                        seg: ImmutableSegment, on_decline=None):
        """StarTreePick(tree, index, predicates) for the CHEAPEST fitting
        tree when one exists and the option allows it, else None — the
        single gate for both executors. ``on_decline`` receives the
        most-specific reason code when trees exist but none fits (the
        decision ledger's hook)."""
        from pinot_tpu.engine import startree_exec

        if ctx.options.get("useStarTree", "true").lower() == "false":
            return None  # operator opt-out, not a decline
        return startree_exec.pick_star_tree(ctx, aggs, seg,
                                            on_decline=on_decline)

    def _startree_kernel(self, spec: Tuple):
        """spec -> jitted star-tree node-slice kernel (LRU-capped)."""
        from pinot_tpu.engine.startree_device import build_startree_kernel

        with self._startree_kernel_lock:
            k = self._startree_kernels.get(spec)
            if k is not None:
                self._startree_kernels.move_to_end(spec)
                return k
        k = build_startree_kernel(spec)
        with self._startree_kernel_lock:
            cur = self._startree_kernels.setdefault(spec, k)
            self._startree_kernels.move_to_end(spec)
            if len(self._startree_kernels) > 256:
                self._startree_kernels.popitem(last=False)
            return cur

    def _index_kernel(self, spec: Tuple):
        """spec -> jitted index-rung docId-gather kernel. Shares the
        star-tree kernel LRU under a distinct key: the gather differs
        (dictvals stay un-gathered — they're dictId-shaped), so the two
        rungs never alias a cache entry."""
        from pinot_tpu.engine.index_exec import build_gather_kernel

        key = ("index", spec)
        with self._startree_kernel_lock:
            k = self._startree_kernels.get(key)
            if k is not None:
                self._startree_kernels.move_to_end(key)
                return k
        k = build_gather_kernel(spec)
        with self._startree_kernel_lock:
            cur = self._startree_kernels.setdefault(key, k)
            self._startree_kernels.move_to_end(key)
            if len(self._startree_kernels) > 256:
                self._startree_kernels.popitem(last=False)
            return cur

    def _try_star_tree(self, ctx: QueryContext, aggs: List[AggDef],
                       seg: ImmutableSegment, stats: QueryStats):
        """Pre-aggregated path when a star-tree fits the query
        (ref: AggregationGroupByOrderByPlanNode.java:66-87 selection).
        Returns ``(result, rung)`` — rung 'startree_device' when the node
        arrays served through the device kernels, 'startree' for the host
        walker — or None (no fit / untranslatable predicate -> scan)."""
        import time as _time

        from pinot_tpu.engine import startree_device, startree_exec

        def declined(reason: str) -> None:
            record_decision(stats, "startree", "scan", "startree", reason)

        if not getattr(seg, "star_trees", None):
            return None  # no trees: nothing to walk, and not a decline
        with maybe_span(stats, "StarTreeWalk",
                        segment=seg.segment_name) as sp:
            pick = self._star_tree_pick(ctx, aggs, seg, on_decline=declined)
            if pick is None:
                return None
            tree, tree_index, preds = pick
            matches = startree_exec.resolve_matches(seg, preds,
                                                    on_decline=declined)
            if matches is None:
                return None  # predicate not dictId-translatable -> scan
            group_cols = [e.name for e in ctx.group_by]
            if sp is None:
                idx = tree.select_records(matches, group_cols)
            else:
                # traced: what the walk did (nodes, emitted, gathered) and
                # the wall time of select_records alone
                walk: Dict[str, int] = {}
                t0 = _time.perf_counter()
                idx = tree.select_records(matches, group_cols, walk)
                sp.attrs.update(
                    tree=tree_index, records=int(idx.shape[0]), **walk,
                    selectMs=round((_time.perf_counter() - t0) * 1e3, 3))

        def chose(rung: str) -> None:
            # the CHOSEN tree rides the ledger and QueryStats: with
            # multiple trees per segment, "which tree served" is the
            # fact the bench records per query (startree:scan->
            # startree_device:tree<i>)
            record_decision(stats, "startree", rung, "scan",
                            f"tree{tree_index}")
            stats.startree_tree_index = tree_index

        if self.use_device and self._device_admitted(stats):
            try:
                res = startree_device.execute_star_tree_device(
                    self, ctx, aggs, seg, tree, matches, stats,
                    tree_index=tree_index, idx=idx)
                if res is not None:
                    chose("startree_device")
                    return res, "startree_device"
            except PlanError as e:
                # node plan over device limits -> host walker
                record_decision(stats, "startree", "startree_host",
                                "startree_device", e.reason_code)
        res = startree_exec.execute_with_matches(ctx, aggs, seg, tree,
                                                 matches, stats, idx=idx)
        if res is None:
            # the host walker refused a tree the pick accepted (defensive:
            # the fit re-check inside execute_with_matches disagreed) —
            # the scan serves, and the ledger says why
            declined("startree_walker_declined")
            return None
        chose("startree")
        return res, "startree"

    def _metadata_fast_path(self, ctx: QueryContext, aggs: List[AggDef],
                            seg: ImmutableSegment,
                            stats: QueryStats) -> Optional[AggResult]:
        """Filter-less COUNT(*)/MIN/MAX answered from metadata
        (ref: MetadataBasedAggregationOperator, DictionaryBasedAggregationOperator)."""
        if ctx.filter is not None or ctx.is_group_by:
            return None
        if getattr(seg, "is_mutable", False):
            # consuming segment: live dictionary min/max can include an
            # in-flight (unpublished) row — answer from a real scan
            return None
        if getattr(seg, "valid_doc_ids", None) is not None:
            # upsert: metadata counts/extremes include invalidated docs
            # (ref: the fast paths require allDocsMatch + no validDocIds)
            return None
        states: List[Any] = []
        for agg, fn in zip(aggs, ctx.aggregations):
            vexpr = agg_value_expr(fn)
            if agg.base == "count" and not agg.mv and vexpr is None:
                states.append(seg.num_docs)
                continue
            if (agg.base in ("min", "max", "minmaxrange") and not agg.mv
                    and isinstance(vexpr, Identifier)):
                cm = seg.metadata.columns.get(vexpr.name)
                if (cm is not None and cm.data_type.is_numeric
                        and not cm.has_nulls and cm.min_value is not None):
                    lo, hi = float(cm.min_value), float(cm.max_value)
                    states.append(lo if agg.base == "min" else
                                  hi if agg.base == "max" else (lo, hi))
                    continue
            return None
        stats.num_segments_processed += 1
        stats.num_segments_matched += 1
        stats.total_docs += seg.num_docs
        return AggResult(states)

    def _run_device_scalar(self, plan: SegmentPlan, seg: ImmutableSegment,
                           stats: QueryStats) -> AggResult:
        served = self._try_pallas(plan, seg, stats)
        if served is not None:
            out, plan = served
        else:
            out = self._run_kernel(plan, seg, stats)
        with maybe_span(stats, "Decode"):
            return decode_scalar_result(plan, seg, out)

    # -- group-by ----------------------------------------------------------
    def _execute_group_by(self, ctx: QueryContext, aggs: List[AggDef],
                          segments: List[ImmutableSegment],
                          stats: QueryStats) -> GroupByResult:
        merged = GroupByResult()
        parts = self._map_segments(
            lambda seg, st: self._segment_group_by(ctx, aggs, seg, st),
            segments, stats)
        with maybe_span(stats, "CombineSegments", segments=len(parts)):
            for part in parts:
                merged.merge(part, aggs)
        return merged

    def _segment_group_by(self, ctx: QueryContext, aggs: List[AggDef],
                          seg: ImmutableSegment,
                          stats: QueryStats) -> GroupByResult:
        done = _segment_tracer(ctx, stats, "SegmentGroupBy", seg)

        st = self._try_star_tree(ctx, aggs, seg, stats)
        if st is not None:
            result, rung = st
            stats.group_by_rung = rung
            return done(result, rung)
        if self.use_device and self._device_admitted(stats):
            if getattr(seg, "is_mutable", False):
                from pinot_tpu.engine import mutable_staging

                res = mutable_staging.serve_group_by(self, ctx, aggs,
                                                     seg, stats)
                if res is not None:
                    stats.group_by_rung = "mutable_device"
                    return done(res, "mutable_device")
            else:
                from pinot_tpu.engine import index_exec

                ix = index_exec.try_index_rung(self, ctx, aggs, seg, stats,
                                               grouped=True)
                if ix is not None:
                    stats.group_by_rung = "index"
                    return done(ix, "index")
                try:
                    plan = self._plan_for(ctx, seg, stats)
                    return done(self._run_device_grouped(plan, seg, stats),
                                "device")
                except PlanError as e:
                    record_decision(stats, "plan", "host_engine",
                                    "device_kernel", e.reason_code)
        stats.group_by_rung = "host"
        with maybe_span(stats, "HostScan", segment=seg.segment_name):
            return done(host_engine.host_group_by_segment(ctx, aggs, seg,
                                                          stats), "host")

    def _plan_for(self, ctx: QueryContext, seg: ImmutableSegment,
                  stats: Optional[QueryStats] = None):
        """plan_segment with an LRU keyed on (sql, segment); a reloaded
        segment (new object, same name) misses via the identity check."""
        with maybe_span(stats, "Plan", cacheHit=False) as sp:
            plan, hit = self._plan_cached(ctx, seg)
            if sp is not None:
                sp.attrs["cacheHit"] = hit
        return plan

    def _plan_cached(self, ctx: QueryContext, seg: ImmutableSegment):
        """-> (plan, whether the cache held it)."""
        if ctx.sql is None:
            return plan_segment(ctx, seg), False
        import weakref

        # the key carries: a filter FINGERPRINT (the hybrid split and the
        # IN_SUBQUERY rewrite change ctx.filter under the SAME sql) and
        # bitmap presence (a valid-doc bitmap attached after caching must
        # not serve the no-validdocs plan). The fingerprint is a digest
        # memoized per ctx — str(filter) can embed large idset literals and
        # must not be rebuilt per segment. The segment rides as a weakref:
        # entries must not pin unloaded segments + their LUT params alive.
        key = (ctx.sql, filter_fingerprint(ctx), seg.segment_name,
               getattr(seg, "valid_doc_ids", None) is not None)
        with self._plan_cache_lock:
            hit = self._plan_cache.get(key)
            if hit is not None and hit[0]() is seg:
                self._plan_cache.move_to_end(key)
                return hit[1], True
        plan = plan_segment(ctx, seg)
        with self._plan_cache_lock:
            self._plan_cache[key] = (weakref.ref(seg), plan)
            if len(self._plan_cache) > self._plan_cache_cap:
                self._plan_cache.popitem(last=False)
        return plan, False

    def _run_device_grouped(self, plan: SegmentPlan, seg: ImmutableSegment,
                            stats: QueryStats) -> GroupByResult:
        served = self._try_pallas(plan, seg, stats)
        if served is not None:
            # decode against the EFFECTIVE plan: the probe-narrowed shape
            # (large sparse key spaces) carries its own strides/bases
            out, plan = served
        else:
            out = self._run_kernel(plan, seg, stats)
        with maybe_span(stats, "Decode"):
            result = decode_grouped_result(plan, seg, out)
        stats.group_by_rung = grouped_rung(plan.spec, out)
        return result

    def _try_pallas(self, plan: SegmentPlan, seg: ImmutableSegment,
                    stats: QueryStats
                    ) -> Optional[Tuple[Dict[str, Any], SegmentPlan]]:
        """Fused Pallas scan when the plan is eligible; returns the
        unpacked output tree (same shape as the jnp kernel's) plus the
        EFFECTIVE plan it decodes against (the original, or the
        probe-narrowed plan for large-group shapes), or None."""
        from pinot_tpu.engine import pallas_kernels
        from pinot_tpu.engine.kernels import fetch_outputs, unpack_outputs

        interpret = self._pallas_mode()
        if interpret is None:
            # auto mode on a non-TPU backend is a BACKEND decision, not a
            # pallas-eligibility one: it records under the backend point
            # so the ledger still explains the fallback per query, while
            # the pallas histogram (and its decline-burst trigger) stays
            # reserved for real eligibility gaps. Explicit config
            # (use_pallas=False / GPU) keeps the pallas-point record.
            point = "backend" if self.use_pallas is None else "pallas"
            record_decision(stats, point, "jnp_kernel", "pallas_kernel",
                            "pallas_disabled_on_backend")
            return None
        if plan.spec in self._pallas_blocked:
            # preflight-seeded shapes decline with their predicted rule
            # (pallas_preflight_*); runtime failures keep the generic code
            record_decision(stats, "pallas", "jnp_kernel", "pallas_kernel",
                            self._pallas_blocked.reason_for(plan.spec))
            return None
        with maybe_span(stats, "Stage", segment=seg.segment_name):
            staged = self.residency.stage(seg, lease=self._lease_of(stats))

        def declined(reason: str) -> None:
            record_decision(stats, "pallas", "jnp_kernel", "pallas_kernel",
                            reason)

        def launch():
            specs = []
            with maybe_span(stats, "Dispatch"):
                served = pallas_kernels.run_segment(
                    plan, staged, self.pallas_kernels, interpret,
                    on_decline=declined, lut_run_cap=self._pallas_lut_runs,
                    on_probe=self._count_pallas_launch,
                    on_launch=specs.append)
            if served is None:
                return None
            packed, eff = served
            return unpack_outputs(fetch_outputs(stats, packed),
                                  eff.spec), eff, specs[-1]

        try:
            # per-segment coalescing contract: concurrent identical queries
            # (same cached plan object, same staged resident) share ONE
            # fused-kernel launch + ONE D2H; followers decode the shared
            # tree. id()-keying is sound because the leader's closure pins
            # both objects alive for the flight's lifetime.
            import time as _time

            from pinot_tpu.common.telemetry import observe_ms

            t0 = _time.perf_counter()
            with maybe_span(stats, "Kernel", kernel="pallas",
                            segment=seg.segment_name) as sp:
                served, shared = self._kernel_flight.do(
                    ("pallas", id(plan), id(staged)), launch)
                took = (self._note_pallas_launch(served[2],
                                                 count=not shared)
                        if served is not None else {})
                if sp is not None:
                    sp.attrs.update(took, served=served is not None)
            observe_ms(getattr(stats, "_tel_table", ""), "kernel",
                       (_time.perf_counter() - t0) * 1e3)
        except Exception:  # lowering/compile failure -> jnp kernels
            import logging

            logging.getLogger(__name__).exception(
                "pallas kernel failed; disabling pallas for this QUERY "
                "SHAPE (other shapes keep the fused path)")
            # per-SPEC blocklist, not a process-wide kill switch: one
            # Mosaic-unlowerable shape must not cost every other query
            # its fused kernel
            self._pallas_blocked.add(plan.spec)
            declined("pallas_exec_failed")
            return None
        if served is None:
            return None  # run_segment recorded its own reason (on_decline)
        self._track_kernel_stats(served[0], seg, stats)
        return served[:2]

    # -- shared ------------------------------------------------------------
    def _run_kernel(self, plan: SegmentPlan, seg: ImmutableSegment,
                    stats: QueryStats) -> Dict[str, Any]:
        from pinot_tpu.engine.kernels import fetch_outputs, unpack_outputs

        with maybe_span(stats, "Stage", segment=seg.segment_name):
            staged = self.residency.stage(seg, lease=self._lease_of(stats))
        has_validdocs = plan.spec[0][:1] == ("and",) \
            and plan.spec[0][1][0] == ("validdocs",)

        def launch():
            with maybe_span(stats, "Dispatch"):
                cols = {name: staged.column(name).tree()
                        for name in plan.columns}
                kernel = self.kernels.get(plan.spec)
                params = tuple(plan.params)
                if has_validdocs:
                    # fill the planner's placeholder (staging owns the
                    # snapshot build + version-keyed device cache)
                    params = (staged.valid_mask(),) + params[1:]
                packed = kernel(cols, params, np.int32(seg.num_docs))
            # one D2H fetch for the whole output tree (each transfer is a
            # host<->device round trip; see kernels.output_layout)
            return unpack_outputs(fetch_outputs(stats, packed), plan.spec)

        # per-segment coalescing: identical concurrent queries (same cached
        # plan object + same staged resident) share one launch + D2H.
        # Upsert-managed plans are excluded — their valid mask advances
        # between calls, so two launches are NOT interchangeable.
        import time as _time

        from pinot_tpu.common.telemetry import observe_ms

        key = None if has_validdocs else ("seg", id(plan), id(staged))
        t0 = _time.perf_counter()
        with maybe_span(stats, "Kernel", kernel="jnp",
                        segment=seg.segment_name):
            out, _ = self._kernel_flight.do(key, launch)
        observe_ms(getattr(stats, "_tel_table", ""), "kernel",
                   (_time.perf_counter() - t0) * 1e3)
        self._track_kernel_stats(out, seg, stats)
        return out

    def _track_kernel_stats(self, out: Dict[str, Any], seg: ImmutableSegment,
                            stats: QueryStats) -> None:
        stats.num_segments_processed += 1
        stats.total_docs += seg.num_docs
        matched = int(out.get("num_matched",
                              np.asarray(out.get("presence", [0])).sum()))
        stats.num_docs_scanned += matched
        stats.num_segments_matched += 1 if matched else 0

    def _validate_columns(self, ctx: QueryContext,
                          seg: ImmutableSegment) -> None:
        from pinot_tpu.engine.host_eval import VIRTUAL_COLUMNS

        known = set(seg.metadata.columns.keys()) | set(VIRTUAL_COLUMNS)
        for c in ctx.referenced_columns():
            if c not in known:
                raise QueryError(f"unknown column {c!r} in table "
                                 f"{ctx.table_name!r}")

    def _schema_types(self, seg: ImmutableSegment) -> Dict[str, str]:
        from pinot_tpu.engine.host_eval import VIRTUAL_COLUMNS

        out = {name: cm.data_type.label
               for name, cm in seg.metadata.columns.items()}
        out.update(VIRTUAL_COLUMNS)
        return out


# --------------------------------------------------------------------------
# kernel-output decode (shared with the sharded combine path, which merges
# partials on device and decodes against the batch's unified dictionaries)
# --------------------------------------------------------------------------

def decode_scalar_result(plan: SegmentPlan, provider: Any,
                         out: Dict[str, Any]) -> AggResult:
    """``provider`` is anything with ``data_source(col).dictionary`` —
    an ImmutableSegment or a SegmentBatch."""
    states: List[Any] = []
    for i, aspec in enumerate(plan.spec[1]):
        raw = out[f"agg{i}"]
        states.append(_decode_scalar_state(aspec, raw, provider))
    return AggResult(states)


def _decode_scalar_state(aspec: Tuple, raw: Any, provider: Any) -> Any:
    base = aspec[0]
    if base == "distinctcount":
        presence = np.asarray(raw)
        ids = np.nonzero(presence)[0]
        d = provider.data_source(aspec[1]).dictionary
        return frozenset(d.get_values(ids))
    if base == "distinctcounthll":
        from pinot_tpu.utils.hll import HyperLogLog

        regs = np.asarray(raw).astype(np.uint8)
        return HyperLogLog(aspec[2], regs).serialize()
    if base == "count":
        return int(raw)
    if base in ("sum", "min", "max"):
        return float(raw)
    if base == "avg":
        return (float(raw[0]), int(raw[1]))
    if base == "minmaxrange":
        return (float(raw[0]), float(raw[1]))
    raise AssertionError(base)


def decode_grouped_result(plan: SegmentPlan, provider: Any,
                          out: Dict[str, Any]) -> GroupByResult:
    presence = np.asarray(out["presence"])
    gidx = np.nonzero(presence)[0]
    result = GroupByResult()
    if gidx.size == 0:
        return result

    # decode composed keys -> per-column dictIds -> values, using the
    # planner's own strides and bases (single source of truth for key
    # layout; gdict bases are nonzero when the filter narrowed the column's
    # dictId range)
    cards = plan.group_cards
    strides = plan.group_strides.astype(np.int64)
    bases = plan.group_bases or [0] * len(cards)
    key_cols: List[List[Any]] = []
    for i, ((strat, payload), card) in enumerate(zip(plan.group_defs, cards)):
        dids = (gidx // strides[i]) % card
        base = int(bases[i])
        if strat == "gdict":
            d = provider.data_source(payload).dictionary
            key_cols.append(d.get_values(dids + base))
        elif strat == "graw":  # value-space (base = the column's min value)
            key_cols.append([int(x) + base for x in dids])
        else:  # gexpr: the def carries the expression's lower bound
            key_cols.append([int(x) + int(payload) for x in dids])
    keys = list(zip(*key_cols))

    agg_specs = plan.spec[1]
    states_per_agg: List[List[Any]] = []
    for i, aspec in enumerate(agg_specs):
        raw = out[f"agg{i}"]
        base = aspec[0]
        if base == "count":
            arr = np.asarray(raw)[gidx]
            states_per_agg.append([int(v) for v in arr])
        elif base in ("sum", "min", "max"):
            arr = np.asarray(raw)[gidx]
            states_per_agg.append([float(v) for v in arr])
        elif base == "avg":
            s = np.asarray(raw[0])[gidx]
            c = np.asarray(raw[1])[gidx]
            states_per_agg.append([(float(a), int(b)) for a, b in zip(s, c)])
        elif base == "minmaxrange":
            lo = np.asarray(raw[0])[gidx]
            hi = np.asarray(raw[1])[gidx]
            states_per_agg.append([(float(a), float(b)) for a, b in zip(lo, hi)])
        elif base == "distinctcounthll":
            from pinot_tpu.utils.hll import HyperLogLog

            log2m = aspec[2]
            regs = np.asarray(raw).reshape(-1, 1 << log2m)[gidx]
            states_per_agg.append(
                [HyperLogLog(log2m, r.astype(np.uint8)).serialize()
                 for r in regs])
        else:
            raise AssertionError(base)

    for gi, key in enumerate(keys):
        result.groups[key] = [states_per_agg[ai][gi]
                              for ai in range(len(plan.agg_defs))]
    return result
