"""Query lifecycle tracing: span trees + the path-decision ledger.

Re-design of the reference's request-scoped tracing
(``TraceContext.java:46`` — per-operator trace trees attached to traced
requests — plus the ``ServerQueryPhase``/``BrokerQueryPhase`` timer
pyramid and the broker slow-query log), with one addition the reference
never had: a **decision ledger** that records WHY execution declined a
faster path.

Two data products ride together:

- **Span trees** (:class:`Span` / :class:`SpanRecorder`): a hierarchical
  record of the full query lifecycle — broker parse/route/scatter ->
  server admission queue -> scheduler queue -> residency lease ->
  launch-dispatcher queue + vmap batch -> per-segment walk, plan,
  dispatch, device wait, D2H, decode -> sharded combine -> broker
  reduce. Every span carries wall ms, its start as an offset from its
  root's (``startMs``), the CPU time of the thread that ran it
  (``cpuMs``; 0 for a pure wait), that thread's name, an explicit
  queue-vs-work split (``queueMs``/``workMs``) where a queue exists, and
  structured attributes; each root (``BrokerQuery``, ``ServerQuery``)
  also its start on the wall clock (``startEpochMs``) and the request's
  id. A span's self time is its ``ms`` less the union of its children's
  intervals. Server trees ship on the DataTable wire
  (``QueryStats.spans``) and are re-parented under the broker root at
  reduce; the flat ``traceInfo["entries"]`` view is derived from the
  tree (:func:`flatten_spans`), never kept beside it. Every recorded
  span is also a ``jax.profiler.TraceAnnotation``: under a profiler
  session the program's spans lie on the device trace's clock.
- **The decision ledger**: every point where execution declines a faster
  rung emits a machine-readable ``(decision_point, chosen, declined,
  reason_code)`` record — pallas eligibility, star-tree fit, residency
  spill/slice, backend selection, host-engine fallbacks. Records
  aggregate into ``QueryStats.decisions`` (summed at merge) and into the
  process-level :data:`LEDGER` histogram surfaced on ``/metrics`` — the
  forensics the "why did pallas never fire" question needs.

Cost model: spans are recorded only when a recorder is attached to the
stats (``trace=true``, the ``pinot.server.query.trace.sample`` rate, or
a configured slow-query threshold); the off path pays one ``getattr``
per site. Reason-code counters are always on — they fire only at decline
points, which are off the resident fast path.
"""

from __future__ import annotations

import re
import threading
import time

from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

# span-dict keys the serializer owns; attributes must not collide
_RESERVED = ("name", "ms", "queueMs", "workMs", "children", "startMs",
             "cpuMs", "thread", "startEpochMs", "requestId")

_ANNOTATION: Any = None  # jax.profiler.TraceAnnotation, bound on first use


def annotate(name: str, request_id: Optional[str]):
    """An entered ``jax.profiler.TraceAnnotation`` for one recorded span:
    with a profiler session open the span lands on its thread's line of
    ``/host:CPU`` beside the device's ``XLA Ops``; with none it is a
    TraceMe that records nothing. None where jax is not installed."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except ImportError:  # a jax-less client process
            _ANNOTATION = False
    if not _ANNOTATION:
        return None
    ann = (_ANNOTATION(name, request=request_id) if request_id
           else _ANNOTATION(name))
    ann.__enter__()
    return ann


def _measured_span(name: str, wall_ms: float, start_ms: float,
                   cpu_ms: float, thread: Optional[str],
                   queue_ms: Optional[float],
                   attrs: Dict[str, Any]) -> Dict[str, Any]:
    """The wire form of a span that was measured, not recorded (a wait
    that is over, a phase another thread stamped)."""
    d: Dict[str, Any] = {
        "name": name, "ms": round(wall_ms, 3),
        "startMs": round(start_ms, 3), "cpuMs": round(cpu_ms, 3),
        "thread": thread or threading.current_thread().name}
    if queue_ms is not None:
        d["queueMs"] = round(queue_ms, 3)
        d["workMs"] = round(max(wall_ms - queue_ms, 0.0), 3)
    for k, v in attrs.items():
        if k not in _RESERVED:
            d[k] = v
    return d


class Span:
    """One open span. Closed spans become plain dicts (wire-ready)."""

    __slots__ = ("name", "t0", "c0", "start_ms", "wall_ms", "cpu_ms",
                 "queue_ms", "thread", "epoch_ms", "request_id", "attrs",
                 "children", "_ann")

    def __init__(self, name: str, attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.t0 = time.perf_counter()
        self.c0 = time.thread_time()
        self.start_ms = 0.0
        self.wall_ms = 0.0
        self.cpu_ms = 0.0
        self.queue_ms: Optional[float] = None
        self.thread = threading.current_thread().name
        # roots only: the wall clock read beside t0, and the request's id
        self.epoch_ms: Optional[float] = None
        self.request_id: Optional[str] = None
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.children: List[Dict[str, Any]] = []
        self._ann: Any = None

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"name": self.name, "ms": round(self.wall_ms, 3),
                             "startMs": round(self.start_ms, 3),
                             "cpuMs": round(self.cpu_ms, 3),
                             "thread": self.thread}
        if self.epoch_ms is not None:
            d["startEpochMs"] = round(self.epoch_ms, 3)
            if self.request_id is not None:
                d["requestId"] = self.request_id
        if self.queue_ms is not None:
            # the explicit queue-vs-work split: queueMs is time spent
            # WAITING at this level, workMs the remainder
            d["queueMs"] = round(self.queue_ms, 3)
            d["workMs"] = round(max(self.wall_ms - self.queue_ms, 0.0), 3)
        for k, v in self.attrs.items():
            if k not in _RESERVED:
                d[k] = v
        if self.children:
            d["children"] = self.children
        return d


class SpanRecorder:
    """Per-query span collector. One per :class:`QueryStats`;
    thread-confined — segment fan-out workers record into their private
    stats' recorders, and ``QueryStats.merge`` re-parents their finished
    spans under the caller's currently-open span.

    ``sink`` is the completed-top-level-span list (normally the stats'
    own ``spans`` field, so finished trees land directly on the wire
    payload). ``origin`` is the ``perf_counter`` reading every span's
    ``startMs`` is an offset from: the first span opened sets it (and is
    the ROOT: it alone carries ``startEpochMs`` and ``requestId``); a
    worker's recorder is handed its query's origin, so its trees come
    out on the root's clock and are adopted as they are."""

    __slots__ = ("spans", "_stack", "origin", "request_id")

    def __init__(self, sink: Optional[List[Dict[str, Any]]] = None,
                 origin: Optional[float] = None,
                 request_id: Optional[str] = None):
        self.spans: List[Dict[str, Any]] = sink if sink is not None else []
        self._stack: List[Span] = []
        self.origin = origin
        self.request_id = request_id

    # -- open/close ----------------------------------------------------------
    def span_begin(self, name: str, **attrs: Any) -> Span:
        """Open a child of the current span (or a new root). MUST reach
        ``span_end`` on every path, exception edges included — the
        graftlint ``spanpair`` obligation gates manual pairs; prefer the
        ``span()`` context manager."""
        ann = annotate(name, self.request_id)
        sp = Span(name, attrs)
        sp._ann = ann
        if self.origin is None:
            self.origin = sp.t0
            sp.epoch_ms = time.time() * 1e3
            sp.request_id = self.request_id
        sp.start_ms = (sp.t0 - self.origin) * 1e3
        self._stack.append(sp)
        return sp

    def span_end(self, span: Span, queue_ms: Optional[float] = None,
                 **attrs: Any) -> Optional[Dict[str, Any]]:
        """Close ``span`` (idempotent: a second close is a no-op). A
        still-open child left behind by an error path is swept closed
        into ``span`` first, so exception edges can never leave a
        dangling open span below a closed parent."""
        if span not in self._stack:
            return None
        while self._stack[-1] is not span:
            self.span_end(self._stack[-1])
        self._stack.pop()
        span.wall_ms = (time.perf_counter() - span.t0) * 1e3
        # a sweep from another thread (a worker's tree closed by its
        # caller on an error path) reads that thread's clock: never < 0
        span.cpu_ms = max((time.thread_time() - span.c0) * 1e3, 0.0)
        if span._ann is not None:
            span._ann.__exit__(None, None, None)
            span._ann = None
        if queue_ms is not None:
            span.queue_ms = queue_ms
        if attrs:
            span.attrs.update(attrs)
        d = span.to_dict()
        target = self._stack[-1].children if self._stack else self.spans
        target.append(d)
        return d

    def backdate_root(self, ms: float) -> None:
        """Move the just-opened root's start back by ``ms``: a wait that
        ended as the root opened (the admission gate's) is then a child
        inside the root's interval, at ``startMs`` 0."""
        root = self._stack[0]
        root.t0 -= ms / 1e3
        root.epoch_ms -= ms
        self.origin = root.t0

    @contextmanager
    def span(self, name: str, **attrs: Any):
        sp = self.span_begin(name, **attrs)
        try:
            yield sp
        finally:
            self.span_end(sp)

    def close_all(self) -> None:
        """Close every open span, outermost last (query teardown /
        exception edge)."""
        if self._stack:
            self.span_end(self._stack[0])

    @property
    def open_depth(self) -> int:
        return len(self._stack)

    # -- pre-measured / adopted spans ---------------------------------------
    def add_completed(self, name: str, wall_ms: float,
                      queue_ms: Optional[float] = None,
                      start: Optional[float] = None, cpu_ms: float = 0.0,
                      thread: Optional[str] = None,
                      **attrs: Any) -> Dict[str, Any]:
        """Attach an already-measured span (a queue wait that ended
        before the recorder existed, a phase another thread stamped) as a
        child of the current span. ``start`` is its ``perf_counter``
        start; without one it is taken to have ended now. A pure wait
        carries ``cpu_ms`` 0. No profiler annotation: it is over."""
        if start is None:
            start = time.perf_counter() - wall_ms / 1e3
        if self.origin is None:
            self.origin = start
        d = _measured_span(name, wall_ms, (start - self.origin) * 1e3,
                           cpu_ms, thread, queue_ms, attrs)
        target = self._stack[-1].children if self._stack else self.spans
        target.append(d)
        return d

    def adopt(self, span_dicts: List[Dict[str, Any]]) -> None:
        """Re-parent completed span dicts (a worker stats' trees, a
        server's wire trees) under the currently-open span."""
        target = self._stack[-1].children if self._stack else self.spans
        target.extend(span_dicts)


# --------------------------------------------------------------------------
# QueryStats attachment (the stats object stays a plain dataclass; the
# recorder rides as a private attribute so untraced queries allocate nothing)
# --------------------------------------------------------------------------

def stats_tracer(stats: Any) -> Optional[SpanRecorder]:
    """The stats' recorder, or None (untraced: zero-allocation path)."""
    return getattr(stats, "_recorder", None)


def start_trace(stats: Any, parent: Optional[SpanRecorder] = None,
                request_id: Optional[str] = None) -> SpanRecorder:
    """Attach a recorder to ``stats`` (idempotent). Completed roots land
    in ``stats.spans`` (the wire field). ``parent`` is the query's own
    recorder when ``stats`` is a fan-out worker's private stats: the
    worker records on the parent's clock and under its request id."""
    rec = getattr(stats, "_recorder", None)
    if rec is None:
        if parent is not None:
            rec = SpanRecorder(sink=stats.spans, origin=parent.origin,
                               request_id=parent.request_id)
        else:
            rec = SpanRecorder(sink=stats.spans, request_id=request_id)
        stats._recorder = rec
    return rec


class _NullSpanCm:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpanCm()


def maybe_span(stats: Any, name: str, **attrs: Any):
    """Context manager that records a span when ``stats`` is traced and
    is a shared no-op singleton otherwise (the off-path cost is one
    ``getattr``)."""
    rec = getattr(stats, "_recorder", None)
    if rec is None:
        return _NULL_SPAN
    return rec.span(name, **attrs)


def _shift_starts(spans: List[Dict[str, Any]], by_ms: float) -> None:
    for d in spans:
        d["startMs"] = round(d.get("startMs", 0.0) + by_ms, 3)
        _shift_starts(d.get("children", ()), by_ms)


def attach_root_child(stats: Any, name: str, wall_ms: float,
                      queue_ms: Optional[float] = None, front: bool = False,
                      cpu_ms: float = 0.0, **attrs: Any) -> None:
    """Retroactively attach a pre-measured child to the stats' FINISHED
    root span (the scheduler-queue wait is measured by the server tier
    after the executor already closed the tree). The root's wall time
    grows to keep the tree self-consistent (children must account inside
    the root): a ``front`` child is laid directly before the root's old
    start, which moves back by the child's length (``startEpochMs`` with
    it, every other span's ``startMs`` forward); any other child is laid
    after the root's old end. A pure wait carries ``cpu_ms`` 0."""
    if not stats.spans:
        return
    root = stats.spans[0]
    kids = root.setdefault("children", [])
    child = _measured_span(name, wall_ms,
                           0.0 if front else root.get("ms", 0.0), cpu_ms,
                           None, queue_ms, attrs)
    if front:
        _shift_starts(kids, wall_ms)
        if "startEpochMs" in root:
            root["startEpochMs"] = round(root["startEpochMs"] - wall_ms, 3)
        kids.insert(0, child)
    else:
        kids.append(child)
    root["ms"] = round(root.get("ms", 0.0) + wall_ms, 3)


def flatten_spans(span_dicts: List[Dict[str, Any]]
                  ) -> List[Dict[str, Any]]:
    """Span trees -> the flat ``traceInfo["entries"]`` view (pre-order):
    one ``{"operator", "ms", ...attributes}`` entry a span. The one
    source of the flat view: nothing keeps a second list beside the
    tree. A root's ``instance`` tag (set at gather, before re-parenting)
    is handed down to every entry of its tree."""
    out: List[Dict[str, Any]] = []

    def walk(d: Dict[str, Any], instance: Any) -> None:
        e = {"operator": d["name"], "ms": d["ms"]}
        for k, v in d.items():
            if k not in ("name", "ms", "children"):
                e[k] = v
        instance = d.get("instance", instance)
        if instance is not None:
            e["instance"] = instance
        out.append(e)
        for c in d.get("children", ()):
            walk(c, instance)

    for d in span_dicts:
        walk(d, None)
    return out


def build_broker_root(phase_ms: Dict[str, float],
                      server_spans: List[Dict[str, Any]],
                      total_ms: float,
                      admission_wait_ms: float = 0.0,
                      reduce_folds: Optional[List[Dict[str, Any]]] = None,
                      phase_start_ms: Optional[Dict[str, float]] = None,
                      phase_cpu_ms: Optional[Dict[str, float]] = None,
                      start_epoch_ms: Optional[float] = None,
                      request_id: Optional[str] = None
                      ) -> Dict[str, Any]:
    """Assemble the broker root span from the measured broker phases
    (COMPILATION/ROUTING/SCATTER_GATHER/REDUCE), re-parenting the
    per-server trees under the ScatterGather child — the reduce-side half
    of the reference's per-server ``traceInfo`` keying.

    ``phase_start_ms`` gives each phase's (first) start as an offset from
    the root's (``ADMISSION`` for the front-door wait), ``phase_cpu_ms``
    the broker thread's CPU time in it (``TOTAL``: in the whole root;
    absent where it was not taken: a query only a server sampled),
    ``start_epoch_ms`` the root's start on the wall clock. A server's root keeps its own ``startEpochMs`` and
    gains the ``startMs`` that puts it on the broker's clock.

    ``reduce_folds`` is the reduce-as-arrivals split: one Fold child per
    folded DataTable. Its work overlapped the gather wait, so the folds
    are children of ScatterGather, where their time lies; the Reduce
    child keeps the final merge/trim/HAVING pass and a foldMs rollup."""
    starts = phase_start_ms or {}
    cpus = phase_cpu_ms or {}
    thread = threading.current_thread().name

    def span(name: str, phase: str, ms: float) -> Dict[str, Any]:
        d: Dict[str, Any] = {"name": name, "ms": round(ms, 3),
                             "startMs": round(starts.get(phase, 0.0), 3),
                             "thread": thread}
        if phase in cpus:
            d["cpuMs"] = round(cpus[phase], 3)
        return d

    children: List[Dict[str, Any]] = []
    if admission_wait_ms > 0:
        adm = span("Admission", "ADMISSION", admission_wait_ms)
        adm.update(cpuMs=0.0, queueMs=round(admission_wait_ms, 3),
                   workMs=0.0)
        children.append(adm)
    for phase, name in (("COMPILATION", "Compile"), ("ROUTING", "Routing")):
        if phase in phase_ms:
            children.append(span(name, phase, phase_ms[phase]))
    sg = span("ScatterGather", "SCATTER_GATHER",
              phase_ms.get("SCATTER_GATHER", 0.0))
    gathered = list(server_spans)
    if start_epoch_ms is not None:
        for s in gathered:
            if "startEpochMs" in s:
                s["startMs"] = round(s["startEpochMs"] - start_epoch_ms, 3)
    gathered.extend(reduce_folds or ())
    if gathered:
        sg["children"] = gathered
    children.append(sg)
    if "REDUCE" in phase_ms:
        reduce_span = span("Reduce", "REDUCE", phase_ms["REDUCE"])
        if reduce_folds:
            reduce_span["foldMs"] = round(
                sum(f.get("ms", 0.0) for f in reduce_folds), 3)
        children.append(reduce_span)
    root: Dict[str, Any] = {"name": "BrokerQuery", "ms": round(total_ms, 3),
                            "startMs": 0.0, "thread": thread}
    if cpus:
        root["cpuMs"] = round(cpus.get("TOTAL", sum(cpus.values())), 3)
    if start_epoch_ms is not None:
        root["startEpochMs"] = round(start_epoch_ms, 3)
    if request_id is not None:
        root["requestId"] = request_id
    root["children"] = children
    return root


# --------------------------------------------------------------------------
# path-decision ledger
# --------------------------------------------------------------------------

# Ordered (substring, reason_code) classification of decline messages.
# More specific substrings FIRST. Every PlanError / pallas ineligibility
# message in the engine maps to a stable code here; the normalizing
# fallback below keeps even unlisted messages classified (never
# "unknown" for a non-empty message) — the bench loud-fails on "unknown".
_DECLINE_RULES: Tuple[Tuple[str, str], ...] = (
    ("mutable segment", "mutable_segment"),
    ("star-tree group key space", "startree_group_space_over_limit"),
    ("no pre-agg pairs", "startree_no_preagg_pair"),
    ("star-tree param", "startree_param_drift"),
    ("group key space", "group_space_over_limit"),
    ("not device-supported", "agg_not_device_supported"),
    ("DISTINCTCOUNTHLL argument", "hll_arg_not_column"),
    ("DISTINCTCOUNTHLL needs", "hll_needs_sv_dict"),
    ("HLL register space", "hll_register_space_over_limit"),
    ("DISTINCTCOUNT argument", "distinctcount_arg_not_column"),
    ("DISTINCTCOUNT on raw", "distinctcount_raw_column"),
    ("DISTINCTCOUNT on MV", "distinctcount_mv_column"),
    ("DISTINCTCOUNT cardinality", "distinctcount_cardinality_over_limit"),
    ("MV aggregation argument", "mv_agg_arg_not_column"),
    ("needs a numeric MV column", "mv_agg_not_numeric"),
    ("group-by on virtual column", "group_virtual_column"),
    ("group-by on MV column", "group_mv_column"),
    ("raw int group-by span", "group_raw_span_over_limit"),
    ("group-by on raw float", "group_raw_float_column"),
    ("group-by expression span", "group_expression_span_over_limit"),
    ("group-by expression", "group_expression_unbounded"),
    ("expression predicate", "expression_predicate"),
    ("virtual column predicate", "virtual_column_predicate"),
    ("JSON_MATCH on MV", "json_match_mv_column"),
    ("on raw column -> host", "raw_predicate_unsupported"),
    ("raw MV column predicate", "raw_mv_predicate"),
    ("predicate", "predicate_unsupported"),
    ("non-numeric literal", "value_literal_non_numeric"),
    ("virtual column in value", "value_virtual_column"),
    ("in value expression", "value_column_not_numeric_sv"),
    ("transform", "transform_unsupported"),
    ("cannot compile value", "value_expression_uncompilable"),
    ("live groups exceed the compact cap", "compact_cap_overflow"),
    ("doc axis", "capacity_mesh_mismatch"),
    # pallas eligibility (engine/pallas_kernels.py _Ineligible messages)
    ("unpackable column", "pallas_unpackable_column"),
    ("lut with too many runs", "pallas_lut_too_many_runs"),
    ("raw group key", "pallas_raw_group_key"),
    ("non-numeric/MV agg value column", "pallas_value_not_numeric_sv"),
    ("no stats for int value bound", "pallas_no_int_stats"),
    ("i64-staged value column", "pallas_i64_value_column"),
    ("i64 sum bound over i64", "pallas_i64_sum_bound_over_i64"),
    ("i64 column in float expression", "pallas_i64_in_float_expr"),
    ("missing agg value", "pallas_missing_agg_value"),
    ("int expr bound exceeds i32", "pallas_expression_bound_over_i32"),
    ("agg value", "pallas_agg_value_op_unsupported"),
    ("mv aggregation", "pallas_mv_aggregation"),
    ("int min/max not f32-exact", "pallas_minmax_not_f32_exact"),
)

# Reason codes recorded DIRECTLY at decline sites (never routed through
# classify_decline's message table). The graftlint ``decline`` family
# checks every ``decline("...")`` literal in engine/pallas_kernels.py
# against this registry plus _DECLINE_RULES' code column, so a new
# decline site can never reach the ledger as an unregistered code.
DIRECT_DECLINE_CODES = frozenset({
    "pallas_too_many_groups",
    "pallas_distinct_agg",
    "pallas_docs_over_i32",
    "pallas_column_not_packable",
    "pallas_value_layout_unsupported",
    "pallas_disabled_on_backend",
    "pallas_shape_blocked",
    "pallas_exec_failed",
    "pallas_build_failed",
})

# Reason codes the broker-side ROUTING decision point records
# (broker/routing.py): a prune that fired, or why a configured pruner
# could not help. Registered for the same reason as DIRECT_DECLINE_CODES:
# every reason reaching the ledger must be a known, stable code —
# test_cluster_routing scans routing.py's record sites against this set.
ROUTING_DECISION_REASONS = frozenset({
    "partition_prune",
    "time_prune",
    "no_filter",
    "no_partition_predicate",
    "no_partition_metadata",
    "partition_all_match",
    "no_time_bound",
    "time_all_match",
})

# Reason codes the STAR-TREE decision point records
# (engine/startree_exec.py: pick_star_tree's note()/decline() sites and
# _matching_ids' reason strings). Same contract as
# ROUTING_DECISION_REASONS: every reason literal in startree_exec.py must
# be registered here — test_startree's conformance test scans the source —
# so a new decline site can never reach the ledger unregistered. The
# CHOSEN-tree success records ("startree:scan-><rung>:tree<i>") carry the
# dynamic reason matched by STARTREE_TREE_REASON instead.
STARTREE_DECISION_REASONS = frozenset({
    "startree_upsert_valid_docs",
    "startree_filter_or_not_shape",
    "startree_group_expression",
    "startree_group_off_split_order",
    "startree_filter_non_dimension",
    "startree_predicate_type_unsupported",
    "startree_agg_not_pairable",
    "startree_expression_agg_no_pair",
    "startree_missing_function_pair",
    "startree_no_fitting_tree",
    "startree_raw_dimension",
    "startree_dictid_overflow_noncontiguous",
    # recorded from engine/executor.py _try_star_tree: the host walker
    # refused a tree the pick accepted (defensive disagreement) -> scan
    "startree_walker_declined",
})

# the chosen-tree ledger reason: which of the segment's trees served
STARTREE_TREE_REASON = re.compile(r"tree\d+\Z")

# Reason codes the broker GATHER point records (broker/broker.py) when a
# scattered-to server fails to produce a usable DataTable — the loud
# accounting behind every partial result.
GATHER_DECISION_REASONS = frozenset({
    "server_not_connected",
    "server_timeout",
    "server_error",
})

# Reason codes the broker REDUCE point records (broker/reduce.py) when
# the vectorized (array-native) merge cannot prove bit-exactness against
# the row-path oracle and falls back to it. Same contract as
# ROUTING_DECISION_REASONS: every reason literal at a reduce.py record
# site must be registered here — test_reduce_vectorized scans the source.
REDUCE_DECISION_REASONS = frozenset({
    "reduce_group_key_not_sortable",
    "reduce_distinct_key_not_sortable",
    "reduce_order_key_not_sortable",
    "reduce_column_kind_mismatch",
    "reduce_nan_numeric_state",
    "reduce_nan_order_key",
    "reduce_i64_sum_bound",
})

# Reason codes the broker REDUCE point records (broker/reduce.py
# ``_decline_device`` sites) when the DEVICE group-by merge
# (parallel/reduce_device.py) cannot prove bit-exactness or has no
# substrate, and the query falls back ONE rung to the vectorized host
# path ("reduce:device->host:<reason>"). Distinct prefix from
# REDUCE_DECISION_REASONS: that set explains vectorized->oracle falls.
REDUCE_DEVICE_REASONS = frozenset({
    "reduce_device_mesh_unavailable",
    "reduce_device_obj_state",
    "reduce_device_cross_process",
    "reduce_device_rows_over_capacity",
    "reduce_device_nan_key",
    "reduce_device_key_space_overflow",
    "reduce_device_f64_sum_order",
    "reduce_device_i64_sum_bound",
    "reduce_device_kernel_error",
})

# Reason codes the KERNEL PREFLIGHT seeds into the per-shape pallas
# blocklist (tools/preflight.py): one code per lowering-model rule. A
# blocked shape then declines with ``pallas_preflight_<rule>`` instead of
# the generic ``pallas_shape_blocked``, so the ledger says WHICH lowering
# constraint the shape was predicted to violate — before any chip saw it.
PALLAS_PREFLIGHT_REASONS = frozenset({
    "pallas_preflight_tile_align",
    "pallas_preflight_vmem_budget",
    "pallas_preflight_smem_budget",
    "pallas_preflight_groups_bound",
    "pallas_preflight_grid_bound",
    "pallas_preflight_dtype_unsupported",
    "pallas_preflight_limb_planes",
})


# --------------------------------------------------------------------------
# unified reason registry: ONE lookup + ONE conformance harness for every
# reason namespace above (they were five hand-rolled frozensets with four
# near-duplicate source-scanning tests; the namespaces keep their public
# frozenset names — plenty of code imports them — but registration,
# lookup, and conformance scanning now go through here).
# --------------------------------------------------------------------------

class ReasonNamespace:
    """One decision-point reason namespace: the registered code set plus
    everything the generic conformance harness needs to scan its source
    module — regexes whose group(1) captures a reason literal at a record
    site, an optional prefix that makes EVERY quoted ``"<prefix>..."``
    literal in the module a reason, an optional pattern for allowed
    dynamic reasons (``tree<i>``), and a floor on sites found (a scan
    that finds nothing means the patterns drifted, not that the module
    conformed)."""

    __slots__ = ("name", "codes", "module", "literal_patterns", "prefix",
                 "dynamic", "min_sites", "exact")

    def __init__(self, name: str, codes: frozenset, module: str,
                 literal_patterns: Tuple[str, ...] = (),
                 prefix: Optional[str] = None,
                 dynamic: Optional["re.Pattern"] = None,
                 min_sites: int = 1, exact: bool = False):
        self.name = name
        self.codes = codes
        self.module = module
        self.literal_patterns = literal_patterns
        self.prefix = prefix
        self.dynamic = dynamic
        self.min_sites = min_sites
        self.exact = exact

    def scan_source(self) -> set:
        """All reason literals found at this namespace's record sites (by
        pattern and/or prefix) in its module's source. A namespace rooted
        at a package (the module has ``__path__``) scans every ``.py``
        beneath it — ``race_ok`` waivers live wherever shared state
        lives, not in one module."""
        import importlib
        import os

        mod = importlib.import_module(self.module)
        paths: List[str] = []
        if hasattr(mod, "__path__"):
            for root, _dirs, files in os.walk(list(mod.__path__)[0]):
                paths.extend(os.path.join(root, f) for f in sorted(files)
                             if f.endswith(".py"))
        else:
            paths.append(mod.__file__.rstrip("c"))
        found: set = set()
        for path in paths:
            with open(path, encoding="utf-8") as f:
                src = f.read()
            for pat in self.literal_patterns:
                found |= set(re.findall(pat, src))
            if self.prefix:
                found |= set(
                    re.findall(rf'"({self.prefix}[a-z0-9_]+)"', src))
        return found

    def conformance(self) -> Tuple[set, set]:
        """(literals found, unregistered literals) — the generic
        source-scanning conformance check. Dynamic reasons matching
        ``dynamic`` are allowed without registration."""
        found = self.scan_source()
        bad = {r for r in found - self.codes
               if not (self.dynamic and self.dynamic.fullmatch(r))}
        return found, bad


_REASON_REGISTRY: Dict[str, ReasonNamespace] = {}


def _register_reasons(ns: ReasonNamespace) -> None:
    _REASON_REGISTRY[ns.name] = ns


def reason_registry(name: Optional[str] = None):
    """The unified reason-namespace registry. With ``name``, the one
    :class:`ReasonNamespace`; without, the full ``{name: namespace}``
    dict. Every reason code that can reach the ledger from a registered
    decision point lives in exactly one namespace here."""
    if name is None:
        return dict(_REASON_REGISTRY)
    return _REASON_REGISTRY[name]


def registered_reason_codes() -> frozenset:
    """Union of every namespace's code set."""
    out: set = set()
    for ns in _REASON_REGISTRY.values():
        out |= ns.codes
    return frozenset(out)


# the five pre-existing namespaces + the preflight namespace, registered
# through the one harness (tests/test_reasons.py parameterizes over this
# registry — the four per-module conformance tests collapsed into it)
_register_reasons(ReasonNamespace(
    "pallas", DIRECT_DECLINE_CODES | frozenset(
        code for _needle, code in _DECLINE_RULES
        if code.startswith("pallas_")),
    "pinot_tpu.engine.pallas_kernels",
    literal_patterns=(r'decline\("([a-z0-9_]+)"\)',),
    min_sites=3))
_register_reasons(ReasonNamespace(
    "routing", ROUTING_DECISION_REASONS, "pinot_tpu.broker.routing",
    literal_patterns=(r'declined\("([a-z_]+)"\)',
                      r'"pruned", "all_servers",\s*\n?\s*"([a-z_]+)"'),
    min_sites=4))
_register_reasons(ReasonNamespace(
    "gather", GATHER_DECISION_REASONS, "pinot_tpu.broker.broker",
    literal_patterns=(r'"full_result",\s*\n?\s*"([a-z_]+)"',),
    min_sites=3, exact=True))
_register_reasons(ReasonNamespace(
    "startree", STARTREE_DECISION_REASONS,
    "pinot_tpu.engine.startree_exec",
    prefix="startree_", dynamic=STARTREE_TREE_REASON, min_sites=10))
_register_reasons(ReasonNamespace(
    "reduce", REDUCE_DECISION_REASONS, "pinot_tpu.broker.reduce",
    literal_patterns=(r'_decline\(\s*"([a-z0-9_]+)"',), min_sites=3))
_register_reasons(ReasonNamespace(
    "reduce_device", REDUCE_DEVICE_REASONS, "pinot_tpu.broker.reduce",
    literal_patterns=(r'_decline_device\(\s*"([a-z0-9_]+)"',),
    min_sites=4, exact=True))
_register_reasons(ReasonNamespace(
    "pallas_preflight", PALLAS_PREFLIGHT_REASONS,
    "pinot_tpu.tools.preflight",
    literal_patterns=(r'_Rule\(\s*"([a-z0-9_]+)"',), min_sites=5,
    exact=True))
# realtime serving tier (PR-17): consuming-segment device declines,
# broker hybrid time-boundary routing, and the seal swap
MUTABLE_DECLINE_REASONS = frozenset({
    "mutable_empty_watermark",   # nothing published yet: host answers
    "mutable_hll_lut_unstable",  # HLL register LUTs go stale as the
                                 # dictionary grows mid-consume
    "mutable_exec_failed",       # staging/kernel raised: host fallback
    # the consuming-segment index rung (PR-18), recorded through
    # _decline_rung/_chose_rung — declines fall to the full chunk scan
    # (NOT to host), so these ride the "index" decision point with the
    # mutable device scan as the chosen side
    "mutable_index_unsupported_shape",  # OR/NOT, non-EQ/IN/RANGE, MV,
                                        # dictionary-less, or upsert
    "mutable_index_over_threshold",     # broad match: the chunk scan wins
    "mutable_index_exec_failed",        # gather kernel raised: chunk scan
    "mutable_index_served",             # gather served the snapshot
})
HYBRID_ROUTE_REASONS = frozenset({
    "hybrid_single_table",    # only one physical table: no split
    "hybrid_no_time_column",  # split predicate inexpressible
    "hybrid_no_boundary",     # boundary not published: realtime serves all
    "hybrid_time_split",      # offline <= boundary < realtime
})
SEAL_SWAP_REASONS = frozenset({
    "seal_swap",      # local consumer committed: mutable -> immutable
    "seal_download",  # replica download of a sealed segment
})
_register_reasons(ReasonNamespace(
    "mutable", MUTABLE_DECLINE_REASONS,
    "pinot_tpu.engine.mutable_staging",
    literal_patterns=(
        r'_decline\(\s*[a-zA-Z_][a-zA-Z0-9_]*\s*,\s*"([a-z0-9_]+)"',
        r'_decline_rung\(\s*[a-zA-Z_][a-zA-Z0-9_]*\s*,\s*"([a-z0-9_]+)"',
        r'_chose_rung\(\s*[a-zA-Z_][a-zA-Z0-9_]*\s*,\s*"([a-z0-9_]+)"',),
    min_sites=3, exact=True))
_register_reasons(ReasonNamespace(
    "hybrid", HYBRID_ROUTE_REASONS, "pinot_tpu.broker.broker",
    literal_patterns=(r'_hybrid_route\(\s*stats,\s*"([a-z0-9_]+)"',),
    min_sites=4, exact=True))
_register_reasons(ReasonNamespace(
    "seal", SEAL_SWAP_REASONS, "pinot_tpu.server.data_manager",
    literal_patterns=(r'"(seal_[a-z0-9_]+)"',), min_sites=2, exact=True))
# index rung (PR-18): docId-gather over inverted/sorted/range indexes —
# every outcome on an index-candidate filter shape, chosen and declined
INDEX_DECISION_REASONS = frozenset({
    "index_served",              # gather rung served the segment
    "index_filter_shape",        # OR/NOT or non-column predicate
    "index_pred_type_unsupported",  # not EQ / IN / RANGE
    "index_missing_index",       # a predicate column has no usable index
    "index_selectivity_over_threshold",  # broad match: the scan wins
    "index_upsert_valid_docs",   # valid-doc bitmap ANDs the filter
    "index_plan_error",          # device plan/unpack declined -> scan
    "index_exec_failed",         # staging/kernel raised -> scan serves
})
_register_reasons(ReasonNamespace(
    "index", INDEX_DECISION_REASONS, "pinot_tpu.engine.index_exec",
    literal_patterns=(
        r'_decline\(\s*stats,\s*"([a-z0-9_]+)"',
        r'raise _Decline\(\s*"([a-z0-9_]+)"',
        r'_chose\(\s*stats,\s*"([a-z0-9_]+)"',),
    min_sites=6, exact=True))
# race waivers (PR-20): the ``threads`` lint family's ``# race-ok:``
# annotations. Each code names a concurrency DESIGN the reference also
# relies on, not a dismissal — the lint rejects any code not in this set,
# so the vocabulary can only grow through here, next to its meaning.
RACE_OK_REASONS = frozenset({
    "single_writer",         # one runtime thread performs every write;
                             # readers take GIL-atomic snapshots and
                             # tolerate one-batch staleness (the
                             # volatile-numDocsIndexed watermark pattern)
    "publish_once",          # reference assigned once at setup, never
                             # reassigned; readers null-check
    "delegates_locking",     # field holds an object that does its own
                             # locking; the mutator call the lint sees is
                             # the delegate's atomic op, and the reference
                             # itself never changes after __init__
    "quiesced_by_refcount",  # teardown mutation that runs only after the
                             # residency refcount proves no reader holds
                             # the object
})
_register_reasons(ReasonNamespace(
    "race_ok", RACE_OK_REASONS, "pinot_tpu",
    literal_patterns=(r'#\s*race-ok:\s*([a-z0-9_]+)',),
    min_sites=4, exact=True))


_SANITIZE = re.compile(r"[^a-z0-9]+")
_DIGITS = re.compile(r"\d+")


def classify_decline(message: str) -> str:
    """Decline message -> stable snake_case reason code. The table covers
    every engine decline message; the fallback strips runtime-variable
    digits and normalizes, so new messages stay machine-readable (and
    non-``unknown``) until classified properly."""
    for needle, code in _DECLINE_RULES:
        if needle in message:
            return code
    code = _SANITIZE.sub("_", _DIGITS.sub("", message).lower()).strip("_")
    return code[:64] if code else "unknown"


class DecisionLedger:
    """Always-on histogram of path-decision records, keyed on the full
    ``(decision_point, chosen, declined, reason_code)`` tuple. One
    process-level instance (:data:`LEDGER`) backs ``/metrics`` and the
    bench per-suite deltas; tests may instantiate private ledgers."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[Tuple[str, str, str, str], int] = {}  # guarded-by: _lock
        self._registries: List[Any] = []  # guarded-by-writes: _lock

    # the one labeled prometheus family every decline lands in
    METRIC_FAMILY = "decision_declined_total"

    def record(self, point: str, chosen: str, declined: str,
               reason: str) -> None:
        key = (point, chosen, declined, reason)
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + 1
            regs = list(self._registries)
        for reg in regs:
            reg.labeled_meter(self.METRIC_FAMILY,
                              point=point, reason=reason).mark()
        if point == "pallas":
            # pallas-decline burst is a flight-recorder anomaly trigger:
            # a storm of declines is how "pallas_kernels: 0" looks live
            from pinot_tpu.common.telemetry import TELEMETRY

            TELEMETRY.note_event("pallas_decline")

    def bind_metrics(self, registry: Any) -> None:
        """Surface the histogram on a MetricsRegistry as ONE labeled
        ``decision_declined_total{point=...,reason=...}`` family on
        ``/metrics`` (one name-mangled counter per cell pre-dates labeled
        families; see spi/metrics.py labeled_meter)."""
        with self._lock:
            if registry not in self._registries:
                self._registries.append(registry)
            existing = dict(self._counts)
        registry.set_help(self.METRIC_FAMILY,
                          "Path decisions where execution declined a "
                          "faster rung, by decision point and reason.")
        for (point, _c, _d, reason), n in existing.items():
            registry.labeled_meter(self.METRIC_FAMILY,
                                   point=point, reason=reason).mark(n)

    def snapshot(self) -> Dict[str, int]:
        """``"point:declined->chosen:reason" -> count`` (the same key
        shape ``QueryStats.decisions`` uses)."""
        with self._lock:
            return {decision_key(p, c, d, r): n
                    for (p, c, d, r), n in self._counts.items()}

    def reason_histogram(self) -> Dict[str, int]:
        """reason_code -> count across all decision points."""
        out: Dict[str, int] = {}
        with self._lock:
            for (_p, _c, _d, r), n in self._counts.items():
                out[r] = out.get(r, 0) + n
        return out

    def delta(self, mark: Dict[str, int]) -> Dict[str, int]:
        """Per-suite histogram since ``mark`` (a prior ``snapshot()``)."""
        now = self.snapshot()
        return {k: v - mark.get(k, 0) for k, v in now.items()
                if v - mark.get(k, 0)}


def decision_key(point: str, chosen: str, declined: str,
                 reason: str) -> str:
    return f"{point}:{declined}->{chosen}:{reason}"


def parse_decision_key(key: str) -> Tuple[str, str, str, str]:
    """Inverse of :func:`decision_key` -> (point, chosen, declined,
    reason)."""
    point, rest = key.split(":", 1)
    path, reason = rest.rsplit(":", 1)
    declined, chosen = path.split("->", 1)
    return point, chosen, declined, reason


LEDGER = DecisionLedger()


def record_decision(stats: Any, point: str, chosen: str, declined: str,
                    reason: str) -> None:
    """One ledger record: execution declined ``declined`` in favor of
    ``chosen`` at ``point`` because ``reason``. Lands in the per-query
    ``QueryStats.decisions`` dict (summed across segments/shards/servers
    at merge) AND the process :data:`LEDGER` histogram — both always on;
    a decline is never silent."""
    if stats is not None:
        key = decision_key(point, chosen, declined, reason)
        stats.decisions[key] = stats.decisions.get(key, 0) + 1
    LEDGER.record(point, chosen, declined, reason)


# --------------------------------------------------------------------------
# query registry: /debug/queries + slow-query log
# --------------------------------------------------------------------------

class QueryRegistry:
    """Backing store for ``/debug/queries``: the currently-running query
    set, a ring buffer of the last N completed, and a slow-query log
    (``pinot.server.query.slow.threshold.ms``) that retains the full
    span tree for over-threshold queries — the executor force-records
    spans for every query while the threshold is configured, and ships
    them on the wire only when the query was actually traced/sampled, so
    a slow query's forensics survive even when sampling missed it."""

    def __init__(self, ring_size: int = 128, slow_log_size: int = 32,
                 slow_threshold_ms: float = 0.0):
        self.ring_size = max(1, int(ring_size))
        self.slow_log_size = max(1, int(slow_log_size))
        self.slow_threshold_ms = float(slow_threshold_ms)
        self._lock = threading.Lock()
        self._seq = 0  # guarded-by: _lock
        self._running: Dict[int, Dict[str, Any]] = {}  # guarded-by: _lock
        self._completed: List[Dict[str, Any]] = []  # guarded-by: _lock
        self._slow: List[Dict[str, Any]] = []  # guarded-by: _lock
        self.slow_queries = 0  # guarded-by: _lock

    @property
    def force_trace(self) -> bool:
        """True when every query must record spans so the slow log can
        retain trees sampling missed."""
        return self.slow_threshold_ms > 0

    def begin(self, ctx: Any, stats: Any = None) -> Dict[str, Any]:
        token: Dict[str, Any] = {
            "sql": getattr(ctx, "sql", None),
            "table": getattr(ctx, "table_name", None),
            "requestId": getattr(ctx, "request_id", None),
            "phase": "executing",
            "t0": time.perf_counter(),
            "stats": stats,
        }
        with self._lock:
            self._seq += 1
            token["id"] = self._seq
            self._running[token["id"]] = token
        return token

    def phase(self, token: Dict[str, Any], phase: str) -> None:
        token["phase"] = phase

    def end(self, token: Dict[str, Any], error: Any = None) -> float:
        elapsed_ms = (time.perf_counter() - token["t0"]) * 1e3
        stats = token.get("stats")
        entry: Dict[str, Any] = {
            "id": token["id"],
            "sql": token["sql"],
            "table": token["table"],
            "elapsedMs": round(elapsed_ms, 3),
        }
        if token.get("requestId"):
            entry["requestId"] = token["requestId"]
        if error is not None:
            entry["error"] = f"{type(error).__name__}: {error}"[:200]
        if stats is not None and stats.decisions:
            entry["decisions"] = dict(stats.decisions)
        slow = self.slow_threshold_ms > 0 \
            and elapsed_ms >= self.slow_threshold_ms
        if slow and stats is not None and stats.spans:
            # copy the LIST (dicts shared): the executor may clear the
            # stats' wire field when the query wasn't actually traced
            entry["spans"] = list(stats.spans)
        with self._lock:
            self._running.pop(token["id"], None)
            self._completed.append(entry)
            if len(self._completed) > self.ring_size:
                del self._completed[0]
            if slow:
                self.slow_queries += 1
                self._slow.append(entry)
                if len(self._slow) > self.slow_log_size:
                    del self._slow[0]
        if stats is not None and stats.spans:
            # flight-recorder feed: every completed query whose span tree
            # was recorded (traced / sampled / slow-log-forced) lands in
            # the black box's bounded ring — copied like the slow log, so
            # the executor clearing the wire field can't empty it
            from pinot_tpu.common.telemetry import TELEMETRY

            fr = dict(entry)
            fr.setdefault("spans", list(stats.spans))
            TELEMETRY.recorder.note_query(fr)
        return elapsed_ms

    def snapshot(self) -> Dict[str, Any]:
        """``/debug/queries`` body."""
        now = time.perf_counter()
        with self._lock:
            running = list(self._running.values())
            completed = list(self._completed)
            slow = list(self._slow)
            slow_n = self.slow_queries
        run_out = []
        for t in running:
            lease = getattr(t.get("stats"), "_staging_lease", None)
            run_out.append({
                "id": t["id"],
                "sql": t["sql"],
                "table": t["table"],
                "phase": t["phase"],
                "elapsedMs": round((now - t["t0"]) * 1e3, 3),
                "pinsHeld": len(lease._pinned) if lease is not None else 0,
            })
        return {
            "running": run_out,
            "completed": completed,
            "slow": slow,
            "slowThresholdMs": self.slow_threshold_ms,
            "slowQueries": slow_n,
        }
