"""Layered key/value configuration.

Re-design of ``pinot-spi/.../env/PinotConfiguration.java:88``: merges (in
priority order) explicit overrides > environment variables (``PINOT_``
prefixed, mapping ``PINOT_SERVER_PORT`` -> ``pinot.server.port``) >
properties files > defaults, with relaxed key matching (case-insensitive,
``-``/``_``/``.``/camelCase-insensitive within a segment).
"""

from __future__ import annotations

import os
import re
from typing import Any, Dict, Iterator, List, Mapping, Optional

_SEP = re.compile(r"[-_.]")

# Layer priorities: higher wins, regardless of insertion order.
PRIORITY_DEFAULT = 0
PRIORITY_FILE = 1
PRIORITY_ENV = 2
PRIORITY_OVERRIDE = 3


def _segments(key: str) -> List[str]:
    return [s for s in _SEP.split(key.lower()) if s]


def _relax(key: str) -> str:
    """Relaxed key normalization: case-insensitive, separator-insensitive.

    ``timeoutMs`` == ``timeout.ms`` == ``TIMEOUT_MS`` == ``timeout-ms``.
    """
    return "".join(_segments(key))


class PinotConfiguration:
    def __init__(self, overrides: Optional[Mapping[str, Any]] = None,
                 use_env: bool = True):
        self._store: Dict[str, Any] = {}
        self._priority: Dict[str, int] = {}
        self._raw_keys: Dict[str, str] = {}
        if use_env:
            for k, v in os.environ.items():
                if k.startswith("PINOT_"):
                    # PINOT_SERVER_PORT -> pinot.server.port (prefix retained:
                    # all framework keys are namespaced under pinot.*)
                    self.set(k.lower().replace("_", "."), v, PRIORITY_ENV)
        if overrides:
            for k, v in overrides.items():
                self.set(k, v, PRIORITY_OVERRIDE)

    # -- mutation ----------------------------------------------------------
    def set(self, key: str, value: Any, priority: int = PRIORITY_OVERRIDE) -> None:
        rk = _relax(key)
        if self._priority.get(rk, -1) > priority:
            return  # a higher layer already owns this key
        self._store[rk] = value
        self._priority[rk] = priority
        self._raw_keys[rk] = key

    def set_default(self, key: str, value: Any) -> None:
        self.set(key, value, PRIORITY_DEFAULT)

    def load_properties_file(self, path: str) -> None:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line or line.startswith(("#", "!")):
                    continue
                if "=" in line:
                    k, _, v = line.partition("=")
                    self.set(k.strip(), v.strip(), PRIORITY_FILE)

    # -- access ------------------------------------------------------------
    def get(self, key: str, default: Any = None) -> Any:
        return self._store.get(_relax(key), default)

    def get_int(self, key: str, default: int = 0) -> int:
        v = self.get(key)
        return default if v is None else int(v)

    def get_float(self, key: str, default: float = 0.0) -> float:
        v = self.get(key)
        return default if v is None else float(v)

    def get_bool(self, key: str, default: bool = False) -> bool:
        v = self.get(key)
        if v is None:
            return default
        if isinstance(v, bool):
            return v
        return str(v).strip().lower() in ("true", "1", "yes", "on")

    def get_str(self, key: str, default: str = "") -> str:
        v = self.get(key)
        return default if v is None else str(v)

    def subset(self, prefix: str) -> "PinotConfiguration":
        """All keys under ``prefix``, prefix stripped, matched on whole
        key segments (``subset('server')`` does NOT match ``serverx.port``)."""
        psegs = _segments(prefix)
        out = PinotConfiguration(use_env=False)
        for rk, raw in self._raw_keys.items():
            ksegs = _segments(raw)
            if len(ksegs) > len(psegs) and ksegs[: len(psegs)] == psegs:
                out.set(".".join(ksegs[len(psegs):]), self._store[rk],
                        self._priority[rk])
        return out

    def keys(self) -> Iterator[str]:
        return iter(self._raw_keys.values())

    def to_dict(self) -> Dict[str, Any]:
        return {raw: self._store[rk] for rk, raw in self._raw_keys.items()}

    def __contains__(self, key: str) -> bool:
        return _relax(key) in self._store

    def __repr__(self) -> str:
        return f"PinotConfiguration({self.to_dict()!r})"


class CommonConstants:
    """Centralized config keys + defaults (ref: pinot-spi CommonConstants.java)."""

    DEFAULT_BROKER_QUERY_PORT = 8099
    DEFAULT_SERVER_QUERY_PORT = 8098
    DEFAULT_CONTROLLER_PORT = 9000
    DEFAULT_QUERY_TIMEOUT_MS = 10_000
    DEFAULT_MAX_ROWS_IN_RESPONSE = 10_000
    # Engine defaults (ref: InstancePlanMakerImplV2.java:67-84)
    DEFAULT_NUM_GROUPS_LIMIT = 100_000
    DEFAULT_GROUPBY_TRIM_THRESHOLD = 1_000_000
    DEFAULT_MIN_SEGMENT_GROUP_TRIM_SIZE = -1
    DEFAULT_MIN_SERVER_GROUP_TRIM_SIZE = 5000
    # Device-resident broker reduce (parallel/reduce_device.py): when
    # broker and servers share the process (embedded cluster / bench
    # topology) group-by partials merge ON DEVICE — segment-sum/sort-rung
    # kernels + psum over the broker mesh — instead of the host lexsort.
    # Off by default: cross-process tables already paid D2H + wire, so
    # the host path is the natural fallback frame. Per-query override:
    # OPTION(deviceReduce=true|false).
    BROKER_DEVICE_REDUCE_KEY = "pinot.broker.reduce.device.enabled"
    DEFAULT_BROKER_DEVICE_REDUCE = False
    # Dense-rung slot cap: composite key spaces up to this many slots
    # merge via direct segment-sum scatter; larger spaces ride the sort
    # rung, and spaces whose composite encoding cannot fit i64 decline.
    DEFAULT_DEVICE_REDUCE_DENSE_SLOTS = 1 << 21
    # Row cap on the padded merge input (all servers' groups concatenated,
    # padded to a shared pow2 capacity); above it the device path declines
    # loudly rather than committing unbounded HBM.
    DEFAULT_DEVICE_REDUCE_MAX_ROWS = 1 << 22
    # Block size: the reference drains filters in 10k-doc blocks
    # (DocIdSetPlanNode.java:29). On TPU we tile the doc dimension instead;
    # this is the host-side fallback block size.
    MAX_DOC_PER_CALL = 10_000
    # HBM residency (engine/residency.py): device-staging byte budget.
    # Unset -> auto from the backend's reported device memory times the
    # fraction below (uncapped on backends that report nothing, e.g. CPU);
    # <= 0 -> explicitly uncapped.
    HBM_BUDGET_BYTES_KEY = "pinot.server.query.hbm.budget.bytes"
    DEFAULT_HBM_BUDGET_FRACTION = 0.75
    # Host-RAM spill tier (engine/residency.py): eviction demotes device
    # arrays to pinned host numpy copies instead of dropping them, so a
    # re-stage is one H2D transfer instead of a full column rebuild (the
    # ISCA'23 D2H+H2D vs rebuild cost model — ~10x cheaper). Budget key
    # unset -> auto from psutil available RAM times the fraction below
    # (uncapped when psutil is missing); <= 0 -> explicitly uncapped.
    # The enabled key turns the tier off wholesale (eviction drops, the
    # pre-tier behavior) — the bench uses it for the spill baseline.
    HOSTRAM_BUDGET_BYTES_KEY = "pinot.server.query.hostram.budget.bytes"
    HOSTRAM_ENABLED_KEY = "pinot.server.query.hostram.enabled"
    DEFAULT_HOSTRAM_BUDGET_FRACTION = 0.5
    # Budget-sliced sharded combine (parallel/executor.py): a query whose
    # working set exceeds the HBM budget — but whose largest single
    # segment fits — runs the combine in budget-sized slices (stage k
    # segments, launch, demote-to-host, repeat) instead of spilling to
    # the host engine. Disable to restore spill-on-over-budget.
    HBM_SLICING_ENABLED_KEY = "pinot.server.query.hbm.slicing.enabled"
    # Server pool sizing (ref: the pqr/pqw pools,
    # CommonConstants.Server.*_QUERY_RUNNER_THREADS /
    # QUERY_WORKER_THREADS): runner threads execute whole queries off the
    # scheduler queue; worker threads fan segment plans out inside one
    # query (engine/executor._map_segments). Worker default: min(cpu, 8),
    # the pre-knob hardcoded fan-out width.
    RUNNER_THREADS_KEY = "pinot.server.query.runner.threads"
    DEFAULT_RUNNER_THREADS = 8
    # Pallas LUT eligibility (engine/pallas_kernels.py): max interval runs
    # a boolean dictId LUT (IN / REGEXP / TEXT_MATCH predicates) may
    # decompose into before the fused kernel declines to the jnp
    # LUT-gather path. Small run counts bake into the filter tree; past
    # _MAX_LUT_RUNS and up to this cap they ride the padded interval-set
    # ("ivs") fallback node — each run is one SMEM compare pair per tile.
    PALLAS_LUT_MAX_RUNS_KEY = "pinot.server.query.pallas.lut.max.runs"
    DEFAULT_PALLAS_LUT_MAX_RUNS = 64
    # Per-shape pallas blocklist persistence (engine/pallas_blocklist.py):
    # when set, runtime lowering failures AND preflight-predicted failures
    # (tools/preflight.py) are written through to this JSON file and
    # reloaded at executor start — a chip that fell over mid-round must
    # not forget its lowering failures on restart.
    PALLAS_BLOCKLIST_PATH_KEY = "pinot.server.query.pallas.blocklist.path"
    WORKER_THREADS_KEY = "pinot.server.query.worker.threads"
    # Scheduler policy (server/scheduler.py make_scheduler): fcfs |
    # tokenbucket | priority | sewf (shortest-expected-work-first with an
    # age-based anti-starvation boost — the default).
    SCHEDULER_POLICY_KEY = "pinot.server.query.scheduler.policy"
    DEFAULT_SCHEDULER_POLICY = "sewf"
    # Admission gate (server/admission.py): bounded concurrency + bounded
    # queue in front of query execution. 0 = auto-size (concurrent from
    # cpu count, queue from the concurrency bound); max.concurrent < 0
    # disables the gate. Past the queue bound — or past the wait bound —
    # queries are REJECTED with a typed retriable QueryRejectedError, so
    # overload degrades to bounded-latency rejection instead of convoy
    # collapse.
    ADMISSION_MAX_CONCURRENT_KEY = \
        "pinot.server.query.admission.max.concurrent"
    DEFAULT_ADMISSION_MAX_CONCURRENT = 0
    ADMISSION_MAX_QUEUE_KEY = "pinot.server.query.admission.max.queue"
    DEFAULT_ADMISSION_MAX_QUEUE = 0
    ADMISSION_MAX_WAIT_MS_KEY = "pinot.server.query.admission.max.wait.ms"
    DEFAULT_ADMISSION_MAX_WAIT_MS = 10_000.0
    # Query lifecycle tracing (common/tracing.py): span trees are
    # recorded when the request carries OPTION(trace=true) OR this sample
    # rate (0..1) hits — sampled traces ship in the response exactly like
    # requested ones. 0 (the default) keeps the untraced path at its
    # zero-allocation cost.
    TRACE_SAMPLE_KEY = "pinot.server.query.trace.sample"
    DEFAULT_TRACE_SAMPLE = 0.0
    # Slow-query log (/debug/queries): a query over this wall-time
    # threshold retains its FULL span tree in the server's slow log even
    # when trace/sampling missed it — while the threshold is configured,
    # the executor records spans for every query and ships them only for
    # traced ones. 0 (the default) disables the forced recording so the
    # serving path stays span-free.
    SLOW_THRESHOLD_MS_KEY = "pinot.server.query.slow.threshold.ms"
    DEFAULT_SLOW_THRESHOLD_MS = 0.0
    # Continuous telemetry (common/telemetry.py): sampler resolution for
    # the gauge-history rings (staged/host bytes, queue depths, arrival
    # EWMA, rejection counters) and the flight recorder's anomaly checks.
    TELEMETRY_RESOLUTION_S_KEY = "pinot.server.telemetry.resolution.s"
    DEFAULT_TELEMETRY_RESOLUTION_S = 2.0
    # Flight recorder (common/telemetry.py FlightRecorder): post-mortem
    # bundle directory (default <tmp>/pinot_tpu_flightrecorder), the
    # freeze debounce, and the windowed-p99-vs-EWMA spike factor.
    FLIGHT_DIR_KEY = "pinot.server.telemetry.flightrecorder.dir"
    FLIGHT_MIN_INTERVAL_S_KEY = \
        "pinot.server.telemetry.flightrecorder.min.interval.s"
    FLIGHT_P99_FACTOR_KEY = \
        "pinot.server.telemetry.flightrecorder.p99.factor"
    # Per-table SLOs (common/telemetry.py SloTracker): latency and error
    # objectives parsed from the RAW key strings so table names survive
    # relaxed-key normalization —
    #   pinot.broker.slo.<table>.p99.ms   (latency objective, ms)
    #   pinot.broker.slo.<table>.error.pct (error-rate objective, percent)
    # Burn rates (>1 = over-burning the budget) ride /debug/slo and the
    # slo_burn_rate exposition gauges.
    SLO_KEY_PREFIX = "pinot.broker.slo."
