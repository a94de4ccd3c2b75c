"""Cross-query launch coalescing: micro-batched device dispatch.

The sharded combine used to serialize every multi-device launch under a
process-global lock (interleaved collective programs deadlock the runtime),
so N concurrent queries paid N back-to-back device programs — the measured
QPS story was ~1.0x scaling at 4 client threads. This module turns that
serialization point into a *coalescing* point, the device-query analogue of
continuous batching in an inference server (and of the reference's sized
combine pools, ``BaseCombineOperator.java:55``):

- Queries never call a compiled combine directly. They submit a
  :class:`_LaunchRequest` — ``(LaunchKernel, runtime params, num_docs)`` —
  to the per-mesh :class:`LaunchScheduler` and block on a future.
- A single daemon dispatcher thread drains the queue. Because only this
  thread ever launches device programs, the old ``_combine_lock`` becomes an
  *emergent property* of the design: launches are totally ordered, so
  collective programs can never interleave, with no lock held across the
  serving path.
- While one program runs, waiting requests pile up. The dispatcher groups
  them by **compiled-kernel identity** (``LaunchKernel.key`` — the
  literal-normalized plan fingerprint, so same-shape queries with different
  literals share a kernel):

  * requests whose runtime params are the *same device arrays* (exact
    repeats served by the executor's param cache) share ONE launch and ONE
    result buffer (dedup);
  * distinct param sets stack along a new leading axis and run as ONE
    vmapped launch (sizes padded to powers of two so compile variants stay
    bounded), each query's future receiving its row of the output.

- Different-shape queries pipeline through the queue in arrival order
  instead of convoying behind a lock: while query A's caller decodes its
  result, the dispatcher is already launching query B.

A kernel whose vmapped form fails to build/run (e.g. a batching rule a
backend can't lower) is marked non-batchable and its group falls back to
serial launches on the dispatcher thread — coalescing degrades to the old
serialized behavior, never to a wrong answer.

The dispatcher never BUILDS a vmapped form: its first call traces and
compiles a batched program, seconds on the chip, and every rider of the
group (and every query queued behind it) would wait for that inside its
own launch. A group whose variant this kernel has not built launches its
members one by one (``unbuiltGroups`` on ``/debug/launches`` counts them);
``LaunchKernel.run_many`` called off the serving path builds it.
"""

from __future__ import annotations

import logging
import threading
import time

from collections import OrderedDict, deque
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple

from pinot_tpu.common import tracing

log = logging.getLogger(__name__)

# stats keys whose QueryStats.launch merge takes MAX (the rest sum); shared
# with engine/results.py so wire merge and launcher agree on semantics
LAUNCH_MAX_KEYS = ("batchSize", "queueWaitMs")


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class LaunchKernel:
    """One coalescable compiled combine program.

    ``call(params, num_docs) -> packed`` is the solo form (params are this
    query's runtime arrays; everything else — staged columns, mesh, output
    layout — is closed over). ``key`` is the literal-normalized identity two
    requests must share to ride one launch: same compiled kernel, same
    staged arrays, same num_docs source. The vmapped form is built per
    padded batch size by the first ``run_many`` of that size — never by the
    dispatcher, which asks ``has_batched`` first — and maps ONLY over params
    (``in_axes=(0, None)``), so staged columns are broadcast, not copied per
    batch element.
    """

    __slots__ = ("key", "call", "is_pallas", "max_batch", "batchable",
                 "_vmapped", "_lock")

    def __init__(self, key: Tuple, call, is_pallas: bool = False,
                 max_batch: int = 8):
        self.key = key
        self.call = call
        self.is_pallas = is_pallas
        self.max_batch = max(1, int(max_batch))
        # flips False on the first vmapped failure; the group then runs
        # serially forever (correctness over throughput)
        self.batchable = self.max_batch > 1
        self._vmapped: Dict[int, Any] = {}  # guarded-by: _lock
        self._lock = threading.Lock()

    def run_one(self, params, num_docs):
        return self.call(params, num_docs)

    def has_batched(self, n: int) -> bool:
        """Whether the batched variant a group of ``n`` would ride has been
        built (a ``run_many`` of its padded size has returned)."""
        size = min(_next_pow2(n), _next_pow2(self.max_batch))
        with self._lock:
            return size in self._vmapped

    def run_many(self, params_list: List[Any], num_docs) -> List[Any]:
        """One vmapped launch over ``len(params_list)`` stacked param sets;
        returns one output row per param set (device-sliced, D2H deferred
        to each caller's decode). Sizes pad up to a power of two with
        repeats of the last param set so the jit cache holds at most
        log2(max_batch) batched variants per kernel."""
        import jax
        import jax.numpy as jnp

        n = len(params_list)
        size = min(_next_pow2(n), _next_pow2(self.max_batch))
        padded = list(params_list) + [params_list[-1]] * (size - n)
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)
        with self._lock:
            fn = self._vmapped.get(size)
        if fn is None:
            # vmap of the jitted solo call: pjit's batching rule traces
            # the inner program with a leading batch dim and caches the
            # compile in the inner jit's own cache (no outer jit — that
            # would bake the closed-over staged columns in as constants)
            fn = jax.vmap(self.call, in_axes=(0, None))
        out = fn(stacked, num_docs)
        with self._lock:    # built: its trace and compile are done
            self._vmapped.setdefault(size, fn)
        return [out[j] for j in range(n)]


class _LaunchRequest:
    """One query's pending launch + its coalescing outcome (the fields the
    executor copies into ``QueryStats.launch``)."""

    __slots__ = ("kernel", "params", "num_docs", "future", "t_submit",
                 "batch_size", "queue_wait_ms", "launches_saved", "deduped",
                 "traced", "request_id", "t_dispatch", "t_launched",
                 "t_ready", "dispatch_cpu_ms", "thread")

    def __init__(self, kernel: LaunchKernel, params, num_docs,
                 traced: bool = False, request_id: Optional[str] = None):
        self.kernel = kernel
        self.params = params
        self.num_docs = num_docs
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        self.batch_size = 1
        self.queue_wait_ms = 0.0
        self.launches_saved = 0
        self.deduped = False
        # a traced query's launch: the dispatcher stamps its group's
        # phases here (beside t_submit), the query's thread attaches them
        self.traced = traced
        self.request_id = request_id
        self.t_dispatch = self.t_launched = self.t_ready = 0.0
        self.dispatch_cpu_ms = 0.0
        self.thread = ""

    def result(self, timeout: Optional[float] = None):
        return self.future.result(timeout)

    def add_spans(self, rec) -> None:
        """The dispatcher thread's two phases of this launch as children
        of the recorder's open span: ``Dispatch`` (host side: group,
        stack, the jit call until it returns) and ``DeviceWait``
        (``block_until_ready``). A coalesced group's requests all carry
        the group's one pair."""
        if not self.t_ready:
            return  # never launched (the submit or the group failed)
        rec.add_completed(
            "Dispatch", wall_ms=(self.t_launched - self.t_dispatch) * 1e3,
            start=self.t_dispatch, cpu_ms=self.dispatch_cpu_ms,
            thread=self.thread)
        rec.add_completed(
            "DeviceWait", wall_ms=(self.t_ready - self.t_launched) * 1e3,
            start=self.t_launched, thread=self.thread)


class LaunchScheduler:
    """Per-mesh dispatcher: one daemon thread owns every device launch."""

    def __init__(self, name: str = "combine-launch"):
        self._name = name
        # writes-only guard: queue-depth gauges read len() lock-free
        # (GIL-atomic), mutation stays on the condition
        self._queue: "deque[_LaunchRequest]" = deque()  # guarded-by-writes: _cond
        self._cond = threading.Condition()
        self._thread: Optional[threading.Thread] = None  # guarded-by-writes: _cond
        self._closed = False  # guarded-by: _cond
        # adaptive micro-batch window: when the arrival-rate EWMA says the
        # queue is HOT (inter-arrival <= hot threshold), the dispatcher
        # holds up to window_max_ms for stragglers before grouping — vmap
        # batches get bigger exactly when traffic would fill them; idle
        # traffic never waits (window collapses to zero). Writes-only
        # guards: the dispatcher reads these lock-free between drains.
        self.window_max_ms = 1.0  # guarded-by-writes: _cond
        self.window_hot_ms = 2.0  # guarded-by-writes: _cond
        self._arrival_ewma_ms: Optional[float] = None  # guarded-by-writes: _cond
        self._last_arrival: Optional[float] = None  # guarded-by: _cond
        # cumulative counters (process lifetime; bench suites diff
        # stats_snapshot() marks, /debug/launches serves snapshot()).
        # Writes-only guard: gauge lambdas read single counters lock-free;
        # stats_snapshot() takes the lock for a consistent cut.
        self._stats_lock = threading.Lock()
        self.requests = 0  # guarded-by-writes: _stats_lock
        self.launches = 0  # guarded-by-writes: _stats_lock
        self.coalesced_launches = 0  # guarded-by-writes: _stats_lock
        self.launches_saved = 0  # guarded-by-writes: _stats_lock
        self.deduped_requests = 0  # guarded-by-writes: _stats_lock
        self.batched_requests = 0  # guarded-by-writes: _stats_lock
        self.failures = 0  # guarded-by-writes: _stats_lock
        self.unbuilt_groups = 0  # guarded-by-writes: _stats_lock
        self.max_batch_size = 0  # guarded-by-writes: _stats_lock
        self.queue_wait_ms_total = 0.0  # guarded-by-writes: _stats_lock
        self.queue_wait_ms_max = 0.0  # guarded-by-writes: _stats_lock
        self.window_waits = 0  # guarded-by-writes: _stats_lock
        self.window_gathered = 0  # guarded-by-writes: _stats_lock
        self.window_last_ms = 0.0  # guarded-by-writes: _stats_lock
        self._registries: List[Any] = []  # guarded-by-writes: _stats_lock

    # -- submission ----------------------------------------------------------
    def submit(self, kernel: LaunchKernel, params, num_docs,
               traced: bool = False, request_id: Optional[str] = None
               ) -> _LaunchRequest:
        req = _LaunchRequest(kernel, params, num_docs, traced, request_id)
        with self._cond:
            if self._closed:
                raise RuntimeError(f"launch scheduler {self._name} is closed")
            if self._thread is None or not self._thread.is_alive():
                # also revives a dispatcher a defensive-coded bug killed:
                # queued waiters must never hang on a dead thread
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name=self._name)
                self._thread.start()
            self._note_arrival_locked(req.t_submit)
            self._queue.append(req)
            self._cond.notify()
        return req

    def _note_arrival_locked(self, now: float) -> None:
        """Arrival-rate EWMA feeding the adaptive window (caller holds
        ``_cond``). A gap far beyond the hot threshold RESETS the average —
        the first queries after an idle stretch must not inherit a hot
        window from yesterday's burst."""
        if self._last_arrival is not None:
            dt_ms = (now - self._last_arrival) * 1e3
            e = self._arrival_ewma_ms
            if e is None or dt_ms > 8 * max(self.window_hot_ms, 0.001):
                self._arrival_ewma_ms = dt_ms
            else:
                self._arrival_ewma_ms = 0.2 * dt_ms + 0.8 * e
        self._last_arrival = now

    def set_window(self, max_ms: Optional[float] = None,
                   hot_ms: Optional[float] = None) -> None:
        """Configure the adaptive micro-batch window: ``max_ms`` = the
        straggler hold cap (<= 0 disables), ``hot_ms`` = the inter-arrival
        EWMA threshold below which traffic counts as hot."""
        with self._cond:
            if max_ms is not None:
                self.window_max_ms = float(max_ms)
            if hot_ms is not None:
                self.window_hot_ms = float(hot_ms)

    def close(self) -> None:
        """Stop accepting; the dispatcher drains what's queued and exits.
        Only meaningful for privately-owned schedulers (the per-mesh
        registry keeps its daemons for the process lifetime)."""
        with self._cond:
            self._closed = True
            self._cond.notify()

    # -- dispatcher ----------------------------------------------------------
    def _window_hold_s(self, n_drained: int) -> float:
        """Adaptive window decision for one drain: hold only when traffic
        is HOT (EWMA inter-arrival under the hot threshold) and the drain
        is still small enough that stragglers would grow the vmap group.
        Idle traffic returns 0.0 — no added latency at low QPS."""
        w = self.window_max_ms
        if w <= 0 or n_drained >= 8:
            return 0.0
        ewma = self._arrival_ewma_ms
        if ewma is None or ewma > self.window_hot_ms:
            return 0.0
        return w / 1e3

    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if not self._queue and self._closed:
                    return
                drained = list(self._queue)
                self._queue.clear()
            hold_s = self._window_hold_s(len(drained))
            if hold_s > 0:
                # hot queue: hold for stragglers so this drain's vmap
                # groups get bigger — the micro-batch window
                deadline = time.perf_counter() + hold_s
                gathered = 0
                with self._cond:
                    while not self._closed:
                        remaining = deadline - time.perf_counter()
                        if remaining <= 0:
                            break
                        self._cond.wait(remaining)
                    if self._queue:
                        gathered = len(self._queue)
                        drained += list(self._queue)
                        self._queue.clear()
                with self._stats_lock:
                    self.window_waits += 1
                    self.window_gathered += gathered
                    self.window_last_ms = hold_s * 1e3
                self._mark("LAUNCH_WINDOW_WAITS", 1)
                self._mark("LAUNCH_WINDOW_GATHERED", gathered)
            # group by compiled-kernel identity, preserving the arrival
            # order of the FIRST request of each group (FIFO fairness across
            # shapes; later same-shape arrivals ride the earlier slot)
            groups: "OrderedDict[Tuple, List[_LaunchRequest]]" = OrderedDict()
            for req in drained:
                groups.setdefault(req.kernel.key, []).append(req)
            for reqs in groups.values():
                # a failure escaping _launch_group (import error, a bug in
                # the grouping itself) must still complete every waiter's
                # future — the alternative is N client threads hung forever
                # on a dead dispatcher
                try:
                    self._launch_group(reqs)
                except BaseException as e:  # noqa: BLE001
                    log.exception("launch group failed outside the "
                                  "per-request paths")
                    for r in reqs:
                        if not r.future.done():
                            r.future.set_exception(e)

    def _launch_group(self, reqs: List[_LaunchRequest]) -> None:
        import jax

        kernel = reqs[0].kernel
        num_docs = reqs[0].num_docs
        now = time.perf_counter()
        for r in reqs:
            r.queue_wait_ms = (now - r.t_submit) * 1e3
        traced = [r for r in reqs if r.traced]
        cpu0 = time.thread_time() if traced else 0.0
        # a traced group's two phases also go onto a profiler trace, on
        # this thread's line (under the first traced request's id)
        ann = (tracing.annotate("Dispatch", traced[0].request_id)
               if traced else None)
        # dedup exact repeats: the executor's param cache hands identical
        # queries the SAME device param objects, so identity is the test
        uniq: List[Any] = []
        req_slot: List[int] = []
        seen: Dict[int, int] = {}
        for r in reqs:
            slot = seen.get(id(r.params))
            if slot is None:
                slot = len(uniq)
                seen[id(r.params)] = slot
                uniq.append(r.params)
            req_slot.append(slot)

        outs: List[Any] = [None] * len(uniq)
        errs: List[Optional[BaseException]] = [None] * len(uniq)
        launches = 0
        if len(uniq) == 1:
            try:
                outs[0] = kernel.run_one(uniq[0], num_docs)
            except BaseException as e:  # noqa: BLE001 — futures carry it
                errs[0] = e
            launches = 1
        else:
            start = 0
            while start < len(uniq):
                chunk = uniq[start:start + kernel.max_batch]
                batched = kernel.batchable and len(chunk) > 1
                if batched and not kernel.has_batched(len(chunk)):
                    # building it here would be a compile inside these
                    # queries' launch: its members go one by one instead
                    batched = False
                    with self._stats_lock:
                        self.unbuilt_groups += 1
                if batched:
                    try:
                        rows = kernel.run_many(chunk, num_docs)
                        outs[start:start + len(chunk)] = rows
                        launches += 1
                        start += len(chunk)
                        continue
                    except BaseException:  # noqa: BLE001 — serial fallback
                        log.exception(
                            "vmapped combine launch failed for %r; "
                            "disabling coalescing for this kernel",
                            kernel.key[:2])
                        kernel.batchable = False
                        # path-decision ledger: a kernel degrading to
                        # serial launches is a throughput decline worth
                        # explaining (no per-query stats on the
                        # dispatcher thread — the process histogram
                        # carries it)
                        from pinot_tpu.common.tracing import record_decision

                        record_decision(None, "launch", "serial_launches",
                                        "vmap_batch", "vmap_failed")
                for j, p in enumerate(chunk):
                    try:
                        outs[start + j] = kernel.run_one(p, num_docs)
                    except BaseException as e:  # noqa: BLE001
                        errs[start + j] = e
                    launches += 1
                start += len(chunk)
        # wait INSIDE the dispatcher before the next group: device execution
        # stays totally ordered (the no-interleaved-collectives invariant)
        # and the queue keeps filling while this program runs — which is
        # exactly what makes the next drain coalesce
        launched = time.perf_counter()
        cpu_ms = (time.thread_time() - cpu0) * 1e3 if traced else 0.0
        if ann is not None:
            ann.__exit__(None, None, None)
            ann = tracing.annotate("DeviceWait", traced[0].request_id)
        try:
            jax.block_until_ready([o for o in outs if o is not None])
        except BaseException:  # noqa: BLE001 — surface at the fetch instead
            pass
        ready = time.perf_counter()
        if ann is not None:
            ann.__exit__(None, None, None)
        for r in traced:
            r.t_dispatch, r.t_launched, r.t_ready = now, launched, ready
            r.dispatch_cpu_ms = cpu_ms
            r.thread = self._name

        n = len(reqs)
        for r, slot in zip(reqs, req_slot):
            r.batch_size = n
            r.launches_saved = n - launches
            r.deduped = req_slot.count(slot) > 1
            if errs[slot] is not None:
                r.future.set_exception(errs[slot])
            else:
                r.future.set_result(outs[slot])
        self._note(reqs, uniq, launches,
                   n_failed=sum(e is not None for e in errs))

    # -- stats / observability ----------------------------------------------
    def _note(self, reqs, uniq, launches: int, n_failed: int) -> None:
        n = len(reqs)
        wait = [r.queue_wait_ms for r in reqs]
        # windowed dispatcher-queue-wait histogram: the launch tier's
        # sliding-percentile view (per-mesh, no table attribution here)
        from pinot_tpu.common.telemetry import TELEMETRY

        wh = TELEMETRY.histo("", "launch_queue")
        for w in wait:
            wh.record(w)
        with self._stats_lock:
            self.requests += n
            self.launches += launches
            self.failures += n_failed
            if n > launches:
                self.coalesced_launches += 1
                self.launches_saved += n - launches
            self.deduped_requests += n - len(uniq)
            if len(uniq) > 1 and launches < len(uniq):
                self.batched_requests += n - (n - len(uniq))
            if n > self.max_batch_size:
                self.max_batch_size = n
            self.queue_wait_ms_total += sum(wait)
            self.queue_wait_ms_max = max(self.queue_wait_ms_max, *wait)
        self._mark("LAUNCH_REQUESTS", n)
        self._mark("LAUNCHES", launches)
        if n > launches:
            self._mark("LAUNCHES_COALESCED", 1)
            self._mark("LAUNCHES_SAVED", n - launches)

    def bind_metrics(self, registry) -> None:
        """Attach a MetricsRegistry (spi/metrics.py ServerMeter.LAUNCH*_).
        Multiple server instances may share one per-mesh scheduler, so
        every bound registry gets the marks."""
        with self._stats_lock:
            if registry not in self._registries:
                self._registries.append(registry)
        registry.gauge("launch_queue_depth", lambda: float(len(self._queue)))
        registry.gauge("launch_max_batch_size",
                       lambda: float(self.max_batch_size))

    def _mark(self, name: str, n: int) -> None:
        if not self._registries or n <= 0:
            return
        from pinot_tpu.spi.metrics import ServerMeter

        metric = getattr(ServerMeter, name, None)
        if metric is None:
            return
        for reg in list(self._registries):
            reg.meter(metric).mark(n)

    def stats_snapshot(self) -> Dict[str, float]:
        """Cumulative counters (bench per-suite deltas diff two of these)."""
        with self._stats_lock:
            return {
                "requests": self.requests,
                "launches": self.launches,
                "coalescedLaunches": self.coalesced_launches,
                "launchesSaved": self.launches_saved,
                "dedupedRequests": self.deduped_requests,
                "batchedRequests": self.batched_requests,
                "failures": self.failures,
                "unbuiltGroups": self.unbuilt_groups,
                "maxBatchSize": self.max_batch_size,
                "queueWaitMsTotal": round(self.queue_wait_ms_total, 3),
                "queueWaitMsMax": round(self.queue_wait_ms_max, 3),
                "windowWaits": self.window_waits,
                "windowGathered": self.window_gathered,
                "windowLastMs": round(self.window_last_ms, 3),
            }

    def snapshot(self) -> Dict[str, Any]:
        """``/debug/launches`` body: counters + live queue state."""
        out: Dict[str, Any] = self.stats_snapshot()
        out["queued"] = len(self._queue)
        out["dispatcherAlive"] = (self._thread is not None
                                  and self._thread.is_alive())
        out["windowMaxMs"] = self.window_max_ms
        out["windowHotMs"] = self.window_hot_ms
        ewma = self._arrival_ewma_ms
        out["arrivalEwmaMs"] = None if ewma is None else round(ewma, 3)
        return out


# --------------------------------------------------------------------------
# per-mesh registry: every executor over the same device set shares ONE
# dispatcher, so two executors can no longer interleave collective programs
# (the old per-executor _combine_lock never protected against that)
# --------------------------------------------------------------------------

_LAUNCHERS: Dict[Tuple, LaunchScheduler] = {}
_REGISTRY_LOCK = threading.Lock()


def launcher_for_mesh(mesh) -> LaunchScheduler:
    key = tuple(getattr(d, "id", i)
                for i, d in enumerate(mesh.devices.flat))
    with _REGISTRY_LOCK:
        sched = _LAUNCHERS.get(key)
        if sched is None:
            sched = LaunchScheduler(name=f"combine-launch-{len(_LAUNCHERS)}")
            _LAUNCHERS[key] = sched
            # gauge-history rings for the dispatcher: queue depth and the
            # arrival-interval EWMA (the adaptive window's input) at
            # few-second resolution — the history behind /debug/launches'
            # instants. len()/float reads are GIL-atomic, never a sync.
            from pinot_tpu.common.telemetry import TELEMETRY

            TELEMETRY.track_gauge(
                f"{sched._name}.queue_depth",
                lambda s=sched: float(len(s._queue)))
            TELEMETRY.track_gauge(
                f"{sched._name}.arrival_ewma_ms",
                lambda s=sched: float(s._arrival_ewma_ms or 0.0))
        return sched
