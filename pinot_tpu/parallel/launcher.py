"""The sharded combine's launch dispatcher: one thread, ordered launches.

The sharded combine used to serialize every multi-device launch under a
process-global lock (interleaved collective programs deadlock the
runtime). This module is that serialization point (the reference's sized
combine pools, ``BaseCombineOperator.java:55``, with one worker):

- Queries never call a compiled combine directly. They submit a
  :class:`_LaunchRequest` — ``(LaunchKernel, runtime params, num_docs)`` —
  to the per-mesh :class:`LaunchScheduler` and block on a future.
- A single daemon dispatcher thread drains the queue. Because only this
  thread ever launches device programs, launches are totally ordered, so
  collective programs can never interleave, with no lock held across the
  serving path.
- While one program runs, waiting requests pile up. The dispatcher groups
  a drain by **compiled-kernel identity** (``LaunchKernel.key`` — the
  literal-normalized plan fingerprint, so same-shape queries with different
  literals share a kernel). Within a group, requests whose runtime params
  are the *same device arrays* (exact repeats served by the executor's
  param cache) share ONE launch and ONE result buffer (dedup); every
  distinct param set gets one launch of its own, in arrival order.
- Different-shape queries pipeline through the queue in arrival order
  instead of convoying behind a lock: while query A's caller decodes its
  result, the dispatcher is already launching query B.
- The dispatcher keeps its own clock: every second of its thread's life is
  charged to one of :data:`CLOCK_STATES` (``/debug/launches`` ``clock``),
  from ``time.perf_counter`` alone, whether or not a query is traced.
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

# the dispatcher's clock: where each second of its thread's life went.
# empty: waiting with nothing queued; waking: from the submit that found it
# waiting until it holds the drained list (the notify, the condition's lock,
# the interpreter lock); dispatching: a group's jit calls; deviceWait:
# block_until_ready; handingOff: everything else (counters, futures, the
# drain and grouping, the step to the next group or the next wait)
CLOCK_STATES = ("empty", "waking", "dispatching", "deviceWait", "handingOff")


class LaunchKernel:
    """One compiled combine program the dispatcher launches.

    ``call(params, num_docs) -> packed`` takes this query's runtime arrays;
    everything else — staged columns, mesh, output layout — is closed over.
    ``key`` is the literal-normalized identity two requests must share to
    ride one group: same compiled kernel, same staged arrays, same
    num_docs source. ``pallas_spec`` is the fused Pallas kernel's
    PallasSpec, None for a jnp program.
    """

    __slots__ = ("key", "call", "pallas_spec")

    def __init__(self, key: Tuple, call, pallas_spec=None):
        self.key = key
        self.call = call
        self.pallas_spec = pallas_spec

    @property
    def is_pallas(self) -> bool:
        return self.pallas_spec is not None

    def run_one(self, params, num_docs):
        return self.call(params, num_docs)


class _LaunchRequest:
    """One query's pending launch + its coalescing outcome (the fields the
    executor copies into ``QueryStats.launch``)."""

    __slots__ = ("kernel", "params", "num_docs", "future", "t_submit",
                 "batch_size", "queue_wait_ms", "launches_saved",
                 "traced", "request_id", "t_dispatch", "t_launched",
                 "t_ready", "t_handed", "t_resumed", "dispatch_cpu_ms",
                 "thread")

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
        # a traced query's launch: the dispatcher stamps its group's
        # phases here (beside t_submit), the query's thread attaches them
        self.traced = traced
        self.request_id = request_id
        self.t_dispatch = self.t_launched = self.t_ready = 0.0
        self.t_handed = self.t_resumed = 0.0
        self.dispatch_cpu_ms = 0.0
        self.thread = ""

    def result(self, timeout: Optional[float] = None):
        out = self.future.result(timeout)
        if self.traced:
            self.t_resumed = time.perf_counter()
        return out

    def add_spans(self, rec) -> None:
        """This launch's phases as children of the recorder's open span:
        the dispatcher thread's ``Dispatch`` (host side: group, the jit
        calls until the last returns), ``DeviceWait``
        (``block_until_ready``) and ``HandOff`` (ready until it set this
        request's future), and the query thread's ``Resume`` (the future
        set until the thread ran past ``result()``). A group's requests all
        carry the group's one ``Dispatch`` / ``DeviceWait`` pair.
        ``HandOff`` and ``Resume`` carry no CPU: the dispatcher's hand-off
        is the group's, which every rider would count again, and the query
        thread only waits in ``Resume``."""
        if not self.t_ready:
            return  # never launched (the submit or the group failed)
        rec.add_completed(
            "Dispatch", wall_ms=(self.t_launched - self.t_dispatch) * 1e3,
            start=self.t_dispatch, cpu_ms=self.dispatch_cpu_ms,
            thread=self.thread)
        rec.add_completed(
            "DeviceWait", wall_ms=(self.t_ready - self.t_launched) * 1e3,
            start=self.t_launched, thread=self.thread)
        rec.add_completed(
            "HandOff", wall_ms=(self.t_handed - self.t_ready) * 1e3,
            start=self.t_ready, thread=self.thread)
        if self.t_resumed:
            rec.add_completed(
                "Resume", wall_ms=(self.t_resumed - self.t_handed) * 1e3,
                start=self.t_handed)


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
        # cumulative counters (process lifetime; callers diff
        # stats_snapshot() marks, /debug/launches serves snapshot()).
        # Writes-only guard: gauge lambdas read single counters lock-free;
        # stats_snapshot() takes the lock for a consistent cut.
        self._stats_lock = threading.Lock()
        self.requests = 0  # guarded-by-writes: _stats_lock
        self.launches = 0  # guarded-by-writes: _stats_lock
        self.coalesced_launches = 0  # guarded-by-writes: _stats_lock
        self.launches_saved = 0  # guarded-by-writes: _stats_lock
        self.failures = 0  # guarded-by-writes: _stats_lock
        self.max_batch_size = 0  # guarded-by-writes: _stats_lock
        self.queue_wait_ms_total = 0.0  # guarded-by-writes: _stats_lock
        self.queue_wait_ms_max = 0.0  # guarded-by-writes: _stats_lock
        self._registries: List[Any] = []  # guarded-by-writes: _stats_lock
        # the dispatcher's clock, seconds a state. The thread
        # charges what it has passed through at each group's _note and at
        # each wake; while it waits, _idle_since (and the waking submit's
        # _woke_at) let a snapshot count the wait so far
        self._clock = dict.fromkeys(CLOCK_STATES, 0.0)  # guarded-by: _stats_lock
        self._wakes = 0  # guarded-by: _stats_lock
        self._groups = 0  # guarded-by: _stats_lock
        self._idle_since: Optional[float] = None  # guarded-by: _stats_lock
        self._waiting = False  # guarded-by: _cond
        self._woke_at: Optional[float] = None  # guarded-by: _stats_lock
        self._charged_to = 0.0  # dispatcher thread only: charged up to here

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
            self._queue.append(req)
            if self._waiting:
                # this submit wakes a waiting dispatcher: the wake's start
                # (read inside _stats_lock, as every charge is)
                self._waiting = False
                with self._stats_lock:
                    self._woke_at = time.perf_counter()
            self._cond.notify()
        return req

    def close(self) -> None:
        """Stop accepting; the dispatcher drains what's queued and exits.
        Only meaningful for privately-owned schedulers (the per-mesh
        registry keeps its daemons for the process lifetime)."""
        with self._cond:
            self._closed = True
            self._cond.notify()

    # -- dispatcher ----------------------------------------------------------
    def _loop(self) -> None:
        self._charged_to = time.perf_counter()
        while True:
            with self._cond:
                woke = False
                if not self._queue and not self._closed:
                    self._charge_to_wait()
                    self._waiting = True
                    while not self._queue and not self._closed:
                        self._cond.wait()
                    woke = not self._waiting    # a submit, not close()
                    self._waiting = False
                if not self._queue and self._closed:
                    self._charge_to_exit()
                    return
                drained = list(self._queue)
                self._queue.clear()
            if woke:
                self._charge_wake()
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

    # -- the dispatcher's clock ----------------------------------------------
    # (each charge reads the clock inside _stats_lock, so that a snapshot,
    # which counts a wait under way up to its own reading, never sees a
    # counter go back)
    def _charge_to_wait(self) -> None:
        """Nothing queued: charge the step here as handing off; ``empty``
        runs from now until a submit wakes the dispatcher."""
        with self._stats_lock:
            t = time.perf_counter()
            self._clock["handingOff"] += t - self._charged_to
            self._idle_since = t

    def _charge_wake(self) -> None:
        """The drained list in hand after a wait: ``empty`` up to the
        submit that woke the dispatcher, ``waking`` from there to now."""
        with self._stats_lock:
            held = time.perf_counter()
            self._clock["empty"] += self._woke_at - self._idle_since
            self._clock["waking"] += held - self._woke_at
            self._wakes += 1
            self._idle_since = self._woke_at = None
        self._charged_to = held

    def _charge_to_exit(self) -> None:
        """The dispatcher's exit: what is left since the last charge. A
        wait that only ``close()`` ended counts as ``empty``."""
        with self._stats_lock:
            t = time.perf_counter()
            if self._idle_since is not None:
                self._clock["empty"] += t - self._idle_since
                self._idle_since = None
            else:
                self._clock["handingOff"] += t - self._charged_to
        self._charged_to = t

    def _launch_group(self, reqs: List[_LaunchRequest]) -> None:
        import jax

        kernel = reqs[0].kernel
        num_docs = reqs[0].num_docs
        now = time.perf_counter()
        for r in reqs:
            r.queue_wait_ms = (now - r.t_submit) * 1e3
        traced = [r for r in reqs if r.traced]
        cpu0 = time.thread_time() if traced else 0.0
        # a traced group's three phases (Dispatch, DeviceWait, HandOff) also
        # go onto a profiler trace, on this thread's line (under the first
        # traced request's id)
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
        for slot, params in enumerate(uniq):
            try:
                outs[slot] = kernel.run_one(params, num_docs)
            except BaseException as e:  # noqa: BLE001 — futures carry it
                errs[slot] = e
        # wait INSIDE the dispatcher before the next group: device execution
        # stays totally ordered (the no-interleaved-collectives invariant)
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
            ann = tracing.annotate("HandOff", traced[0].request_id)
        for r in traced:
            r.t_dispatch, r.t_launched, r.t_ready = now, launched, ready
            r.dispatch_cpu_ms = cpu_ms
            r.thread = self._name

        # counters before futures: a rider that reads /debug/launches after
        # its answer finds its own launch counted. The clock is charged up
        # to ready; what follows is handed off at the next charge
        self._note(reqs, launches=len(uniq),
                   n_failed=sum(e is not None for e in errs),
                   clock=(now - self._charged_to, launched - now,
                          ready - launched))
        self._charged_to = ready
        n = len(reqs)
        saved = n - len(uniq)
        for r, slot in zip(reqs, req_slot):
            r.batch_size = n
            r.launches_saved = saved
            if r.traced:
                # stamped before the future is set: the waiter reads it
                r.t_handed = time.perf_counter()
            if errs[slot] is not None:
                r.future.set_exception(errs[slot])
            else:
                r.future.set_result(outs[slot])
        if ann is not None:
            ann.__exit__(None, None, None)

    # -- stats / observability ----------------------------------------------
    def _note(self, reqs, launches: int, n_failed: int,
              clock: Tuple[float, float, float]) -> None:
        """Counters of one group, and the dispatcher's clock up to its
        ``ready``: ``clock`` is (handing off before the group, its
        dispatching, its device wait) in seconds."""
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
            if n > launches:    # riders that shared an identical one's launch
                self.coalesced_launches += 1
                self.launches_saved += n - launches
            if n > self.max_batch_size:
                self.max_batch_size = n
            self.queue_wait_ms_total += sum(wait)
            self.queue_wait_ms_max = max(self.queue_wait_ms_max, *wait)
            self._clock["handingOff"] += clock[0]
            self._clock["dispatching"] += clock[1]
            self._clock["deviceWait"] += clock[2]
            self._groups += 1
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

    def stats_snapshot(self) -> Dict[str, Any]:
        """Cumulative counters (callers diff two of these)."""
        with self._stats_lock:
            return {
                "requests": self.requests,
                "launches": self.launches,
                "coalescedLaunches": self.coalesced_launches,
                "launchesSaved": self.launches_saved,
                "failures": self.failures,
                "maxBatchSize": self.max_batch_size,
                "queueWaitMsTotal": round(self.queue_wait_ms_total, 3),
                "queueWaitMsMax": round(self.queue_wait_ms_max, 3),
                "clock": self._clock_locked(),
            }

    def _clock_locked(self) -> Dict[str, float]:
        """The dispatcher's clock in ms, cumulative. A wait under way is
        counted up to now: ``empty`` until the submit that woke it,
        ``waking`` since. Busy, the charges lag by the group in hand."""
        secs = dict(self._clock)
        if self._idle_since is not None:
            now = time.perf_counter()
            woke = now if self._woke_at is None else self._woke_at
            secs["empty"] += woke - self._idle_since
            secs["waking"] += now - woke
        out = {f"{state}Ms": round(s * 1e3, 3) for state, s in secs.items()}
        out.update(wakes=self._wakes, groups=self._groups)
        return out

    def snapshot(self) -> Dict[str, Any]:
        """``/debug/launches`` body: counters + live queue state."""
        out: Dict[str, Any] = self.stats_snapshot()
        out["queued"] = len(self._queue)
        out["dispatcherAlive"] = (self._thread is not None
                                  and self._thread.is_alive())
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
            # gauge-history ring for the dispatcher's queue depth at
            # few-second resolution — the history behind /debug/launches'
            # instant. A len() read is GIL-atomic, never a sync.
            from pinot_tpu.common.telemetry import TELEMETRY

            TELEMETRY.track_gauge(
                f"{sched._name}.queue_depth",
                lambda s=sched: float(len(s._queue)))
        return sched
