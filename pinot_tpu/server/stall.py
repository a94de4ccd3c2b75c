"""The server's stall watch: gaps with requests in flight and none done.

Under an open loop a server that answers nothing for a while builds a
backlog, and one such gap decides a window's tail. The watch counts, while
at least one request is in flight, every gap of more than ``threshold_ms``
between two completions (or from the arrival that ended an idle spell to
the first completion), and says what the process was doing in it as far as
the process can know:

- the collector: ``gc.callbacks`` times every run; a stall carries the
  milliseconds of collector runs that lie inside it;
- residency work: the residency manager's counters (misses, evictions,
  prefetches, demotions, promotions, spills) that moved inside it;
- everything else, a lock's holder included: a sampler thread that wakes
  every ``SAMPLE_EVERY_S`` takes, once a gap that has passed the threshold,
  the stack of every thread that stands in this package: the innermost
  frame of the package and the frame it is in. A thread that waits for a
  lock shows the line that takes it, and the one thread that is somewhere
  else is its holder.

A slow query alone in flight is a stall too: nothing completed, something
was due. ``/debug/scheduler`` carries the snapshot under ``stallWatch``.
"""

from __future__ import annotations

import gc
import sys
import threading
import time

from collections import Counter, deque
from typing import Any, Deque, Dict, List, Optional, Tuple

THRESHOLD_MS = 50.0
SAMPLE_EVERY_S = 0.025
_RESIDENCY_COUNTERS = ("misses", "evictions", "prefetched", "demotions",
                       "promotions", "spills")
_PACKAGE = "pinot_tpu"


def _stacks(skip_ident: int) -> List[str]:
    """``<thread role>:<innermost frame of the package> > <leaf frame>`` of
    every thread that stands in the package."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        if ident == skip_ident:
            continue
        leaf = f"{frame.f_code.co_name}:{frame.f_lineno}"
        ours, f = None, frame
        while f is not None:
            mod = f.f_globals.get("__name__", "")
            if mod.startswith(_PACKAGE):
                ours = f"{mod[len(_PACKAGE) + 1:]}.{f.f_code.co_name}" \
                       f":{f.f_lineno}"
                break
            f = f.f_back
        if ours is None:
            continue
        role = names.get(ident, "?").rstrip("0123456789-_")
        out.append(f"{role}:{ours}" if f is frame
                   else f"{role}:{ours} > {leaf}")
    return out


class StallWatch:
    """``begin()`` as a request reaches the server, ``end()`` as its answer
    leaves; everything else is the snapshot."""

    def __init__(self, residency: Any = None,
                 threshold_ms: float = THRESHOLD_MS,
                 clock=time.perf_counter):
        self._residency = residency
        self.threshold_ms = float(threshold_ms)
        self._clock = clock
        self._lock = threading.Lock()
        self._inflight = 0  # guarded-by: _lock
        self._mark = 0.0  # start of the running gap; guarded-by: _lock
        self._mark_residency: Tuple[int, ...] = ()  # guarded-by: _lock
        self.stalls = 0  # guarded-by: _lock
        self.stall_ms_total = 0.0  # guarded-by: _lock
        self.stall_ms_max = 0.0  # guarded-by: _lock
        self.stall_gc_ms = 0.0  # guarded-by: _lock
        self._residency_in_stalls: Counter = Counter()  # guarded-by: _lock
        self._where: Counter = Counter()  # guarded-by: _lock
        self._last: Deque[Dict[str, Any]] = deque(maxlen=8)  # guarded-by: _lock
        # collector runs: (start, stop, generation), newest last
        self._gc_runs: Deque[Tuple[float, float, int]] = deque(maxlen=512)
        self._gc_t0 = 0.0
        self.gc_runs = [0, 0, 0]
        self.gc_ms_total = 0.0
        # the sampler's one reading of the running gap: (its mark, stacks)
        self._sample: Optional[Tuple[float, List[str]]] = None  # guarded-by: _lock
        self.sampled = 0  # guarded-by: _lock
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "StallWatch":
        if self._thread is None:
            gc.callbacks.append(self._on_gc)
            self._thread = threading.Thread(target=self._sampler, daemon=True,
                                            name="stall-watch")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
            try:
                gc.callbacks.remove(self._on_gc)
            except ValueError:
                pass

    # -- the requests --------------------------------------------------------
    def begin(self) -> None:
        with self._lock:
            if self._inflight == 0:
                self._set_mark_locked(self._clock())
            self._inflight += 1

    def end(self) -> None:
        with self._lock:
            now = self._clock()
            gap_ms = (now - self._mark) * 1e3
            if gap_ms > self.threshold_ms:
                self._stalled_locked(self._mark, now, gap_ms)
            self._inflight -= 1
            self._set_mark_locked(now)

    def _set_mark_locked(self, now: float) -> None:
        self._mark = now
        r = self._residency
        if r is not None:
            self._mark_residency = tuple(getattr(r, k, 0)
                                         for k in _RESIDENCY_COUNTERS)

    def _stalled_locked(self, begin: float, end: float,
                        gap_ms: float) -> None:
        self.stalls += 1
        self.stall_ms_total += gap_ms
        self.stall_ms_max = max(self.stall_ms_max, gap_ms)
        gc_ms = sum(max(0.0, min(b, end) - max(a, begin))
                    for a, b, _ in list(self._gc_runs)) * 1e3
        self.stall_gc_ms += gc_ms
        moved = {}
        r = self._residency
        if r is not None and self._mark_residency:
            for k, before in zip(_RESIDENCY_COUNTERS, self._mark_residency):
                d = getattr(r, k, 0) - before
                if d:
                    moved[k] = d
                    self._residency_in_stalls[k] += d
        sample = self._sample
        where = sample[1] if sample is not None and sample[0] == begin else []
        self._where.update(where)
        self._last.append({"atEpochMs": round(time.time() * 1e3, 1),
                           "ms": round(gap_ms, 3), "gcMs": round(gc_ms, 3),
                           "inflight": self._inflight, "residency": moved,
                           "where": where[:12]})

    # -- what the process was doing -----------------------------------------
    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        now = self._clock()
        if phase == "start":
            self._gc_t0 = now
            return
        gen = min(int(info.get("generation", 0)), 2)
        self.gc_runs[gen] += 1
        self.gc_ms_total += (now - self._gc_t0) * 1e3
        self._gc_runs.append((self._gc_t0, now, gen))

    def _sampler(self) -> None:
        me = threading.get_ident()
        while not self._stop.wait(SAMPLE_EVERY_S):
            with self._lock:
                mark = self._mark
                due = (self._inflight > 0
                       and (self._clock() - mark) * 1e3 > self.threshold_ms
                       and (self._sample is None
                            or self._sample[0] != mark))
            if due:
                stacks = _stacks(me)
                with self._lock:
                    self._sample = (mark, stacks)
                    self.sampled += 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "thresholdMs": self.threshold_ms,
                "inflight": self._inflight,
                "stalls": self.stalls,
                "stallMsTotal": round(self.stall_ms_total, 3),
                "stallMsMax": round(self.stall_ms_max, 3),
                "gc": {"runs": list(self.gc_runs),
                       "msTotal": round(self.gc_ms_total, 3),
                       "msInStalls": round(self.stall_gc_ms, 3)},
                "residencyInStalls": dict(self._residency_in_stalls),
                "sampled": self.sampled,
                "where": dict(self._where.most_common(16)),
                "last": list(self._last),
            }
