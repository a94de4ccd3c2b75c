"""Launch-dispatcher tests: the one dispatcher thread must (a) return
bit-identical results vs the serial path under concurrent mixed-shape load,
(b) launch every distinct param set once, in arrival order, and let
identical ones share a launch, (c) never overlap two launches, and (d)
never deadlock on the multi-device mesh — the original reason the old
global combine lock existed. Plus the satellites that ride along: the
literal-normalized launch cache, the worker/runner pool config keys, the
batch-column borrow path, and the QueryStats.launch wire."""

import threading
import time

import numpy as np
import pandas as pd
import pytest

from pinot_tpu.common.datatable import DataTable
from pinot_tpu.engine import ServerQueryExecutor
from pinot_tpu.engine.results import QueryStats
from pinot_tpu.parallel import ShardedQueryExecutor
from pinot_tpu.parallel.launcher import LaunchKernel, LaunchScheduler
from pinot_tpu.query import compile_query
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.spi import DataType, FieldSpec, FieldType, IndexingConfig, Schema
from pinot_tpu.spi.config import CommonConstants, PinotConfiguration

RNG = np.random.default_rng(23)
NUM_SEGMENTS = 4
DOCS = 1024  # EQUAL sizes: the borrow path requires capacity parity


def make_schema():
    return Schema("sales", [
        FieldSpec("region", DataType.STRING),
        FieldSpec("kind", DataType.STRING),
        FieldSpec("year", DataType.INT),
        FieldSpec("qty", DataType.LONG, FieldType.METRIC),
        FieldSpec("price", DataType.DOUBLE, FieldType.METRIC),
        FieldSpec("raw_amt", DataType.LONG, FieldType.METRIC),
    ])


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    out = tmp_path_factory.mktemp("launcher_segs")
    regions = ["east", "west", "north", "south"]
    kinds = ["a", "b", "c"]
    segs, frames = [], []
    for i in range(NUM_SEGMENTS):
        # every segment carries the FULL region/kind value sets (leading
        # rows), so each per-segment dictionary equals the unified one —
        # the identity-remap precondition the borrow path verifies
        r = [regions[j % 4] for j in range(4)] + \
            [regions[j] for j in RNG.integers(0, 4, DOCS - 4)]
        k = [kinds[j % 3] for j in range(3)] + \
            [kinds[j] for j in RNG.integers(0, 3, DOCS - 3)]
        frame = {
            "region": r,
            "kind": k,
            "year": RNG.integers(2015, 2024, DOCS).astype(np.int64),
            # full 1..49 coverage per segment: qty's per-segment dictionary
            # must equal the unified one for the dictvals-sharing check
            "qty": np.r_[np.arange(1, 50),
                         RNG.integers(1, 50, DOCS - 49)].astype(np.int64),
            "price": np.round(RNG.normal(100, 25, DOCS), 2),
            "raw_amt": RNG.integers(0, 10_000, DOCS).astype(np.int64),
        }
        frames.append(pd.DataFrame(frame))
        b = SegmentBuilder(
            make_schema(), f"sales_{i}",
            indexing_config=IndexingConfig(no_dictionary_columns=["raw_amt"]))
        b.build({c: list(frame[c]) for c in frame}, str(out))
        segs.append(load_segment(str(out / f"sales_{i}")))
    return pd.concat(frames, ignore_index=True), segs


# --------------------------------------------------------------------------
# scheduler unit tests (fake kernels; deterministic groups via a blocker
# request that pins the dispatcher while the group piles up)
# --------------------------------------------------------------------------

def _pin(sched):
    """(request, release): a launch that parks the dispatcher. Returns once
    the dispatcher is inside it, so that whatever is submitted next waits
    in the queue and meets one drain."""
    gate = threading.Event()
    entered = threading.Event()

    def call(params, num_docs):
        entered.set()
        gate.wait(20)
        return params

    req = sched.submit(LaunchKernel(("blocker",), call), 0, 0)
    assert entered.wait(30), "the dispatcher never picked the blocker up"
    return req, gate


def test_dedup_identical_params():
    sched = LaunchScheduler(name="t-dedup")
    calls = []

    def counted(params, num_docs):
        calls.append(params)
        return ("out", params)

    kern = LaunchKernel(("k1",), counted)
    b, gate = _pin(sched)
    params = ("p",)
    reqs = [sched.submit(kern, params, 7) for _ in range(3)]
    gate.set()
    assert b.result(30) == 0
    outs = [r.result(30) for r in reqs]
    assert outs == [("out", params)] * 3
    assert len(calls) == 1, "identical params must share one launch"
    assert all(r.batch_size == 3 for r in reqs)
    assert all(r.launches_saved == 2 for r in reqs)
    snap = sched.stats_snapshot()
    assert snap["launchesSaved"] >= 2
    assert snap["coalescedLaunches"] >= 1


@pytest.mark.parametrize("n", [2, 3, 8])
def test_distinct_params_each_launch_once_in_arrival_order(n):
    """A same-kernel group of n distinct param sets queued behind a slow
    launch: each rider gets its own answer from its own launch."""
    sched = LaunchScheduler(name=f"t-distinct-{n}")
    calls = []

    def call(params, num_docs):
        calls.append(params)
        return ("out", params, num_docs)

    kern = LaunchKernel(("kd",), call)
    mark = sched.stats_snapshot()
    b, gate = _pin(sched)
    reqs = [sched.submit(kern, (f"p{i}",), 5) for i in range(n)]
    gate.set()
    assert b.result(30) == 0
    outs = [r.result(30) for r in reqs]
    assert outs == [("out", (f"p{i}",), 5) for i in range(n)]
    assert calls == [(f"p{i}",) for i in range(n)]
    assert all(r.batch_size == n and r.launches_saved == 0 for r in reqs)
    assert len({id(o) for o in outs}) == n, "one result buffer a launch"
    snap = sched.stats_snapshot()
    assert snap["launches"] - mark["launches"] == 1 + n   # the blocker's
    assert snap["launchesSaved"] == 0 and snap["coalescedLaunches"] == 0
    assert snap["maxBatchSize"] == n


@pytest.mark.parametrize("order", ["aab", "abab"])
def test_mixed_group_shares_identical_params_only(order):
    sched = LaunchScheduler(name=f"t-mixed-{order}")
    calls = []

    def call(params, num_docs):
        calls.append(params)
        return ("out", params)

    kern = LaunchKernel(("km",), call)
    objs = {"a": ("a",), "b": ("b",)}
    b, gate = _pin(sched)
    mark = sched.stats_snapshot()
    reqs = [sched.submit(kern, objs[c], 0) for c in order]
    gate.set()
    b.result(30)
    outs = [r.result(30) for r in reqs]
    assert outs == [("out", objs[c]) for c in order]
    assert calls == [("a",), ("b",)], "one launch a distinct param object"
    # riders of one param object share its launch's one result buffer
    assert [sum(o is p for p in outs) > 1 for o in outs] \
        == [order.count(c) > 1 for c in order]
    assert all(r.launches_saved == len(order) - 2 for r in reqs)
    snap = sched.stats_snapshot()
    delta = {k: snap[k] - mark[k] for k in ("requests", "launches",
                                            "launchesSaved")}
    # the blocker's own launch is counted after the mark
    assert delta["requests"] == 1 + len(order)
    assert delta["launches"] + delta["launchesSaved"] == delta["requests"]
    assert delta["launches"] == 1 + 2
    assert delta["launchesSaved"] == len(order) - 2


def test_no_two_launches_overlap():
    """The invariant the dispatcher exists for: whatever threads submit and
    whichever kernels they name, one launch runs at a time."""
    sched = LaunchScheduler(name="t-overlap")
    state = {"inside": 0, "overlaps": 0, "calls": 0}
    guard = threading.Lock()

    def call(params, num_docs):
        with guard:
            state["inside"] += 1
            state["calls"] += 1
            if state["inside"] > 1:
                state["overlaps"] += 1
        time.sleep(0.001)       # wide enough for a second launcher to show
        with guard:
            state["inside"] -= 1
        return params

    kernels = [LaunchKernel((f"ko{i}",), call) for i in range(2)]
    errors = []
    start = threading.Barrier(4)

    def pump(tid: int) -> None:
        try:
            start.wait(30)
            for it in range(25):
                params = (tid, it)
                got = sched.submit(kernels[(tid + it) % 2], params,
                                   0).result(30)
                assert got is params
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=pump, args=(t,), daemon=True)
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    assert state["calls"] == 100 and state["overlaps"] == 0
    snap = sched.stats_snapshot()
    assert snap["requests"] == 100 == snap["launches"]


def test_launch_errors_reach_every_rider():
    sched = LaunchScheduler(name="t-err")

    def boom(params, num_docs):
        raise RuntimeError("kernel exploded")

    kern = LaunchKernel(("k4",), boom)
    b, gate = _pin(sched)
    params = ("same",)
    reqs = [sched.submit(kern, params, 0) for _ in range(2)]
    gate.set()
    b.result(30)
    for r in reqs:
        with pytest.raises(RuntimeError, match="kernel exploded"):
            r.result(30)
    assert sched.stats_snapshot()["failures"] >= 1


# --------------------------------------------------------------------------
# the dispatcher's clock: every second of its thread in one of five states
# --------------------------------------------------------------------------

CLOCK_MS = ("emptyMs", "wakingMs", "dispatchingMs", "deviceWaitMs",
            "handingOffMs")


def _clock(sched):
    return sched.stats_snapshot()["clock"]


def _wait_idle(sched, timeout_s=30.0):
    """Until the dispatcher waits with nothing queued."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with sched._stats_lock:
            if sched._idle_since is not None:
                return
        time.sleep(0.001)
    raise AssertionError("the dispatcher never went idle")


def test_the_clock_sums_to_the_dispatchers_wall_time():
    """Waits, wakes, launches back to back and a slow kernel: the five
    states together are the thread's wall time since it started."""
    sched = LaunchScheduler(name="t-clock-sum")
    kern = LaunchKernel(("kc",), lambda params, num_docs:
                        (time.sleep(0.005), params)[1])
    t0 = time.perf_counter()        # the first submit starts the thread
    for i in range(4):
        reqs = [sched.submit(kern, (i, j), 0) for j in range(3)]
        assert [r.result(30) for r in reqs] == [(i, j) for j in range(3)]
        time.sleep(0.2)
    _wait_idle(sched)
    time.sleep(0.2)
    clock = _clock(sched)
    elapsed_ms = (time.perf_counter() - t0) * 1e3
    assert set(clock) == set(CLOCK_MS) | {"wakes", "groups"}
    total = sum(clock[k] for k in CLOCK_MS)
    assert total == pytest.approx(elapsed_ms, rel=0.02), (clock, elapsed_ms)
    assert min(clock[k] for k in CLOCK_MS) >= 0.0
    assert clock["groups"] >= 4 and 1 <= clock["wakes"] <= 4


def test_an_idle_dispatcher_accrues_empty_and_a_submit_counts_one_wake():
    sched = LaunchScheduler(name="t-clock-idle")
    kern = LaunchKernel(("ki",), lambda params, num_docs: params)
    sched.submit(kern, ("warm",), 0).result(30)
    _wait_idle(sched)
    before = _clock(sched)
    time.sleep(0.2)
    idle = _clock(sched)
    assert idle["emptyMs"] - before["emptyMs"] >= 150.0
    assert {k: idle[k] for k in ("wakes", "groups", "dispatchingMs")} \
        == {k: before[k] for k in ("wakes", "groups", "dispatchingMs")}
    req = sched.submit(kern, ("p",), 0, traced=True)
    assert req.result(30) == ("p",)
    _wait_idle(sched)
    after = _clock(sched)
    assert after["wakes"] == idle["wakes"] + 1
    assert after["groups"] == idle["groups"] + 1
    assert after["wakingMs"] > idle["wakingMs"]
    # the wake lies inside the waker's queue wait: its submit until the
    # drain, before the group starts
    assert after["wakingMs"] - idle["wakingMs"] <= req.queue_wait_ms + 0.002
    # every reading goes forward
    for a, b in ((before, idle), (idle, after)):
        assert all(b[k] >= a[k] for k in CLOCK_MS)


def test_a_slow_jit_call_shows_in_dispatching():
    sched = LaunchScheduler(name="t-clock-slow")

    def slow(params, num_docs):
        time.sleep(0.02)
        return params

    sched.submit(LaunchKernel(("kw",), lambda p, n: p), 0, 0).result(30)
    _wait_idle(sched)
    before = _clock(sched)
    reqs = [sched.submit(LaunchKernel(("ks",), slow), (i,), 0)
            for i in range(3)]
    for r in reqs:
        r.result(30)
    _wait_idle(sched)
    after = _clock(sched)
    grew = {k: after[k] - before[k] for k in CLOCK_MS}
    assert grew["dispatchingMs"] >= 3 * 20.0 * 0.95, grew
    # the calls returned host values: nothing to wait for on a device
    assert grew["deviceWaitMs"] < grew["dispatchingMs"] / 4, grew


def test_the_clock_never_goes_back_under_load():
    """More submitting threads than cores and a reader snapshotting all
    the while, on a short switch interval: every reading of every state
    goes forward, and the states end up summing to the thread's life."""
    import os
    import sys

    sched = LaunchScheduler(name="t-clock-load")
    kern = LaunchKernel(("kl",), lambda params, num_docs: params)
    readings, errors = [], []
    stop = threading.Event()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        t0 = time.perf_counter()
        sched.submit(kern, ("first",), 0).result(30)

        def read():
            while not stop.is_set():
                readings.append(_clock(sched))

        def pump(tid):
            try:
                for it in range(20):
                    assert sched.submit(kern, (tid, it), 0).result(30) \
                        == (tid, it)
                    if it % 5 == 0:
                        time.sleep(0.002)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        reader = threading.Thread(target=read, daemon=True)
        reader.start()
        threads = [threading.Thread(target=pump, args=(t,), daemon=True)
                   for t in range((os.cpu_count() or 1) + 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
        _wait_idle(sched)
        stop.set()
        reader.join(30)
        assert not reader.is_alive()
        end = _clock(sched)
        elapsed_ms = (time.perf_counter() - t0) * 1e3
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors[:3]
    readings.append(end)
    for a, b in zip(readings, readings[1:]):
        assert all(b[k] >= a[k] for k in CLOCK_MS + ("wakes", "groups")), \
            (a, b)
    assert sum(end[k] for k in CLOCK_MS) == pytest.approx(elapsed_ms,
                                                          rel=0.02)
    assert end["wakes"] <= end["groups"]


def test_dispatcher_crash_completes_waiters_and_recovers(monkeypatch):
    """A failure escaping _launch_group entirely (an import error, a bug in
    the grouping) must still complete every waiter's future — the 8-thread
    hang shape — and the dispatcher must keep serving afterwards."""
    sched = LaunchScheduler(name="t-crash")
    orig = LaunchScheduler._launch_group
    crashed = []

    def flaky(self, reqs):
        if not crashed:
            crashed.append(True)
            raise RuntimeError("synthetic dispatcher bug")
        return orig(self, reqs)

    monkeypatch.setattr(LaunchScheduler, "_launch_group", flaky)
    kern = LaunchKernel(("k5",), lambda params, num_docs: params)
    req = sched.submit(kern, ("p1",), 0)
    with pytest.raises(RuntimeError, match="synthetic dispatcher bug"):
        req.result(30)
    # the dispatcher thread survived (or was revived): next launch works
    assert sched.submit(kern, ("p2",), 0).result(30) == ("p2",)


# --------------------------------------------------------------------------
# the hammer: mixed same-shape / different-shape queries from >= 8 threads
# --------------------------------------------------------------------------

HAMMER_QUERIES = [
    # same shape, different literals: share one compiled kernel (the
    # literal-normalized launch tier), one launch each
    "SELECT region, sum(qty), count(*) FROM sales WHERE year >= 2016 "
    "GROUP BY region ORDER BY region",
    "SELECT region, sum(qty), count(*) FROM sales WHERE year >= 2018 "
    "GROUP BY region ORDER BY region",
    "SELECT region, sum(qty), count(*) FROM sales WHERE year >= 2020 "
    "GROUP BY region ORDER BY region",
    # different shapes: pipeline through the queue
    "SELECT count(*), sum(price) FROM sales WHERE kind = 'a'",
    "SELECT year, min(price), max(price) FROM sales GROUP BY year "
    "ORDER BY year",
    "SELECT kind, avg(qty), sum(raw_amt) FROM sales GROUP BY kind "
    "ORDER BY kind",
]

THREADS = 8
ITERS = 6


def test_concurrency_hammer(setup):
    _, segs = setup
    dev = ShardedQueryExecutor()  # the suite-wide virtual 8-device mesh
    ctxs = [compile_query(q) for q in HAMMER_QUERIES]
    # serial reference pass (also warms every compile)
    serial = []
    for ctx in ctxs:
        rt, _ = dev.execute(ctx, segs)
        serial.append(rt.rows)
    mark = dev.launcher.stats_snapshot()

    errors = []
    start = threading.Barrier(THREADS)

    def pump(tid: int) -> None:
        try:
            start.wait(30)
            for it in range(ITERS):
                qi = (tid + it) % len(ctxs)
                rt, stats = dev.execute(ctxs[qi], segs)
                # (a) bit-identical vs the serial path
                assert rt.rows == serial[qi], \
                    f"thread {tid} iter {it} q{qi} diverged"
                assert stats.launch["launches"] == 1
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=pump, args=(t,), daemon=True)
               for t in range(THREADS)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 120
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    # (d) no deadlock on the multi-device mesh
    assert not any(t.is_alive() for t in threads), \
        "hammer threads hung: combine launches deadlocked"
    assert not errors, errors[:3]
    # (b) every request passed the dispatcher once: launched, or riding an
    # identical rider's launch (which of the two is arrival timing)
    snap = dev.launcher.stats_snapshot()
    delta = {k: snap[k] - mark[k] for k in ("requests", "launches",
                                            "launchesSaved", "failures")}
    # (queries the executor's single flight merged never reach it)
    assert 0 < delta["requests"] <= THREADS * ITERS
    assert delta["launches"] + delta["launchesSaved"] == delta["requests"]
    assert delta["failures"] == 0


def test_uncontended_single_query_stats(setup):
    """The uncontended path must not report phantom coalescing (and must
    still flow through the dispatcher: launches == requests)."""
    _, segs = setup
    dev = ShardedQueryExecutor()
    rt, stats = dev.execute(compile_query(HAMMER_QUERIES[3]), segs)
    assert stats.launch["launches"] == 1
    assert stats.launch["batchSize"] == 1
    assert stats.launch["coalesced"] == 0


@pytest.mark.parametrize("template", [
    "SELECT count(*), sum(qty) FROM sales WHERE year >= {y}",
    "SELECT region, sum(qty), count(*) FROM sales WHERE year >= {y} "
    "GROUP BY region ORDER BY region",
], ids=["scalar", "group_by"])
def test_two_literals_together_answer_as_alone(setup, template):
    """Two literals of one shape met by one drain of the real sharded
    executor's dispatcher (shard_map + psum on the 8-device mesh): one
    kernel, two launches, each answer bit for bit what it is alone."""
    _, segs = setup
    dev = ShardedQueryExecutor()
    ctxs = [compile_query(template.format(y=y)) for y in (2016, 2019)]
    alone = [dev.execute(ctx, segs)[0].rows for ctx in ctxs]
    assert alone[0] != alone[1]
    b, gate = _pin(dev.launcher)
    got = [None, None]

    def query(i: int) -> None:
        got[i] = dev.execute(ctxs[i], segs)

    threads = [threading.Thread(target=query, args=(i,), daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 60
    while len(dev.launcher._queue) < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    queued = len(dev.launcher._queue)
    gate.set()
    b.result(30)
    for t in threads:
        t.join(60)
    assert queued == 2 and not any(t.is_alive() for t in threads)
    for i, (rt, stats) in enumerate(got):
        assert rt.rows == alone[i]
        assert stats.launch["batchSize"] == 2, "one kernel, one group"
        assert stats.launch["launches"] == 1
        assert stats.launch["launchesSaved"] == 0


# --------------------------------------------------------------------------
# literal-normalized launch tier (the query-cache churn satellite)
# --------------------------------------------------------------------------

def test_unique_literals_share_compiled_launch_entry(setup):
    _, segs = setup
    host = ServerQueryExecutor(use_device=False)
    dev = ShardedQueryExecutor()
    sqls = [f"SELECT region, sum(qty) FROM sales WHERE year >= {y} "
            "GROUP BY region ORDER BY region" for y in (2016, 2017, 2019,
                                                        2021)]
    rt0, _ = dev.execute(compile_query(sqls[0]), segs)
    n_launch = len(dev._launch_cache)
    n_kernels = len(dev.sharded_kernels)
    for sql in sqls[1:]:
        got, _ = dev.execute(compile_query(sql), segs)
        want, _ = host.execute(compile_query(sql), segs)
        assert [r[0] for r in got.rows] == [r[0] for r in want.rows]
        for gr, wr in zip(got.rows, want.rows):
            assert gr[1] == pytest.approx(wr[1], rel=1e-5)
    # unique literals HIT the launch tier (one compiled closure), while the
    # exact-literal param tier holds one entry per literal set
    assert len(dev._launch_cache) == n_launch
    assert len(dev.sharded_kernels) == n_kernels
    assert len(dev._param_cache) >= len(sqls)
    # exact repeat: the param tier serves the same device params object,
    # which is what makes dispatcher-level dedup possible
    with dev._cache_lock:
        before = {k: id(v[2]) for k, v in dev._param_cache.items()}
    dev.execute(compile_query(sqls[0]), segs)
    with dev._cache_lock:
        after = {k: id(v[2]) for k, v in dev._param_cache.items()}
    assert before == after


# --------------------------------------------------------------------------
# pool sizing knobs (runner/worker threads satellite)
# --------------------------------------------------------------------------

def test_worker_threads_config_key():
    import os

    cfg = PinotConfiguration({CommonConstants.WORKER_THREADS_KEY: 3})
    ex = ServerQueryExecutor(use_device=False, config=cfg)
    assert ex.worker_threads == 3
    assert ex._worker_pool().num_workers == 3
    # default preserves the old hardcoded fan-out bound
    ex2 = ServerQueryExecutor(use_device=False)
    assert ex2.worker_threads == min(os.cpu_count() or 1, 8)
    # the relaxed key spelling resolves too (PinotConfiguration contract)
    cfg3 = PinotConfiguration({"pinot.server.query.workerThreads": 2})
    ex3 = ServerQueryExecutor(use_device=False, config=cfg3)
    assert ex3.worker_threads == 2


def test_worker_pool_runs_fanout_and_reuses(setup):
    _, segs = setup
    cfg = PinotConfiguration({CommonConstants.WORKER_THREADS_KEY: 4})
    ex = ServerQueryExecutor(use_device=False, config=cfg)
    ctx = compile_query("SELECT region, sum(qty) FROM sales "
                        "GROUP BY region ORDER BY region")
    rt1, _ = ex.execute(ctx, segs)
    pool = ex._segment_pool
    assert pool is not None, "fan-out should have built the persistent pool"
    rt2, _ = ex.execute(compile_query(
        "SELECT region, sum(qty) FROM sales GROUP BY region "
        "ORDER BY region"), segs)
    assert ex._segment_pool is pool, "pool must persist across queries"
    assert rt1.rows == rt2.rows


def test_runner_threads_config_key():
    from pinot_tpu.server.scheduler import make_scheduler

    cfg = PinotConfiguration({CommonConstants.RUNNER_THREADS_KEY: 2})
    sched = make_scheduler("fcfs", config=cfg)
    try:
        assert len(sched._pool._threads) == 2
    finally:
        sched.shutdown(timeout_s=1)


# --------------------------------------------------------------------------
# cross-query column dedup (batch -> per-segment borrow satellite)
# --------------------------------------------------------------------------

def test_per_segment_path_borrows_batch_columns(setup):
    _, segs = setup
    dev = ShardedQueryExecutor()
    host = ServerQueryExecutor(use_device=False)
    sql = ("SELECT region, sum(raw_amt) FROM sales "
           "GROUP BY region ORDER BY region")
    # sharded combine stages the batch's device copies of region/raw_amt
    dev.execute(compile_query(sql), segs)
    assert dev.residency.stats_snapshot()["borrows"] == 0
    # single-segment queries take the per-segment path; its staging must
    # borrow the resident batch copies instead of a second H2D pass
    got, _ = dev.execute(compile_query(sql), [segs[0]])
    want, _ = host.execute(compile_query(sql), [segs[0]])
    assert [r[0] for r in got.rows] == [r[0] for r in want.rows]
    for gr, wr in zip(got.rows, want.rows):
        assert gr[1] == pytest.approx(wr[1], rel=1e-6)
    snap = dev.residency.stats_snapshot()
    assert snap["borrows"] >= 1, "per-segment staging re-staged columns " \
        "a resident batch already holds on device"
    # numeric dict columns share the unified dictvals BUFFER outright
    staged = dev.residency.stage(segs[0])
    qty_batch = dev._staged_column(dev.batch_for(segs), "qty",
                                   dev.mesh.shape["seg"])
    assert staged.column("qty").dictvals is qty_batch["dictvals"]


def test_borrow_skips_incompatible_remaps(tmp_path):
    """Segments whose dictionaries DIFFER from the unified one must stage
    their own arrays — a borrowed row would carry foreign dictIds."""
    out = tmp_path / "skew"
    segs = []
    for i, vals in enumerate((["aa", "bb"], ["bb", "cc"])):
        b = SegmentBuilder(Schema("skew", [
            FieldSpec("d", DataType.STRING),
            FieldSpec("m", DataType.LONG, FieldType.METRIC)]), f"skew_{i}")
        b.build({"d": [vals[j % 2] for j in range(64)],
                 "m": list(range(64))}, str(out))
        segs.append(load_segment(str(out / f"skew_{i}")))
    dev = ShardedQueryExecutor()
    host = ServerQueryExecutor(use_device=False)
    sql = "SELECT d, sum(m) FROM skew GROUP BY d ORDER BY d"
    dev.execute(compile_query(sql), segs)
    borrows0 = dev.residency.stats_snapshot()["borrows"]
    got, _ = dev.execute(compile_query(sql), [segs[1]])
    want, _ = host.execute(compile_query(sql), [segs[1]])
    assert got.rows == want.rows
    # segment 1 stages TWO columns: 'm' (identical value sets -> identity
    # remap) may borrow, but 'd' ('bb' is unified id 1, its own id 0) must
    # NOT — a borrowed row would group under the wrong keys
    assert dev.residency.stats_snapshot()["borrows"] - borrows0 <= 1


# --------------------------------------------------------------------------
# QueryStats.launch on the wire + merge semantics
# --------------------------------------------------------------------------

def test_launch_stats_merge_and_wire():
    a = QueryStats()
    a.launch = {"launches": 1, "coalesced": 1, "batchSize": 3,
                "launchesSaved": 2, "queueWaitMs": 1.5}
    b = QueryStats()
    b.launch = {"launches": 1, "coalesced": 0, "batchSize": 1,
                "launchesSaved": 0, "queueWaitMs": 4.0}
    a.merge(b)
    assert a.launch["launches"] == 2          # counters sum
    assert a.launch["coalesced"] == 1
    assert a.launch["launchesSaved"] == 2
    assert a.launch["batchSize"] == 3         # max keys
    assert a.launch["queueWaitMs"] == 4.0

    dt = DataTable.for_aggregation([1.0], a)
    for raw in (dt.to_bytes(), dt.to_json_bytes()):
        back = DataTable.from_bytes(raw)
        assert back.stats.launch == a.launch
    # absent stays absent (no phantom key on host-path replies)
    empty = DataTable.for_aggregation([1.0], QueryStats())
    assert DataTable.from_bytes(empty.to_bytes()).stats.launch == {}


LAUNCHES_KEYS = {
    "enabled", "requests", "launches", "coalescedLaunches", "launchesSaved",
    "failures", "maxBatchSize", "queueWaitMsTotal", "queueWaitMsMax",
    "clock", "queued", "dispatcherAlive"}
SCHEDULER_KEYS = {"scheduler", "admission", "kernelFlight", "queryFlight",
                  "stallWatch"}


@pytest.mark.parametrize("method, keys", [
    ("launch_debug", LAUNCHES_KEYS), ("scheduler_debug", SCHEDULER_KEYS)],
    ids=["launches", "scheduler"])
def test_debug_endpoint_key_sets(method, keys):
    """``/debug/launches`` and ``/debug/scheduler`` hold these keys and no
    other: what went with the batching and the window stays gone."""
    from pinot_tpu.controller.state import ClusterStateStore
    from pinot_tpu.server.server import ServerInstance

    inst = ServerInstance(f"Server_keys_{method}", ClusterStateStore(),
                          executor=ShardedQueryExecutor())
    assert set(getattr(inst, method)()) == keys


def test_debug_launches_endpoint(setup):
    _, segs = setup
    from pinot_tpu.controller.state import ClusterStateStore
    from pinot_tpu.server.server import ServerInstance

    store = ClusterStateStore()
    inst = ServerInstance("Server_launch_0", store,
                         executor=ShardedQueryExecutor())
    try:
        d = inst.launch_debug()
        assert d["enabled"] is True
        assert "launches" in d and "queued" in d
        host_inst = ServerInstance("Server_launch_1", store,
                                   executor=ServerQueryExecutor())
        assert host_inst.launch_debug() == {"enabled": False}
    finally:
        pass  # instances were never started; nothing to drain
