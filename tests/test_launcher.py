"""Launch-coalescing tests: the micro-batched dispatcher must (a) return
bit-identical results vs the serial path under concurrent mixed-shape load,
(b) actually coalesce (batch size > 1) when requests pile up, and (c) never
deadlock on the multi-device mesh — the original reason the old global
combine lock existed. Plus the satellites that ride along: the
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
# scheduler unit tests (fake kernels; deterministic coalescing via a
# blocker request that pins the dispatcher while the batch piles up)
# --------------------------------------------------------------------------

def _blocker():
    """(kernel, release) whose single launch parks the dispatcher."""
    gate = threading.Event()

    def call(params, num_docs):
        gate.wait(20)
        return params

    return LaunchKernel(("blocker",), call, max_batch=1), gate


def test_dedup_identical_params():
    sched = LaunchScheduler(name="t-dedup")
    blocker, gate = _blocker()
    calls = []

    def counted(params, num_docs):
        calls.append(params)
        return ("out", params)

    kern = LaunchKernel(("k1",), counted, max_batch=8)
    kern.batchable = False  # isolate the dedup path from vmap
    b = sched.submit(blocker, 0, 0)
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
    assert snap["dedupedRequests"] >= 2
    assert snap["coalescedLaunches"] >= 1


def test_vmapped_batch_distinct_params():
    import jax.numpy as jnp

    sched = LaunchScheduler(name="t-batch")
    blocker, gate = _blocker()
    launches = []

    def call(params, num_docs):
        launches.append(1)
        return params * num_docs

    kern = LaunchKernel(("k2",), call, max_batch=8)
    nd = jnp.int32(3)
    # the dispatcher rides a batched variant, it never builds one: built
    # here, off the serving path (three pad to the variant of four)
    kern.run_many([jnp.float32(v) for v in (0.0, 7.0, 9.0)], nd)
    launches.clear()
    b = sched.submit(blocker, 0, 0)
    reqs = [sched.submit(kern, jnp.float32(v), nd) for v in (1.0, 2.0, 5.0)]
    gate.set()
    b.result(30)
    outs = [float(np.asarray(r.result(30))) for r in reqs]
    assert outs == [3.0, 6.0, 15.0]
    # one vmapped trace serves the whole chunk (the solo fn body runs once
    # under the batching trace, not once per request)
    assert len(launches) == 1
    assert all(r.batch_size == 3 for r in reqs)
    assert sched.stats_snapshot()["launchesSaved"] >= 2
    assert sched.stats_snapshot()["unbuiltGroups"] == 0


def test_unbuilt_batched_variant_is_never_built_by_the_dispatcher():
    """A group whose batched variant the kernel has not built launches its
    members one by one and is counted: tracing and compiling a batched
    program inside a live query's launch makes its riders wait seconds on
    the chip. Built off the serving path, the next group rides it."""
    import jax.numpy as jnp

    sched = LaunchScheduler(name="t-unbuilt")
    launches = []

    def call(params, num_docs):
        launches.append(1)
        return params * num_docs

    kern = LaunchKernel(("ku",), call, max_batch=8)
    nd = jnp.int32(3)
    for round_, want_launches in ((0, 2), (1, 1)):
        blocker, gate = _blocker()
        b = sched.submit(blocker, 0, 0)
        reqs = [sched.submit(kern, jnp.float32(v), nd) for v in (1.0, 2.0)]
        gate.set()
        b.result(30)
        assert [float(np.asarray(r.result(30))) for r in reqs] == [3.0, 6.0]
        assert len(launches) == want_launches, round_
        assert all(r.batch_size == 2 for r in reqs)
        assert reqs[0].launches_saved == 2 - want_launches
        assert sched.snapshot()["unbuiltGroups"] == 1
        if round_ == 0:
            assert not kern.has_batched(2)
            kern.run_many([jnp.float32(0.0), jnp.float32(4.0)], nd)
            assert kern.has_batched(2) and not kern.has_batched(3)
            launches.clear()


def test_unbatchable_kernel_falls_back_serial():
    sched = LaunchScheduler(name="t-serial")
    blocker, gate = _blocker()

    def call(params, num_docs):
        # .item() works on concrete values, explodes under a vmap trace —
        # the shape of backend batching-rule failures
        return params.item() * 2

    import jax.numpy as jnp

    import jax

    kern = LaunchKernel(("k3",), call, max_batch=8)
    # a variant that passes for built and fails when it runs
    kern._vmapped[2] = jax.vmap(call, in_axes=(0, None))
    b = sched.submit(blocker, 0, 0)
    reqs = [sched.submit(kern, jnp.float32(v), 0) for v in (1.0, 4.0)]
    gate.set()
    b.result(30)
    assert [r.result(30) for r in reqs] == [2.0, 8.0]
    assert kern.batchable is False, "failed vmap must disable batching"
    # a later round stays serial and still serves
    r2 = sched.submit(kern, jnp.float32(3.0), 0)
    assert r2.result(30) == 6.0


def test_launch_errors_reach_every_rider():
    sched = LaunchScheduler(name="t-err")
    blocker, gate = _blocker()

    def boom(params, num_docs):
        raise RuntimeError("kernel exploded")

    kern = LaunchKernel(("k4",), boom, max_batch=4)
    kern.batchable = False
    b = sched.submit(blocker, 0, 0)
    params = ("same",)
    reqs = [sched.submit(kern, params, 0) for _ in range(2)]
    gate.set()
    b.result(30)
    for r in reqs:
        with pytest.raises(RuntimeError, match="kernel exploded"):
            r.result(30)
    assert sched.stats_snapshot()["failures"] >= 1


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
    kern = LaunchKernel(("k5",), lambda params, num_docs: params,
                        max_batch=1)
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
    # literal-normalized launch tier) and stack into vmapped launches
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


def _build_batched(dev, segs, sizes=(2, 4, 8)) -> None:
    """Every launch kernel's batched variants, built off the serving path
    (the dispatcher rides them and never builds one)."""
    from pinot_tpu.parallel.combine import SEG_AXIS, pad_segments

    batch = dev.batch_for(segs)
    S = pad_segments(batch.num_segments, dev.mesh.shape[SEG_AXIS])
    num_docs = dev._device_num_docs(batch, S)
    with dev._cache_lock:
        entries = list(dev._param_cache.values())
    for _, lkey, params in entries:
        for size in sizes:
            dev._launch_cache[lkey].run_many([params] * size, num_docs)


def test_concurrency_hammer(setup):
    _, segs = setup
    dev = ShardedQueryExecutor()  # the suite-wide virtual 8-device mesh
    ctxs = [compile_query(q) for q in HAMMER_QUERIES]
    # serial reference pass (also warms every compile)
    serial = []
    for ctx in ctxs:
        rt, _ = dev.execute(ctx, segs)
        serial.append(rt.rows)
    _build_batched(dev, segs)
    mark = dev.launcher.stats_snapshot()

    errors = []
    coalesced_seen = []
    start = threading.Barrier(THREADS)

    def pump(tid: int) -> None:
        try:
            start.wait(30)
            for it in range(ITERS):
                qi = (tid + it) % len(ctxs)
                stats = QueryStats()
                rt, stats = dev.execute(ctxs[qi], segs)
                # (a) bit-identical vs the serial path
                assert rt.rows == serial[qi], \
                    f"thread {tid} iter {it} q{qi} diverged"
                if stats.launch.get("batchSize", 0) > 1:
                    coalesced_seen.append(stats.launch)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=pump, args=(t,), daemon=True)
               for t in range(THREADS)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 120
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    # (c) no deadlock on the multi-device mesh
    assert not any(t.is_alive() for t in threads), \
        "hammer threads hung: combine launches deadlocked"
    assert not errors, errors[:3]
    # (b) at least one coalesced launch with batch size > 1
    delta = dev.launcher.stats_snapshot()
    assert delta["coalescedLaunches"] > mark["coalescedLaunches"], \
        f"no coalescing under {THREADS}-thread load: {delta}"
    assert delta["maxBatchSize"] >= 2
    assert coalesced_seen, "no query reported riding a coalesced batch"
    assert delta["launchesSaved"] > mark["launchesSaved"]


def test_uncontended_single_query_stats(setup):
    """The uncontended path must not report phantom coalescing (and must
    still flow through the dispatcher: launches == requests)."""
    _, segs = setup
    dev = ShardedQueryExecutor()
    rt, stats = dev.execute(compile_query(HAMMER_QUERIES[3]), segs)
    assert stats.launch["launches"] == 1
    assert stats.launch["batchSize"] == 1
    assert stats.launch["coalesced"] == 0


def test_vmapped_real_combine_bit_identical(setup):
    """The vmapped form of the ACTUAL sharded combine (shard_map + psum +
    all_gather on the 8-device mesh) must produce bit-identical packed
    outputs to solo launches — the property the hammer's exactness rides
    on even when scheduling happens to dedup instead of batch."""
    _, segs = setup
    from pinot_tpu.parallel.combine import SEG_AXIS, pad_segments

    dev = ShardedQueryExecutor()
    sqls = [f"SELECT region, sum(qty), count(*) FROM sales "
            f"WHERE year >= {y} GROUP BY region ORDER BY region"
            for y in (2016, 2019)]
    for sql in sqls:  # populate both cache tiers
        dev.execute(compile_query(sql), segs)
    with dev._cache_lock:
        entries = list(dev._param_cache.values())
    assert len(entries) == 2
    (_, lkey0, params0), (_, lkey1, params1) = entries
    assert lkey0 == lkey1, "same-shape literals must share the launch key"
    kernel = dev._launch_cache[lkey0]
    batch = dev.batch_for(segs)
    S = pad_segments(batch.num_segments, dev.mesh.shape[SEG_AXIS])
    num_docs = dev._device_num_docs(batch, S)
    solo = [np.asarray(kernel.run_one(p, num_docs))
            for p in (params0, params1)]
    rows = kernel.run_many([params0, params1], num_docs)
    assert np.array_equal(np.asarray(rows[0]), solo[0])
    assert np.array_equal(np.asarray(rows[1]), solo[1])


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


def test_launch_max_batch_config_key():
    cfg = PinotConfiguration({CommonConstants.LAUNCH_MAX_BATCH_KEY: 1})
    dev = ShardedQueryExecutor(config=cfg)
    assert dev._launch_max_batch == 1


# --------------------------------------------------------------------------
# adaptive micro-batch window (the straggler hold)
# --------------------------------------------------------------------------

def test_window_gathers_stragglers_into_one_batch():
    """With a hot arrival EWMA the dispatcher holds the window open, so a
    straggler submitted ~20 ms behind the first request still rides the
    SAME vmapped launch — no blocker pinning needed."""
    import jax.numpy as jnp

    sched = LaunchScheduler(name="t-window")
    # hot_ms=inf: any PRIMED ewma counts as hot, so the hold is
    # deterministic; prime with a tight synthetic arrival train
    sched.set_window(max_ms=250.0, hot_ms=float("inf"))
    with sched._cond:
        t = time.perf_counter()
        for i in range(5):
            sched._note_arrival_locked(t + i * 0.0005)
    launches = []

    def call(params, num_docs):
        launches.append(1)
        return params * num_docs

    kern = LaunchKernel(("kw",), call, max_batch=8)
    kern.run_many([jnp.float32(0.0), jnp.float32(1.0)], jnp.int32(3))
    launches.clear()
    r1 = sched.submit(kern, jnp.float32(2.0), jnp.int32(3))
    time.sleep(0.02)  # arrives mid-window: must join r1's drain
    r2 = sched.submit(kern, jnp.float32(5.0), jnp.int32(3))
    assert float(np.asarray(r1.result(30))) == 6.0
    assert float(np.asarray(r2.result(30))) == 15.0
    assert r1.batch_size == 2 and r2.batch_size == 2, \
        "the straggler rode the held window into one batch"
    assert len(launches) == 1
    snap = sched.stats_snapshot()
    assert snap["windowWaits"] >= 1
    assert snap["windowGathered"] >= 1
    assert sched.snapshot()["windowMaxMs"] == 250.0


def test_window_idle_traffic_pays_no_hold():
    """Cold EWMA (hot_ms=0 means nothing ever counts hot): a lone request
    must dispatch immediately — no added latency at low QPS."""
    sched = LaunchScheduler(name="t-window-idle")
    sched.set_window(max_ms=500.0, hot_ms=0.0)

    def call(params, num_docs):
        return params

    kern = LaunchKernel(("ki",), call, max_batch=8)
    t0 = time.perf_counter()
    r = sched.submit(kern, ("p",), 0)
    assert r.result(30) == ("p",)
    assert (time.perf_counter() - t0) < 0.4, \
        "idle dispatch must not wait out the window"
    assert sched.stats_snapshot()["windowWaits"] == 0


def test_window_arrival_ewma_tracks_and_resets():
    sched = LaunchScheduler(name="t-ewma")
    sched.set_window(max_ms=1.0, hot_ms=2.0)
    with sched._cond:
        t = 100.0
        sched._note_arrival_locked(t)
        for _ in range(10):  # 1 ms apart: hot
            t += 0.001
            sched._note_arrival_locked(t)
        hot = sched._arrival_ewma_ms
        assert hot is not None and hot < 2.0
        t += 10.0  # a 10 s gap must RESET, not decay over many arrivals
        sched._note_arrival_locked(t)
        assert sched._arrival_ewma_ms > 2.0
    assert sched._window_hold_s(1) == 0.0


def test_window_config_keys():
    cfg = PinotConfiguration({
        CommonConstants.LAUNCH_WINDOW_MS_KEY: 3.5,
        CommonConstants.LAUNCH_WINDOW_HOT_MS_KEY: 9.0})
    dev = ShardedQueryExecutor(config=cfg)
    assert dev.launcher.window_max_ms == 3.5
    assert dev.launcher.window_hot_ms == 9.0
    # restore the shared per-mesh dispatcher for other tests
    dev.launcher.set_window(
        max_ms=CommonConstants.DEFAULT_LAUNCH_WINDOW_MS,
        hot_ms=CommonConstants.DEFAULT_LAUNCH_WINDOW_HOT_MS)


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
