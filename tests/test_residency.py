"""HBM residency manager: budget / pins / LRU / spill / prefetch / wire.

The invariants the subsystem guarantees (engine/residency.py):
- concurrent stagers of one segment share ONE StagedSegment (the old
  get-then-set race built duplicate device arrays and leaked one set);
- budget enforcement evicts LRU-first and only UNPINNED residents;
- a query whose working set cannot fit spills to the host engine and
  returns host-identical results (graceful degradation, no device OOM);
- reload keeps the identity-based invalidation;
- ``QueryStats.staging`` merges across segments/shards and round-trips
  the DataTable wire;
- sharded batch eviction drops EVERY cache derived from a batch, for
  every batch containing an evicted segment.
"""

import threading

import numpy as np
import pytest

from pinot_tpu.common.datatable import DataTable
from pinot_tpu.engine import QueryStats, ServerQueryExecutor
from pinot_tpu.engine.residency import (
    QueryLease,
    ResidencyManager,
    estimate_segment_bytes,
)
from pinot_tpu.parallel import ShardedQueryExecutor
from pinot_tpu.query import compile_query
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema

RNG = np.random.default_rng(7)
N = 1024
NUM_SEGMENTS = 4
COLUMNS = ("region", "qty")

GROUP_SQL = ("SELECT region, sum(qty), count(*) FROM sales "
             "GROUP BY region ORDER BY region")
AGG_SQL = "SELECT sum(qty), count(*) FROM sales WHERE region != 'west'"


def _schema():
    return Schema("sales", [
        FieldSpec("region", DataType.STRING),
        FieldSpec("qty", DataType.LONG, FieldType.METRIC),
    ])


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    out = tmp_path_factory.mktemp("residency_segs")
    regions = ["east", "west", "north", "south"]
    built = []
    for i in range(NUM_SEGMENTS):
        b = SegmentBuilder(_schema(), f"sales_{i}")
        b.build({
            "region": [regions[j] for j in RNG.integers(0, 4, N)],
            "qty": RNG.integers(1, 50, N).tolist(),
        }, str(out))
        built.append(load_segment(str(out / f"sales_{i}")))
    return built


def _stage_full(rm: ResidencyManager, seg, lease=None):
    st = rm.stage(seg, lease=lease)
    for c in COLUMNS:
        st.column(c)
    return st


def _fullest_device(rm: ResidencyManager) -> int:
    return max(d["stagedBytes"] for d in rm.snapshot()["devices"])


def _host_rows(segs, sql):
    host = ServerQueryExecutor(use_device=False)
    rt, _ = host.execute(compile_query(sql), segs)
    return rt.rows


# --------------------------------------------------------------------------
# lock correctness (the stage() race satellite)
# --------------------------------------------------------------------------

def test_concurrent_stage_shares_one_resident(segs):
    rm = ResidencyManager(budget_bytes=0)  # uncapped
    barrier = threading.Barrier(8)
    got = []

    def worker():
        barrier.wait()
        got.append(rm.stage(segs[0]))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len({id(s) for s in got}) == 1, \
        "concurrent stagers built duplicate StagedSegments (device leak)"
    assert rm.misses == 1 and rm.hits == 7


def test_stage_evict_thread_hammer(segs):
    """Stage + column-build + evict from many threads: no exceptions, and
    the manager ends in a consistent state."""
    rm = ResidencyManager(budget_bytes=0)
    stop = threading.Event()
    errors = []

    def stager(seg):
        while not stop.is_set():
            try:
                st = rm.stage(seg)
                st.column("region")
                st.column("qty")
            except Exception as e:  # pragma: no cover - failure mode
                errors.append(e)
                return

    def evictor():
        while not stop.is_set():
            for s in segs[:2]:
                try:
                    rm.evict(s.segment_name)
                except Exception as e:  # pragma: no cover - failure mode
                    errors.append(e)
                    return

    threads = [threading.Thread(target=stager, args=(s,))
               for s in segs[:2] for _ in range(3)]
    threads.append(threading.Thread(target=evictor))
    for t in threads:
        t.start()
    stop.wait(1.0)
    stop.set()
    for t in threads:
        t.join()
    assert not errors
    # post-hammer: staging still serves working residents
    st = rm.stage(segs[0])
    assert st.column("region").fwd is not None
    assert rm.staged_bytes() > 0


# --------------------------------------------------------------------------
# budget / LRU / pins
# --------------------------------------------------------------------------

def test_budget_evicts_lru_first(segs):
    rm = ResidencyManager(budget_bytes=0)
    for s in segs[:3]:
        _stage_full(rm, s)
    per_seg = rm.staged_bytes() // 3
    # touch segment 0: LRU order becomes [1, 2, 0]
    rm.stage(segs[0])
    rm.set_budget_bytes(int(per_seg * 2.5))
    names = rm.resident_names()
    assert segs[1].segment_name not in names, "LRU entry must evict first"
    assert segs[0].segment_name in names
    assert segs[2].segment_name in names
    snap = rm.stats_snapshot()
    assert snap["evictions"] == 1
    assert snap["stagedBytes"] <= int(per_seg * 2.5)


def test_register_accounts_and_enforces_on_insert():
    """Regression (graftlint conservation finding): ``register()`` used to
    insert a batch resident without re-running byte accounting or budget
    enforcement — stagedBytes drifted from reality until the next
    unrelated refresh, and over-budget batch inserts never evicted."""
    class _Resident:
        def __init__(self, n):
            self._n = n
            self.released = False

        def nbytes(self):
            return self._n

        def release(self):
            self.released = True

    rm = ResidencyManager(budget_bytes=1000)
    a = _Resident(600)
    rm.register("a", lambda: a)
    assert rm.staged_bytes() == 600, \
        "insert must be accounted on the register() call itself"
    b = _Resident(600)
    rm.register("b", lambda: b)
    # over budget: the unpinned LRU entry (a) must evict on the SAME call
    assert a.released and not b.released
    assert rm.resident_names() == ["b"]
    assert rm.staged_bytes() == 600


def test_pinned_segments_survive_eviction_pressure(segs):
    rm = ResidencyManager(budget_bytes=0)
    lease = QueryLease()
    _stage_full(rm, segs[0], lease=lease)
    _stage_full(rm, segs[1])  # unpinned
    assert rm.staged_bytes() > 0
    rm.set_budget_bytes(1)  # everything must go... except pins
    names = rm.resident_names()
    assert segs[0].segment_name in names, "pinned resident was evicted"
    assert segs[1].segment_name not in names
    assert rm.pin_blocked >= 1
    # lease closes -> the pin releases -> budget enforcement reclaims it
    stats = QueryStats()
    rm.end_query(lease, stats)
    assert segs[0].segment_name not in rm.resident_names()
    assert stats.staging["pinBlockedEvictions"] >= 0
    assert stats.staging["stagedBytes"] == 0


def test_reload_keeps_identity_invalidation(segs, tmp_path):
    rm = ResidencyManager(budget_bytes=0)
    st1 = _stage_full(rm, segs[0])
    reloaded = load_segment(segs[0].segment_dir)  # same name, new object
    st2 = rm.stage(reloaded)
    assert st2 is not st1
    assert st2.segment is reloaded
    assert rm.misses == 2  # both stagings were builds, not a stale hit
    assert len(rm.resident_names()) == 1


def test_estimate_tracks_actual_bytes(segs):
    rm = ResidencyManager(budget_bytes=0)
    st = _stage_full(rm, segs[0])
    est = estimate_segment_bytes(segs[0], COLUMNS)
    actual = st.nbytes()
    assert est > 0 and actual > 0
    # metadata estimate within 2x of truth either way (admission quality)
    assert actual / 2 <= est <= actual * 2


# --------------------------------------------------------------------------
# spill to host (admission control)
# --------------------------------------------------------------------------

def test_per_segment_spill_matches_host_oracle(segs):
    dev = ServerQueryExecutor(hbm_budget_bytes=64)
    for sql in (GROUP_SQL, AGG_SQL):
        rt, stats = dev.execute(compile_query(sql), segs)
        assert rt.rows == _host_rows(segs, sql)
        assert stats.staging["spills"] == 1
        assert stats.staging["stagedBytes"] == 0
    assert dev.residency.spills == 2


def test_sharded_spill_matches_host_oracle(segs):
    dev = ShardedQueryExecutor(hbm_budget_bytes=64)
    rt, stats = dev.execute(compile_query(GROUP_SQL), segs)
    assert rt.rows == _host_rows(segs, GROUP_SQL)
    assert stats.staging["spills"] == 1
    assert stats.group_by_rung == "host"


def test_sharded_capped_budget_churns_but_stays_correct(segs):
    """Budget (bytes a device) fits ONE batch resident's share of the
    fullest device: alternating working sets (the full segment list vs a
    subset batch) evict each other — LRU churn — while every answer stays
    host-identical and nothing device-OOMs."""
    probe = ShardedQueryExecutor()
    ctx_all = compile_query(GROUP_SQL)
    probe.execute(ctx_all, segs)
    one_batch = _fullest_device(probe.residency)
    assert one_batch > 0

    dev = ShardedQueryExecutor(hbm_budget_bytes=int(one_batch * 1.5))
    ctx_sub = compile_query(AGG_SQL)
    want_all = _host_rows(segs, GROUP_SQL)
    want_sub = _host_rows(segs[:2], AGG_SQL)
    for _ in range(2):
        rt, stats = dev.execute(ctx_all, segs)
        assert rt.rows == want_all
        assert stats.staging["spills"] == 0
        rt, stats = dev.execute(ctx_sub, segs[:2])
        assert rt.rows == want_sub
    snap = dev.residency.stats_snapshot()
    assert snap["evictions"] >= 1, "capped budget never churned"
    assert _fullest_device(dev.residency) <= int(one_batch * 1.5)


def test_warm_hit_rate_is_total(segs):
    dev = ShardedQueryExecutor()
    ctx = compile_query(GROUP_SQL)
    dev.execute(ctx, segs)  # cold: miss + stage
    _, stats = dev.execute(ctx, segs)
    assert stats.staging["misses"] == 0
    assert stats.staging["hits"] >= 1
    assert stats.staging["spills"] == 0


# --------------------------------------------------------------------------
# sharded batch eviction (the _evict_batch satellite)
# --------------------------------------------------------------------------

def test_evict_segment_clears_every_containing_batch(segs):
    dev = ShardedQueryExecutor()
    ctx_all = compile_query(GROUP_SQL)
    ctx_sub = compile_query(AGG_SQL)
    want_all = _host_rows(segs, GROUP_SQL)
    dev.execute(ctx_all, segs)       # batch over all four segments
    dev.execute(ctx_sub, segs[:2])   # a second batch sharing segment 0
    assert len(dev._batches) == 2
    assert dev._device_cols and dev._param_cache and dev._launch_cache

    dev.evict_segment(segs[0].segment_name)
    assert not dev._batches, "a batch containing the segment survived"
    assert not dev._device_cols, "sharded device arrays leaked"
    assert not dev._launch_cache, \
        "compiled query closures (pinning old arrays) leaked"
    assert not dev._param_cache, "device param arrays leaked"
    assert not dev.residency.resident_names()

    # and the path rebuilds cleanly
    rt, _ = dev.execute(ctx_all, segs)
    assert rt.rows == want_all


def test_evict_batch_clears_query_cache_by_batch_name(segs):
    """Regression for the k[1]-vs-k[2] key bug: both cache tiers carry the
    batch name at slot [-2]; the old evictor compared the batch name
    against the FINGERPRINT slot and never evicted anything."""
    dev = ShardedQueryExecutor()
    dev.execute(compile_query(GROUP_SQL), segs)
    assert dev._param_cache and dev._launch_cache
    batch = dev.batch_for(segs)
    dev._evict_batch(batch)
    assert not dev._param_cache and not dev._launch_cache


# --------------------------------------------------------------------------
# stats plumbing: merge + wire
# --------------------------------------------------------------------------

def test_staging_stats_merge_counters_sum_bytes_max():
    a = QueryStats(staging={"hits": 1, "misses": 2, "spills": 0,
                            "stagedBytes": 100})
    b = QueryStats(staging={"hits": 3, "misses": 1, "spills": 1,
                            "stagedBytes": 40, "evictions": 2})
    a.merge(b)
    assert a.staging == {"hits": 4, "misses": 3, "spills": 1,
                         "stagedBytes": 100, "evictions": 2}


def test_staging_rides_the_datatable_wire():
    stats = QueryStats(num_docs_scanned=5,
                       staging={"hits": 2, "misses": 1, "evictions": 1,
                                "pinBlockedEvictions": 0, "spills": 0,
                                "stagedBytes": 4096})
    dt = DataTable.for_aggregation([7], stats)
    out = DataTable.from_bytes(dt.to_bytes())
    assert out.stats.staging == stats.staging
    # legacy JSON framing too (mixed-version interop)
    out2 = DataTable.from_bytes(dt.to_json_bytes())
    assert out2.stats.staging == stats.staging


# --------------------------------------------------------------------------
# prefetch + lifecycle hooks + debug snapshot
# --------------------------------------------------------------------------

def test_prefetch_stages_in_background(segs):
    rm = ResidencyManager(budget_bytes=0)
    try:
        rm.prefetch(segs[0])
        rm.drain_prefetch()
        assert segs[0].segment_name in rm.resident_names()
        assert rm.staged_bytes() > 0
        assert rm.stats_snapshot()["prefetched"] == 1
    finally:
        rm.close()


def test_prefetch_never_evicts_for_itself(segs):
    rm = ResidencyManager(budget_bytes=0)
    try:
        _stage_full(rm, segs[0])
        rm.set_budget_bytes(rm.staged_bytes())  # exactly full
        rm.stage(segs[0])  # pinless touch: seg 0 is MRU anyway
        rm.prefetch(segs[1])
        rm.drain_prefetch()
        assert segs[0].segment_name in rm.resident_names(), \
            "prefetch evicted a serving resident"
    finally:
        rm.close()


def test_prefetch_queued_before_remove_cannot_resurrect(segs):
    """The prefetch-vs-removeSegment race, made deterministic: a prefetch
    sits in the queue behind a stalled item while the segment is evicted.
    When the worker finally runs it, the retire-generation check must turn
    it into a no-op — staging anyway would resurrect a removed segment as
    an orphaned resident no removeSegment will ever clean up."""
    from types import SimpleNamespace

    release_worker = threading.Event()

    class _BlockingCols:
        def keys(self):
            release_worker.wait(10.0)
            return []

    blocker = SimpleNamespace(
        segment_name="__blocker__", is_mutable=False, num_docs=0,
        padded_capacity=0, metadata=SimpleNamespace(columns=_BlockingCols()))

    rm = ResidencyManager(budget_bytes=0)
    try:
        rm.prefetch(blocker)            # worker stalls inside this item
        rm.prefetch(segs[0])            # queued behind the stall
        rm.evict(segs[0].segment_name)  # removeSegment lands first
        release_worker.set()
        rm.drain_prefetch()
        assert segs[0].segment_name not in rm.resident_names(), \
            "queued prefetch resurrected a removed segment"
        # a re-add AFTER the remove is a fresh generation and must prefetch
        rm.prefetch(segs[0])
        rm.drain_prefetch()
        assert segs[0].segment_name in rm.resident_names()
    finally:
        release_worker.set()
        rm.close()


def test_prefetch_vs_remove_thread_hammer(segs):
    """Background lifecycle-listener staging racing removeSegment eviction:
    no exceptions, no orphaned resident after the final remove, and byte
    accounting stays exact (== sum of resident bytes, never negative)."""
    rm = ResidencyManager(budget_bytes=0)
    stop = threading.Event()
    errors = []

    def prefetcher(seg):
        while not stop.is_set():
            try:
                rm.prefetch(seg)
            except Exception as e:  # pragma: no cover - failure mode
                errors.append(e)
                return

    def remover():
        while not stop.is_set():
            for s in segs[:2]:
                try:
                    rm.evict(s.segment_name)
                except Exception as e:  # pragma: no cover - failure mode
                    errors.append(e)
                    return

    threads = [threading.Thread(target=prefetcher, args=(s,))
               for s in segs[:2] for _ in range(2)]
    threads += [threading.Thread(target=remover) for _ in range(2)]
    try:
        for t in threads:
            t.start()
        stop.wait(1.0)
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors
    rm.drain_prefetch()
    # the final word is remove: nothing may stay (or come back) resident
    for s in segs[:2]:
        rm.evict(s.segment_name)
    rm.drain_prefetch()
    for s in segs[:2]:
        assert s.segment_name not in rm.resident_names()
    snap = rm.snapshot()
    by_resident = sum(e["bytes"] for e in snap["stagedSegments"].values())
    assert snap["stagedBytes"] == by_resident >= 0
    rm.close()


def test_data_manager_lifecycle_hooks(segs, tmp_path):
    from pinot_tpu.server.data_manager import TableDataManager

    class Listener:
        def __init__(self):
            self.added, self.removed = [], []

        def segment_added(self, table, segment):
            self.added.append((table, segment.segment_name))

        def segment_removed(self, table, segment_name):
            self.removed.append((table, segment_name))

    lis = Listener()
    tdm = TableDataManager("sales_OFFLINE", listener=lis)
    tdm.add_segment(segs[0])
    assert lis.added == [("sales_OFFLINE", segs[0].segment_name)]
    tdm.remove_segment(segs[0].segment_name)
    assert lis.removed == [("sales_OFFLINE", segs[0].segment_name)]


def test_snapshot_is_bytes_accurate(segs):
    rm = ResidencyManager(budget_bytes=0)
    st = _stage_full(rm, segs[0])
    snap = rm.snapshot()
    ent = snap["stagedSegments"][segs[0].segment_name]
    assert ent["bytes"] == st.nbytes() > 0
    assert ent["columns"] == len(COLUMNS)
    assert snap["stagedBytes"] == ent["bytes"]
    assert snap["peakBytes"] >= snap["stagedBytes"]
    assert snap["budgetBytes"] is None


class _Batch:
    def nbytes(self):
        return 100

    def release(self):
        pass


def test_snapshot_gives_every_resident_its_touch_and_kind(segs):
    """``touch`` and ``kind`` on each resident, whatever its kind: two
    readings say which residents no query read in between (the
    benchmark's ``staged_unread_share``)."""
    rm = ResidencyManager(budget_bytes=0)
    rm.stage(segs[0])
    rm.stage(segs[1])
    rm.register("batch(x)", _Batch)
    first = rm.snapshot()["stagedSegments"]
    assert {n: e["kind"] for n, e in first.items()} == {
        segs[0].segment_name: "StagedSegment",
        segs[1].segment_name: "StagedSegment", "batch(x)": "_Batch"}
    assert len({e["touch"] for e in first.values()}) == 3

    rm.stage(segs[0])                   # a hit
    rm.register("batch(x)", _Batch)     # a hit on the batch
    second = rm.snapshot()["stagedSegments"]
    moved = {n for n in first if second[n]["touch"] != first[n]["touch"]}
    assert moved == {segs[0].segment_name, "batch(x)"}
    assert second[segs[0].segment_name]["touch"] \
        > first["batch(x)"]["touch"]


@pytest.mark.parametrize("how", ["stage", "register"])
def test_a_resident_staged_anew_takes_a_new_touch(segs, how):
    rm = ResidencyManager(budget_bytes=0)
    rm.stage(segs[2])
    if how == "stage":
        name = segs[3].segment_name
        rm.stage(segs[3])
        before = rm.snapshot()["stagedSegments"][name]["touch"]
        rm.evict(name)
        rm.stage(segs[3])
    else:
        name = "batch(y)"
        rm.register(name, _Batch)
        before = rm.snapshot()["stagedSegments"][name]["touch"]
        rm.register(name, _Batch, same=lambda r: False)    # stale: rebuilt
    after = rm.snapshot()["stagedSegments"]
    assert after[name]["touch"] > before
    assert after[segs[2].segment_name]["touch"] < before


# --------------------------------------------------------------------------
# budget resolution against the backend
# --------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform = platform
        self.device_kind = f"fake {platform}"
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,stats,want", [
    ("tpu", {"bytes_limit": 1000}, 750),
    ("cpu", None, None),                 # the CPU answer: uncapped
    ("cpu", {}, None),
])
def test_auto_budget_from_backend(monkeypatch, platform, stats, want):
    import jax

    from pinot_tpu.engine.residency import resolve_budget_bytes

    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice(platform, stats)])
    assert resolve_budget_bytes() == want


def test_auto_budget_raises_on_accelerator_without_bytes_limit(monkeypatch):
    """A TPU that reports no memory limit is an error, not "uncapped"."""
    import jax

    from pinot_tpu.engine.residency import resolve_budget_bytes

    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice("tpu", None)])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        resolve_budget_bytes()
    # an explicit budget never asks the backend
    assert resolve_budget_bytes(1234) == 1234


# --------------------------------------------------------------------------
# bytes are reckoned a device (HBM is a chip's, so is the budget)
# --------------------------------------------------------------------------

def _mesh_executor(n_devices: int, **kw) -> ShardedQueryExecutor:
    import jax

    from pinot_tpu.parallel import make_combine_mesh

    return ShardedQueryExecutor(
        mesh=make_combine_mesh(jax.devices()[:n_devices]), **kw)


def _table_bytes(segs, n_devices: int):
    """(all devices together, the fullest device) of the whole table
    resident behind GROUP_SQL and AGG_SQL on an ``n_devices`` mesh."""
    probe = _mesh_executor(n_devices)
    for sql in (GROUP_SQL, AGG_SQL):
        probe.execute(compile_query(sql), segs)
    return probe.residency.staged_bytes(), _fullest_device(probe.residency)


def test_table_over_one_devices_budget_is_resident_on_four(segs):
    """All bytes together exceed a device's budget, each device's share
    fits: the table sits resident on the four devices and is served
    sharded, nothing evicted, sliced or spilled, answers the host's."""
    total, fullest = _table_bytes(segs, 4)
    budget = int(fullest * 1.6)
    assert fullest < budget < total
    dev = _mesh_executor(4, hbm_budget_bytes=budget)
    for _ in range(2):
        for sql in (GROUP_SQL, AGG_SQL):
            rt, stats = dev.execute(compile_query(sql), segs)
            assert rt.rows == _host_rows(segs, sql)
            assert stats.staging["slices"] == 0
    snap = dev.residency.snapshot()
    counters = snap["counters"]
    assert (counters["evictions"], counters["slicedQueries"],
            counters["spills"]) == (0, 0, 0)
    assert snap["stagedBytes"] == total > snap["budgetBytes"]
    held = [d for d in snap["devices"] if d["stagedBytes"]]
    assert len(held) == 4
    assert all(d["stagedBytes"] <= d["budgetBytes"] == budget for d in held)


def test_table_over_every_devices_share_is_sliced_as_ever(segs):
    """The same table under a budget that a device's share of it does not
    fit streams through in slices, exact, never spilled."""
    _, fullest = _table_bytes(segs, 2)
    dev = _mesh_executor(2, hbm_budget_bytes=int(fullest * 0.6))
    rt, stats = dev.execute(compile_query(GROUP_SQL), segs)
    assert rt.rows == _host_rows(segs, GROUP_SQL)
    assert stats.staging["spills"] == 0
    assert stats.staging["slices"] >= 2
    assert dev.residency.stats_snapshot()["slicedQueries"] == 1


def test_one_device_counts_what_it_always_did(segs):
    """On one device every byte count is the sum of the arrays' ``nbytes``
    (the only reckoning there was): the sharded batch, a staged segment,
    the total, and the one entry of ``devices`` that holds anything."""
    from pinot_tpu.engine.staging import DEFAULT_DEVICE

    dev = _mesh_executor(1)
    dev.execute(compile_query(GROUP_SQL), segs)
    _stage_full(dev.residency, segs[0])

    def plain(obj) -> int:
        if isinstance(obj, dict):
            obj = list(obj.values())
        if isinstance(obj, (tuple, list)):
            return sum(plain(v) for v in obj)
        return int(getattr(obj, "nbytes", 0))

    with dev._device_cols_lock:
        batch_bytes = plain(list(dev._device_cols.values()))
    st = dev.residency.stage(segs[0])
    seg_bytes = sum(plain(c.tree()) for c in st._columns.values())
    snap = dev.residency.snapshot()
    assert st.nbytes() == seg_bytes > 0
    assert snap["stagedBytes"] == batch_bytes + seg_bytes
    assert [d["stagedBytes"] for d in snap["devices"] if d["stagedBytes"]] \
        == [snap["stagedBytes"]]
    assert snap["devices"][0]["id"] == DEFAULT_DEVICE


def test_debug_memory_devices_add_up_with_replicas_on_each(segs):
    """``devices[].stagedBytes`` sum to ``stagedBytes``; a replicated
    array (the unified dictionary values) is counted on every device that
    holds a copy, a sharded one shard by shard."""
    dev = _mesh_executor(4)
    dev.execute(compile_query(GROUP_SQL), segs)
    snap = dev.residency.snapshot()
    assert len(snap["devices"]) == 8   # every device of the process
    assert {"id", "budgetBytes", "stagedBytes", "bytesInUse",
            "peakBytes"} == set(snap["devices"][0])
    assert sum(d["stagedBytes"] for d in snap["devices"]) \
        == snap["stagedBytes"]
    with dev._device_cols_lock:
        trees = [v for v in dev._device_cols.values()
                 if isinstance(v, dict)]
    sharded = sum(int(a.nbytes) for t in trees for k, a in t.items()
                  if k != "dictvals")
    replicated = sum(int(a.nbytes) for t in trees for k, a in t.items()
                     if k == "dictvals")
    assert replicated > 0
    with dev._device_cols_lock:
        rest = sum(int(v.nbytes) for v in dev._device_cols.values()
                   if not isinstance(v, dict))     # the doc counts
    by_device = sorted(d["stagedBytes"] for d in snap["devices"])
    assert by_device[:4] == [0] * 4
    assert by_device[4:] == [(sharded + rest) // 4 + replicated] * 4
