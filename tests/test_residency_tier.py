"""Tiered residency: host-RAM spill tier + restage-cost-aware eviction +
budget-sliced sharded combine (engine/residency.py, engine/staging.py,
parallel/executor.py).

The invariants the tier guarantees:

- eviction DEMOTES to host numpy copies instead of dropping; a re-stage
  promotes with a plain H2D (no decode/dictionary/pack) and the restored
  arrays are bit-identical to a cold rebuild;
- a working set over the HBM budget is served ON THE DEVICE PATH in
  budget-sized slices (sharded combine slices + the per-segment serial
  fallback), bit-identical to the uncapped oracle — host-engine spill only
  when a single segment alone cannot fit;
- host-tier entries are themselves LRU-dropped under their own budget;
- lease pins survive demotion pressure (a pinned resident never demotes
  mid-query);
- the eviction ranking prefers evicting cheap-to-restage residents
  (host-tier-backed) over expensive ones (star-tree-bearing) at equal
  bytes/recency;
- admission estimates are validated against measured bytes and the
  correction factor feeds back into admission + slice sizing;
- the new ``QueryStats.staging`` keys (promotions/demotions/hostBytes/
  slices) merge and ride the DataTable wire incl. legacy JSON.
"""

import threading

import numpy as np
import pytest

from pinot_tpu.common.datatable import DataTable
from pinot_tpu.engine import QueryStats, ServerQueryExecutor
from pinot_tpu.engine import residency as residency_mod
from pinot_tpu.engine.residency import (
    COST_HOST_RESTAGE,
    COST_STARTREE_BUILD,
    QueryLease,
    ResidencyManager,
)
from pinot_tpu.parallel import ShardedQueryExecutor
from pinot_tpu.parallel.combine import make_combine_mesh
from pinot_tpu.query import compile_query
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema

pytestmark = pytest.mark.residency_tier

RNG = np.random.default_rng(11)
N = 512
NUM_SEGMENTS = 16
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
    out = tmp_path_factory.mktemp("tier_segs")
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


def _one_device_mesh():
    """Single-device mesh: batch stacking pads S to the seg-axis width, so
    slice bytes only scale with k on a width-1 mesh — the shape the
    sliced-combine math is exercised on (the 8-virtual-device default mesh
    pads every slice to 8 segments)."""
    import jax

    return make_combine_mesh(jax.devices()[:1])


def _stage_full(rm: ResidencyManager, seg, lease=None):
    st = rm.stage(seg, lease=lease)
    for c in COLUMNS:
        st.column(c)
    return st


@pytest.fixture(scope="module")
def oracle(segs):
    """Uncapped sharded executor: the bit-identical reference for every
    capped/sliced configuration, plus the measured working set."""
    dev = ShardedQueryExecutor(mesh=_one_device_mesh())
    rows = {}
    for name, sql in (("group", GROUP_SQL), ("agg", AGG_SQL)):
        rt, _ = dev.execute(compile_query(sql), segs)
        rows[name] = rt.rows
    ws = dev.residency.staged_bytes()
    assert ws > 0
    return {"rows": rows, "ws": ws}


# --------------------------------------------------------------------------
# demote/promote parity
# --------------------------------------------------------------------------

def test_demote_then_promote_restores_identical_arrays(segs):
    rm = ResidencyManager(budget_bytes=0)
    st = _stage_full(rm, segs[0])
    cold = {c: np.asarray(st.column(c).fwd) for c in COLUMNS}
    cold_vals = np.asarray(st.value_column("qty"))
    assert rm.demote(segs[0].segment_name)
    assert segs[0].segment_name not in rm.resident_names()
    assert segs[0].segment_name in rm.host_entry_names()
    assert rm.host_bytes() > 0

    st2 = _stage_full(rm, segs[0])
    assert st2 is not st
    snap = rm.stats_snapshot()
    assert snap["demotions"] == 1
    assert snap["promotions"] == 1
    # promotion consumed the host entry; bytes moved back to the device
    assert segs[0].segment_name not in rm.host_entry_names()
    assert rm.host_bytes() == 0
    for c in COLUMNS:
        assert np.array_equal(np.asarray(st2.column(c).fwd), cold[c])
    assert np.array_equal(np.asarray(st2.value_column("qty")), cold_vals)


def test_promote_validates_segment_identity(segs):
    """A reloaded segment (same name, new object) must never promote from
    a stale host image — the image is dropped and a cold build serves."""
    rm = ResidencyManager(budget_bytes=0)
    _stage_full(rm, segs[0])
    assert rm.demote(segs[0].segment_name)
    reloaded = load_segment(segs[0].segment_dir)
    st = _stage_full(rm, reloaded)
    assert st.segment is reloaded
    snap = rm.stats_snapshot()
    assert snap["promotions"] == 0
    assert snap["hostDrops"] == 1
    assert rm.host_bytes() == 0


def test_eviction_demotes_instead_of_dropping(segs):
    """The budget evictor's doomed residents land in the host tier (the
    old behavior dropped their bytes outright)."""
    rm = ResidencyManager(budget_bytes=0)
    for s in segs[:3]:
        _stage_full(rm, s)
    per_seg = rm.staged_bytes() // 3
    rm.set_budget_bytes(int(per_seg * 1.5))
    assert rm.stats_snapshot()["demotions"] == 2
    assert rm.host_entry_count() == 2
    assert rm.host_bytes() > 0
    # the demoted residents promote back when budget allows again
    rm.set_budget_bytes(0)
    for s in segs[:3]:
        _stage_full(rm, s)
    assert rm.stats_snapshot()["promotions"] == 2


def test_query_parity_under_demote_promote_churn_vs_uncapped(segs, oracle):
    """Per-segment executor with a budget of ~2 segments: repeated queries
    churn every segment through demote -> promote cycles and every answer
    stays bit-identical to the uncapped oracle."""
    est = residency_mod.estimate_segment_bytes(segs[0], COLUMNS)
    dev = ServerQueryExecutor(hbm_budget_bytes=int(est * 2.5))
    for _ in range(2):
        for name, sql in (("group", GROUP_SQL), ("agg", AGG_SQL)):
            rt, stats = dev.execute(compile_query(sql), segs)
            assert rt.rows == oracle["rows"][name]
            assert stats.staging["spills"] == 0
    snap = dev.residency.stats_snapshot()
    assert snap["demotions"] > 0
    assert snap["promotions"] > 0, \
        "repeat passes must promote from the host tier, not rebuild"
    assert snap["spills"] == 0


# --------------------------------------------------------------------------
# budget-sliced sharded combine
# --------------------------------------------------------------------------

@pytest.mark.parametrize("frac", [4, 10])
def test_sliced_combine_parity_at_fraction_of_working_set(segs, oracle,
                                                          frac):
    ws = oracle["ws"]
    dev = ShardedQueryExecutor(mesh=_one_device_mesh(),
                               hbm_budget_bytes=ws // frac)
    for name, sql in (("group", GROUP_SQL), ("agg", AGG_SQL)):
        rt, stats = dev.execute(compile_query(sql), segs)
        assert rt.rows == oracle["rows"][name], \
            f"sliced combine at ws/{frac} diverged from the oracle"
        assert stats.staging["spills"] == 0, \
            "over-budget query fell to the host engine instead of slicing"
        assert stats.staging["slices"] >= 2
        assert stats.staging["demotions"] >= 1
    # repeat pass: slices promote from the host tier instead of rebuilding
    rt, stats = dev.execute(compile_query(GROUP_SQL), segs)
    assert rt.rows == oracle["rows"]["group"]
    assert stats.staging["promotions"] >= 1
    snap = dev.residency.stats_snapshot()
    assert snap["slicedQueries"] >= 3
    assert snap["spills"] == 0
    assert snap["stagedBytes"] <= ws // frac


def test_sliced_combine_on_padded_mesh_degrades_to_per_segment(segs,
                                                               oracle):
    """On a 2-wide mesh every batch pads to 2 segments, one a device. A
    budget (bytes a device) that holds one segment and not the two a
    device's share of the four comes to fits no padded slice under the
    planner's fill — plan_slices returns None and the per-segment sliced
    path serves, still on device, still exact."""
    import jax

    est = residency_mod.estimate_segment_bytes(segs[0], COLUMNS)
    dev = ShardedQueryExecutor(mesh=make_combine_mesh(jax.devices()[:2]),
                               hbm_budget_bytes=int(est * 1.1))
    rt, stats = dev.execute(compile_query(GROUP_SQL), segs)
    assert rt.rows == oracle["rows"]["group"]
    assert stats.staging["spills"] == 0
    assert stats.staging["slices"] >= 2


def test_single_segment_over_budget_still_spills(segs):
    """Slicing has a floor: when one segment alone exceeds the budget the
    host engine still serves (host-identical, no device OOM) — the old
    admission contract."""
    host = ServerQueryExecutor(use_device=False)
    want, _ = host.execute(compile_query(GROUP_SQL), segs)
    dev = ShardedQueryExecutor(mesh=_one_device_mesh(), hbm_budget_bytes=64)
    rt, stats = dev.execute(compile_query(GROUP_SQL), segs)
    assert rt.rows == want.rows
    assert stats.staging["spills"] == 1
    assert stats.staging["slices"] == 0


def test_selection_is_not_sliceable(segs):
    """Selection/distinct shapes keep fit-or-spill admission (their
    execution cannot release pins mid-query)."""
    sql = "SELECT region, qty FROM sales ORDER BY qty LIMIT 5"
    host = ServerQueryExecutor(use_device=False)
    want, _ = host.execute(compile_query(sql), segs)
    est = residency_mod.estimate_segment_bytes(segs[0],
                                               ("region", "qty"))
    dev = ShardedQueryExecutor(mesh=_one_device_mesh(),
                               hbm_budget_bytes=int(est * 2.5))
    rt, stats = dev.execute(compile_query(sql), segs)
    assert rt.rows == want.rows
    assert stats.staging["spills"] == 1
    assert stats.staging["slices"] == 0


def test_slicing_disabled_by_config_restores_spill(segs):
    from pinot_tpu.spi.config import CommonConstants, PinotConfiguration

    cfg = PinotConfiguration(
        {CommonConstants.HBM_SLICING_ENABLED_KEY: "false"}, use_env=False)
    host = ServerQueryExecutor(use_device=False)
    want, _ = host.execute(compile_query(GROUP_SQL), segs)
    est = residency_mod.estimate_segment_bytes(segs[0], COLUMNS)
    dev = ShardedQueryExecutor(mesh=_one_device_mesh(),
                               hbm_budget_bytes=int(est * 3), config=cfg)
    rt, stats = dev.execute(compile_query(GROUP_SQL), segs)
    assert rt.rows == want.rows
    assert stats.staging["spills"] == 1


# --------------------------------------------------------------------------
# host-tier budget / LRU
# --------------------------------------------------------------------------

def test_host_tier_lru_drop_under_its_own_budget(segs):
    rm = ResidencyManager(budget_bytes=0)
    for s in segs[:3]:
        _stage_full(rm, s)
    per_seg = rm.staged_bytes() // 3
    # host tier fits roughly one segment image
    rm.set_host_budget_bytes(int(per_seg * 1.5))
    rm.set_budget_bytes(1)  # demote everything
    snap = rm.stats_snapshot()
    assert snap["demotions"] == 3
    assert snap["hostDrops"] >= 2, "host tier never LRU-dropped"
    assert rm.host_bytes() <= int(per_seg * 1.5)
    assert rm.host_entry_count() <= 1
    # the survivor is the most recently demoted (LRU order)
    assert rm.host_entry_names() == [segs[2].segment_name]


def test_host_tier_disabled_drops_on_eviction(segs):
    rm = ResidencyManager(budget_bytes=0)
    rm.set_host_tier_enabled(False)
    _stage_full(rm, segs[0])
    rm.set_budget_bytes(1)
    snap = rm.stats_snapshot()
    assert snap["evictions"] == 1
    assert snap["demotions"] == 0
    assert rm.host_entry_count() == 0


def test_evict_drops_both_tiers(segs):
    rm = ResidencyManager(budget_bytes=0)
    _stage_full(rm, segs[0])
    assert rm.demote(segs[0].segment_name)
    assert rm.host_entry_count() == 1
    rm.evict(segs[0].segment_name)
    assert rm.host_entry_count() == 0
    assert rm.host_bytes() == 0
    assert rm.stats_snapshot()["hostDrops"] == 1


def test_snapshot_reports_both_tiers(segs):
    rm = ResidencyManager(budget_bytes=0)
    _stage_full(rm, segs[0])
    _stage_full(rm, segs[1])
    assert rm.demote(segs[0].segment_name)
    snap = rm.snapshot()
    assert segs[1].segment_name in snap["stagedSegments"]
    tier = snap["hostTier"]
    assert tier["enabled"] is True
    assert segs[0].segment_name in tier["entries"]
    assert tier["entries"][segs[0].segment_name]["bytes"] > 0
    assert tier["hostBytes"] == sum(e["bytes"]
                                    for e in tier["entries"].values())
    assert tier["peakBytes"] >= tier["hostBytes"]


# --------------------------------------------------------------------------
# pins + eviction ranking
# --------------------------------------------------------------------------

def test_lease_pins_survive_demotion_pressure(segs):
    """A pinned resident is never demoted mid-query; once the lease
    closes it demotes normally and the next stage promotes it."""
    rm = ResidencyManager(budget_bytes=0)
    lease = QueryLease()
    st = _stage_full(rm, segs[0], lease=lease)
    rm.set_budget_bytes(1)
    assert segs[0].segment_name in rm.resident_names(), \
        "pinned resident was demoted/evicted under pressure"
    assert rm.host_entry_count() == 0
    # the pinned resident's arrays stayed live on device
    assert st.column("region").fwd is not None
    stats = QueryStats()
    rm.end_query(lease, stats)
    assert segs[0].segment_name not in rm.resident_names()
    assert segs[0].segment_name in rm.host_entry_names()
    assert stats.staging["demotions"] == 1
    assert stats.staging["hostBytes"] > 0
    # promotion after the lease closed
    st2 = _stage_full(rm, segs[0])
    assert rm.stats_snapshot()["promotions"] == 1
    assert st2.segment is segs[0]


def test_eviction_prefers_cheap_to_restage_over_pure_lru(segs):
    """Restage-cost ranking (bytes * staleness / rebuild_cost): at equal
    bytes, a host-tier-backed resident (cost 1) evicts BEFORE an older
    cold resident (cost 4) — pure LRU would pick the older one."""
    rm = ResidencyManager(budget_bytes=0)
    _stage_full(rm, segs[0])  # cold build, OLDER
    _stage_full(rm, segs[1])  # newer, about to gain host backing
    from pinot_tpu.engine.staging import SegmentHostImage

    with rm._lock:
        # white-box: a host image for seg1, as a prior demotion leaves it
        rm._host_entries[segs[1].segment_name] = residency_mod._Entry(
            SegmentHostImage(segs[1]))
        c0 = rm._rebuild_cost_locked(segs[0].segment_name,
                                     rm._entries[segs[0].segment_name])
        c1 = rm._rebuild_cost_locked(segs[1].segment_name,
                                     rm._entries[segs[1].segment_name])
    assert c0 == residency_mod.COST_COLUMN_BUILD
    assert c1 == COST_HOST_RESTAGE
    per = rm.staged_bytes() // 2
    rm.set_budget_bytes(int(per * 1.5))
    names = rm.resident_names()
    assert segs[0].segment_name in names, \
        "cost-aware ranking should keep the expensive-to-rebuild resident"
    assert segs[1].segment_name not in names, \
        "the host-backed (cheap-restage) resident must evict first"


def test_startree_residents_rank_expensive(segs):
    """Star-tree-bearing residents carry the highest rebuild cost — the
    budget preferentially keeps node arrays (tree walk + H2D to rebuild)
    over plain column sets."""
    import jax.numpy as jnp

    from pinot_tpu.engine.staging import StagedSegment

    rm = ResidencyManager(budget_bytes=0)
    st = StagedSegment(segs[0])
    st._startree[0] = {"stdim:a": jnp.zeros(4, dtype=jnp.int32)}
    e = residency_mod._Entry(st)
    with rm._lock:
        assert rm._rebuild_cost_locked("x", e) == COST_STARTREE_BUILD


# --------------------------------------------------------------------------
# admission-estimate drift
# --------------------------------------------------------------------------

def test_estimate_drift_correction_feeds_admission(segs, monkeypatch):
    """A deliberately 4x-under-estimating metadata path: after one staged
    query the EWMA correction rises toward measured/estimated, and the
    corrected estimates change the admission outcome for the same
    budget."""
    real = residency_mod.estimate_segment_bytes
    monkeypatch.setattr(residency_mod, "estimate_segment_bytes",
                        lambda s, c: max(1, real(s, c) // 4))
    rm = ResidencyManager(budget_bytes=0)
    est = residency_mod.estimate_segment_bytes(segs[0], COLUMNS)
    # budget fits the raw (4x-under) 2-segment estimate comfortably, but
    # NOT the corrected one (8x est); one corrected segment (4x) does fit
    rm.set_budget_bytes(int(est * 5))
    lease = rm.begin_query(segs[:2], COLUMNS, sliceable=True)
    assert lease.device_allowed and not lease.sliced, \
        "raw mis-estimate should admit un-sliced"
    for s in segs[:2]:
        _stage_full(rm, s, lease=lease)
    rm.end_query(lease, QueryStats())
    assert rm.est_observations >= 2
    assert rm.estimate_scale() > 1.3, \
        f"EWMA barely moved: {rm.estimate_scale()}"
    # same budget, same query: corrected estimates now exceed it -> the
    # admission outcome flips to sliced
    for _ in range(8):  # converge the EWMA
        rm.observe_estimate(est, est * 4)
    lease2 = rm.begin_query(segs[:2], COLUMNS, sliceable=True)
    assert lease2.sliced, "corrected estimates did not reach admission"
    # and slice sizing shrinks: k segments per slice from real bytes
    chunks = rm.plan_slices(segs[:4], COLUMNS, lease2)
    assert chunks is not None
    assert max(len(c) for c in chunks) <= 2


def test_observe_estimate_clamps():
    rm = ResidencyManager(budget_bytes=0)
    for _ in range(100):
        rm.observe_estimate(1, 1000)  # 1000x drift
    assert rm.estimate_scale() <= 4.0
    for _ in range(100):
        rm.observe_estimate(1000, 1)
    assert rm.estimate_scale() >= 0.25


# --------------------------------------------------------------------------
# wire + merge
# --------------------------------------------------------------------------

def test_tier_stats_merge_counters_sum_bytes_max():
    a = QueryStats(staging={"promotions": 1, "demotions": 2, "slices": 3,
                            "hostBytes": 100, "stagedBytes": 10})
    b = QueryStats(staging={"promotions": 2, "demotions": 1, "slices": 1,
                            "hostBytes": 40, "stagedBytes": 20})
    a.merge(b)
    assert a.staging == {"promotions": 3, "demotions": 3, "slices": 4,
                         "hostBytes": 100, "stagedBytes": 20}


def test_tier_stats_ride_the_datatable_wire():
    stats = QueryStats(num_docs_scanned=5,
                       staging={"hits": 2, "misses": 1, "evictions": 1,
                                "pinBlockedEvictions": 0, "spills": 0,
                                "promotions": 3, "demotions": 2,
                                "slices": 4, "stagedBytes": 4096,
                                "hostBytes": 8192})
    dt = DataTable.for_aggregation([7], stats)
    out = DataTable.from_bytes(dt.to_bytes())
    assert out.stats.staging == stats.staging
    out2 = DataTable.from_bytes(dt.to_json_bytes())
    assert out2.stats.staging == stats.staging


# --------------------------------------------------------------------------
# churn-while-querying hammer
# --------------------------------------------------------------------------

def test_churn_while_querying_hammer(segs, oracle):
    """Multi-thread: capped sliced executors answering queries while a
    churner forces demotions/evictions — no exceptions, every result
    bit-identical to the uncapped oracle, byte accounting consistent."""
    ws = oracle["ws"]
    dev = ShardedQueryExecutor(mesh=_one_device_mesh(),
                               hbm_budget_bytes=ws // 4)
    ctxs = {"group": compile_query(GROUP_SQL),
            "agg": compile_query(AGG_SQL)}
    stop = threading.Event()
    errors = []

    def querier(name):
        while not stop.is_set():
            try:
                rt, _ = dev.execute(ctxs[name], segs)
                if rt.rows != oracle["rows"][name]:
                    errors.append(AssertionError(
                        f"{name}: parity lost under churn"))
                    return
            except Exception as e:  # pragma: no cover - failure mode
                errors.append(e)
                return

    def churner():
        while not stop.is_set():
            for s in segs[::3]:
                try:
                    dev.residency.demote(s.segment_name)
                except Exception as e:  # pragma: no cover - failure mode
                    errors.append(e)
                    return

    threads = [threading.Thread(target=querier, args=(n,))
               for n in ("group", "agg") for _ in range(2)]
    threads.append(threading.Thread(target=churner))
    for t in threads:
        t.start()
    stop.wait(2.0)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors[:3]
    # accounting stayed exact across the churn
    snap = dev.residency.snapshot()
    by_resident = sum(e["bytes"] for e in snap["stagedSegments"].values())
    assert snap["stagedBytes"] == by_resident >= 0
    tier = snap["hostTier"]
    assert tier["hostBytes"] == sum(e["bytes"]
                                    for e in tier["entries"].values()) >= 0
