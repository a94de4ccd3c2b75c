"""Sharded combine on a REAL >1-device mesh (``pytest -m cluster_routing``,
part of tier-1).

ISSUE 12's device half: ``make_combine_mesh`` builds from ALL local devices
(the conftest forces 8 virtual CPU devices through
``--xla_force_host_platform_device_count``, subprocess-safe via XLA_FLAGS),
every psum/pmin/pmax in the combine actually crosses device boundaries, and
the results are BIT-identical to the 1-device mesh for all 13 SSB flights.
The PR-8 slice planner pads to the segment axis of the actual mesh, and
launch/coalescing stats stay correct across mesh shapes.

Bit parity is exact (==, not approx): the SSB aggregates are integer-valued
sums accumulated in f64 far below 2^53, so the cross-device reduction order
cannot change a single bit.
"""

import numpy as np
import pytest

from pinot_tpu.parallel import ShardedQueryExecutor, make_combine_mesh
from pinot_tpu.parallel.combine import DOC_AXIS, SEG_AXIS
from pinot_tpu.query import compile_query
from pinot_tpu.tools import ssb

pytestmark = pytest.mark.cluster_routing

NUM_SEGMENTS = 4
ROWS = 10_000  # per-segment capacity pads to 4096 (remainder-tile shape)

QIDS = sorted(ssb.QUERIES)


@pytest.fixture(scope="module")
def ssb_segs(tmp_path_factory):
    # star_tree=False: every flight must ride the sharded combine (a tree
    # would reroute Q2.x onto the per-segment star-tree rung)
    out = tmp_path_factory.mktemp("mesh_ssb")
    return ssb.build_segments(0, str(out), num_segments=NUM_SEGMENTS,
                              rows=ROWS, star_tree=False, workers=1)


@pytest.fixture(scope="module")
def exec_1dev(forced_mesh_devices):
    mesh = make_combine_mesh(devices=forced_mesh_devices[:1])
    return ShardedQueryExecutor(mesh=mesh)


@pytest.fixture(scope="module")
def exec_8dev(forced_mesh_devices):
    mesh = make_combine_mesh(devices=forced_mesh_devices)
    assert mesh.shape[SEG_AXIS] == 8 and mesh.shape[DOC_AXIS] == 1
    return ShardedQueryExecutor(mesh=mesh)


def test_default_mesh_spans_all_local_devices(forced_mesh_devices):
    """make_combine_mesh() with no argument must take EVERY local device —
    the 1-device mesh every pre-ISSUE-12 measurement ran on is now only
    reachable by explicit request."""
    mesh = make_combine_mesh()
    assert mesh.devices.size == len(forced_mesh_devices) == 8


@pytest.mark.parametrize("qid", QIDS)
def test_ssb_bit_parity_8dev_vs_1dev(ssb_segs, exec_1dev, exec_8dev, qid):
    sql = ssb.QUERIES[qid] + " LIMIT 100000"
    rt1, st1 = exec_1dev.execute(compile_query(sql), ssb_segs)
    rt8, st8 = exec_8dev.execute(compile_query(sql), ssb_segs)
    assert len(rt8.rows) == len(rt1.rows)
    for r8, r1 in zip(rt8.rows, rt1.rows):
        assert r8 == r1  # BIT parity, incl. the float aggregate cells
    # stats parity across mesh shapes: same docs matched, same server-side
    # min/max pruning, same rung story (prune + process covers the table)
    assert st8.num_docs_scanned == st1.num_docs_scanned
    assert st8.num_segments_processed == st1.num_segments_processed
    assert st8.num_segments_pruned == st1.num_segments_pruned
    assert st8.num_segments_processed + st8.num_segments_pruned \
        == NUM_SEGMENTS
    assert st8.group_by_rung == st1.group_by_rung


def test_doc_axis_sharding_bit_parity(ssb_segs, exec_1dev,
                                      forced_mesh_devices):
    """4x2 mesh: the doc dimension ALSO crosses devices (context
    parallelism) — same bits out."""
    ex = ShardedQueryExecutor(
        mesh=make_combine_mesh(devices=forced_mesh_devices, doc_shards=2))
    for qid in ("Q1.1", "Q3.2", "Q4.3"):
        sql = ssb.QUERIES[qid] + " LIMIT 100000"
        rt, _ = ex.execute(compile_query(sql), ssb_segs)
        want, _ = exec_1dev.execute(compile_query(sql), ssb_segs)
        assert rt.rows == want.rows


def test_launch_stats_correct_across_mesh_shapes(ssb_segs, exec_1dev,
                                                 exec_8dev):
    """The coalescing counters describe LAUNCHES, not devices: one query =
    one launch on any mesh shape, and repeats stay launch-cache hits."""
    sql = ssb.QUERIES["Q1.1"] + " LIMIT 100000"
    for ex in (exec_1dev, exec_8dev):
        _, stats = ex.execute(compile_query(sql), ssb_segs)
        assert stats.launch["launches"] == 1
        assert stats.launch["batchSize"] >= 1
        assert stats.launch["queueWaitMs"] >= 0


def test_slice_planner_pads_to_actual_mesh(ssb_segs):
    """plan_slices costs each slice at ceil(k / seg_axis) * seg_axis
    segments: a budget that fits a couple of raw segments fits NO 8-padded
    slice (-> None, per-segment fallback), while the 1-wide mesh slices
    happily — the PR-8 planner keyed on the REAL mesh shape, not a
    hardcoded 1."""
    from pinot_tpu.engine.residency import (
        ResidencyManager,
        estimate_segment_bytes,
    )

    cols = ["lo_extendedprice", "lo_discount", "d_year", "lo_quantity"]
    est = estimate_segment_bytes(ssb_segs[0], cols)
    rm = ResidencyManager(budget_bytes=int(3 * est))
    assert rm.plan_slices(ssb_segs, cols, pad_to=8) is None
    slices = rm.plan_slices(ssb_segs, cols, pad_to=1)
    assert slices is not None and len(slices) >= 2
    assert sorted(s.segment_name for sl in slices for s in sl) == \
        sorted(s.segment_name for s in ssb_segs)
    # a budget that fits the 8-pad slices on the 8-wide mesh too
    rm_big = ResidencyManager(budget_bytes=int(20 * est))
    slices8 = rm_big.plan_slices(ssb_segs, cols, pad_to=8)
    assert slices8 is not None


def test_sliced_combine_on_8dev_mesh_matches_uncapped(ssb_segs,
                                                      forced_mesh_devices):
    """Budget-sliced execution over the 8-device mesh stays bit-identical
    to the uncapped oracle (PR-8's guarantee, now on a real mesh)."""
    sql = ssb.QUERIES["Q4.1"] + " LIMIT 100000"
    oracle = ShardedQueryExecutor(
        mesh=make_combine_mesh(devices=forced_mesh_devices))
    want, _ = oracle.execute(compile_query(sql), ssb_segs)
    from pinot_tpu.engine.residency import estimate_segment_bytes

    cols = compile_query(sql).referenced_columns()
    ws = sum(estimate_segment_bytes(s, cols) for s in ssb_segs)
    capped = ShardedQueryExecutor(
        mesh=make_combine_mesh(devices=forced_mesh_devices),
        hbm_budget_bytes=max(int(ws * 0.6), 1))
    got, stats = capped.execute(compile_query(sql), ssb_segs)
    assert got.rows == want.rows
    assert stats.staging.get("spills", 0) == 0, \
        "capped run spilled to host instead of slicing on the mesh"


def test_four_device_mesh_holds_a_table_one_device_could_not(
        ssb_segs, exec_1dev, forced_mesh_devices):
    """The deployment of ``ssb_scan_x4`` at toy size: mesh 4x1, a budget
    (bytes a device) under the table's bytes and over a device's share.
    Every flight is served sharded and exact, nothing is evicted, sliced
    or spilled, and the spans say where the bytes lie."""
    from pinot_tpu.common.tracing import flatten_spans

    def executor(**kw):
        return ShardedQueryExecutor(
            mesh=make_combine_mesh(devices=forced_mesh_devices[:4]), **kw)

    sqls = [ssb.QUERIES[q] + " LIMIT 100000" for q in QIDS]
    probe = executor()
    for sql in sqls:
        probe.execute(compile_query(sql), ssb_segs)
    snap = probe.residency.snapshot()
    total = snap["stagedBytes"]
    fullest = max(d["stagedBytes"] for d in snap["devices"])
    budget = int(fullest * 1.5)
    assert fullest < budget < total

    ex = executor(hbm_budget_bytes=budget)
    for sql in sqls:    # stage every column (the first bind of each does)
        ex.execute(compile_query(sql), ssb_segs)
    paths = []
    for sql in sqls:
        ctx = compile_query(sql + " OPTION(trace=true)")
        got, stats = ex.execute(ctx, ssb_segs)
        want, _ = exec_1dev.execute(compile_query(sql), ssb_segs)
        assert got.rows == want.rows
        spans = {e["operator"]: e for e in flatten_spans(stats.spans)}
        paths.append(spans["Route"]["path"])
        if paths[-1] == "per_segment":   # pruned to one segment: device 0
            continue
        assert spans["ShardedCombine"]["mesh"] == "4x1"
        assert spans["Stage"]["devices"] == 4
        assert 0 < spans["Stage"]["fullestDeviceBytes"] <= budget
    assert paths.count("sharded") >= 10 and set(paths) <= {"sharded",
                                                           "per_segment"}
    counters = ex.residency.snapshot()["counters"]
    assert (counters["evictions"], counters["slicedQueries"],
            counters["spills"]) == (0, 0, 0)
    assert ex.residency.staged_bytes() == total
