"""SSB suite parity at test scale (benchmarks/run.py measures on the chip).

Ref: contrib/pinot-druid-benchmark (the reference's macro benchmark
harness); pandas is the oracle here, mirroring the reference's H2-parity
strategy (SURVEY.md §4.3).
"""

import numpy as np
import pandas as pd
import pytest

from pinot_tpu.engine import ServerQueryExecutor
from pinot_tpu.parallel import ShardedQueryExecutor
from pinot_tpu.query import compile_query
from pinot_tpu.tools import ssb

ROWS = 120_000


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    out = tmp_path_factory.mktemp("ssb_segs")
    segs = ssb.build_segments(0, str(out), num_segments=4, rows=ROWS)
    cols = ssb.generate_table(4, ROWS)
    return cols, segs


@pytest.fixture(scope="module")
def dev_exec():
    return ShardedQueryExecutor()


@pytest.fixture(scope="module")
def host_exec():
    return ServerQueryExecutor(use_device=False)


@pytest.mark.parametrize("qid", ["Q1.1", "Q1.2", "Q1.3"])
def test_q1_vs_pandas_oracle(setup, dev_exec, qid):
    cols, segs = setup
    rt, _ = dev_exec.execute(compile_query(ssb.QUERIES[qid]), segs)
    exp = ssb.pandas_answer(cols, qid)
    assert rt.rows[0][0] == pytest.approx(exp, rel=1e-4)


def _assert_rows_match(qid, got, want):
    assert len(got.rows) == len(want.rows), qid
    for gr, wr in zip(got.rows, want.rows):
        for g, w in zip(gr, wr):
            if isinstance(w, float):
                assert g == pytest.approx(w, rel=1e-4), (qid, gr, wr)
            else:
                assert g == w, (qid, gr, wr)


@pytest.mark.parametrize("qid", sorted(ssb.QUERIES))
def test_device_matches_host(setup, dev_exec, host_exec, qid):
    cols, segs = setup
    ctx = compile_query(ssb.QUERIES[qid])
    got, _ = dev_exec.execute(ctx, segs)
    want, _ = host_exec.execute(ctx, segs)
    _assert_rows_match(qid, got, want)


def test_capped_hbm_budget_matches_host(setup, host_exec):
    """The residency acceptance bar: with the HBM budget capped below the
    SSB working set, every flight still returns host-engine-identical
    results — wide queries spill to host, narrow ones churn the LRU — and
    nothing device-OOMs. Under the DEFAULT (uncapped) budget the suite
    must not spill at all and must serve warm queries 100% from cache."""
    cols, segs = setup
    probe = ShardedQueryExecutor()
    probe.execute(compile_query(ssb.QUERIES["Q1.1"]), segs)
    one_flight = probe.residency.staged_bytes()
    assert one_flight > 0

    capped = ShardedQueryExecutor(hbm_budget_bytes=int(one_flight * 1.5))
    for qid in sorted(ssb.QUERIES):
        ctx = compile_query(ssb.QUERIES[qid])
        got, stats = capped.execute(ctx, segs)
        want, _ = host_exec.execute(ctx, segs)
        _assert_rows_match(qid, got, want)
        assert "spills" in stats.staging, qid
    snap = capped.residency.stats_snapshot()
    assert snap["spills"] + snap["evictions"] >= 1, \
        "cap below the working set exercised neither churn nor spill"
    budget = capped.residency.budget_bytes
    assert snap["stagedBytes"] <= budget

    # default budget: warm reruns are all hits, never spills
    warm = ShardedQueryExecutor()
    qids = sorted(ssb.QUERIES)[:4]
    for qid in qids:
        warm.execute(compile_query(ssb.QUERIES[qid]), segs)
    for qid in qids:
        _, stats = warm.execute(compile_query(ssb.QUERIES[qid]), segs)
        assert stats.staging["misses"] == 0, qid
        assert stats.staging["spills"] == 0, qid
        assert stats.staging["hits"] >= 1, qid


def test_q2_groupby_vs_pandas(setup, dev_exec):
    cols, segs = setup
    df = pd.DataFrame(cols)
    rt, _ = dev_exec.execute(compile_query(ssb.QUERIES["Q2.1"]), segs)
    m = (df.p_category == "MFGR#12") & (df.s_region == "AMERICA")
    exp = (df[m].groupby(["d_year", "p_brand1"]).lo_revenue.sum()
           .reset_index().sort_values(["d_year", "p_brand1"]).head(10))
    assert len(rt.rows) == min(10, len(exp))
    for row, (_, erow) in zip(rt.rows, exp.iterrows()):
        assert row[0] == erow.d_year and row[1] == erow.p_brand1
        assert row[2] == pytest.approx(erow.lo_revenue, rel=1e-6)


def test_generator_distributions(setup):
    cols, _ = setup
    assert set(np.unique(cols["c_region"])) == set(ssb.REGIONS)
    assert len(np.unique(cols["p_brand1"])) == 1000
    assert len(np.unique(cols["c_city"])) == 250
    assert cols["lo_discount"].min() >= 0 and cols["lo_discount"].max() <= 10
    # revenue derivation holds
    np.testing.assert_array_equal(
        cols["lo_revenue"],
        cols["lo_extendedprice"] * (100 - cols["lo_discount"]) // 100)


def test_all_13_flights_on_sub_scan_rung(setup, dev_exec):
    """PR-13 acceptance: with the default multi-tree config every SSB
    flight serves from the star-tree DEVICE rung — zero
    expression-pair/group-off coverage-gap declines, docs_scanned orders
    of magnitude under the scan, chosen tree recorded."""
    cols, segs = setup
    assert all(s.metadata.star_tree_count == 5 for s in segs)
    for qid in sorted(ssb.QUERIES):
        ctx = compile_query(ssb.QUERIES[qid] + " LIMIT 100000")
        _, stats = dev_exec.execute(ctx, segs)
        served = [k for k in stats.decisions
                  if k.startswith("startree:scan->startree_device:tree")]
        assert served, (qid, stats.decisions)
        assert stats.startree_tree_index is not None, qid
        if stats.group_by_rung:
            assert stats.group_by_rung == "startree_device", \
                (qid, stats.group_by_rung)
        assert stats.num_docs_scanned < ROWS // 10, \
            (qid, stats.num_docs_scanned)
        gap = [k for k in stats.decisions
               if "startree_expression_agg_no_pair" in k
               or "startree_group_off_split_order" in k]
        assert not gap, (qid, gap)


def test_tree_build_times_recorded(setup):
    """The creator stamps per-tree build wall time into segment metadata
    (what the bench sums into the round JSON)."""
    _, segs = setup
    for s in segs:
        bs = s.metadata.star_tree_build_s
        assert len(bs) == s.metadata.star_tree_count
        assert all(b >= 0 for b in bs)
