"""Star-tree index: build/load round-trip, the reference's core parity
strategy — star-tree answers must equal non-star-tree answers on the same
data (ref: StarTreeClusterIntegrationTest) — and the DEVICE rung: node
slices through the group-by kernels, bit-identical to the scan paths."""

import numpy as np
import pandas as pd
import pytest

from pinot_tpu.engine import ServerQueryExecutor
from pinot_tpu.engine.aggregates import resolve_agg
from pinot_tpu.engine.startree_exec import pick_star_tree
from pinot_tpu.query import compile_query
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.segment.startree import (
    STAR,
    DictIdRange,
    StarTree,
    StarTreeBuilder,
    StarTreeConfig,
)
from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.spi.table import IndexingConfig, StarTreeIndexConfig

pytestmark = pytest.mark.startree

N = 4000


def make_schema():
    return Schema("orders", [
        FieldSpec("country", DataType.STRING),
        FieldSpec("category", DataType.STRING),
        FieldSpec("channel", DataType.STRING),
        FieldSpec("revenue", DataType.DOUBLE, FieldType.METRIC),
        FieldSpec("units", DataType.LONG, FieldType.METRIC),
    ])


def make_df(n=N, seed=3):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "country": [f"c{i}" for i in rng.integers(0, 12, n)],
        "category": [f"k{i}" for i in rng.integers(0, 8, n)],
        "channel": [["web", "store", "app"][i] for i in rng.integers(0, 3, n)],
        "revenue": np.round(rng.gamma(2.0, 50.0, n), 2),
        "units": rng.integers(1, 20, n).astype(np.int64),
    })


@pytest.fixture(scope="module", params=[10_000, 16], ids=["fat-leaves", "deep-split"])
def seg_with_tree(request, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("st"))
    df = make_df()
    cfg = IndexingConfig(star_tree_index_configs=[StarTreeIndexConfig(
        dimensions_split_order=["country", "category", "channel"],
        function_column_pairs=["COUNT__*", "SUM__revenue", "MAX__revenue",
                               "MIN__revenue", "SUM__units"],
        max_leaf_records=request.param)])
    b = SegmentBuilder(make_schema(), "orders_0", indexing_config=cfg)
    b.build({c: df[c].tolist() for c in df.columns}, out)
    seg = load_segment(f"{out}/orders_0")
    assert seg.metadata.star_tree_count == 1
    assert len(seg.star_trees) == 1
    return seg, df


PARITY_QUERIES = [
    "SELECT count(*), sum(revenue) FROM orders",
    "SELECT sum(revenue), sum(units) FROM orders WHERE country = 'c3'",
    "SELECT min(revenue), max(revenue) FROM orders WHERE category IN ('k1','k2')",
    "SELECT country, sum(revenue), count(*) FROM orders GROUP BY country "
    "ORDER BY country LIMIT 50",
    "SELECT country, category, sum(units) FROM orders WHERE channel = 'web' "
    "GROUP BY country, category ORDER BY country, category LIMIT 200",
    "SELECT category, avg(revenue) FROM orders GROUP BY category "
    "ORDER BY category LIMIT 50",
    "SELECT channel, max(revenue) FROM orders WHERE country != 'c0' "
    "GROUP BY channel ORDER BY channel LIMIT 50",
]


class TestStarTreeParity:
    @pytest.mark.parametrize("sql", PARITY_QUERIES)
    def test_star_tree_matches_scan(self, seg_with_tree, sql):
        """The reference's StarTreeClusterIntegrationTest invariant."""
        seg, _ = seg_with_tree
        ex = ServerQueryExecutor(use_device=False)
        ctx = compile_query(sql)
        aggs = [resolve_agg(f) for f in ctx.aggregations]
        assert pick_star_tree(ctx, aggs, seg) is not None, "tree must fit"

        with_tree, stats_tree = ex.execute(ctx, [seg])
        ctx2 = compile_query(sql)
        ctx2.options["useStarTree"] = "false"
        without, _ = ex.execute(ctx2, [seg])
        assert len(with_tree.rows) == len(without.rows)
        for a, b in zip(with_tree.rows, without.rows):
            for x, y in zip(a, b):
                if isinstance(y, float):
                    assert x == pytest.approx(y, rel=1e-9)
                else:
                    assert x == y

    def test_tree_scans_fewer_records(self, seg_with_tree):
        seg, _ = seg_with_tree
        ex = ServerQueryExecutor(use_device=False)
        ctx = compile_query("SELECT sum(revenue) FROM orders")
        _, stats = ex.execute(ctx, [seg])
        # filter-less total should touch far fewer pre-agg records than docs
        assert 0 < stats.num_docs_scanned < N / 2

    def test_unfit_queries_fall_through(self, seg_with_tree):
        seg, _ = seg_with_tree
        ex = ServerQueryExecutor(use_device=False)
        # revenue (a metric, not a dim) in the filter -> not fit, still correct
        t, _ = ex.execute(compile_query(
            "SELECT count(*) FROM orders WHERE revenue > 100"), [seg])
        ctx = compile_query("SELECT count(*) FROM orders WHERE revenue > 100")
        aggs = [resolve_agg(f) for f in ctx.aggregations]
        assert pick_star_tree(ctx, aggs, seg) is None
        assert t.rows[0][0] > 0


class TestStarTreeBuilder:
    def test_save_load_round_trip(self, tmp_path):
        df = make_df(500, seed=9)
        cfg = StarTreeConfig(["country", "category"],
                             [("count", "*"), ("sum", "revenue")],
                             max_leaf_records=8)
        # dictIds: factorize in sorted order like the segment dictionaries
        c_codes = pd.Categorical(df.country).codes.astype(np.int32)
        k_codes = pd.Categorical(df.category).codes.astype(np.int32)
        tree = StarTreeBuilder(cfg).build(
            {"country": c_codes, "category": k_codes},
            {"revenue": df.revenue.to_numpy()}, len(df))
        tree.save(str(tmp_path))
        loaded = StarTree.load(str(tmp_path))
        assert loaded is not None
        assert loaded.num_records == tree.num_records
        np.testing.assert_array_equal(np.asarray(loaded.dims),
                                      np.asarray(tree.dims))

        # filter-less total via traversal (star path / un-split leaves)
        idx = loaded.select_records({}, [])
        assert np.asarray(loaded.metrics["count__*"])[idx].sum() == len(df)

    def test_skip_star_creation(self):
        df = make_df(300, seed=11)
        c = pd.Categorical(df.country).codes.astype(np.int32)
        k = pd.Categorical(df.category).codes.astype(np.int32)
        cfg = StarTreeConfig(["country", "category"], [("count", "*")],
                             max_leaf_records=1,
                             skip_star_creation=["country"])
        tree = StarTreeBuilder(cfg).build({"country": c, "category": k}, {},
                                          len(df))
        # no record may have STAR at the skipped dimension
        assert not np.any(np.asarray(tree.dims)[:, 0] == STAR)
        # grouping by category still answers correctly via concrete rows
        idx = tree.select_records({}, ["category"])
        got = {}
        cats = np.asarray(tree.dims)[idx, 1]
        cnts = np.asarray(tree.metrics["count__*"])[idx]
        for cat, n in zip(cats, cnts):
            got[cat] = got.get(cat, 0) + int(n)
        want = df.groupby(k).size().to_dict()
        assert got == want

    def test_default_star_tree(self, tmp_path):
        df = make_df(400, seed=13)
        cfg = IndexingConfig(enable_default_star_tree=True)
        b = SegmentBuilder(make_schema(), "orders_d", indexing_config=cfg)
        b.build({c: df[c].tolist() for c in df.columns}, str(tmp_path))
        seg = load_segment(f"{tmp_path}/orders_d")
        assert seg.metadata.star_tree_count == 1
        tree = seg.star_trees[0]
        assert tree.has_pair("count", "*")
        assert tree.has_pair("sum", "revenue")
        assert tree.has_pair("sum", "units")


# ==========================================================================
# the device rung: node slices through the group-by kernels
# ==========================================================================

SSB_DIMS = ["d_year", "c_region", "s_region", "p_category", "p_brand1"]


def ssb_shaped_schema():
    D, M = FieldType.DIMENSION, FieldType.METRIC
    return Schema("lineorder_t", [
        FieldSpec("d_year", DataType.INT, D),
        FieldSpec("c_region", DataType.STRING, D),
        FieldSpec("s_region", DataType.STRING, D),
        FieldSpec("p_category", DataType.STRING, D),
        FieldSpec("p_brand1", DataType.STRING, D),
        FieldSpec("lo_quantity", DataType.INT, D),
        FieldSpec("lo_revenue", DataType.LONG, M),
        FieldSpec("lo_supplycost", DataType.LONG, M),
        FieldSpec("tags", DataType.LONG, single_value=False),
    ])


def ssb_shaped_frame(n, seed):
    rng = np.random.default_rng(seed)
    regions = np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE"])
    cat_i = rng.integers(0, 5, n)
    brand_i = rng.integers(0, 4, n)
    return {
        "d_year": rng.integers(1992, 1999, n).astype(np.int64),
        "c_region": regions[rng.integers(0, 4, n)],
        "s_region": regions[rng.integers(0, 4, n)],
        "p_category": np.array([f"C{i}" for i in range(5)])[cat_i],
        "p_brand1": np.array([f"C{c}B{b}" for c in range(5)
                              for b in range(4)])[cat_i * 4 + brand_i],
        "lo_quantity": rng.integers(1, 50, n).astype(np.int64),
        "lo_revenue": rng.integers(100, 900_000, n).astype(np.int64),
        "lo_supplycost": rng.integers(50, 60_000, n).astype(np.int64),
        "tags": [list(rng.integers(0, 9, rng.integers(1, 4)))
                 for _ in range(n)],
    }


@pytest.fixture(scope="module")
def ssb_shaped(tmp_path_factory):
    """Two SSB-shaped segments with the full pre-agg pair set (sum/min/max
    revenue + sum supplycost + count, so avg/min/max queries are eligible
    too)."""
    out = str(tmp_path_factory.mktemp("st_dev"))
    cfg = IndexingConfig(star_tree_index_configs=[StarTreeIndexConfig(
        dimensions_split_order=list(SSB_DIMS),
        function_column_pairs=["COUNT__*", "SUM__lo_revenue",
                               "SUM__lo_supplycost", "MIN__lo_revenue",
                               "MAX__lo_revenue"],
        max_leaf_records=64)])
    segs = []
    for i in range(2):
        b = SegmentBuilder(ssb_shaped_schema(), f"lot_{i}",
                           indexing_config=cfg)
        b.build(ssb_shaped_frame(6000, seed=50 + i), out)
        segs.append(load_segment(f"{out}/lot_{i}"))
    assert all(s.metadata.star_tree_count == 1 for s in segs)
    return segs


@pytest.fixture(scope="module")
def device_exec():
    return ServerQueryExecutor()


@pytest.fixture(scope="module")
def host_exec():
    return ServerQueryExecutor(use_device=False)


def _run3(sql, segs, device_exec, host_exec):
    """(device rows+stats, device-scan rows, host rows) for one SQL."""
    got, stats = device_exec.execute(compile_query(sql), segs)
    scan_ctx = compile_query(sql)
    scan_ctx.options["useStarTree"] = "false"
    scan, _ = device_exec.execute(scan_ctx, segs)
    want, _ = host_exec.execute(compile_query(sql), segs)
    return got, stats, scan, want


def _assert_identical(name, a_rows, b_rows):
    """BIT-identical: pre-agg sums of integers in f64 are exact, so the
    star-tree rung owes the scan paths full equality, not approx."""
    assert len(a_rows) == len(b_rows), (name, len(a_rows), len(b_rows))
    for ar, br in zip(a_rows, b_rows):
        assert ar == br, (name, ar, br)


class TestStarTreeDeviceRung:
    AGGS = ["count(*)", "sum(lo_revenue)", "sum(lo_supplycost)",
            "min(lo_revenue)", "max(lo_revenue)", "avg(lo_revenue)"]

    def test_q2_shape_serves_from_device_nodes(self, ssb_shaped,
                                               device_exec, host_exec):
        sql = ("SELECT d_year, p_brand1, sum(lo_revenue) FROM lineorder_t "
               "WHERE p_category = 'C2' AND s_region = 'AMERICA' "
               "GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1 "
               "LIMIT 10000")
        got, stats, scan, want = _run3(sql, ssb_shaped, device_exec,
                                       host_exec)
        assert stats.group_by_rung == "startree_device"
        total = sum(s.num_docs for s in ssb_shaped)
        assert 0 < stats.num_docs_scanned < total / 10
        _assert_identical("q2-scan", got.rows, scan.rows)
        _assert_identical("q2-host", got.rows, want.rows)

    def test_parity_fuzz_eligible(self, ssb_shaped, device_exec, host_exec):
        """Randomized eligible queries: device star-tree rung vs full-scan
        device path vs host engine, bit-identical, rung recorded."""
        rng = np.random.default_rng(7)
        preds_pool = [
            "c_region = 'ASIA'",
            "s_region IN ('AMERICA', 'EUROPE')",
            "p_category = 'C1'",
            "p_brand1 BETWEEN 'C1B0' AND 'C3B2'",
            "d_year BETWEEN 1993 AND 1996",
            "d_year IN (1992, 1995, 1998)",
        ]
        for trial in range(20):
            gdims = list(rng.choice(SSB_DIMS, size=int(rng.integers(1, 4)),
                                    replace=False))
            aggs = list(rng.choice(self.AGGS,
                                   size=int(rng.integers(1, 4)),
                                   replace=False))
            preds = list(rng.choice(preds_pool,
                                    size=int(rng.integers(0, 3)),
                                    replace=False))
            sql = (f"SELECT {', '.join(gdims + aggs)} FROM lineorder_t "
                   + (f"WHERE {' AND '.join(preds)} " if preds else "")
                   + f"GROUP BY {', '.join(gdims)} "
                   + f"ORDER BY {', '.join(gdims)} LIMIT 100000")
            got, stats, scan, want = _run3(sql, ssb_shaped, device_exec,
                                           host_exec)
            assert stats.group_by_rung == "startree_device", (trial, sql)
            _assert_identical(f"fuzz{trial}-scan", got.rows, scan.rows)
            _assert_identical(f"fuzz{trial}-host", got.rows, want.rows)

    @pytest.mark.parametrize("sql,why", [
        ("SELECT d_year, sum(lo_revenue) FROM lineorder_t "
         "WHERE c_region = 'ASIA' OR s_region = 'ASIA' "
         "GROUP BY d_year ORDER BY d_year", "OR filter"),
        ("SELECT lo_quantity, sum(lo_revenue) FROM lineorder_t "
         "WHERE c_region = 'ASIA' GROUP BY lo_quantity "
         "ORDER BY lo_quantity LIMIT 100", "group-by off the split order"),
        ("SELECT d_year, summv(tags) FROM lineorder_t GROUP BY d_year "
         "ORDER BY d_year", "MV aggregation has no pre-agg pair"),
        ("SELECT d_year, sum(lo_quantity) FROM lineorder_t GROUP BY d_year "
         "ORDER BY d_year", "aggregation outside the pre-agg set"),
    ])
    def test_almost_eligible_falls_to_scan(self, ssb_shaped, device_exec,
                                           host_exec, sql, why):
        """Queries one rule short of eligibility must take the scan path —
        correct rung AND correct answers."""
        got, stats = device_exec.execute(compile_query(sql), ssb_shaped)
        assert stats.group_by_rung not in ("startree_device", "startree"), \
            (why, stats.group_by_rung)
        want, _ = host_exec.execute(compile_query(sql), ssb_shaped)
        _assert_identical(why, got.rows, want.rows)

    def test_scalar_aggregation_on_device_nodes(self, ssb_shaped,
                                                device_exec, host_exec):
        sql = ("SELECT count(*), sum(lo_revenue), avg(lo_revenue) "
               "FROM lineorder_t WHERE c_region = 'AMERICA'")
        got, stats, scan, want = _run3(sql, ssb_shaped, device_exec,
                                       host_exec)
        total = sum(s.num_docs for s in ssb_shaped)
        assert 0 < stats.num_docs_scanned < total / 10
        _assert_identical("scalar-scan", got.rows, scan.rows)
        _assert_identical("scalar-host", got.rows, want.rows)

    def test_empty_slice_matches_scan(self, ssb_shaped, device_exec,
                                      host_exec):
        sql = ("SELECT d_year, sum(lo_revenue) FROM lineorder_t "
               "WHERE c_region = 'AMERICA' AND c_region = 'ASIA' "
               "GROUP BY d_year ORDER BY d_year")
        got, stats, scan, want = _run3(sql, ssb_shaped, device_exec,
                                       host_exec)
        _assert_identical("empty-scan", got.rows, scan.rows)
        _assert_identical("empty-host", got.rows, want.rows)
        assert got.rows == []


class TestCapSafeRange:
    def test_range_over_cap_declines_to_slice(self, ssb_shaped, device_exec,
                                              host_exec, monkeypatch):
        """A RANGE whose dictId set would exceed _MAX_RANGE_IDS must ride a
        contiguous DictIdRange slice check — still the star-tree rung, same
        answers — instead of bailing to the full scan."""
        from pinot_tpu.engine import startree_exec

        monkeypatch.setattr(startree_exec, "_MAX_RANGE_IDS", 4)
        sql = ("SELECT d_year, p_brand1, sum(lo_revenue) FROM lineorder_t "
               "WHERE p_brand1 BETWEEN 'C0B0' AND 'C2B3' "  # 12 dictIds > 4
               "GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1 "
               "LIMIT 100000")
        got, stats, scan, want = _run3(sql, ssb_shaped, device_exec,
                                       host_exec)
        assert stats.group_by_rung == "startree_device"
        _assert_identical("cap-scan", got.rows, scan.rows)
        _assert_identical("cap-host", got.rows, want.rows)

    def test_range_at_cap_boundary_stays_set(self, ssb_shaped, device_exec,
                                             host_exec, monkeypatch):
        from pinot_tpu.engine import startree_exec
        from pinot_tpu.query.expressions import Identifier, Predicate, PredicateType

        monkeypatch.setattr(startree_exec, "_MAX_RANGE_IDS", 4)
        seg = ssb_shaped[0]
        # exactly at the cap: still a set
        p = Predicate(PredicateType.RANGE, Identifier("p_brand1"),
                      lower="C0B0", upper="C0B3",
                      lower_inclusive=True, upper_inclusive=True)
        m = startree_exec._matching_ids(seg, p)
        assert isinstance(m, set) and len(m) == 4
        # one past the cap: the contiguous slice representation
        p2 = Predicate(PredicateType.RANGE, Identifier("p_brand1"),
                       lower="C0B0", upper="C1B0",
                       lower_inclusive=True, upper_inclusive=True)
        m2 = startree_exec._matching_ids(seg, p2)
        assert isinstance(m2, DictIdRange) and len(m2) == 5

    def test_noncontiguous_over_cap_falls_to_scan(self, ssb_shaped,
                                                  device_exec, host_exec,
                                                  monkeypatch):
        from pinot_tpu.engine import startree_exec

        monkeypatch.setattr(startree_exec, "_MAX_RANGE_IDS", 4)
        # NOT_IN materializes card-1 non-contiguous ids > cap -> scan path
        sql = ("SELECT d_year, sum(lo_revenue) FROM lineorder_t "
               "WHERE p_brand1 NOT IN ('C2B1') GROUP BY d_year "
               "ORDER BY d_year")
        got, stats = device_exec.execute(compile_query(sql), ssb_shaped)
        assert stats.group_by_rung not in ("startree_device", "startree")
        want, _ = host_exec.execute(compile_query(sql), ssb_shaped)
        _assert_identical("notin", got.rows, want.rows)

    def test_select_records_range_equals_set(self, ssb_shaped):
        tree = ssb_shaped[0].star_trees[0]
        as_range = tree.select_records({"p_brand1": DictIdRange(3, 9)},
                                       ["d_year"])
        as_set = tree.select_records({"p_brand1": set(range(3, 10))},
                                     ["d_year"])
        np.testing.assert_array_equal(np.sort(as_range), np.sort(as_set))


class TestNodeArrayResidency:
    def test_nodes_in_memory_accounting_and_evictable(self, ssb_shaped):
        """Acceptance: node arrays appear in /debug/memory byte accounting
        and are evictable under budget pressure."""
        ex = ServerQueryExecutor()
        sql = ("SELECT d_year, sum(lo_revenue) FROM lineorder_t "
               "WHERE p_category = 'C1' GROUP BY d_year ORDER BY d_year")
        _, stats = ex.execute(compile_query(sql), ssb_shaped)
        assert stats.group_by_rung == "startree_device"

        snap = ex.residency.snapshot()
        staged = snap["stagedSegments"]
        assert staged, "star-tree query staged nothing"
        assert all(d["startrees"] >= 1 for d in staged.values()), staged
        assert snap["stagedBytes"] > 0
        # node bytes are part of the resident's accounting: releasing the
        # trees must shrink nbytes
        name = next(iter(staged))
        resident = ex.residency._entries[name].resident
        with_nodes = resident.nbytes()
        node_bytes = sum(int(a.nbytes) for t in resident._startree.values()
                         for a in t.values())
        assert node_bytes > 0
        assert with_nodes >= node_bytes

        # budget pressure: unpinned residents (trees included) evict
        ex.residency.set_budget_bytes(1)
        assert ex.residency.resident_count() == 0
        assert resident._startree == {}
        # and the rung recovers after eviction (restage on demand)
        ex.residency.set_budget_bytes(0)  # uncapped
        _, stats2 = ex.execute(compile_query(sql), ssb_shaped)
        assert stats2.group_by_rung == "startree_device"

    def test_spilled_query_uses_host_walker(self, ssb_shaped, host_exec):
        """Admission spill (device not allowed) must still serve star-tree
        queries — through the host walker, host-identical."""
        ex = ServerQueryExecutor(hbm_budget_bytes=1)
        sql = ("SELECT d_year, sum(lo_revenue) FROM lineorder_t "
               "WHERE p_category = 'C1' GROUP BY d_year ORDER BY d_year")
        got, stats = ex.execute(compile_query(sql), ssb_shaped)
        assert stats.group_by_rung == "startree"
        assert stats.staging.get("spills") == 1
        want, _ = host_exec.execute(compile_query(sql), ssb_shaped)
        _assert_identical("spill", got.rows, want.rows)


class TestShardedStarTree:
    def test_sharded_executor_rides_device_rung(self, ssb_shaped,
                                                host_exec):
        """The sharded combine routes star-tree-fit queries to the
        per-segment path: each segment's node slice through the device
        kernels, partials merged by GroupByResult (the CombineOperator
        analogue) — coalescing machinery untouched."""
        from pinot_tpu.parallel import ShardedQueryExecutor

        ex = ShardedQueryExecutor()
        sql = ("SELECT d_year, p_brand1, sum(lo_revenue), count(*) "
               "FROM lineorder_t WHERE s_region = 'EUROPE' "
               "GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1 "
               "LIMIT 100000")
        got, stats = ex.execute(compile_query(sql), ssb_shaped)
        assert stats.group_by_rung == "startree_device"
        assert stats.num_segments_processed == len(ssb_shaped)
        want, _ = host_exec.execute(compile_query(sql), ssb_shaped)
        _assert_identical("sharded", got.rows, want.rows)


# ==========================================================================
# PR-13: expression pre-agg pairs, multi-tree selection, lexsort build
# ==========================================================================


@pytest.fixture(scope="module")
def expr_shaped(tmp_path_factory):
    """Two segments whose tree carries DERIVED expression pairs
    (ref: StarTreeV2 derived-column function-column pairs)."""
    out = str(tmp_path_factory.mktemp("st_expr"))
    cfg = IndexingConfig(star_tree_index_configs=[StarTreeIndexConfig(
        dimensions_split_order=["d_year", "c_region", "lo_quantity"],
        function_column_pairs=["COUNT__*", "SUM__lo_revenue",
                               "SUM__lo_revenue*lo_quantity",
                               "SUM__lo_revenue-lo_supplycost"],
        max_leaf_records=64)])
    segs = []
    for i in range(2):
        b = SegmentBuilder(ssb_shaped_schema(), f"loe_{i}",
                           indexing_config=cfg)
        b.build(ssb_shaped_frame(6000, seed=90 + i), out)
        segs.append(load_segment(f"{out}/loe_{i}"))
    assert all(s.metadata.star_tree_count == 1 for s in segs)
    return segs


class TestExpressionPairs:
    """Tentpole (a): sum/avg over +/-/* expressions serve from derived
    pre-agg pairs, bit-identical to both scan paths."""

    EXPR_AGGS = ["sum(lo_revenue * lo_quantity)",
                 "sum(lo_quantity * lo_revenue)",   # commutative canon
                 "sum(lo_revenue - lo_supplycost)",
                 "avg(lo_revenue * lo_quantity)",
                 "count(*)"]

    def test_parity_fuzz_expression_pairs(self, expr_shaped, device_exec,
                                          host_exec):
        rng = np.random.default_rng(23)
        gpool = ["d_year", "c_region", "lo_quantity"]
        ppool = ["c_region = 'ASIA'", "d_year BETWEEN 1993 AND 1996",
                 "lo_quantity < 25", "d_year IN (1992, 1995)"]
        for trial in range(12):
            gdims = list(rng.choice(gpool, size=int(rng.integers(0, 3)),
                                    replace=False))
            aggs = list(rng.choice(self.EXPR_AGGS,
                                   size=int(rng.integers(1, 4)),
                                   replace=False))
            preds = list(rng.choice(ppool, size=int(rng.integers(0, 3)),
                                    replace=False))
            sql = (f"SELECT {', '.join(gdims + aggs)} FROM lineorder_t "
                   + (f"WHERE {' AND '.join(preds)} " if preds else "")
                   + (f"GROUP BY {', '.join(gdims)} "
                      f"ORDER BY {', '.join(gdims)} " if gdims else "")
                   + "LIMIT 100000")
            got, stats, scan, want = _run3(sql, expr_shaped, device_exec,
                                           host_exec)
            if gdims:
                assert stats.group_by_rung == "startree_device", (trial, sql)
            else:
                assert stats.startree_tree_index == 0, (trial, sql)
            _assert_identical(f"expr{trial}-scan", got.rows, scan.rows)
            _assert_identical(f"expr{trial}-host", got.rows, want.rows)

    def test_almost_eligible_expression_declines(self, expr_shaped,
                                                 device_exec, host_exec):
        """sum(a*b + c): a valid arithmetic shape whose derived pair is
        NOT stored — must decline with the expression reason and still
        answer correctly from the scan."""
        sql = ("SELECT d_year, sum(lo_revenue * lo_quantity + lo_supplycost) "
               "FROM lineorder_t GROUP BY d_year ORDER BY d_year")
        got, stats = device_exec.execute(compile_query(sql), expr_shaped)
        assert stats.group_by_rung not in ("startree_device", "startree")
        assert any("startree_expression_agg_no_pair" in k
                   for k in stats.decisions), stats.decisions
        want, _ = host_exec.execute(compile_query(sql), expr_shaped)
        _assert_identical("almost", got.rows, want.rows)

    def test_division_never_pairs(self, expr_shaped, device_exec):
        """sum(a/b) is outside the pre-aggregable subset (float division
        breaks the exact-integer pre-agg contract) — scan serves."""
        sql = ("SELECT sum(lo_revenue / lo_quantity) FROM lineorder_t "
               "WHERE c_region = 'ASIA'")
        _, stats = device_exec.execute(compile_query(sql), expr_shaped)
        assert stats.startree_tree_index is None
        assert any("startree_expression_agg_no_pair" in k
                   for k in stats.decisions), stats.decisions


class TestMultiTreeSelection:
    """Tentpole (b): every fitting tree scored by estimated records-read;
    cheapest wins, index breaks ties."""

    def _segment(self, tmp_path, configs, name="orders_mt"):
        df = make_df(1200, seed=21)
        cfg = IndexingConfig(star_tree_index_configs=configs)
        b = SegmentBuilder(make_schema(), name, indexing_config=cfg)
        b.build({c: df[c].tolist() for c in df.columns}, str(tmp_path))
        return load_segment(f"{tmp_path}/{name}")

    def test_cheapest_tree_wins(self, tmp_path):
        """Tree 0 skips star creation on its leading (free) dim, so a
        category-filtered scalar query costs card(country) there; tree 1
        answers it from one record slice — the pick must take tree 1."""
        seg = self._segment(tmp_path, [
            StarTreeIndexConfig(
                dimensions_split_order=["country", "category"],
                skip_star_node_creation_for_dimensions=["country"],
                function_column_pairs=["COUNT__*", "SUM__revenue"],
                max_leaf_records=4),
            StarTreeIndexConfig(
                dimensions_split_order=["category"],
                function_column_pairs=["COUNT__*", "SUM__revenue"],
                max_leaf_records=4),
        ])
        assert seg.metadata.star_tree_count == 2
        ctx = compile_query(
            "SELECT sum(revenue) FROM orders WHERE category = 'k3'")
        aggs = [resolve_agg(f) for f in ctx.aggregations]
        pick = pick_star_tree(ctx, aggs, seg)
        assert pick is not None and pick.index == 1

    def test_tie_breaks_on_lower_index(self, tmp_path):
        """Two trees scoring identically: the configured order pins the
        winner (index 0) — deterministic plans across restarts."""
        twice = [StarTreeIndexConfig(
            dimensions_split_order=["country", "category"],
            function_column_pairs=["COUNT__*", "SUM__revenue"],
            max_leaf_records=4)] * 2
        seg = self._segment(tmp_path, twice, name="orders_tie")
        assert seg.metadata.star_tree_count == 2
        ctx = compile_query(
            "SELECT sum(revenue) FROM orders WHERE country = 'c1'")
        aggs = [resolve_agg(f) for f in ctx.aggregations]
        pick = pick_star_tree(ctx, aggs, seg)
        assert pick is not None and pick.index == 0

    def test_selection_rides_ledger_and_stats(self, tmp_path):
        seg = self._segment(tmp_path, [
            StarTreeIndexConfig(
                dimensions_split_order=["country"],
                function_column_pairs=["COUNT__*"],
                max_leaf_records=4),
            StarTreeIndexConfig(
                dimensions_split_order=["category", "channel"],
                function_column_pairs=["COUNT__*", "SUM__revenue"],
                max_leaf_records=4),
        ], name="orders_led")
        ex = ServerQueryExecutor(use_device=False)
        _, stats = ex.execute(compile_query(
            "SELECT channel, sum(revenue) FROM orders "
            "GROUP BY channel ORDER BY channel"), [seg])
        assert stats.startree_tree_index == 1
        assert stats.decisions.get("startree:scan->startree:tree1") == 1

    def test_most_specific_decline_reason_across_trees(self, tmp_path):
        """Satellite: a tree failing on missing_function_pair (one config
        line from serving) must out-report a sibling failing on
        group_off_split_order — in EITHER tree order."""
        a = StarTreeIndexConfig(
            dimensions_split_order=["country"],
            function_column_pairs=["COUNT__*"], max_leaf_records=4)
        b = StarTreeIndexConfig(
            dimensions_split_order=["country", "category"],
            function_column_pairs=["COUNT__*"], max_leaf_records=4)
        for name, configs in (("mt_ab", [a, b]), ("mt_ba", [b, a])):
            seg = self._segment(tmp_path, configs, name=name)
            ctx = compile_query(
                "SELECT category, sum(revenue) FROM orders "
                "GROUP BY category ORDER BY category")
            aggs = [resolve_agg(f) for f in ctx.aggregations]
            reasons = []
            assert pick_star_tree(ctx, aggs, seg,
                                  on_decline=reasons.append) is None
            # tree [country] fails the group check; tree [country,
            # category] fits the shape but lacks SUM__revenue — the
            # more-specific reason wins regardless of order
            assert reasons == ["startree_missing_function_pair"], (name,
                                                                   reasons)


class TestLexsortBuildEquality:
    """Tentpole (c): the vectorized builder must emit byte-identical
    arrays to the recursive oracle on the existing fixtures."""

    @pytest.mark.parametrize("max_leaf,skip", [
        (10_000, []), (16, []), (1, []), (64, ["country"]),
        (8, ["category", "channel"]),
    ])
    def test_node_arrays_identical(self, max_leaf, skip):
        df = make_df(N, seed=3)
        cfg = StarTreeConfig(
            ["country", "category", "channel"],
            [("count", "*"), ("sum", "revenue"), ("min", "revenue"),
             ("max", "revenue"), ("sum", "units")],
            max_leaf_records=max_leaf, skip_star_creation=skip)
        dims = {
            "country": pd.Categorical(df.country).codes.astype(np.int32),
            "category": pd.Categorical(df.category).codes.astype(np.int32),
            "channel": pd.Categorical(df.channel).codes.astype(np.int32),
        }
        mets = {"revenue": df.revenue.to_numpy(),
                "units": df.units.to_numpy()}
        rec = StarTreeBuilder(cfg).build(dict(dims), dict(mets), len(df),
                                         engine="recursive")
        vec = StarTreeBuilder(cfg).build(dict(dims), dict(mets), len(df))
        np.testing.assert_array_equal(rec.dims, vec.dims)
        np.testing.assert_array_equal(rec.nodes, vec.nodes)
        assert set(rec.metrics) == set(vec.metrics)
        for k in rec.metrics:
            np.testing.assert_array_equal(rec.metrics[k], vec.metrics[k],
                                          err_msg=k)

    def test_derived_pair_equality_and_values(self):
        df = make_df(800, seed=31)
        cfg = StarTreeConfig(
            ["country"], [("count", "*"), ("sum", "(revenue*units)")],
            max_leaf_records=8)
        dims = {"country": pd.Categorical(df.country).codes.astype(np.int32)}
        mets = {"revenue": df.revenue.to_numpy(),
                "units": df.units.to_numpy()}
        rec = StarTreeBuilder(cfg).build(dict(dims), dict(mets), len(df),
                                         engine="recursive")
        vec = StarTreeBuilder(cfg).build(dict(dims), dict(mets), len(df))
        np.testing.assert_array_equal(rec.dims, vec.dims)
        np.testing.assert_array_equal(rec.metrics["sum__(revenue*units)"],
                                      vec.metrics["sum__(revenue*units)"])
        idx = vec.select_records({}, [])
        got = float(np.asarray(vec.metrics["sum__(revenue*units)"])[idx].sum())
        assert got == pytest.approx(float((df.revenue * df.units).sum()))


class TestPerTreeResidency:
    def test_release_one_tree_keeps_sibling(self, tmp_path):
        """Satellite: per-tree residency — evicting one tree must not drop
        its sibling, and the accounting must move by exactly the released
        tree's bytes."""
        df = make_df(1500, seed=41)
        cfg = IndexingConfig(star_tree_index_configs=[
            StarTreeIndexConfig(
                dimensions_split_order=["country", "category"],
                function_column_pairs=["COUNT__*", "SUM__revenue"],
                max_leaf_records=16),
            StarTreeIndexConfig(
                dimensions_split_order=["channel"],
                function_column_pairs=["COUNT__*", "SUM__units"],
                max_leaf_records=16),
        ])
        b = SegmentBuilder(make_schema(), "orders_rt", indexing_config=cfg)
        b.build({c: df[c].tolist() for c in df.columns}, str(tmp_path))
        seg = load_segment(f"{tmp_path}/orders_rt")
        ex = ServerQueryExecutor()
        # stage both trees through real queries
        _, s1 = ex.execute(compile_query(
            "SELECT country, sum(revenue) FROM orders "
            "GROUP BY country ORDER BY country"), [seg])
        _, s2 = ex.execute(compile_query(
            "SELECT channel, sum(units) FROM orders "
            "GROUP BY channel ORDER BY channel"), [seg])
        assert s1.startree_tree_index == 0
        assert s2.startree_tree_index == 1
        name = seg.segment_name
        resident = ex.residency._entries[name].resident
        per_tree = resident.startree_nbytes()
        assert set(per_tree) == {0, 1} and all(v > 0
                                               for v in per_tree.values())
        before = resident.nbytes()
        snap = ex.residency.snapshot()["stagedSegments"][name]
        assert snap["startrees"] == 2
        assert set(snap["startreeBytes"]) == {"0", "1"}

        assert ex.residency.release_startree(name, 0)
        assert set(resident.startree_nbytes()) == {1}  # sibling intact
        assert resident.nbytes() == before - per_tree[0]
        snap = ex.residency.snapshot()["stagedSegments"][name]
        assert snap["startrees"] == 1
        assert snap["startreeBytes"] == {"1": per_tree[1]}
        # double release is a no-op; unknown resident refuses
        assert not ex.residency.release_startree(name, 0)
        assert not ex.residency.release_startree("nope", 0)
        # the evicted tree restages on demand, same answers
        got, s3 = ex.execute(compile_query(
            "SELECT country, sum(revenue) FROM orders "
            "GROUP BY country ORDER BY country"), [seg])
        assert s3.startree_tree_index == 0
        assert set(resident.startree_nbytes()) == {0, 1}


# (The star-tree reason-registry conformance test moved to
# tests/test_reasons.py: ONE generic harness parameterized over
# tracing.reason_registry() replaced the per-module scans.)


# --------------------------------------------------------------------------
# the walk: binary search inside the sorted leaves, against the old loop
# --------------------------------------------------------------------------

def _reference_select_records(tree, eq_in_per_dim, group_by_dims):
    """The walk as it was before it searched the leaves, kept as the
    tests' oracle: a pointer chase over ``tree.nodes``, every leaf's whole
    range emitted, then every predicate and grouped dimension gathered and
    tested over all of it. Order of the indices: the stack's."""
    grouped = set(tree._dim_index[d] for d in group_by_dims)
    predicates = {tree._dim_index[d]: ids
                  for d, ids in eq_in_per_dim.items()}
    out = []
    stack = [0]
    nodes = tree.nodes
    while stack:
        ni = stack.pop()
        n = nodes[ni]
        if n["child_first"] < 0:
            out.append(np.arange(n["start"], n["end"], dtype=np.int64))
            continue
        dim = int(n["dim"])
        kids = range(int(n["child_first"]), int(n["child_last"]))
        if dim in predicates:
            match = predicates[dim]
            for c in kids:
                if int(nodes[c]["value"]) in match:
                    stack.append(c)
        elif dim in grouped:
            for c in kids:
                if int(nodes[c]["value"]) != STAR:
                    stack.append(c)
        else:
            star = next((c for c in kids
                         if int(nodes[c]["value"]) == STAR), None)
            if star is not None:
                stack.append(star)
            else:
                for c in kids:
                    stack.append(c)
    if not out:
        return np.empty(0, dtype=np.int64)
    idx = np.concatenate(out)
    mask = np.ones(idx.shape[0], dtype=bool)
    for dim, match in predicates.items():
        col = tree.dims[idx, dim]
        if isinstance(match, DictIdRange):
            mask &= (col >= match.lo) & (col <= match.hi)
        else:
            mask &= np.isin(col, np.fromiter(match, dtype=np.int32,
                                             count=len(match)))
    for dim in grouped:
        mask &= tree.dims[idx, dim] != STAR
    return idx[mask]


WALK_CARDS = [7, 4, 4, 5, 20]      # dictIds a dimension of SSB_DIMS below
WALK_TREES = {
    # name -> (builder engine, max_leaf_records, skip_star_creation, loaded)
    "lexsort": ("lexsort", 64, [], False),
    "recursive": ("recursive", 64, [], False),
    "lexsort-loaded": ("lexsort", 64, [], True),
    "recursive-loaded": ("recursive", 64, [], True),
    "skip-star": ("lexsort", 64, ["c_region", "p_category"], False),
    "skip-star-recursive": ("recursive", 64, ["c_region", "p_category"],
                            False),
    "root-is-a-leaf": ("lexsort", 10 ** 9, [], False),
    "tiny-leaves": ("lexsort", 1, [], False),
    "tiny-leaves-skip-star": ("recursive", 1, ["d_year", "p_brand1"], True),
    "fat-leaves-loaded": ("lexsort", 1500, [], True),
}
WALK_TREE_NAMES = ["ssb-shaped-segment"] + sorted(WALK_TREES)


@pytest.fixture(scope="module")
def walk_trees(ssb_shaped, tmp_path_factory):
    """Trees over one set of SSB-shaped dictIds by both builders, built
    and loaded, beside the segment builder's own loaded tree."""
    rng = np.random.default_rng(77)
    n = 6000
    dims = {d: rng.integers(0, c, n).astype(np.int32)
            for d, c in zip(SSB_DIMS, WALK_CARDS)}
    revenue = rng.integers(100, 900_000, n).astype(np.int64)
    trees = {"ssb-shaped-segment": ssb_shaped[0].star_trees[0]}
    for name, (engine, max_leaf, skip, loaded) in WALK_TREES.items():
        cfg = StarTreeConfig(list(SSB_DIMS),
                             [("count", "*"), ("sum", "lo_revenue")],
                             max_leaf_records=max_leaf,
                             skip_star_creation=skip)
        tree = StarTreeBuilder(cfg).build(
            dict(dims), {"lo_revenue": revenue}, n, engine=engine)
        if loaded:
            out = str(tmp_path_factory.mktemp("walk"))
            tree.save(out)
            tree = StarTree.load(out)
        trees[name] = tree
    return trees


# name -> (matches, grouped dimensions[, the matches the old loop is asked:
# where a match reaches below dictId 0 the old loop took STAR for a value])
WALK_CASES = {
    "nothing": ({}, []),
    "eq-first-dim": ({"d_year": {3}}, []),
    "eq-last-dim": ({"p_brand1": {7}}, []),
    "eq-absent-value": ({"s_region": {99}}, ["d_year"]),
    "eq-every-dim": ({"d_year": {2}, "c_region": {1}, "s_region": {3},
                      "p_category": {0}, "p_brand1": {11}}, []),
    # either side of _MAX_PINNED_VALUES: four values are searched one by
    # one, five fall to their bounding range and the mask
    "in-4-pinned": ({"p_brand1": {1, 5, 9, 13}}, []),
    "in-5-masked": ({"p_brand1": {1, 5, 9, 13, 17}}, []),
    "in-2-then-eq": ({"s_region": {0, 3}, "p_category": {2}}, ["d_year"]),
    "in-4-then-eq": ({"p_category": {0, 2, 3, 4}, "p_brand1": {6}}, []),
    "in-5-then-eq": ({"s_region": {1}, "p_category": {0, 1, 2, 3, 4},
                      "p_brand1": {6}}, []),
    "in-5-gaps-first-dim": ({"d_year": {0, 2, 4, 5, 6}}, ["s_region"]),
    "in-5-gaps-then-in-5-gaps": ({"d_year": {0, 2, 4, 5, 6},
                                  "p_brand1": {0, 3, 4, 18, 19}}, []),
    # ids too far apart for a table: np.isin tests them
    "in-5-sparse-ids": ({"p_brand1": {1, 5, 9, 13, 3_000_000}},
                        ["p_category"]),
    "in-contiguous-6": ({"p_brand1": set(range(3, 9))}, ["d_year"]),
    "range": ({"p_brand1": DictIdRange(3, 9)}, ["d_year"]),
    "range-3-pinned": ({"c_region": DictIdRange(1, 3), "s_region": {2}}, []),
    "range-one-value": ({"p_category": DictIdRange(2, 2),
                         "p_brand1": DictIdRange(4, 11)}, []),
    "range-past-the-end": ({"p_brand1": DictIdRange(15, 2 ** 31 - 1)}, []),
    # below a range the next column is not sorted: the equality is masked
    "range-then-eq": ({"d_year": DictIdRange(1, 5), "c_region": {2}}, []),
    "range-then-eq-in-leaf": ({"p_category": DictIdRange(0, 4),
                               "p_brand1": {10}}, ["d_year"]),
    "eq-range-eq": ({"s_region": {2}, "p_category": DictIdRange(0, 4),
                     "p_brand1": {5}}, []),
    # STAR is -1 and no dictId: a match that reaches below 0 selects what
    # its part from 0 up selects
    "range-below-zero": ({"c_region": DictIdRange(-5, 1)}, [],
                         {"c_region": DictIdRange(0, 1)}),
    "range-below-zero-in-leaf": ({"d_year": {4},
                                  "p_brand1": DictIdRange(-1, 6)}, [],
                                 {"d_year": {4},
                                  "p_brand1": DictIdRange(0, 6)}),
    "range-all-below-zero": ({"s_region": DictIdRange(-9, -1)}, ["d_year"],
                             {"s_region": set()}),
    "set-with-star": ({"c_region": {-1, 1}, "p_brand1": {-1, 0, 1, 2, 8, 9}},
                      [], {"c_region": {1}, "p_brand1": {0, 1, 2, 8, 9}}),
    "empty-set": ({"s_region": set()}, ["d_year"]),
    "empty-range": ({"p_brand1": DictIdRange(5, 4), "d_year": {1}}, []),
    "q2.1-shape": ({"p_category": {2}, "s_region": {1}},
                   ["d_year", "p_brand1"]),
    "q2.2-shape": ({"p_brand1": set(range(8, 16)), "s_region": {2}},
                   ["d_year", "p_brand1"]),
    "q3.3-shape": ({"c_region": {0, 2}, "s_region": {1, 3},
                    "d_year": set(range(0, 6))}, ["d_year"]),
    "grouped-only": ({}, ["c_region", "p_category"]),
    "grouped-then-eq": ({"p_brand1": {3}}, ["p_category"]),
    "grouped-and-filtered": ({"p_brand1": {2, 3, 11}}, ["p_brand1"]),
    "free-then-eq": ({"p_category": {1}}, []),
    "every-dim": ({"d_year": {0, 6}, "c_region": DictIdRange(1, 2),
                   "s_region": {0, 1, 3}, "p_category": {4},
                   "p_brand1": set(range(0, 20, 3))}, []),
}
WALK_RANDOM_SEEDS = (11, 12, 13)
WALK_RANDOM_DRAWS = 100


def _random_walk_queries(seed, draws=WALK_RANDOM_DRAWS):
    """Seeded predicate / group-by draws over SSB_DIMS' dictIds."""
    rng = np.random.default_rng(seed)
    for _ in range(draws):
        matches, groups = {}, []
        for d, card in zip(SSB_DIMS, WALK_CARDS):
            if rng.random() < 0.25:
                groups.append(d)
            kind = rng.integers(0, 10)
            if kind == 0:
                matches[d] = {int(rng.integers(0, card))}
            elif kind == 1:     # a set, its size on both sides of the cap
                k = int(rng.integers(2, min(card, 8) + 1))
                matches[d] = set(rng.choice(card, k, replace=False).tolist())
            elif kind == 2:
                lo = int(rng.integers(0, card))
                matches[d] = set(range(lo, int(rng.integers(lo, card)) + 1))
            elif kind == 3:
                lo = int(rng.integers(0, card))
                matches[d] = DictIdRange(lo, int(rng.integers(lo, card + 2)))
            elif kind == 4 and rng.random() < 0.1:
                matches[d] = set()
        yield matches, groups


@pytest.mark.parametrize("case", sorted(WALK_CASES)
                         + [f"random-{seed}" for seed in WALK_RANDOM_SEEDS])
@pytest.mark.parametrize("tree_name", WALK_TREE_NAMES)
def test_walk_selects_the_reference_records(walk_trees, tree_name, case):
    tree = walk_trees[tree_name]
    queries = ([WALK_CASES[case]] if case in WALK_CASES
               else _random_walk_queries(int(case.split("-")[1])))
    for matches, groups, *asked in queries:
        walk = {}
        got = tree.select_records(matches, groups, walk)
        want = np.sort(_reference_select_records(
            tree, asked[0] if asked else matches, groups))
        # the same records, and in ascending order
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{matches} {groups}")
        assert got.dtype == np.int64
        np.testing.assert_array_equal(
            got, tree.select_records(matches, groups))
        assert 0 <= walk["gathered"] <= walk["emitted"]
        assert got.shape[0] <= walk["emitted"]
        assert walk["nodes"] >= 1 or got.shape[0] == 0


def _nodes_with_depth(tree):
    """(node index, depth) of every node, from the root down."""
    out, stack = [], [(0, 0)]
    while stack:
        ni, depth = stack.pop()
        out.append((ni, depth))
        n = tree.nodes[ni]
        if n["child_first"] >= 0:
            assert n["dim"] == depth
            stack.extend((c, depth + 1) for c
                         in range(int(n["child_first"]),
                                  int(n["child_last"])))
    return out


@pytest.mark.parametrize("tree_name", WALK_TREE_NAMES)
def test_what_the_search_inside_a_leaf_stands_on(walk_trees, tree_name):
    """Both builders: a star child, where there is one, is its parent's
    last child and the value children stand in dictId order; a leaf at
    depth L is constant on the dimensions < L, holds no STAR on those
    >= L and is sorted on them in split order."""
    tree = walk_trees[tree_name]
    dims = np.asarray(tree.dims)
    nodes = tree.nodes
    leaves = stars = 0
    for ni, depth in _nodes_with_depth(tree):
        n = nodes[ni]
        rows = dims[int(n["start"]):int(n["end"])]
        if n["child_first"] >= 0:
            values = nodes["value"][int(n["child_first"]):
                                    int(n["child_last"])]
            stars += int(values[-1] == STAR)
            concrete = values[:-1] if values[-1] == STAR else values
            assert not np.any(concrete == STAR)
            assert np.all(np.diff(concrete) > 0)
            continue
        leaves += 1
        assert rows.shape[0] > 0
        assert np.all(rows[:, :depth] == rows[0, :depth])
        tail = rows[:, depth:]
        assert not np.any(tail == STAR)
        if tail.shape[1]:
            order = np.lexsort(tuple(tail[:, i] for i
                                     in range(tail.shape[1] - 1, -1, -1)))
            np.testing.assert_array_equal(order, np.arange(tail.shape[0]))
    assert leaves >= 1
    assert stars >= 1 or tree_name == "root-is-a-leaf"


def test_the_search_decides_where_the_old_walk_gathered(walk_trees):
    """On a root that is one sorted leaf: Q2.1's shape has d_year grouped,
    which stops the search at once, so all is masked; with d_year and
    c_region pinned the two equalities after them are searched too and
    nothing is masked; an IN-list of two is two pinned searches; a range
    cuts the leaf and leaves the equality after it to the mask."""
    tree = walk_trees["root-is-a-leaf"]
    n, walk = tree.num_records, {}
    tree.select_records({"p_category": {2}, "s_region": {1}},
                        ["d_year", "p_brand1"], walk)
    assert walk == {"nodes": 1, "emitted": n, "gathered": n}
    got = tree.select_records({"d_year": {3}, "c_region": {1},
                               "s_region": {1}, "p_category": {2}},
                              ["p_brand1"], walk)
    assert walk == {"nodes": 1, "emitted": n, "gathered": 0}
    assert got.shape[0] > 0
    got = tree.select_records({"d_year": {3, 5}, "c_region": {1}}, [], walk)
    assert walk == {"nodes": 1, "emitted": n, "gathered": 0}
    assert got.shape[0] > 0
    got = tree.select_records({"d_year": DictIdRange(0, 4),
                               "c_region": {1}}, [], walk)
    in_range = int(np.sum(np.asarray(tree.dims)[:, 0] <= 4))
    assert walk == {"nodes": 1, "emitted": n, "gathered": in_range}
    assert 0 < got.shape[0] < in_range < n
    # an empty match returns before a node is read
    tree.select_records({"d_year": {3}, "c_region": set()}, [], walk)
    assert walk == {"nodes": 0, "emitted": 0, "gathered": 0}


@pytest.mark.parametrize("tree_name", ["ssb-shaped-segment",
                                       "lexsort-loaded",
                                       "fat-leaves-loaded"])
def test_a_loaded_tree_walks_in_memory(walk_trees, tree_name, monkeypatch):
    """``dims.npy`` and ``nodes.npy`` are mapped; the walk reads node
    fields copied into memory and a plain view of the records, so no step
    of it passes ``np.memmap.__getitem__``."""
    tree = walk_trees[tree_name]
    assert isinstance(tree.dims, np.memmap)
    assert isinstance(tree.nodes, np.memmap)
    fields = dict(dim=tree._node_dim, value=tree._node_value,
                  start=tree._node_start, end=tree._node_end,
                  child_first=tree._child_first, child_last=tree._child_last)
    for name, arr in fields.items():
        assert type(arr) is np.ndarray and arr.flags.c_contiguous
        np.testing.assert_array_equal(arr, np.asarray(tree.nodes[name]))
    assert type(tree._walk_dims) is np.ndarray
    queries = [({"p_brand1": {1, 5, 9, 13, 17}, "s_region": {2}},
                ["d_year"]),
               ({"d_year": {2}, "c_region": {0, 3}}, ["p_category"])]
    want = [np.sort(_reference_select_records(tree, m, g))
            for m, g in queries]

    def refuse(self, index):
        raise AssertionError("the walk read a np.memmap")

    monkeypatch.setattr(np.memmap, "__getitem__", refuse)
    got = [tree.select_records(m, g) for m, g in queries]
    monkeypatch.undo()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
        assert g.shape[0] > 0
