"""Pallas fused scan kernel parity: bit-unpack + predicate + one-hot
group-by matmul vs the host engine (interpret mode on the CPU backend;
the same kernel compiles for real TPUs).

Ref parity targets: SVScanDocIdIterator.java:36 (predicate scan),
PinotDataBitSet.java:25 (bit extraction), DefaultGroupByExecutor (grouping).
"""

import numpy as np
import pandas as pd
import pytest

from pinot_tpu.engine import ServerQueryExecutor

pytestmark = pytest.mark.pallas
from pinot_tpu.engine.plan import plan_segment
from pinot_tpu.engine.staging import PALLAS_TILE, StagingCache, pack_bits
from pinot_tpu.query import compile_query
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema

N = 2 * PALLAS_TILE - 700   # 2 tiles with a padded tail


def make_schema():
    return Schema("pl_sales", [
        FieldSpec("region", DataType.STRING),
        FieldSpec("city", DataType.STRING),
        FieldSpec("year", DataType.INT),
        FieldSpec("qty", DataType.LONG, FieldType.METRIC),
        FieldSpec("price", DataType.DOUBLE, FieldType.METRIC),
    ])


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    out = tmp_path_factory.mktemp("pallas_segs")
    rng = np.random.default_rng(11)
    regions = ["east", "west", "north", "south"]
    cities = [f"c{i:03d}" for i in range(137)]   # 8-bit dictIds
    df = pd.DataFrame({
        "region": [regions[i] for i in rng.integers(0, 4, N)],
        "city": [cities[i] for i in rng.integers(0, len(cities), N)],
        "year": rng.integers(2000, 2024, N).astype(np.int64),
        "qty": rng.integers(1, 100, N).astype(np.int64),
        "price": np.round(rng.normal(80.0, 30.0, N), 2),
    })
    segs = []
    for i, sl in enumerate([slice(0, N // 2), slice(N // 2, N)]):
        b = SegmentBuilder(make_schema(), f"pl_sales_{i}")
        b.build({c: df[c].tolist()[sl] for c in df.columns}, str(out))
        segs.append(load_segment(str(out / f"pl_sales_{i}")))
    return df, segs


@pytest.fixture(scope="module")
def pallas_exec():
    return ServerQueryExecutor(use_device=True, use_pallas=True)


@pytest.fixture(scope="module")
def host_exec():
    return ServerQueryExecutor(use_device=False)


QUERIES = [
    "SELECT region, count(*) FROM pl_sales GROUP BY region ORDER BY region",
    "SELECT region, sum(qty), count(*) FROM pl_sales "
    "WHERE year BETWEEN 2005 AND 2015 GROUP BY region ORDER BY region",
    "SELECT region, sum(price), avg(price) FROM pl_sales "
    "WHERE region != 'west' GROUP BY region ORDER BY region",
    "SELECT city, sum(qty), avg(qty) FROM pl_sales WHERE year = 2010 "
    "GROUP BY city ORDER BY city LIMIT 200",
    "SELECT region, city, sum(price), count(*) FROM pl_sales "
    "WHERE year >= 2012 AND region = 'east' "
    "GROUP BY region, city ORDER BY region, city LIMIT 200",
    "SELECT year, sum(qty), sum(price) FROM pl_sales "
    "GROUP BY year ORDER BY year LIMIT 30",
]


def test_plans_are_pallas_eligible(setup, pallas_exec):
    """The suite must actually exercise the pallas path, not fall back."""
    from pinot_tpu.engine.pallas_kernels import extract_plan

    _, segs = setup
    for sql in QUERIES:
        plan = plan_segment(compile_query(sql), segs[0])
        assert extract_plan(plan, segs[0]) is not None, sql


@pytest.mark.parametrize("sql", QUERIES, ids=[q[:60] for q in QUERIES])
def test_pallas_matches_host(setup, pallas_exec, host_exec, sql):
    _, segs = setup
    got, _ = pallas_exec.execute(compile_query(sql), segs)
    want, _ = host_exec.execute(compile_query(sql), segs)
    assert len(got.rows) == len(want.rows)
    for gr, wr in zip(got.rows, want.rows):
        for g, w in zip(gr, wr):
            if isinstance(w, float):
                assert g == pytest.approx(w, rel=1e-5, abs=1e-6), (sql, gr, wr)
            else:
                assert g == w, (sql, gr, wr)
    assert len(pallas_exec.pallas_kernels) >= 1


def test_pallas_kernels_cached(setup, pallas_exec):
    _, segs = setup
    before = len(pallas_exec.pallas_kernels)
    sql = QUERIES[1]
    pallas_exec.execute(compile_query(sql), segs)
    pallas_exec.execute(compile_query(sql), segs)
    assert len(pallas_exec.pallas_kernels) == before


def test_packed_layout_roundtrip(setup):
    """Planar packing: unpacking word j%W slot (j//W)*B recovers dictIds."""
    _, segs = setup
    staged = StagingCache().stage(segs[0])
    for col in ("region", "city", "year"):
        pc = staged.packed_column(col)
        assert pc is not None
        bits, K = pc.bits, pc.vals_per_word
        assert bits == pack_bits(
            max(1, (segs[0].metadata.column(col).cardinality - 1).bit_length()))
        words = np.asarray(pc.words)               # [tiles, W]
        W = PALLAS_TILE // K
        got = np.zeros((words.shape[0], K, W), dtype=np.uint32)
        for k in range(K):
            got[:, k, :] = (words >> np.uint32(k * bits)) & ((1 << bits) - 1)
        fwd = np.asarray(segs[0].data_source(col).forward_index)
        flat = got.reshape(-1)[:fwd.shape[0]]
        np.testing.assert_array_equal(flat, fwd.astype(np.uint32))


# -- widened eligibility (round-4): scalar aggs, min/max, OR filters --------

WIDE_QUERIES = [
    "SELECT count(*), sum(qty) FROM pl_sales WHERE region = 'east'",
    "SELECT sum(price), avg(qty) FROM pl_sales "
    "WHERE year BETWEEN 2005 AND 2015",
    "SELECT min(price), max(price), minmaxrange(qty) FROM pl_sales "
    "WHERE region != 'west'",
    "SELECT region, min(qty), max(price) FROM pl_sales "
    "GROUP BY region ORDER BY region",
    "SELECT region, sum(qty) FROM pl_sales "
    "WHERE year = 2010 OR region = 'east' GROUP BY region ORDER BY region",
    "SELECT count(*) FROM pl_sales "
    "WHERE (region = 'east' OR region = 'west') AND year >= 2012",
]


def test_wide_plans_are_pallas_eligible(setup):
    from pinot_tpu.engine.pallas_kernels import extract_plan

    _, segs = setup
    for sql in WIDE_QUERIES:
        plan = plan_segment(compile_query(sql), segs[0])
        assert extract_plan(plan, segs[0]) is not None, sql


@pytest.mark.parametrize("sql", WIDE_QUERIES, ids=[q[:60] for q in WIDE_QUERIES])
def test_wide_pallas_matches_host(setup, pallas_exec, host_exec, sql):
    _, segs = setup
    got, _ = pallas_exec.execute(compile_query(sql), segs)
    want, _ = host_exec.execute(compile_query(sql), segs)
    assert len(got.rows) == len(want.rows)
    for gr, wr in zip(got.rows, want.rows):
        for g, w in zip(gr, wr):
            if isinstance(w, float):
                assert g == pytest.approx(w, rel=1e-5, abs=1e-6), (sql, gr, wr)
            else:
                assert g == w, (sql, gr, wr)


# -- round-5 eligibility: expression agg values + limb-exact big-int sums ---

@pytest.fixture(scope="module")
def big_setup(tmp_path_factory):
    """SSB-shaped values: products and sums far beyond the old kernel's
    f32-per-tile and provider-wide-i32 exactness bounds."""
    out = tmp_path_factory.mktemp("pallas_big")
    rng = np.random.default_rng(23)
    n = N
    schema = Schema("pl_big", [
        FieldSpec("k", DataType.STRING),
        FieldSpec("price", DataType.INT, FieldType.METRIC),
        FieldSpec("disc", DataType.INT, FieldType.METRIC),
        FieldSpec("rev", DataType.LONG, FieldType.METRIC),
    ])
    frame = {
        "k": np.array(["a", "b", "c"])[rng.integers(0, 3, n)],
        "price": rng.integers(905, 5_550_000, n).astype(np.int64),
        "disc": rng.integers(0, 11, n).astype(np.int64),
        "rev": rng.integers(0, 5_500_000, n).astype(np.int64),
    }
    segs = []
    for i, sl in enumerate([slice(0, n // 2), slice(n // 2, n)]):
        b = SegmentBuilder(schema, f"pl_big_{i}")
        b.build({c: v[sl] for c, v in frame.items()}, str(out))
        segs.append(load_segment(str(out / f"pl_big_{i}")))
    return frame, segs


BIG_QUERIES = [
    # all three SSB Q1 flights are sum(extendedprice * discount) shapes
    "SELECT sum(price * disc) FROM pl_big WHERE disc BETWEEN 1 AND 3",
    # literal operands bake into the kernel spec as constants
    "SELECT sum(disc * 1000), max(rev) FROM pl_big WHERE disc > 2",
    "SELECT k, sum(fromEpochSeconds(disc)) FROM pl_big GROUP BY k "
    "ORDER BY k",
    "SELECT sum(rev) FROM pl_big",                       # > i32 total
    "SELECT k, sum(rev), count(*) FROM pl_big GROUP BY k ORDER BY k",
    "SELECT k, sum(price * disc), avg(rev) FROM pl_big "
    "GROUP BY k ORDER BY k",
    "SELECT sum(rev - price) FROM pl_big WHERE disc > 5",  # Q4 shape
]


def test_big_value_plans_are_pallas_eligible(big_setup):
    from pinot_tpu.engine.pallas_kernels import extract_plan

    _, segs = big_setup
    for sql in BIG_QUERIES:
        plan = plan_segment(compile_query(sql), segs[0])
        assert extract_plan(plan, segs[0]) is not None, sql


@pytest.mark.parametrize("sql", BIG_QUERIES, ids=[q[:60] for q in BIG_QUERIES])
def test_big_value_sums_exact(big_setup, pallas_exec, host_exec, sql):
    """Limb-split accumulation must be EXACT (integer equality), not
    approximately right: the host engine computes in f64/int64."""
    _, segs = big_setup
    got, _ = pallas_exec.execute(compile_query(sql), segs)
    want, _ = host_exec.execute(compile_query(sql), segs)
    assert len(got.rows) == len(want.rows)
    for gr, wr in zip(got.rows, want.rows):
        for g, w in zip(gr, wr):
            if isinstance(w, float):
                assert g == pytest.approx(w, rel=1e-12), (sql, gr, wr)
            else:
                assert g == w, (sql, gr, wr)


def test_product_sum_matches_numpy_exactly(big_setup, pallas_exec):
    frame, segs = big_setup
    m = (frame["disc"] >= 1) & (frame["disc"] <= 3)
    exact = int((frame["price"][m] * frame["disc"][m]).sum())
    got, _ = pallas_exec.execute(compile_query(BIG_QUERIES[0]), segs)
    assert float(got.rows[0][0]) == float(exact)


# -- sharded fused-pallas combine (the serving path) ------------------------

@pytest.fixture(scope="module", params=[1, 2], ids=["doc1", "doc2"])
def sharded_pallas_exec(request):
    from pinot_tpu.parallel import ShardedQueryExecutor, make_combine_mesh

    mesh = make_combine_mesh(doc_shards=request.param)
    return ShardedQueryExecutor(mesh=mesh, use_pallas=True)


@pytest.mark.parametrize("sql", QUERIES + WIDE_QUERIES,
                         ids=[q[:60] for q in QUERIES + WIDE_QUERIES])
def test_sharded_pallas_matches_host(setup, sharded_pallas_exec, host_exec,
                                     sql):
    _, segs = setup
    got, stats = sharded_pallas_exec.execute(compile_query(sql), segs)
    want, _ = host_exec.execute(compile_query(sql), segs)
    assert len(got.rows) == len(want.rows)
    for gr, wr in zip(got.rows, want.rows):
        for g, w in zip(gr, wr):
            if isinstance(w, float):
                assert g == pytest.approx(w, rel=1e-5, abs=1e-6), (sql, gr, wr)
            else:
                assert g == w, (sql, gr, wr)
    assert stats.num_segments_processed == len(segs)


def test_sharded_pallas_kernel_actually_used(setup, sharded_pallas_exec):
    """The serving path must run the fused kernel, not the jnp fallback."""
    _, segs = setup
    sharded_pallas_exec.execute(compile_query(QUERIES[1]), segs)
    assert len(sharded_pallas_exec._pallas_sharded) >= 1


def test_lowering_failure_blocks_only_that_shape(setup, host_exec,
                                                 monkeypatch):
    """A Mosaic/compile failure must blocklist the failing QUERY SHAPE,
    not disable pallas process-wide (one unlowerable shape on the chip
    must not cost every other query its fused kernel)."""
    from pinot_tpu.engine import pallas_kernels as pk

    _, segs = setup
    ex = ServerQueryExecutor(use_device=True, use_pallas=True)
    bad_sql = QUERIES[0]
    good_sql = QUERIES[1]
    bad_spec = {}

    real = pk.run_segment

    def flaky(plan, staged, cache, interpret, **kw):
        if not bad_spec:
            bad_spec["spec"] = plan.spec
        if plan.spec == bad_spec["spec"]:
            raise RuntimeError("simulated Mosaic lowering failure")
        return real(plan, staged, cache, interpret, **kw)

    monkeypatch.setattr(pk, "run_segment", flaky)
    got, _ = ex.execute(compile_query(bad_sql), segs)     # falls back
    want, _ = host_exec.execute(compile_query(bad_sql), segs)
    assert got.rows == want.rows
    assert ex.use_pallas is not False                      # NOT global
    assert len(ex._pallas_blocked) == 1
    before = len(ex.pallas_kernels)
    ex.execute(compile_query(good_sql), segs)              # still fused
    assert len(ex.pallas_kernels) > before


def test_sharded_lowering_failure_blocks_only_that_shape(setup, host_exec,
                                                         monkeypatch):
    """Same per-shape containment on the SHARDED combine: the failing
    spec's compiled kernel is evicted, the shape falls back to the jnp
    combine with correct results, and other shapes keep the fused path."""
    from pinot_tpu.parallel import ShardedQueryExecutor, combine

    _, segs = setup
    ex = ShardedQueryExecutor(use_pallas=True)
    bad_sql, good_sql = QUERIES[0], QUERIES[1]

    real = combine.build_sharded_pallas_kernel

    def poisoned(spec, plan_spec, mesh):
        kernel = real(spec, plan_spec, mesh)
        state = {"first": True}

        def run(*args, **kw):
            if state["first"]:
                state["first"] = False
                raise RuntimeError("simulated Mosaic lowering failure")
            return kernel(*args, **kw)

        return run

    monkeypatch.setattr(combine, "build_sharded_pallas_kernel", poisoned)
    got, _ = ex.execute(compile_query(bad_sql), segs)      # jnp fallback
    want, _ = host_exec.execute(compile_query(bad_sql), segs)
    assert got.rows == want.rows
    assert ex.use_pallas is not False
    assert len(ex._pallas_blocked) == 1
    assert not ex._pallas_sharded                           # evicted
    monkeypatch.setattr(combine, "build_sharded_pallas_kernel", real)
    ex.execute(compile_query(good_sql), segs)               # still fused
    assert len(ex._pallas_sharded) == 1
    # the blocked shape stays on jnp even though pallas works again
    got2, _ = ex.execute(compile_query(bad_sql), segs)
    assert got2.rows == want.rows
    assert len(ex._pallas_sharded) == 1


# --------------------------------------------------------------------------
# build_kernel against a numpy oracle: the two-level accumulate at every
# shape of the group axis (H = G / 128 = 1, 2, 3, 32, 64)
# --------------------------------------------------------------------------

_KEY_OFFSET = 50      # the narrowed range's base: masked docs fall below it
_SEG_DOCS = (2 * PALLAS_TILE - 1000, PALLAS_TILE + 17)   # partial last tiles


def _pack_planar(ids, bits):
    """[S, TPS, T] dictIds -> [S, TPS, W/128, 128] u32 planar words (value
    j of a tile in word j % W at bit slot (j // W) * bits)."""
    S, TPS, T = ids.shape
    K = 32 // bits
    W = T // K
    planes = ids.reshape(S, TPS, K, W).astype(np.uint32)
    words = np.zeros((S, TPS, W), dtype=np.uint32)
    for k in range(K):
        words |= planes[:, :, k, :] << np.uint32(k * bits)
    return words.reshape(S, TPS, W // 128, 128)


def _edge_keys(G):
    """Narrowed keys at the one-hot's edges: a chunk's first and last
    lanes (0, 1, 126, 127, and the last chunk's), the last real group;
    masked ones just outside: negative (down to dictId 0), the pad groups
    [Gn, G), G and past it, and hi in the pad rows [H, Hp) and at Hp."""
    from pinot_tpu.engine.pallas_kernels import accumulate_rows

    H, Hp, _ = accumulate_rows(G)
    Gn = G - 7
    top = 128 * (H - 1)
    real = {0, 1, 126, 127, top, top + 1, Gn - 2, Gn - 1}
    masked = {-_KEY_OFFSET, 1 - _KEY_OFFSET, -2, -1, Gn, G - 1, G, G + 1,
              G + 126, G + 127, 128 * Hp - 1, 128 * Hp}
    return np.asarray(sorted(real | masked))


def _accumulate_case(G, case, keys="random"):
    """(spec, params, cols, oracle rows) of one exactness case: a 16-bit
    group column whose dictIds overshoot the narrowed range [50, 50 + Gn)
    on both sides (the filter masks those docs; their keys are negative or
    >= G), an 8-bit filter column, two segments with partial last tiles.
    ``keys``: ``random`` draws them over the range and past it, ``edges``
    from ``_edge_keys``."""
    from pinot_tpu.engine.pallas_kernels import PallasSpec

    S, TPS, T = 2, 2, PALLAS_TILE
    Gn = G - 7                      # real groups: the pad is not empty
    rng = np.random.default_rng(G * 31 + len(case))
    if keys == "random":
        gid = rng.integers(0, G + 100, (S, TPS, T))
    else:
        gid = _KEY_OFFSET + rng.choice(_edge_keys(G), (S, TPS, T))
    fid = rng.integers(0, 256, (S, TPS, T))
    doc = np.arange(TPS * T).reshape(TPS, T)
    valid = np.stack([doc < n for n in _SEG_DOCS])
    mask = (valid & (gid >= _KEY_OFFSET) & (gid <= _KEY_OFFSET + Gn - 1)
            & (fid <= 199))
    kept_keys = (gid - _KEY_OFFSET)[mask]
    assert (gid - _KEY_OFFSET)[~mask].min() < 0 \
        and (gid - _KEY_OFFSET)[~mask].max() >= G

    def by_group(v):
        out = np.zeros(G, dtype=v.dtype)
        np.add.at(out, kept_keys, v[mask])
        return out

    if case == "int":
        # count + an int sum whose values take the signed top limb of 3
        aggs = (("count", None, None), ("sum", ("v", 0), 3))
        v = rng.integers(-2_000_000_000, 2_000_000_000, (S, TPS, T))
        values, is_int, limbs = [v.astype(np.int32)], (True,), (0,)
        want = {"count": by_group(np.ones_like(v)), "isum": by_group(v)}
    elif case == "float":
        aggs = (("sum", ("v", 0), None),)
        v = rng.uniform(1.0, 1.0e4, (S, TPS, T)).astype(np.float32)
        values, is_int, limbs = [v], (False,), (0,)
        want = {"count": by_group(np.ones(v.shape, dtype=np.int64)),
                "fsum": by_group(v.astype(np.float64))}
    else:                           # an i64 column as 4 pre-split planes
        aggs = (("sum", ("v64", 0), 4),)
        v = rng.integers(-(1 << 46), 1 << 46, (S, TPS, T))
        values = [((v >> (12 * k)) & 0xFFF if k < 3 else v >> 36)
                  .astype(np.int32) for k in range(4)]
        is_int, limbs = (True,), (4,)
        want = {"count": by_group(np.ones_like(v)), "isum": by_group(v)}

    spec = PallasSpec(
        num_segs=S, tiles_per_seg=TPS, packed_bits=(16, 8),
        filter_tree=("and", (("iv", 0, 0), ("iv", 1, 1))), n_slots=2,
        group_idx=(0,), group_strides=(1,), group_key_offset=_KEY_OFFSET,
        num_groups_padded=G, aggs=aggs, value_is_int=is_int,
        value_limbs=limbs, interpret=True)
    params = np.asarray([_KEY_OFFSET, _KEY_OFFSET + Gn - 1, 0, 199,
                         *_SEG_DOCS, 0], dtype=np.int32)
    cols = [_pack_planar(gid, 16), _pack_planar(fid, 8)] + [
        x.reshape(S, TPS, T // 128, 128) for x in values]
    want["seg"] = mask.reshape(S, -1).sum(axis=1)
    return spec, params, cols, want


@pytest.mark.parametrize("case", ["int", "float", "v64"])
@pytest.mark.parametrize("G", [128, 256, 384, 4096, 8192])
def test_build_kernel_accumulate_is_exact(G, case):
    """Counts and int sums equal the oracle bit for bit at every group
    shape (single chunk, two-level with a padded hi axis, two-level at the
    Q2.1 shape and at MAX_PALLAS_GROUPS); float sums within the file's
    tolerance. Masked docs whose narrowed key is negative or >= G match
    nothing; the carry chain runs across tiles and segments."""
    _check_accumulate(G, case, "random")


@pytest.mark.parametrize("case", ["int", "float", "v64"])
@pytest.mark.parametrize("G", [128, 384, 8192])
def test_one_hot_is_exact_at_the_build_edges(G, case):
    """The one-hot holds two groups a 32-bit word (an even key in its low
    half, an odd one in its high half): counts, int-sum limbs and float
    sums stay exact with every doc on a chunk's edge lanes (0, 1, 126,
    127) or a masked doc just outside the range (negative keys, the pad
    groups, ``hi`` in the pad rows [H, Hp) and at Hp)."""
    _check_accumulate(G, case, "edges")


def _check_accumulate(G, case, keys):
    import jax

    from pinot_tpu.engine.pallas_kernels import _row_layout, build_kernel

    spec, params, cols, want = _accumulate_case(G, case, keys)
    out_f, out_i, _mm, out_seg = jax.jit(build_kernel(spec))(params, *cols)
    out_f, out_i = np.asarray(out_f), np.asarray(out_i)
    fsum_row, isum_row, _, Mf, Mi, _ = _row_layout(spec)
    assert out_f.shape == (Mf, G) and out_i.shape == (Mi, G)
    np.testing.assert_array_equal(out_i[0], want["count"])
    np.testing.assert_array_equal(np.asarray(out_seg).sum(axis=1),
                                  want["seg"])
    assert want["count"][G - 7:].sum() == 0 and want["count"].sum() > 0
    if keys == "edges":
        assert np.count_nonzero(want["count"]) == len(
            set(_edge_keys(G)) & set(range(G - 7)))
    for _vexpr, (start, L) in isum_row.items():
        rows = out_i[start:start + L + 2].astype(np.int64)
        # normalized carry chain: every limb row back inside 12 bits
        assert rows[:L + 1].min() >= 0 and rows[:L + 1].max() < (1 << 12)
        got = sum(int(1 << (12 * k)) * rows[k] for k in range(L + 2))
        np.testing.assert_array_equal(got, want["isum"])
    for _vexpr, r in fsum_row.items():
        got = out_f[r].astype(np.float64) + out_f[r + 1].astype(np.float64)
        np.testing.assert_allclose(got, want["fsum"], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("top", [4095, -4096])
@pytest.mark.parametrize("column", ["int", "v64"])
@pytest.mark.parametrize("G", [128, 8192])
@pytest.mark.parametrize("group", ["first", "last"])
def test_bf16_pass_is_exact_at_the_limb_limits(group, G, column, top):
    """A full tile in ONE group (the first or the last: its one-hot
    entry sits in the low or the high half of a word, on the first or the
    last sublane, and at 8192 groups its hi row is the first or the
    last), every doc carrying 4095
    in each limb below the top and ``top`` in the signed top limb: each
    limb half's partial is the largest a tile makes (255 * T, and -16 * T
    or 15 * T), so one bf16 pass must still give numpy's int64 sum to the
    unit and the exact count. ``int`` splits a 2-limb i32 value in the
    kernel; ``v64`` ships four pre-split planes (an i64 column)."""
    import jax

    from pinot_tpu.engine.pallas_kernels import (
        PallasSpec,
        _row_layout,
        build_kernel,
    )

    T = PALLAS_TILE
    L = 2 if column == "int" else 4
    limbs = [4095] * (L - 1) + [top]
    v = sum(limb << (12 * k) for k, limb in enumerate(limbs))
    if column == "int":
        values, vexpr = [np.full((1, 1, T // 128, 128), v, np.int32)], "v"
    else:
        values, vexpr = [np.full((1, 1, T // 128, 128), limb, np.int32)
                         for limb in limbs], "v64"
    key = 0 if group == "first" else G - 1
    spec = PallasSpec(
        num_segs=1, tiles_per_seg=1, packed_bits=(16,),
        filter_tree=("true",), n_slots=0, group_idx=(0,),
        group_strides=(1,), group_key_offset=0, num_groups_padded=G,
        aggs=(("count", None, None), ("sum", (vexpr, 0), L)),
        value_is_int=(True,), value_limbs=(0 if column == "int" else L,),
        interpret=True)
    params = np.asarray([T, 0], dtype=np.int32)
    cols = [_pack_planar(np.full((1, 1, T), key), 16)] + values
    _f, out_i, _mm, out_seg = jax.jit(build_kernel(spec))(params, *cols)
    out_i = np.asarray(out_i).astype(np.int64)
    _, isum_row, _, _, _, _ = _row_layout(spec)
    (start, _L), = isum_row.values()
    want_count = np.zeros(G, np.int64)
    want_count[key] = T
    np.testing.assert_array_equal(out_i[0], want_count)
    assert int(np.asarray(out_seg).sum()) == T
    got = sum(out_i[start + k] << (12 * k) for k in range(L + 2))
    want = np.zeros(G, np.int64)
    want[key] = np.int64(T) * np.int64(v)
    np.testing.assert_array_equal(got, want)


def _counter_spec(aggs, value_is_int=(), groups=0):
    from pinot_tpu.engine.pallas_kernels import PallasSpec

    return PallasSpec(
        num_segs=1, tiles_per_seg=1, packed_bits=(8,) if groups else (),
        filter_tree=("true",), n_slots=0,
        group_idx=(0,) if groups else (), group_strides=(1,) if groups
        else (), group_key_offset=0,
        num_groups_padded=-(-max(groups, 1) // 128) * 128, aggs=aggs,
        value_is_int=value_is_int)


def test_pallas_launch_counter_loses_no_update_under_threads():
    """``/debug/pallas`` ``launches`` and ``mxu`` are bumped from every
    query thread: more threads than cores with a short switch interval
    must lose none."""
    import os
    import sys
    import threading

    ex = ServerQueryExecutor(use_device=False)
    scalar = _counter_spec((("count", None, None),))
    summed = _counter_spec((("sum", ("v", 0), None),), (False,))
    grouped = _counter_spec((("count", None, None),), groups=4000)
    n_threads, each = 2 * (os.cpu_count() or 4), 500

    def work():
        for _ in range(each):
            ex._note_pallas_launch(scalar)
            ex._note_pallas_launch(summed)
            ex._note_pallas_launch(grouped)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert ex.pallas_launches() == {"single": n_threads * each,
                                    "two_level": n_threads * each,
                                    "scalar": n_threads * each}
    assert ex.pallas_mxu() == {"bf16": n_threads * each,
                               "fp32": n_threads * each}


# -- a scalar key space: no one-hot, min/max reduced over the tile ---------

# the group-range probe of two SSB flights over synthetic columns whose
# dictionaries nest as SSB's do (a city id is nation * 10 + r, a brand id
# category * 40 + r): (bits, cardinality, parent column or None) per packed
# column, the filter's (column, lo, hi) intervals, the group columns
_PROBE_SHAPES = {
    "Q3.2": dict(cols=[(8, 25, None), (8, 25, None), (4, 7, None),
                       (8, 250, 0), (8, 250, 1)],
                 filter=[(0, 24, 24), (1, 24, 24), (2, 0, 5)],
                 groups=(3, 4, 2)),
    "Q4.3": dict(cols=[(8, 25, None), (4, 7, None), (8, 25, None),
                       (8, 250, 0), (16, 1000, 2)],
                 filter=[(0, 24, 24), (1, 5, 6), (2, 3, 3)],
                 groups=(1, 3, 4)),
    # no nation 30: nothing matches, every range collapses to (0, 0)
    "nothing_matches": dict(cols=[(8, 25, None), (8, 25, None),
                                  (4, 7, None), (8, 250, 0), (8, 250, 1)],
                            filter=[(0, 30, 30), (1, 24, 24), (2, 0, 5)],
                            groups=(3, 4, 2)),
}
_PROBE_SEG_DOCS = _SEG_DOCS + (2 * PALLAS_TILE,)


def _check_probe_case(shape):
    """The probe kernel over three segments (two with a partial last tile
    whose invalid docs match the filter and widen every group range):
    its ranges are numpy's min/max of the group dictIds under the filter,
    its count and per-segment counts the filter's."""
    import jax

    from pinot_tpu.engine.pallas_kernels import (
        PallasSpec,
        build_kernel,
        decode_probe_ranges,
    )

    case = _PROBE_SHAPES[shape]
    S, TPS, T = len(_PROBE_SEG_DOCS), 2, PALLAS_TILE
    rng = np.random.default_rng(len(shape) * 7 + 3)
    doc = np.arange(TPS * T).reshape(TPS, T)
    valid = np.stack([doc < n for n in _PROBE_SEG_DOCS])
    ids = []
    for _bits, card, parent in case["cols"]:
        if parent is None:
            ids.append(rng.integers(0, card, (S, TPS, T)))
        else:
            fan = card // case["cols"][parent][1]
            ids.append(ids[parent] * fan + rng.integers(0, fan, (S, TPS, T)))
    mask = valid.copy()
    params = []
    for ci, lo, hi in case["filter"]:
        # the invalid tail matches the filter with ids that span its
        # group columns' whole dictionaries
        ids[ci] = np.where(valid, ids[ci], lo)
        mask &= (ids[ci] >= lo) & (ids[ci] <= hi)
        params += [lo, hi]
    for gi in case["groups"]:
        card = case["cols"][gi][1]
        ids[gi] = np.where(valid, ids[gi],
                           rng.integers(0, card, ids[gi].shape))
    aggs = []
    for gi in case["groups"]:
        aggs += [("min", ("id", gi), None), ("max", ("id", gi), None)]
    spec = PallasSpec(
        num_segs=S, tiles_per_seg=TPS,
        packed_bits=tuple(b for b, _c, _p in case["cols"]),
        filter_tree=("and", tuple(("iv", ci, k) for k, (ci, _l, _h)
                                  in enumerate(case["filter"]))),
        n_slots=len(case["filter"]), group_idx=(), group_strides=(),
        group_key_offset=0, num_groups_padded=128, aggs=tuple(aggs),
        value_is_int=(), interpret=True)
    params = np.asarray(params + list(_PROBE_SEG_DOCS) + [0],
                        dtype=np.int32)
    cols = [_pack_planar(x, b) for x, (b, _c, _p) in zip(ids, case["cols"])]
    _f, out_i, out_mm, out_seg = jax.jit(build_kernel(spec))(params, *cols)
    want = [(int(ids[gi][mask].min()), int(ids[gi][mask].max()))
            if mask.any() else (0, 0) for gi in case["groups"]]
    assert decode_probe_ranges(spec, out_mm, len(want)) == want
    assert np.asarray(out_i).shape == (1, 1)
    assert int(np.asarray(out_i)[0, 0]) == int(mask.sum())
    np.testing.assert_array_equal(np.asarray(out_seg).sum(axis=1),
                                  mask.reshape(S, -1).sum(axis=1))
    if shape != "nothing_matches":
        # the tail would have widened the ranges had it counted
        assert mask.sum() > 0 and any(
            ids[gi][~valid].min() < lo or ids[gi][~valid].max() > hi
            for gi, (lo, hi) in zip(case["groups"], want))


# scalar MIN / MAX / COUNT with and without a SUM beside them: the
# accumulate each takes (no sum: no one-hot at all)
_SCALAR_SQL = {
    "min_max_count": (
        "SELECT min(qty), max(year), count(*) FROM pl_sales "
        "WHERE region = 'east'", "scalar"),
    "minmaxrange_min_price": (
        "SELECT minmaxrange(year), min(price), max(price) FROM pl_sales "
        "WHERE city BETWEEN 'c010' AND 'c040'", "scalar"),
    "min_max_count_sum": (
        "SELECT min(price), max(qty), count(*), sum(qty) FROM pl_sales "
        "WHERE year >= 2010", "single"),
    "min_avg": (
        "SELECT min(year), avg(price) FROM pl_sales "
        "WHERE region != 'west' AND year < 2020", "single"),
}


@pytest.fixture(scope="module")
def scalar_sharded_exec():
    from pinot_tpu.parallel import ShardedQueryExecutor

    return ShardedQueryExecutor(use_pallas=True)


def _check_scalar_sql(name, setup, host_exec, sharded):
    """Per segment and sharded, the fused kernel serves the query with the
    accumulate it should take and answers as the host engine does."""
    _, segs = setup
    sql, kind = _SCALAR_SQL[name]
    want, _ = host_exec.execute(compile_query(sql), segs)
    per_seg = ServerQueryExecutor(use_device=True, use_pallas=True)
    for ex in (per_seg, sharded):
        before = ex.pallas_launches()
        got, _ = ex.execute(compile_query(sql), segs)
        after = ex.pallas_launches()
        assert {k: after[k] - before[k] for k in after if after[k] > before[k]
                } == {kind: (len(segs) if ex is per_seg else 1)}, (name, ex)
        assert len(got.rows) == len(want.rows) == 1
        for g, w in zip(got.rows[0], want.rows[0]):
            assert g == pytest.approx(w, rel=1e-6), (name, got.rows, want.rows)


@pytest.mark.parametrize("case", [f"probe_{k}" for k in _PROBE_SHAPES]
                         + [f"sql_{k}" for k in _SCALAR_SQL])
def test_scalar_key_space_answers(case, setup, host_exec,
                                  scalar_sharded_exec):
    """A scalar key space reduces min/max over the tile and counts by the
    mask sum: the group-range probe's ranges, and scalar MIN / MAX /
    COUNT queries beside a SUM or not, answer as before."""
    kind, name = case.split("_", 1)
    if kind == "probe":
        _check_probe_case(name)
    else:
        _check_scalar_sql(name, setup, host_exec, scalar_sharded_exec)


@pytest.mark.parametrize("path", ["per_segment", "sharded"])
@pytest.mark.parametrize("agg, mxu", [("sum(qty)", "bf16"),
                                      ("sum(price)", "fp32")])
def test_mxu_counter_and_span_say_which_contraction(setup, host_exec,
                                                    scalar_sharded_exec,
                                                    path, agg, mxu):
    """An integer sum takes the one bf16 pass and a float sum an fp32
    contraction besides: ``/debug/pallas`` ``mxu`` counts each launch
    under that name, the ``Kernel`` / ``ShardedCombine`` span carries it,
    and the answer is the host engine's."""
    from pinot_tpu.common.tracing import flatten_spans

    _, segs = setup
    sql = (f"SELECT region, {agg} FROM pl_sales GROUP BY region "
           "ORDER BY region OPTION(trace=true)")
    if path == "sharded":
        ex, scans, n = scalar_sharded_exec, "ShardedCombine", 1
    else:
        ex = ServerQueryExecutor(use_device=True, use_pallas=True)
        scans, n = "Kernel", len(segs)
    before = ex.pallas_mxu()
    got, stats = ex.execute(compile_query(sql), segs)
    after = ex.pallas_mxu()
    assert {k: after[k] - before[k] for k in after} == {
        "bf16": 0, "fp32": 0, mxu: n}
    took = [e.get("mxu") for e in flatten_spans(stats.spans)
            if e["operator"] == scans and e.get("kernel") == "pallas"]
    assert took == [mxu] * n
    want, _ = host_exec.execute(compile_query(sql), segs)
    assert len(got.rows) == len(want.rows)
    for g, w in zip(got.rows, want.rows):
        # an integer sum is exact; a float sum within the file's tolerance
        assert g[0] == w[0] and g[1] == pytest.approx(
            w[1], rel=0 if mxu == "bf16" else 1e-6)
