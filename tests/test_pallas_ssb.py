"""SSB-13 fused-kernel eligibility + interpret-mode parity (tier-1).

The acceptance suite for the zero-decline pallas SSB goal: every one of
the 13 SSB flights must extract an eligible pallas plan (Q3.2/Q4.3 via the
group-range probe narrowing), run the fused kernel in interpret mode on
CPU, and match the jnp kernel bit-for-bit — packed f64 vector equality
where the layouts coincide, exact decoded-group equality for the
probe-narrowed shapes whose packed layout is the narrowed dense one.
Fixtures deliberately use a REMAINDER-TILE capacity (padded_capacity not a
multiple of PALLAS_TILE) and an i64-staged value column, the two shapes the
widened eligibility must cover.
"""

import numpy as np
import pytest

from pinot_tpu.engine import ensure_x64

ensure_x64()

from pinot_tpu.common.tracing import LEDGER, parse_decision_key
from pinot_tpu.engine import ServerQueryExecutor
from pinot_tpu.engine.kernels import build_kernel, unpack_outputs
from pinot_tpu.engine.pallas_kernels import (
    MAX_PALLAS_GROUPS,
    extract_plan,
    run_segment,
)
from pinot_tpu.engine.plan import plan_segment
from pinot_tpu.engine.staging import PALLAS_TILE, StagingCache
from pinot_tpu.query import compile_query
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.tools import ssb

pytestmark = pytest.mark.pallas

# 2 segments x 9000 rows -> padded_capacity 9216 (x1024), which is NOT a
# multiple of PALLAS_TILE (4096): every kernel in this suite carries a
# masked remainder tile
ROWS = 18_000

# the two flights whose composed key space exceeds MAX_PALLAS_GROUPS until
# the group-range probe narrows it
NARROWED = ("Q3.2", "Q4.3")


@pytest.fixture(scope="module")
def ssb_segs(tmp_path_factory):
    out = tmp_path_factory.mktemp("pallas_ssb")
    return ssb.build_segments(0, str(out), num_segments=2, rows=ROWS)


@pytest.fixture(scope="module")
def ctxs():
    # explicit LIMIT: full group sets
    return {qid: compile_query(q + " LIMIT 100000")
            for qid, q in ssb.QUERIES.items()}


@pytest.fixture(scope="module")
def pallas_cache():
    from pinot_tpu.engine.pallas_kernels import PallasKernelCache

    return PallasKernelCache()


def test_fixture_has_remainder_tiles(ssb_segs):
    assert ssb_segs[0].padded_capacity % PALLAS_TILE != 0


def test_all_13_extract_eligible(ssb_segs, ctxs):
    """Every SSB flight extracts an eligible plan at the extract level —
    directly for 11, and for Q3.2/Q4.3 the ONLY obstacle is the group
    bound the probe removes."""
    for qid, ctx in ctxs.items():
        reasons = []
        plan = plan_segment(ctx, ssb_segs[0])
        pp = extract_plan(plan, ssb_segs[0], on_decline=reasons.append)
        if qid in NARROWED:
            assert pp is None and reasons == ["pallas_too_many_groups"], \
                (qid, reasons)
            # the probe path's precondition: the unchecked extraction
            # (filter/values/aggs) is fully eligible
            assert extract_plan(plan, ssb_segs[0],
                                unchecked_groups=True) is not None, qid
        else:
            assert pp is not None, (qid, reasons)


def test_all_13_run_segment_zero_declines(ssb_segs, ctxs, pallas_cache):
    """run_segment serves every flight (probe narrowing included) without
    a single decline."""
    staged = StagingCache().stage(ssb_segs[0])
    for qid, ctx in ctxs.items():
        reasons = []
        plan = plan_segment(ctx, ssb_segs[0])
        served = run_segment(plan, staged, pallas_cache, interpret=True,
                             on_decline=reasons.append)
        assert served is not None and not reasons, (qid, reasons)
        packed, eff = served
        if qid in NARROWED:
            assert eff is not plan
            assert eff.num_groups <= MAX_PALLAS_GROUPS
            assert getattr(eff, "_narrowed_from") == plan.spec
        else:
            assert eff is plan


@pytest.mark.parametrize("qid", sorted(ssb.QUERIES))
def test_ssb13_bit_parity_vs_jnp(ssb_segs, ctxs, pallas_cache, qid):
    """Per segment: the fused kernel's PACKED output is bit-identical to
    the jnp kernel's (same f64 vector where the spec coincides; exact
    decoded-group equality for the probe-narrowed shapes, whose packed
    layout is the narrowed dense one while jnp's is the sparse compact)."""
    from pinot_tpu.engine.executor import decode_grouped_result

    ctx = ctxs[qid]
    for seg in ssb_segs:
        plan = plan_segment(ctx, seg)
        staged = StagingCache().stage(seg)
        served = run_segment(plan, staged, pallas_cache, interpret=True)
        assert served is not None, qid
        packed_pl, eff = served

        cols = {name: staged.column(name).tree() for name in plan.columns}
        packed_jnp = np.asarray(build_kernel(plan.spec)(
            cols, tuple(plan.params), np.int32(seg.num_docs)))

        if eff is plan:
            np.testing.assert_array_equal(np.asarray(packed_pl),
                                          packed_jnp, err_msg=qid)
        else:
            got = decode_grouped_result(
                eff, seg, unpack_outputs(np.asarray(packed_pl), eff.spec))
            want = decode_grouped_result(
                plan, seg, unpack_outputs(packed_jnp, plan.spec))
            assert got.groups == want.groups, qid


@pytest.mark.parametrize("qid", sorted(q for q in ssb.QUERIES
                                 if q not in NARROWED))
def test_segment_launch_is_one_program(ssb_segs, ctxs, pallas_cache, qid):
    """A per-segment launch is ONE compiled program from the runtime
    params to the packed answer: once the segment's columns are staged
    (the first run decodes and pads them), a traced ``run_segment`` holds
    no device operation but the one jitted call (run eagerly, the params'
    concatenate, the blocks' reshapes, the limb reassembly and the packing
    cost a dispatch each, and a request's thread the interpreter lock
    again after each)."""
    import jax

    staged = StagingCache().stage(ssb_segs[0])
    plan = plan_segment(ctxs[qid], ssb_segs[0])
    assert run_segment(plan, staged, pallas_cache, interpret=True)
    jaxpr = jax.make_jaxpr(lambda: run_segment(
        plan, staged, pallas_cache, interpret=True)[0])()
    assert [e.primitive.name for e in jaxpr.eqns] == ["jit"], qid


def test_sharded_all_13_parity_and_zero_declines(ssb_segs, ctxs):
    """The serving path: every flight through the sharded executor with
    pallas on matches the host engine exactly, the decline histogram
    records ZERO pallas entries, and the fused kernels actually fired."""
    from pinot_tpu.parallel import ShardedQueryExecutor

    dev = ShardedQueryExecutor(use_pallas=True)
    host = ServerQueryExecutor(use_device=False)
    mark = LEDGER.snapshot()
    for qid in sorted(ssb.QUERIES):
        # useStarTree=false: Q2.x must exercise the pallas scan here, not
        # the pre-agg rung (the star-tree suite covers that path)
        sql = ssb.QUERIES[qid] + " LIMIT 100000 OPTION(useStarTree=false)"
        got, stats = dev.execute(compile_query(sql), ssb_segs)
        want, _ = host.execute(compile_query(sql), ssb_segs)
        assert sorted(map(tuple, got.rows)) == sorted(map(tuple, want.rows)), qid
    delta = LEDGER.delta(mark)
    pallas = {k: v for k, v in delta.items()
              if parse_decision_key(k)[0] == "pallas"}
    assert not pallas, pallas
    assert len(dev._pallas_sharded) > 0


@pytest.mark.parametrize("qid, accumulate", [
    ("Q1.1", "single"), ("Q2.1", "two_level")])
def test_sharded_span_and_counter_say_which_accumulate(ssb_segs, qid,
                                                       accumulate):
    """A forced scan through the sharded executor: the ``ShardedCombine``
    span carries the kernel's ``groups`` and the ``accumulate`` it took,
    ``GET /debug/pallas`` counts the launch under that name, and a query
    the jnp combine served counts under neither. (Q1.1 over every year:
    its own year leaves one segment, which the per-segment ladder takes.)"""
    from types import SimpleNamespace

    from pinot_tpu.common.tracing import flatten_spans
    from pinot_tpu.parallel import ShardedQueryExecutor
    from pinot_tpu.server.server import ServerInstance

    def launches(ex):
        return ServerInstance.pallas_debug(
            SimpleNamespace(executor=ex))["launches"]

    dev = ShardedQueryExecutor(use_pallas=True)
    assert launches(dev) == {"single": 0, "two_level": 0, "scalar": 0}
    sql = (ssb.QUERIES[qid].replace("d_year = 1993", "d_year >= 1992")
           + " LIMIT 100000 OPTION(useStarTree=false, trace=true)")
    _got, stats = dev.execute(compile_query(sql), ssb_segs)
    spans = {e["operator"]: e for e in flatten_spans(stats.spans)}
    combine = spans["ShardedCombine"]
    (spec, _plan_spec), = dev._pallas_sharded     # the one kernel it built
    assert (spec.num_groups_padded > 128) is (accumulate == "two_level")
    assert (combine["kernel"], combine["groups"], combine["accumulate"],
            combine["mxu"]) \
        == ("pallas", spec.num_groups_padded, accumulate, "bf16")
    want = {"single": 0, "two_level": 0, "scalar": 0, accumulate: 1}
    assert launches(dev) == want
    assert ServerInstance.pallas_debug(SimpleNamespace(executor=dev))[
        "mxu"] == {"bf16": 1, "fp32": 0}

    jnp_only = ShardedQueryExecutor(use_pallas=False)
    _got, stats = jnp_only.execute(compile_query(sql), ssb_segs)
    combine = {e["operator"]: e
               for e in flatten_spans(stats.spans)}["ShardedCombine"]
    assert combine["kernel"] == "jnp" and "accumulate" not in combine \
        and "mxu" not in combine
    assert launches(jnp_only) == {"single": 0, "two_level": 0,
                                  "scalar": 0}
    assert jnp_only.pallas_mxu() == {"bf16": 0, "fp32": 0}


def test_per_segment_kernel_span_says_which_accumulate(ssb_segs):
    """The per-segment ladder's Pallas ``Kernel`` span (what a sharded
    query pruned to one segment takes) carries the same two attributes,
    and its launch is counted."""
    from pinot_tpu.common.tracing import flatten_spans

    ex = ServerQueryExecutor(use_device=True, use_pallas=True)
    sql = (ssb.QUERIES["Q2.1"]
           + " LIMIT 100000 OPTION(useStarTree=false, trace=true)")
    _got, stats = ex.execute(compile_query(sql), ssb_segs[:1])
    kernels = [e for e in flatten_spans(stats.spans)
               if e["operator"] == "Kernel" and e.get("kernel") == "pallas"]
    assert kernels and all(
        (k["groups"], k["accumulate"], k["mxu"]) == (4096, "two_level",
                                                     "bf16")
        for k in kernels)
    assert ex.pallas_launches() == {"single": 0, "two_level": len(kernels),
                                    "scalar": 0}
    assert ex.pallas_mxu() == {"bf16": len(kernels), "fp32": 0}


@pytest.mark.parametrize("path", ["sharded", "per_segment"])
def test_group_range_probe_counts_as_scalar(ssb_segs, path):
    """Q3.2's group-range probe builds no one-hot: ``/debug/pallas``
    counts it under ``scalar``, beside the narrowed scan's own accumulate
    (sharded: one probe and one scan over the batch; per segment: one of
    each a segment)."""
    from pinot_tpu.common.tracing import flatten_spans
    from pinot_tpu.parallel import ShardedQueryExecutor

    sql = (ssb.QUERIES["Q3.2"]
           + " LIMIT 100000 OPTION(useStarTree=false, trace=true)")
    if path == "sharded":
        ex = ShardedQueryExecutor(use_pallas=True)
        segs, scans = ssb_segs, "ShardedCombine"
    else:
        ex = ServerQueryExecutor(use_device=True, use_pallas=True)
        segs, scans = ssb_segs[:1], "Kernel"
    _got, stats = ex.execute(compile_query(sql), segs)
    took = [e["accumulate"] for e in flatten_spans(stats.spans)
            if e["operator"] == scans and e.get("kernel") == "pallas"]
    assert len(took) == 1 and took[0] in ("single", "two_level")
    want = {"single": 0, "two_level": 0, "scalar": 1}
    want[took[0]] += 1
    assert ex.pallas_launches() == want
    # the probe builds no one-hot: only the narrowed scan takes the MXU
    assert ex.pallas_mxu() == {"bf16": 1, "fp32": 0}


def test_narrow_declines_when_probe_cannot_shrink(tmp_path):
    """Adversarial shape: unfiltered high-card group columns keep their
    full ranges under the probe, so the narrowed product still exceeds
    the bound — a CLASSIFIED decline, never a wrong result."""
    rng = np.random.default_rng(5)
    n = 6000
    vals = [f"v{i:04d}" for i in range(600)]
    schema = Schema("wide", [FieldSpec("a", DataType.STRING),
                             FieldSpec("b", DataType.STRING),
                             FieldSpec("qty", DataType.INT,
                                       FieldType.METRIC)])
    frame = {"a": np.array(vals)[rng.integers(0, 600, n)],
             "b": np.array(vals)[rng.integers(0, 600, n)],
             "qty": rng.integers(1, 50, n).astype(np.int64)}
    b = SegmentBuilder(schema, "wide_0")
    b.build(frame, str(tmp_path))
    seg = load_segment(str(tmp_path / "wide_0"))

    from pinot_tpu.engine.pallas_kernels import PallasKernelCache

    plan = plan_segment(compile_query(
        "SELECT a, b, sum(qty) FROM wide GROUP BY a, b LIMIT 400000"), seg)
    reasons = []
    served = run_segment(plan, StagingCache().stage(seg),
                         PallasKernelCache(), interpret=True,
                         on_decline=reasons.append)
    assert served is None
    assert reasons == ["pallas_too_many_groups"]


# -- i64-staged value columns (limb planes at the value-load layer) --------

@pytest.fixture(scope="module")
def i64_segs(tmp_path_factory):
    out = tmp_path_factory.mktemp("pallas_i64")
    rng = np.random.default_rng(9)
    n = 9_000   # 4500/segment -> remainder tile again
    schema = Schema("big64", [
        FieldSpec("k", DataType.STRING),
        FieldSpec("big", DataType.LONG, FieldType.METRIC),
        FieldSpec("qty", DataType.INT, FieldType.METRIC),
    ])
    frame = {
        "k": np.array(["a", "b", "c"])[rng.integers(0, 3, n)],
        # values far beyond i32 -> staged_int_dtype int64 -> limb planes
        "big": (rng.integers(0, 1 << 40, n) - (1 << 39)).astype(np.int64),
        "qty": rng.integers(1, 50, n).astype(np.int64),
    }
    segs = []
    for i, sl in enumerate([slice(0, n // 2), slice(n // 2, n)]):
        b = SegmentBuilder(schema, f"big64_{i}")
        b.build({c: v[sl] for c, v in frame.items()}, str(out))
        segs.append(load_segment(str(out / f"big64_{i}")))
    return frame, segs


I64_QUERIES = [
    "SELECT sum(big) FROM big64",
    "SELECT k, sum(big), count(*) FROM big64 GROUP BY k ORDER BY k",
    "SELECT sum(big), avg(big) FROM big64 WHERE qty > 25",
]


def test_i64_value_eligible_with_limb_planes(i64_segs):
    _, segs = i64_segs
    for sql in I64_QUERIES:
        plan = plan_segment(compile_query(sql), segs[0])
        reasons = []
        pp = extract_plan(plan, segs[0], on_decline=reasons.append)
        assert pp is not None, (sql, reasons)
        assert any(l > 0 for l in pp.value_limbs), sql


@pytest.mark.parametrize("sql", I64_QUERIES, ids=[q[:50] for q in I64_QUERIES])
def test_i64_value_sums_exact(i64_segs, sql):
    """Limb-plane accumulation is EXACT (integer equality vs the host
    engine's int64 math), per-segment and sharded."""
    from pinot_tpu.parallel import ShardedQueryExecutor

    _, segs = i64_segs
    dev = ServerQueryExecutor(use_device=True, use_pallas=True)
    sh = ShardedQueryExecutor(use_pallas=True)
    host = ServerQueryExecutor(use_device=False)
    want, _ = host.execute(compile_query(sql), segs)
    got, _ = dev.execute(compile_query(sql), segs)
    shg, _ = sh.execute(compile_query(sql), segs)
    assert got.rows == want.rows, sql
    assert shg.rows == want.rows, sql


def test_i64_sum_matches_numpy_exactly(i64_segs):
    frame, segs = i64_segs
    dev = ServerQueryExecutor(use_device=True, use_pallas=True)
    got, _ = dev.execute(compile_query("SELECT sum(big) FROM big64"), segs)
    assert float(got.rows[0][0]) == float(int(frame["big"].sum()))


# -- many-run LUT predicates (the interval-set fallback) -------------------

def test_lut_interval_set_fallback(ssb_segs):
    """An IN over many scattered cities exceeds the static leaf budget but
    rides the padded interval-set node — eligible, exact, and the
    over-cap decline stays classified."""
    cities = sorted({c for c in np.asarray(
        ssb_segs[0].data_source("c_city").dictionary.get_values(
            range(ssb_segs[0].metadata.column("c_city").cardinality)))})
    picks = cities[::7][:24]   # scattered -> ~24 runs
    vals = ", ".join(f"'{c}'" for c in picks)
    sql = (f"SELECT sum(lo_revenue), count(*) FROM ssb_lineorder "
           f"WHERE c_city IN ({vals})")
    plan = plan_segment(compile_query(sql), ssb_segs[0])
    reasons = []
    pp = extract_plan(plan, ssb_segs[0], on_decline=reasons.append)
    assert pp is not None and not reasons
    assert any(node == "ivs" for node in _flatten_ops(pp.filter_tree))

    dev = ServerQueryExecutor(use_device=True, use_pallas=True)
    host = ServerQueryExecutor(use_device=False)
    got, _ = dev.execute(compile_query(sql), ssb_segs)
    want, _ = host.execute(compile_query(sql), ssb_segs)
    assert got.rows == want.rows

    # over the configured cap: a CLASSIFIED decline
    reasons = []
    pp = extract_plan(plan, ssb_segs[0], on_decline=reasons.append,
                      lut_run_cap=4)
    assert pp is None and reasons == ["pallas_lut_too_many_runs"]


def _flatten_ops(tree):
    out = [tree[0]]
    if tree[0] in ("and", "or", "not"):
        for c in tree[1]:
            out.extend(_flatten_ops(c))
    return out
