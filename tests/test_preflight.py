"""Kernel preflight (tools/preflight.py): the tier-1 acceptance suite.

The contract under ``pytest -m pallas_preflight``:

- every SSB flight's extracted plan PASSES the lowering model at the
  default config (zero predicted failures), and every passing shape runs
  ``run_segment`` bit-parity in Pallas interpret mode — the model admits
  exactly what the engine can execute;
- every fuzz-grid FAIL shape fails with its intended
  ``pallas_preflight_<rule>`` code (no ``unknown``, no misattribution);
- a seeded predicted-fail shape declines through BOTH executors with its
  preflight reason on the decision ledger — and still serves the correct
  answer on the jnp path;
- the blocklist round-trips through disk
  (``pinot.server.query.pallas.blocklist.path``) and surfaces on
  ``GET /debug/pallas`` together with the verdict table.
"""

import dataclasses

import numpy as np
import pytest

from pinot_tpu.engine import ensure_x64

ensure_x64()

from pinot_tpu.common.tracing import PALLAS_PREFLIGHT_REASONS
from pinot_tpu.engine.pallas_blocklist import PallasBlocklist
from pinot_tpu.engine.plan import plan_segment
from pinot_tpu.engine.staging import PALLAS_TILE, StagingCache
from pinot_tpu.query import compile_query
from pinot_tpu.tools import preflight, ssb

pytestmark = pytest.mark.pallas_preflight

# 2 segments x 3000 rows -> padded capacity not a multiple of
# PALLAS_TILE: every extracted spec carries a remainder tile
ROWS = 6_000


@pytest.fixture(scope="module")
def segs(tmp_path_factory):
    out = tmp_path_factory.mktemp("preflight_ssb")
    return ssb.build_segments(0, str(out), num_segments=2, rows=ROWS,
                              workers=1)


@pytest.fixture(scope="module")
def table(segs):
    return preflight.run_preflight(segs)


def _ssb_rows(table):
    return {r["shape"]: r for r in table["shapes"]
            if r["source"] == "ssb"}


# -- the acceptance gate: 13 flights, zero predicted failures ---------------

def test_all_13_ssb_flights_pass_at_default_config(table):
    rows = _ssb_rows(table)
    assert sorted(rows) == sorted(ssb.QUERIES)
    failed = {q: r for q, r in rows.items() if r["verdict"] != "pass"}
    assert not failed, failed
    assert table["ssb_failed"] == []


def test_verdicts_carry_budget_accounting(table):
    for r in _ssb_rows(table).values():
        assert r["vmem_bytes"] > 0
        assert r["smem_slots"] >= 3   # >= 1 interval-free params vector


# -- interpret-mode cross-check: every preflight PASS executes --------------

def test_every_pass_shape_runs_bit_parity_in_interpret_mode(segs, table):
    """A preflight PASS is a promise: the shape must actually run. Every
    passing SSB spec executes run_segment in interpret mode and matches
    the jnp kernel bit-for-bit (decoded-group equality for the
    probe-narrowed shapes, whose packed layout is the narrowed one)."""
    from pinot_tpu.engine.executor import decode_grouped_result
    from pinot_tpu.engine.kernels import build_kernel, unpack_outputs
    from pinot_tpu.engine.pallas_kernels import (
        PallasKernelCache,
        run_segment,
    )

    passing = [q for q, r in _ssb_rows(table).items()
               if r["verdict"] == "pass"]
    assert passing
    seg = segs[0]
    staged = StagingCache().stage(seg)
    cache = PallasKernelCache()
    for qid in passing:
        ctx = compile_query(ssb.QUERIES[qid] + " LIMIT 100000")
        plan = plan_segment(ctx, seg)
        served = run_segment(plan, staged, cache, interpret=True)
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


# -- fuzz grid: each FAIL shape fails with its intended rule ----------------

EXPECTED_FUZZ_FAILS = {
    "limbs8_over": "pallas_preflight_limb_planes",
    "limbs_on_float": "pallas_preflight_dtype_unsupported",
    "ivs512_over": "pallas_preflight_smem_budget",
    "groups16384_over": "pallas_preflight_groups_bound",
    "groups8100_unpadded": "pallas_preflight_groups_bound",
    "bits6_straddle": "pallas_preflight_tile_align",
    "grid_zero_tiles": "pallas_preflight_grid_bound",
    "wide96_vmem_over": "pallas_preflight_vmem_budget",
}


def test_fuzz_grid_rules_exact(table):
    fuzz = {r["shape"]: r for r in table["shapes"]
            if r["source"] == "fuzz"}
    fails = {s: r["rule"] for s, r in fuzz.items()
             if r["verdict"] == "fail"}
    assert fails == EXPECTED_FUZZ_FAILS
    # the pass side of the grid proves the model admits what the engine
    # emits: limb range, in-cap ivs pads, the dense group spectrum,
    # every word-aligned packed width, remainder tiles
    passing = {s for s, r in fuzz.items() if r["verdict"] == "pass"}
    for expected in ("limbs6", "ivs128", "groups8192", "bits16",
                     "tiles_remainder"):
        assert expected in passing
    # every rule in the registered namespace is exercised by the grid
    assert set(EXPECTED_FUZZ_FAILS.values()) == PALLAS_PREFLIGHT_REASONS


@pytest.mark.parametrize("label, fits", [
    ("groups128", True), ("groups1024", True), ("groups8192", True),
    ("rows6_groups8192", True), ("wide96_vmem_over", False)])
def test_vmem_estimate_of_the_two_level_working_set(label, fits):
    """The model's working set follows build_kernel's: one [128, T]
    bf16 one-hot a tile whatever the group count, plus, above 128 groups,
    the hi-select mask and ONE expanded row block (of bf16 integer rows,
    two a limb, or of f32 float-sum rows). The widest plans stay inside
    the budget at 8192 groups; 96 aggregations there do not."""
    from pinot_tpu.engine.pallas_kernels import _row_layout, accumulate_rows

    model = preflight.TPU_V5E
    spec = dict(preflight.fuzz_specs())[label]
    got = preflight._vmem_estimate(spec, model)
    assert (got <= model.vmem_budget) is fits
    # the same plan at 128 groups: what the group axis adds, to the byte
    T, lane = PALLAS_TILE, model.lane
    G = spec.num_groups_padded
    H, Hp, rows_per_dot = accumulate_rows(G)
    _f, isum, _mm, Mf, Mi, Mm = _row_layout(spec)
    n_int = 1 + 2 * sum(L for _s, L in isum.values())
    n_float = Mf // 2
    added = (Mf + Mi) * (Hp - 1) * lane * 4 + Mm * (G - lane) * 4
    if H > 1:
        rows_int = min(n_int, rows_per_dot) * Hp
        rows_float = min(n_float, rows_per_dot) * Hp
        # two MXU heights, or one stack row
        assert max(rows_int, rows_float) <= max(256, Hp)
        added += Hp * T * (4 + 2) + max(rows_int * (T * 2 + lane * 4),
                                        rows_float * (T + lane) * 4)
    base = preflight._vmem_estimate(
        dataclasses.replace(spec, num_groups_padded=lane), model)
    assert got - base == added


def test_vmem_estimate_pins_the_single_chunk_one_hot(segs):
    """Q2.2's working set at the benchmark's grid, to the byte: its
    blocks, dictId planes and accumulators, the integer rows (the count
    and two bf16 halves a limb), and the one-hot as build_kernel holds it
    with every key on its lane: the key-pair and bf16-one planes, the
    [64, 128] pair iota and the [128, T] bf16 one-hot, 1,114,112 bytes
    (the lane-broadcast build held a [T, 128] i32 iota beside it, three
    times that)."""
    from pinot_tpu.engine.pallas_kernels import _row_layout

    ctx = compile_query(ssb.QUERIES["Q2.2"] + " LIMIT 100000")
    staged = StagingCache().stage(segs[0])
    spec, _eff, reason = preflight.extract_query_spec(
        plan_segment(ctx, staged.segment), staged)
    assert spec is not None, reason
    spec = dataclasses.replace(spec, num_segs=8, tiles_per_seg=733)
    assert spec.num_groups_padded == 128 and spec.value_is_int == (True,)
    T, lane = PALLAS_TILE, preflight.TPU_V5E.lane
    _f, isum, _mm, Mf, Mi, Mm = _row_layout(spec)
    (L,) = [L for _start, L in isum.values()]
    blocks = sum(T * bits // 32 * 4 for bits in spec.packed_bits) + T * 4
    planes = len(spec.packed_bits) * T * 4
    accumulators = (Mf + Mi) * lane * 4 + Mm * 128 * 4 + 8 * lane * 4
    int_rows = (1 + 2 * L) * T * 2
    one_hot = 2 * T * 4 + 64 * lane * 4 + 128 * T * 2
    assert one_hot == 1_114_112
    assert preflight._vmem_estimate(spec, preflight.TPU_V5E) == (
        blocks + planes + accumulators + int_rows + one_hot)


def test_fuzz_grid_covers_the_announced_axes():
    """The grid actually spans the axes it claims: limb counts, ivs run
    counts, group ranges, packed widths, remainder tiles."""
    labels = dict(preflight.fuzz_specs())
    assert labels["limbs6"].value_limbs == (6,)
    assert labels["ivs128"].n_slots == 128
    assert labels["groups8192"].num_groups_padded == 8192
    assert labels["bits16"].packed_bits == (16,)
    # a prime tile count models capacity % PALLAS_TILE != 0 segments
    assert labels["tiles_remainder"].tiles_per_seg == 5


# -- seeded FAIL shapes decline with their preflight reason -----------------

def test_seeded_fail_declines_per_segment_with_rule_reason(segs):
    """A predicted-fail shape seeded into the blocklist declines with
    its pallas_preflight_* reason (never ``unknown``, never the generic
    shape_blocked) AND the jnp path still serves the right answer."""
    from pinot_tpu.engine import ServerQueryExecutor

    ex = ServerQueryExecutor(use_device=True, use_pallas=True)
    host = ServerQueryExecutor(use_device=False)
    # useStarTree=false: the pre-agg rung would otherwise serve Q1.1
    # without ever consulting the pallas blocklist
    sql = ssb.QUERIES["Q1.1"] + " OPTION(useStarTree=false)"
    plan = plan_segment(compile_query(sql), segs[0])
    ex._pallas_blocked.add(plan.spec,
                           reason="pallas_preflight_vmem_budget")
    got, stats = ex.execute(compile_query(sql), segs)
    want, _ = host.execute(compile_query(sql), segs)
    assert got.rows == want.rows
    keys = [k for k in stats.decisions
            if k.endswith(":pallas_preflight_vmem_budget")]
    assert keys, stats.decisions
    assert not [k for k in stats.decisions if k.endswith(":unknown")]


def test_seeded_fail_declines_sharded_with_rule_reason(segs):
    from pinot_tpu.parallel import ShardedQueryExecutor

    ex = ShardedQueryExecutor(use_pallas=True)
    sql = ssb.QUERIES["Q2.1"] + " LIMIT 100000 OPTION(useStarTree=false)"
    # the sharded combine plans against the unified BATCH (its own
    # dictionaries/capacity), so the blocklist key must be the batch plan
    batch = ex.batch_for(segs)
    plan = plan_segment(compile_query(sql), batch)
    ex._pallas_blocked.add(plan.spec,
                           reason="pallas_preflight_smem_budget")
    _got, stats = ex.execute(compile_query(sql), segs)
    keys = [k for k in stats.decisions
            if k.endswith(":pallas_preflight_smem_budget")]
    assert keys, stats.decisions


def test_attach_verdicts_seeds_blocklist_under_pessimal_model(segs):
    """The whole loop: a pessimized model predicts every SSB shape
    fails -> attach_verdicts seeds all 13 into the executor blocklist
    with vmem reasons -> the engine declines them loudly."""
    from pinot_tpu.engine import ServerQueryExecutor

    tiny = dataclasses.replace(preflight.TPU_V5E, vmem_bytes=1 << 16)
    table = preflight.run_preflight(segs, model=tiny, fuzz=False)
    assert len(table["ssb_failed"]) == 13
    ex = ServerQueryExecutor(use_device=True, use_pallas=True)
    seeded = preflight.attach_verdicts(ex, table)
    assert seeded == 13
    assert len(ex._pallas_blocked) == 13
    assert ex.preflight_verdicts["failed"] >= 13
    # verdict table attached to the executor is the /debug/pallas body
    assert "_plan_specs" not in ex.preflight_verdicts
    sql = ssb.QUERIES["Q1.1"]
    plan = plan_segment(compile_query(sql), segs[0])
    assert ex._pallas_blocked.reason_for(plan.spec) \
        == "pallas_preflight_vmem_budget"


# -- blocklist persistence + /debug/pallas ----------------------------------

def test_blocklist_roundtrips_through_disk(tmp_path, segs):
    path = str(tmp_path / "blocklist.json")
    bl = PallasBlocklist(path=path)
    plan = plan_segment(compile_query(ssb.QUERIES["Q1.1"]), segs[0])
    bl.add(plan.spec, reason="pallas_preflight_tile_align")
    bl.add(("runtime", "shape"))   # runtime failure: default reason
    # a fresh instance (the restarted chip) remembers both
    bl2 = PallasBlocklist(path=path)
    assert plan.spec in bl2
    assert bl2.reason_for(plan.spec) == "pallas_preflight_tile_align"
    assert bl2.reason_for(("runtime", "shape")) == "pallas_shape_blocked"
    assert len(bl2) == 2


def test_executor_loads_blocklist_from_config(tmp_path, segs):
    from pinot_tpu.engine import ServerQueryExecutor
    from pinot_tpu.spi.config import CommonConstants, PinotConfiguration

    path = str(tmp_path / "bl.json")
    plan = plan_segment(compile_query(ssb.QUERIES["Q1.2"]), segs[0])
    PallasBlocklist(path=path).add(plan.spec,
                                   reason="pallas_preflight_smem_budget")
    ex = ServerQueryExecutor(
        use_device=True, use_pallas=True,
        config=PinotConfiguration(
            {CommonConstants.PALLAS_BLOCKLIST_PATH_KEY: path}))
    assert plan.spec in ex._pallas_blocked
    assert ex._pallas_blocked.reason_for(plan.spec) \
        == "pallas_preflight_smem_budget"
    # a runtime failure learned by THIS process persists for the next
    ex._pallas_blocked.add(("another", "shape"))
    assert ("another", "shape") in PallasBlocklist(path=path)


def test_debug_pallas_body(segs, table):
    """The ServerInstance /debug/pallas body: blocklist rows with
    reasons + the attached verdict table."""
    from types import SimpleNamespace

    from pinot_tpu.engine import ServerQueryExecutor
    from pinot_tpu.server.server import ServerInstance

    ex = ServerQueryExecutor(use_device=True, use_pallas=True)
    preflight.attach_verdicts(ex, table)
    ex._pallas_blocked.add(("bad", "shape"),
                           reason="pallas_preflight_groups_bound")
    body = ServerInstance.pallas_debug(SimpleNamespace(executor=ex))
    assert body["blockedShapes"] == 1
    [row] = body["blocklist"]
    assert row["reason"] == "pallas_preflight_groups_bound"
    assert body["preflight"]["passed"] == table["passed"]
    import json

    json.dumps(body)   # wire-safe


def test_not_extractable_plan_reports_reason(segs):
    """A plan the fused kernel cannot serve at all (distinct agg) gets a
    verdict row, not a crash."""
    staged = StagingCache().stage(segs[0])
    plan = plan_segment(compile_query(
        "SELECT distinctcount(c_city) FROM ssb_lineorder"), segs[0])
    spec, eff, reason = preflight.extract_query_spec(plan, staged)
    assert spec is None and eff is None
    assert reason == "pallas_distinct_agg"


def test_lowering_model_is_keyed_by_a_named_device_kind():
    assert preflight.model_for("TPU v5 lite") is preflight.TPU_V5E
    assert preflight.TPU_V5E.device_kind == "TPU v5 lite"
    with pytest.raises(ValueError, match="no lowering model"):
        preflight.model_for("TPU v9 imaginary")
    with pytest.raises(TypeError):
        preflight.LoweringModel()       # no anonymous 16 MB default
