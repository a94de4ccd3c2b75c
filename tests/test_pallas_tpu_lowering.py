"""The fused Pallas kernel lowers for TPU (tier-1, CPU, no chip).

``jax.jit(build_kernel(spec)).trace(*args).lower(lowering_platforms=
("tpu",))`` runs the Pallas -> Mosaic lowering with jax_enable_x64 on, as
the executors run it: the check that caught the kernel's two refusals (a
``dot_general`` contracting two dims, 64-bit scalars leaking into the
body) before any chip was asked. The ``compiles_for_v5e`` tests go one
step on: libtpu compiles the two-level accumulate at its widest shapes,
Q2.2's single chunk and the float-sum rows, for a v5e that is described and not attached
(VMEM, tiling and slices are judged there, not in the lowering). Neither
runs anything:
``chip_smoke.py`` and the benchmark do.
"""

import os

from dataclasses import replace

import jax
import jax.extend
import jax.numpy as jnp
import pytest

from pinot_tpu.engine import ensure_x64

ensure_x64()

from pinot_tpu.engine.pallas_kernels import build_kernel, probe_plan_of
from pinot_tpu.engine.plan import plan_segment
from pinot_tpu.engine.staging import PALLAS_TILE, StagingCache
from pinot_tpu.query import compile_query
from pinot_tpu.tools import preflight, ssb

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def staged(tmp_path_factory):
    out = tmp_path_factory.mktemp("tpu_lowering")
    segs = ssb.build_segments(0, str(out), num_segments=2, rows=18_000,
                              workers=1, star_tree=False)
    return StagingCache().stage(segs[0])


def _abstract_args(spec):
    S, TPS = spec.num_segs, spec.tiles_per_seg
    args = [jax.ShapeDtypeStruct((2 * spec.n_slots + S + 1,), jnp.int32)]
    for bits in spec.packed_bits:
        words = PALLAS_TILE // (32 // bits)
        args.append(jax.ShapeDtypeStruct((S, TPS, words // 128, 128),
                                         jnp.uint32))
    limbs = spec.value_limbs or (0,) * len(spec.value_is_int)
    for planes, is_int in zip(limbs, spec.value_is_int):
        for _ in range(planes or 1):
            args.append(jax.ShapeDtypeStruct(
                (S, TPS, PALLAS_TILE // 128, 128),
                jnp.int32 if is_int else jnp.float32))
    return args


def _lower_for_tpu(spec):
    spec = replace(spec, interpret=False)
    assert jax.config.jax_enable_x64
    jax.jit(build_kernel(spec)).trace(*_abstract_args(spec)).lower(
        lowering_platforms=("tpu",))


def _spec_of(qid, staged):
    ctx = compile_query(ssb.QUERIES[qid] + " LIMIT 100000")
    spec, _eff, reason = preflight.extract_query_spec(
        plan_segment(ctx, staged.segment), staged)
    assert spec is not None, (qid, reason)
    return spec


@pytest.mark.parametrize("qid", sorted(ssb.QUERIES))
def test_ssb_flight_lowers_for_tpu(staged, qid):
    _lower_for_tpu(_spec_of(qid, staged))


@pytest.mark.parametrize("groups", [256, 384, 4096, 8192])
def test_two_level_accumulate_lowers_for_tpu(staged, groups):
    """The Q2.1 shape at every form of the hi axis: two and three chunks
    (padded to eight sublane rows), its own 32, and MAX_PALLAS_GROUPS."""
    _lower_for_tpu(replace(_spec_of("Q2.1", staged),
                           num_groups_padded=groups))


@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described and not attached; libtpu is loaded by
    this file's worker alone, and only once a test here has started."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("groups", [4096, 8192])
def test_two_level_accumulate_compiles_for_v5e(staged, one_chip, groups):
    """Mosaic's verdict on the Q2.1 program at the benchmark's grid (8
    segments of 733 tiles) and at MAX_PALLAS_GROUPS, where the expanded
    row block and the accumulators are largest."""
    spec = replace(_spec_of("Q2.1", staged), interpret=False, num_segs=8,
                   tiles_per_seg=733, num_groups_padded=groups)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in _abstract_args(spec)]
    assert preflight.preflight_spec(spec).ok
    jax.jit(build_kernel(spec)).lower(*args).compile()


def test_sharded_spec_lowers_for_tpu(staged):
    """Several local segments per device: the per-segment matched-doc
    block walks the segment grid axis."""
    _lower_for_tpu(replace(_spec_of("Q2.1", staged), num_segs=4,
                           tiles_per_seg=8))


def _probe_spec(staged, qid="Q3.2", **grid):
    from pinot_tpu.engine.pallas_kernels import _stage_packed, extract_plan

    ctx = compile_query(ssb.QUERIES[qid] + " LIMIT 100000")
    full = extract_plan(plan_segment(ctx, staged.segment), staged.segment,
                        unchecked_groups=True)
    probe = probe_plan_of(full)
    _cols, bits = _stage_packed(probe, staged, lambda reason: None)
    return replace(probe.spec(interpret=False, **grid),
                   packed_bits=tuple(bits))


def test_group_range_probe_lowers_for_tpu(staged):
    """The min/max-of-dictId probe Q3.2 and Q4.3 run first (no matmul
    rows, one min/max pair per group column)."""
    _lower_for_tpu(_probe_spec(staged, num_segs=2, tiles_per_seg=3))


def test_group_range_probe_compiles_for_v5e(staged, one_chip):
    """Mosaic's verdict on the probe's scalar key space at the
    benchmark's grid (8 segments of 733 tiles)."""
    spec = _probe_spec(staged, num_segs=8, tiles_per_seg=733)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in _abstract_args(spec)]
    assert preflight.preflight_spec(spec).ok
    jax.jit(build_kernel(spec)).lower(*args).compile()


def _kernel_eqns(spec):
    """Every equation of ``build_kernel(spec)``'s jaxpr, nested jaxprs
    (the pallas_call's kernel body among them) included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if isinstance(sub, jax.extend.core.Jaxpr):
                        yield from walk(sub)

    closed = jax.make_jaxpr(build_kernel(spec))(*_abstract_args(spec))
    return list(walk(closed.jaxpr))


@pytest.mark.parametrize("shape, one_hot", [
    ("probe_Q3.2", False), ("probe_Q4.3", False),
    ("Q1.1", True), ("Q2.1", True)])
def test_scalar_key_space_builds_no_one_hot(staged, shape, one_hot):
    """A probe's program has no matmul and no [.., 128, 128] array (no
    one-hot, no values broadcast across lanes); a scalar spec with a sum
    (Q1.1) and a grouped one (Q2.1) keep their ``dot_general``."""
    if shape.startswith("probe_"):
        spec = _probe_spec(staged, shape[len("probe_"):], num_segs=2,
                           tiles_per_seg=3)
    else:
        spec = _spec_of(shape, staged)
    eqns = _kernel_eqns(spec)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    wide = [v.aval.shape for e in eqns for v in e.outvars
            if tuple(getattr(v.aval, "shape", ()))[-2:] == (128, 128)]
    assert bool(dots) is one_hot, shape
    if not one_hot:
        assert not wide, wide


def _dots(spec):
    """(operand dtypes, precision) of every ``dot_general`` in the
    kernel's jaxpr."""
    return [(tuple(str(v.aval.dtype) for v in e.invars),
             e.params.get("precision"))
            for e in _kernel_eqns(spec) if e.primitive.name == "dot_general"]


def _default_precision(precision):
    return precision is None or all(
        p in (None, jax.lax.Precision.DEFAULT) for p in precision)


@pytest.mark.parametrize("shape", sorted(ssb.QUERIES)
                         + ["Q2.1_g8192", "float_sum", "probe_Q3.2"])
def test_integer_rows_take_one_bf16_pass(staged, shape):
    """Every SSB flight's program (and Q2.1's at MAX_PALLAS_GROUPS, whose
    rows run in two blocks) multiplies bf16 operands at default precision
    in every ``dot_general``: the one-hot and the integer rows go through
    the MXU in a single bf16 pass. A float sum keeps exactly one fp32
    ``dot`` (HIGHEST) beside the bf16 one; the probe has none."""
    if shape == "probe_Q3.2":
        assert _dots(_probe_spec(staged, "Q3.2", num_segs=2,
                                 tiles_per_seg=3)) == []
        return
    if shape == "float_sum":
        dots = _dots(_float_sum_spec("Q1.1", staged))
        assert sorted(d for d, _p in dots) == [
            ("bfloat16", "bfloat16"), ("float32", "float32")]
        for dtypes, precision in dots:
            assert _default_precision(precision) is (
                dtypes == ("bfloat16", "bfloat16")), (dtypes, precision)
        return
    qid, _, groups = shape.partition("_g")
    spec = _spec_of(qid, staged)
    if groups:
        spec = replace(spec, num_groups_padded=int(groups))
    dots = _dots(spec)
    assert dots and all(d == ("bfloat16", "bfloat16")
                        and _default_precision(p) for d, p in dots), dots


def test_single_chunk_accumulate_compiles_for_v5e(staged, one_chip):
    """Mosaic's verdict on Q2.2's program (``H = 1``: its integer rows
    and bf16 one-hot go into one matmul) at the benchmark's grid."""
    spec = replace(_spec_of("Q2.2", staged), interpret=False, num_segs=8,
                   tiles_per_seg=733)
    assert spec.num_groups_padded == 128
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in _abstract_args(spec)]
    assert preflight.preflight_spec(spec).ok
    jax.jit(build_kernel(spec)).lower(*args).compile()


@pytest.mark.parametrize("qid", ["Q1.1", "Q2.1"])
def test_float_sum_accumulate_compiles_for_v5e(staged, one_chip, qid):
    """Mosaic's verdict on the float-sum rows' fp32 contraction
    (HIGHEST, against the one-hot cast to f32) at the benchmark's grid:
    in one chunk beside a bf16 integer pass (Q1.1) and two-level (Q2.1 at
    its 4096 groups, its sum over a float column)."""
    spec = replace(_float_sum_spec(qid, staged), interpret=False,
                   num_segs=8, tiles_per_seg=733)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in _abstract_args(spec)]
    assert preflight.preflight_spec(spec).ok
    jax.jit(build_kernel(spec)).lower(*args).compile()


def _float_sum_spec(qid, staged):
    """The flight's program with a float sum: Q1.1's two value inputs
    an int sum of one and a float sum of the other (not bf16-exact);
    Q2.1's one sum over a float column."""
    spec = _spec_of(qid, staged)
    if qid == "Q1.1":
        return replace(spec, aggs=(("sum", ("v", 0), 3),
                                   ("sum", ("v", 1), None)),
                       value_is_int=(True, False))
    return replace(spec, aggs=(("sum", ("v", 0), None),),
                   value_is_int=(False,))


def _assert_keys_stay_on_lanes(spec):
    """The kernel builds its one-hot as ``[128 groups, T docs]`` blocks
    from each key row broadcast down the sublanes, and contracts it on
    the docs axis of both operands: no array of the ``[RT, 128, 128]``
    shape that broadcasting each key across 128 lanes makes (a lane-to-
    sublane relayout of every key of a tile), and every ``dot_general``
    takes the one-hot as a ``[128, T]`` right operand."""
    RT = PALLAS_TILE // 128
    eqns = _kernel_eqns(spec)
    wide = [(e.primitive.name, v.aval.shape) for e in eqns for v in e.outvars
            if tuple(getattr(v.aval, "shape", ())) == (RT, 128, 128)]
    assert not wide, wide
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert dots
    for e in dots:
        assert e.params["dimension_numbers"] == (((1,), (1,)), ((), ()))
        assert e.invars[1].aval.shape == (128, PALLAS_TILE)


@pytest.mark.parametrize("shape", sorted(ssb.QUERIES) + ["Q2.1_g8192"])
def test_one_hot_keeps_every_key_on_its_lane(staged, shape):
    """Every SSB flight's program (each builds a one-hot: the grouped ones
    and Q1's sums), and Q2.1's at MAX_PALLAS_GROUPS, keeps the build that
    moves no key off its lane."""
    qid, _, groups = shape.partition("_g")
    spec = _spec_of(qid, staged)
    if groups:
        spec = replace(spec, num_groups_padded=int(groups))
    _assert_keys_stay_on_lanes(spec)


@pytest.mark.parametrize("shape", sorted(ssb.QUERIES) + [
    f"Q2.1_g{g}" for g in (256, 384, 4096, 8192)])
def test_preflight_admits_every_ssb_shape(staged, shape):
    """The VMEM model reckons the bf16 one-hot and the split limb rows,
    and every SSB flight, with Q2.1 at each form of the hi axis, still
    passes it at the benchmark's grid."""
    qid, _, groups = shape.partition("_g")
    spec = replace(_spec_of(qid, staged), num_segs=8, tiles_per_seg=733)
    if groups:
        spec = replace(spec, num_groups_padded=int(groups))
    verdict = preflight.preflight_spec(spec)
    assert verdict.ok, (shape, verdict.rule, verdict.detail)
