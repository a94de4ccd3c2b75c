"""The fused Pallas kernel lowers for TPU (tier-1, CPU, no chip).

``jax.jit(build_kernel(spec)).trace(*args).lower(lowering_platforms=
("tpu",))`` runs the Pallas -> Mosaic lowering with jax_enable_x64 on, as
the executors run it: the check that caught the kernel's two refusals (a
``dot_general`` contracting two dims, 64-bit scalars leaking into the
body) before any chip was asked. It guards the lowering, not libtpu's
verdict — that is ``chip_smoke.py``'s job.
"""

from dataclasses import replace

import jax
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


def test_sharded_spec_lowers_for_tpu(staged):
    """Several local segments per device: the per-segment matched-doc
    block walks the segment grid axis."""
    _lower_for_tpu(replace(_spec_of("Q2.1", staged), num_segs=4,
                           tiles_per_seg=8))


def test_group_range_probe_lowers_for_tpu(staged):
    """The min/max-of-dictId probe Q3.2 and Q4.3 run first (no matmul
    rows, one min/max pair per group column)."""
    from pinot_tpu.engine.pallas_kernels import _stage_packed, extract_plan

    ctx = compile_query(ssb.QUERIES["Q3.2"] + " LIMIT 100000")
    full = extract_plan(plan_segment(ctx, staged.segment), staged.segment,
                        unchecked_groups=True)
    probe = probe_plan_of(full)
    _cols, bits = _stage_packed(probe, staged, lambda reason: None)
    _lower_for_tpu(replace(
        probe.spec(num_segs=2, tiles_per_seg=3, interpret=False),
        packed_bits=tuple(bits)))
