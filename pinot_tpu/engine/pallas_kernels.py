"""Fused Pallas scan kernel: bit-unpack -> predicate -> aggregate on MXU.

TPU-native re-design of the reference's hottest loop — the per-segment
``Filter -> Projection -> GroupBy/Aggregate`` chain
(``SVScanDocIdIterator.java:36`` predicate scan, ``PinotDataBitSet.java:25``
bit extraction, ``AggregationGroupByOrderByOperator.java:61-128`` execution,
``DefaultGroupByExecutor`` scatter into group slots) — as ONE Pallas kernel
over a ``(segments, tiles)`` grid:

- forward indexes arrive as **planar bit-packed words** (engine/staging.py
  PackedColumn): a tile's value ``j`` lives in word ``j % W`` at bit slot
  ``(j // W) * B``, so the in-VMEM unpack is ``K = 32/B`` static shift+mask
  ops over contiguous words — vector ops only, no gathers;
- the filter tree is compiled to an AND/OR/NOT expression over dictId
  interval tests (sorted dictionaries turn EQ/NEQ/RANGE into intervals, the
  vectorized form of dictionary-based predicate evaluators);
- aggregation values may be **elementwise expressions** of staged columns
  (``sum(lo_extendedprice * lo_discount)``): integer expressions evaluate
  exactly in i32 (plan-time bound check), float expressions in f32;
- sums/counts/avg are a **one-hot matmul on the MXU**: rows
  ``[value rows..., mask] @ one_hot(keys)`` accumulate ``[aggs, groups]``
  partials — the fixed-shape scatter-add replacement for
  ``GroupByResultHolder``. Above 128 groups the key splits in **two
  levels**, ``hi = key >> 7`` and ``lo = key & 127``: a tile builds ONE
  ``[128, T]`` one-hot of ``lo`` (groups on sublanes, every key left on
  its lane) and expands each row into ``H = G / 128`` rows by ``hi`` (row
  ``m * H + h`` keeps the docs whose ``hi == h``), so one full-height
  matmul gives every group's partial and a tile's work
  does not multiply rows by 128-group chunks. Exactness scheme:
  - **integer sums** split each value into 12-bit limbs (``L`` limbs for a
    plan-time ``max_abs`` bound), and each limb enters the MXU as two
    bf16-exact halves, its low byte (0..255) and ``limb >> 8`` (-16..15),
    beside the 0/1 count row, against a bf16 one-hot, in ONE default-
    precision bf16 pass: every per-tile half partial is at most
    ``255 * PALLAS_TILE < 2^24``, exact in the MXU's f32 accumulation;
    the halves' partials recombine in i32 (``(hi << 8) + lo``).
    Limb partials land in per-limb **i32 accumulators with a carry chain**
    (base-2^12 positional rows, normalized every grid step), so provider-
    wide sums are exact up to ~2^62 with no i64 math inside the kernel;
  - **float sums** accumulate with Neumaier-compensated f32 pairs
    (sum row + compensation row), recovering near-f64 accuracy over
    hundreds of millions of rows; their rows are not bf16-exact, so a spec
    with a float sum runs them through an fp32 contraction of their own
    (``Precision.HIGHEST``) beside the integer rows' bf16 pass;
- min/max/minmaxrange reduce on the VPU per 128-group chunk;
- scalar (non-group-by) aggregations are the same kernel with a single
  group (all keys 0);
- per-segment matched-doc counts accumulate into a segment-indexed i32
  output (QueryStats parity with the jnp path).

The same kernel body serves the per-segment executor (grid [1, T]) and the
sharded combine (grid [S_local, T_local] per device under shard_map, partials
merged with psum/pmin/pmax over ICI — see parallel/combine.py).

Eligibility is decided per plan (``extract_plan``); anything else falls back
to the jnp masked-vector kernels (engine/kernels.py).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pinot_tpu.common.bounds import I64_FOLD_BOUND
from pinot_tpu.engine.staging import LIMB_BITS, PALLAS_TILE, StagedSegment

# one-hot chunk width along the group dimension (lane count)
_G_CHUNK = 128
# most LHS rows of one accumulate matmul (two MXU heights): a plan whose
# expanded row stack is taller runs it in blocks of this many rows
_EXPAND_ROWS = 256
# max padded group count the pallas path handles (VMEM bound: the
# accumulators and one expanded row block grow with it);
# 8192 covers every SSB flight except the Q3.2+/Q4.3 city/brand key spaces
# (those ride the jnp sparse-group ladder, engine/kernels.py)
MAX_PALLAS_GROUPS = 8192
# int values are split into limbs of this many bits: a tile's limb partial,
# (2^12 - 1) * PALLAS_TILE < 2^24, and the carry chain stay i32-bounded
# (staging.LIMB_BITS is the same constant — the host-side limb-plane split
# for i64 columns must mirror the in-kernel split bit-for-bit)
_LIMB_BITS = LIMB_BITS
_LIMB_MASK = (1 << _LIMB_BITS) - 1
# a limb enters the MXU as two halves bf16 holds exactly (every integer of
# magnitude <= 256): its low byte and ``limb >> 8`` (-16..15 for a signed
# top limb)
_HALF_BITS = 8
# bf16's bits of 1.0: a one-hot entry, written into a 32-bit word's half
_BF16_ONE = 0x3F80
_HALF_MASK = (1 << _HALF_BITS) - 1
# f32 can represent integers exactly below 2^24 (min/max value bound)
_F32_EXACT = 1 << 24
_I32_MAX = (1 << 31) - 1

_POS = np.float32(np.inf)
_NEG = np.float32(-np.inf)

assert _LIMB_BITS <= 2 * _HALF_BITS, "a limb's halves must be bf16-exact"
assert _HALF_MASK * PALLAS_TILE < _F32_EXACT, "half partials must be f32-exact"


@dataclass(frozen=True)
class PallasSpec:
    """Hashable kernel-cache key (all static shapes/strides/tree)."""

    num_segs: int                         # grid segment dim
    tiles_per_seg: int                    # grid tile dim
    packed_bits: Tuple[int, ...]          # per packed input column
    # nested tuples: ("true",) | ("and"|"or", (children...)) | ("not", (c,))
    # | ("iv", packed_input_idx, param_slot)
    filter_tree: Tuple
    n_slots: int                          # interval param slots
    group_idx: Tuple[int, ...]            # packed input idx per group col
    group_strides: Tuple[int, ...]
    # sum(base_i * stride_i): subtracted from the composed key — nonzero
    # when plan.py filter-narrowed a group column's dictId range (masked
    # docs may then compose negative keys; the one-hot match drops them)
    group_key_offset: int
    num_groups_padded: int                # multiple of 128
    # per agg: (base, vexpr, limbs); base in count/sum/avg/min/max/minmaxrange;
    # vexpr is a nested value expression: ("v", input_idx) |
    # ("times"|"plus"|"minus", lhs, rhs); limbs = L for exact int sums,
    # None for float sums and non-sum aggregations
    aggs: Tuple[Tuple[str, Optional[Tuple], Optional[int]], ...]
    value_is_int: Tuple[bool, ...]        # per value input
    # per value input: 0 = one staged f32/i32 array ref; L > 0 = the input
    # is an i64-staged column shipped as L pre-split 12-bit limb PLANES
    # (i32 refs, host-split with the kernel's exact shift/mask scheme) —
    # its sums accumulate limb-by-limb with no i64 math in-kernel
    value_limbs: Tuple[int, ...] = ()
    interpret: bool = False


class _Ineligible(Exception):
    pass


# max interval runs a boolean dictId LUT decomposes into as STATIC spec
# leaves (each run is one compare pair baked into the filter tree); more
# runs fall back to the padded interval-set node below
_MAX_LUT_RUNS = 8
# default runtime cap on interval runs the padded "ivs" (interval-bitmap)
# fallback accepts: each run is one SMEM compare pair per tile, so the cap
# bounds in-kernel work. Configurable via
# pinot.server.query.pallas.lut.max.runs (callers thread it through).
DEFAULT_LUT_RUN_CAP = 64


def _lut_runs(lut: np.ndarray,
              cap: int = DEFAULT_LUT_RUN_CAP) -> Optional[List[Tuple[int, int]]]:
    """Boolean LUT -> [(lo, hi)] inclusive dictId runs, or None if more
    than ``cap`` (fall back to the jnp LUT-gather kernel)."""
    idx = np.nonzero(np.asarray(lut, dtype=bool))[0]
    if idx.size == 0:
        return []
    breaks = np.nonzero(np.diff(idx) > 1)[0]
    if breaks.size + 1 > cap:
        return None
    runs = []
    start = 0
    for b in list(breaks) + [idx.size - 1]:
        runs.append((int(idx[start]), int(idx[b])))
        start = b + 1
    return runs


# --------------------------------------------------------------------------
# plan -> (core spec fields, static params, column names)
# --------------------------------------------------------------------------

@dataclass
class PallasPlan:
    """Staging-independent extraction of a SegmentPlan: what to pack, what
    to stage as values, the static interval params, and the spec core."""

    packed_names: List[str]
    value_names: List[str]
    value_is_int: Tuple[bool, ...]
    filter_tree: Tuple
    n_slots: int
    group_idx: Tuple[int, ...]
    group_strides: Tuple[int, ...]
    group_key_offset: int
    num_groups_padded: int
    aggs: Tuple[Tuple[str, Optional[Tuple], Optional[int]], ...]
    static_params: np.ndarray             # [2 * n_slots] i32 interval bounds
    # per value input: limb-plane count (0 = plain f32/i32 array)
    value_limbs: Tuple[int, ...] = ()

    def spec(self, num_segs: int, tiles_per_seg: int,
             interpret: bool) -> PallasSpec:
        return PallasSpec(
            num_segs=num_segs, tiles_per_seg=tiles_per_seg,
            packed_bits=(), filter_tree=self.filter_tree,
            n_slots=self.n_slots, group_idx=self.group_idx,
            group_strides=self.group_strides,
            group_key_offset=self.group_key_offset,
            num_groups_padded=self.num_groups_padded,
            aggs=self.aggs, value_is_int=self.value_is_int,
            value_limbs=self.value_limbs,
            interpret=interpret)


def _limbs_for(max_abs: int) -> int:
    """Number of 12-bit value limbs covering |v| <= max_abs (top limb holds
    the sign; intermediate limbs are the non-negative two's-complement
    slices, so ``L * 12`` bits must cover ``max_abs`` itself)."""
    return max(1, -(-max(max_abs.bit_length(), 1) // _LIMB_BITS))


def extract_plan(plan, provider, on_decline=None,
                 lut_run_cap: int = DEFAULT_LUT_RUN_CAP,
                 unchecked_groups: bool = False) -> Optional[PallasPlan]:
    """SegmentPlan -> PallasPlan, or None when the query shape isn't covered
    by the fused kernel. ``provider`` supplies column metadata (an
    ImmutableSegment or a SegmentBatch with unified stats). ``on_decline``
    (if given) receives the machine-readable reason code whenever None is
    returned — the path-decision ledger's hook; every ineligibility is
    classified, never ``unknown``. ``lut_run_cap`` bounds the interval-set
    fallback for many-run LUT predicates. ``unchecked_groups`` skips the
    MAX_PALLAS_GROUPS bound — the group-range probe path extracts the full
    plan first, derives a probe kernel from it, and re-extracts against the
    probe-narrowed plan (never build a grouped kernel from an unchecked
    extraction directly)."""
    from pinot_tpu.engine.kernels import _ParamCursor
    from pinot_tpu.engine.staging import staged_int_dtype

    def decline(reason: str) -> None:
        if on_decline is not None:
            on_decline(reason)

    filter_spec, agg_specs, group_specs, num_groups, _ = plan.spec
    if group_specs and num_groups > MAX_PALLAS_GROUPS \
            and not unchecked_groups:
        decline("pallas_too_many_groups")
        return None
    if any(a[0] in ("distinctcount", "distinctcounthll")
           for a in agg_specs):
        decline("pallas_distinct_agg")
        return None  # 3-tuple specs (col, card/log2m) — jnp path serves
    if provider.metadata.num_docs > _I32_MAX:
        decline("pallas_docs_over_i32")
        return None  # count/carry-chain bounds assume i32 doc counts

    try:
        packed_names: List[str] = []

        def packed_idx(col: str) -> int:
            cm = provider.metadata.column(col)
            if not (cm.has_dictionary and cm.single_value):
                raise _Ineligible("unpackable column")
            if col not in packed_names:
                packed_names.append(col)
            return packed_names.index(col)

        # -- filter tree -> interval expression (mirrors the jnp kernel's
        # param consumption order exactly)
        pc = _ParamCursor(plan.params)
        intervals: List[Tuple[int, int]] = []

        def iv_leaf(col: str, lo: int, hi: int) -> Tuple:
            slot = len(intervals)
            intervals.append((lo, hi))
            return ("iv", packed_idx(col), slot)

        def walk(node) -> Tuple:
            op = node[0]
            if op == "true":
                return ("true",)
            if op in ("and", "or"):
                return (op, tuple(walk(c) for c in node[1]))
            if op == "not":
                return ("not", (walk(node[1][0]),))
            if op in ("eq", "neq"):
                did = int(pc.take())
                leaf = iv_leaf(node[1], did, did)
                return ("not", (leaf,)) if op == "neq" else leaf
            if op == "range":
                iv = np.asarray(pc.take())
                return iv_leaf(node[1], int(iv[0]), int(iv[1]))
            if op == "lut":
                # boolean LUT over a SORTED dictionary = union of dictId
                # runs; small run counts become OR-of-intervals (covers
                # IN / merged-EQ / many REGEXP predicates); past
                # _MAX_LUT_RUNS and up to ``lut_run_cap`` the runs ride ONE
                # padded interval-set node ("ivs") — the interval-bitmap
                # fallback: a pow2-padded block of runtime interval slots
                # (empty pads encoded (1, 0)) OR-reduced in-kernel, so the
                # spec stays stable across literal sets with similar run
                # counts instead of baking each run into the tree shape
                lut = np.asarray(pc.take())
                runs = _lut_runs(lut, max(_MAX_LUT_RUNS, lut_run_cap))
                if runs is None:
                    raise _Ineligible("lut with too many runs")
                if not runs:
                    return ("not", (("true",),))
                if len(runs) <= _MAX_LUT_RUNS:
                    leaves = tuple(iv_leaf(node[1], lo, hi)
                                   for lo, hi in runs)
                    return leaves[0] if len(leaves) == 1 else ("or", leaves)
                pi = packed_idx(node[1])
                n_pad = 1 << (len(runs) - 1).bit_length()
                slot0 = len(intervals)
                for lo, hi in runs:
                    intervals.append((lo, hi))
                for _ in range(n_pad - len(runs)):
                    intervals.append((1, 0))   # empty interval pad
                return ("ivs", pi, slot0, n_pad)
            raise _Ineligible(op)

        tree = walk(filter_spec)

        # -- group columns (params: strides + bases arrays)
        group_idx: List[int] = []
        strides: List[int] = []
        key_offset = 0
        if group_specs:
            for strat, col in group_specs:
                if strat != "gdict":
                    raise _Ineligible("raw group key")
                group_idx.append(packed_idx(col))
            strides = [int(s) for s in np.asarray(pc.take())]
            # gdict bases are nonzero when the planner filter-narrowed the
            # column's dictId range; fold them into one static key offset
            bases = [int(b) for b in np.asarray(pc.take())]
            key_offset = sum(b * s for b, s in zip(bases, strides))
        G = padded_groups(plan.spec)

        # -- aggregation value expressions (ref: the reference evaluates
        # transform expressions inside the aggregation operator,
        # AggregationFunctionUtils + TransformOperator; here int exprs run
        # exactly in i32, float exprs in f32, inside the fused kernel).
        # i64-staged columns (stats beyond i32) ship as pre-split 12-bit
        # limb PLANES (staging.value_limb_planes) and ride the existing
        # multi-limb i32 accumulation at the value-load layer: the limb
        # rows come straight from the planes, no i64 math in-kernel.
        value_names: List[str] = []
        value_is_int: List[bool] = []
        value_limbs: List[int] = []

        def leaf_idx(name: str) -> Tuple[Tuple, bool, Optional[int]]:
            cm = provider.metadata.column(name)
            if not (cm.single_value and cm.data_type.is_numeric):
                raise _Ineligible("non-numeric/MV agg value column")
            is_int = cm.data_type.is_integral
            max_abs: Optional[int] = None
            limbs = 0
            if is_int:
                if cm.min_value is None or cm.max_value is None:
                    raise _Ineligible("no stats for int value bound")
                max_abs = max(abs(int(cm.min_value)), abs(int(cm.max_value)))
                if staged_int_dtype(cm) != np.dtype(np.int32):
                    # exact reassembly needs the provider-wide sum inside
                    # i64 (the carry-chain rows shift by up to 62 bits)
                    if max_abs * max(1, provider.metadata.num_docs) \
                            >= I64_FOLD_BOUND:
                        raise _Ineligible("i64 sum bound over i64")
                    limbs = _limbs_for(max_abs)
            if name not in value_names:
                value_names.append(name)
                value_is_int.append(is_int)
                value_limbs.append(limbs)
            vi = value_names.index(name)
            leaf = ("v64", vi) if limbs else ("v", vi)
            return leaf, is_int, max_abs

        def compile_vexpr(vspec) -> Tuple[Tuple, bool, Optional[int]]:
            if vspec is None:
                raise _Ineligible("missing agg value")
            if vspec[0] == "col":
                return leaf_idx(vspec[1])
            if vspec[0] == "lit":
                # literal params become SPEC constants: units/factors are
                # low-cardinality, so keying the kernel cache on them is
                # cheap and keeps the kernel free of an extra params lane
                # (the cursor position mirrors the jnp kernel's consumption
                # order exactly)
                v = float(np.asarray(pc.take()))
                if v.is_integer() and abs(v) <= _I32_MAX:
                    return ("litc", int(v)), True, abs(int(v))
                return ("litf", v), False, None
            if (vspec[0] == "fn" and vspec[1] in ("times", "plus", "minus")
                    and len(vspec[2]) == 2):
                le, li, lm = compile_vexpr(vspec[2][0])
                re_, ri, rm = compile_vexpr(vspec[2][1])
                if li and ri:
                    max_abs = lm * rm if vspec[1] == "times" else lm + rm
                    if max_abs > _I32_MAX:
                        # in-kernel i32 arithmetic would wrap (an i64
                        # operand always lands here: its bound alone
                        # exceeds i32, so limb planes stay sum-only)
                        raise _Ineligible("int expr bound exceeds i32")
                    return (vspec[1], le, re_), True, max_abs
                if _has_v64(le) or _has_v64(re_):
                    # limb planes carry no per-doc value to convert to f32
                    raise _Ineligible("i64 column in float expression")
                return (vspec[1], le, re_), False, None
            # mod/floordiv deliberately stay jnp-served: Mosaic integer
            # division support is not guaranteed, and one lowering failure
            # at run time would disable pallas for the whole process
            raise _Ineligible(f"agg value {vspec[0]!r}")

        aggs: List[Tuple[str, Optional[Tuple], Optional[int]]] = []
        for aspec in agg_specs:
            base, mv, vspec = aspec[0], aspec[1], aspec[2]
            if mv:
                raise _Ineligible("mv aggregation")
            if base == "count":
                aggs.append(("count", None, None))
                continue
            if base not in ("sum", "avg", "min", "max", "minmaxrange"):
                raise _Ineligible(base)
            vexpr, is_int, max_abs = compile_vexpr(vspec)
            if base in ("sum", "avg"):
                aggs.append((base, vexpr, _limbs_for(max_abs) if is_int
                             else None))
            else:
                # min/max rows reduce in f32: int values >= 2^24 would round
                # (the jnp kernel keeps them exact in i32) -> ineligible;
                # i64 limb planes are sum-only (covered by this bound too)
                if is_int and max_abs >= _F32_EXACT:
                    raise _Ineligible("int min/max not f32-exact")
                aggs.append((base, vexpr, None))
        # runtime protocol mirror: every eligible plan must have walked
        # the cursor to the end (an unconsumed tail is pack/unpack drift,
        # not ineligibility — let the AssertionError propagate)
        pc.finish()
    except _Ineligible as e:
        from pinot_tpu.common.tracing import classify_decline

        reason = classify_decline(str(e))
        if not reason.startswith("pallas_"):
            # messages raised with bare op names (filter/agg ops outside
            # the covered set) classify through the generic fallback;
            # namespace them so the histogram reads per decision point
            reason = f"pallas_{reason}"
        decline(reason)
        return None

    params = np.asarray([v for lo, hi in intervals for v in (lo, hi)],
                        dtype=np.int32).reshape(-1)
    return PallasPlan(
        packed_names=packed_names, value_names=value_names,
        value_is_int=tuple(value_is_int), filter_tree=tree,
        n_slots=len(intervals), group_idx=tuple(group_idx),
        group_strides=tuple(strides), group_key_offset=key_offset,
        num_groups_padded=G,
        aggs=tuple(aggs), static_params=params,
        value_limbs=tuple(value_limbs))


def _has_v64(vexpr: Tuple) -> bool:
    if vexpr[0] == "v64":
        return True
    if vexpr[0] in ("v", "litc", "litf", "id"):
        return False
    return _has_v64(vexpr[1]) or _has_v64(vexpr[2])


# --------------------------------------------------------------------------
# group-range probe: the narrowing pass that puts LARGE-but-sparse composed
# key spaces (SSB Q3.2/Q4.3: city x city x year, brand x city x year) on the
# dense one-hot rung. The filter makes those spaces sparse (only one
# nation's cities, one category's brands survive), but plan-time narrowing
# can only use predicates ON the group columns themselves. The probe runs
# the SAME fused scan (unpack + filter) with per-group-column masked
# min/max-of-dictId aggregations — a tiny min/max-row kernel, no matmul —
# and the host narrows each column's key range to the observed [lo, hi]
# before building the real kernel (plan.narrow_plan_groups rewrites
# strides/bases, so decode/merge machinery applies unchanged). Sorted
# dictionaries make the correlated value sets contiguous, so the narrowed
# product collapses to the live group count's scale.
# --------------------------------------------------------------------------

def probe_plan_of(pp: PallasPlan) -> PallasPlan:
    """Derive the group-range probe plan from an (unchecked-groups) full
    extraction: same packed columns / filter tree / interval params, no
    value inputs, and one (min, max) masked-dictId aggregation pair per
    group column via the ``("id", packed_idx)`` value node."""
    aggs: List[Tuple[str, Optional[Tuple], Optional[int]]] = []
    for gi in pp.group_idx:
        aggs.append(("min", ("id", gi), None))
        aggs.append(("max", ("id", gi), None))
    return PallasPlan(
        packed_names=list(pp.packed_names), value_names=[],
        value_is_int=(), filter_tree=pp.filter_tree, n_slots=pp.n_slots,
        group_idx=(), group_strides=(), group_key_offset=0,
        num_groups_padded=_G_CHUNK, aggs=tuple(aggs),
        static_params=pp.static_params, value_limbs=())


def decode_probe_ranges(spec: PallasSpec, out_mm,
                        n_cols: int) -> List[Tuple[int, int]]:
    """Probe kernel output -> per-group-column inclusive (lo, hi) observed
    dictId ranges. A column no matched row touched (min row still +inf)
    collapses to (0, 0) — a 1-slot key space is enough for an empty
    result."""
    _, _, mm_row, _, _, _ = _row_layout(spec)
    mm = np.asarray(out_mm)
    ranges: List[Tuple[int, int]] = []
    for i in range(n_cols):
        vexpr = spec.aggs[2 * i][1]
        lo = float(mm[mm_row[(vexpr, "min")], 0])
        hi = float(mm[mm_row[(vexpr, "max")], 0])
        if not (np.isfinite(lo) and np.isfinite(hi)) or lo > hi:
            ranges.append((0, 0))
        else:
            ranges.append((int(lo), int(hi)))
    return ranges


def probe_narrowed_plan(plan, provider, run_probe, lut_run_cap, decline
                        ) -> Optional[Tuple]:
    """Group-range narrowing orchestration shared by the per-segment and
    sharded callers: full unchecked extraction -> probe kernel (executed
    by ``run_probe(probe_pp, probe_spec_fn)``, which stages the packed
    inputs its own way and returns the out_mm rows) -> narrowed effective
    SegmentPlan -> re-extraction. Returns (PallasPlan, effective plan) or
    None (with the reason on ``decline``)."""
    from pinot_tpu.engine.plan import narrow_plan_groups

    pp_full = extract_plan(plan, provider, on_decline=decline,
                           lut_run_cap=lut_run_cap, unchecked_groups=True)
    if pp_full is None:
        return None
    # min/max rows reduce in f32: dictIds past 2^24 would round
    for card in plan.group_cards:
        if card >= _F32_EXACT:
            decline("pallas_too_many_groups")
            return None
    probe_pp = probe_plan_of(pp_full)
    out_mm = run_probe(probe_pp)
    if out_mm is None:
        return None   # run_probe recorded its own reason
    ranges = decode_probe_ranges(
        probe_pp.spec(num_segs=1, tiles_per_seg=1, interpret=True),
        out_mm, len(plan.group_cards))
    eff = narrow_plan_groups(plan, ranges)
    if eff.num_groups > MAX_PALLAS_GROUPS:
        decline("pallas_too_many_groups")
        return None
    pp = extract_plan(eff, provider, on_decline=decline,
                      lut_run_cap=lut_run_cap)
    if pp is None:
        return None
    return pp, eff


class _DeferredDecline:
    """Capture extract declines so the probe path can retry on
    ``pallas_too_many_groups`` without double-recording; ``flush`` forwards
    the captured reason when no retry succeeded."""

    def __init__(self, on_decline):
        self.on_decline = on_decline
        self.reasons: List[str] = []

    def __call__(self, reason: str) -> None:
        self.reasons.append(reason)

    @property
    def only_group_bound(self) -> bool:
        return self.reasons == ["pallas_too_many_groups"]

    def flush(self) -> None:
        if self.on_decline is not None:
            for r in self.reasons:
                self.on_decline(r)


# --------------------------------------------------------------------------
# kernel builder
# --------------------------------------------------------------------------

def _row_layout(spec: PallasSpec):
    """Single source of truth for the accumulator layout:
    - out_f [Mf, G] f32: per float sum a (sum, compensation) Neumaier ROW
      PAIR at (r, r+1) (>=1 row, dummy if none)
    - out_i [Mi, G] i32: row 0 = count; per int sum a base-2^12 carry-chain
      of ``L + 2`` accumulator rows starting at ``start`` (limb ``k``'s
      partials add at ``start + k``; the two extra rows absorb carries)
    - out_mm [Mm, G] f32: (vexpr, kind) min/max rows (>=1 row, dummy if none)
    Returns (fsum_row, isum_row, mm_row, Mf, Mi, Mm) where fsum_row maps
    vexpr -> sum-row index, isum_row maps vexpr -> (start_row, L), mm_row
    maps (vexpr, 'min'|'max') -> row index."""
    fsum_row: Dict[Tuple, int] = {}
    isum_row: Dict[Tuple, Tuple[int, int]] = {}
    mm_row: Dict[Tuple[Tuple, str], int] = {}
    next_i = 1
    for base, vexpr, limbs in spec.aggs:
        if base in ("sum", "avg"):
            if limbs is not None:
                if vexpr not in isum_row:
                    isum_row[vexpr] = (next_i, limbs)
                    next_i += limbs + 2
            else:
                fsum_row.setdefault(vexpr, 2 * len(fsum_row))
        elif base == "min":
            mm_row.setdefault((vexpr, "min"), len(mm_row))
        elif base == "max":
            mm_row.setdefault((vexpr, "max"), len(mm_row))
        elif base == "minmaxrange":
            mm_row.setdefault((vexpr, "min"), len(mm_row))
            mm_row.setdefault((vexpr, "max"), len(mm_row))
    Mf = max(2 * len(fsum_row), 1)
    Mi = next_i
    Mm = max(len(mm_row), 1)
    return fsum_row, isum_row, mm_row, Mf, Mi, Mm


def _expr_is_int(vexpr: Tuple, value_is_int: Tuple[bool, ...]) -> bool:
    if vexpr[0] == "v":
        return value_is_int[vexpr[1]]
    if vexpr[0] == "litc":
        return True
    if vexpr[0] == "litf":
        return False
    return (_expr_is_int(vexpr[1], value_is_int)
            and _expr_is_int(vexpr[2], value_is_int))


def accumulate_rows(num_groups_padded: int) -> Tuple[int, int, int]:
    """(H, Hp, rows_per_dot) of the two-level accumulate: the composed key
    splits into ``hi = key >> 7`` in [0, H) and ``lo = key & 127``; a
    sum/count accumulator row holds its groups as Hp sublane rows of 128
    lanes — 1 when H == 1, else H rounded up to whole (8, 128) vregs; one
    matmul takes ``rows_per_dot`` matmul rows, expanded to at most
    _EXPAND_ROWS LHS rows (the working set tools/preflight.py budgets)."""
    H = num_groups_padded // _G_CHUNK
    Hp = 1 if H == 1 else -(-H // 8) * 8
    return H, Hp, max(1, _EXPAND_ROWS // Hp)


def padded_groups(plan_spec: Tuple) -> int:
    """``num_groups_padded`` of the kernel that serves a plan spec: its
    group count rounded up to whole 128-lane chunks; a scalar aggregation
    is a single group at key 0."""
    _, _, group_specs, num_groups, _ = plan_spec
    if not group_specs:
        return _G_CHUNK
    return -(-num_groups // _G_CHUNK) * _G_CHUNK


def builds_one_hot(grouped: bool, agg_bases) -> bool:
    """Whether the kernel accumulates through the key's one-hot matmul. A
    scalar key space (no group columns, every key 0) with no sum rows
    builds none: its count is the tile's mask sum and its min/max rows
    reduce over the tile (the group-range probe is this case)."""
    return grouped or any(b in ("sum", "avg") for b in agg_bases)


def accumulate_kind(num_groups_padded: int, one_hot: bool) -> str:
    """What a span or counter calls the accumulate a spec takes."""
    if not one_hot:
        return "scalar"
    return "single" if num_groups_padded <= _G_CHUNK else "two_level"


def spec_accumulate_kind(spec: PallasSpec) -> str:
    """``accumulate_kind`` of the kernel ``build_kernel(spec)`` builds."""
    return accumulate_kind(spec.num_groups_padded, builds_one_hot(
        bool(spec.group_idx), (base for base, _v, _l in spec.aggs)))


def spec_mxu_kind(spec: PallasSpec) -> Optional[str]:
    """The MXU contraction of the kernel ``build_kernel(spec)`` builds:
    ``bf16`` where every matmul row is an integer row (count, limb
    halves) in one bf16 pass, ``fp32`` where float-sum rows add an fp32
    contraction of their own beside it, None where no one-hot is built."""
    if spec_accumulate_kind(spec) == "scalar":
        return None
    return "fp32" if _row_layout(spec)[0] else "bf16"


def build_kernel(spec: PallasSpec):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = PALLAS_TILE
    RT = T // 128
    G = spec.num_groups_padded
    H, Hp, rows_per_dot = accumulate_rows(G)
    # a scalar key space (no group columns, every key 0) reduces min/max
    # rows over the tile, and with no sum rows builds no one-hot at all
    scalar = not spec.group_idx
    one_hot = spec_accumulate_kind(spec) != "scalar"
    n_packed = len(spec.packed_bits)
    n_values = len(spec.value_is_int)
    # per value input: how many refs it occupies (1 plain array, or L
    # pre-split 12-bit limb planes for i64-staged columns) and where its
    # ref block starts
    vlimbs = spec.value_limbs or (0,) * n_values
    v_start: List[int] = []
    n_value_refs = 0
    for l in vlimbs:
        v_start.append(n_value_refs)
        n_value_refs += l if l else 1
    S = spec.num_segs
    TPS = spec.tiles_per_seg

    fsum_row, isum_row, mm_row, Mf, Mi, Mm = _row_layout(spec)
    # matmul row plans: the float-sum rows (fp32 contraction) in out_f row
    # order; the integer rows (one bf16 pass) are [1 count row][per int
    # sum, per limb: its low byte, its high half], and int_target[m] =
    # (out_i row, left shift) that integer row m's partial adds at
    float_sums = sorted(fsum_row.items(), key=lambda kv: kv[1])
    int_sums = sorted(isum_row.items(), key=lambda kv: kv[1][0])
    int_target = [(0, 0)]
    for _vexpr, (start, L) in int_sums:
        for k in range(L):
            int_target += [(start + k, 0), (start + k, _HALF_BITS)]

    def acc(r):
        """Accumulator row ``r``'s [Hp, 128] block of out_f / out_i: group
        h * 128 + l sits at [r * Hp + h, l] (whole vregs for H > 1)."""
        return slice(r * Hp, (r + 1) * Hp)

    # params: [2*n_slots intervals][S num_docs][1 doc_base], held in SMEM as
    # one [1, n] row — 2-D so that a vmap over params (the launcher's
    # coalesced form) squeezes a LEADING dim and leaves a legal block
    nd_off = 2 * spec.n_slots

    def kernel(params_ref, *refs):
        packed = refs[:n_packed]
        values = refs[n_packed:n_packed + n_value_refs]
        out_f, out_i, out_mm, out_seg = refs[n_packed + n_value_refs:]
        s = pl.program_id(0)
        t = pl.program_id(1)

        @pl.when((s == 0) & (t == 0))
        def _init_global():
            out_f[...] = jnp.zeros_like(out_f)
            out_i[...] = jnp.zeros_like(out_i)
            for (vexpr, kind), r in mm_row.items():
                out_mm[r, :] = jnp.full((G,), _POS if kind == "min" else _NEG,
                                        dtype=jnp.float32)
            if not mm_row:
                out_mm[...] = jnp.zeros_like(out_mm)

        @pl.when(t == 0)
        def _init_seg():
            out_seg[...] = jnp.zeros_like(out_seg)

        # -- unpack planar words -> dictIds [RT, 128] i32 per column
        ids = []
        for ci, bits in enumerate(spec.packed_bits):
            K = 32 // bits
            vmask = jnp.uint32((1 << bits) - 1)
            w = packed[ci][0, 0]                   # [W/128, 128] u32
            planes = [((w >> jnp.uint32(k * bits)) & vmask).astype(jnp.int32)
                      for k in range(K)]
            ids.append(planes[0] if K == 1 else
                       jnp.concatenate(planes, axis=0))  # [RT, 128]

        # -- validity + filter expression
        num_docs = params_ref[0, nd_off + s]
        doc_base = params_ref[0, nd_off + S]
        row = jax.lax.broadcasted_iota(jnp.int32, (RT, 128), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (RT, 128), 1)
        doc = doc_base + t * T + row * 128 + lane
        valid = doc < num_docs

        def emit(node):
            op = node[0]
            if op == "true":
                return jnp.ones((RT, 128), dtype=bool)
            if op == "and":
                m = emit(node[1][0])
                for c in node[1][1:]:
                    m = m & emit(c)
                return m
            if op == "or":
                m = emit(node[1][0])
                for c in node[1][1:]:
                    m = m | emit(c)
                return m
            if op == "not":
                return ~emit(node[1][0])
            if op == "ivs":
                # interval-set fallback for many-run LUTs: OR over a
                # pow2-padded block of runtime interval slots (pads are
                # empty (1, 0) intervals matching nothing)
                _, pi, slot0, n_runs = node
                m = jnp.zeros((RT, 128), dtype=bool)
                for j in range(n_runs):
                    lo = params_ref[0, 2 * (slot0 + j)]
                    hi = params_ref[0, 2 * (slot0 + j) + 1]
                    m = m | ((ids[pi] >= lo) & (ids[pi] <= hi))
                return m
            _, pi, slot = node                     # "iv"
            lo = params_ref[0, 2 * slot]
            hi = params_ref[0, 2 * slot + 1]
            return (ids[pi] >= lo) & (ids[pi] <= hi)

        mask = emit(spec.filter_tree) & valid

        # -- value expressions [RT, 128]: int exprs evaluate exactly in i32
        # (plan-time bound check), float exprs in f32 (the vectorized form
        # of the reference's transform-then-aggregate chain)
        vexpr_cache: Dict[Tuple, Any] = {}

        def emit_vexpr(vexpr):
            v = vexpr_cache.get(vexpr)
            if v is not None:
                return v
            if vexpr[0] == "v64":
                # limb planes carry no single per-doc value; extract_plan
                # keeps them sum-only (their limb rows read planes directly)
                raise AssertionError("v64 leaves never emit as values")
            if vexpr[0] == "id":
                # unpacked dictIds as a value row (the group-range probe's
                # masked min/max-of-id aggregations)
                v = ids[vexpr[1]]
            elif vexpr[0] == "v":
                v = values[v_start[vexpr[1]]][0, 0]
            elif vexpr[0] == "litc":
                v = jnp.int32(vexpr[1])
            elif vexpr[0] == "litf":
                v = jnp.float32(vexpr[1])
            else:
                a = emit_vexpr(vexpr[1])
                b = emit_vexpr(vexpr[2])
                if not (_expr_is_int(vexpr[1], spec.value_is_int)
                        and _expr_is_int(vexpr[2], spec.value_is_int)):
                    a = a.astype(jnp.float32)
                    b = b.astype(jnp.float32)
                if vexpr[0] == "times":
                    v = a * b
                elif vexpr[0] == "plus":
                    v = a + b
                else:
                    v = a - b
            vexpr_cache[vexpr] = v
            return v

        # -- composed group keys (all zero for scalar aggregation); masked
        # docs outside a narrowed key range go negative and simply match no
        # one-hot column (their rows are mask-zeroed anyway)
        keys = jnp.zeros((RT, 128), dtype=jnp.int32)
        for gi, stride in zip(spec.group_idx, spec.group_strides):
            keys = keys + ids[gi] * jnp.int32(stride)
        if spec.group_key_offset:
            keys = keys - jnp.int32(spec.group_key_offset)

        # -- per-segment matched docs (QueryStats parity), exact i32: the
        # tile's four 8-row slabs add into the segment's [8, 128] block
        # (whole vregs; a (1, 128) block is legal only when S == 1)
        m_i = mask.astype(jnp.int32)
        out_seg[0] += sum(m_i[r:r + 8] for r in range(0, RT, 8))

        if not one_hot:
            # -- count row: the tile's exact mask sum, as per-lane
            # partials the wrapper folds (no one-hot, no matmul)
            out_i[acc(0)] += m_i.sum(axis=0, keepdims=True)
        else:
            # -- integer matmul rows [1 + 2 * sum(L), T] (docs flattened):
            # the count row, then each limb as its two bf16-exact halves
            int_rows = [m_i]                       # count row (out_i row 0)
            for vexpr, (start, L) in int_sums:
                if vexpr[0] == "v64":
                    # i64-staged column: the limbs ARE the staged planes
                    # (host-split with the identical shift/mask scheme), so
                    # the accumulation below is bit-for-bit the in-kernel
                    # split
                    base_ref = v_start[vexpr[1]]
                    limbs = [jnp.where(mask, values[base_ref + k][0, 0], 0)
                             for k in range(L)]
                else:
                    v = jnp.where(mask, emit_vexpr(vexpr), 0)
                    # the top limb keeps the sign (arithmetic shift)
                    limbs = [(v >> (k * _LIMB_BITS)) & _LIMB_MASK
                             if k < L - 1 else v >> (k * _LIMB_BITS)
                             for k in range(L)]
                for limb in limbs:
                    int_rows += [limb & _HALF_MASK, limb >> _HALF_BITS]
            R = jnp.stack(int_rows).astype(jnp.bfloat16).reshape(
                len(int_rows), T)

            # -- two-level one-hot accumulate: group g = hi * 128 + lo. ONE
            # [128, T] one-hot of ``lo`` a tile; ``hi`` expands every matmul
            # row into Hp rows (row m*Hp + h keeps the docs whose hi == h), so
            # part[m*Hp + h, l] is row m's partial of group h*128 + l and the
            # MXU sees a full-height LHS once, not M rows against G/128
            # one-hots. H == 1 (scalar aggregations, <= 128 groups) needs no
            # expansion: the rows go in as they are. A plain 2-D matmul over
            # the tile's flattened docs: Mosaic has no dot_general with two
            # contracting dims, and takes the (RT, 128) -> T flattening of
            # the few matmul rows as a relayout. The one-hot (0/1) and the
            # integer rows (0/1, a limb's low byte, its high half; an
            # expanded row holds those or 0) are bf16-exact, so they take ONE
            # default-precision bf16 pass whose f32 partials are exact
            # integers (the argument above); float-sum rows take an fp32
            # contraction against the same one-hot.
            # The one-hot keeps every key on its lane: groups on sublanes,
            # docs on lanes, one [128, 128] block a key row (that row
            # broadcast down the sublanes), the blocks side by side along
            # lanes in the rows' flattened doc order. Each 32-bit word holds
            # two groups' bf16 entries, the even one's in its low half (the
            # packed bf16 layout ``pltpu.bitcast`` reads), so a block is 64
            # compares and selects of whole words; the contraction on the
            # docs axis of both operands latches the one-hot transposed into
            # the MXU. Masked docs outside a narrowed key range: a negative
            # key's pair is negative, and with H == 1 a key >= 128 has a
            # pair >= 64, so neither matches a group (their rows are
            # mask-zeroed anyway)
            lo = keys if H == 1 else keys & (_G_CHUNK - 1)
            pair = lo >> 1
            one = jnp.where((lo & 1) == 0, _BF16_ONE, _BF16_ONE << 16)
            # lax ops, not jnp's indexing and where: Mosaic gets the same
            # ops, and the 32 blocks trace and lower in about a third less
            # time (the warm-up lowers every scan program, cached or not)
            block = (_G_CHUNK // 2, 128)
            pair_iota = jax.lax.broadcasted_iota(jnp.int32, block, 0)
            no_word = jnp.zeros(block, jnp.int32)

            def word_block(r):
                row = (r + 1, 128)
                hit = jax.lax.slice(pair, (r, 0), row) == pair_iota
                word = jax.lax.broadcast_in_dim(
                    jax.lax.slice(one, (r, 0), row), block, (0, 1))
                return pltpu.bitcast(jax.lax.select(hit, word, no_word),
                                     jnp.bfloat16)

            oh_lo = jnp.concatenate([word_block(r) for r in range(RT)],
                                    axis=1)
            if H > 1:
                # masked docs outside a narrowed key range: an arithmetic
                # shift leaves hi negative or >= H, which selects no row (or a
                # pad row h in [H, Hp) that the wrapper drops; their values
                # are mask-zeroed anyway)
                hi = (keys >> 7).reshape(1, T)
                sel = hi == jax.lax.broadcasted_iota(jnp.int32, (Hp, T), 0)

            def accumulate(rows, oh, precision, land):
                """``rows`` [M, T] against ``oh`` [128, T] (both of one
                dtype) on the docs axis of both, in blocks of
                rows_per_dot matmul rows; ``land(m, x)`` takes row m's
                [Hp, 128] partial. bf16 rows expand by a 0/1 product
                (Mosaic cannot relayout a bool mask onto bf16's (16, 128)
                tiles; an integer row is finite, so the product is the
                select)."""
                if H > 1 and rows.dtype != jnp.float32:
                    sel_r = sel.astype(rows.dtype)
                for m0 in range(0, rows.shape[0], rows_per_dot):
                    m1 = min(m0 + rows_per_dot, rows.shape[0])
                    if H == 1:
                        lhs = rows[m0:m1]
                    elif rows.dtype == jnp.float32:
                        lhs = jnp.concatenate(
                            [jnp.where(sel, rows[m:m + 1], 0.0)
                             for m in range(m0, m1)], axis=0)
                    else:
                        lhs = jnp.concatenate(
                            [sel_r * rows[m:m + 1] for m in range(m0, m1)],
                            axis=0)
                    part = jax.lax.dot_general(
                        lhs, oh, (((1,), (1,)), ((), ())),
                        precision=precision,
                        preferred_element_type=jnp.float32)
                    for m in range(m0, m1):
                        land(m, part[(m - m0) * Hp:(m - m0 + 1) * Hp])

            def land_int(m, x):
                # count + limb-half partials: f32 -> exact i32 (each is an
                # integer < 2^24), a high half's shifted back into its limb
                r, shift = int_target[m]
                xi = x.astype(jnp.int32)
                out_i[acc(r)] += (xi << shift) if shift else xi

            accumulate(R, oh_lo, None, land_int)

            if float_sums:
                def land_float(m, x):
                    # float sums: Neumaier-compensated (sum, comp) pair
                    r = float_sums[m][1]
                    a = out_f[acc(r)]
                    t_ = a + x
                    err = jnp.where(jnp.abs(a) >= jnp.abs(x),
                                    (a - t_) + x, (x - t_) + a)
                    out_f[acc(r)] = t_
                    out_f[acc(r + 1)] += err

                mask_f = mask.astype(jnp.float32)
                F = jnp.stack([emit_vexpr(vexpr).astype(jnp.float32) * mask_f
                               for vexpr, _r in float_sums]).reshape(
                    len(float_sums), T)
                accumulate(F, oh_lo.astype(jnp.float32),
                           jax.lax.Precision.HIGHEST, land_float)

        if scalar:
            # -- min/max rows of a scalar key space: per-lane partials
            # over the tile's sublanes, folded by the wrapper
            for (vexpr, kind), r in mm_row.items():
                neutral = _POS if kind == "min" else _NEG
                vm = jnp.where(mask, emit_vexpr(vexpr).astype(jnp.float32),
                               neutral)
                red = vm.min(axis=0) if kind == "min" else vm.max(axis=0)
                cur = out_mm[r, :]
                out_mm[r, :] = (jnp.minimum(cur, red) if kind == "min"
                                else jnp.maximum(cur, red))

        # -- min/max rows reduce on the VPU per 128-group chunk
        for c in range(H if mm_row and not scalar else 0):
            g0 = c * _G_CHUNK
            eq = keys[:, :, None] == g0 + jax.lax.broadcasted_iota(
                jnp.int32, (RT, 128, _G_CHUNK), 2)
            for (vexpr, kind), r in mm_row.items():
                neutral = _POS if kind == "min" else _NEG
                v = emit_vexpr(vexpr).astype(jnp.float32)
                vm = jnp.where(mask, v, neutral)
                v3 = jnp.where(eq, vm[:, :, None], neutral)
                red = (v3.min(axis=(0, 1)) if kind == "min"
                       else v3.max(axis=(0, 1)))
                cur = out_mm[r, g0:g0 + _G_CHUNK]
                out_mm[r, g0:g0 + _G_CHUNK] = (
                    jnp.minimum(cur, red) if kind == "min"
                    else jnp.maximum(cur, red))

        # -- carry-chain normalization: every limb accumulator returns to
        # [0, 2^12) (arithmetic shift floors, so signed top limbs carry
        # correctly); the chain's top row absorbs the running magnitude,
        # keeping every row i32-bounded regardless of provider size
        for vexpr, (start, L) in int_sums:
            for k in range(L + 1):                 # rows start .. start+L
                a = out_i[acc(start + k)]
                carry = a >> _LIMB_BITS
                out_i[acc(start + k)] = a - (carry << _LIMB_BITS)
                out_i[acc(start + k + 1)] += carry

    def block(shape0):
        nd = len(shape0)
        return pl.BlockSpec((1, 1) + shape0,
                            lambda s, t: (s, t) + (0,) * nd,
                            memory_space=pltpu.VMEM)

    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    for bits in spec.packed_bits:
        W = T // (32 // bits)
        in_specs.append(block((W // 128, 128)))
    for _ in range(n_value_refs):
        in_specs.append(block((RT, 128)))

    out_specs = (
        pl.BlockSpec((Mf * Hp, _G_CHUNK), lambda s, t: (0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((Mi * Hp, _G_CHUNK), lambda s, t: (0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((Mm, G), lambda s, t: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 8, 128), lambda s, t: (s, 0, 0),
                     memory_space=pltpu.VMEM),
    )
    out_shape = (
        jax.ShapeDtypeStruct((Mf * Hp, _G_CHUNK), jnp.float32),
        jax.ShapeDtypeStruct((Mi * Hp, _G_CHUNK), jnp.int32),
        jax.ShapeDtypeStruct((Mm, G), jnp.float32),
        jax.ShapeDtypeStruct((S, 8, 128), jnp.int32),
    )

    fused = pl.pallas_call(
        kernel,
        grid=(S, TPS),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=spec.interpret,
    )

    mm_is_min = np.zeros((Mm, 1), dtype=bool)
    for (_vexpr, kind), r in mm_row.items():
        mm_is_min[r] = kind == "min"

    def pallas_scan(params, *cols):
        """-> (out_f [Mf, G], out_i [Mi, G], out_mm [Mm, G], out_seg
        [S, 128]). Kernel body and index maps trace with 32-bit defaults:
        under jax_enable_x64 every weak Python scalar enters the jaxpr as
        a 64-bit literal, Mosaic has no 64 -> 32 conversion and wants i32
        from an index map (every operand is already a 32-bit array). The
        kernel's [rows * Hp, 128] sum/count accumulators are [rows, G]
        read row-major (less the pad rows h >= H). A scalar key space's
        per-lane partials fold here to one lane: out_mm [Mm, 1], and
        out_i [Mi, 1] where no one-hot counted."""
        with jax.enable_x64(False):
            out_f, out_i, out_mm, out_seg = fused(params.reshape(1, -1),
                                                  *cols)
            out_f = out_f.reshape(Mf, Hp * _G_CHUNK)[:, :G]
            out_i = out_i.reshape(Mi, Hp * _G_CHUNK)[:, :G]
            if not one_hot:
                out_i = out_i.sum(axis=1, keepdims=True)
            if scalar and mm_row:
                out_mm = jnp.where(mm_is_min,
                                   out_mm.min(axis=1, keepdims=True),
                                   out_mm.max(axis=1, keepdims=True))
            return out_f, out_i, out_mm, out_seg.sum(axis=1)

    return pallas_scan


class PallasKernelCache:
    """The per-segment path's compiled programs, one a (kernel spec,
    plan spec) pair: see ``segment_program``."""

    def __init__(self):
        self._cache: Dict[Tuple, Any] = {}

    def get(self, spec: PallasSpec, plan_spec: Optional[Tuple] = None):
        key = (spec, plan_spec)
        k = self._cache.get(key)
        if k is None:
            k = jax.jit(segment_program(spec, plan_spec))
            self._cache[key] = k
        return k

    def pop(self, spec: PallasSpec, plan_spec: Optional[Tuple] = None
            ) -> None:
        """Evict a kernel whose compile/run failed (the caller blocklists
        the plan shape; keeping the entry would only leak the closure)."""
        self._cache.pop((spec, plan_spec), None)

    def __len__(self):
        return len(self._cache)


# --------------------------------------------------------------------------
# output assembly: pallas accumulators -> jnp-kernel-shaped output tree
# --------------------------------------------------------------------------

def assemble_outputs(plan_spec: Tuple, spec: PallasSpec, out_f, out_i, out_mm,
                     seg_matched) -> Dict[str, Any]:
    """Map the pallas accumulators onto the jnp kernel's output tree so
    pack_outputs/unpack_outputs/decode apply unchanged. ``seg_matched`` is
    the [S] per-segment matched-doc count (summed over lanes, and over mesh
    axes by the sharded caller). Int sums re-combine their carry-chain rows
    as ``sum_k row_k * 2^(12k)`` in i64 (exact; the packed f64 output then
    carries them exactly to 2^53, the reference's own double-SUM contract)."""
    _, agg_specs, group_specs, num_groups, _ = plan_spec
    fsum_row, isum_row, mm_row, _, _, _ = _row_layout(spec)
    grouped = bool(group_specs)
    n = num_groups if grouped else 1
    counts = out_i[0, :n]

    def sum_leaf(vexpr, limbs):
        if limbs is None:
            r = fsum_row[vexpr]
            return (out_f[r, :n].astype(jnp.float64)
                    + out_f[r + 1, :n].astype(jnp.float64))
        start, L = isum_row[vexpr]
        acc = jnp.zeros((n,), dtype=jnp.int64)
        for k in range(L + 2):
            if k * _LIMB_BITS >= 63:
                # rows past the i64 range are provably zero (eligibility
                # bounds the exact sum inside i64); shifting >= 64 bits is
                # undefined, so skip them instead of lowering the shift
                continue
            acc = acc + (out_i[start + k, :n].astype(jnp.int64)
                         << (k * _LIMB_BITS))
        return acc

    out: Dict[str, Any] = {}
    if grouped:
        out["presence"] = counts
    else:
        out["num_matched"] = counts[0]
    for i, (base, vexpr, limbs) in enumerate(spec.aggs):
        if base == "count":
            leaf: Any = counts
        elif base in ("sum", "avg"):
            leaf = sum_leaf(vexpr, limbs)
            if base == "avg":
                leaf = (leaf, counts)
        elif base == "min":
            leaf = out_mm[mm_row[(vexpr, "min")], :n]
        elif base == "max":
            leaf = out_mm[mm_row[(vexpr, "max")], :n]
        else:  # minmaxrange
            leaf = (out_mm[mm_row[(vexpr, "min")], :n],
                    out_mm[mm_row[(vexpr, "max")], :n])
        if not grouped:
            leaf = (tuple(x[0] for x in leaf) if isinstance(leaf, tuple)
                    else leaf[0])
        out[f"agg{i}"] = leaf
    if seg_matched is not None:
        out["seg_matched"] = seg_matched
    return out


# --------------------------------------------------------------------------
# per-segment runner (engine/executor.py fallback path)
# --------------------------------------------------------------------------

def _stage_packed(pp: PallasPlan, staged: StagedSegment, decline):
    """(packed device words, bits) for the plan's packed columns, or None
    (reason recorded); ``segment_program`` cuts the words into blocks."""
    packed_cols = []
    bits = []
    for nm in pp.packed_names:
        pc = staged.packed_column(nm)
        if pc is None:
            decline("pallas_column_not_packable")
            return None
        bits.append(pc.bits)
        packed_cols.append(pc.words)
    return packed_cols, bits


def _stage_values(pp: PallasPlan, staged: StagedSegment, decline):
    """Value refs in kernel order: one f32/i32 array per plain input, L
    i32 limb planes per i64-staged input (the value-load layer of the
    multi-limb accumulation), cut into blocks by ``segment_program``.
    None (reason recorded) when a column can't serve the fused layout."""
    vlimbs = pp.value_limbs or (0,) * len(pp.value_names)
    value_cols = []
    for nm, L in zip(pp.value_names, vlimbs):
        if L:
            planes = staged.value_limb_planes(nm, L)
            if planes is None:
                decline("pallas_value_layout_unsupported")
                return None
            value_cols.extend(planes)
            continue
        v = staged.value_column(nm)
        if v is None or v.dtype not in (jnp.float32, jnp.int32):
            decline("pallas_value_layout_unsupported")
            return None
        value_cols.append(v)
    return value_cols


def segment_program(spec: PallasSpec, plan_spec: Optional[Tuple]):
    """fn(static_params, num_docs, packed_cols, value_cols) over ONE
    staged segment, everything from the runtime params to the answer in
    one program: the params vector, the blocks' tile shapes, the kernel,
    and with a ``plan_spec`` the packed f64 output vector
    (``assemble_outputs`` + ``pack_outputs``), without it the group-range
    probe's min/max rows. One dispatch a launch: the same steps run
    eagerly cost a dispatch each, some forty a query, and a request's
    thread pays each of them again for the interpreter lock when other
    requests run Python beside it."""
    from pinot_tpu.engine.kernels import pack_outputs

    call = build_kernel(spec)

    def pallas_scan_segment(static_params, num_docs, packed_cols,
                            value_cols):
        params = jnp.concatenate([
            static_params.astype(jnp.int32).reshape(-1),
            jnp.stack([num_docs.astype(jnp.int32), jnp.int32(0)])])
        packed = [c.reshape(1, -1, PALLAS_TILE // (32 // bits) // 128, 128)
                  for c, bits in zip(packed_cols, spec.packed_bits)]
        values = [v.reshape(1, -1, PALLAS_TILE // 128, 128)
                  for v in value_cols]
        out_f, out_i, out_mm, _seg = call(params, *packed, *values)
        if plan_spec is None:
            return out_mm
        return pack_outputs(assemble_outputs(plan_spec, spec, out_f, out_i,
                                             out_mm, seg_matched=None),
                            plan_spec)

    return pallas_scan_segment


def _segment_args(pp: PallasPlan, staged: StagedSegment):
    """The runtime params of a per-segment launch, as host arrays."""
    return (np.asarray(pp.static_params, dtype=np.int32).reshape(-1),
            np.int32(staged.num_docs))


def _run_probe_segment(probe_pp: PallasPlan, staged: StagedSegment,
                       cache: PallasKernelCache, interpret: bool, decline,
                       on_launch=None):
    """Launch the group-range probe over one staged segment -> out_mm;
    ``on_launch`` receives the launch's accumulate kind."""
    got = _stage_packed(probe_pp, staged, decline)
    if got is None:
        return None
    packed_cols, bits = got
    tiles = staged.pallas_capacity() // PALLAS_TILE
    spec = _with_bits(
        probe_pp.spec(num_segs=1, tiles_per_seg=tiles, interpret=interpret),
        tuple(bits))
    kernel = cache.get(spec)
    try:
        out_mm = kernel(*_segment_args(probe_pp, staged), packed_cols, [])
    except Exception:
        cache.pop(spec)
        raise
    if on_launch is not None:
        on_launch(spec_accumulate_kind(spec))
    return out_mm


def run_segment(plan, staged: StagedSegment, cache: PallasKernelCache,
                interpret: bool, on_decline=None,
                lut_run_cap: int = DEFAULT_LUT_RUN_CAP, on_probe=None,
                on_launch=None):
    """Run the fused kernel over one staged segment; returns
    ``(packed, effective_plan)`` — the PACKED f64 output vector
    (kernels.pack_outputs layout, single D2H fetch) plus the plan whose
    spec describes it (the original plan, or the probe-narrowed plan for
    large-group shapes; the caller MUST unpack/decode against it) — or
    None when the plan/staging isn't eligible (``on_decline`` receives the
    reason code, same contract as ``extract_plan``). ``on_probe`` receives
    the accumulate kind of each group-range probe launched, ``on_launch``
    the PallasSpec of the scan launched."""
    def decline(reason: str) -> None:
        if on_decline is not None:
            on_decline(reason)

    defer = _DeferredDecline(on_decline)
    pp = extract_plan(plan, staged.segment, on_decline=defer,
                      lut_run_cap=lut_run_cap)
    eff = plan
    if pp is None:
        if not defer.only_group_bound:
            defer.flush()
            return None

        def run_probe(probe_pp):
            return _run_probe_segment(probe_pp, staged, cache, interpret,
                                      decline, on_probe)

        res = probe_narrowed_plan(plan, staged.segment, run_probe,
                                  lut_run_cap, decline)
        if res is None:
            return None
        pp, eff = res

    got = _stage_packed(pp, staged, decline)
    if got is None:
        return None
    packed_cols, bits = got
    value_cols = _stage_values(pp, staged, decline)
    if value_cols is None:
        return None

    tiles = staged.pallas_capacity() // PALLAS_TILE
    spec = pp.spec(num_segs=1, tiles_per_seg=tiles, interpret=interpret)
    spec = _with_bits(spec, tuple(bits))
    kernel = cache.get(spec, eff.spec)

    try:
        packed = kernel(*_segment_args(pp, staged), packed_cols, value_cols)
    except Exception:
        # symmetric with the sharded handler's eviction
        cache.pop(spec, eff.spec)
        raise
    if on_launch is not None:
        on_launch(spec)
    return packed, eff


def _with_bits(spec: PallasSpec, bits: Tuple[int, ...]) -> PallasSpec:
    from dataclasses import replace

    return replace(spec, packed_bits=bits)
