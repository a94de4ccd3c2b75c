"""Kernel preflight: static shape/dtype/memory verification of every
Pallas plan before it touches the chip.

The ROADMAP's remaining TPU risk is a runtime-discovery loop: ship a
round, watch Mosaic reject shapes, read ``pallas_exec_failed`` ledger
entries, fix, repeat — on scarce chip time. The lowering constraints that
loop discovers are PUBLISHED (tile alignment by dtype, ~16 MB VMEM per
core, small SMEM, supported dtypes — PAPERS.md: Jouppi et al. ISCA'23,
the JAX/Pallas references), so this module verifies them ahead of time:

- :class:`LoweringModel` — a pure-Python TPU lowering model: VMEM/SMEM
  budgets, lane/sublane tiling, the supported packed bit-widths, limb
  bounds — ASSUMPTIONS about one named ``device_kind`` (``TPU_V5E``),
  taken from published figures and not from the compiler. Numbers are
  deliberately conservative (utilization headroom for compiler scratch
  and double buffering). ``model_for`` refuses a device it has no model
  for.
- :func:`preflight_spec` — one concrete :class:`PallasSpec` against the
  model: mirrors ``build_kernel``'s exact BlockSpec/accumulator layout
  (via ``_row_layout``) and sizes every VMEM block, the matmul row stack,
  the one-hot and the expanded row block of the two-level accumulate, and
  the SMEM param vector. Emits a verdict
  row with the first violated rule's ``pallas_preflight_<rule>`` code
  (registered in ``tracing.PALLAS_PREFLIGHT_REASONS``).
- :func:`extract_query_spec` — a SegmentPlan to its concrete kernel spec
  the same way ``run_segment`` would (group-range probe narrowing
  included, probe runs in interpret mode), WITHOUT launching the real
  kernel.
- :func:`run_preflight` — the plan space: every SSB flight's extracted
  spec plus a fuzzed shape grid (limb counts, ivs run counts, remainder
  tiles, narrowed group ranges, packed widths) -> a per-shape verdict
  table.
- :func:`seed_blocklist` / :func:`attach_verdicts` — predicted-fail SSB
  shapes land in the executor's per-shape blocklist with their rule code,
  so the engine declines them loudly (``pallas_preflight_<rule>`` on the
  ledger) instead of dying inside Mosaic; the verdict table rides
  ``GET /debug/pallas`` and the bench round JSON.

``python -m pinot_tpu.tools.preflight`` builds a small SSB fixture and
prints the table (``--json`` for machines).
"""

from __future__ import annotations

import json

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from pinot_tpu.engine.staging import LIMB_BITS, PALLAS_TILE

# lane width / one-hot chunk (pallas_kernels._G_CHUNK)
_LANE = 128


@dataclass(frozen=True)
class _Rule:
    code: str        # the ledger reason (pallas_preflight_*)
    title: str       # one-line README/verdict-table description


# rule order is severity order: the FIRST violated rule is the verdict's
# primary code (a shape failing groups_bound usually fails vmem too — the
# cause, not the symptom, should reach the ledger)
RULES: Tuple[_Rule, ...] = (
    _Rule("pallas_preflight_groups_bound",
          "padded group count is lane-aligned (%128) and within "
          "MAX_PALLAS_GROUPS"),
    _Rule("pallas_preflight_tile_align",
          "packed bit-widths are word-aligned powers of two; every VMEM "
          "block is (sublane, 128k)-tiled for its dtype"),
    _Rule("pallas_preflight_dtype_unsupported",
          "ref dtypes stay in {u32, i32, f32}; limb planes only on "
          "integer inputs; plane counts consistent with value inputs"),
    _Rule("pallas_preflight_limb_planes",
          "limb counts cover <= i64 sums (L <= 6) and every per-tile "
          "limb partial is f32-exact"),
    _Rule("pallas_preflight_grid_bound",
          "grid dims positive and the step count bounded"),
    _Rule("pallas_preflight_smem_budget",
          "SMEM scalar params (interval slots + per-segment doc counts) "
          "fit the scalar-memory budget"),
    _Rule("pallas_preflight_vmem_budget",
          "per-step VMEM working set (blocks + matmul row stack + "
          "one-hot + expanded row block) fits the ~16 MB/core budget"),
)


@dataclass(frozen=True)
class LoweringModel:
    """Assumed lowering limits of ONE device kind. The device-specific
    fields carry no default: a model exists only for a device somebody
    wrote the assumptions down for."""

    device_kind: str                  # jax Device.device_kind it speaks for
    vmem_bytes: int                   # scoped VMEM a kernel may assume
    # headroom for compiler scratch, double buffering, and spills the
    # model cannot see — the budget the working set must fit
    vmem_utilization: float
    # modeled SMEM capacity in i32 scalar slots for the params vector
    smem_slots: int
    lane: int = _LANE
    sublane_f32: int = 8
    # planar unpack requires word-aligned widths (staging.pack_bits)
    packed_bits_ok: Tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    max_groups: int = 8192            # pallas_kernels.MAX_PALLAS_GROUPS
    max_limbs: int = 6                # ceil(62 bits / 12-bit limbs)
    max_grid_steps: int = 1 << 24

    @property
    def vmem_budget(self) -> int:
        return int(self.vmem_bytes * self.vmem_utilization)


# One v5e TensorCore, as the pallas guide describes it: ~16 MB of VMEM a
# kernel can count on, a small SMEM, (8, 128) min tile for 32-bit dtypes,
# MXU 128x128. Assumed, not measured: chip_smoke.py prints this model's
# verdict beside what the compiler did.
TPU_V5E = LoweringModel(device_kind="TPU v5 lite", vmem_bytes=16 * 2 ** 20,
                        vmem_utilization=0.75, smem_slots=1024)

_MODELS = {m.device_kind: m for m in (TPU_V5E,)}


def model_for(device_kind: str) -> LoweringModel:
    """The lowering model written for ``device_kind``; an unknown device
    is an error, never a default."""
    try:
        return _MODELS[device_kind]
    except KeyError:
        raise ValueError(
            f"no lowering model for device kind {device_kind!r} "
            f"(known: {sorted(_MODELS)})") from None


@dataclass
class Verdict:
    """One shape's preflight outcome."""

    shape: str                        # human label (qid or fuzz label)
    source: str                       # "ssb" | "fuzz"
    ok: bool
    rule: Optional[str] = None        # first violated rule's code
    detail: str = ""
    vmem_bytes: int = 0
    smem_slots: int = 0
    failures: List[Tuple[str, str]] = field(default_factory=list)

    def row(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "shape": self.shape, "source": self.source,
            "verdict": "pass" if self.ok else "fail",
            "vmem_bytes": self.vmem_bytes, "smem_slots": self.smem_slots,
        }
        if not self.ok:
            out["rule"] = self.rule
            out["detail"] = self.detail
            if len(self.failures) > 1:
                out["also"] = [r for r, _ in self.failures[1:]]
        return out


# --------------------------------------------------------------------------
# the lowering model applied to one concrete PallasSpec
# --------------------------------------------------------------------------

def _vmem_estimate(spec, model: LoweringModel) -> int:
    """Per-grid-step VMEM bytes: every BlockSpec block build_kernel binds
    plus the kernel's large intermediates (matmul row stacks, the bf16
    one-hot of the key's low 7 bits, one expanded row block of the
    two-level accumulate, min-max select buffers). Mirrors
    pallas_kernels.build_kernel's layout via the same ``_row_layout`` and
    ``accumulate_rows``."""
    from pinot_tpu.engine.pallas_kernels import (
        _G_CHUNK,
        _row_layout,
        accumulate_rows,
        spec_accumulate_kind,
    )

    T = PALLAS_TILE
    _fsum, isum, mm_row, Mf, Mi, Mm = _row_layout(spec)
    G = spec.num_groups_padded
    H, Hp, rows_per_dot = accumulate_rows(G)
    n_values = len(spec.value_is_int)
    vlimbs = spec.value_limbs or (0,) * n_values
    n_value_refs = sum(l if l else 1 for l in vlimbs)

    total = 0
    # packed input blocks: (1, 1, W/128, 128) u32
    for bits in spec.packed_bits:
        vpw = 32 // max(1, bits)
        total += (T // max(1, vpw)) * 4
    # value ref blocks: (1, 1, RT, 128) i32/f32
    total += n_value_refs * T * 4
    # unpacked dictId planes [RT, 128] i32 per packed column
    total += len(spec.packed_bits) * T * 4
    # output accumulators (whole arrays resident across the grid): sum and
    # count rows as [rows * Hp, 128], min/max rows as [Mm, G]
    total += (Mf + Mi) * Hp * model.lane * 4 + Mm * G * 4
    total += model.sublane_f32 * model.lane * 4  # out_seg block (1, 8, 128)
    if spec_accumulate_kind(spec) != "scalar":
        # matmul row stacks: the integer rows [1 + 2 * limbs, T] bf16 (the
        # count row and each limb as two halves), the float-sum rows
        # [nf, T] f32
        n_int = 1 + 2 * sum(L for (_s, L) in isum.values())
        n_float = Mf // 2
        total += n_int * T * 2 + n_float * T * 4
        # the tile's one-hot, groups on sublanes: the key-pair and bf16-one
        # planes [RT, 128] i32 and the pair iota [_G_CHUNK / 2, 128] i32
        # it is built from, oh_lo [_G_CHUNK, T] bf16 (its 32-bit words
        # bitcast in place), and its f32 copy where float rows take the
        # fp32 contraction. Counted once: the RT selected [64, 128] word
        # blocks are the one-hot's own vregs, since a concatenate along
        # lanes at 128-lane boundaries copies nothing (Mosaic's dump of
        # Q2.2 latches each selected vreg into the MXU as it is made: 256
        # selects, 256 latches, 16 vector stores in the whole tile body),
        # so this term bounds the build from above
        total += 2 * T * 4 + _G_CHUNK // 2 * model.lane * 4
        total += _G_CHUNK * T * (2 + (4 if n_float else 0))
        if H > 1:
            # the hi-select mask [Hp, T] (and its bf16 0/1 copy), and the
            # larger of the two stacks' expanded LHS blocks (at most
            # _EXPAND_ROWS rows, whole stack rows) with its [rows, 128]
            # f32 partial
            rows_int = min(n_int, rows_per_dot) * Hp
            rows_float = min(n_float, rows_per_dot) * Hp
            total += Hp * T * (4 + 2) + max(
                rows_int * (T * 2 + model.lane * 4),
                rows_float * (T + model.lane) * 4)
    if mm_row:
        # min/max select buffers: eq + v3 over a chunk of groups, or one
        # masked [RT, 128] tile where the key space is scalar
        total += (T * 4 if not spec.group_idx
                  else 2 * T * model.lane * 4)
    return total


def preflight_spec(spec, model: Optional[LoweringModel] = None,
                   shape: str = "", source: str = "fuzz") -> Verdict:
    """Verify one concrete PallasSpec against the lowering model (the
    v5e assumptions unless told otherwise)."""
    model = model or TPU_V5E
    failures: List[Tuple[str, str]] = []

    def fail(code: str, detail: str) -> None:
        failures.append((code, detail))

    G = spec.num_groups_padded
    if G <= 0 or G % model.lane or G > model.max_groups:
        fail("pallas_preflight_groups_bound",
             f"padded groups {G} (lane {model.lane}, "
             f"max {model.max_groups})")

    for bits in spec.packed_bits:
        if bits not in model.packed_bits_ok:
            fail("pallas_preflight_tile_align",
                 f"packed width {bits} is not word-aligned "
                 f"({model.packed_bits_ok}); unpack planes would not "
                 f"tile to (sublane, {model.lane})")
            break

    n_values = len(spec.value_is_int)
    vlimbs = spec.value_limbs or (0,) * n_values
    if len(vlimbs) != n_values:
        fail("pallas_preflight_dtype_unsupported",
             f"value_limbs has {len(vlimbs)} entries for "
             f"{n_values} value inputs")
    else:
        for i, (l, is_int) in enumerate(zip(vlimbs, spec.value_is_int)):
            if l and not is_int:
                fail("pallas_preflight_dtype_unsupported",
                     f"value input {i} carries {l} limb planes but is "
                     f"not integral (planes are i32 slices of i64)")
                break

    agg_limbs = [limbs for (_b, _v, limbs) in spec.aggs
                 if limbs is not None]
    all_limbs = list(agg_limbs) + [l for l in vlimbs if l]
    if any(l <= 0 or l > model.max_limbs for l in all_limbs):
        fail("pallas_preflight_limb_planes",
             f"limb counts {sorted(set(all_limbs))} outside "
             f"[1, {model.max_limbs}] — i64 reassembly would shift past "
             f"the exactness bound")
    elif ((1 << LIMB_BITS) - 1) * PALLAS_TILE >= (1 << 24):
        fail("pallas_preflight_limb_planes",
             "per-tile limb partial not f32-exact")

    S, TPS = spec.num_segs, spec.tiles_per_seg
    if S < 1 or TPS < 1 or S * TPS > model.max_grid_steps:
        fail("pallas_preflight_grid_bound",
             f"grid ({S}, {TPS}) outside (1..{model.max_grid_steps})")

    smem = 2 * spec.n_slots + max(S, 0) + 1
    if smem > model.smem_slots:
        fail("pallas_preflight_smem_budget",
             f"{smem} scalar param slots ({spec.n_slots} intervals + "
             f"{S} doc counts) > {model.smem_slots}")

    vmem = _vmem_estimate(spec, model)
    if vmem > model.vmem_budget:
        fail("pallas_preflight_vmem_budget",
             f"{vmem} B working set > {model.vmem_budget} B "
             f"({model.vmem_bytes} B * {model.vmem_utilization})")

    order = {r.code: i for i, r in enumerate(RULES)}
    failures.sort(key=lambda f: order[f[0]])
    return Verdict(
        shape=shape, source=source, ok=not failures,
        rule=failures[0][0] if failures else None,
        detail=failures[0][1] if failures else "",
        vmem_bytes=vmem, smem_slots=smem, failures=failures)


# --------------------------------------------------------------------------
# SegmentPlan -> concrete PallasSpec (run_segment's extraction, no launch)
# --------------------------------------------------------------------------

def extract_query_spec(plan, staged, cache=None,
                       lut_run_cap: Optional[int] = None,
                       interpret: bool = True):
    """-> ``(spec, effective_plan, None)`` with the concrete PallasSpec
    ``run_segment`` would build for this plan over ``staged`` (group-range
    probe narrowing included — the probe kernel runs in interpret mode),
    or ``(None, None, reason)`` when the plan is not pallas-eligible."""
    from pinot_tpu.engine.pallas_kernels import (
        DEFAULT_LUT_RUN_CAP,
        PallasKernelCache,
        _DeferredDecline,
        _run_probe_segment,
        _stage_packed,
        _with_bits,
        extract_plan,
        probe_narrowed_plan,
    )

    cap = DEFAULT_LUT_RUN_CAP if lut_run_cap is None else lut_run_cap
    cache = cache if cache is not None else PallasKernelCache()
    reasons: List[str] = []
    defer = _DeferredDecline(reasons.append)
    pp = extract_plan(plan, staged.segment, on_decline=defer,
                      lut_run_cap=cap)
    eff = plan
    if pp is None:
        if not defer.only_group_bound:
            defer.flush()
            return None, None, (reasons or ["unknown"])[0]

        def run_probe(probe_pp):
            return _run_probe_segment(probe_pp, staged, cache, interpret,
                                      reasons.append)

        res = probe_narrowed_plan(plan, staged.segment, run_probe, cap,
                                  reasons.append)
        if res is None:
            return None, None, (reasons or ["unknown"])[0]
        pp, eff = res

    got = _stage_packed(pp, staged, reasons.append)
    if got is None:
        return None, None, (reasons or ["unknown"])[0]
    _cols, bits = got
    tiles = staged.pallas_capacity() // PALLAS_TILE
    spec = _with_bits(
        pp.spec(num_segs=1, tiles_per_seg=tiles, interpret=interpret),
        tuple(bits))
    return spec, eff, None


# --------------------------------------------------------------------------
# the fuzzed shape grid
# --------------------------------------------------------------------------

def _mk_spec(num_segs=1, tiles=3, bits=(8,), filter_tree=("true",),
             n_slots=0, groups=128, aggs=(("count", None, None),),
             value_is_int=(), value_limbs=()):
    """A hand-built PallasSpec for the fuzz grid (remainder-tile default:
    tiles=3 models a capacity % PALLAS_TILE != 0 segment), grouped on
    packed column 0 so that every shape takes the one-hot accumulate."""
    from pinot_tpu.engine.pallas_kernels import PallasSpec

    return PallasSpec(
        num_segs=num_segs, tiles_per_seg=tiles, packed_bits=tuple(bits),
        filter_tree=filter_tree, n_slots=n_slots, group_idx=(0,),
        group_strides=(1,), group_key_offset=0, num_groups_padded=groups,
        aggs=tuple(aggs), value_is_int=tuple(value_is_int),
        value_limbs=tuple(value_limbs), interpret=True)


def fuzz_specs() -> List[Tuple[str, Any]]:
    """The fuzzed plan-space grid: limb counts, ivs run counts, remainder
    tiles, narrowed group ranges, packed widths — passing shapes prove
    the model admits what the engine emits; failing shapes are the
    predicted-fail fixtures the tests pin rule codes on."""
    shapes: List[Tuple[str, Any]] = []
    fsum = (("sum", ("v", 0), None),)

    # limb planes: the full eligible range, then one past it
    for L in (1, 3, 6):
        shapes.append((f"limbs{L}", _mk_spec(
            aggs=(("sum", ("v64", 0), L),), value_is_int=(True,),
            value_limbs=(L,))))
    shapes.append(("limbs8_over", _mk_spec(
        aggs=(("sum", ("v64", 0), 8),), value_is_int=(True,),
        value_limbs=(8,))))
    shapes.append(("limbs_on_float", _mk_spec(
        aggs=fsum, value_is_int=(False,), value_limbs=(3,))))

    # interval-set runs: in-cap pads, then an SMEM-busting pad
    for runs in (8, 64, 128):
        shapes.append((f"ivs{runs}", _mk_spec(
            filter_tree=("ivs", 0, 0, runs), n_slots=runs,
            aggs=fsum, value_is_int=(False,), value_limbs=(0,))))
    shapes.append(("ivs512_over", _mk_spec(
        filter_tree=("ivs", 0, 0, 512), n_slots=512,
        aggs=fsum, value_is_int=(False,), value_limbs=(0,))))

    # narrowed group ranges: the dense rung's spectrum, then over/unpadded
    for g in (128, 1024, 8192):
        shapes.append((f"groups{g}", _mk_spec(groups=g)))
    # six matmul rows at the full fan-out: the two-level accumulate's
    # expanded row stack (6 x 64 rows) runs in blocks of _EXPAND_ROWS
    shapes.append(("rows6_groups8192", _mk_spec(
        groups=8192, aggs=(("sum", ("v", 0), None), ("avg", ("v", 1), None),
                           ("count", None, None), ("sum", ("v", 2), 3)),
        value_is_int=(False, False, True), value_limbs=(0, 0, 0))))
    shapes.append(("groups16384_over", _mk_spec(groups=16384)))
    shapes.append(("groups8100_unpadded", _mk_spec(groups=8100)))

    # packed widths: every word-aligned width, then a straddling one
    for b in (1, 2, 4, 8, 16, 32):
        shapes.append((f"bits{b}", _mk_spec(bits=(b,))))
    shapes.append(("bits6_straddle", _mk_spec(bits=(6,))))

    # remainder tiles / grid
    shapes.append(("tiles_remainder", _mk_spec(tiles=5)))
    shapes.append(("grid_zero_tiles", _mk_spec(tiles=0)))

    # a VMEM-busting wide-aggregation shape: 48 float sum+min pairs at
    # full group fan-out
    wide_aggs = tuple(("sum", ("v", i), None) for i in range(48)) \
        + tuple(("min", ("v", i), None) for i in range(48))
    shapes.append(("wide96_vmem_over", _mk_spec(
        groups=8192, aggs=wide_aggs, value_is_int=(False,) * 48,
        value_limbs=(0,) * 48)))
    return shapes


# --------------------------------------------------------------------------
# plan-space preflight: SSB matrix + fuzz grid -> verdict table
# --------------------------------------------------------------------------

def preflight_ssb_plans(segs, model: Optional[LoweringModel] = None,
                        lut_run_cap: Optional[int] = None
                        ) -> Tuple[List[Verdict], Dict[str, Tuple]]:
    """Every SSB flight's extracted concrete spec through the model.
    Returns (verdicts, {qid: original plan.spec}) — the plan specs are
    the blocklist keys ``seed_blocklist`` uses for predicted failures."""
    from pinot_tpu.engine.plan import plan_segment
    from pinot_tpu.engine.staging import StagingCache
    from pinot_tpu.query import compile_query
    from pinot_tpu.tools import ssb

    model = model or TPU_V5E
    staged = StagingCache().stage(segs[0])
    verdicts: List[Verdict] = []
    plan_specs: Dict[str, Tuple] = {}
    for qid in sorted(ssb.QUERIES):
        ctx = compile_query(ssb.QUERIES[qid] + " LIMIT 100000")
        plan = plan_segment(ctx, segs[0])
        spec, _eff, reason = extract_query_spec(plan, staged,
                                                lut_run_cap=lut_run_cap)
        if spec is None:
            # not pallas-eligible at all: that is an extraction decline
            # (classified), not a lowering prediction — record it as such
            verdicts.append(Verdict(
                shape=qid, source="ssb", ok=False,
                rule="pallas_preflight_grid_bound",
                detail=f"not extractable: {reason}"))
            plan_specs[qid] = plan.spec
            continue
        v = preflight_spec(spec, model, shape=qid, source="ssb")
        verdicts.append(v)
        plan_specs[qid] = plan.spec
    return verdicts, plan_specs


def run_preflight(segs=None, model: Optional[LoweringModel] = None,
                  lut_run_cap: Optional[int] = None,
                  fuzz: bool = True, rows: int = 6000) -> Dict[str, Any]:
    """The full plan-space preflight -> verdict table dict (the shape the
    bench round JSON and ``GET /debug/pallas`` carry). ``segs``: SSB
    segments to extract flight plans from; when None a small fixture set
    is built in a temp dir."""
    import tempfile

    from pinot_tpu.tools import ssb

    model = model or TPU_V5E
    if segs is None:
        with tempfile.TemporaryDirectory() as td:
            segs = ssb.build_segments(0, td, num_segments=2, rows=rows,
                                      workers=1)
            return run_preflight(segs, model, lut_run_cap, fuzz)
    ssb_verdicts, plan_specs = preflight_ssb_plans(segs, model,
                                                   lut_run_cap)
    verdicts = list(ssb_verdicts)
    if fuzz:
        for label, spec in fuzz_specs():
            verdicts.append(preflight_spec(spec, model, shape=label,
                                           source="fuzz"))
    table = {
        "model": {
            "device_kind": model.device_kind,
            "vmem_bytes": model.vmem_bytes,
            "vmem_utilization": model.vmem_utilization,
            "smem_slots": model.smem_slots,
            "max_groups": model.max_groups,
            "max_limbs": model.max_limbs,
        },
        "shapes": [v.row() for v in verdicts],
        "passed": sum(1 for v in verdicts if v.ok),
        "failed": sum(1 for v in verdicts if not v.ok),
        "ssb_failed": [v.shape for v in ssb_verdicts if not v.ok],
        "_plan_specs": plan_specs,   # stripped before serialization
    }
    return table


def serializable_table(table: Dict[str, Any]) -> Dict[str, Any]:
    """The verdict table without the in-memory plan-spec keys."""
    return {k: v for k, v in table.items() if not k.startswith("_")}


def seed_blocklist(blocklist, table: Dict[str, Any]) -> int:
    """Pre-seed predicted-fail SSB shapes into a per-shape blocklist with
    their ``pallas_preflight_<rule>`` reason; returns how many were
    seeded. The engine then declines those shapes loudly (ledger carries
    the rule) instead of discovering the failure inside Mosaic."""
    plan_specs = table.get("_plan_specs", {})
    n = 0
    for row in table["shapes"]:
        if row["source"] != "ssb" or row["verdict"] == "pass":
            continue
        spec = plan_specs.get(row["shape"])
        if spec is None:
            continue
        blocklist.add(spec, reason=row["rule"])
        n += 1
    return n


def attach_verdicts(executor, table: Dict[str, Any]) -> int:
    """Wire a preflight run into an executor: verdicts surface on
    ``GET /debug/pallas`` and predicted-fail shapes join its blocklist."""
    executor.preflight_verdicts = serializable_table(table)
    return seed_blocklist(executor._pallas_blocked, table)


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m pinot_tpu.tools.preflight",
        description="Static TPU lowering preflight over the SSB plan "
                    "matrix + a fuzzed shape grid.")
    ap.add_argument("--rows", type=int, default=6000,
                    help="fixture rows for SSB plan extraction")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--no-fuzz", action="store_true")
    args = ap.parse_args(argv)

    table = run_preflight(rows=args.rows, fuzz=not args.no_fuzz)
    out = serializable_table(table)
    if args.as_json:
        print(json.dumps(out, indent=1, sort_keys=True))
    else:
        for row in out["shapes"]:
            mark = "PASS" if row["verdict"] == "pass" else \
                f"FAIL {row['rule']}: {row['detail']}"
            print(f"{row['source']:4} {row['shape']:22} {mark}")
        print(f"preflight: {out['passed']} pass, {out['failed']} fail "
              f"(ssb failures: {out['ssb_failed'] or 'none'})")
    return 1 if out["ssb_failed"] else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
