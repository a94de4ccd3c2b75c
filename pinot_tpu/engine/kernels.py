"""Device kernels: spec -> jitted query function.

The TPU execution of the reference's per-segment operator chain
(``Filter -> DocIdSet -> Projection -> Transform -> Aggregate``, SURVEY.md
section 3.1 hot loop): instead of streaming 10k-doc blocks through iterators,
the whole segment is evaluated as fixed-shape masked vector ops that XLA
fuses into a few HBM passes:

- filter tree  -> boolean doc mask (vector compares / LUT gathers)
- projection   -> dictId gathers (``dictvals[fwd]``)
- aggregation  -> masked reductions; group-by via composed keys +
                  ``jax.ops.segment_sum`` scatter-adds (the fixed-shape
                  analogue of DictionaryBasedGroupKeyGenerator + GroupByResultHolder)

One kernel is built per *spec* (query structure + static sizes) and cached;
literal values arrive as device arrays so repeated query shapes skip
retracing entirely.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

POS_INF = float("inf")
NEG_INF = float("-inf")

# accumulator dtypes, chosen per aggregation at plan time from column stats
# (plan._acc_dtype): capacity-sized math runs narrow (v5e has no native
# f64/i64 units), partials widen to i64/f64 at kernel output so cross-segment
# merging is exact
_ACC = {"i32": jnp.int32, "i64": jnp.int64,
        "f32": jnp.float32, "f64": jnp.float64}

# None = backend-keyed (batched on TPU, split on CPU); tests override to
# exercise the batched branch on the CPU oracle
FORCE_BATCH_SCATTERS = None


def _acc_info(acc: str):
    """(dtype, widened dtype, min-neutral, max-neutral) for an acc tag."""
    dt = _ACC[acc]
    if acc in ("i32", "i64"):
        info = jnp.iinfo(dt)
        return dt, jnp.int64, info.max, info.min
    return dt, jnp.float64, POS_INF, NEG_INF


class _ParamCursor:
    """Walks the flat params tuple in the same order the planner wrote it."""

    def __init__(self, params):
        self.params = params
        self.i = 0

    def take(self):
        p = self.params[self.i]
        self.i += 1
        return p

    def finish(self):
        """Assert full consumption at kernel-build end — the runtime
        mirror of the lint protocol family, catching dynamically-built
        specs the static model can't prove. Trace-time only (``i`` is a
        plain int), so the check costs nothing per launch."""
        if self.i != len(self.params):
            raise AssertionError(
                f"param cursor finished at {self.i} of "
                f"{len(self.params)} params — pack/unpack drift between "
                f"plan.py and the kernel consumers")


# --------------------------------------------------------------------------
# filter mask emission
# --------------------------------------------------------------------------

def _emit_filter(spec: Tuple, cols: Dict[str, Dict[str, jnp.ndarray]],
                 pc: _ParamCursor, capacity: int) -> jnp.ndarray:
    op = spec[0]
    if op == "true":
        return jnp.ones(capacity, dtype=bool)
    if op == "false":
        return jnp.zeros(capacity, dtype=bool)
    if op == "validdocs":
        # upsert valid-doc snapshot [capacity] (plan.py injects the param)
        return pc.take()
    if op == "and":
        m = _emit_filter(spec[1][0], cols, pc, capacity)
        for s in spec[1][1:]:
            m = m & _emit_filter(s, cols, pc, capacity)
        return m
    if op == "or":
        m = _emit_filter(spec[1][0], cols, pc, capacity)
        for s in spec[1][1:]:
            m = m | _emit_filter(s, cols, pc, capacity)
        return m
    if op == "not":
        return ~_emit_filter(spec[1][0], cols, pc, capacity)

    col = spec[1]
    c = cols[col]

    # ---- dictionary SV strategies ----
    if op == "eq":
        return c["fwd"] == pc.take()
    if op == "neq":
        return c["fwd"] != pc.take()
    if op == "range":
        iv = pc.take()
        return (c["fwd"] >= iv[0]) & (c["fwd"] <= iv[1])
    if op == "lut":
        return pc.take()[c["fwd"]]

    # ---- dictionary MV strategies (ANY-value-matches semantics) ----
    if op.startswith("mv_"):
        mv, cnt = c["mv"], c["mvcount"]
        entry_valid = (jnp.arange(mv.shape[1], dtype=jnp.int32)[None, :]
                       < cnt[:, None])
        sub = op[3:]
        if sub == "eq":
            hit = mv == pc.take()
        elif sub == "neq":
            hit = mv != pc.take()
        elif sub == "range":
            iv = pc.take()
            hit = (mv >= iv[0]) & (mv <= iv[1])
        else:  # lut
            hit = pc.take()[mv]
        return (hit & entry_valid).any(axis=-1)

    # ---- raw-value strategies ----
    if op == "veq":
        return c["fwd"] == pc.take()
    if op == "vneq":
        return c["fwd"] != pc.take()
    if op == "vrange":
        lo, hi = pc.take(), pc.take()
        lo_inc, hi_inc = spec[2], spec[3]
        m = (c["fwd"] >= lo) if lo_inc else (c["fwd"] > lo)
        m &= (c["fwd"] <= hi) if hi_inc else (c["fwd"] < hi)
        return m
    if op in ("vin", "vnotin"):
        vals = pc.take()
        m = (c["fwd"][:, None] == vals[None, :]).any(axis=-1)
        return ~m if op == "vnotin" else m

    # ---- null strategies ----
    if op == "isnull":
        return c["null"]
    if op == "isnotnull":
        return ~c["null"]

    raise AssertionError(f"unknown filter op {op!r}")


# --------------------------------------------------------------------------
# value expression emission
# --------------------------------------------------------------------------

def _emit_value(vspec: Tuple, cols, pc: _ParamCursor,
                compute_dt=jnp.float32) -> jnp.ndarray:
    op = vspec[0]
    if op == "lit":
        return pc.take()
    if op == "col":
        _, name, has_dict = vspec
        c = cols[name]
        if has_dict:
            return c["dictvals"][c["fwd"]]
        return c["fwd"]
    if op == "fn":
        _, name, args = vspec
        vals = [_emit_value(a, cols, pc, compute_dt) for a in args]
        a = vals[0].astype(compute_dt) if hasattr(vals[0], "astype") else vals[0]
        b = vals[1].astype(compute_dt) if hasattr(vals[1], "astype") else vals[1]
        if name == "plus":
            return a + b
        if name == "minus":
            return a - b
        if name == "times":
            return a * b
        if name == "divide":
            return a / b
        if name == "mod":
            return a % b
        if name == "floordiv":
            return jnp.floor_divide(a, b)
    raise AssertionError(f"unknown value op {vspec!r}")


# --------------------------------------------------------------------------
# kernel factory
# --------------------------------------------------------------------------

def build_kernel_body(spec: Tuple, capacity_override: int = 0,
                      sparse_k: int = 0, sparse_rung: str = "cond"):
    """spec = (filter_spec, agg_specs, group_specs, num_groups, capacity)
    -> unjitted fn(cols, params, num_docs, doc_offset) -> dict of partials.

    ``doc_offset`` is the global doc index of local row 0 — nonzero when the
    doc dimension is sharded over a mesh axis (the sharded combine path
    evaluates each device's sub-range of the scan; ref: the doc-dimension
    "context parallelism" mapping, SURVEY.md §5). ``capacity_override``
    replaces the spec's capacity with the per-shard local capacity.
    ``sparse_k`` > 0 switches the group-by path to sparse grouping over K
    compact slots; ``sparse_rung`` picks how:

    - "cond" (per-segment default): hash-aggregate, with an in-kernel
      ``lax.cond`` falling back to the sort rung when the table overflows;
    - "hash": hash rung only — the ``"rung"`` output flags overflow and the
      caller must discard the (garbage) leaves and rerun the sort body.
      The sharded combine needs this split because a cond UNDER vmap
      lowers to select (both branches always execute, paying the sort);
    - "sort": the sort/compaction rung only.
    """
    filter_spec, agg_specs, group_specs, num_groups, capacity = spec
    if capacity_override:
        capacity = capacity_override

    def kernel(cols, params, num_docs, doc_offset):
        pc = _ParamCursor(params)
        mask = _emit_filter(filter_spec, cols, pc, capacity)
        valid = (jnp.arange(capacity, dtype=jnp.int32) + doc_offset) < num_docs
        mask = mask & valid

        if not group_specs:
            out: Dict[str, Any] = {
                "num_matched": mask.sum(dtype=jnp.int32).astype(jnp.int64)}
            for i, aspec in enumerate(agg_specs):
                out[f"agg{i}"] = _emit_scalar_agg(aspec, cols, pc, mask)
            pc.finish()
            return out

        # ---- group-by path ----
        strides = pc.take()           # [g] int32
        _bases = pc.take()            # [g] int64 (host uses for decode; keys
        #                               subtract base on device — nonzero for
        #                               graw/gexpr and for filter-narrowed
        #                               gdict columns, see plan.py)
        keys = jnp.zeros(capacity, dtype=jnp.int32)
        for gi, (strat, payload) in enumerate(group_specs):
            if strat == "gdict":
                k = cols[payload]["fwd"] - _bases[gi].astype(jnp.int32)
            elif strat == "graw":  # value-space key
                k = (cols[payload]["fwd"] - _bases[gi]).astype(jnp.int32)
            else:  # gexpr: bounded integral expression, key = value - lo
                v = _emit_value(payload, cols, pc, jnp.int64)
                k = (v - _bases[gi]).astype(jnp.int32)
            keys = keys + k * strides[gi]
        if sparse_k:
            return _emit_grouped_rung(agg_specs, cols, pc, mask, keys,
                                      num_groups, sparse_k, capacity,
                                      sparse_rung)
        seg_ids = jnp.where(mask, keys, num_groups)  # overflow bucket
        out = _emit_grouped_all(agg_specs, cols, pc, mask, seg_ids,
                                num_groups)
        pc.finish()
        return out

    return kernel


def compact_from_sorted(sk: jnp.ndarray, K: int):
    """Shared compaction core for BOTH sparse-grouping paths (the
    per-segment kernel here and the cross-device merge in
    parallel/combine.py): ``sk`` = ascending keys with _SENTINEL_KEY fill.
    Returns (first, n_live, uniq): first-occurrence flags over sk, the live
    distinct-key count, and the first K live keys (SENT-filled past
    n_live)."""
    SENT = jnp.int32(_SENTINEL_KEY)
    valid = sk != SENT
    first = valid & jnp.concatenate(
        [jnp.ones((1,), dtype=bool), sk[1:] != sk[:-1]])
    n_live = first.sum(dtype=jnp.int32)
    pos = jnp.nonzero(first, size=K, fill_value=sk.shape[0] - 1)[0]
    live = jnp.arange(K, dtype=jnp.int32) < jnp.minimum(n_live, K)
    uniq = jnp.where(live, sk[pos], SENT)
    return first, n_live, uniq


def _emit_grouped_sparse(agg_specs, cols, pc, mask, keys, num_groups, K):
    """Sort/compaction-based grouping for LARGE composed key spaces — the
    device rung of the reference's cardinality ladder past dense array
    holders (DictionaryBasedGroupKeyGenerator.java:62): sort the masked
    keys, compact the live groups into K slots, scatter aggregates over
    [K+1] instead of [num_groups+1]. The output is ALREADY compact
    ("ck" = sorted live composed keys, "compact_n" = live count); more
    than K live groups reports compact_n > K so the decode falls back to
    the host path instead of truncating."""
    SENT = jnp.int32(_SENTINEL_KEY)
    mk = jnp.where(mask, keys, SENT)
    sk = jnp.sort(mk)
    first, n_live, uniq = compact_from_sorted(sk, K)
    live = uniq != SENT
    # doc -> slot rank via a dense key-space LUT: ONE gather per doc (a
    # searchsorted would cost log2(K) gather passes on TPU). Fill slots
    # park at the LUT's overflow cell.
    lut = jnp.full((num_groups + 1,), jnp.int32(K))
    park = jnp.where(live, uniq, num_groups)
    lut = lut.at[park].set(
        jnp.where(live, jnp.arange(K, dtype=jnp.int32), K))
    rank = lut[jnp.clip(keys, 0, num_groups - 1)]
    seg_ids = jnp.where(mask, rank, K)
    out = _emit_grouped_all(agg_specs, cols, pc, mask, seg_ids, K)
    out["ck"] = uniq
    out["compact_n"] = n_live
    return out


# --------------------------------------------------------------------------
# hash-aggregation rung: the device ladder step BETWEEN the dense
# segment_sum rung and the sort-based sparse rung. Selective queries whose
# composed key space is huge but whose LIVE rows are few (SSB Q3.2/Q3.3
# shape: a few thousand matches against a 2^19 key space) pay the sort rung
# an n*log(n) over ALL docs; here the live docs are compacted to a fixed
# window and their keys scatter-minned into an open-addressing table, so
# cost scales with live rows. Overflow (too many live docs, probe failure,
# or more live groups than K) falls back to the sort rung — in-kernel via
# lax.cond on the per-segment path, at the device level on the sharded
# path (see build_kernel_body's sparse_rung).
# --------------------------------------------------------------------------

# open-addressing table: 2^15 slots, 4x the compact output K so the load
# factor for K-bounded group sets stays low enough that the bounded probe
# chain below almost never overflows
_HASH_BITS = 15
HASH_TABLE_SLOTS = 1 << _HASH_BITS
# linear-probe passes unrolled at trace time; each pass is one scatter-min
# + one gather over the live window
HASH_PROBES = 4
# live-doc window: more matched docs than this -> sort rung
HASH_LIVE_DOCS = 1 << 16
# Knuth multiplicative hash (2^32 / phi)
_HASH_MULT = 2654435761

# per-column arrays with a leading capacity dim (gathered down to the live
# window); everything else (dictvals) is shared
_CAPACITY_KEYS = ("fwd", "null", "mv", "mvcount")


def _compact_positions(mask: jnp.ndarray, L: int):
    """(pos, n) — ascending doc positions of the first L masked docs (the
    ascending order keeps per-group accumulation in doc order, so hash-rung
    sums are bit-exact with the sort rung's) and the total masked count.
    cumsum-scatter, not jnp.nonzero: this must stay cheap under vmap."""
    capacity = mask.shape[0]
    r = jnp.cumsum(mask.astype(jnp.int32)) - 1
    n = jnp.where(capacity > 0, r[-1] + 1, 0)
    tgt = jnp.where(mask & (r < L), r, L)
    pos = jnp.zeros(L + 1, dtype=jnp.int32).at[tgt].set(
        jnp.arange(capacity, dtype=jnp.int32), mode="drop")[:L]
    return pos, n


def _hash_probe(mask, keys, K, capacity):
    """Place masked composed keys into the open-addressing table.

    Returns (overflow, pos, mask_live, seg_ids, ck, n_live): ``pos`` indexes
    the live-doc window, ``seg_ids`` [L] maps each live doc to its compact
    group slot (K = parked), ``ck`` the K live keys in slot order
    (SENT-filled), ``n_live`` the live group count. ``overflow`` means the
    hash results are unusable and the sort rung must serve."""
    SENT = jnp.int32(_SENTINEL_KEY)
    H = HASH_TABLE_SLOTS
    L = min(capacity, HASH_LIVE_DOCS)

    pos, n_docs = _compact_positions(mask, L)
    mask_live = jnp.arange(L, dtype=jnp.int32) < jnp.minimum(n_docs, L)
    mk = jnp.where(mask_live, keys[pos], SENT)

    h = ((mk.astype(jnp.uint32) * jnp.uint32(_HASH_MULT))
         >> jnp.uint32(32 - _HASH_BITS)).astype(jnp.int32)
    slot = jnp.where(mask_live, h, H)      # fill docs park at slot H
    placed = ~mask_live
    table = jnp.full(H + 1, SENT, dtype=jnp.int32)
    for p in range(HASH_PROBES):
        if p:
            slot = jnp.where(placed, slot, (slot + 1) & (H - 1))
        put = jnp.where(placed, H, slot)
        # scatter-min claims the slot for the smallest competing key; docs
        # whose key won (or was already there) are placed, the rest probe on
        table = table.at[put].min(jnp.where(placed, SENT, mk))
        placed = placed | (table[put] == mk)
    # a later pass can STEAL a claimed slot (scatter-min lowers it with a
    # smaller key while the earlier claimant has already stopped probing) —
    # re-validate every claim against the final table; stolen claims count
    # as overflow so the sort rung serves instead of merging two groups
    placed = placed & (table[jnp.where(mask_live, slot, H)] == mk)

    live_tab = table[:H] != SENT
    n_live = live_tab.sum(dtype=jnp.int32)
    overflow = ((n_docs > L) | (mask_live & ~placed).any() | (n_live > K))

    # slot -> compact rank (cumsum, no scatter); park slot H -> K
    rk = jnp.cumsum(live_tab.astype(jnp.int32)) - 1
    rank = jnp.where(live_tab, jnp.minimum(rk, K), K)
    rank_ext = jnp.concatenate(
        [rank, jnp.full((1,), K, dtype=jnp.int32)])
    seg_ids = jnp.where(placed & mask_live, rank_ext[slot], K)

    # first K live slots -> compact keys (slot order, not sorted — the
    # decode and the cross-shard merge are both order-agnostic)
    stgt = jnp.where(live_tab & (rk < K), rk, K)
    spos = jnp.zeros(K + 1, dtype=jnp.int32).at[stgt].set(
        jnp.arange(H, dtype=jnp.int32), mode="drop")[:K]
    livek = jnp.arange(K, dtype=jnp.int32) < jnp.minimum(n_live, K)
    ck = jnp.where(livek, table[spos], SENT)
    return overflow, pos, mask_live, seg_ids, ck, n_live


def _hash_finish(agg_specs, cols, pc, probe, K):
    """Aggregate over the live-doc window: every capacity-sized column is
    gathered down to [L] first, so the scatter work scales with live rows."""
    _, pos, mask_live, seg_ids, ck, n_live = probe
    cols_live = {name: {k: (v[pos] if k in _CAPACITY_KEYS else v)
                        for k, v in tree.items()}
                 for name, tree in cols.items()}
    out = _emit_grouped_all(agg_specs, cols_live, pc, mask_live, seg_ids, K)
    out["ck"] = ck
    out["compact_n"] = n_live
    return out


def _emit_grouped_rung(agg_specs, cols, pc, mask, keys, num_groups, K,
                       capacity, rung):
    """Sparse-grouping dispatch: hash rung with sort fallback (see
    build_kernel_body docstring for the rung modes). The ``"rung"`` output
    leaf is 0 when the hash table served, 1 when the sort rung ran (or, in
    "hash" mode, when it MUST run)."""
    if rung == "sort":
        out = _emit_grouped_sparse(agg_specs, cols, pc, mask, keys,
                                   num_groups, K)
        pc.finish()
        out["rung"] = jnp.ones((), dtype=jnp.int32)
        return out
    probe = _hash_probe(mask, keys, K, capacity)
    overflow = probe[0]
    if rung == "hash":
        out = _hash_finish(agg_specs, cols, pc, probe, K)
        pc.finish()
        out["rung"] = overflow.astype(jnp.int32)
        return out
    # "cond": both branches re-walk the agg params from the same cursor
    # position with their own cursors (one traced consumption each);
    # the OUTER cursor deliberately stays at ``start`` — each branch
    # copy asserts full consumption instead
    start = pc.i

    def _hash_branch(_):
        pc2 = _ParamCursor(pc.params)
        pc2.i = start
        out = _hash_finish(agg_specs, cols, pc2, probe, K)
        pc2.finish()
        return out

    def _sort_branch(_):
        pc2 = _ParamCursor(pc.params)
        pc2.i = start
        out = _emit_grouped_sparse(agg_specs, cols, pc2, mask, keys,
                                   num_groups, K)
        pc2.finish()
        return out

    out = jax.lax.cond(overflow, _sort_branch, _hash_branch, None)
    out["rung"] = overflow.astype(jnp.int32)
    return out


def _emit_grouped_all(agg_specs, cols, pc, mask, seg_ids, num_groups):
    """All grouped aggregations + presence through BATCHED scatters: leaves
    sharing (reduce op, accumulator dtype) stack into one [N, k] array and
    reduce with a single segment_sum/min/max — scatters are the expensive
    op on TPU, and a 6-aggregation query otherwise issues 8+ of them.
    Param-cursor order is preserved (vectors are built in agg order; only
    the scatters are deferred)."""
    n = num_groups + 1
    # (op, dtype-str) -> list of [N] vectors to reduce together
    buckets: Dict[Tuple[str, str], List] = {}

    def enqueue(op: str, vec, post):
        b = buckets.setdefault((op, str(vec.dtype)), [])
        b.append(vec)
        return (op, str(vec.dtype), len(b) - 1, post)

    # presence / COUNT(*) / AVG counts are all the SAME masked count —
    # enqueue one column and share the ref (duplicate columns in a scatter
    # are not CSE'd away)
    count_ref = enqueue("sum", mask.astype(jnp.int32),
                        lambda r: r.astype(jnp.int64))
    refs: Dict[str, Any] = {}
    refs["presence"] = count_ref

    out: Dict[str, Any] = {}
    for i, aspec in enumerate(agg_specs):
        key = f"agg{i}"
        if aspec[0] == "distinctcounthll":
            # composed (group, bucket) id space: its own scatter
            _, colname, log2m = aspec
            m = 1 << log2m
            fwd = cols[colname]["fwd"]
            bucket = pc.take()[fwd]
            rank = pc.take()[fwd]
            ids = seg_ids * m + bucket
            regs = jax.ops.segment_max(jnp.where(mask, rank, 0), ids,
                                       num_segments=n * m)
            out[key] = jnp.maximum(regs[:num_groups * m], 0)
            continue
        base, mv, vals, dt, wide, min_n, max_n = _masked_values(
            aspec, cols, pc, mask)
        zero = jnp.zeros((), dtype=dt)
        if base == "count":
            refs[key] = count_ref
            continue
        fv = vals if vals.ndim else jnp.full(mask.shape[0], vals, dtype=dt)
        if base == "sum":
            refs[key] = enqueue("sum", jnp.where(mask, fv, zero),
                                lambda r, w=wide: r.astype(w))
        elif base == "min":
            refs[key] = enqueue(
                "min", jnp.where(mask, fv, min_n),
                lambda r: r.astype(jnp.float64))
        elif base == "max":
            refs[key] = enqueue(
                "max", jnp.where(mask, fv, max_n),
                lambda r: r.astype(jnp.float64))
        elif base == "avg":
            refs[key] = [
                enqueue("sum", jnp.where(mask, fv, zero),
                        lambda r, w=wide: r.astype(w)),
                count_ref]
        elif base == "minmaxrange":
            refs[key] = [
                enqueue("min", jnp.where(mask, fv, min_n),
                        lambda r: r.astype(jnp.float64)),
                enqueue("max", jnp.where(mask, fv, max_n),
                        lambda r: r.astype(jnp.float64))]
        else:
            raise AssertionError(f"agg {base} has no device grouped kernel")

    # one scatter per (op, dtype) bucket on TPU: the scatter's minor dim
    # pads to 128 lanes either way, so k stacked leaves cost ~one leaf.
    # CPU lowers separate 1-D scatters faster — keep them split there.
    # (FORCE_BATCH_SCATTERS overrides for tests of the batched branch.)
    batch = (FORCE_BATCH_SCATTERS if FORCE_BATCH_SCATTERS is not None
             else jax.default_backend() not in ("cpu",))
    reduced: Dict[Tuple[str, str], List] = {}
    scatter = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
               "max": jax.ops.segment_max}
    for (op, dts), vecs in buckets.items():
        if batch and len(vecs) > 1:
            data = jnp.stack(vecs, axis=1)  # [N, k]
            r = scatter[op](data, seg_ids, num_segments=n)[:num_groups]
            reduced[(op, dts)] = [r[:, j] for j in range(len(vecs))]
        else:
            reduced[(op, dts)] = [
                scatter[op](v, seg_ids, num_segments=n)[:num_groups]
                for v in vecs]

    def resolve(ref):
        op, dts, idx, post = ref
        return post(reduced[(op, dts)][idx])

    for key, ref in refs.items():
        if key in out:
            continue
        # multi-leaf states (avg, minmaxrange) ride as LISTS of refs;
        # single refs are 4-tuples
        out[key] = (tuple(resolve(r) for r in ref)
                    if isinstance(ref, list) else resolve(ref))
    return out


def build_kernel(spec: Tuple):
    """Single-segment entry: jitted fn(cols, params, num_docs) -> packed
    f64 output vector (ONE device array -> one D2H fetch per query; see
    output_layout)."""
    body = build_kernel_body(spec, sparse_k=sparse_mode(spec))

    def scan_segment(cols, params, num_docs):
        return pack_outputs(body(cols, params, num_docs, jnp.int32(0)), spec)

    # a jitted entry is named for its kernel family: the name is the
    # program's on a profiler trace (``jit_scan_segment``)
    return jax.jit(scan_segment)


# --------------------------------------------------------------------------
# packed output: every kernel output leaf concatenated into ONE f64 vector.
#
# Every host<->device transfer is a synchronous round trip with a fixed
# latency, whatever its size; fetching each output leaf separately (presence
# + N agg leaves + seg stats) makes decode latency-bound, not compute-bound
# (a 6-agg group-by pays seven sequential small D2H fetches for one kernel).
# f64 keeps counts and i32-ranged sums
# exact to 2^53; SUM finalizes as double anyway (ref: the reference
# aggregates SUM in double, AggregationFunctionType SUM -> DOUBLE).
#
# SPARSE COMPACTION: dense group-by outputs scale with the PADDED key space
# (SSB Q4.3: 2^20 slots for ~800 real groups -> megabytes of D2H
# per query). At >= COMPACT_MIN_GROUPS the pack switches to a compact
# layout — device-side ``nonzero(presence, size=K)`` + gathers — so D2H
# scales with actual groups (the fixed-shape analogue of the reference's
# DictionaryBasedGroupKeyGenerator cardinality ladder switching from dense
# arrays to maps). More than K live groups raises PlanError at decode and
# the executor falls back to the host path (full results, never truncation).
# --------------------------------------------------------------------------

COMPACT_MIN_GROUPS = 8192
COMPACT_K = 8192

# past this key-space size the kernel switches from dense scatter slots to
# SORT-BASED SPARSE GROUPING (_emit_grouped_sparse): the device analogue of
# the reference's cardinality ladder stepping off dense array-based group-key
# holders onto maps (DictionaryBasedGroupKeyGenerator.java:62,
# InstancePlanMakerImplV2.java:67-84 numGroupsLimit)
SPARSE_MIN_GROUPS = 1 << 15
# composed keys never reach this value (MAX_DEVICE_GROUPS < 2^31)
_SENTINEL_KEY = (1 << 31) - 1


def sparse_mode(spec: Tuple) -> int:
    """0 = dense grouping; else the compact K for sort-based sparse
    grouping. Shares compact_mode's K so the packed output layout is
    identical either way."""
    _, agg_specs, group_specs, num_groups, _ = spec
    if not group_specs or num_groups < SPARSE_MIN_GROUPS:
        return 0
    if any(a[0] in ("distinctcount", "distinctcounthll") for a in agg_specs):
        return 0
    return min(COMPACT_K, num_groups)


def compact_mode(spec: Tuple) -> int:
    """0 = dense; else the compact K for this spec. distinctcount/HLL
    leaves carry their own [cardinality]/[G*m] shapes and stay dense."""
    _, agg_specs, group_specs, num_groups, _ = spec
    if not group_specs or num_groups < COMPACT_MIN_GROUPS:
        return 0
    if any(a[0] in ("distinctcount", "distinctcounthll") for a in agg_specs):
        return 0
    return min(COMPACT_K, num_groups)

def output_layout(spec: Tuple, num_seg: int = 0) -> List[Tuple[str, int]]:
    """[(key, size)] slices of the packed vector, in pack order. Key
    ``aggI.J`` is leaf J of a multi-leaf aggregation state (avg, minmaxrange).
    ``num_seg > 0`` appends the sharded combine's per-segment matched-doc
    counts. In compact mode, grouped leaves shrink to K gathered entries
    prefixed by the live-group count and their group indices."""
    _, agg_specs, group_specs, num_groups, _ = spec
    K = compact_mode(spec)
    if K:
        num_groups = K
    reducers = partial_reduce_ops(spec)
    entries: List[Tuple[str, int]] = []
    if K:
        entries.append(("compact_n", 1))
        entries.append(("compact_idx", K))
        entries.append(("presence", K))
    elif group_specs:
        entries.append(("presence", num_groups))
    else:
        entries.append(("num_matched", 1))
    for i, aspec in enumerate(agg_specs):
        if aspec[0] == "distinctcount":
            entries.append((f"agg{i}", aspec[2]))  # [cardinality] presence
            continue
        if aspec[0] == "distinctcounthll":
            m = 1 << aspec[2]
            entries.append((f"agg{i}", (num_groups or 1) * m))
            continue
        nleaves = len(reducers[f"agg{i}"])
        size = num_groups if group_specs else 1
        if nleaves == 1:
            entries.append((f"agg{i}", size))
        else:
            entries.extend((f"agg{i}.{j}", size) for j in range(nleaves))
    if sparse_mode(spec):
        # which sparse rung actually served (0 = hash table, 1 = sort
        # fallback): bench/stats surface this per query
        entries.append(("rung", 1))
    if num_seg:
        entries.append(("seg_matched", num_seg))
    return entries


def pack_outputs(out: Dict[str, Any], spec: Tuple) -> jnp.ndarray:
    """Flatten the kernel output tree into one f64 vector (device side).
    Sparse-grouped trees (``"ck"`` present) arrive ALREADY compact — their
    unique composed keys go out as compact_idx directly (a composed key IS
    the dense group index, so the decode is identical); dense trees past
    the compact threshold get gathered down to their live slots here."""
    num_seg = out["seg_matched"].shape[0] if "seg_matched" in out else 0
    K = compact_mode(spec)
    idx = None
    gat = None
    if K:
        if "ck" in out:
            n = out["compact_n"]
            idx = out["ck"]
        else:
            presence = out["presence"]
            # fill 0 is safe: positions >= n are ignored by the decode
            gat = jnp.nonzero(presence > 0, size=K, fill_value=0)[0]
            idx = gat
            n = (presence > 0).sum(dtype=jnp.int32)
    parts = []
    for key, _ in output_layout(spec, num_seg):
        if key == "compact_n":
            leaf = n
        elif key == "compact_idx":
            leaf = idx
        elif "." in key:
            k, j = key.split(".")
            leaf = out[k][int(j)]
            if gat is not None:
                leaf = jnp.asarray(leaf)[gat]
        else:
            leaf = out[key]
            if gat is not None and key != "seg_matched":
                leaf = jnp.asarray(leaf)[gat]
        parts.append(jnp.asarray(leaf, dtype=jnp.float64).reshape(-1))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def fetch_outputs(stats, packed, wait: bool = True):
    """The packed output vector as a host array: the one D2H fetch of a
    launch. A traced query (``stats`` carries a recorder) splits it into
    a ``DeviceWait`` span (``block_until_ready``: the only span in which
    the device is owed work; ``wait=False`` where the launcher's
    dispatcher already waited) and a ``D2H`` span (the copy); an untraced
    one just copies, which blocks all the same."""
    import numpy as np

    rec = getattr(stats, "_recorder", None)
    if rec is None:
        return np.asarray(packed)
    if wait:
        import jax

        with rec.span("DeviceWait"):
            jax.block_until_ready(packed)
    with rec.span("D2H") as sp:
        host = np.asarray(packed)
        sp.attrs["bytes"] = int(host.nbytes)
    return host


def unpack_outputs(packed, spec: Tuple, num_seg: int = 0) -> Dict[str, Any]:
    """Packed f64 vector (host numpy) -> the kernel output tree the decode
    helpers consume. Scalar leaves come back as python-indexable scalars,
    vector leaves (grouped/presence/seg_matched) as arrays. Compact-mode
    leaves are scattered back into dense [num_groups] arrays host-side
    (cheap zeros; the expensive part was shipping them off the device)."""
    import numpy as np

    packed = np.asarray(packed)
    grouped = bool(spec[2])
    num_groups = spec[3]
    K = compact_mode(spec)
    dc = {f"agg{i}" for i, a in enumerate(spec[1])
          if a[0] in ("distinctcount", "distinctcounthll")}
    out: Dict[str, Any] = {}
    multi: Dict[str, Dict[int, Any]] = {}
    off = 0
    n = 0
    idx = None

    def expand(leaf):
        if idx is None:
            return leaf
        dense = np.zeros(num_groups, dtype=leaf.dtype)
        dense[idx] = leaf[:n]
        return dense

    for key, size in output_layout(spec, num_seg):
        leaf = packed[off:off + size]
        off += size
        if key == "compact_n":
            n = int(leaf[0])
            if n > K:
                from pinot_tpu.engine.plan import PlanError

                raise PlanError(
                    f"{n} live groups exceed the compact cap {K} "
                    f"-> host path serves the full result")
            continue
        if key == "compact_idx":
            idx = leaf[:n].astype(np.int64)
            continue
        if "." in key:
            k, j = key.split(".")
            multi.setdefault(k, {})[int(j)] = \
                expand(leaf) if grouped else leaf[0]
            continue
        if key == "num_matched":
            out[key] = leaf[0]
        elif key == "rung":
            out[key] = int(leaf[0])
        elif key == "seg_matched":
            out[key] = leaf
        elif grouped or key in dc:
            out[key] = expand(leaf)
        else:
            out[key] = leaf[0]
    for k, leaves in multi.items():
        out[k] = tuple(leaves[j] for j in sorted(leaves))
    return out


def partial_reduce_ops(spec: Tuple) -> Dict[str, Tuple[str, ...]]:
    """Per-output-leaf merge op ('sum'|'min'|'max') for combining partials
    across segments/devices — the state algebra of the combine phase
    (ref: BaseCombineOperator merge + AggregationFunction.merge)."""
    _, agg_specs, group_specs, _, _ = spec
    ops: Dict[str, Tuple[str, ...]] = {}
    if group_specs:
        ops["presence"] = ("sum",)
    else:
        ops["num_matched"] = ("sum",)
    for i, aspec in enumerate(agg_specs):
        base = aspec[0]
        ops[f"agg{i}"] = {
            "count": ("sum",),
            "sum": ("sum",),
            "min": ("min",),
            "max": ("max",),
            "avg": ("sum", "sum"),
            "minmaxrange": ("min", "max"),
            "distinctcount": ("max",),
            "distinctcounthll": ("max",),  # register merge = pmax
        }[base]
    return ops


def _masked_values(aspec, cols, pc, mask):
    base, mv, vspec, acc = aspec[0], aspec[1], aspec[2], aspec[3]
    dt, wide, min_neutral, max_neutral = _acc_info(acc)
    # MV values are read inside the MV branch (dense mv + counts), not here
    vals = (_emit_value(vspec, cols, pc, dt)
            if (vspec is not None and not mv) else None)
    if vals is not None and hasattr(vals, "astype"):
        vals = vals.astype(dt)
    return base, mv, vals, dt, wide, min_neutral, max_neutral


def _count32(mask):
    """Per-segment doc counts always fit i32; widen for exact merging."""
    return mask.sum(dtype=jnp.int32).astype(jnp.int64)


def _emit_scalar_agg(aspec, cols, pc, mask):
    if aspec[0] == "distinctcount":
        _, colname, card = aspec
        fwd = cols[colname]["fwd"]
        presence = jnp.zeros(card, dtype=jnp.int32).at[fwd].max(
            mask.astype(jnp.int32), mode="drop")
        return presence  # [card] 0/1; host maps present dictIds -> values
    if aspec[0] == "distinctcounthll":
        # HLL register update as masked scatter-max over precomputed
        # per-dictId (bucket, rank) LUTs (utils/hll.register_updates)
        _, colname, log2m = aspec
        m = 1 << log2m
        fwd = cols[colname]["fwd"]
        bucket = pc.take()[fwd]
        rank = pc.take()[fwd]
        regs = jax.ops.segment_max(jnp.where(mask, rank, 0), bucket,
                                   num_segments=m)
        return jnp.maximum(regs, 0)  # untouched buckets -> 0, not int-min
    base, mv, vals, dt, wide, min_n, max_n = _masked_values(
        aspec, cols, pc, mask)
    zero = jnp.zeros((), dtype=dt)

    if mv:
        c = cols[aspec[2][1]]
        mvv, cnt = c["dictvals"][c["mv"]], c["mvcount"]
        entry = (jnp.arange(c["mv"].shape[1], dtype=jnp.int32)[None, :]
                 < cnt[:, None]) & mask[:, None]
        fv = mvv.astype(dt)
        any_entry = entry.any()
        if base == "count":
            # acc sized at plan time for capacity*max_mv total entries
            return jnp.where(mask, cnt, 0).sum(dtype=dt).astype(jnp.int64)
        if base == "sum":
            return jnp.where(entry, fv, zero).sum().astype(wide)
        if base == "min":
            v = jnp.where(entry, fv, min_n).min().astype(jnp.float64)
            return jnp.where(any_entry, v, POS_INF)
        if base == "max":
            v = jnp.where(entry, fv, max_n).max().astype(jnp.float64)
            return jnp.where(any_entry, v, NEG_INF)
        if base == "avg":
            return (jnp.where(entry, fv, zero).sum().astype(wide),
                    entry.sum(dtype=jnp.int32).astype(jnp.int64))
        raise AssertionError(f"MV agg {base} has no device kernel")

    if base == "count":
        return _count32(mask)
    fv = vals if vals.ndim else jnp.full(mask.shape[0], vals, dtype=dt)
    any_match = mask.any()
    if base == "sum":
        return jnp.where(mask, fv, zero).sum().astype(wide)
    if base == "min":
        v = jnp.where(mask, fv, min_n).min().astype(jnp.float64)
        return jnp.where(any_match, v, POS_INF)
    if base == "max":
        v = jnp.where(mask, fv, max_n).max().astype(jnp.float64)
        return jnp.where(any_match, v, NEG_INF)
    if base == "avg":
        return (jnp.where(mask, fv, zero).sum().astype(wide), _count32(mask))
    if base == "minmaxrange":
        lo = jnp.where(mask, fv, min_n).min().astype(jnp.float64)
        hi = jnp.where(mask, fv, max_n).max().astype(jnp.float64)
        return (jnp.where(any_match, lo, POS_INF),
                jnp.where(any_match, hi, NEG_INF))
    raise AssertionError(f"agg {base} has no device scalar kernel")


class KernelCache:
    """spec -> jitted kernel (the engine's plan cache)."""

    def __init__(self):
        self._cache: Dict[Tuple, Any] = {}

    def get(self, spec: Tuple):
        k = self._cache.get(spec)
        if k is None:
            k = build_kernel(spec)
            self._cache[spec] = k
        return k

    def __len__(self) -> int:
        return len(self._cache)
