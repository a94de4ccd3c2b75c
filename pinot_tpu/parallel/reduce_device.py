"""Device-resident broker reduce: group-by merge over the broker mesh.

The "last hop over ICI" (ROADMAP): when the embedded cluster's servers
and broker share the process, per-server group-by partials are already
host arrays that never crossed a wire — so the broker merge can stay on
the same device substrate the per-segment kernels used, instead of the
PR-14 host lexsort. The shape mirrors the reference's broker-side
``IndexedTable`` upsert-merge (GroupByDataTableReducer.java:66) mapped
onto ``shard_map`` + ICI collectives, the same machinery as
``parallel/combine.py``'s cross-segment merge:

- keys composite-encode to ONE non-negative i64 per row (injective
  codes: first-occurrence ranks for str, ``np.unique`` ranks for f64,
  min-offset for i64 — equal rows and ONLY equal rows collide, which is
  all the contract needs because the caller's stable
  ``argsort(first_idx)`` restores oracle insertion order afterwards);
- the concatenated (keys, states, arrival-index) block pads to a shared
  pow2 capacity and scatters over the 1-D broker mesh (``MERGE_AXIS``);
- **dense rung** (composite space <= ``DEFAULT_DEVICE_REDUCE_DENSE_SLOTS``):
  each device ``segment_sum``/``min``/``max``-scatters its shard into the
  full [space] slot array and partials merge over the mesh axis —
  ``psum``/``pmin``/``pmax`` for small slot spaces (replicated output),
  an ``all_to_all`` slice exchange + local fold past ``_PSUM_SLOTS``
  (each device merges one slot-space slice, so the combine moves each
  slot once over ICI instead of replicating the full array to every
  device) — the group-by analogue of the dense aggregation rung in
  ``engine/kernels.py``;
- **sort rung** (larger spaces): ``all_gather`` the composite keys, one
  global argsort + first-occurrence compaction + rank scatter — the
  ``_sparse_cross_combine`` shape from combine.py over i64 keys.

Only shapes whose folds are provably order-independent reach here (the
caller in ``broker/reduce.py`` declines i64 near-overflow sums,
non-integral f64 sums, NaN keys, obj states — each with a registered
``reduce:device->host:<reason>`` ledger record), so the merged states
are bit-identical to the host fold regardless of reduction order.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.common.bounds import (
    F64_EXACT_INT_BOUND,
    I64_KEY_SPACE_BOUND,
    I64_PAD_SENTINEL,
)
from pinot_tpu.spi.config import CommonConstants

# the broker merge mesh is 1-D: every device holds one shard of the
# concatenated (keys, states) block and partials meet over this axis
MERGE_AXIS = "merge"

# composite keys are non-negative and < I64_KEY_SPACE_BOUND
# (encode_composite_keys declines anything larger), so i64 max is a safe
# pad/sentinel key that sorts strictly after every live key
_PAD_KEY = I64_PAD_SENTINEL

# caps (spi/config.py): dense-rung slot budget and padded-row ceiling
DENSE_SLOTS = CommonConstants.DEFAULT_DEVICE_REDUCE_DENSE_SLOTS
MAX_MERGE_ROWS = CommonConstants.DEFAULT_DEVICE_REDUCE_MAX_ROWS

# dense-rung combine flavor split: slot spaces at or under this budget
# all-reduce with psum/pmin/pmax (replicated output, no reshard); larger
# spaces exchange slot-space slices with all_to_all and fold locally, so
# each slot crosses ICI once instead of being replicated to every device
_PSUM_SLOTS = 1 << 12


_MESH = None
_MESH_FAILED = False
_KERNELS: Dict[Tuple, object] = {}


def broker_mesh():
    """The (cached) 1-D broker merge mesh over every visible device, or
    None when no usable device backend exists — the caller records
    ``reduce_device_mesh_unavailable`` and serves from the host path."""
    global _MESH, _MESH_FAILED
    if _MESH is not None or _MESH_FAILED:
        return _MESH
    try:
        from pinot_tpu.engine import ensure_compile_cache, ensure_x64

        ensure_x64()  # i64 keys/sums through the collectives
        ensure_compile_cache()
        import jax

        from jax.sharding import Mesh

        devices = jax.devices()
        if not devices:
            raise RuntimeError("no devices")
        _MESH = Mesh(np.asarray(devices), (MERGE_AXIS,))
    except Exception:
        _MESH_FAILED = True
        _MESH = None
    return _MESH


def reset_mesh_cache() -> None:
    """Test hook: drop the cached mesh + compiled kernels."""
    global _MESH, _MESH_FAILED
    _MESH = None
    _MESH_FAILED = False
    _KERNELS.clear()


def encode_composite_keys(key_cols: List[np.ndarray]
                          ) -> Tuple[Optional[np.ndarray], int]:
    """Concatenated key columns -> (one non-negative i64 composite per
    row, composite space size), or ``(None, 0)`` when the space cannot
    fit the i64 budget (the caller declines
    ``reduce_device_key_space_overflow``).

    Column encodings only need to be INJECTIVE — equal rows and ONLY
    equal rows collide on the composite (the caller restores oracle
    insertion order from ``argsort(first_idx)``, so code ORDER never
    leaks into the output): str columns take first-occurrence ranks
    from one dict pass (no O(n log n) string sort), f64 columns
    rank-encode through ``np.unique`` (which merges -0.0/0.0 exactly
    like the host lexsort runs do), i64 columns shift by their minimum.
    NaN keys never reach here (pre-declined)."""
    n = int(key_cols[0].shape[0]) if key_cols else 0
    comp = np.zeros(n, dtype=np.int64)
    space = 1
    for a in key_cols:
        if a.dtype.kind == "i":
            lo = int(a.min())
            r = int(a.max()) - lo + 1
            codes = a.astype(np.int64) - lo
        elif a.dtype.kind == "f":
            _, inv = np.unique(a, return_inverse=True)
            codes = inv.astype(np.int64).reshape(n)
            r = int(codes.max()) + 1 if n else 1
        else:
            lut: Dict = {}
            codes = np.fromiter(
                (lut.setdefault(v, len(lut)) for v in a.tolist()),
                dtype=np.int64, count=n)
            r = len(lut) if n else 1
        if r < 1 or space > I64_KEY_SPACE_BOUND // r:
            return None, 0
        comp = comp * r + codes
        space *= r
    return comp, space


def f64_sum_exact(arr: np.ndarray) -> bool:
    """True when folding ``arr`` is order-independent in f64: finite,
    integral-valued, and total absolute mass under 2^53 (every partial
    sum is then an exactly-representable integer)."""
    if not bool(np.isfinite(arr).all()):
        return False
    if not bool((arr == np.floor(arr)).all()):
        return False
    return float(np.abs(arr).sum()) < F64_EXACT_INT_BOUND


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def _merge_cap(n: int, n_dev: int) -> int:
    """Padded row capacity: ``n`` rounded up to an eighth-of-octave step
    (the next multiple of ``next_pow2(n) / 8``). At most 8 distinct caps
    per power of two keeps the compiled-kernel cache bounded like pure
    pow2 padding would, but the pad tail every scatter still has to
    chew through stays under 12.5% instead of up to 100%. Steps are
    clamped to ``n_dev`` (a pow2), so ``cap % n_dev == 0`` always."""
    step = max(_next_pow2(n) // 8, n_dev, 1)
    return -(-max(n, 1) // step) * step


def _pad_identity(arr: np.ndarray, op: str) -> Tuple[int, float]:
    """Fold identity for the pad tail (pads scatter into a dropped slot
    either way; the identity keeps them inert even there)."""
    if op == "sum":
        return 0
    if arr.dtype.kind == "i":
        info = np.iinfo(arr.dtype)
        return info.max if op == "min" else info.min
    return np.inf if op == "min" else -np.inf


def _axis_reduce(v, op: str, axis, mesh):
    """psum/pmin/pmax over one mesh axis (size-1 axes are a no-op — the
    single-device broker mesh still runs the same program)."""
    import jax

    if mesh.shape[axis] == 1:
        return v
    if op == "sum":
        return jax.lax.psum(v, axis)
    if op == "min":
        return jax.lax.pmin(v, axis)
    if op == "max":
        return jax.lax.pmax(v, axis)
    raise AssertionError(op)


def _slice_reduce(v, op: str, axis, mesh):
    """all_to_all slice exchange + local fold over one mesh axis: pad
    the per-device [m] slot partials to an axis-size multiple, trade
    slot-space slices so every device holds all partials of ONE slice,
    and fold them locally — each slot crosses ICI once (vs psum's
    replicated output), and the result shards as [m_pad // n_dev] per
    device (``out_specs=P(axis)`` reassembles the [m_pad] array; the
    pad tail carries the fold identity, so the merged arrival-index
    tail stays at ``segment_min``'s identity and the host's live-slot
    compaction never selects it)."""
    import jax
    import jax.numpy as jnp

    n_dev = mesh.shape[axis]
    m = int(v.shape[0])
    pad_to = -(-m // n_dev) * n_dev
    if op == "sum":
        fill = 0
    elif jnp.issubdtype(v.dtype, jnp.integer):
        info = jnp.iinfo(v.dtype)
        fill = info.max if op == "min" else info.min
    else:
        fill = jnp.inf if op == "min" else -jnp.inf
    v = jnp.pad(v, (0, pad_to - m), constant_values=fill)
    v = v.reshape(n_dev, pad_to // n_dev)
    v = jax.lax.all_to_all(v, axis, 0, 0)
    red = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[op]
    return red(v, axis=0)


def _build_dense_merge(mesh, space: int, ops: Tuple[str, ...],
                       dtypes: Tuple[str, ...], a2a: bool):
    """Dense rung: each device scatters its local shard into the FULL
    [space] slot array (one segment op per aggregation + arrival-index
    min), then slot partials combine over the mesh axis — psum/pmin/
    pmax (replicated [space] outputs) for small spaces,
    ``_slice_reduce``'s all_to_all exchange (sharded outputs) when
    ``a2a``. The merged arrival-index doubles as the live-slot mask
    (``segment_min``'s identity, i32 max, survives ONLY in slots no
    real row touched — pads all carry ``comp == space``, the dropped
    slot), so no separate occupancy scatter is needed; the host
    compacts live slots either way."""
    import jax
    import jax.numpy as jnp

    from jax.sharding import PartitionSpec as P

    from pinot_tpu.parallel.combine import _shard_map

    seg_op = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
              "max": jax.ops.segment_max}

    def _combine(v, op):
        # axis literals live HERE (not threaded further) so the lint
        # family's one-hop mesh-axis resolution sees them
        if a2a:
            return _slice_reduce(v, op, MERGE_AXIS, mesh)
        return _axis_reduce(v, op, MERGE_AXIS, mesh)

    def per_device(comp, idx, vals):
        # pads carry comp == space: one extra slot swallows them
        min_idx = jax.ops.segment_min(idx, comp,
                                      num_segments=space + 1)[:space]
        min_idx = _combine(min_idx, "min")
        leaves = tuple(
            _combine(seg_op[op](v, comp, num_segments=space + 1)[:space], op)
            for v, op in zip(vals, ops))
        return min_idx, leaves

    sharded = _shard_map(
        per_device, mesh=mesh,
        in_specs=(P(MERGE_AXIS), P(MERGE_AXIS), [P(MERGE_AXIS)] * len(ops)),
        out_specs=P(MERGE_AXIS) if a2a else P())
    return jax.jit(sharded)


def _build_sort_merge(mesh, cap: int, ops: Tuple[str, ...],
                      dtypes: Tuple[str, ...]):
    """Sort rung (composite spaces past the dense slot budget): gather
    the padded [cap] composite block over the mesh axis, ONE global
    argsort, first-occurrence compaction, and a rank scatter per
    aggregation — the ``_sparse_cross_combine`` shape from combine.py
    over i64 keys. Pad keys (i64 max) sort strictly last, so ranks
    0..n_live-1 enumerate the groups in ascending composite order."""
    import jax
    import jax.numpy as jnp

    from jax.sharding import PartitionSpec as P

    from pinot_tpu.parallel.combine import _shard_map

    seg_op = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
              "max": jax.ops.segment_max}
    SENT = jnp.int64(_PAD_KEY)

    def _gather(x):
        if mesh.shape[MERGE_AXIS] == 1:
            return x
        return jax.lax.all_gather(x, MERGE_AXIS, tiled=True)

    def per_device(comp, idx, vals):
        keys = _gather(comp)                               # [cap]
        order = jnp.argsort(keys)
        sk = keys[order]
        valid = sk != SENT
        first = valid & jnp.concatenate(
            [jnp.ones((1,), dtype=bool), sk[1:] != sk[:-1]])
        n_live = first.sum(dtype=jnp.int32)
        rank = jnp.cumsum(first) - 1                       # [cap]
        rank = jnp.where(valid, rank, cap)                 # pad bucket
        min_idx = jax.ops.segment_min(_gather(idx)[order], rank,
                                      num_segments=cap + 1)[:cap]
        leaves = tuple(
            seg_op[op](_gather(v)[order], rank,
                       num_segments=cap + 1)[:cap]
            for v, op in zip(vals, ops))
        return n_live, min_idx, leaves

    sharded = _shard_map(
        per_device, mesh=mesh,
        in_specs=(P(MERGE_AXIS), P(MERGE_AXIS), [P(MERGE_AXIS)] * len(ops)),
        out_specs=P())
    return jax.jit(sharded)


def device_group_merge(mesh, comp: np.ndarray, space: int,
                       vals: List[np.ndarray], ops: List[str]
                       ) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Merge the concatenated group-by block on device.

    -> ``(first_idx, folded)``: per merged group (in ascending composite
    order — any fixed enumeration works, the caller's stable
    ``argsort(first_idx)`` restores oracle insertion order), the
    earliest input row index and one exactly-folded state array per
    aggregation — the same contract as the host path's
    ``lexsort_runs`` + ``fold_grouped_runs`` + ``order[starts]``."""
    n = int(comp.shape[0])
    n_dev = int(mesh.shape[MERGE_AXIS])
    cap = _merge_cap(n, n_dev)
    rung = "dense" if space <= DENSE_SLOTS else "sort"

    comp_p = np.full(cap, space if rung == "dense" else _PAD_KEY,
                     dtype=np.int64)
    comp_p[:n] = comp
    idx_p = np.full(cap, np.iinfo(np.int32).max, dtype=np.int32)
    idx_p[:n] = np.arange(n, dtype=np.int32)
    vals_p = []
    for v, op in zip(vals, ops):
        vp = np.full(cap, _pad_identity(v, op), dtype=v.dtype)
        vp[:n] = v
        vals_p.append(vp)

    a2a = rung == "dense" and n_dev > 1 and space > _PSUM_SLOTS
    dtypes = tuple(str(v.dtype) for v in vals)
    key = (id(mesh), rung, a2a, cap, space if rung == "dense" else 0,
           tuple(ops), dtypes)
    fn = _KERNELS.get(key)
    if fn is None:
        if rung == "dense":
            fn = _build_dense_merge(mesh, space, tuple(ops), dtypes, a2a)
        else:
            fn = _build_sort_merge(mesh, cap, tuple(ops), dtypes)
        _KERNELS[key] = fn
    if rung == "dense":
        min_idx, leaves = fn(comp_p, idx_p, vals_p)
        # live slots are exactly those some real row touched: the
        # merged arrival-index still at segment_min's identity marks
        # an untouched (or pad-tail) slot
        mi = np.asarray(min_idx)
        live = np.flatnonzero(mi < np.iinfo(np.int32).max)
        first_idx = mi[live].astype(np.int64)
        folded = [np.asarray(lf)[live] for lf in leaves]
    else:
        n_live, min_idx, leaves = fn(comp_p, idx_p, vals_p)
        k = int(n_live)
        first_idx = np.asarray(min_idx)[:k].astype(np.int64)
        folded = [np.asarray(lf)[:k] for lf in leaves]
    return first_idx, folded
