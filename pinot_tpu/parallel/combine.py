"""Sharded multi-segment combine: shard_map over a device mesh + ICI collectives.

TPU-native re-design of the instance-level combine
(ref: ``BaseCombineOperator.java:55-140`` — N executor tasks over the segment
list, partials merged through a BlockingQueue). Here the segment list is a
:class:`SegmentBatch` stacked into ``[S, capacity]`` arrays and sharded over
a 2-D ``jax.sharding.Mesh``:

- ``seg`` axis: segments data-parallel across devices (the reference's
  task-per-segment-group parallelism),
- ``doc`` axis: the doc dimension of every segment split across devices
  (the "context parallelism" of the scan, SURVEY.md §5).

Each device runs the single-segment kernel body (vmapped over its local
segments) and partials merge with ``psum``/``pmin``/``pmax`` over **both**
mesh axes — XLA lowers these to ICI all-reduces. The merged result is
replicated, so the host decode is identical to the single-segment path.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pinot_tpu.engine.kernels import (
    _SENTINEL_KEY,
    build_kernel_body,
    compact_from_sorted,
    pack_outputs,
    partial_reduce_ops,
    sparse_mode,
)
from pinot_tpu.engine.plan import PlanError

SEG_AXIS = "seg"
DOC_AXIS = "doc"


def _shard_map(f, mesh: Mesh, in_specs, out_specs):
    """Replication checking stays off: pack_outputs concatenates psum'd
    and all_gather'd leaves, which the checker can't see through."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

# shard spec per staged-column array kind. dictvals is the unified
# dictionary: replicated (every device gathers from the full dictionary).
KIND_SPEC = {
    "fwd": P(SEG_AXIS, DOC_AXIS),
    "mv": P(SEG_AXIS, DOC_AXIS, None),
    "mvcount": P(SEG_AXIS, DOC_AXIS),
    "null": P(SEG_AXIS, DOC_AXIS),
    "dictvals": P(),
}


def device_stage_column(mesh: Mesh, tree: Dict[str, np.ndarray]):
    """Host column arrays -> committed device arrays with the combine
    shardings (the sharded analogue of StagedSegment: pay H2D once, reuse
    across queries)."""
    return {k: jax.device_put(v, NamedSharding(mesh, KIND_SPEC[k]))
            for k, v in tree.items()}


def make_combine_mesh(devices: Optional[List] = None,
                      doc_shards: int = 1) -> Mesh:
    """Mesh over all (or given) devices: segments over ``seg``, the doc
    dimension over ``doc``. ``doc_shards`` must divide the device count."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n % doc_shards:
        raise ValueError(f"doc_shards {doc_shards} !| {n} devices")
    arr = np.asarray(devices).reshape(n // doc_shards, doc_shards)
    return Mesh(arr, (SEG_AXIS, DOC_AXIS))


def _local_reduce(v: jnp.ndarray, op: str) -> jnp.ndarray:
    if op == "sum":
        return v.sum(axis=0)
    if op == "min":
        return v.min(axis=0)
    if op == "max":
        return v.max(axis=0)
    raise AssertionError(op)


def _cross_reduce(v: jnp.ndarray, op: str, axes, mesh: Mesh) -> jnp.ndarray:
    # collectives only over axes with >1 device: on a size-1 axis (every
    # axis of a one-chip mesh) the reduce is the identity
    axes = tuple(a for a in axes if mesh.shape[a] > 1)
    if not axes:
        return v
    if op == "sum":
        return jax.lax.psum(v, axes)
    if op == "min":
        return jax.lax.pmin(v, axes)
    if op == "max":
        return jax.lax.pmax(v, axes)
    raise AssertionError(op)


def _sparse_cross_combine(partials, reducers, K, axes, mesh):
    """Merge per-segment SPARSE compact partials across segments and mesh
    axes. Dense partials share a key space and merge with psum; sparse
    compacts carry DIFFERENT key sets per segment/shard, so the merge is:
    all_gather every (keys, leaves) compact over both mesh axes, then
    re-sort + re-group the concatenated [M = total_compacts * K] entries
    into one [K] compact (the device analogue of the reference's
    IndexedTable upsert-merge of map-based group-by blocks,
    BaseCombineOperator merge for group-by). Segment-level overflow
    (compact_n > K anywhere) propagates so the decode rejects rather than
    truncates."""
    SENT = jnp.int32(_SENTINEL_KEY)

    def gather(x):
        for a in axes:
            if mesh.shape[a] > 1:
                x = jax.lax.all_gather(x, a, tiled=True)
        return x

    keys = gather(partials["ck"]).reshape(-1)          # [M]
    seg_n = gather(partials["compact_n"]).max()
    M = keys.shape[0]
    order = jnp.argsort(keys)
    sk = keys[order]
    valid = sk != SENT
    first, n_live, uniq = compact_from_sorted(sk, K)
    rank = jnp.cumsum(first) - 1                       # [M] sorted-pos rank
    rank = jnp.where(valid & (rank < K), rank, K)      # overflow bucket
    scatter = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
               "max": jax.ops.segment_max}

    def merge_leaf(leaf, op):
        v = gather(leaf).reshape(M)[order]
        return scatter[op](v, rank, num_segments=K + 1)[:K]

    out = {}
    for key, ops in reducers.items():
        if key == "num_matched":
            continue
        val = partials[key]
        if isinstance(val, tuple):
            out[key] = tuple(merge_leaf(v, op) for v, op in zip(val, ops))
        else:
            out[key] = merge_leaf(val, ops[0])
    out["ck"] = uniq
    # if ANY per-segment compact overflowed, its keys were truncated before
    # this merge — surface a count > K so unpack raises (host path serves)
    out["compact_n"] = jnp.maximum(n_live, seg_n)
    # rung flag: 'sort' wins if ANY shard's hash table overflowed
    rung = partials.get("rung")
    if rung is not None:
        out["rung"] = _cross_reduce(rung.max(), "max", axes, mesh)
    return out


class ShardedKernelCache:
    """(spec, mesh-shape) -> compiled sharded combine kernel."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._cache: Dict[Tuple, object] = {}

    def get(self, spec: Tuple, col_layouts: Tuple[Tuple[str, Tuple[str, ...]], ...]):
        key = (spec, col_layouts)
        fn = self._cache.get(key)
        if fn is None:
            fn = build_sharded_kernel(spec, self.mesh, col_layouts)
            self._cache[key] = fn
        return fn

    def __len__(self) -> int:
        return len(self._cache)


def build_sharded_kernel(spec: Tuple, mesh: Mesh,
                         col_layouts: Tuple[Tuple[str, Tuple[str, ...]], ...]):
    """Compile the sharded combine for one kernel spec.

    ``col_layouts``: per staged column, its array keys (('fwd',),
    ('mv','mvcount'), +'dictvals'/'null') — static so the shard specs and
    vmap axes are built once per (spec, layout).
    """
    n_seg = mesh.shape[SEG_AXIS]
    n_doc = mesh.shape[DOC_AXIS]
    capacity = spec[-1]
    if capacity % n_doc:
        # PlanError so the executor falls back to the per-segment path
        raise PlanError(f"capacity {capacity} !| doc axis {n_doc}")
    local_cap = capacity // n_doc
    sparse_k = sparse_mode(spec)
    # sparse specs build BOTH sparse-rung bodies: the hash body runs first
    # for every local segment, and a device-level lax.cond reruns the sort
    # body only when a hash table overflowed. The cond must sit OUTSIDE the
    # segment vmap — a cond under vmap lowers to select and would execute
    # (and pay for) the sort on every query.
    body = build_kernel_body(spec, capacity_override=local_cap,
                             sparse_k=sparse_k,
                             sparse_rung="hash" if sparse_k else "cond")
    body_sort = (build_kernel_body(spec, capacity_override=local_cap,
                                   sparse_k=sparse_k, sparse_rung="sort")
                 if sparse_k else None)
    reducers = partial_reduce_ops(spec)

    kind_axis = {"fwd": 0, "mv": 0, "mvcount": 0, "null": 0, "dictvals": None}

    cols_spec = {name: {k: KIND_SPEC[k] for k in keys}
                 for name, keys in col_layouts}
    cols_axes = {name: {k: kind_axis[k] for k in keys}
                 for name, keys in col_layouts}

    def scan_sharded(cols, params, num_docs):
        doc_off = (jax.lax.axis_index(DOC_AXIS) * local_cap).astype(jnp.int32)

        def one_segment(seg_cols, nd):
            return body(seg_cols, params, nd, doc_off)

        partials = jax.vmap(one_segment, in_axes=(cols_axes, 0))(cols, num_docs)
        axes = (SEG_AXIS, DOC_AXIS)
        if sparse_k:
            # hash-rung overflow anywhere in this device's segments -> rerun
            # them all through the sort body (one branch executes; the
            # cross-shard merge is rung-agnostic, so devices may disagree)
            hash_partials = partials

            def _sort_all(_):
                return jax.vmap(
                    lambda seg_cols, nd: body_sort(seg_cols, params, nd,
                                                   doc_off),
                    in_axes=(cols_axes, 0))(cols, num_docs)

            partials = jax.lax.cond(hash_partials["rung"].max() > 0,
                                    _sort_all, lambda _: hash_partials,
                                    None)
            out = _sparse_cross_combine(partials, reducers, sparse_k,
                                        axes, mesh)
        else:
            out = {}
            for key, val in partials.items():
                ops = reducers[key]
                if isinstance(val, tuple):
                    out[key] = tuple(
                        _cross_reduce(_local_reduce(v, op), op, axes, mesh)
                        for v, op in zip(val, ops))
                else:
                    out[key] = _cross_reduce(_local_reduce(val, ops[0]),
                                             ops[0], axes, mesh)
        # per-segment matched doc counts [S] (stats parity with the
        # per-segment executor: numSegmentsMatched / numDocsScanned)
        if "num_matched" in partials:
            local = partials["num_matched"]            # [S_local]
        else:
            local = partials["presence"].sum(axis=1)   # [S_local]
        if mesh.shape[DOC_AXIS] > 1:
            local = jax.lax.psum(local, DOC_AXIS)
        if mesh.shape[SEG_AXIS] > 1:
            local = jax.lax.all_gather(local, SEG_AXIS, tiled=True)
        out["seg_matched"] = local
        # ONE replicated f64 vector out: a single D2H fetch serves the whole
        # decode (one transfer latency, not one per leaf; see
        # kernels.output_layout)
        return pack_outputs(out, spec)

    sharded = _shard_map(
        scan_sharded, mesh=mesh,
        in_specs=(cols_spec, P(), P(SEG_AXIS)),
        out_specs=P())
    return jax.jit(sharded)


def pad_segments(n: int, n_seg: int) -> int:
    """Segments padded up to a multiple of the seg-axis size."""
    return ((n + n_seg - 1) // n_seg) * n_seg


# --------------------------------------------------------------------------
# sharded fused-Pallas combine: the flagship serving path for eligible
# aggregation/group-by queries. Each device runs the fused scan kernel
# (pallas_kernels.build_kernel) over its local [S_local, T_local] shard of
# the planar bit-packed batch; partials merge with psum/pmin/pmax over ICI.
# --------------------------------------------------------------------------

def build_sharded_pallas_kernel(spec, plan_spec: Tuple, mesh: Mesh):
    """jitted fn(static_params, packed_cols, value_cols, num_docs) ->
    packed f64 vector.

    ``spec`` is a pallas_kernels.PallasSpec already sized PER DEVICE
    (num_segs/tiles_per_seg local to one mesh cell); inputs are
    device-committed arrays sharded (seg, doc) over the mesh:
    packed [S, T, W/128, 128] u32, values [S, T, TILE/128, 128] f32/i32,
    num_docs [S] i32, static_params [2*n_slots] i32 replicated (interval
    literals stay runtime args so same-shape queries share the compile)."""
    from pinot_tpu.engine.pallas_kernels import (
        _row_layout,
        assemble_outputs,
        build_kernel,
    )
    from pinot_tpu.engine.staging import PALLAS_TILE

    T_l = spec.tiles_per_seg
    call = build_kernel(spec)
    _, _, mm_row, _, _, _ = _row_layout(spec)
    axes = (SEG_AXIS, DOC_AXIS)

    def pallas_scan_sharded(static_params, packed_cols, value_cols,
                            num_docs):
        doc_base = (jax.lax.axis_index(DOC_AXIS)
                    * (T_l * PALLAS_TILE)).astype(jnp.int32)
        params = jnp.concatenate([
            static_params.astype(jnp.int32).reshape(-1),
            num_docs.astype(jnp.int32), doc_base[None]])
        out_f, out_i, out_mm, out_seg = call(params, *packed_cols,
                                             *value_cols)
        out_f = _cross_reduce(out_f, "sum", axes, mesh)
        # per-device int accumulator rows are i32-bounded by the kernel's
        # per-step carry-chain normalization (pallas_kernels.build_kernel);
        # widen before the mesh psum so the cross-device limb totals can't
        # wrap (O(groups) cost only)
        out_i = _cross_reduce(out_i.astype(jnp.int64), "sum", axes, mesh)
        if mm_row:
            rows = list(out_mm)
            for (_, kind), r in mm_row.items():
                rows[r] = _cross_reduce(out_mm[r], kind, axes, mesh)
            out_mm = jnp.stack(rows)
        seg_local = out_seg.sum(axis=1)            # [S_l]
        seg_local = _cross_reduce(seg_local, "sum", (DOC_AXIS,), mesh)
        if mesh.shape[SEG_AXIS] > 1:
            seg_local = jax.lax.all_gather(seg_local, SEG_AXIS, tiled=True)
        tree = assemble_outputs(plan_spec, spec, out_f, out_i, out_mm,
                                seg_matched=seg_local)
        return pack_outputs(tree, plan_spec)

    pk_spec = P(SEG_AXIS, DOC_AXIS, None, None)
    n_value_refs = sum(l if l else 1 for l in
                       (spec.value_limbs or (0,) * len(spec.value_is_int)))
    sharded = _shard_map(
        pallas_scan_sharded, mesh=mesh,
        in_specs=(P(),
                  [pk_spec] * len(spec.packed_bits),
                  [pk_spec] * n_value_refs,
                  P(SEG_AXIS)),
        out_specs=P())
    return jax.jit(sharded)


def build_sharded_pallas_probe(spec, mesh: Mesh):
    """jitted fn(static_params, packed_cols, num_docs) -> out_mm rows,
    min/max-reduced over both mesh axes.

    ``spec`` is the group-range PROBE PallasSpec
    (pallas_kernels.probe_plan_of): the same fused unpack+filter scan with
    one masked (min, max)-of-dictId aggregation pair per group column and
    no matmul — the narrowing pass that collapses large sparse composed
    key spaces onto the dense one-hot rung. Totally ordered through the
    launch dispatcher like any other multi-device program."""
    from pinot_tpu.engine.pallas_kernels import _row_layout, build_kernel
    from pinot_tpu.engine.staging import PALLAS_TILE

    T_l = spec.tiles_per_seg
    call = build_kernel(spec)
    _, _, mm_row, _, _, _ = _row_layout(spec)
    axes = (SEG_AXIS, DOC_AXIS)

    def pallas_probe_sharded(static_params, packed_cols, num_docs):
        doc_base = (jax.lax.axis_index(DOC_AXIS)
                    * (T_l * PALLAS_TILE)).astype(jnp.int32)
        params = jnp.concatenate([
            static_params.astype(jnp.int32).reshape(-1),
            num_docs.astype(jnp.int32), doc_base[None]])
        _f, _i, out_mm, _s = call(params, *packed_cols)
        rows = list(out_mm)
        for (_, kind), r in mm_row.items():
            rows[r] = _cross_reduce(out_mm[r], kind, axes, mesh)
        return jnp.stack(rows)

    pk_spec = P(SEG_AXIS, DOC_AXIS, None, None)
    sharded = _shard_map(
        pallas_probe_sharded, mesh=mesh,
        in_specs=(P(), [pk_spec] * len(spec.packed_bits), P(SEG_AXIS)),
        out_specs=P())
    return jax.jit(sharded)
