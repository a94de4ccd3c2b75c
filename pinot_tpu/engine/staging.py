"""Device staging: segment columns -> HBM arrays.

The TPU analogue of the reference's mmap-into-PinotDataBuffer read path
(``ImmutableSegmentLoader`` + ``DataFetcher.java:44`` bulk reads): a column is
staged once into device memory as tile-aligned arrays and reused across
queries. Staging is lazy per (segment, column) and cached; the cache is the
HBM residency manager (eviction hooks come with the server layer).

Staged layout per column:
- SV dict column:  ``fwd``  [capacity] int32 dictIds (upcast from narrow)
- SV raw column:   ``fwd``  [capacity] value dtype
- numeric dict:    ``dictvals`` [cardinality] values (dictId -> value gather)
- MV dict column:  ``mv`` [capacity, max_mv] int32 + ``mvcount`` [capacity]
- null bitmap:     ``null`` [capacity] bool
"""

from __future__ import annotations

import functools
import threading

from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from pinot_tpu.segment.immutable import ImmutableSegment
from pinot_tpu.spi.data import DataType


# Metadata-driven narrowing: v5e has no native f64/i64 units (XLA emulates
# them as f32/i32 pairs), so capacity-sized device arrays are narrowed
# whenever column min/max bounds allow. Raw FLOAT/DOUBLE forward arrays stay
# f64: filter literals compare against exact stored values and rounding to
# f32 could flip boundary rows (dictionary columns filter on dictIds, so
# their value tables narrow safely to f32).
_I32_MIN, _I32_MAX = np.iinfo(np.int32).min, np.iinfo(np.int32).max


def staged_int_dtype(cm) -> np.dtype:
    """Device dtype for an integral column's values, from stats min/max."""
    if (cm.min_value is not None and cm.max_value is not None
            and _I32_MIN <= int(cm.min_value)
            and int(cm.max_value) <= _I32_MAX):
        return np.dtype(np.int32)
    return np.dtype(np.int64)


# the device a resident's bytes lie on when it does not say (a plain
# ``jnp.asarray`` lands there)
DEFAULT_DEVICE = 0


@functools.lru_cache(maxsize=4096)
def _shard_layout(sharding, shape: Tuple[int, ...],
                  itemsize: int) -> Tuple[Tuple[int, int], ...]:
    """((device id, bytes), ...) of an array of ``shape`` under
    ``sharding``: every device of the sharding holds one shard, so a
    replicated array costs a whole copy on each."""
    n = itemsize
    for d in sharding.shard_shape(shape):
        n *= d
    return tuple((d.id, n) for d in sharding.device_set)


def add_device_bytes(arr, into: Dict[int, int]) -> None:
    """Add ``arr``'s device bytes to ``into`` by device id (HBM is a
    device's, so the residency budget is reckoned a device). Reads only
    the array's sharding and shape: never a sync, never a copy."""
    sharding = getattr(arr, "sharding", None)
    if sharding is None:
        n = int(getattr(arr, "nbytes", 0) or 0)
        if n:
            into[DEFAULT_DEVICE] = into.get(DEFAULT_DEVICE, 0) + n
        return
    for d, n in _shard_layout(sharding, arr.shape, arr.dtype.itemsize):
        into[d] = into.get(d, 0) + n


class StagedColumn:
    """One column's device-resident arrays."""

    def __init__(self, fwd=None, dictvals=None, mv=None, mvcount=None,
                 null=None, data_type: Optional[DataType] = None,
                 has_dictionary: bool = True):
        self.fwd = fwd
        self.dictvals = dictvals
        self.mv = mv
        self.mvcount = mvcount
        self.null = null
        self.data_type = data_type
        self.has_dictionary = has_dictionary

    def tree(self) -> Dict[str, jnp.ndarray]:
        """The pytree handed to jitted kernels (only present arrays)."""
        out = {}
        for k in ("fwd", "dictvals", "mv", "mvcount", "null"):
            v = getattr(self, k)
            if v is not None:
                out[k] = v
        return out


# Pallas tile: docs per grid step of the fused scan kernel. Packed columns
# are laid out planar per tile (value j of a tile lives in word j%W at bit
# slot (j//W)*B) so the in-kernel unpack is K static shift+mask ops over
# contiguous words — no gathers, no cross-lane interleave
# (TPU-side re-design of the reference's unaligned bit extraction,
# io/util/PinotDataBitSet.java:25).
PALLAS_TILE = 4096

# resident idx arrays per segment (index rung): LRU working-set bound —
# each is at most ~SELECTIVITY_THRESHOLD * capacity int32s, so the cap
# bounds idx residency to a small multiple of one staged column
_INDEX_SLICE_CAP = 64

# 12-bit value limbs for the fused kernel's exact integer accumulation
# (pallas_kernels._LIMB_BITS aliases this): i64-staged value columns ship
# as pre-split limb PLANES so the kernel never touches i64 math
LIMB_BITS = 12


def pack_bits(bits_needed: int) -> int:
    """Device bit width: power-of-two so values never straddle words."""
    for b in (1, 2, 4, 8, 16):
        if bits_needed <= b:
            return b
    return 32


class PackedColumn:
    """Planar bit-packed dictIds: ``words`` [num_tiles, W] uint32."""

    def __init__(self, words, bits: int):
        self.words = words
        self.bits = bits
        self.vals_per_word = 32 // bits


class SegmentHostImage:
    """Host-RAM tier image of one demoted :class:`StagedSegment`: numpy
    copies of every device array, byte-accounted against the residency
    manager's host budget (``pinot.server.query.hostram.budget.bytes``).
    Promotion hands the image back to a fresh StagedSegment, which
    restores each array with a plain H2D ``jnp.asarray`` — no decode, no
    dictionary build, no bit-packing (the cheap half of the ISCA'23
    D2H+H2D vs rebuild tradeoff, ~10x cheaper than a cold column build).
    Containers mirror the StagedSegment caches: ``columns`` holds
    :class:`StagedColumn` objects whose fields are numpy arrays."""

    __slots__ = ("columns", "packed", "values", "startree",
                 "segment_names", "_segment_ref", "_nbytes")

    def __init__(self, segment):
        import weakref

        # weakref: a host image must not keep an unloaded segment (and its
        # mmapped buffers) alive; identity is re-validated at promotion
        self._segment_ref = weakref.ref(segment)
        self.segment_names = (segment.segment_name,)
        self.columns: Dict[str, StagedColumn] = {}  # race-ok: quiesced_by_refcount
        self.packed: Dict[str, tuple] = {}  # race-ok: quiesced_by_refcount
        self.values: Dict[str, np.ndarray] = {}  # race-ok: quiesced_by_refcount
        self.startree: Dict[int, Dict[str, np.ndarray]] = {}  # race-ok: quiesced_by_refcount
        self._nbytes = 0

    def seal(self) -> "SegmentHostImage":
        """Freeze the byte count after the demoting thread filled the
        containers (the residency manager accounts this number once, at
        host-tier admission)."""
        total = 0
        for col in self.columns.values():
            for arr in col.tree().values():
                total += int(getattr(arr, "nbytes", 0))
        for words, _bits in self.packed.values():
            total += int(getattr(words, "nbytes", 0))
        for v in self.values.values():
            total += int(getattr(v, "nbytes", 0))
        for tree in self.startree.values():
            for arr in tree.values():
                total += int(getattr(arr, "nbytes", 0))
        self._nbytes = total
        return self

    def empty(self) -> bool:
        return not (self.columns or self.packed or self.values
                    or self.startree)

    def matches(self, segment) -> bool:
        """Identity check at promotion: a reloaded segment (same name, new
        object) must never be served stale host copies."""
        return segment is not None and self._segment_ref() is segment

    def nbytes(self) -> int:
        return self._nbytes

    def release(self) -> None:
        """Drop the host arrays eagerly (big numpy buffers should not wait
        for GC of stray references)."""
        self.columns.clear()
        self.packed.clear()
        self.values.clear()
        self.startree.clear()
        self._nbytes = 0


class StagedSegment:
    """Device image of one segment (subset of columns, staged on demand).

    Column builds serialize on a per-segment lock: two query threads
    staging the same column must share ONE set of device arrays — a
    duplicate build leaks its losing copy until GC (the round-2 residency
    hazard). Reads stay lock-free (dict get is atomic under the GIL).

    Conservation contract (machine-enforced by the lint ``conservation``
    family's cache-parity rule): every field this class populates outside
    ``__init__`` must be counted in ``nbytes()`` AND cleared in
    ``release()`` — staged bytes invisible to the HBM budget, or device
    arrays that outlive eviction, are exactly the drift the gate blocks."""

    def __init__(self, segment: ImmutableSegment, borrower=None,
                 host_image: Optional[SegmentHostImage] = None):
        self.segment = segment
        self.num_docs = segment.num_docs
        self.capacity = segment.padded_capacity
        # host-tier promotion source (residency demote/promote protocol):
        # per-array numpy copies consumed on first access — a restored
        # array is one H2D jnp.asarray, skipping decode/dictionary/pack
        # work entirely. Host RAM, so never counted in nbytes(); arrays
        # leave the image as they promote, and release() drops leftovers.
        self._host_image = host_image
        # writes-only guard: double-checked locking — reads are deliberate
        # lock-free dict gets (atomic under the GIL), builds serialize
        self._columns: Dict[str, StagedColumn] = {}  # guarded-by-writes: _lock
        self._packed: Dict[str, PackedColumn] = {}  # guarded-by-writes: _lock
        self._values: Dict[str, jnp.ndarray] = {}  # guarded-by-writes: _lock
        # star-tree node arrays: tree index -> {pseudo-column key -> array}
        # (engine/plan.py startree_dim_key/startree_metric_key namespace) —
        # resident like any column: counted in nbytes(), dropped in release()
        self._startree: Dict[int, Dict[str, jnp.ndarray]] = {}  # guarded-by-writes: _lock
        # index-rung idx arrays: filter fingerprint -> padded int32 docIds
        # (LRU-capped; tiny next to columns but resident all the same —
        # counted in nbytes(), dropped in release())
        self._index_slices: "OrderedDict[Any, jnp.ndarray]" = OrderedDict()  # guarded-by-writes: _lock
        self._valid_cache = None  # guarded-by-writes: _lock
        self._lock = threading.Lock()
        # device_nbytes() is asked for every resident several times a
        # query; the walk is remembered until a staged array comes or
        # goes. _mutations is bumped AFTER each such change, so a walk
        # that raced one is stamped with the older count and walked again
        self._mutations = 0  # guarded-by-writes: _lock
        self._nbytes_memo = None  # (mutations, bytes by device)
        # cross-query dedup hook: ``borrower(segment, name)`` may return a
        # StagedColumn built from a resident sharded batch's device copy of
        # the same column (no second H2D, dictvals buffer shared) — wired
        # by the sharded executor through the residency manager
        self._borrower = borrower

    def column(self, name: str) -> StagedColumn:
        col = self._columns.get(name)
        if col is None:
            with self._lock:
                col = self._columns.get(name)
                if col is None:
                    if self._borrower is not None:
                        col = self._borrower(self.segment, name)
                    if col is None:
                        col = self._promote_column(name)
                    if col is None:
                        col = self._stage(name)
                    self._columns[name] = col
                    self._mutations += 1
        return col

    def _promote_column(self, name: str) -> Optional[StagedColumn]:
        """Host-tier restore: plain H2D of the demoted numpy arrays (no
        decode/dictionary/pack work). Consumes the image's copy — promoted
        bytes are device-owned from here on."""
        img = self._host_image
        if img is None:
            return None
        hc = img.columns.pop(name, None)
        if hc is None:
            return None
        sc = StagedColumn(data_type=hc.data_type,
                          has_dictionary=hc.has_dictionary)
        for k in ("fwd", "dictvals", "mv", "mvcount", "null"):
            v = getattr(hc, k)
            if v is not None:
                setattr(sc, k, jnp.asarray(v))
        return sc

    def _stage(self, name: str) -> StagedColumn:
        ds = self.segment.data_source(name)
        cm = ds.metadata
        sc = StagedColumn(data_type=cm.data_type, has_dictionary=cm.has_dictionary)

        if cm.single_value:
            fwd = np.asarray(ds.forward_index)
            if cm.has_dictionary:
                sc.fwd = jnp.asarray(fwd.astype(np.int32))
            else:
                # RAW values: integral narrowed by stats bounds; floats stay
                # f64 for exact filter-literal comparison (see module note)
                if cm.data_type.is_integral:
                    sc.fwd = jnp.asarray(fwd.astype(staged_int_dtype(cm)))
                else:
                    sc.fwd = jnp.asarray(fwd.astype(np.float64))
        else:
            dense, counts = ds.dense_mv()
            sc.mv = jnp.asarray(dense)
            sc.mvcount = jnp.asarray(counts)

        if cm.has_dictionary and cm.data_type.is_numeric:
            vals = np.asarray(ds.dictionary.device_values())
            if cm.data_type.is_integral:
                sc.dictvals = jnp.asarray(vals.astype(staged_int_dtype(cm)))
            else:
                sc.dictvals = jnp.asarray(vals.astype(np.float32))

        if cm.has_nulls:
            sc.null = jnp.asarray(np.asarray(ds.null_bitmap))
        return sc

    def packed_column(self, name: str) -> Optional[PackedColumn]:
        """Planar bit-packed dictIds for the Pallas scan kernel, or None if
        the column/segment shape doesn't fit the packed layout."""
        pc = self._packed.get(name)
        if pc is None:
            with self._lock:
                pc = self._packed.get(name)
                if pc is None:
                    pc = self._promote_packed(name)
                    if pc is None:
                        pc = self._pack(name)
                    if pc is None:
                        return None
                    self._packed[name] = pc
                    self._mutations += 1
        return pc

    def _promote_packed(self, name: str) -> Optional["PackedColumn"]:
        img = self._host_image
        if img is None:
            return None
        hp = img.packed.pop(name, None)
        if hp is None:
            return None
        words, bits = hp
        return PackedColumn(jnp.asarray(words), bits)

    def pallas_capacity(self) -> int:
        """Doc capacity padded up to a whole number of Pallas tiles (the
        kernel's validity mask drops the zero-padded tail)."""
        return -(-self.capacity // PALLAS_TILE) * PALLAS_TILE

    def _pack(self, name: str) -> Optional["PackedColumn"]:
        ds = self.segment.data_source(name)
        cm = ds.metadata
        if not (cm.has_dictionary and cm.single_value):
            return None
        bits = pack_bits(max(1, (max(cm.cardinality - 1, 1)).bit_length()))
        K = 32 // bits
        W = PALLAS_TILE // K
        cap = self.pallas_capacity()
        ids = np.zeros(cap, dtype=np.uint32)
        fwd = np.asarray(ds.forward_index)
        ids[:fwd.shape[0]] = fwd.astype(np.uint32)
        tiles = cap // PALLAS_TILE
        planes = ids.reshape(tiles, K, W)
        words = np.zeros((tiles, W), dtype=np.uint32)
        for k in range(K):
            words |= planes[:, k, :] << np.uint32(k * bits)
        return PackedColumn(jnp.asarray(words), bits)

    def value_column(self, name: str) -> Optional[jnp.ndarray]:
        """Decoded per-doc numeric values [capacity] (f32 / i32) for kernels
        that read values without a dictionary gather; one-time decode, cached
        in HBM (the metric-column analogue of raw chunk indexes)."""
        v = self._values.get(name)
        if v is None:
            img = self._host_image
            if img is not None:
                with self._lock:
                    v = self._values.get(name)
                    if v is None:
                        hv = img.values.pop(name, None)
                        if hv is not None:
                            v = jnp.asarray(hv)
                            self._values[name] = v
                            self._mutations += 1
                if v is not None:
                    return v
            ds = self.segment.data_source(name)
            cm = ds.metadata
            if not (cm.single_value and cm.data_type.is_numeric):
                return None
            col = self.column(name)
            with self._lock:
                v = self._values.get(name)
                if v is None:
                    if cm.has_dictionary:
                        v = col.dictvals[col.fwd]
                    else:
                        v = col.fwd
                    if cm.data_type.is_integral:
                        v = v.astype(staged_int_dtype(cm))
                    else:
                        v = v.astype(jnp.float32)
                    pad = self.pallas_capacity() - v.shape[0]
                    if pad:
                        v = jnp.pad(v, (0, pad))
                    self._values[name] = v
                    self._mutations += 1
        return v

    @staticmethod
    def _limb_key(name: str, k: int) -> str:
        # '#' can't appear in a column name, so limb-plane cache entries
        # never collide with value_column entries in _values
        return f"{name}#limb{k}"

    def value_limb_planes(self, name: str,
                          limbs: int) -> Optional[List[jnp.ndarray]]:
        """i64-staged value column as ``limbs`` pre-split 12-bit limb
        PLANES [pallas_capacity] i32 (plane ``k`` = ``(v >> 12k) & 0xFFF``;
        the top plane keeps the sign via arithmetic shift — bit-for-bit
        the fused kernel's own in-kernel split, applied host-side at the
        value-load layer). Cached in ``_values`` under reserved keys, so
        the residency conservation contract (nbytes/release/demote/
        promote) covers the planes like any staged value array."""
        keys = [self._limb_key(name, k) for k in range(limbs)]
        got = [self._values.get(k) for k in keys]
        if all(v is not None for v in got):
            return got
        ds = self.segment.data_source(name)
        cm = ds.metadata
        if not (cm.single_value and cm.data_type.is_numeric
                and cm.data_type.is_integral):
            return None
        with self._lock:
            got = [self._values.get(k) for k in keys]
            if all(v is not None for v in got):
                return got
            img = self._host_image
            if img is not None:
                hv = [img.values.pop(k, None) for k in keys]
                if all(v is not None for v in hv):
                    planes = [jnp.asarray(v) for v in hv]
                    for k, p in zip(keys, planes):
                        self._values[k] = p
                    self._mutations += 1
                    return planes
                for k, v in zip(keys, hv):   # partial image: rebuild cold
                    if v is not None:
                        img.values[k] = v
            fwd = np.asarray(ds.forward_index)
            if cm.has_dictionary:
                vals = np.asarray(ds.dictionary.device_values()
                                  ).astype(np.int64)
                v = vals[fwd]
            else:
                v = fwd.astype(np.int64)
            pad = self.pallas_capacity() - v.shape[0]
            if pad:
                v = np.pad(v, (0, pad))
            mask = np.int64((1 << LIMB_BITS) - 1)
            planes = []
            for k in range(limbs):
                if k < limbs - 1:
                    p = ((v >> (k * LIMB_BITS)) & mask).astype(np.int32)
                else:
                    p = (v >> (k * LIMB_BITS)).astype(np.int32)
                planes.append(jnp.asarray(p))
            for k, p in zip(keys, planes):
                self._values[k] = p
            self._mutations += 1
        return planes

    def startree_nodes(self, tree_index: int) -> Dict[str, jnp.ndarray]:
        """Device image of star-tree ``tree_index``'s node record columns:
        one int32 [R] array per split dimension (dictIds, STAR = -1) and
        one value array per pre-agg pair (i64 counts, f64 values). Staged
        once per resident — the star-tree rung gathers query-selected node
        slices out of these, so repeat queries pay zero H2D for the tree."""
        key = int(tree_index)
        t = self._startree.get(key)
        if t is None:
            with self._lock:
                t = self._startree.get(key)
                if t is None:
                    t = self._promote_startree(key)
                    if t is None:
                        t = self._stage_startree(key)
                    self._startree[key] = t
                    self._mutations += 1
        return t

    def release_startree(self, tree_index: int) -> int:
        """Drop ONE star-tree's device arrays, leaving sibling trees (and
        every staged column) resident — the per-tree eviction grain.
        Returns the device bytes released. Host-image leftovers for the
        tree are kept on purpose: a later ``startree_nodes`` call then
        restages with one H2D promotion instead of a cold rebuild.
        In-flight launches holding the popped dict keep their arrays alive
        by reference; only the residency accounting lets go here."""
        with self._lock:
            t = self._startree.pop(int(tree_index), None)
            self._mutations += 1
        if t is None:
            return 0
        return sum(int(getattr(a, "nbytes", 0)) for a in t.values())

    def startree_nbytes(self) -> Dict[int, int]:
        """Device bytes per resident tree index (each tree accounted
        independently — /debug/memory's per-tree view)."""
        return {ti: sum(int(getattr(a, "nbytes", 0)) for a in t.values())
                for ti, t in list(self._startree.items())}

    def _promote_startree(self, key: int):
        img = self._host_image
        if img is None:
            return None
        ht = img.startree.pop(key, None)
        if ht is None:
            return None
        return {k: jnp.asarray(v) for k, v in ht.items()}

    def _stage_startree(self, tree_index: int) -> Dict[str, jnp.ndarray]:
        from pinot_tpu.engine.plan import (
            startree_dim_key,
            startree_metric_key,
        )

        tree = self.segment.star_trees[tree_index]
        cols: Dict[str, jnp.ndarray] = {}
        dims = np.asarray(tree.dims)
        for i, name in enumerate(tree.config.dimensions_split_order):
            cols[startree_dim_key(name)] = jnp.asarray(
                np.ascontiguousarray(dims[:, i]).astype(np.int32))
        for pair, vals in tree.metrics.items():
            fn, _, col = pair.partition("__")
            dt = np.int64 if fn == "count" else np.float64
            cols[startree_metric_key(fn, col)] = jnp.asarray(
                np.asarray(vals).astype(dt))
        return cols

    def index_slice(self, key, build) -> jnp.ndarray:
        """Device idx array for one resolved filter (index rung): the padded
        int32 docId slice, H2D'd once per (filter, capacity) and reused by
        repeat queries — the point-lookup analogue of the star-tree node
        cache. ``build()`` returns the padded host array on miss. LRU-capped:
        a dashboard's rotating literal set must not grow the resident
        unboundedly (the residency manager re-measures via ``account`` after
        every install, so the cap is a working-set bound, not the budget)."""
        arr = self._index_slices.get(key)
        if arr is not None:
            with self._lock:
                if key in self._index_slices:
                    self._index_slices.move_to_end(key)
            return arr
        with self._lock:
            arr = self._index_slices.get(key)
            if arr is None:
                arr = jnp.asarray(build())
                self._index_slices[key] = arr
                while len(self._index_slices) > _INDEX_SLICE_CAP:
                    self._index_slices.popitem(last=False)
                self._mutations += 1
        return arr

    def release_index_slices(self) -> int:
        """Drop every resident idx array (columns stay resident) — the
        index rung's eviction grain. Returns the device bytes released;
        in-flight launches keep their array alive by reference."""
        with self._lock:
            slices = list(self._index_slices.values())
            self._index_slices.clear()
            self._mutations += 1
        return sum(int(getattr(a, "nbytes", 0)) for a in slices)

    def index_nbytes(self) -> int:
        """Device bytes held by resident idx arrays (/debug/memory view)."""
        return sum(int(getattr(a, "nbytes", 0))
                   for a in list(self._index_slices.values()))

    def valid_mask(self):
        """Upsert valid-doc snapshot [capacity] for the validdocs kernel
        param, or None when the segment isn't upsert-managed. Versioned
        bitmaps (_LiveValidDocs) get a DEVICE-committed snapshot cached on
        the mutation version, so repeat queries skip the H2D upload (a
        round trip per query otherwise); unversioned raw-array attaches get
        a fresh host snapshot per call (per-query snapshot semantics
        either way). The single implementation of the snapshot build."""
        v = getattr(self.segment, "valid_doc_ids", None)
        if v is None:
            return None
        ver = getattr(v, "version", None)
        if ver is not None:
            cached = getattr(self, "_valid_cache", None)
            if cached is not None and cached[0] == ver:
                return cached[1]
        n = self.segment.num_docs
        snap = np.zeros(self.capacity, dtype=bool)
        snap[:n] = np.asarray(v[:n])
        if ver is None:
            return snap
        arr = jnp.asarray(snap)
        with self._lock:
            self._valid_cache = (ver, arr)
            self._mutations += 1
        return arr

    def device_nbytes(self) -> Dict[int, int]:
        """Device bytes this segment holds resident, by the device they
        lie on (HBM accounting for the residency manager): its own arrays
        on the device they were put on, a column borrowed from a sharded
        batch wherever that slice lives. Walks the staged arrays — list()
        snapshots the dicts against concurrent stagers — once a state of
        them: the answer is kept until ``_mutations`` moves."""
        seen = self._mutations
        memo = self._nbytes_memo
        if memo is not None and memo[0] == seen:
            return dict(memo[1])
        into: Dict[int, int] = {}
        for col in list(self._columns.values()):
            for arr in col.tree().values():
                add_device_bytes(arr, into)
        for pc in list(self._packed.values()):
            add_device_bytes(pc.words, into)
        for v in list(self._values.values()):
            add_device_bytes(v, into)
        for t in list(self._startree.values()):
            for arr in t.values():
                add_device_bytes(arr, into)
        for a in list(self._index_slices.values()):
            add_device_bytes(a, into)
        vc = self._valid_cache
        if vc is not None:
            add_device_bytes(vc[1], into)
        self._nbytes_memo = (seen, into)
        return dict(into)

    def nbytes(self) -> int:
        """All of ``device_nbytes()``: on one device what it always was."""
        return sum(self.device_nbytes().values())

    def demote(self) -> Optional[SegmentHostImage]:
        """D2H snapshot for the residency host-RAM tier, then release the
        device arrays. Returns the host image (or None when nothing was
        staged — nothing worth keeping). The device syncs run OUTSIDE the
        segment lock (the snapshot under the lock is just dict copies):
        a column build landing after the snapshot is simply not captured
        and rebuilds cold on the next stage. Unconsumed leftovers of this
        resident's OWN promotion image are still-valid host copies and
        carry over, so demote(promote(demote(x))) never decays."""
        with self._lock:
            cols = dict(self._columns)
            packed = dict(self._packed)
            values = dict(self._values)
            trees = dict(self._startree)
            src = self._host_image
        img = SegmentHostImage(self.segment)
        for name, col in cols.items():
            hc = StagedColumn(data_type=col.data_type,
                              has_dictionary=col.has_dictionary)
            for k in ("fwd", "dictvals", "mv", "mvcount", "null"):
                v = getattr(col, k)
                if v is not None:
                    setattr(hc, k, np.asarray(v))
            img.columns[name] = hc
        for name, pc in packed.items():
            img.packed[name] = (np.asarray(pc.words), pc.bits)
        for name, v in values.items():
            img.values[name] = np.asarray(v)
        for ti, tree in trees.items():
            img.startree[ti] = {k: np.asarray(v) for k, v in tree.items()}
        if src is not None:
            for name, hc in src.columns.items():
                img.columns.setdefault(name, hc)
            for name, hp in src.packed.items():
                img.packed.setdefault(name, hp)
            for name, hv in src.values.items():
                img.values.setdefault(name, hv)
            for ti, ht in src.startree.items():
                img.startree.setdefault(ti, ht)
        self.release()
        if img.empty():
            return None
        return img.seal()

    def release(self) -> None:
        """Drop device references (HBM freed when XLA GCs the buffers).
        Locked against in-flight column builds: a build completing after
        the clear would re-insert into a released segment (its arrays are
        then invisible to the residency accounting until GC)."""
        with self._lock:
            self._columns.clear()
            self._packed.clear()
            self._values.clear()
            self._startree.clear()
            # idx arrays rebuild from the host-resolved docIds in one H2D —
            # cheaper than any column restage, so they never demote to the
            # host image; release drops them outright
            self._index_slices.clear()
            self._valid_cache = None
            # (a plain assignment: the lint's cache-parity rule wants
            # every populated field assigned in release())
            self._mutations = self._mutations + 1
            self._nbytes_memo = None
            img = self._host_image
            if img is not None:
                # demote() re-homed anything worth keeping before calling
                # release(); leftover numpy buffers free eagerly
                img.release()


# The HBM residency manager subsumed the old unbounded StagingCache
# (budget + pins + LRU + spill admission live in engine/residency.py);
# the name stays importable from here for existing callers. Lazy (PEP 562)
# because residency imports this module for StagedSegment.
def __getattr__(name: str):
    if name in ("StagingCache", "ResidencyManager"):
        from pinot_tpu.engine import residency

        return getattr(residency, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
