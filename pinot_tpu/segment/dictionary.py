"""Per-column sorted dictionaries.

Re-design of ``pinot-segment-local/.../readers/BaseImmutableDictionary.java``
and ``SegmentDictionaryCreator.java:45``: values are sorted ascending so
dictId order == value order, which makes range predicates on dictionary
columns a *dictId interval* — the property the TPU filter kernels exploit
(a RANGE filter compiles to ``lo <= dictId <= hi``, pure vector compares).

Numeric dictionaries are plain sorted numpy arrays (device-stageable
directly). String/bytes dictionaries use an offsets+blob layout (mmap
friendly) on disk and are read from process memory where their size
allows; the device only ever sees their dictIds.
"""

from __future__ import annotations

import sys
from bisect import bisect_left
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from pinot_tpu.spi.data import DataType


class Dictionary:
    """Read interface (ref: pinot-segment-spi index/reader/Dictionary.java:33)."""

    data_type: DataType

    def __len__(self) -> int:
        raise NotImplementedError

    @property
    def cardinality(self) -> int:
        return len(self)

    def hll_register_luts(self, log2m: int):
        """Memoized (bucket, rank) register LUTs over this dictionary's
        values — the device HLL's plan-time parameters (string hashing is
        python-loop FNV, so recomputing per query would dominate plan
        time; the LUT depends only on (dictionary, log2m))."""
        cache = getattr(self, "_hll_luts", None)
        if cache is None:
            cache = {}
            self._hll_luts = cache
        luts = cache.get(log2m)
        if luts is None:
            from pinot_tpu.utils.hll import dictionary_register_luts

            luts = dictionary_register_luts(
                self.get_values(range(len(self))), log2m)
            cache[log2m] = luts
        return luts

    def index_of(self, value: Any) -> int:
        """value -> dictId, or -1 if absent (ref: Dictionary.NULL_VALUE_INDEX)."""
        raise NotImplementedError

    def insertion_index_of(self, value: Any) -> int:
        """Like index_of, but returns -(insertion_point+1) when absent
        (binary-search contract used by range predicate evaluation)."""
        raise NotImplementedError

    def get_value(self, dict_id: int) -> Any:
        raise NotImplementedError

    def get_values(self, dict_ids: Sequence[int]) -> List[Any]:
        return [self.get_value(i) for i in dict_ids]

    @property
    def min_value(self) -> Any:
        return self.get_value(0)

    @property
    def max_value(self) -> Any:
        return self.get_value(len(self) - 1)

    def device_values(self) -> Optional[np.ndarray]:
        """Numeric dictionaries expose their sorted value array for HBM
        staging (dictId -> value gather on device); None for var-width."""
        return None

    def range_to_dict_id_interval(self, lo: Any, hi: Any,
                                  lo_inclusive: bool, hi_inclusive: bool) -> Tuple[int, int]:
        """Map a value range to the matching closed dictId interval [a, b]
        (empty iff a > b). Core of dictionary-based range predicate eval
        (ref: RangePredicateEvaluatorFactory dictionary-based path)."""
        n = len(self)
        if lo is None:
            a = 0
        else:
            idx = self.insertion_index_of(lo)
            if idx >= 0:
                a = idx if lo_inclusive else idx + 1
            else:
                a = -idx - 1
        if hi is None:
            b = n - 1
        else:
            idx = self.insertion_index_of(hi)
            if idx >= 0:
                b = idx if hi_inclusive else idx - 1
            else:
                b = -idx - 2
        return a, b


def needle_for(arr: np.ndarray, v: Any) -> Any:
    """``v`` as a scalar of ``arr``'s own integer dtype where it fits:
    numpy copies an int32 array to search it with an int64 or a Python
    int, which is the whole column (or dictionary) a query."""
    dt = arr.dtype
    if (dt.kind in "iu" and isinstance(v, (int, np.integer))
            and not isinstance(v, bool)):
        info = np.iinfo(dt)
        if info.min <= int(v) <= info.max:
            return dt.type(v)
    return v


class NumericDictionary(Dictionary):
    def __init__(self, values: np.ndarray, data_type: DataType):
        # values must be sorted ascending and unique
        self._values = values
        self.data_type = data_type

    def __len__(self) -> int:
        return int(self._values.shape[0])

    def index_of(self, value: Any) -> int:
        i = int(np.searchsorted(self._values,
                                needle_for(self._values, value)))
        if i < len(self._values) and self._values[i] == value:
            return i
        return -1

    def insertion_index_of(self, value: Any) -> int:
        i = int(np.searchsorted(self._values,
                                needle_for(self._values, value)))
        if i < len(self._values) and self._values[i] == value:
            return i
        return -(i + 1)

    def get_value(self, dict_id: int) -> Any:
        v = self._values[dict_id]
        if self.data_type in (DataType.FLOAT, DataType.DOUBLE):
            return float(v)
        return int(v)

    def get_values(self, dict_ids: Sequence[int]) -> List[Any]:
        arr = self._values[np.asarray(dict_ids)]
        return arr.tolist()

    def device_values(self) -> Optional[np.ndarray]:
        return self._values

    @property
    def raw_array(self) -> np.ndarray:
        return self._values


# A string dictionary whose values would take at most this much process
# memory (``_host_bytes_estimate``) is read from memory; a larger one stays on
# its mapped blob. A server holds segments x string columns of these, and only
# those a query has read: SSB's largest (p_brand1, 1,000 values, 9 KB of blob)
# estimates to 114 KB, and 1,000 segments of 20 such columns to 2.3 GB.
MATERIALISE_MAX_HOST_BYTES = 1 << 20


def _host_bytes_estimate(cardinality: int, blob_bytes: int) -> int:
    """Process memory of ``cardinality`` entries held as ``bytes`` and as
    ``str``: two object headers (33 + 49 B) and two pointers (list, object
    array) an entry, and the blob's bytes twice."""
    return 98 * cardinality + 2 * blob_bytes


class _BlobEntries:
    """The raw entries of an offsets + blob pair as a sequence of ``bytes``,
    read through plain views of the two arrays: a mapped file is then read by
    buffer slices, and no ``np.memmap`` method runs for a value."""

    def __init__(self, offsets: np.ndarray, blob: np.ndarray):
        self._offsets = offsets.view(np.ndarray)
        self._blob = memoryview(blob.view(np.ndarray))

    def __len__(self) -> int:
        return int(self._offsets.shape[0]) - 1

    def __getitem__(self, dict_id: int) -> bytes:
        lo, hi = self._offsets[dict_id:dict_id + 2].tolist()
        return bytes(self._blob[lo:hi])

    def take(self, dict_ids: np.ndarray) -> List[bytes]:
        blob = self._blob
        return [bytes(blob[lo:hi])
                for lo, hi in zip(self._offsets[dict_ids].tolist(),
                                  self._offsets[dict_ids + 1].tolist())]


class _Materialised:
    """A dictionary's entries in process memory: ``raw`` in the file's
    (bytewise) order for the search, ``values`` decoded for the reads."""

    def __init__(self, raw: List[bytes], is_bytes: bool):
        self.raw = raw
        self.values = np.empty(len(raw), dtype=object)
        self.values[:] = raw if is_bytes else [r.decode("utf-8") for r in raw]
        held = [raw] if is_bytes else [raw, self.values]
        self.host_bytes = (sys.getsizeof(raw) + self.values.nbytes
                           + sum(sys.getsizeof(v) for vs in held for v in vs))


class StringDictionary(Dictionary):
    """Sorted UTF-8 strings as offsets[card+1] + byte blob.

    Bytes dictionaries reuse this with raw bytes (sorted bytewise, which
    matches the reference's ByteArray comparison order).

    Reads go a batch at a time over one of two storage forms, chosen by the
    size known at load: up to ``MATERIALISE_MAX_HOST_BYTES`` the entries are
    materialised in process memory on first use; above it they stay on the
    (mapped) blob and are read through ``_BlobEntries``.
    """

    def __init__(self, offsets: np.ndarray, blob: np.ndarray, data_type: DataType):
        self._offsets = offsets
        self._blob = blob
        self.data_type = data_type
        self._is_bytes = data_type is DataType.BYTES
        self._entries = _BlobEntries(offsets, blob)
        self.blob_backed = _host_bytes_estimate(
            len(self._entries), int(blob.shape[0])) > MATERIALISE_MAX_HOST_BYTES
        # built aside and assigned once: workers that race here build equal
        # values and the last assignment wins
        self._materialised: Optional[_Materialised] = None

    @classmethod
    def from_values(cls, sorted_values: List[Any], data_type: DataType) -> "StringDictionary":
        encoded = [v if isinstance(v, bytes) else str(v).encode("utf-8")
                   for v in sorted_values]
        offsets = np.zeros(len(encoded) + 1, dtype=np.int64)
        for i, e in enumerate(encoded):
            offsets[i + 1] = offsets[i] + len(e)
        blob = np.frombuffer(b"".join(encoded), dtype=np.uint8).copy()
        return cls(offsets, blob, data_type)

    def __len__(self) -> int:
        return len(self._entries)

    def _in_memory(self) -> Optional[_Materialised]:
        m = self._materialised
        if m is None and not self.blob_backed:
            m = _Materialised(
                self._entries.take(np.arange(len(self), dtype=np.intp)),
                self._is_bytes)
            self._materialised = m
        return m

    @property
    def host_bytes(self) -> int:
        """Process memory held by the materialised entries (0 before the
        first read, and always for a blob-backed dictionary)."""
        m = self._materialised
        return 0 if m is None else m.host_bytes

    def get_value(self, dict_id: int) -> Any:
        m = self._in_memory()
        if m is not None:
            return m.values[dict_id]
        raw = self._entries[dict_id]
        return raw if self._is_bytes else raw.decode("utf-8")

    def get_values(self, dict_ids: Sequence[int]) -> List[Any]:
        ids = np.asarray(dict_ids, dtype=np.intp)
        m = self._in_memory()
        if m is not None:
            return m.values[ids].tolist()
        raws = self._entries.take(ids)
        return raws if self._is_bytes else [r.decode("utf-8") for r in raws]

    def _encode(self, value: Any) -> bytes:
        return value if isinstance(value, bytes) else str(value).encode("utf-8")

    def insertion_index_of(self, value: Any) -> int:
        target = self._encode(value)
        m = self._in_memory()
        raws = self._entries if m is None else m.raw
        i = bisect_left(raws, target)
        if i < len(raws) and raws[i] == target:
            return i
        return -(i + 1)

    def index_of(self, value: Any) -> int:
        i = self.insertion_index_of(value)
        return i if i >= 0 else -1

    @property
    def offsets(self) -> np.ndarray:
        return self._offsets

    @property
    def blob(self) -> np.ndarray:
        return self._blob


def build_dictionary(sorted_unique_values: List[Any], data_type: DataType) -> Dictionary:
    """Creator-side entry (ref: SegmentDictionaryCreator.java:45)."""
    if data_type.is_numeric:
        arr = np.asarray(sorted_unique_values, dtype=data_type.stored_np)
        return NumericDictionary(arr, data_type)
    return StringDictionary.from_values(sorted_unique_values, data_type)
