"""Immutable segment: mmap loader + per-column DataSource access.

Re-design of ``ImmutableSegmentImpl.java:48`` / ``ImmutableSegmentLoader.java:57``
+ ``datasource/DataSource.java:36``: a loaded segment wires each column's
dictionary, forward index, optional null bitmap and inverted index behind one
access object. All index arrays are ``np.load(mmap_mode="r")`` views — the
host never copies column data until it is staged to the device.
"""

from __future__ import annotations

import os
from functools import cached_property
from typing import Dict, List, Optional, Tuple

import numpy as np

from pinot_tpu.segment import metadata as meta
from pinot_tpu.segment.creator import COLUMNS_DIR, compute_dir_crc
from pinot_tpu.segment.dictionary import (
    Dictionary,
    NumericDictionary,
    StringDictionary,
)
from pinot_tpu.spi.data import DataType


class DataSource:
    """Single column's read access (ref: DataSource.java:36)."""

    def __init__(self, segment: "ImmutableSegment", name: str):
        self._segment = segment
        self.name = name
        self.metadata = segment.metadata.column(name)

    @cached_property
    def dictionary(self) -> Optional[Dictionary]:
        return self._segment._load_dictionary(self.name)

    @cached_property
    def forward_index(self) -> np.ndarray:
        """SV: [padded_capacity] dictIds or raw values.
        MV: [total_entries] flattened dictIds (use ``mv_offsets``)."""
        cm = self.metadata
        if cm.stored_dtype.startswith("packed:"):
            # fixed-bit packed (native unpack into an int32 staging buffer,
            # ref: FixedBitSVForwardIndexReaderV2.java:32)
            from pinot_tpu import native

            bits = int(cm.stored_dtype.split(":", 1)[1])
            buf = native.MmapBuffer(
                self._segment._path(self.name, "fwdpk", ext="bin"))
            try:
                return native.bitunpack(
                    buf.read(), self._segment.metadata.padded_capacity, bits)
            finally:
                buf.release()
        if cm.compression_codec:
            # chunk-compressed raw column: decompress once (HBM staging
            # consumes the dense array; ref: BaseChunkSVForwardIndexReader)
            from pinot_tpu.segment.compression import read_compressed

            return read_compressed(
                self._segment._path(self.name, "fwdcc", ext="bin"))
        return self._segment._load_array(self.name, "fwd")

    @cached_property
    def mv_offsets(self) -> Optional[np.ndarray]:
        if self.metadata.single_value:
            return None
        return self._segment._load_array(self.name, "mvoff")

    @cached_property
    def null_bitmap(self) -> Optional[np.ndarray]:
        if not self.metadata.has_nulls:
            return None
        return self._segment._load_array(self.name, "null")

    @cached_property
    def bloom_filter(self):
        """BloomFilter over distinct values, or None
        (ref: BloomFilterReader; used by the server-side pruner)."""
        if not self.metadata.has_bloom_filter:
            return None
        from pinot_tpu.utils.bloom import BloomFilter

        return BloomFilter.from_array(
            self._segment._load_array(self.name, "bloom"))

    @cached_property
    def json_index(self):
        """JsonIndexReader, or None (ref: ImmutableJsonIndexReader)."""
        if not self.metadata.has_json_index:
            return None
        from pinot_tpu.segment.jsonindex import JsonIndexReader

        with open(self._segment._path(self.name, "jinv", ext="bin"),
                  "rb") as f:
            blob = f.read()
        return JsonIndexReader(
            self._segment._load_array(self.name, "jkeysoff"),
            self._segment._load_array(self.name, "jkeysblob"),
            self._segment._load_array(self.name, "jinvoff"),
            self._segment._load_array(self.name, "jinvbo"),
            blob, self._segment.num_docs)

    @cached_property
    def text_index(self):
        """TextIndexReader over dictIds, or None (ref: TextIndexReader)."""
        if not self.metadata.has_text_index:
            return None
        from pinot_tpu.segment.textindex import TextIndexReader

        with open(self._segment._path(self.name, "txtinv", ext="bin"),
                  "rb") as f:
            blob = f.read()
        d = self.dictionary
        return TextIndexReader(
            self._segment._load_array(self.name, "txtoff"),
            self._segment._load_array(self.name, "txtblob"),
            self._segment._load_array(self.name, "txtinvoff"),
            self._segment._load_array(self.name, "txtinvbo"),
            blob, self.metadata.cardinality,
            value_of=lambda i: d.get_value(int(i)))

    @cached_property
    def fst_index(self):
        """FstIndexReader for REGEXP prefix narrowing, or None
        (ref: LuceneFSTIndexReader)."""
        if not self.metadata.has_fst_index:
            return None
        from pinot_tpu.segment.fstindex import FstIndexReader

        return FstIndexReader(
            self._segment._load_array(self.name, "fstoff"),
            self._segment._load_array(self.name, "fstlab"),
            self._segment._load_array(self.name, "fsttgt"),
            self._segment._load_array(self.name, "fstrng"),
            self.dictionary)

    @cached_property
    def geo_index(self):
        """GeoIndexReader for distance prefilters, or None
        (ref: ImmutableH3IndexReader)."""
        if not self.metadata.has_geo_index:
            return None
        from pinot_tpu.segment.geoindex import GeoIndexReader

        meta_arr = self._segment._load_array(self.name, "geometa")
        # segments built before coordinate arrays existed fall back to the
        # reader's parse-candidates path
        has_coords = os.path.exists(
            self._segment._path(self.name, "geolng"))
        return GeoIndexReader(
            self._segment._load_array(self.name, "geocells"),
            int(meta_arr[0]), self.dictionary,
            lngs=(self._segment._load_array(self.name, "geolng")
                  if has_coords else None),
            lats=(self._segment._load_array(self.name, "geolat")
                  if has_coords else None))

    @cached_property
    def range_order(self):
        """Sorted-order permutation for RANGE binary search, or None
        (host-path equivalent of BitSlicedRangeIndexReader)."""
        if not self.metadata.has_range_index:
            return None
        return self._segment._load_array(self.name, "rangeord")

    @cached_property
    def range_sorted_values(self):
        """Values in sorted order, gathered ONCE per staged segment so a
        RANGE lookup is O(log n) search + O(k) scatter per query."""
        order = self.range_order
        if order is None:
            return None
        n = self._segment.num_docs
        return np.asarray(self.forward_index[:n])[np.asarray(order)]

    @cached_property
    def inverted_index(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(doc-count offsets[card+1], byte offsets[card+1]) of the varint
        posting lists, or None (ref: BitmapInvertedIndexReader.java:34)."""
        if not self.metadata.has_inverted_index:
            return None
        return (self._segment._load_array(self.name, "invoff"),
                self._segment._load_array(self.name, "invbo"))

    @cached_property
    def _inv_blob(self):
        from pinot_tpu import native

        return native.MmapBuffer(
            self._segment._path(self.name, "inv", ext="bin"))

    def doc_ids_for_dict_id(self, dict_id: int) -> np.ndarray:
        """Inverted lookup: sorted docIds containing dictId (native varint
        posting-list decode)."""
        from pinot_tpu import native

        inv = self.inverted_index
        if inv is None:
            raise ValueError(f"no inverted index on column {self.name!r}")
        offsets, byte_offsets = inv
        n = int(offsets[dict_id + 1] - offsets[dict_id])
        if n == 0:
            return np.empty(0, dtype=np.int32)
        raw = self._inv_blob.as_array(
            np.uint8, count=int(byte_offsets[dict_id + 1] - byte_offsets[dict_id]),
            offset=int(byte_offsets[dict_id]))
        return native.varint_decode(raw.tobytes(), n)

    def dense_mv(self) -> Tuple[np.ndarray, np.ndarray]:
        """Densify the MV column for device staging:
        returns (values [padded_capacity, max_mv] with 0-padding,
                 counts [padded_capacity] int32).

        Fixed-shape layout is the TPU representation of the reference's
        var-length MV forward index (FixedBitMVForwardIndexReader)."""
        cm = self.metadata
        assert not cm.single_value
        capacity = self._segment.metadata.padded_capacity
        num_docs = self._segment.metadata.num_docs
        max_mv = max(cm.max_num_multi_values, 1)
        offsets = self.mv_offsets
        flat = self.forward_index
        row_counts = np.diff(offsets)
        counts = np.zeros(capacity, dtype=np.int32)
        counts[:num_docs] = row_counts.astype(np.int32)
        dense = np.zeros((capacity, max_mv), dtype=np.int32)
        # CSR -> dense: rows are variable length; vectorized fill
        row_idx = np.repeat(np.arange(num_docs), row_counts)
        col_idx = np.arange(offsets[-1]) - np.repeat(offsets[:-1], row_counts)
        dense[row_idx, col_idx] = flat.astype(np.int32)
        return dense, counts


class ImmutableSegment:
    """Ref: ImmutableSegmentImpl.java:48 (read path only; creation lives in
    segment/creator.py, mutation in segment/mutable.py)."""

    def __init__(self, segment_dir: str, metadata: meta.SegmentMetadata):
        self.segment_dir = segment_dir
        self.metadata = metadata
        self._data_sources: Dict[str, DataSource] = {}

    # -- IndexSegment interface (ref: IndexSegment.java:32) ---------------
    @property
    def segment_name(self) -> str:
        return self.metadata.segment_name

    @property
    def num_docs(self) -> int:
        return self.metadata.num_docs

    @property
    def padded_capacity(self) -> int:
        return self.metadata.padded_capacity

    @property
    def column_names(self):
        return list(self.metadata.columns.keys())

    def data_source(self, column: str) -> DataSource:
        ds = self._data_sources.get(column)
        if ds is None:
            self.metadata.column(column)  # raises on unknown column
            ds = DataSource(self, column)
            self._data_sources[column] = ds
        return ds

    def loaded_string_dictionaries(self) -> List[StringDictionary]:
        """The STRING/BYTES dictionaries some reader has opened so far;
        opens none itself (``/debug/memory``)."""
        found = (ds.__dict__.get("dictionary")
                 for ds in list(self._data_sources.values()))
        return [d for d in found if isinstance(d, StringDictionary)]

    @cached_property
    def star_trees(self):
        """Loaded star-trees (ref: ImmutableSegmentImpl star-tree wiring)."""
        from pinot_tpu.segment.startree import StarTree

        trees = []
        for i in range(self.metadata.star_tree_count):
            t = StarTree.load(self.segment_dir, index=i)
            if t is not None:
                trees.append(t)
        return trees

    # -- loading helpers ---------------------------------------------------
    def _path(self, column: str, suffix: str, ext: str = "npy") -> str:
        return os.path.join(self.segment_dir, COLUMNS_DIR,
                            f"{column}.{suffix}.{ext}")

    def _load_array(self, column: str, suffix: str) -> np.ndarray:
        return np.load(self._path(column, suffix), mmap_mode="r")

    def _load_dictionary(self, column: str) -> Optional[Dictionary]:
        cm = self.metadata.column(column)
        if not cm.has_dictionary:
            return None
        if cm.data_type.is_numeric:
            return NumericDictionary(self._load_array(column, "dict"), cm.data_type)
        return StringDictionary(self._load_array(column, "dictoff"),
                                self._load_array(column, "dictblob"),
                                cm.data_type)

    # -- value reads (host-side; used by selection results + tests) -------
    def get_value(self, column: str, doc_id: int):
        ds = self.data_source(column)
        cm = ds.metadata
        if cm.single_value:
            v = ds.forward_index[doc_id]
            if cm.has_dictionary:
                return ds.dictionary.get_value(int(v))
            return cm.data_type.convert(v)
        offsets = ds.mv_offsets
        ids = ds.forward_index[offsets[doc_id]:offsets[doc_id + 1]]
        return [ds.dictionary.get_value(int(i)) for i in ids]

    def __repr__(self) -> str:
        return (f"ImmutableSegment({self.segment_name!r}, docs={self.num_docs}, "
                f"columns={len(self.metadata.columns)})")


def load_segment(segment_dir: str) -> ImmutableSegment:
    """Ref: ImmutableSegmentLoader.load:57 (mmap via PinotDataBuffer in the
    reference; numpy mmap here)."""
    md_path = os.path.join(segment_dir, meta.METADATA_FILE)
    if not os.path.isfile(md_path):
        raise FileNotFoundError(f"not a segment directory (no {meta.METADATA_FILE}): "
                                f"{segment_dir}")
    sm = meta.SegmentMetadata.load(md_path)
    return ImmutableSegment(segment_dir, sm)


def verify_crc(segment_dir: str) -> bool:
    """Recompute the CRC over all index files and compare to metadata
    (refresh detection, ref: creation.meta CRC)."""
    seg = load_segment(segment_dir)
    col_dir = os.path.join(segment_dir, COLUMNS_DIR)
    return compute_dir_crc(col_dir) == seg.metadata.crc
