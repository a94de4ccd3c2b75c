"""Segment creation: the two-pass columnar index build.

Re-design of ``SegmentIndexCreationDriverImpl.java:81`` +
``SegmentColumnarIndexCreator.java:78``: pass 1 collects per-column stats
(unique values, min/max, sortedness, MV fan-out), then dictionaries are
built, then pass 2 writes the forward (and optional inverted) indexes.

Output layout (file-per-index, like the reference's v1 format,
``V1Constants.java:25-27``) under ``<segment_dir>/``:

- ``metadata.json``                   segment + column metadata, CRC
- ``columns/<col>.dict.npy``          numeric dictionary (sorted values)
- ``columns/<col>.dictoff.npy`` / ``.dictblob.npy``  string/bytes dictionary
- ``columns/<col>.fwdpk.bin``         SV dict column: fixed-bit packed
  dictIds over [padded_capacity] (native pack/unpack,
  ref: FixedBitSVForwardIndexWriter; stored_dtype records ``packed:<bits>``)
- ``columns/<col>.fwd.npy``           RAW numeric SV values; MV: flattened
  dictIds
- ``columns/<col>.mvoff.npy``         MV row offsets [num_docs + 1]
- ``columns/<col>.null.npy``          optional null bitmap [padded_capacity]
- ``columns/<col>.invoff.npy`` / ``.invbo.npy`` / ``.inv.bin``  optional
  inverted index: per-dictId delta+varint posting lists (the
  RoaringBitmap-equivalent form, ref: BitmapInvertedIndexReader.java:34)

Forward indexes are padded to ``padded_capacity`` (multiple of 1024 docs) so
staged device arrays are tile-aligned; pad rows carry dictId 0 / value 0 and
are masked by ``doc_id >= num_docs`` in kernels.
"""

from __future__ import annotations

import os
import time

from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Union

import numpy as np

from pinot_tpu import native
from pinot_tpu.segment import metadata as meta
from pinot_tpu.segment.dictionary import (
    NumericDictionary,
    StringDictionary,
    build_dictionary,
)
from pinot_tpu.spi.data import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.spi.table import IndexingConfig, TableConfig
from pinot_tpu.utils.partition import get_partition_function

COLUMNS_DIR = "columns"


def _sorted_factorize(arr: np.ndarray):
    """(sorted unique values, int64 dictIds) for a flat value array.

    Hash-based ``pd.factorize`` + a cardinality-sized sort: O(n + k log k)
    vs the O(n log n) full-column sort of ``np.unique(return_inverse=True)``
    — the segment-build hot spot at SSB scale (profiling: ~70% of build
    time was argsorting 375k-row string columns whose cardinality is 25)."""
    import pandas as pd

    codes, uniq = pd.factorize(arr, use_na_sentinel=False)
    uniq = np.asarray(uniq)
    order = np.argsort(uniq, kind="stable")
    rank = np.empty(order.shape[0], dtype=np.int64)
    rank[order] = np.arange(order.shape[0], dtype=np.int64)
    return uniq[order], rank[codes]


def compute_dir_crc(col_dir: str) -> int:
    """CRC over all index files in canonical (sorted-filename) order, for
    refresh detection (ref: creation.meta CRC, V1Constants.java:56).
    Native file CRC when the library is available."""
    crc = 0
    for fname in sorted(os.listdir(col_dir)):
        crc = native.crc32_file(os.path.join(col_dir, fname), crc)
    return crc & 0xFFFFFFFF

RowsInput = Union[Iterable[Mapping[str, Any]], Mapping[str, Sequence[Any]]]


def build_inverted_index(name: str, dict_ids_flat: np.ndarray,
                         mv_counts: Optional[np.ndarray], num_docs: int,
                         cardinality: int, save, col_dir: str) -> None:
    """Inverted index: per dictId, the sorted docIds containing it, stored
    as delta+varint posting lists (the RoaringBitmap-equivalent compressed
    form; ref: creators under segment/creator/impl/inv/). ``invoff`` =
    cumulative doc counts, ``invbo`` = byte offsets into the varint blob.
    Shared by the creator and the reload preprocessor."""
    if mv_counts is None:
        doc_ids = np.arange(num_docs, dtype=np.int64)
        ids = dict_ids_flat[:num_docs]
    else:
        doc_ids = np.repeat(np.arange(num_docs, dtype=np.int64), mv_counts)
        ids = dict_ids_flat
    order = np.lexsort((doc_ids, ids))
    sorted_ids = ids[order]
    sorted_docs = doc_ids[order].astype(np.int32)
    offsets = np.zeros(cardinality + 1, dtype=np.int64)
    np.add.at(offsets, sorted_ids + 1, 1)
    offsets = np.cumsum(offsets)
    save("invoff", offsets)
    blob, byte_offsets = native.varint_encode_lists(sorted_docs, offsets)
    save("invbo", byte_offsets)
    with open(os.path.join(col_dir, f"{name}.inv.bin"), "wb") as f:
        f.write(blob)


class SegmentBuilder:
    """Driver for building one immutable segment directory.

    ``rows`` may be an iterable of row dicts (GenericRow equivalent,
    ref: pinot-spi data/readers/GenericRow.java) or a columnar mapping
    ``column -> sequence`` (fast path for batch ingest).
    """

    def __init__(self, schema: Schema, segment_name: str,
                 table_name: Optional[str] = None,
                 indexing_config: Optional[IndexingConfig] = None,
                 table_config: Optional[TableConfig] = None):
        self.schema = schema
        self.segment_name = segment_name
        if table_config is not None:
            self.table_name = table_config.table_name
            self.indexing = table_config.indexing_config
            self.field_configs = {c.name: c
                                  for c in table_config.field_config_list}
        else:
            self.table_name = table_name or schema.schema_name
            self.indexing = indexing_config or IndexingConfig()
            self.field_configs = {}

    # -- public API --------------------------------------------------------
    def build(self, rows: RowsInput, out_dir: str) -> meta.SegmentMetadata:
        columns = self._to_columnar(rows)
        num_docs = self._num_docs(columns)
        capacity = meta.pad_capacity(num_docs)

        seg_dir = os.path.join(out_dir, self.segment_name)
        col_dir = os.path.join(seg_dir, COLUMNS_DIR)
        os.makedirs(col_dir, exist_ok=True)

        col_metas: Dict[str, meta.ColumnMetadata] = {}
        for fs in self.schema.field_specs:
            values = columns.get(fs.name)
            cm = self._build_column(fs, values, num_docs, capacity, col_dir)
            col_metas[fs.name] = cm
        crc = compute_dir_crc(col_dir)

        time_col = self.schema.time_column
        min_t = max_t = None
        if time_col is not None and col_metas[time_col].min_value is not None:
            # integral time columns store the range as ints; string/float time
            # columns keep the raw values (pruners compare in column order)
            mn, mx = col_metas[time_col].min_value, col_metas[time_col].max_value
            if self.schema.field_spec(time_col).data_type.is_integral:
                min_t, max_t = int(mn), int(mx)
            else:
                min_t, max_t = mn, mx

        sm = meta.SegmentMetadata(
            segment_name=self.segment_name,
            table_name=self.table_name,
            schema=self.schema,
            num_docs=num_docs,
            padded_capacity=capacity,
            creation_time_ms=meta.now_ms(),
            time_column=time_col,
            min_time=min_t,
            max_time=max_t,
            crc=crc,
            columns=col_metas,
        )
        sm.star_tree_count = self._build_star_trees(seg_dir, sm)
        sm.save(os.path.join(seg_dir, meta.METADATA_FILE))
        return sm

    def _build_star_trees(self, seg_dir: str, sm: meta.SegmentMetadata) -> int:
        """Build configured star-trees over the just-written columns
        (ref: MultipleTreesBuilder after SegmentColumnarIndexCreator)."""
        from pinot_tpu.segment.startree import StarTreeBuilder, StarTreeConfig

        configs = [StarTreeConfig.from_spi(c)
                   for c in self.indexing.star_tree_index_configs]
        if self.indexing.enable_default_star_tree and not configs:
            default = self._default_star_tree_config(sm)
            if default is not None:
                configs = [default]
        if not configs:
            return 0

        col_dir = os.path.join(seg_dir, COLUMNS_DIR)

        def load(col: str, suffix: str) -> np.ndarray:
            return np.load(os.path.join(col_dir, f"{col}.{suffix}.npy"))

        def load_fwd(col: str) -> np.ndarray:
            cm = sm.columns[col]
            if cm.stored_dtype.startswith("packed:"):
                bits = int(cm.stored_dtype.split(":", 1)[1])
                with open(os.path.join(col_dir, f"{col}.fwdpk.bin"),
                          "rb") as f:
                    return native.bitunpack(f.read(), sm.padded_capacity,
                                            bits)
            if cm.compression_codec:
                from pinot_tpu.segment.compression import read_compressed

                return read_compressed(
                    os.path.join(col_dir, f"{col}.fwdcc.bin"))
            return np.load(os.path.join(col_dir, f"{col}.fwd.npy"))

        from pinot_tpu.segment.startree import derived_pair_expr

        count = 0
        build_s: List[float] = []
        for cfg in configs:
            try:
                dim_ids = {}
                for d in cfg.dimensions_split_order:
                    cm = sm.columns[d]
                    if not (cm.has_dictionary and cm.single_value):
                        raise ValueError(f"dimension {d} must be a "
                                         "dict-encoded SV column")
                    dim_ids[d] = load_fwd(d).astype(np.int32)
                metric_vals = {}
                for fn, col in cfg.function_column_pairs:
                    if col == "*":
                        continue
                    # derived pair columns ('sum__(a*b)') evaluate in the
                    # builder from their base columns' raw values
                    expr = derived_pair_expr(col)
                    for c in (expr.columns() if expr is not None else [col]):
                        if c in metric_vals:
                            continue
                        cm = sm.columns[c]
                        if not (cm.single_value and cm.data_type.is_numeric):
                            raise ValueError(f"metric {c} must be a numeric "
                                             "SV column")
                        fwd = load_fwd(c)
                        if cm.has_dictionary:
                            metric_vals[c] = load(c, "dict")[fwd]
                        else:
                            metric_vals[c] = fwd
                t0 = time.perf_counter()
                tree = StarTreeBuilder(cfg).build(dim_ids, metric_vals,
                                                  sm.num_docs)
                build_s.append(round(time.perf_counter() - t0, 4))
                tree.save(seg_dir, index=count)
                count += 1
            except (ValueError, KeyError, OSError) as e:
                import logging

                logging.getLogger(__name__).warning(
                    "skipping star-tree for %s: %s", self.segment_name, e)
        sm.star_tree_build_s = build_s
        return count

    def _default_star_tree_config(self, sm: meta.SegmentMetadata):
        """Ref: enableDefaultStarTree — dimensions with bounded cardinality
        (descending), COUNT(*) + SUM per numeric metric."""
        from pinot_tpu.segment.startree import StarTreeConfig

        dims = [(cm.cardinality, name) for name, cm in sm.columns.items()
                if cm.has_dictionary and cm.single_value
                and sm.schema.field_spec(name).is_dimension
                and 1 < cm.cardinality <= 10_000]
        if not dims:
            return None
        split = [n for _, n in sorted(dims, reverse=True)]
        pairs = [("count", "*")]
        for name, cm in sm.columns.items():
            if sm.schema.field_spec(name).is_metric and cm.data_type.is_numeric \
                    and cm.single_value:
                pairs.append(("sum", name))
        return StarTreeConfig(split, pairs, max_leaf_records=10_000)

    # -- internals ---------------------------------------------------------
    def _to_columnar(self, rows: RowsInput) -> Dict[str, List[Any]]:
        if isinstance(rows, Mapping):
            # numpy arrays pass through untouched (vectorized build path)
            return {k: (v if isinstance(v, np.ndarray) else list(v))
                    for k, v in rows.items()}
        columns: Dict[str, List[Any]] = {n: [] for n in self.schema.column_names}
        for row in rows:
            for name in self.schema.column_names:
                columns[name].append(row.get(name))
        return columns

    def _num_docs(self, columns: Dict[str, List[Any]]) -> int:
        sizes = {len(v) for v in columns.values() if v is not None}
        if not sizes:
            raise ValueError("no input rows")
        if len(sizes) != 1:
            raise ValueError(f"ragged column lengths: { {k: len(v) for k, v in columns.items()} }")
        return sizes.pop()

    def _normalize(self, fs: FieldSpec, values: Optional[List[Any]],
                   num_docs: int) -> tuple:
        """Null substitution + type coercion. Returns (values, null_mask).

        Vectorized fast path: an SV column handed a numpy array skips the
        per-element convert loop (the batch-ingest analogue of the
        reference's columnar stats collectors — SSB-scale builds would
        otherwise spend minutes in python object conversion)."""
        if (isinstance(values, np.ndarray) and values.ndim == 1
                and fs.single_value):
            if fs.data_type.is_numeric and values.dtype.kind in "iuf":
                if values.dtype.kind == "f":
                    nulls = np.isnan(values)
                    if nulls.any():
                        out = values.copy()
                        out[nulls] = fs.default_null_value
                        return out.astype(fs.data_type.stored_np), nulls
                return (values.astype(fs.data_type.stored_np),
                        np.zeros(num_docs, dtype=bool))
            if (values.dtype.kind == "U"
                    and fs.data_type in (DataType.STRING, DataType.JSON)):
                # unicode arrays only: BYTES columns (and 'S' arrays) must
                # go through per-element convert or str(v) would store
                # python byte reprs
                return values, np.zeros(num_docs, dtype=bool)
        if values is None:
            values = [None] * num_docs
        null_mask = np.zeros(num_docs, dtype=bool)
        out: List[Any] = []
        default = fs.default_null_value
        if fs.single_value:
            for i, v in enumerate(values):
                if v is None or (isinstance(v, float) and v != v):
                    # None and float NaN are both nulls (real-world readers
                    # surface missing numeric cells as NaN)
                    null_mask[i] = True
                    out.append(default)
                else:
                    out.append(fs.data_type.convert(v))
        else:
            def is_nan(x):
                return isinstance(x, float) and x != x

            for i, v in enumerate(values):
                if v is None or is_nan(v) or (
                        isinstance(v, (list, tuple, np.ndarray)) and len(v) == 0):
                    null_mask[i] = True
                    out.append([default])
                elif isinstance(v, (list, tuple, np.ndarray)):
                    vals = [fs.data_type.convert(x) for x in v
                            if not (x is None or is_nan(x))]
                    if vals:
                        out.append(vals)
                    else:
                        null_mask[i] = True
                        out.append([default])
                else:
                    out.append([fs.data_type.convert(v)])
        return out, null_mask

    def _build_column(self, fs: FieldSpec, raw_values: Optional[List[Any]],
                      num_docs: int, capacity: int,
                      col_dir: str) -> meta.ColumnMetadata:
        values, null_mask = self._normalize(fs, raw_values, num_docs)
        has_nulls = bool(null_mask.any())
        fc = self.field_configs.get(fs.name)
        no_dict = ((fs.name in self.indexing.no_dictionary_columns
                    or (fc is not None and fc.encoding_type.upper() == "RAW"))
                   and fs.data_type.is_numeric and fs.single_value)
        want_inverted = fs.name in self.indexing.inverted_index_columns

        def save(suffix: str, arr: np.ndarray) -> None:
            np.save(os.path.join(col_dir, f"{fs.name}.{suffix}.npy"), arr)

        if has_nulls:
            nb = np.zeros(capacity, dtype=bool)
            nb[:num_docs] = null_mask
            save("null", nb)

        if no_dict:
            # RAW numeric column: fwd index holds values directly
            arr = np.zeros(capacity, dtype=fs.data_type.stored_np)
            arr[:num_docs] = np.asarray(values, dtype=fs.data_type.stored_np)
            codec_used = None
            if fc is not None and fc.compression_codec:
                # chunk-compressed raw index (ref: ChunkCompressorFactory +
                # VarByteChunkSVForwardIndexWriterV4)
                from pinot_tpu.segment.compression import write_compressed

                codec_used = write_compressed(
                    os.path.join(col_dir, f"{fs.name}.fwdcc.bin"),
                    arr, fc.compression_codec)
            else:
                save("fwd", arr)
            data = arr[:num_docs]
            uniq = np.unique(data)
            is_sorted = bool(np.all(data[:-1] <= data[1:])) if num_docs > 1 else True
            has_range = False
            if fs.name in self.indexing.range_index_columns and num_docs:
                # sorted-order permutation: RANGE resolves by binary search
                # + slice instead of a full compare scan (the host-path
                # equivalent of BitSlicedRangeIndexReader; the device path
                # keeps its dense compare — that IS the TPU-shaped plan)
                save("rangeord", np.argsort(data, kind="stable")
                     .astype(np.int32))
                has_range = True
            return meta.ColumnMetadata(
                name=fs.name, data_type=fs.data_type, field_type=fs.field_type,
                single_value=True, encoding=meta.Encoding.RAW,
                cardinality=int(len(uniq)),
                stored_dtype=str(arr.dtype),
                min_value=data.min() if num_docs else None,
                max_value=data.max() if num_docs else None,
                is_sorted=is_sorted, has_dictionary=False, has_nulls=has_nulls,
                has_bloom_filter=self._maybe_build_bloom(fs.name, uniq, save),
                has_range_index=has_range,
                compression_codec=codec_used,
                **self._partition_meta(fs.name, values),
            )

        # -- dictionary encoding ------------------------------------------
        if fs.single_value:
            flat = values
        else:
            flat = [x for row in values for x in row]

        if fs.data_type.is_numeric:
            flat_arr = np.asarray(flat, dtype=fs.data_type.stored_np)
            dict_values, dict_ids_flat = _sorted_factorize(flat_arr)
            dictionary = build_dictionary(dict_values, fs.data_type)
        elif isinstance(flat, np.ndarray):
            # vectorized string dictionary build (numpy sorts ASCII the
            # same way python does)
            uniq_arr, dict_ids_flat = _sorted_factorize(flat)
            dictionary = build_dictionary([str(v) for v in uniq_arr],
                                          fs.data_type)
        else:
            uniq = sorted(set(flat))
            dictionary = build_dictionary(uniq, fs.data_type)
            lookup = {v: i for i, v in enumerate(uniq)}
            dict_ids_flat = np.fromiter((lookup[v] for v in flat),
                                        dtype=np.int64, count=len(flat))

        card = dictionary.cardinality
        dtype = meta.narrowest_int_dtype(card)

        # persist dictionary
        if isinstance(dictionary, NumericDictionary):
            save("dict", dictionary.raw_array)
        else:
            assert isinstance(dictionary, StringDictionary)
            save("dictoff", dictionary.offsets)
            save("dictblob", dictionary.blob)

        if fs.single_value:
            # fixed-bit packed forward index (ref: FixedBitSVForwardIndexWriter
            # — the dominant scan format; unpacked natively at load into
            # int32 HBM-staging buffers)
            bits = native.bits_needed(max(card, 1))
            fwd = np.zeros(capacity, dtype=np.int32)
            fwd[:num_docs] = dict_ids_flat.astype(np.int32)
            with open(os.path.join(col_dir, f"{fs.name}.fwdpk.bin"),
                      "wb") as f:
                f.write(native.bitpack(fwd, bits))
            dtype = f"packed:{bits}"
            sv_ids = dict_ids_flat
            is_sorted = bool(np.all(sv_ids[:-1] <= sv_ids[1:])) if num_docs > 1 else True
            max_mv, total_entries = 0, num_docs
        else:
            offsets = np.zeros(num_docs + 1, dtype=np.int64)
            for i, row in enumerate(values):
                offsets[i + 1] = offsets[i] + len(row)
            save("mvoff", offsets)
            save("fwd", dict_ids_flat.astype(dtype))
            is_sorted = False
            max_mv = int(max((len(r) for r in values), default=0))
            total_entries = int(offsets[-1])

        if want_inverted:
            self._build_inverted(fs.name, dict_ids_flat,
                                 values if not fs.single_value else None,
                                 num_docs, card, save, col_dir=col_dir)

        has_bloom = self._maybe_build_bloom(
            fs.name, lambda: dictionary.get_values(range(card)), save)
        has_json = self._maybe_build_json_index(fs, values, num_docs, save,
                                                col_dir)
        has_text = False
        if (fs.name in self.indexing.text_index_columns
                and fs.single_value and not fs.data_type.is_numeric):
            # text index over the DICTIONARY values: postings hold dictIds,
            # so TEXT_MATCH resolves to the same dictId-LUT shape the
            # device scan consumes (ref: LuceneTextIndexCreator)
            from pinot_tpu.segment.textindex import build_text_index

            build_text_index(dictionary.get_values(range(card)), save,
                             col_dir, fs.name)
            has_text = True

        has_geo = False
        if (fc is not None and (fc.index_type or "").upper() == "H3"
                and fs.single_value and not fs.data_type.is_numeric):
            # grid-cell geo index over the dictionary's WKT points
            # (ref: H3IndexCreator; design note in geoindex.py)
            from pinot_tpu.segment.geoindex import (
                DEFAULT_RESOLUTION,
                build_geo_index,
            )

            res = int(str(fc.properties.get(
                "resolutions", DEFAULT_RESOLUTION)).split(",")[0])
            has_geo = build_geo_index(
                dictionary.get_values(range(card)), res, save)

        has_fst = False
        if ((fs.name in self.indexing.fst_index_columns
             or (fc is not None and (fc.index_type or "").upper() == "FST"))
                and fs.single_value and not fs.data_type.is_numeric):
            # FST index: CSR byte-trie over the sorted dictionary terms
            # (ref: LuceneFSTIndexCreator; design note in fstindex.py)
            from pinot_tpu.segment.fstindex import FstIndexBuilder

            eo, el, et, nr = FstIndexBuilder(
                [str(v) for v in dictionary.get_values(range(card))]).build()
            save("fstoff", eo)
            save("fstlab", el)
            save("fsttgt", et)
            save("fstrng", nr)
            has_fst = True

        return meta.ColumnMetadata(
            name=fs.name, data_type=fs.data_type, field_type=fs.field_type,
            single_value=fs.single_value, encoding=meta.Encoding.DICT,
            cardinality=card, stored_dtype=dtype,
            min_value=dictionary.min_value if card else None,
            max_value=dictionary.max_value if card else None,
            is_sorted=is_sorted, has_dictionary=True,
            has_inverted_index=want_inverted, has_nulls=has_nulls,
            has_bloom_filter=has_bloom, has_json_index=has_json,
            has_text_index=has_text, has_fst_index=has_fst,
            has_geo_index=has_geo,
            max_num_multi_values=max_mv, total_number_of_entries=total_entries,
            **self._partition_meta(fs.name, values),
        )

    def _maybe_build_json_index(self, fs: FieldSpec, values, num_docs: int,
                                save, col_dir: str) -> bool:
        """JSON flattening index when configured (ref: jsonIndexColumns ->
        segment/creator/impl/inv/json/)."""
        if (fs.name not in self.indexing.json_index_columns
                or not fs.single_value or fs.data_type.is_numeric):
            return False
        from pinot_tpu.segment.jsonindex import build_json_index

        build_json_index(list(values), num_docs, save, col_dir, fs.name)
        return True

    def _maybe_build_bloom(self, name: str, distinct_values, save) -> bool:
        """Bloom filter over a column's distinct values when configured
        (ref: bloomFilterColumns -> OnHeapGuavaBloomFilterCreator).
        ``distinct_values`` may be a zero-arg callable so unconfigured
        columns never materialize their dictionary."""
        if name not in self.indexing.bloom_filter_columns:
            return False
        from pinot_tpu.utils.bloom import BloomFilter

        if callable(distinct_values):
            distinct_values = distinct_values()
        bf = BloomFilter.from_values(list(distinct_values))
        save("bloom", bf.to_array())
        return True

    def _build_inverted(self, name: str, dict_ids_flat: np.ndarray,
                        mv_rows: Optional[List[List[Any]]], num_docs: int,
                        cardinality: int, save, col_dir: str) -> None:
        counts = (None if mv_rows is None else
                  np.fromiter((len(r) for r in mv_rows), dtype=np.int64,
                              count=num_docs))
        build_inverted_index(name, dict_ids_flat, counts, num_docs,
                             cardinality, save, col_dir)

    def _partition_meta(self, col: str, values: List[Any]) -> Dict[str, Any]:
        spc = self.indexing.segment_partition_config
        if not spc or col not in spc.column_partition_map:
            return {}
        cfg = spc.column_partition_map[col]
        fn = get_partition_function(cfg.get("functionName", "Murmur"),
                                    int(cfg.get("numPartitions", 1)))
        return {
            "partition_function": fn.name,
            "num_partitions": fn.num_partitions,
            "partitions": fn.partitions_of(values),
        }
