"""StarTreeV2: pre-aggregation index — build, store, and query execution.

Re-design of ``pinot-segment-local/.../startree/v2/builder/BaseSingleTreeBuilder.java``
(sort on dimension split order, recursive node split with ``maxLeafRecords``,
star-node records aggregated over the split dimension) plus the query side
(``StarTreeUtils.isFitForStarTree``/``StarTreeFilterOperator.java:87`` tree
walk and ``StarTreeV2.java:29`` read contract).

TPU-first storage: records are flat columnar arrays — ``dims [R, D]`` int32
dictIds with ``STAR = -1`` sentinels and one contiguous float64/int64 column
per aggregation function pair — so the selected record ranges feed the same
masked-reduction kernels as regular columns. The *tree walk* stays host-side:
it is a pruning structure over R pre-aggregated records (R << num_docs),
where a dense device scan would waste the pre-aggregation. The walk
(``StarTree.select_records``) descends a few nodes, their fields held in
memory, and goes on inside every leaf it reaches by binary search: a node's
record range is sorted on the dimensions in split order, so a leaf at depth
``L`` is a sorted column on dimension ``L``, and on the next dimension below
every single value. Only what the search cannot decide is read and masked,
over the narrowed ranges. The record indices come back in ascending order.
"""

from __future__ import annotations

import json
import os

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

STAR = -1
STARTREE_DIR = "startree{index}"
META_FILE = "startree_metadata.json"


class DictIdRange:
    """Contiguous inclusive dictId interval [lo, hi] — the cap-safe match
    representation for RANGE predicates: sorted dictionaries map a value
    range to one contiguous dictId run, so a predicate matching millions of
    dictIds is a two-compare slice check instead of a materialized set
    (the set-based path caps at ``startree_exec._MAX_RANGE_IDS``)."""

    __slots__ = ("lo", "hi")

    def __init__(self, lo: int, hi: int):
        self.lo = int(lo)
        self.hi = int(hi)

    def __contains__(self, v) -> bool:
        return self.lo <= int(v) <= self.hi

    def __len__(self) -> int:
        return max(0, self.hi - self.lo + 1)

    def __repr__(self) -> str:
        return f"DictIdRange({self.lo}, {self.hi})"


def match_bounds(match) -> Tuple[int, int]:
    """Inclusive (lo, hi) dictId bounds of a match (set or DictIdRange);
    (0, -1) for an empty match."""
    if isinstance(match, DictIdRange):
        return match.lo, match.hi
    if not match:
        return 0, -1
    return min(match), max(match)

# aggregation pairs supported in tree records (ref:
# AggregationFunctionColumnPair; COUNT uses the catch-all '*' column)
_MERGEABLE = {"count", "sum", "min", "max"}

_IDENT_RE = None  # compiled lazily (keeps the numpy-only import surface)


def canonical_pair_column(col: str) -> str:
    """Normalize a function-column pair's column half: bare column names
    pass through; arithmetic EXPRESSIONS (``lo_extendedprice*lo_discount``,
    ref: StarTreeV2 builder configs with derived columns) parse and
    canonicalize into the same key namespace the query side derives from
    aggregation arguments, so ``SUM__a*b`` stores exactly the pair
    ``sum(b * a)`` resolves. Raises ValueError for expressions outside the
    pre-aggregable +/-/* subset."""
    global _IDENT_RE
    if _IDENT_RE is None:
        import re

        _IDENT_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*\Z")
    col = col.strip()
    if col == "*" or _IDENT_RE.match(col):
        return col
    from pinot_tpu.query.expressions import canonical_arith_key
    from pinot_tpu.query.parser import parse_expression

    key = canonical_arith_key(parse_expression(col))
    if key is None:
        raise ValueError(f"function-column pair expression {col!r} is not "
                         "pre-aggregable (+/-/* over columns only)")
    return key


def derived_pair_expr(col: str):
    """The parsed expression behind a DERIVED pair column key (canonical,
    parenthesized), or None for a plain column / '*'."""
    if not col.startswith("("):
        return None
    from pinot_tpu.query.parser import parse_expression

    return parse_expression(col)


def eval_derived_column(expr, columns: Dict[str, np.ndarray],
                        num_docs: int) -> np.ndarray:
    """Vectorized one-shot evaluation of a derived pair column over raw
    forward-column values (the build-time half of expression
    pre-aggregation): integer inputs stay integral so the stored f64
    pre-agg sums are exact."""
    from pinot_tpu.query.expressions import Function, Identifier, Literal

    def ev(e):
        if isinstance(e, Identifier):
            return np.asarray(columns[e.name][:num_docs])
        if isinstance(e, Literal):
            return e.value
        assert isinstance(e, Function) and len(e.args) == 2, e
        a, b = ev(e.args[0]), ev(e.args[1])
        if e.name == "plus":
            return a + b
        if e.name == "minus":
            return a - b
        if e.name == "times":
            return a * b
        raise ValueError(f"derived column op {e.name} unsupported")

    return ev(expr)


@dataclass
class StarTreeConfig:
    """Ref: StarTreeIndexConfig.java + StarTreeV2Metadata."""

    dimensions_split_order: List[str]
    function_column_pairs: List[Tuple[str, str]]  # (agg, column); count -> '*'
    max_leaf_records: int = 10_000
    skip_star_creation: List[str] = field(default_factory=list)

    @classmethod
    def from_spi(cls, spi_config) -> "StarTreeConfig":
        """From spi.table.StarTreeIndexConfig ('SUM__revenue' pair syntax;
        the column half may be a +/-/* expression, 'SUM__a*b')."""
        pairs = []
        for p in spi_config.function_column_pairs:
            fn, _, col = p.partition("__")
            pairs.append((fn.lower(), canonical_pair_column(col or "*")))
        return cls(list(spi_config.dimensions_split_order), pairs,
                   spi_config.max_leaf_records,
                   list(spi_config.skip_star_node_creation_for_dimensions))

    def to_dict(self) -> Dict[str, Any]:
        return {
            "dimensionsSplitOrder": self.dimensions_split_order,
            "functionColumnPairs": [f"{f}__{c}" for f, c in
                                    self.function_column_pairs],
            "maxLeafRecords": self.max_leaf_records,
            "skipStarNodeCreationForDimensions": self.skip_star_creation,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "StarTreeConfig":
        pairs = []
        for p in d["functionColumnPairs"]:
            fn, _, col = p.partition("__")
            pairs.append((fn, canonical_pair_column(col or "*")))
        return cls(d["dimensionsSplitOrder"], pairs, d["maxLeafRecords"],
                   d.get("skipStarNodeCreationForDimensions", []))


# node record dtype: the serialized tree (ref: StarTreeNode on-disk layout)
_NODE_DTYPE = np.dtype([
    ("dim", np.int32),          # split dimension index of the CHILDREN
    ("value", np.int32),        # this node's dictId on parent's dim (STAR ok)
    ("start", np.int64),        # record range [start, end)
    ("end", np.int64),
    ("child_first", np.int64),  # children index range [first, last); -1 leaf
    ("child_last", np.int64),
])


class _BuildNode:
    """Intermediate node for the lexsort construction: a record range
    inside one chunk plus its children (value kids in dictId order, star
    child last), assembled into the serialized DFS layout at the end."""

    __slots__ = ("value", "chunk", "lo", "hi", "dim", "kids", "star", "idx")

    def __init__(self, value: int, chunk: int, lo: int, hi: int):
        self.value = value
        self.chunk = chunk
        self.lo = lo
        self.hi = hi
        self.dim = -1
        self.kids: Optional[List["_BuildNode"]] = None
        self.star: Optional["_BuildNode"] = None
        self.idx = -1


class StarTreeBuilder:
    """On-heap single-tree builder (ref: BaseSingleTreeBuilder, 541 LoC)."""

    def __init__(self, config: StarTreeConfig):
        self.config = config

    def build(self, dim_dict_ids: Dict[str, np.ndarray],
              metric_values: Dict[str, np.ndarray],
              num_docs: int, engine: str = "lexsort") -> "StarTree":
        """``dim_dict_ids``: per split-order dimension, [num_docs] dictIds.
        ``metric_values``: per non-count pair column, [num_docs] raw values
        (derived pair columns evaluate here from their base columns unless
        the caller pre-computed them under the canonical key).

        ``engine``: 'lexsort' (default) runs the level-batched vectorized
        construction; 'recursive' keeps the original per-node recursion —
        both emit byte-identical arrays (pinned by test_startree), the
        recursive path survives as the equality oracle."""
        cfg = self.config
        dims = np.stack([np.asarray(dim_dict_ids[d][:num_docs], dtype=np.int32)
                         for d in cfg.dimensions_split_order], axis=1)

        metrics: Dict[str, np.ndarray] = {}
        for fn, col in cfg.function_column_pairs:
            key = f"{fn}__{col}"
            if fn == "count":
                metrics[key] = np.ones(num_docs, dtype=np.int64)
                continue
            if col not in metric_values:
                expr = derived_pair_expr(col)
                if expr is not None:
                    metric_values[col] = eval_derived_column(
                        expr, metric_values, num_docs)
            metrics[key] = np.asarray(metric_values[col][:num_docs],
                                      dtype=np.float64)

        # pass 1: sort by dims, aggregate duplicate dim tuples
        dims, metrics = self._sort_and_dedup(dims, metrics)
        if engine == "recursive":
            return self._construct_recursive(dims, metrics)
        return self._construct_lexsort(dims, metrics)

    def _construct_recursive(self, dims: np.ndarray,
                             metrics: Dict[str, np.ndarray]) -> "StarTree":
        self._dims_rows: List[np.ndarray] = [dims]
        self._chunk_offsets: List[int] = [0]
        self._metric_rows: Dict[str, List[np.ndarray]] = {
            k: [v] for k, v in metrics.items()}
        self._record_count = dims.shape[0]
        self._nodes: List[Tuple] = []

        # recursive construction from the root
        root_idx = self._new_node(value=STAR, start=0, end=dims.shape[0])
        self._split(root_idx, depth=0)

        all_dims = np.concatenate(self._dims_rows, axis=0)
        all_metrics = {k: np.concatenate(v, axis=0)
                       for k, v in self._metric_rows.items()}
        nodes = np.array([tuple(n) for n in self._nodes], dtype=_NODE_DTYPE)
        return StarTree(self.config, all_dims, all_metrics, nodes)

    # -- vectorized (lexsort) construction -----------------------------------
    def _construct_lexsort(self, dims: np.ndarray,
                           metrics: Dict[str, np.ndarray]) -> "StarTree":
        """Level-batched construction: per depth, ONE boundary scan per
        chunk finds every splitting node's children and ONE ``np.lexsort``
        over all star-candidate records dedups every star child at that
        depth (vs one sort + one ``np.unique`` PER NODE in the recursion —
        the build hot loop at millions of rows). The final assembly replays
        the recursion's DFS so node/record arrays come out byte-identical."""
        cfg = self.config
        D = len(cfg.dimensions_split_order)
        max_leaf = cfg.max_leaf_records
        chunks: List[Tuple[np.ndarray, Dict[str, np.ndarray]]] = [
            (dims, metrics)]
        root = _BuildNode(STAR, 0, 0, dims.shape[0])
        level = [root]
        for depth in range(D):
            splitting = [n for n in level if n.hi - n.lo > max_leaf]
            if not splitting:
                break
            dim_name = cfg.dimensions_split_order[depth]
            make_star = dim_name not in cfg.skip_star_creation
            # one boundary pass per chunk: every position where column
            # ``depth`` changes (records are sorted within node ranges)
            cuts: Dict[int, np.ndarray] = {}
            for ci in {n.chunk for n in splitting}:
                col = chunks[ci][0][:, depth]
                cuts[ci] = np.flatnonzero(col[1:] != col[:-1]) + 1
            next_level: List[_BuildNode] = []
            star_jobs: List[_BuildNode] = []
            for n in splitting:
                n.dim = depth
                b = cuts[n.chunk]
                col = chunks[n.chunk][0][:, depth]
                inner = b[np.searchsorted(b, n.lo, side="right"):
                          np.searchsorted(b, n.hi, side="left")]
                starts = [n.lo] + [int(x) for x in inner]
                ends = starts[1:] + [n.hi]
                n.kids = [_BuildNode(int(col[s]), n.chunk, s, e)
                          for s, e in zip(starts, ends)]
                next_level.extend(n.kids)
                if make_star and len(n.kids) > 1:
                    star_jobs.append(n)
            if star_jobs:
                self._batch_star_children(chunks, star_jobs, depth,
                                          next_level)
            level = next_level
        return self._assemble(self.config, chunks, root)

    def _batch_star_children(self, chunks, star_jobs: List[_BuildNode],
                             depth: int,
                             next_level: List[_BuildNode]) -> None:
        """All star children of one level in ONE lexsort: concatenate the
        splitting nodes' record ranges with the split dim starred, sort by
        (node, dims), aggregate duplicate tuples segment-wise; each node's
        star child is then a contiguous slice of the result, appended as
        its own chunk exactly like the recursion's per-node append."""
        D = chunks[0][0].shape[1]
        keys = list(chunks[0][1].keys())
        d_parts: List[np.ndarray] = []
        id_parts: List[np.ndarray] = []
        m_parts: Dict[str, List[np.ndarray]] = {k: [] for k in keys}
        for j, n in enumerate(star_jobs):
            cd, cm = chunks[n.chunk]
            part = cd[n.lo:n.hi].copy()
            part[:, depth] = STAR
            d_parts.append(part)
            id_parts.append(np.full(n.hi - n.lo, j, dtype=np.int64))
            for k in keys:
                m_parts[k].append(cm[k][n.lo:n.hi])
        bd = np.concatenate(d_parts, axis=0)
        bi = np.concatenate(id_parts)
        bm = {k: np.concatenate(v) for k, v in m_parts.items()}
        # node id is the PRIMARY key (np.lexsort: last key is most
        # significant); within one node this is the recursion's exact
        # _sort_and_dedup permutation (same stable sort, same keys — the
        # starred/constant leading dims tie everywhere)
        order = np.lexsort(tuple(bd[:, i] for i in range(D - 1, -1, -1))
                           + (bi,))
        bd, bi = bd[order], bi[order]
        bm = {k: v[order] for k, v in bm.items()}
        change = (bi[1:] != bi[:-1]) | np.any(bd[1:] != bd[:-1], axis=1)
        starts = np.concatenate([[0], np.flatnonzero(change) + 1])
        gid = np.zeros(bd.shape[0], dtype=np.int64)
        gid[starts[1:]] = 1
        gid = np.cumsum(gid)
        ng = starts.shape[0]
        dd = bd[starts]
        di = bi[starts]
        dm = {k: self._segmented(k, v, gid, ng) for k, v in bm.items()}
        offs = np.searchsorted(di, np.arange(len(star_jobs) + 1))
        for j, n in enumerate(star_jobs):
            lo, hi = int(offs[j]), int(offs[j + 1])
            ci = len(chunks)
            chunks.append((dd[lo:hi],
                           {k: v[lo:hi] for k, v in dm.items()}))
            n.star = _BuildNode(STAR, ci, 0, hi - lo)
            next_level.append(n.star)

    @staticmethod
    def _assemble(cfg: "StarTreeConfig", chunks, root: _BuildNode
                  ) -> "StarTree":
        """Replay the recursion's DFS over the built structure: node
        indices allocate at the parent's split (value kids then star) and
        each star chunk lands in the record stream at exactly the point
        the recursion appended it, so offsets, node order, and child
        ranges match the recursive builder byte for byte."""
        chunk_off = {0: 0}
        chunk_order = [0]
        next_off = chunks[0][0].shape[0]
        nodes: List[List[int]] = []

        def alloc(bn: _BuildNode) -> None:
            bn.idx = len(nodes)
            off = chunk_off[bn.chunk]
            nodes.append([-1, bn.value, off + bn.lo, off + bn.hi, -1, -1])

        alloc(root)
        stack = [root]
        while stack:
            bn = stack.pop()
            if bn.kids is None:
                continue
            rec = nodes[bn.idx]
            rec[0] = bn.dim
            rec[4] = len(nodes)
            for c in bn.kids:
                alloc(c)
            if bn.star is not None:
                ci = bn.star.chunk
                chunk_off[ci] = next_off
                chunk_order.append(ci)
                next_off += chunks[ci][0].shape[0]
                alloc(bn.star)
            rec[5] = len(nodes)
            kids = bn.kids + ([bn.star] if bn.star is not None else [])
            stack.extend(reversed(kids))
        all_dims = np.concatenate([chunks[ci][0] for ci in chunk_order],
                                  axis=0)
        all_metrics = {k: np.concatenate([chunks[ci][1][k]
                                          for ci in chunk_order])
                       for k in chunks[0][1]}
        nodes_arr = np.array([tuple(n) for n in nodes], dtype=_NODE_DTYPE)
        return StarTree(cfg, all_dims, all_metrics, nodes_arr)

    # -- helpers -------------------------------------------------------------
    def _sort_and_dedup(self, dims, metrics):
        order = np.lexsort(tuple(dims[:, i] for i
                                 in range(dims.shape[1] - 1, -1, -1)))
        dims = dims[order]
        metrics = {k: v[order] for k, v in metrics.items()}
        # aggregate equal dim tuples
        if dims.shape[0]:
            change = np.any(np.diff(dims, axis=0) != 0, axis=1)
            starts = np.concatenate([[0], np.nonzero(change)[0] + 1])
            group_id = np.zeros(dims.shape[0], dtype=np.int64)
            group_id[starts[1:]] = 1
            group_id = np.cumsum(group_id)
            n = starts.shape[0]
            dims = dims[starts]
            metrics = {k: self._segmented(k, v, group_id, n)
                       for k, v in metrics.items()}
        return dims, metrics

    @staticmethod
    def _segmented(key: str, v: np.ndarray, gid: np.ndarray, n: int):
        fn = key.split("__", 1)[0]
        if fn in ("count", "sum"):
            out = np.zeros(n, dtype=v.dtype)
            np.add.at(out, gid, v)
            return out
        if fn == "min":
            out = np.full(n, np.inf)
            np.minimum.at(out, gid, v)
            return out
        out = np.full(n, -np.inf)
        np.maximum.at(out, gid, v)
        return out

    def _new_node(self, value: int, start: int, end: int) -> int:
        self._nodes.append([-1, value, start, end, -1, -1])
        return len(self._nodes) - 1

    def _append_records(self, dims: np.ndarray,
                        metrics: Dict[str, np.ndarray]) -> int:
        start = self._record_count
        self._dims_rows.append(dims)
        self._chunk_offsets.append(start)
        for k, v in metrics.items():
            self._metric_rows[k].append(v)
        self._record_count += dims.shape[0]
        return start

    def _range(self, start: int, end: int):
        """Slice one chunk: a node's record range never spans chunks (the
        base chunk holds the sorted input; each star child owns exactly the
        chunk its records were appended as)."""
        import bisect

        ci = bisect.bisect_right(self._chunk_offsets, start) - 1
        off = self._chunk_offsets[ci]
        lo, hi = start - off, end - off
        dims = self._dims_rows[ci][lo:hi]
        metrics = {k: v[ci][lo:hi] for k, v in self._metric_rows.items()}
        return dims, metrics

    def _split(self, node_idx: int, depth: int) -> None:
        """Ref: BaseSingleTreeBuilder.constructStarTree — split the node's
        record range on dimension ``depth``; add a star child aggregating
        the range over that dimension; recurse while above maxLeafRecords."""
        cfg = self.config
        D = len(cfg.dimensions_split_order)
        node = self._nodes[node_idx]
        start, end = node[2], node[3]
        if depth >= D or end - start <= cfg.max_leaf_records:
            return
        self._nodes[node_idx][0] = depth

        dims, metrics = self._range(start, end)
        col = dims[:, depth]
        values, first_idx = np.unique(col, return_index=True)

        children: List[int] = []
        for i, v in enumerate(values):
            c_start = start + first_idx[i]
            c_end = start + (first_idx[i + 1] if i + 1 < len(values)
                             else end - start)
            children.append(self._new_node(int(v), c_start, c_end))

        dim_name = cfg.dimensions_split_order[depth]
        if dim_name not in cfg.skip_star_creation and len(values) > 1:
            # star child: aggregate the range over this dimension
            star_dims = dims.copy()
            star_dims[:, depth] = STAR
            s_dims, s_metrics = self._sort_and_dedup(star_dims, dict(metrics))
            s_start = self._append_records(s_dims, s_metrics)
            children.append(self._new_node(STAR, s_start,
                                           s_start + s_dims.shape[0]))

        self._nodes[node_idx][4] = children[0]
        self._nodes[node_idx][5] = children[-1] + 1
        for c in children:
            self._split(c, depth + 1)


# A set of at most this many dictIds is walked value by value: two searches
# a value, and the search goes on to the next dimension below each. A larger
# set falls to its bounding range, its gaps left to the mask. SSB's IN-lists
# (Q3.3 and Q3.4 two cities, Q4.1 two manufacturers) hold 2; its longer sets
# are BETWEENs, contiguous, so their bounding range is exact.
_MAX_PINNED_VALUES = 4
# A set is tested against a column through one boolean table over the
# dictIds 0..hi; ids further out than this are too far apart for a table
# and ``np.isin`` tests them.
_MAX_TABLE_IDS = 1 << 16
_MAX_DICT_ID = np.iinfo(np.int32).max - 1   # so that a needle hi + 1 fits


class _DimMatch:
    """One dimension's predicate as the walk uses it. The dictIds
    ``[lo, hi]`` bound it; ``exact`` says that every id between them
    matches (a :class:`DictIdRange`, a contiguous set), ``pinned`` that it
    is walked value by value. DictIds are >= 0: STAR matches nothing."""

    __slots__ = ("lo", "hi", "exact", "pinned", "needles", "_ids",
                 "_member")

    def __init__(self, match):
        lo, hi = match_bounds(match)
        is_range = isinstance(match, DictIdRange)
        if lo < 0 <= hi and not is_range:
            match = [v for v in match if v >= 0]
            lo = min(match)
        self.lo = lo = max(lo, 0)
        self.hi = hi = min(hi, _MAX_DICT_ID)
        count = hi - lo + 1 if is_range else len(match)
        self.exact = count == hi - lo + 1
        self.pinned = count <= _MAX_PINNED_VALUES
        if self.pinned:
            # [v, v + 1) a value, in dictId order
            values = range(lo, hi + 1) if self.exact else sorted(match)
            self.needles = np.array([x for v in values for x in (v, v + 1)],
                                    dtype=np.int32)
        else:
            self.needles = np.array([lo, hi + 1], dtype=np.int32)
        self._ids = match
        self._member = None

    def spans(self, col: np.ndarray) -> List[Tuple[int, int]]:
        """Index spans ``[a, b)`` of the sorted ``col`` that may hold a
        match: one a value when pinned (``col`` is that one value there),
        else the one span of ``[lo, hi]``. Binary searches only: the
        needles are int32 like the column (with int64 needles numpy copies
        the column first, and the search is O(n))."""
        cuts = col.searchsorted(self.needles).tolist()
        return [(a, b) for a, b in zip(cuts[::2], cuts[1::2]) if a < b]

    def test(self, col: np.ndarray) -> np.ndarray:
        """Boolean mask of ``col`` (dictIds in any order; STAR is False)."""
        if self.exact:
            if self.lo == self.hi:
                return col == self.lo
            return (col >= self.lo) & (col <= self.hi)
        if self._member is None:
            # made once a dimension a walk, and only if something is masked
            ids = np.fromiter(self._ids, dtype=np.int32, count=len(self._ids))
            if self.hi < _MAX_TABLE_IDS:
                # entry hi + 1 stays False: ids past hi land on it, and
                # STAR = -1 reads it from the end
                top = self.hi + 1
                table = np.zeros(top + 1, dtype=bool)
                table[ids] = True
                self._member = lambda c: table[np.minimum(c, top)]
            else:
                self._member = lambda c: np.isin(c, ids)
        return self._member(col)


def _arange_of_ranges(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """``concatenate([arange(s, s + n) for s, n in zip(starts, lens)])``
    in one vectorised pass."""
    ends = np.cumsum(lens)
    return (np.arange(int(ends[-1]), dtype=np.int64)
            + np.repeat(starts - (ends - lens), lens))


class StarTree:
    """A built (or loaded) star-tree: flat record columns + node array."""

    def __init__(self, config: StarTreeConfig, dims: np.ndarray,
                 metrics: Dict[str, np.ndarray], nodes: np.ndarray):
        self.config = config
        self.dims = dims          # [R, D] int32, STAR = -1
        self.metrics = metrics    # pair key -> [R]
        self.nodes = nodes        # _NODE_DTYPE array; root = 0
        self._dim_index = {d: i for i, d
                           in enumerate(config.dimensions_split_order)}
        # what the walk reads, so that a loaded tree walks like a built one
        # and no step passes ``np.memmap.__getitem__``: every node field a
        # contiguous array in memory (SSB's largest tree has 54,404 nodes,
        # 1.3 MB; ``save`` still writes ``self.nodes``), the records a
        # plain view of ``dims``, mapped or not (a search reads only the
        # rows it probes)
        (self._node_dim, self._node_value, self._node_start, self._node_end,
         self._child_first, self._child_last) = (
            np.array(nodes[f]) for f in _NODE_DTYPE.names)
        self._walk_dims = np.asarray(dims)

    @property
    def num_records(self) -> int:
        return int(self.dims.shape[0])

    def has_pair(self, fn: str, col: str) -> bool:
        return f"{fn}__{col}" in self.metrics

    # -- persistence (ref: startree/v2/store single index file) --------------
    def save(self, seg_dir: str, index: int = 0) -> None:
        d = os.path.join(seg_dir, STARTREE_DIR.format(index=index))
        os.makedirs(d, exist_ok=True)
        np.save(os.path.join(d, "dims.npy"), self.dims)
        np.save(os.path.join(d, "nodes.npy"), self.nodes)
        for k, v in self.metrics.items():
            np.save(os.path.join(d, f"metric_{k}.npy"), v)
        with open(os.path.join(d, META_FILE), "w") as f:
            json.dump(self.config.to_dict(), f, indent=1)

    @classmethod
    def load(cls, seg_dir: str, index: int = 0) -> Optional["StarTree"]:
        d = os.path.join(seg_dir, STARTREE_DIR.format(index=index))
        meta_path = os.path.join(d, META_FILE)
        if not os.path.isfile(meta_path):
            return None
        with open(meta_path) as f:
            config = StarTreeConfig.from_dict(json.load(f))
        dims = np.load(os.path.join(d, "dims.npy"), mmap_mode="r")
        nodes = np.load(os.path.join(d, "nodes.npy"), mmap_mode="r")
        metrics = {}
        for fn, col in config.function_column_pairs:
            k = f"{fn}__{col}"
            metrics[k] = np.load(os.path.join(d, f"metric_{k}.npy"),
                                 mmap_mode="r")
        return cls(config, dims, metrics, nodes)

    # -- query-time traversal (ref: StarTreeFilterOperator.java:87) ----------
    def select_records(self,
                       eq_in_per_dim: Dict[str, Any],
                       group_by_dims: List[str],
                       walk: Optional[Dict[str, int]] = None) -> np.ndarray:
        """Record indices answering the query, **in ascending order**
        (callers sum over them: float64 sums of integers, exact in any
        order).

        Down the nodes, for each split dimension — with a predicate: the
        matching children (value children stand in dictId order, so a
        binary search finds them); grouped: all non-star children;
        otherwise the star child, or every child where the dimension has
        none. A leaf reached at depth ``L`` has its dimensions ``< L``
        decided by the path, holds no STAR on those ``>= L`` (stars are
        written at split dimensions only) and is sorted on them in split
        order, so the walk goes on inside it: while dimension ``d`` has a
        predicate, the range is cut to its matching sub-range(s) by
        ``searchsorted`` on ``dims[s:e, d]``, and ``d + 1`` is searched
        only below a single value (below a range, or below a grouped or
        free dimension, which keep every value, the next column is no
        longer sorted). Predicates on the dimensions after that are masked,
        over the cut ranges alone; a grouped dimension needs no test there.

        Predicate matches are dictId sets or contiguous
        :class:`DictIdRange` slices. ``walk``, when given (a traced query),
        receives what the walk did: ``nodes`` read, ``emitted`` records of
        the leaf ranges reached, ``gathered`` records still read and masked
        after the search."""
        empty = np.empty(0, dtype=np.int64)
        if walk is not None:
            walk.update(nodes=0, emitted=0, gathered=0)
        preds: Dict[int, _DimMatch] = {}
        for name, match in eq_in_per_dim.items():
            m = _DimMatch(match)
            if m.hi < m.lo:
                return empty    # before a node is read
            preds[self._dim_index[name]] = m
        pred_dims = sorted(preds)
        grouped = {self._dim_index[d] for d in group_by_dims}

        value, child_first = self._node_value, self._child_first
        # leaf ranges reached, as (start, end, depth)
        work: List[Tuple[int, int, int]] = []
        stack = [(0, 0)]
        visited = 0
        while stack:
            ni, depth = stack.pop()
            visited += 1
            first = int(child_first[ni])
            if first < 0:
                work.append((int(self._node_start[ni]),
                             int(self._node_end[ni]), depth))
                continue
            last = int(self._child_last[ni])
            dim = int(self._node_dim[ni])
            # both builders write the star child last
            values_end = last - int(value[last - 1] == STAR)
            m = preds.get(dim)
            if m is not None:
                kv = value[first:values_end]
                for a, b in m.spans(kv):
                    if m.pinned or m.exact:
                        kids = range(first + a, first + b)
                    else:
                        kids = (first + a
                                + np.flatnonzero(m.test(kv[a:b]))).tolist()
                    stack.extend((c, dim + 1) for c in kids)
            elif dim in grouped or values_end == last:
                # grouped, or free with no star child: every value child
                stack.extend((c, dim + 1) for c in range(first, values_end))
            else:
                stack.append((last - 1, dim + 1))
        if walk is not None:
            walk.update(nodes=visited, emitted=sum(e - s for s, e, _ in work))

        dims = self._walk_dims
        gathered = 0
        # record ranges selected, as (start, end, mask over them or None)
        parts: List[Tuple[int, int, Optional[np.ndarray]]] = []
        while work:
            s, e, d = work.pop()
            m = preds.get(d)
            if m is not None:
                spans = m.spans(dims[s:e, d])
                if m.pinned:
                    work.extend((s + a, s + b, d + 1) for a, b in spans)
                    continue
                if not spans:
                    continue
                (a, b), = spans
                s, e = s + a, s + b
                if m.exact:
                    d += 1      # decided; else its gaps are masked below
            mask = None
            for dim in pred_dims:
                if dim >= d:
                    t = preds[dim].test(dims[s:e, dim])
                    mask = t if mask is None else mask & t
            if mask is not None:
                gathered += e - s
            parts.append((s, e, mask))

        if walk is not None:
            walk["gathered"] = gathered
        if not parts:
            return empty
        parts.sort(key=lambda p: p[0])
        starts = np.array([p[0] for p in parts], dtype=np.int64)
        lens = np.array([p[1] for p in parts], dtype=np.int64) - starts
        idx = _arange_of_ranges(starts, lens)
        if gathered:
            idx = idx[np.concatenate([np.ones(e - s, dtype=bool)
                                      if mask is None else mask
                                      for s, e, mask in parts])]
        return idx
