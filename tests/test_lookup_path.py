"""The per-member lookup path (PR 35): what a partitioned, sorted table of
96 segments forced in the program, one test a repair.

- ``StagedSegment.device_nbytes`` is remembered until a staged array comes
  or goes (a mutation counter), and equals the walk after every kind of
  change;
- the index rung searches a column with needles of the column's dtype (numpy
  copies an int32 column to search it with an int64);
- a conjunct whose match count is far over the candidates left (or that has
  no index beside one that has) is probed on their forward index: the same
  docIds as resolving and intersecting it;
- the segment creator's partition metadata is computed over the distinct
  values, for all four partition functions what the loop a row gave;
- the ``Prune`` / ``IndexRoute`` spans, ``capacity`` on the gather's
  ``Kernel`` span, and the server's stall watch.
"""

import gc

import numpy as np
import pytest

from pinot_tpu.engine import ServerQueryExecutor, index_exec, staging
from pinot_tpu.engine.staging import StagedColumn, StagedSegment
from pinot_tpu.query import compile_query
from pinot_tpu.segment import SegmentBuilder, load_segment
from pinot_tpu.server.stall import StallWatch
from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema
from pinot_tpu.spi.table import IndexingConfig
from pinot_tpu.utils.partition import get_partition_function

ROWS = 20_000
KEYS = 400


@pytest.fixture(scope="module")
def lookup_segment(tmp_path_factory):
    """One segment sorted on ``key`` (int32 forward index), an inverted
    index on ``region``, none on ``day``; a range index on ``score``."""
    rng = np.random.default_rng(35)
    key = np.sort(rng.integers(0, KEYS, ROWS)) * 7 + 3
    frame = {
        "key": key.astype(np.int64),
        "day": rng.integers(100, 190, ROWS).astype(np.int64),
        "region": np.asarray([f"r{k:02d}" for k in range(16)])[
            rng.integers(0, 16, ROWS)],
        "score": rng.integers(0, 1_000_000, ROWS).astype(np.int64),
        "views": rng.integers(1, 21, ROWS).astype(np.int64),
    }
    schema = Schema("lookups", [
        FieldSpec("key", DataType.INT), FieldSpec("day", DataType.INT),
        FieldSpec("region", DataType.STRING),
        FieldSpec("score", DataType.INT),
        FieldSpec("views", DataType.INT, FieldType.METRIC)])
    out = tmp_path_factory.mktemp("lookup_seg")
    SegmentBuilder(schema, "lookups_0", indexing_config=IndexingConfig(
        sorted_column=["key"], inverted_index_columns=["region"],
        range_index_columns=["score"], no_dictionary_columns=["score"],
        segment_partition_config=None)).build(frame, str(out))
    return load_segment(str(out / "lookups_0")), frame


# -- memoized device bytes ---------------------------------------------------

def _walked(staged: StagedSegment):
    staged._nbytes_memo = None
    return staged.device_nbytes()


def test_memoized_bytes_equal_the_walk_after_every_change(lookup_segment,
                                                          monkeypatch):
    import jax.numpy as jnp

    seg, _ = lookup_segment
    lent = StagedColumn(fwd=jnp.zeros(64, dtype=jnp.int32))
    staged = StagedSegment(
        seg, borrower=lambda s, name: lent if name == "day" else None)
    steps = [
        lambda: staged.column("views"),                 # stage
        lambda: staged.column("day"),                   # borrow
        lambda: staged.packed_column("region"),
        lambda: staged.value_column("views"),
        lambda: staged.index_slice(("f", 128),
                                   lambda: np.zeros(128, np.int32)),
        lambda: staged.index_slice(("g", 256),
                                   lambda: np.zeros(256, np.int32)),
        lambda: staged.release_index_slices(),          # evict a grain
        lambda: staged.release(),                       # evict all
        lambda: staged.column("key"),                   # and stage again
    ]
    seen = [staged.device_nbytes()]
    for step in steps:
        step()
        got = staged.device_nbytes()
        assert got == _walked(staged)
        assert staged.nbytes() == sum(got.values())
        seen.append(got)
    assert len({tuple(sorted(d.items())) for d in seen}) >= 6   # it moved
    assert seen[-2] == {} and seen[0] == {}     # released: nothing held

    # asked again with nothing changed, no array is looked at
    looked = []
    sound = staging.add_device_bytes
    monkeypatch.setattr(staging, "add_device_bytes",
                        lambda arr, into: (looked.append(1),
                                           sound(arr, into)))
    first = staged.device_nbytes()
    assert not looked and first == seen[-1]
    first[0] = -1                               # the caller's own dict
    assert staged.device_nbytes() == seen[-1]
    staged.column("views")
    staged.device_nbytes()
    assert looked                               # a change: walked once more


# -- needles of the column's dtype ---------------------------------------------

def test_the_sorted_and_range_routes_search_with_the_columns_dtype(
        lookup_segment, monkeypatch):
    seg, frame = lookup_segment
    assert np.asarray(seg.data_source("key").forward_index).dtype == np.int32
    searched = []
    sound = np.searchsorted

    def watched(a, v, *args, **kw):
        searched.append((np.asarray(a).dtype, np.asarray(v).dtype))
        return sound(a, v, *args, **kw)

    monkeypatch.setattr(np, "searchsorted", watched)
    u = int(frame["key"][ROWS // 2])
    lo = int(np.partition(frame["score"], 40)[40])
    for sql in (f"SELECT count(*) FROM lookups WHERE key = {u}",
                f"SELECT count(*) FROM lookups WHERE key IN ({u}, "
                f"{int(frame['key'][5])}, 1)",
                f"SELECT count(*) FROM lookups WHERE score < {lo}",
                f"SELECT count(*) FROM lookups WHERE score = {lo}"):
        ctx = compile_query(sql)
        preds = [n.predicate for n in ([ctx.filter] if ctx.filter.predicate
                                       else ctx.filter.children)]
        idx = index_exec.resolve_doc_ids(seg, preds, seg.num_docs, ROWS)
        assert idx is not None and idx.size, sql
    assert searched
    for column, needle in searched:
        assert column == needle, (column, needle)   # else numpy copies


# -- probing on the candidates' forward index ---------------------------------

CONJUNCTIONS = (
    "key = {u} AND day BETWEEN 120 AND 150",                # no index: probed
    "key = {u} AND region IN ('r01', 'r05', 'r09', 'r13')",  # postings
    "key = {u} AND day BETWEEN 100 AND 189 AND region = 'r03'",
    "key IN ({u}, {v}) AND score < 500000",                 # range index
    "key = {u} AND day = 1",                                # keeps no row
    "key = {u} AND region = 'nowhere'",
)


@pytest.mark.parametrize("where", CONJUNCTIONS)
def test_probed_equals_resolved_row_for_row(lookup_segment, monkeypatch,
                                            where):
    seg, frame = lookup_segment
    where = where.format(u=int(frame["key"][ROWS // 3]),
                         v=int(frame["key"][ROWS // 7]))
    sql = f"SELECT region, sum(views) FROM lookups WHERE {where} " \
          f"GROUP BY region"
    ctx = compile_query(sql)
    preds = [n.predicate for n in ctx.filter.children]

    def resolved_with(over):
        monkeypatch.setattr(index_exec, "_PROBE_OVER", over)
        trace = {}
        idx = index_exec.resolve_doc_ids(seg, preds, seg.num_docs,
                                         seg.num_docs, trace)
        return idx, trace

    probed, how_p = resolved_with(0)            # every conjunct it can
    joined, how_j = resolved_with(10 ** 12)     # only those with no index
    assert probed.dtype == joined.dtype == np.int64
    assert probed.tolist() == joined.tolist()
    assert how_p["candidates"] == how_j["candidates"]
    if probed.size or how_p["candidates"]:
        assert how_p["probed"] >= how_j["probed"]
    # and they are the rows the host's masks keep
    from pinot_tpu.engine.host_eval import eval_filter

    assert probed.tolist() == np.flatnonzero(
        eval_filter(seg, ctx.filter)[:seg.num_docs]).tolist()

    # through the rung: same answer and docs scanned as the scan rungs
    monkeypatch.undo()
    dev = ServerQueryExecutor(use_device=True)
    r_i, s_i = dev.execute(compile_query(sql), [seg])
    r_s, _ = dev.execute(compile_query(
        sql + " OPTION(useIndexRung=false)"), [seg])
    assert sorted(map(tuple, r_i.rows)) == sorted(map(tuple, r_s.rows))
    assert s_i.decisions.get("index:scan->index_gather:index_served") == 1
    assert s_i.num_docs_scanned == probed.size


def test_a_conjunction_with_no_index_at_all_still_declines(lookup_segment):
    seg, _ = lookup_segment
    ctx = compile_query("SELECT count(*) FROM lookups WHERE day = 120 "
                        "AND views = 3")
    with pytest.raises(index_exec._Decline) as e:
        index_exec.resolve_doc_ids(
            seg, [n.predicate for n in ctx.filter.children], seg.num_docs,
            seg.num_docs)
    assert e.value.reason == "index_missing_index"


# -- partition metadata ----------------------------------------------------------

def _loop(fn, values):
    parts = set()
    for v in values:
        for x in (v if isinstance(v, list) else [v]):
            parts.add(fn.partition(x))
    return sorted(parts)


@pytest.mark.parametrize("name", ["Murmur", "Modulo", "HashCode",
                                  "ByteArray"])
@pytest.mark.parametrize("kind", ["int64", "int32", "ints", "strings",
                                  "text_array", "mv"])
def test_vectorised_partitions_equal_the_loops(name, kind):
    rng = np.random.default_rng(hash((name, kind)) % 2 ** 32)
    ints = rng.integers(-5_000, 5_000_000, 3_000)
    values = {
        "int64": ints.astype(np.int64),
        "int32": ints.astype(np.int32),
        "ints": ints.tolist(),
        "strings": [f"member-{v}" for v in ints.tolist()],
        "text_array": np.asarray([f"k{v}" for v in ints.tolist()]),
        "mv": [[int(v), int(v) // 3] if v % 2 else [int(v)] for v in ints],
    }[kind]
    if name == "Modulo" and kind in ("strings", "text_array"):
        pytest.skip("Modulo takes integers")
    for n in (1, 7, 48):
        fn = get_partition_function(name, n)
        got = fn.partitions_of(values)
        assert got == _loop(fn, values)
        assert all(type(p) is int for p in got)


def test_the_creator_writes_the_same_partition_metadata(tmp_path):
    """A segment of one partition of 48 by Modulo, as the loop wrote it."""
    members = np.sort(np.random.default_rng(3).integers(0, 20_000, 5_000)
                      ) * 48 + 11
    schema = Schema("p", [FieldSpec("member_id", DataType.INT),
                          FieldSpec("views", DataType.INT,
                                    FieldType.METRIC)])
    cfg = IndexingConfig.from_dict({
        "sortedColumn": ["member_id"],
        "segmentPartitionConfig": {"columnPartitionMap": {
            "member_id": {"functionName": "Modulo",
                          "numPartitions": 48}}}})
    SegmentBuilder(schema, "p_0", indexing_config=cfg).build(
        {"member_id": members.astype(np.int64),
         "views": np.ones(len(members), dtype=np.int64)}, str(tmp_path))
    cm = load_segment(str(tmp_path / "p_0")).metadata.columns["member_id"]
    assert (cm.partition_function, cm.num_partitions, cm.partitions) == (
        "Modulo", 48, [11])
    assert cm.is_sorted


# -- spans -------------------------------------------------------------------------

def _find(spans, name):
    out = []
    for s in spans:
        if s.get("name") == name:
            out.append(s)
        out += _find(s.get("children", ()), name)
    return out


def test_the_lookup_spans_say_what_was_done(lookup_segment):
    seg, frame = lookup_segment
    u = int(frame["key"][ROWS // 2])
    dev = ServerQueryExecutor(use_device=True)
    sql = (f"SELECT region, sum(views) FROM lookups WHERE key = {u} AND "
           f"day BETWEEN 120 AND 150 AND region IN ('r01', 'r02') "
           f"GROUP BY region OPTION(trace=true)")
    _, stats = dev.execute(compile_query(sql), [seg])
    (prune,) = _find(stats.spans, "Prune")
    assert prune["segments"] == 1 and prune["kept"] == 1
    (route,) = _find(stats.spans, "IndexRoute")
    matched = int(((frame["key"] == u) & (frame["day"] >= 120)
                   & (frame["day"] <= 150)
                   & np.isin(frame["region"], ["r01", "r02"])).sum())
    assert route["candidates"] == int((frame["key"] == u).sum())
    assert route["resolved"] == 1 and route["probed"] == 2
    assert route["matched"] == matched
    (kernel,) = [k for k in _find(stats.spans, "Kernel")
                 if k.get("kernel") == "index_gather"]
    assert kernel["records"] == matched
    assert kernel["capacity"] >= max(matched, 128)
    assert kernel["capacity"] & (kernel["capacity"] - 1) == 0
    for child in ("Dispatch", "DeviceWait", "D2H"):
        assert len(_find([kernel], child)) == 1, child
    assert _find(stats.spans, "Plan")

    # a filter that proves the segment empty: pruned by bounds, one kept
    # all the same for the result's shape
    _, stats = dev.execute(compile_query(
        "SELECT count(*) FROM lookups WHERE key = 99999999 "
        "OPTION(trace=true)"), [seg, seg])
    (prune,) = _find(stats.spans, "Prune")
    assert prune["segments"] == 2 and prune["byBounds"] == 2


def test_the_pruner_says_which_proof_excluded_a_segment(tmp_path):
    from pinot_tpu.engine.pruner import prune_segments

    schema = Schema("p", [FieldSpec("member_id", DataType.INT),
                          FieldSpec("day", DataType.INT)])
    cfg = IndexingConfig.from_dict({"segmentPartitionConfig": {
        "columnPartitionMap": {"member_id": {"functionName": "Modulo",
                                             "numPartitions": 4}}}})
    segs = []
    for p in range(4):
        for half in range(2):
            name = f"p_{p}_{half}"
            ids = np.arange(p, 400, 4, dtype=np.int64)
            SegmentBuilder(schema, name, indexing_config=cfg).build(
                {"member_id": ids,
                 "day": np.full(len(ids), 10 + half, dtype=np.int64)},
                str(tmp_path))
            segs.append(load_segment(str(tmp_path / name)))
    why = {}
    ctx = compile_query("SELECT count(*) FROM p WHERE member_id = 102 "
                        "AND day BETWEEN 11 AND 20")
    kept = prune_segments(ctx, segs, why=why)
    assert [s.segment_name for s in kept] == ["p_2_1"]
    # the first proof found counts: the day range excludes the four first
    # halves, the partition three of the four second ones
    assert why == {"byBounds": 4, "byPartition": 3}
    assert sum(why.values()) + len(kept) == len(segs)


# -- the stall watch ---------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_the_stall_watch_counts_gaps_with_requests_in_flight():
    clock = _Clock()
    watch = StallWatch(clock=clock)     # not started: no thread, no gc hook
    # two requests answered 10 ms apart, then a long idle spell: no stall
    watch.begin()
    watch.begin()
    clock.t += 0.010
    watch.end()
    clock.t += 0.010
    watch.end()
    clock.t += 5.0
    assert watch.snapshot()["stalls"] == 0
    # in flight and nothing done for 80 ms: one stall of 80 ms
    watch.begin()
    clock.t += 0.030
    watch.begin()
    clock.t += 0.050
    watch.end()
    snap = watch.snapshot()
    assert (snap["stalls"], snap["inflight"]) == (1, 1)
    assert snap["stallMsTotal"] == pytest.approx(80.0)
    # a collector run inside the next gap is named
    clock.t += 0.020
    watch._on_gc("start", {"generation": 2})
    clock.t += 0.300
    watch._on_gc("stop", {"generation": 2})
    clock.t += 0.010
    watch.end()
    snap = watch.snapshot()
    assert snap["stalls"] == 2 and snap["inflight"] == 0
    assert snap["stallMsMax"] == pytest.approx(330.0)
    assert snap["stallMsTotal"] == pytest.approx(410.0)
    assert snap["gc"]["runs"] == [0, 0, 1]
    assert snap["gc"]["msInStalls"] == pytest.approx(300.0)
    assert snap["last"][-1]["gcMs"] == pytest.approx(300.0)
    assert snap["thresholdMs"] == 50.0


def test_the_stall_watch_samples_the_stacks_of_a_running_gap():
    import threading
    import time

    watch = StallWatch(threshold_ms=20.0).start()
    try:
        assert watch._on_gc in gc.callbacks
        watch.begin()
        release = threading.Event()

        def held_up():      # a thread of the package's, standing in a wait
            from pinot_tpu.utils import partition

            fn = partition.PartitionFunction(
                "wait", 1, lambda v, n: release.wait(5.0) and 0)
            fn.partition(1)

        t = threading.Thread(target=held_up, name="pqw-7")
        t.start()
        deadline = time.monotonic() + 5.0
        while watch.snapshot()["sampled"] == 0:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        release.set()
        t.join()
        watch.end()
        snap = watch.snapshot()
        assert snap["stalls"] == 1 and snap["sampled"] >= 1
        assert any(w.startswith("pqw:utils.partition.partition")
                   for w in snap["where"]), snap["where"]
    finally:
        watch.stop()
    assert watch._on_gc not in gc.callbacks
