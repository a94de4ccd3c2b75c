"""The cells PR 35 adds, as files: ``userfacing.zipf_open_r80`` (the
``member_views`` table, its traffic and its readers) and
``ssb_startree.flights_c1``. Counts and ``correct`` only: a CPU drive
measures no time."""

import json

import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.lib import schedule, work_index
from benchmarks.tables import member_views as mv

UF, C1 = "userfacing.zipf_open_r80", "ssb_startree.flights_c1"
SEED = 2 ** 31 + 3535


# -- the files --------------------------------------------------------------

def test_both_cells_load_and_report_no_p95_end_to_end():
    uf, c1 = bench.load_cell(UF), bench.load_cell(C1)
    for cell in (uf, c1):
        assert cell["cell"]["chips"] == 1
        assert [m["name"] for m in cell["end_to_end"]] == [
            "queries_per_s", "latency_p50_ms", "hbm_peak_bytes_per_row",
            "setup_s"]
        reported = {m["name"] for m in cell["end_to_end"]}
        assert all(m["moves"] in reported for m in cell["per_layer"])
        for m in cell["per_layer"]:
            bench.metric_reader(m["name"])      # every entry finds a reader
    assert uf["config"]["rows"] == 96_000_000
    assert uf["config"]["segments"] == 96
    assert uf["traffic"]["runner"] == "open"
    assert uf["traffic"]["clients"] == 16 and uf["traffic"]["rate"] > 0
    assert {m["name"] for m in uf["per_layer"]} >= {
        "prune_ms", "segments_kept_per_query", "index_route_cpu_ms",
        "index_gather_roofline", "generator_late_p50_ms", "stall_ms_per_s",
        "request_p95_ms.uf", "rest_overhead_ms", "device_idle_share"}
    assert c1["config"]["table"] == "ssb_flat"
    assert c1["traffic"]["clients"] == 1
    c8 = bench.load_cell("ssb_startree.flights_c8")
    assert dict(c8["traffic"], clients=1) == c1["traffic"]
    listed = {m["name"] for m in c8["per_layer"] if "workloads" in m
              and m["moves"] != "latency_p95_ms"}
    assert {f"{n}.c1" for n in listed} <= {m["name"]
                                          for m in c1["per_layer"]}
    forbidden = set(uf["config"]["forbidden_decision_reasons"])
    assert forbidden >= set(c8["config"]["forbidden_decision_reasons"])
    assert forbidden >= {"index_missing_index", "index_exec_failed",
                         "index_selectivity_over_threshold"}


def test_the_cycle_has_2048_distinct_strings_on_every_seed():
    traffic = schedule.load_traffic("zipf_open_r80")
    a, b = (schedule.build_cycle(traffic, s) for s in (SEED, 7))
    assert len(a) == 2048 == len({q["sql"] for q in a})
    assert sorted(q["sql"] for q in a) == sorted(q["sql"] for q in b)
    assert [q["sql"] for q in a] != [q["sql"] for q in b]
    flights = [q["flight"] for q in a]
    assert {f: flights.count(f) for f in set(flights)} == {
        "wvmp_total": 512, "wvmp_industry": 512,
        "wvmp_seniority_region": 512, "wvmp_daily": 512}
    for q in a:
        (_, _, u), (_, _, lo, hi) = q["where"][:2]
        assert f"member_id = {u} AND day BETWEEN {lo} AND {hi}" in q["sql"]
        assert int(hi) - int(lo) in mv.WINDOW_SPANS
        assert mv.DAY0 <= int(lo) <= int(hi) < mv.DAY0 + mv.DAYS
        if q["flight"] == "wvmp_seniority_region":
            regions = q["where"][2][2:]
            assert len(set(regions)) == 4
            assert all(f"'{r}'" in q["sql"] for r in regions)
    # hot keys recur: the hottest member stands in many strings
    members = [q["where"][0][2] for q in a]
    assert max(members.count(m) for m in set(members)) >= 40
    assert len(set(members)) >= 600


def test_the_committed_domains_equal_their_regeneration():
    traffic = schedule.load_traffic("zipf_open_r80")
    domains = traffic["domains"]
    assert domains["members"] == mv.member_domain()
    assert len(domains["members"]) == 65_536
    assert domains["windows"] == mv.window_domain()
    for q, regions in enumerate(mv.region_domains()):
        assert domains[f"regions_q{q + 1}"] == regions
    assert sorted(sum(mv.region_domains(), [])) == sorted(mv.REGIONS)
    assert 0 <= min(domains["members"]) and max(
        domains["members"]) < mv.MEMBERS


# -- the table --------------------------------------------------------------

@pytest.mark.parametrize("segments,rows", [(96, 480_000), (48, 240_000),
                                           (8, 40_000)])
def test_a_segment_holds_one_partition_ascending(segments, rows):
    sizes = mv.segment_sizes(segments, rows)
    assert len(sizes) == segments and sum(sizes) == rows
    for i in (0, 1, segments // 2, segments - 1):
        codes = mv.segment_codes(i, segments, sizes[i], SEED)
        member = codes["member_id"]
        assert member.dtype == np.int32 and len(member) == sizes[i]
        assert set((member % mv.PARTITIONS).tolist()) == {i % mv.PARTITIONS}
        assert mv.partition_of(int(member[0])) == i % mv.PARTITIONS
        assert (np.diff(member) >= 0).all()
        days = mv.segment_days(i, segments)
        assert days.start <= codes["day"].min()
        assert codes["day"].max() < days.stop
        counts = np.unique(member, return_counts=True)[1]
        assert counts.max() <= mv.MEMBER_SHARE_CAP * sizes[i]
        for name, domain in mv.STRING_DOMAINS.items():
            assert 0 <= codes[name].min()
            assert codes[name].max() < len(domain) == mv.CARDINALITY[name]
        assert 1 <= codes["views"].min() and codes["views"].max() <= 20
        assert mv.DWELL_MS[0] <= codes["dwell_ms"].min()
        assert codes["dwell_ms"].max() <= mv.DWELL_MS[1]
        again = mv.segment_codes(i, segments, sizes[i], SEED)
        assert all((codes[k] == again[k]).all() for k in codes)
        other = mv.segment_codes(i, segments, sizes[i], SEED + 1)
        assert (codes["day"] != other["day"]).any()
    # 96 segments: two halves of the span; 48 and fewer: all of it
    spans = {(mv.segment_days(i, segments).start,
              mv.segment_days(i, segments).stop) for i in range(segments)}
    assert len(spans) == mv.time_slices(segments)
    assert min(s for s, _ in spans) == mv.DAY0
    assert max(e for _, e in spans) == mv.DAY0 + mv.DAYS


def test_a_table_of_any_size_holds_the_hottest_members():
    order = mv.rank_order()
    assert sorted(order.tolist()) == list(range(mv.MEMBERS))
    for p in (0, 47):
        few, many = mv.partition_members(p, 40), mv.partition_members(p, 400)
        assert set(few.tolist()) <= set(many.tolist())
        assert (np.diff(many) > 0).all()
        rank = np.empty(mv.MEMBERS, dtype=np.int64)
        rank[order] = np.arange(mv.MEMBERS)
        rest = order[(order % mv.PARTITIONS == p)]
        assert rank[few].max() < rank[rest[40:]].min()
    assert mv.members_of(1_000_000, 96) == mv.MEMBERS_A_PARTITION
    assert mv.members_of(1_000_000, 48) == 10_000
    with pytest.raises(ValueError):
        mv.partition_members(0, mv.MEMBERS_A_PARTITION + 1)
    # more than half of the domain's draws name a member a toy table holds
    held = set()
    for p in range(mv.PARTITIONS):
        held.update(mv.partition_members(p, 40).tolist())
    domain = mv.member_domain()
    assert sum(m in held for m in domain) > 0.5 * len(domain)


# -- the readers, on hand-made records -----------------------------------------

def traced(children, index=0, **rec):
    return dict({"ok": True, "index": index, "raw": {"traceInfo": {"spans": [{
        "name": "BrokerQuery", "ms": 9.0, "startMs": 0.0,
        "startEpochMs": 1_000.0, "children": [{
            "name": "ScatterGather", "ms": 8.0, "startMs": 0.5,
            "children": [{"name": "ServerQuery", "ms": 6.0, "startMs": 1.0,
                          "startEpochMs": 1_001.0, "cpuMs": 4.0,
                          "thread": "q-1", "children": children}]}]}]}}},
                **rec)


def span(name, ms, start=0.0, cpu=0.0, thread="q-1", **attrs):
    return dict({"name": name, "ms": ms, "startMs": start, "cpuMs": cpu,
                 "thread": thread}, **attrs)


BARE = [span("Lease", 0.2)]     # a program without the PR's spans


def test_prune_readers():
    records = [traced([span("Prune", 0.4, segments=96, kept=1,
                            byPartition=94, byBounds=1)]),
               traced([span("Prune", 0.6, segments=96, kept=2,
                            byPartition=94)]),
               traced([span("Prune", 0.9, segments=96, kept=2)]),
               dict(traced([span("Prune", 50.0, kept=96)]), ok=False)]
    assert bench.metric_reader("prune_ms")({"records": records}) == 0.6
    assert bench.metric_reader("segments_kept_per_query")(
        {"records": records}) == pytest.approx(5 / 3)
    for name in ("prune_ms", "segments_kept_per_query"):
        assert bench.metric_reader(name)({"records": [traced(BARE)]}) is None
        assert bench.metric_reader(name)({"records": []}) is None


def test_index_route_cpu_reader():
    read = bench.metric_reader("index_route_cpu_ms")
    records = [traced([span("SegmentAggregate", 3.0, children=[
        span("IndexRoute", 0.3, cpu=0.2, candidates=50)])]),
        traced([span("SegmentAggregate", 3.0, children=[
            span("IndexRoute", 0.3, cpu=0.1)]),
            span("SegmentAggregate", 3.0, children=[
                span("IndexRoute", 0.3, cpu=0.3)])])]
    assert read({"records": records}) == pytest.approx(0.3)     # a mean
    assert read({"records": [traced(BARE)]}) is None


def test_generator_lateness_and_stall_readers():
    late = bench.metric_reader("generator_late_p50_ms")
    records = [{"late_ms": v, "done_s": 1.0 + v} for v in (0.1, 0.3, 9.0)]
    assert late({"records": records}) == 0.3
    assert late({"records": [{"latency_ms": 3.0}]}) is None     # closed loop

    stall = bench.metric_reader("stall_ms_per_s")

    def counters(total):
        return {"scheduler": {"scheduler": {}, "stallWatch": {
            "stalls": 3, "stallMsTotal": total}}}

    ctx = {"records": [{"done_s": 10.0}, {"done_s": 40.0}],
           "before": counters(100.0), "after": counters(500.0)}
    assert stall(ctx) == pytest.approx(10.0)
    bare = {"scheduler": {"scheduler": {}}}     # a program without the watch
    assert stall(dict(ctx, before=bare, after=bare)) is None
    assert stall(dict(ctx, records=[])) is None


def test_gather_bytes_on_a_worked_example():
    total = {"value": ["views"], "group_by": []}
    grouped = {"value": ["dwell_ms"], "group_by": ["viewer_seniority"]}
    daily = {"value": ["views"], "group_by": ["day"]}
    count = {"value": ["*"], "group_by": ["day", "day"]}
    assert work_index.gathered_columns(grouped) == ["dwell_ms",
                                                    "viewer_seniority"]
    # 128 docIds of 4 bytes and one 4-byte entry a docId a column
    assert work_index.gather_least_bytes(total, 128) == 128 * (4 + 4)
    assert work_index.gather_least_bytes(grouped, 256) == 256 * (4 + 8)
    assert work_index.gather_least_bytes(daily, 1024) == 1024 * 12
    assert work_index.gather_least_bytes(count, 128) == 128 * 8


def test_index_gather_roofline_reader():
    read = bench.metric_reader("index_gather_roofline")
    cycle = [{"value": ["views"], "group_by": []},
             {"value": ["dwell_ms"], "group_by": ["viewer_seniority"]}]

    def gather(capacity):
        return span("SegmentGroupBy", 2.0, children=[
            span("Kernel", 1.0, kernel="index_gather", records=50,
                 capacity=capacity)])

    records = [traced([gather(128)], index=0),
               traced([gather(256), gather(128)], index=1)]
    device = {"op_seconds": 3e-4, "device_ops": [
        ["jit_index_gather_agg/fusion.1", 1.5e-4],
        ["jit_index_gather_agg/gather.3", 0.5e-4],
        ["jit_other/copy.1", 1e-4]]}        # another program's: taken off
    ctx = {"device": device, "in_trace": records, "cycle": cycle,
           "peak": {"hbm_bytes_per_s": 819e9}}
    least = 128 * 8 + 256 * 12 + 128 * 12
    assert read(ctx) == pytest.approx(100.0 * least / 819e9 / 2e-4)
    assert read(ctx) < 105.0
    # a program whose Kernel spans carry no capacity, no trace, no query
    old = [traced([span("Kernel", 1.0, kernel="index_gather", records=5)])]
    assert read(dict(ctx, in_trace=old)) is None
    assert read(dict(ctx, device=None)) is None
    assert read(dict(ctx, in_trace=[])) is None


# -- toy drives on the CPU ---------------------------------------------------------

def go(tmp, cell, rows, trace=True):
    lines = bench.run(cell, SEED, 3.0, trace, expect_platform="cpu",
                      rows=rows, data_root=str(tmp), strict=False)
    return json.loads(lines[-1])


def sources(cell_name):
    cell = bench.load_cell(cell_name)
    return {m["name"]: m["source"]
            for m in cell["end_to_end"] + cell["per_layer"]}


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_uf")


def check_no_time(line, cell):
    source = sources(cell)
    for name, m in line["metrics"].items():
        if source[name] in bench.COUNT_SOURCES:
            assert isinstance(m["value"], float), name
        else:
            assert m["value"] == bench.NOT_MEASURED, name
    assert line["device"]["memory_peak_bytes"] == bench.NOT_MEASURED


def test_a_toy_drive_of_the_lookup_cell_is_correct(data_root):
    line = go(data_root, UF, 192_000)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 100
    # every string index-served: a decline of the rung is a forbidden reason
    assert line["compared"]["host_served_decisions"]["value"] == 0
    names = set(line["metrics"])
    assert {"prune_ms", "segments_kept_per_query", "index_route_cpu_ms",
            "generator_late_p50_ms", "stall_ms_per_s", "request_p95_ms.uf",
            "plan_cpu_ms.uf", "dispatch_cpu_ms.uf", "device_wait_ms.uf",
            "d2h_ms.uf", "decode_cpu_ms.uf", "exec_cpu_ms_per_query.uf",
            "sched_wait_ms.uf", "server_unattributed_ms.uf",
            "residency_hit_share.uf", "rest_overhead_ms", "broker_self_ms",
            "exec_host_self_ms", "staged_bytes_per_row"} <= names
    assert 1.0 <= line["metrics"]["segments_kept_per_query"]["value"] <= 2.0
    check_no_time(line, UF)
    timed = go(data_root, UF, 192_000, trace=False)
    assert set(timed["metrics"]) == {"queries_per_s", "latency_p50_ms",
                                     "setup_s"}
    check_no_time(timed, UF)


def test_a_toy_drive_of_the_one_client_cell_is_correct(data_root):
    line = go(data_root, C1, 40_000)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert {"segment_queue_ms.c1", "startree_walk_cpu_ms.c1",
            "flight_q1_p50_ms.c1", "request_p95_ms.c1", "sched_wait_ms.c1",
            "residency_hit_share.c1", "rest_overhead_ms"} <= set(
        line["metrics"])
    check_no_time(line, C1)


@pytest.mark.parametrize("cell,rows", [(UF, 192_000), (C1, 40_000)])
def test_an_altered_answer_fails_the_new_cells(data_root, monkeypatch, cell,
                                               rows):
    from pinot_tpu.broker.reduce import ReduceAccumulator

    sound = ReduceAccumulator.finish

    def altered(self):
        table, stats, exceptions = sound(self)
        if table is not None and table.rows:
            row = list(table.rows[0])
            row[-1] = row[-1] + 1
            table.rows[0] = row
        return table, stats, exceptions

    monkeypatch.setattr(ReduceAccumulator, "finish", altered)
    line = go(data_root, cell, rows, trace=False)
    assert line["correct"] is False
    assert line["failed"] == line["compared"]["responses_wrong"]["value"] > 0
    assert line["compared"]["max_abs_diff"]["value"] >= 1.0
