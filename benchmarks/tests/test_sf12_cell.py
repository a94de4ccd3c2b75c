"""``ssb_scan_sf12.flights_c2`` (PR 37): the cell's files, the arithmetic of
its new reader on made up snapshots, and the cell end to end at toy size on
the CPU (counts and ``correct`` only)."""

import json

import pytest

from benchmarks import run as bench
from benchmarks.tables import ssb_flat

CELL = "ssb_scan_sf12.flights_c2"


@pytest.fixture(scope="module")
def cell():
    return bench.load_cell(CELL)


def test_the_cell_is_the_issues(cell):
    config = cell["config"]
    assert (config["rows"], config["segments"], config["servers"]) \
        == (72_000_000, 28, 1)
    assert "mesh" not in config and cell["cell"]["chips"] == 1
    assert cell["cell"]["traffic"] == "flights_c2"
    assert config["reduced"] == ["rows", "columns"]
    assert {"working_set_over_budget_sliceable", "segments_not_batchable",
            "slice_pad_over_budget"} <= set(
                config["forbidden_decision_reasons"])
    # ssb_scan's table: the same schema, columns and index config
    scan = bench.load_cell("ssb_scan.flights_c2")["config"]
    for key in ("table", "schema", "tableIndexConfig", "columns",
                "guarantees"):
        assert config[key] == scan[key], key
    names = {m["name"] for m in cell["per_layer"]}
    assert {"scan_roofline.sf12", "flight_q2_p50_ms.sf12",
            "staged_unread_share", "segments_kept_per_query.sf12",
            "staged_bytes_per_row", "device_idle_share"} <= names
    assert not {"scan_roofline", "flight_q1_p50_ms", "sched_wait_ms",
                "scan_roofline_mesh"} & names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "queries_per_s", "latency_p50_ms", "hbm_peak_bytes_per_row",
        "setup_s"}


def test_28_windows_of_three_months_cover_the_84():
    windows = [ssb_flat.segment_months(i, 28) for i in range(28)]
    assert all(len(w) == 3 for w in windows)
    assert [m for w in windows for m in w] == ssb_flat.MONTHS
    sizes = ssb_flat.segment_sizes(28, 72_000_000)
    assert sum(sizes) == 72_000_000 and max(sizes) == 2_571_429


def memory(residents, staged):
    return {"memory": {"stagedBytes": staged, "stagedSegments": residents}}


def test_staged_unread_share_reads_the_residents_no_query_touched():
    read = bench.metric_reader("staged_unread_share")
    before = memory({"seg_0": {"bytes": 300, "touch": 5},
                     "seg_1": {"bytes": 200, "touch": 6},
                     "batch(a)": {"bytes": 400, "touch": 7}}, 900)
    after = memory({"seg_0": {"bytes": 300, "touch": 5},        # unread
                    "seg_1": {"bytes": 200, "touch": 11},
                    "batch(a)": {"bytes": 400, "touch": 7},     # unread
                    "batch(b)": {"bytes": 100, "touch": 12}},   # new
                   1000)
    assert read({"before": before, "after": after}) == 70.0
    # everything read: 0
    touched = memory({n: dict(r, touch=r["touch"] + 10) for n, r in
                      after["memory"]["stagedSegments"].items()}, 1000)
    assert read({"before": after, "after": touched}) == 0.0


@pytest.mark.parametrize("why", ["no_touch", "nothing_staged",
                                 "no_residents"])
def test_staged_unread_share_finds_nothing_to_read(why):
    read = bench.metric_reader("staged_unread_share")
    residents = {"seg_0": {"bytes": 300, "touch": 5}}
    if why == "no_touch":       # a program from before PR 37
        residents = {"seg_0": {"bytes": 300, "pins": 0}}
    staged = 0 if why == "nothing_staged" else 300
    if why == "no_residents":
        after = {"memory": {"stagedBytes": 300}}
    else:
        after = memory(residents, staged)
    assert read({"before": after, "after": after}) is None


def test_a_toy_drive_of_the_cell_is_correct(tmp_path):
    lines = bench.run(CELL, 2 ** 31 + 37, 2.0, True, expect_platform="cpu",
                      rows=28 * 2_000, data_root=str(tmp_path),
                      strict=False)
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["residency_spills"] == {"value": 0, "limit": 0}
    assert line["compared"]["host_served_decisions"]["value"] == 0
    metrics = line["metrics"]
    assert 0.0 <= metrics["staged_unread_share"]["value"] < 100.0
    assert metrics["residency_hit_share.sf12"]["value"] == 100.0
    # the pruner keeps 1, 4, 8, 24 or 28 of the 28 segments a string
    assert 1.0 < metrics["segments_kept_per_query.sf12"]["value"] < 28.0
    assert metrics["flight_q2_p50_ms.sf12"]["value"] == bench.NOT_MEASURED
    assert not {"scan_roofline.sf12", "device_idle_share"} & set(metrics)
