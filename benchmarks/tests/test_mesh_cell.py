"""``ssb_scan_x4.flights_c2``: the arithmetic of its three readers on made
up numbers, what they do on a program or a config without their source, and
the cell end to end at toy size on four forced CPU devices (counts and
``correct`` only)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import run as bench
from benchmarks.lib import schedule, work
from benchmarks.tables import ssb_flat

CELL = "ssb_scan_x4.flights_c2"
ROWS, SEGMENTS = 96_000_000, 28
PEAK = {"hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cell():
    return bench.load_cell(CELL)


def scan_ctx(cell, in_trace, op_seconds, mesh=True):
    config = dict(cell["config"])
    if not mesh:
        del config["mesh"]
    cycle = schedule.spec_queries(cell["traffic"])
    return {"device": {"op_seconds": op_seconds}, "config": config,
            "in_trace": [{"index": i} for i in in_trace], "cycle": cycle,
            "rows": ROWS, "peak": PEAK, "table_mod": ssb_flat}


def test_the_cell_is_the_issues(cell):
    config = cell["config"]
    assert (config["rows"], config["segments"], config["servers"]) \
        == (ROWS, SEGMENTS, 1)
    assert config["mesh"] == {"seg": 4, "doc": 1}
    assert cell["cell"]["chips"] == 4 and cell["cell"]["traffic"] \
        == "flights_c2"
    # 28 is the finest split of the 84 months that four divides: 32 leaves
    # segments without a month, and the table refuses it
    assert len(ssb_flat.segment_months(27, 28)) == 3
    with pytest.raises(ValueError):
        ssb_flat.segment_months(0, 32)
    assert max(ssb_flat.segment_sizes(SEGMENTS, ROWS)) == 3_428_572
    names = {m["name"] for m in cell["per_layer"]}
    assert {"scan_roofline_mesh", "collective_share",
            "staged_fullest_device_share", "flight_q1_p50_ms.x4",
            "residency_hit_share.x4", "device_wait_ms.x4",
            "staged_bytes_per_row", "device_idle_share"} <= names
    assert not {"scan_roofline", "flight_q1_p50_ms"} & names


def test_scan_roofline_mesh_divides_by_the_meshs_bandwidth(cell):
    read = bench.metric_reader("scan_roofline_mesh")
    ctx = scan_ctx(cell, [3, 3, 10], op_seconds=2.0)
    least = sum(work.scan_least_bytes(ssb_flat, ctx["cycle"][i], SEGMENTS,
                                      ROWS) for i in (3, 3, 10))
    # Q2.1 twice and Q4.1 keep every segment: 44 and 57 packed bits a row
    assert least == ROWS * (2 * 44 + 57) / 8.0
    assert read(ctx) == pytest.approx(100.0 * least / (4 * 819e9) / 2.0)
    # one chip's reader on the same numbers reads four times as much
    one = bench.metric_reader("scan_roofline")(ctx)
    assert one == pytest.approx(4.0 * read(ctx))


@pytest.mark.parametrize("why", ["no_mesh", "no_trace", "nothing_answered"])
def test_scan_roofline_mesh_finds_nothing_to_read(cell, why):
    read = bench.metric_reader("scan_roofline_mesh")
    ctx = scan_ctx(cell, [] if why == "nothing_answered" else [3],
                   op_seconds=2.0, mesh=why != "no_mesh")
    if why == "no_trace":
        ctx["device"] = None
    assert read(ctx) is None


def test_collective_share_counts_collectives_of_all_chips(cell):
    read = bench.metric_reader("collective_share")
    ops = [["jit_pallas_scan_sharded/pallas_scan_sharded.1", 30.0],
           ["jit_pallas_scan_sharded/all-reduce.3", 1.5],
           ["jit_pallas_scan_sharded/all-reduce-start.1", 0.25],
           ["jit_pallas_scan_sharded/all-reduce-done.1", 0.25],
           ["jit_pallas_probe_sharded/all-gather.2", 1.0],
           ["jit_pallas_scan_sharded/fusion.7", 3.0],
           ["jit_pallas_scan_sharded/reduce.4", 4.0]]
    # op_seconds is a mean over the four chips; the list sums over them
    dev = {"op_seconds": 10.0, "device_ops": ops}
    ctx = {"device": dev, "config": cell["config"]}
    assert read(ctx) == pytest.approx(100.0 * 3.0 / 40.0)
    assert read({"device": dev, "config": {}}) is None
    assert read({"device": None, "config": cell["config"]}) is None


def test_staged_fullest_device_share_reads_the_per_device_view():
    read = bench.metric_reader("staged_fullest_device_share")

    def after(staged):
        return {"after": {"memory": {"devices": [
            {"id": i, "stagedBytes": n} for i, n in enumerate(staged)]}}}

    assert read(after([10, 10, 10, 10])) == 25.0
    assert read(after([70, 10, 10, 10])) == 70.0
    assert read(after([0, 0, 0, 0])) is None
    # a program from before the per-device view: nothing to read
    assert read({"after": {"memory": {"stagedBytes": 40}}}) is None


def test_toy_drive_on_four_forced_devices(tmp_path):
    """The cell's own files through ``run()``: the program picks mesh 4x1
    from the four devices it finds, the answers are the oracle's, and the
    per-device counter reads as a count."""
    code = ("import json\n"
            "from benchmarks import run\n"
            "if __name__ == '__main__':\n"
            f"    line = run.run({CELL!r}, 2 ** 31 + 28, 2.0, True, "
            "expect_platform='cpu', rows=56_000, "
            f"data_root={str(tmp_path)!r}, strict=False)[-1]\n"
            "    print(line)\n")
    script = tmp_path / "drive.py"
    script.write_text(code)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=bench.ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(script)], env=env,
                       cwd=bench.ROOT, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    share = line["metrics"]["staged_fullest_device_share"]
    assert share["unit"] == "%" and 25.0 <= share["value"] <= 100.0
    assert line["metrics"]["flight_q2_p50_ms.x4"]["value"] \
        == bench.NOT_MEASURED
    assert line["compared"]["residency_spills"] == {"value": 0, "limit": 0}
    assert line["compared"]["host_served_decisions"]["value"] == 0
    # no device plane on the CPU: the trace's readers found nothing
    assert not {"scan_roofline_mesh", "collective_share"} \
        & set(line["metrics"])
