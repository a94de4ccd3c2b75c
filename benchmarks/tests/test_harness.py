"""The harness end to end at toy size on the CPU: counts and ``correct``
only (every time reads "not measured"), the faults ``correct`` has to
catch, and that cells, configs, traffic and metrics are found as files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import run as bench

ROOT = bench.ROOT
CELL = "ssb_scan.flights_c2"
ROWS, SEED = 40_000, 2 ** 31 + 1234


def go(tmp, trace, cell=CELL, **kw):
    lines = bench.run(cell, SEED, 3.0, trace, expect_platform="cpu",
                      rows=ROWS, data_root=str(tmp), strict=False, **kw)
    return json.loads(lines[-1])


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_data")


@pytest.fixture(scope="module")
def timed(data_root):
    return go(data_root, False)


@pytest.fixture(scope="module")
def traced(data_root):
    return go(data_root, True)


def test_timed_run_counts_and_is_correct(timed):
    assert list(timed)[:5] == ["correct", "attempted", "failed", "metrics",
                               "device"]
    assert list(timed)[-1] == "compared"
    assert timed["correct"] is True
    assert timed["attempted"] > 0 and timed["failed"] == 0
    assert timed["device"]["platform"] == "cpu"
    compared = timed["compared"]
    assert compared["max_abs_diff"] == {"value": 0.0, "limit": 0}
    assert compared["responses_compared"]["value"] == timed["attempted"]


def sources(cell_name):
    cell = bench.load_cell(cell_name)
    return {m["name"]: m["source"]
            for m in cell["end_to_end"] + cell["per_layer"]}


def test_a_cpu_run_reports_no_time(timed, traced):
    # two clients leave the tail to which two strings meet: c2's p95 is a
    # per-layer reading (PERF.md section 2), the loaded cells' has a bound
    assert set(timed["metrics"]) == {"queries_per_s", "latency_p50_ms",
                                     "setup_s"}
    assert "request_p95_ms" in traced["metrics"]
    assert "latency_p95_ms" in sources(CELL_C8V)
    source = sources(CELL)
    counts = 0
    for line in (timed, traced):
        for name, m in line["metrics"].items():
            if source[name] in bench.COUNT_SOURCES:
                assert isinstance(m["value"], float), name      # a count
                counts += 1
            else:
                assert m["value"] == bench.NOT_MEASURED, name
        assert line["device"]["memory_peak_bytes"] == bench.NOT_MEASURED
    assert counts >= 2      # the traced run's, at the least


def test_traced_run_reads_spans_and_counters(traced):
    assert traced["correct"] is True
    names = set(traced["metrics"])
    assert {"rest_overhead_ms", "broker_self_ms", "exec_host_self_ms",
            "staged_bytes_per_row", "flight_q1_p50_ms"} <= names
    # it moves latency_p95_ms, which c2 no longer reports end to end
    assert "sched_wait_ms" not in names
    # no device plane on the CPU: those readers found nothing to read
    assert not names & {"scan_roofline", "device_idle_share",
                        "kernel_device_ms_per_query", "launches_per_query"}
    assert traced["metrics"]["staged_bytes_per_row"]["value"] > 0


def test_the_command_line_wants_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 tpu" in p.stderr


@pytest.mark.parametrize("cell", [CELL, "ssb_scan.flights_c8v"])
def test_an_altered_answer_makes_the_run_incorrect(data_root, monkeypatch,
                                                   cell):
    """The timed path broken underneath: the broker's reduce adds one to
    the first sum of every answer it produces."""
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
    # warm-up sees HTTP 200 and goes on; the comparison is the window's
    line = go(data_root, False, cell)
    assert line["correct"] is False
    # (an answer with no row at this toy size has nothing to alter)
    assert line["failed"] == line["compared"]["responses_wrong"]["value"] > 0
    assert line["compared"]["max_abs_diff"]["value"] >= 1.0


def test_a_partial_response_counts_as_failed(data_root, monkeypatch):
    """A guarantee broken where the response is made: one server of those
    asked did not answer."""
    from pinot_tpu.common.response import BrokerResponse

    sound = BrokerResponse.to_dict

    def partial(self, *a, **kw):
        d = sound(self, *a, **kw)
        d["numServersResponded"] = d.get("numServersQueried", 1) - 1
        return d

    monkeypatch.setattr(BrokerResponse, "to_dict", partial)
    line = go(data_root, False)
    assert line["correct"] is False
    assert line["compared"]["responses_failed"]["value"] == line["attempted"]


def test_a_window_that_compiled_is_a_failed_run(data_root, monkeypatch):
    sound = bench.run_window

    def compiled(*a, **kw):
        win = sound(*a, **kw)
        win["after"]["cache_entries"] = (win["after"]["cache_entries"]
                                         + ["jit_late-0000-cache"])
        return win

    monkeypatch.setattr(bench, "run_window", compiled)
    with pytest.raises(bench.RunFailed, match="compiled inside"):
        bench.run(CELL, SEED, 2.0, False, expect_platform="cpu", rows=ROWS,
                  data_root=str(data_root))


def test_a_window_that_merged_requests_is_a_failed_run(data_root,
                                                       monkeypatch):
    sound = bench.run_window

    def merged(*a, **kw):
        win = sound(*a, **kw)
        win["after"]["broker"]["singleFlight"]["hits"] += 2
        return win

    monkeypatch.setattr(bench, "run_window", merged)
    with pytest.raises(bench.RunFailed, match="merged with a twin"):
        bench.run(CELL, SEED, 2.0, False, expect_platform="cpu", rows=ROWS,
                  data_root=str(data_root))


def test_files_alone_add_a_config_a_mix_a_cell_and_a_metric(tmp_path):
    """A later PR adds files and appends entries; it edits nothing. Its
    cell takes the readers that are there (a flight's median, the scan's
    roofline) through entries of its own, ``<reader>.<suffix>``."""
    tree = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tree / "benchmarks",
                    ignore=shutil.ignore_patterns(".data", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    with open(tree / "benchmarks" / "configs" / "ssb_scan.json") as f:
        config = json.load(f)
    config["segments"] = 4
    (tree / "benchmarks" / "configs" / "ssb_scan_s4.json").write_text(
        json.dumps(config))
    with open(tree / "benchmarks" / "traffic" / "flights_c2.json") as f:
        traffic = json.load(f)
    traffic["clients"] = 3
    (tree / "benchmarks" / "traffic" / "flights_c3.json").write_text(
        json.dumps(traffic))
    (tree / "benchmarks" / "metrics" / "answered_by_q2.py").write_text(
        "def read(ctx):\n"
        "    return float(sum(r['group'] == 'q2' for r in ctx['records']))\n")
    doc["configs"].append({"name": "ssb_scan_s4", "source": "test",
                           "file": "benchmarks/configs/ssb_scan_s4.json",
                           "reduced": ["rows"], "why": "test"})
    doc["workloads"].append({"name": "ssb_scan_s4.flights_c3",
                             "config": "ssb_scan_s4",
                             "traffic": "flights_c3", "chips": 1,
                             "why": "test"})
    doc["per_layer"].append({"name": "answered_by_q2", "unit": "queries",
                             "better": "higher",
                             "source": "program_counter",
                             "layer": "service, by SSB flight",
                             "moves": "queries_per_s",
                             "workloads": ["ssb_scan_s4.flights_c3"]})
    for entry in doc["per_layer"][:]:
        if entry["name"] in ("flight_q1_p50_ms", "scan_roofline"):
            doc["per_layer"].append(dict(
                entry, name=entry["name"] + ".s4",
                workloads=["ssb_scan_s4.flights_c3"]))
    (tree / "BENCHMARK.json").write_text(json.dumps(doc))
    code = ("import json, sys\n"
            f"sys.path[:0] = [{str(tree)!r}, {ROOT!r}]\n"
            "from benchmarks import run\n"
            f"assert run.ROOT == {str(tree)!r}\n"
            "line = run.run('ssb_scan_s4.flights_c3', 11, 2.0, True, "
            f"expect_platform='cpu', rows={ROWS}, strict=False)[-1]\n"
            "print(line)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=str(tree),
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["answered_by_q2"]["value"] > 0
    assert line["metrics"]["flight_q1_p50_ms.s4"]["unit"] == "ms"
    # metrics that list other cells stay out; so does a roofline on the CPU
    assert not {"scan_roofline", "scan_roofline.s4",
                "flight_q1_p50_ms"} & set(line["metrics"])
    # the new cell's data went under the copy, not under the repository
    assert (tree / "benchmarks" / ".data").is_dir()


def test_old_stores_go_when_the_cap_is_passed(tmp_path):
    from benchmarks.lib import serve

    for i, name in enumerate(["a_seed1_rows9", "a_seed2_rows9",
                              "a_seed3_rows9"]):
        os.makedirs(tmp_path / name / "seg_0")
        (tmp_path / name / "seg_0" / "col").write_bytes(b"x" * 1000)
        (tmp_path / name / "manifest.json").write_text("{}")
        os.utime(tmp_path / name / "manifest.json", (100 + i, 100 + i))
    os.makedirs(tmp_path / "work")          # no manifest: not a store
    newest = str(tmp_path / "a_seed3_rows9")
    serve.prune_stores(str(tmp_path), keep=newest, cap_bytes=2500)
    assert sorted(os.listdir(tmp_path)) == ["a_seed2_rows9", "a_seed3_rows9",
                                            "work"]
    serve.prune_stores(str(tmp_path), keep=newest, cap_bytes=10)
    assert sorted(os.listdir(tmp_path)) == ["a_seed3_rows9", "work"]


def test_every_seed_warms_the_same_strings_in_the_same_order():
    from benchmarks.lib import schedule

    traffic = schedule.load_traffic("flights_c8")
    warmed = []
    for seed in (7, 2 ** 31 + 9):
        cycle = schedule.build_cycle(traffic, seed)
        sql = [q["sql"] for q in cycle]
        warmed.append(([sql[i] for i in bench.walk_order(cycle)],
                       [[sql[i] for i in plan]
                        for plan in bench.burst_plans(cycle, 8)]))
    assert warmed[0] == warmed[1]
    assert sorted(warmed[0][0]) == sorted(sql)      # the whole cycle, once


# --------------------------------------------------------------------------
# the eight-client cell on the scan table (PR 34)
# --------------------------------------------------------------------------

CELL_C8V = "ssb_scan.flights_c8v"


def test_the_eight_client_scan_cell_loads():
    from benchmarks.lib import schedule

    cell = bench.load_cell(CELL_C8V)
    assert cell["cell"]["chips"] == 1
    assert cell["config"] == bench.load_cell(CELL)["config"]    # c2's table
    traffic = cell["traffic"]
    assert (traffic["runner"], traffic["clients"]) == ("closed", 8)
    cycle = schedule.build_cycle(traffic, SEED)
    assert len({q["sql"] for q in cycle}) == len(cycle) == 13 * 25
    names = {m["name"] for m in cell["per_layer"]}
    assert {"launch_queue_wait_ms", "scan_roofline", "flight_q4_p50_ms",
            "residency_hit_share", "device_idle_share"} <= names
    assert not names & {"segment_queue_ms", "startree_walk_cpu_ms",
                        "scan_roofline_mesh", "device_wait_ms.x4"}
    # no accepted cell's line gains the new metric
    for other in ("ssb_startree.flights_c8", CELL, "ssb_scan_x4.flights_c2"):
        assert "launch_queue_wait_ms" not in {
            m["name"] for m in bench.load_cell(other)["per_layer"]}


def test_the_scan_cells_draw_from_one_queue_and_the_tree_cell_does_not():
    """Clients that walk one cycle each at its own pace meet on the scan
    table: eight inside most windows (PERF.md section 4), c2's two in one
    window of some forty (415 twins merged, PR 34: what refused PR 26 and
    PR 31). The scan cells' files feed their clients from one queue, as
    upstream's runner does; flights_c8 on the trees, where no run of the
    ledger's has met a twin, keeps a queue a client."""
    from benchmarks.lib import schedule

    traffic = schedule.load_traffic("flights_c8v")
    assert schedule.offsets(traffic, 325) == [0]
    job = bench.own_job(type("S", (), {"broker_port": 1}), [{}] * 325,
                        [""] * 325, traffic, 1.0)
    assert job["offsets"] == [0] and job["clients"] == 8
    assert schedule.offsets(schedule.load_traffic("flights_c2"),
                            104) == [0]
    assert schedule.offsets(schedule.load_traffic("flights_c8"),
                            104) == [13 * i for i in range(8)]


@pytest.mark.parametrize("clients,offsets", [(8, [0]), (2, [0, 8]),
                                             (8, [0, 4, 8, 12])])
def test_a_queue_hands_a_string_out_again_only_after_every_other(
        clients, offsets):
    """The closed runner against a server that answers at once: every
    queue's strings go out in cycle order from its offset, whichever of
    its clients takes them, and a queue of one client is that client's
    own walk."""
    import http.server
    import threading

    from benchmarks.lib import client

    class Echo(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def do_POST(self):
            self.rfile.read(int(self.headers["Content-Length"]))
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *a):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Echo)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        out = client.Run({
            "host": "127.0.0.1", "port": server.server_address[1],
            "path": "/query/sql", "sqls": [str(i) for i in range(16)],
            "runner": "closed", "clients": clients, "offsets": offsets,
            "seconds": 0.5}).run()
    finally:
        server.shutdown()
        server.server_close()
    records = out["records"]
    assert len(records) > 16 and all(r["status"] == 200 for r in records)
    for q, offset in enumerate(offsets):
        mine = [r["index"] for r in records
                if r["client"] % len(offsets) == q]
        want = [(offset + k) % 16 for k in range(len(mine))]
        assert sorted(mine) == sorted(want)     # no string skipped or twice
        if clients == len(offsets):             # its own walk, in order
            assert mine == want


def traced_record(children):
    return {"ok": True, "raw": {"traceInfo": {"spans": [{
        "name": "BrokerQuery", "ms": 30.0, "children": [{
            "name": "ScatterGather", "ms": 28.0, "children": [{
                "name": "ServerQuery", "ms": 25.0,
                "children": children}]}]}]}}}


def test_launch_queue_wait_reads_the_sharded_combine():
    read = bench.metric_reader("launch_queue_wait_ms")

    def combine(queue_ms):
        return {"name": "ShardedCombine", "ms": queue_ms + 9.0,
                "queueMs": queue_ms, "workMs": 9.0}

    records = [traced_record([combine(4.0)]),
               traced_record([combine(6.5), combine(1.0)]),    # summed
               traced_record([combine(12.0)]),
               # the per-segment ladder: no sharded launch, left out
               traced_record([{"name": "SegmentAggregate", "ms": 20.0}]),
               dict(traced_record([combine(90.0)]), ok=False)]
    assert read({"records": records}) == 7.5
    assert read({"records": records[3:4]}) is None
    assert read({"records": []}) is None


@pytest.fixture(scope="module")
def c8v(data_root):
    return go(data_root, True, CELL_C8V)


def test_a_toy_drive_of_the_eight_client_cell_is_correct(c8v):
    assert c8v["correct"] is True and c8v["failed"] == 0
    assert c8v["attempted"] >= 8
    source = sources(CELL_C8V)
    assert {"launch_queue_wait_ms", "flight_q1_p50_ms", "sched_wait_ms",
            "residency_hit_share"} <= set(c8v["metrics"])
    for name, m in c8v["metrics"].items():
        if source[name] not in bench.COUNT_SOURCES:
            assert m["value"] == bench.NOT_MEASURED, name
    assert c8v["device"]["memory_peak_bytes"] == bench.NOT_MEASURED


# --------------------------------------------------------------------------
# a failed run says why on the line the driver keeps
# --------------------------------------------------------------------------

def main_on_the_cpu(monkeypatch, data_root, trace):
    """``main`` as the driver calls it, but for the look for a chip."""
    real = bench.run

    def run(workload, seed, seconds, trace, **kw):
        return real(workload, seed, seconds, trace, expect_platform="cpu",
                    rows=ROWS, data_root=str(data_root), strict=False, **kw)

    monkeypatch.setattr(bench, "run", run)
    return bench.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                       "2", "--trace", str(trace)])


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_a_refused_warm_up_request_is_a_failed_run_that_says_so(
        data_root, monkeypatch, capsys):
    sound = bench.client_job

    def refused(served, sqls, **kw):
        return dict(sound(served, sqls, **kw), path="/query/nowhere")

    monkeypatch.setattr(bench, "client_job", refused)
    assert main_on_the_cpu(monkeypatch, data_root, 0) == 1
    line = last_line(capsys)
    assert list(line) == ["correct", "failed_run", "phase", "metrics"]
    assert line["correct"] is False and line["metrics"] == {}
    assert line["phase"] == "warm"
    assert "warm-up warm_walk: 104 requests failed" in line["failed_run"]
    assert len(line["failed_run"]) <= 300 and "\n" not in line["failed_run"]


def test_an_exception_in_a_reader_is_a_failed_run_that_says_so(
        data_root, monkeypatch, capsys):
    def broken(name):
        def read(ctx):
            raise KeyError(f"{name} found no such span\nin the tree")
        return read

    monkeypatch.setattr(bench, "metric_reader", broken)
    assert main_on_the_cpu(monkeypatch, data_root, 1) == 1
    line = last_line(capsys)
    assert line["correct"] is False and line["phase"] == "reduce"
    assert line["failed_run"].startswith("KeyError: ")
    assert "\n" not in line["failed_run"]


def test_a_run_that_ends_well_keeps_its_line(data_root, monkeypatch, capsys):
    assert main_on_the_cpu(monkeypatch, data_root, 0) == 0
    line = last_line(capsys)
    assert "failed_run" not in line and "phase" not in line
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]


def test_the_failed_line_is_one_line_of_300_characters_at_most():
    with pytest.raises(bench.RunFailed) as caught:
        with bench.phase("compare"):
            with bench.phase("reduce"):     # the innermost phase stands
                raise bench.RunFailed("x " * 400 + "\n\tend")
    line = json.loads(bench.failed_line(caught.value))
    assert line["phase"] == "reduce" and len(line["failed_run"]) == 300
    assert line["failed_run"].startswith("x x ")
    # raised outside any phase, a run is in its set-up
    assert json.loads(bench.failed_line(OSError("disk")))["phase"] \
        == "set_up"


def test_the_set_up_line_names_the_oracles_own_seconds(data_root, capfd):
    bench.run(CELL, SEED, 1.0, False, expect_platform="cpu", rows=ROWS,
              data_root=str(data_root), strict=False)
    line = next(ln for ln in capfd.readouterr().err.splitlines()
                if " set-up " in ln)
    assert " oracle_join_s=" in line and " oracle_s=" in line
    assert " oracle_strings=104 " in line
