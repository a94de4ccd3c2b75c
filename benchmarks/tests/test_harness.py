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


def go(tmp, trace, **kw):
    lines = bench.run(CELL, SEED, 3.0, trace, expect_platform="cpu",
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


def test_a_cpu_run_reports_no_time(timed, traced):
    assert set(timed["metrics"]) == {"queries_per_s", "latency_p50_ms",
                                     "latency_p95_ms", "setup_s"}
    for line in (timed, traced):
        for name, m in line["metrics"].items():
            if name == "staged_bytes_per_row":
                assert isinstance(m["value"], float)     # a count
            else:
                assert m["value"] == bench.NOT_MEASURED, name
        assert line["device"]["memory_peak_bytes"] == bench.NOT_MEASURED


def test_traced_run_reads_spans_and_counters(traced):
    assert traced["correct"] is True
    names = set(traced["metrics"])
    assert {"rest_overhead_ms", "broker_self_ms", "sched_wait_ms",
            "exec_host_self_ms", "staged_bytes_per_row",
            "flight_q1_p50_ms"} <= names
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


def test_an_altered_answer_makes_the_run_incorrect(data_root, monkeypatch):
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
    line = go(data_root, False)
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
