"""The benchmark: one cell of BENCHMARK.json, measured on the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it builds (or finds) the cell's segments from
``--seed``, brings the cluster up with its REST endpoints on loopback, warms
every shape the cell's cycle uses, and then only waits while a client
process of its own (``lib/client.py``, standard library, never JAX) drives
``POST /query/sql`` for ``--seconds``. Afterwards every response of the
window is compared with the plain reference (``lib/oracle.py``, numpy over
the same seeded rows, computed in a child beside the build).

The last line of standard output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, the numbers compared). A run that
cannot stand as a measurement, or that raised, exits non-zero and its last
line says why: ``{"correct": false, "failed_run": <reason>, "phase":
<set_up | warm | window | compare | reduce>, "metrics": {}}``. With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` every query carries ``trace=true``, a few seconds of the
window are recorded with ``jax.profiler``, and the metrics are the cell's
per-layer metrics, each read by ``metrics/<name>.py``.

A configuration with a ``realtime`` section is a hybrid table
(``lib/hybrid.py``): a producer process of the benchmark's own
(``lib/producer.py``, standard library and numpy) fills its REALTIME half
on a schedule, each response is judged against the stream prefix it could
have read, and the ingest's guarantees are numbers beside limits. Every
branch for it is keyed on that section: any other configuration takes
the paths it always took.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by the name in BENCHMARK.json; nothing here names
one. The command line always expects a TPU; ``run()`` takes the platform,
so that the tests drive the same code at toy size on the CPU, where every
time reads "not measured".
"""

from __future__ import annotations

import time

_T0 = time.time()       # process start, as near as Python gives it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import compare, schedule, stats  # noqa: E402

NOT_MEASURED = "not measured"
WARM_OWN_SECONDS = 2.0      # a slice of the cell's own traffic, warming
COUNT_SOURCES = ("program_counter",)    # what a CPU run may report


def log(msg: str) -> None:
    print(f"bench[{time.time() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


class RunFailed(RuntimeError):
    """The run cannot stand as a measurement (not: an answer was wrong)."""


PHASES = ("set_up", "warm", "window", "compare", "reduce")


@contextlib.contextmanager
def phase(name: str):
    """What is raised inside says in which phase of the run (the
    innermost); outside any, a run is in its set-up."""
    assert name in PHASES, name
    try:
        yield
    except Exception as e:
        if not hasattr(e, "phase"):
            e.phase = name
        raise


def failed_line(error: Exception) -> str:
    """What a failed run prints last on standard output: the driver keeps
    that line, and of standard error only the exit code."""
    reason = (str(error) if isinstance(error, RunFailed)
              else f"{type(error).__name__}: {error}")
    return json.dumps({"correct": False,
                       "failed_run": " ".join(reason.split())[:300],
                       "phase": getattr(error, "phase", PHASES[0]),
                       "metrics": {}})


# --------------------------------------------------------------------------
# the files a cell is made of
# --------------------------------------------------------------------------

def load_cell(workload: str) -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json "
                         f"(has: {', '.join(sorted(cells))})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)

    def reported(metric: Dict[str, Any]) -> bool:
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config,
            "traffic": schedule.load_traffic(cell["traffic"]),
            "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
            "per_layer": [m for m in bench["per_layer"] if reported(m)]}


def metric_reader(name: str):
    """``metrics/<name>.py``, or for ``<base>.<suffix>`` with no file of its
    own ``metrics/<base>.py``: a later cell takes a reader that is there
    through an entry of its own (``flight_q1_p50_ms.c1`` with its cell
    under ``workloads``), and edits no entry that is there."""
    base = name
    while "." in base and not os.path.isfile(
            os.path.join(HERE, "metrics", f"{base}.py")):
        base = base.rsplit(".", 1)[0]
    path = os.path.join(HERE, "metrics", f"{base}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_for(kind: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, "lib", "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise RunFailed(f"no peaks for device kind {kind!r} in lib/peaks.json")
    return peaks[kind]


# --------------------------------------------------------------------------
# children: the oracle and the clients
# --------------------------------------------------------------------------

class Child:
    """A spawned helper that takes a job file and leaves an output file."""

    def __init__(self, script: str, job: Dict[str, Any], work: str,
                 tag: str):
        self.out = os.path.join(work, f"{tag}.out.json")
        job_path = os.path.join(work, f"{tag}.job.json")
        with open(job_path, "w") as f:
            json.dump(job, f)
        if os.path.exists(self.out):
            os.remove(self.out)
        self.tag = tag
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "lib", script), job_path,
             self.out], stdin=subprocess.DEVNULL)

    def join(self, timeout_s: float) -> Dict[str, Any]:
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.kill()
            raise RunFailed(f"{self.tag} child did not end in {timeout_s}s")
        if rc != 0:
            raise RunFailed(f"{self.tag} child exited with {rc}")
        with open(self.out) as f:
            return json.load(f)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()

    def ready(self, timeout_s: float) -> int:
        """The port a child that serves wrote to ``<out>.ready``."""
        deadline = time.monotonic() + timeout_s
        while not os.path.exists(self.out + ".ready"):
            if self.proc.poll() is not None or time.monotonic() > deadline:
                raise RunFailed(f"{self.tag} child did not come up "
                                f"(exit {self.proc.poll()})")
            time.sleep(0.02)
        with open(self.out + ".ready") as f:
            return json.load(f)["port"]


def with_trace(sql: str) -> str:
    if sql.endswith(")") and " OPTION(" in sql:
        return sql[:-1] + ", trace=true)"
    return sql + " OPTION(trace=true)"


def client_job(served, sqls: List[str], **kw: Any) -> Dict[str, Any]:
    return dict(host="127.0.0.1", port=served.broker_port,
                path="/query/sql", sqls=sqls, timeout_s=300.0, **kw)


def own_job(served, cycle: List[Dict[str, Any]], sqls: List[str],
            traffic: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """The cell's own traffic for ``seconds``: the window, and the
    warm-up's slices of it."""
    kw: Dict[str, Any] = {"runner": traffic["runner"],
                          "clients": traffic["clients"], "seconds": seconds}
    if traffic["runner"] == "closed":
        kw["offsets"] = schedule.offsets(traffic, len(cycle))
    else:
        kw["rate"] = traffic["rate"]
    return client_job(served, sqls, **kw)


def walk_order(cycle: List[Dict[str, Any]]) -> List[int]:
    """The warm-up's one-client walk, in an order that no seed changes:
    what a query leaves on the device is still there when the next one
    runs, so the order is part of the run's peak memory."""
    return sorted(range(len(cycle)),
                  key=lambda i: (cycle[i]["flight"], cycle[i]["sql"]))


def burst_plans(cycle: List[Dict[str, Any]], clients: int
                ) -> List[List[int]]:
    """Warming under concurrency: for k = 2, 4, ... up to the cell's
    clients, k clients at a time send k variants of one flight together,
    so that the launch coalescer meets every group size the window can
    form, on every flight. Every seed bursts the same strings in the same
    order: what the bursts leave on the device sets the run's peak."""
    by_flight: Dict[str, List[int]] = {}
    for q in sorted(cycle, key=lambda q: q["sql"]):     # as no seed orders it
        by_flight.setdefault(q["flight"], []).append(q["id"])
    flights = sorted(by_flight)
    plans: List[List[int]] = [[] for _ in range(clients)]
    k = 2
    while k <= clients:
        groups = clients // k
        for step in range(-(-len(flights) // groups)):
            for i in range(groups * k):
                ids = by_flight[flights[(step * groups + i // k)
                                        % len(flights)]]
                plans[i].append(ids[(i % k) % len(ids)])
        k *= 2
    steps = min(len(p) for p in plans)      # lockstep wants equal lengths
    return [p[:steps] for p in plans]


# --------------------------------------------------------------------------
# one window
# --------------------------------------------------------------------------

def profile_inside(work: str, seconds: float, traffic: Dict[str, Any]
                   ) -> Dict[str, Any]:
    """Record a few seconds of the window with jax.profiler (the one thing
    of ours that runs in the server's process during a traced window)."""
    import jax

    from benchmarks.lib import trace_reduce

    lead = min(3.0, 0.25 * seconds)
    span = min(float(traffic.get("trace_seconds", 5.0)), 0.5 * seconds)
    out = os.path.join(work, "profile")
    shutil.rmtree(out, ignore_errors=True)
    time.sleep(lead)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        begin = time.time()
        with jax.profiler.TraceAnnotation(trace_reduce.MARK_BEGIN):
            pass
        time.sleep(span)
        end = time.time()
        with jax.profiler.TraceAnnotation(trace_reduce.MARK_END):
            pass
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return {"wall_begin": begin, "wall_end": end,
            "path": found[-1] if found else None}


def run_window(served, setup: Dict[str, Any], seconds: float, trace: bool,
               tag: str) -> Dict[str, Any]:
    """Counters, the client child, counters; nothing else of ours runs in
    this process meanwhile (the traced run's profiler apart)."""
    traffic, cycle = setup["traffic"], setup["cycle"]
    sqls = [with_trace(q["sql"]) if trace else q["sql"] for q in cycle]
    before = served.counters()
    job = own_job(served, cycle, sqls, traffic, seconds)
    if setup["config"].get("realtime"):     # the producer goes live with it
        from benchmarks.lib import hybrid

        job["start_wall"] = time.time() + hybrid.WINDOW_LEAD_S
        setup["producer"].live(job["start_wall"], seconds)
    child = Child("client.py", job, setup["work"], tag)
    try:
        profile = (profile_inside(setup["work"], seconds, traffic)
                   if trace else None)
        out = child.join(seconds + 240.0)
    finally:
        child.kill()
    after = served.counters()
    return {"before": before, "after": after, "profile": profile,
            "t0_wall": out["t0_wall"], "elapsed_s": out["elapsed_s"],
            "records": out["records"]}


def judge(served, setup: Dict[str, Any], win: Dict[str, Any],
          platform: str) -> Dict[str, Any]:
    """The comparison that decides ``correct``: every response of the
    window against the oracle, and the deployment's guarantees."""
    config, cycle = setup["config"], setup["cycle"]
    if config.get("realtime"):
        from benchmarks.lib import hybrid

        numbers = hybrid.compare_window(
            win["records"], cycle, setup["want"], setup["stream"],
            setup["pins"], win["log"], config, setup["table_mod"])
    else:
        numbers = compare.compare(win["records"], cycle, setup["want"])
    breaches = served.ledger_breaches(config["forbidden_decision_reasons"],
                                      platform)
    spills = (win["after"]["memory"]["counters"]["spills"]
              - setup["counters_at_start"]["memory"]["counters"]["spills"])
    compiled = served.compiled_between(win["before"], win["after"])
    flight_hits = (win["after"]["broker"]["singleFlight"]["hits"]
                   - win["before"]["broker"]["singleFlight"]["hits"])
    log(f"single-flight hits over the window: {flight_hits} (a run with "
        f"any is no measurement)")
    if numbers["first_failed"] or numbers["first_wrong"]:
        log(f"failed: {numbers['first_failed']} "
            f"wrong: {numbers['first_wrong']}")
    if numbers.get("first_stale"):
        log(f"stale: {numbers['first_stale']}")
    if breaches:
        log(f"the device did not serve everything: {breaches[:5]}")
    attempted = len(win["records"])
    compared = {
        "max_abs_diff": {"value": numbers["max_abs_diff"], "limit": 0},
        "responses_wrong": {"value": numbers["responses_wrong"],
                            "limit": 0},
        "responses_failed": {"value": numbers["responses_failed"],
                             "limit": 0},
        "host_served_decisions": {"value": len(breaches), "limit": 0},
        "residency_spills": {"value": spills, "limit": 0},
    }
    if config.get("realtime"):
        compared["responses_stale"] = {"value": numbers["responses_stale"],
                                       "limit": 0}
    ok = attempted > 0 and all(v["value"] <= v["limit"]
                               for v in compared.values())
    compared["responses_compared"] = {"value": numbers["responses_compared"],
                                      "limit": attempted}
    return {"correct": bool(ok), "attempted": attempted,
            "failed": numbers["responses_failed"]
            + numbers["responses_wrong"],
            "compared": compared, "compiled_in_window": compiled,
            "single_flight_hits": flight_hits}


def ok_records(win: Dict[str, Any], cycle: List[Dict[str, Any]]
               ) -> List[Dict[str, Any]]:
    out = []
    for rec in win["records"]:
        if rec.get("ok"):
            q = cycle[rec["index"]]
            rec.update(group=q["group"], flight=q["flight"],
                       sent_wall=win["t0_wall"] + rec["sent_s"],
                       done_wall=win["t0_wall"] + rec["done_s"])
            out.append(rec)
    return out


def end_to_end(win: Dict[str, Any], records: List[Dict[str, Any]],
               setup: Dict[str, Any], peak_bytes: int) -> Dict[str, float]:
    """All the work and all the time of the window: correct responses over
    the time to the last of them; the percentiles of all of them."""
    lat = [r["latency_ms"] for r in records]
    out = {"queries_per_s": len(records) / win["elapsed_s"],
           "latency_p50_ms": stats.percentile(lat, 50.0),
           "latency_p95_ms": stats.percentile(lat, 95.0),
           "setup_s": setup["setup_s"]}
    if peak_bytes:      # a hybrid table's rows: offline and consumed
        rows = setup["rows"] + (sum(win["ingest_end"]["consumed"])
                                if setup["config"].get("realtime") else 0)
        out["hbm_peak_bytes_per_row"] = peak_bytes / rows
    return {k: v for k, v in out.items() if v is not None}


def per_layer(win: Dict[str, Any], records: List[Dict[str, Any]],
              setup: Dict[str, Any], names: List[str]
              ) -> Dict[str, Any]:
    from benchmarks.lib import trace_reduce

    device = None
    profile = win["profile"]
    if profile and profile["path"]:
        trace = trace_reduce.load(profile["path"])
        setup["trace_layout"] = trace["layout"]
        device = trace_reduce.reduce(trace, profile["wall_begin"],
                                     profile["wall_end"], records)
    ctx = {"records": records, "before": win["before"],
           "after": win["after"], "device": device,
           "in_trace": [r for r in records if device
                        and device["wall_begin"] <= r["done_wall"]
                        <= device["wall_end"]],
           "config": setup["config"], "traffic": setup["traffic"],
           "cycle": setup["cycle"], "rows": setup["rows"],
           "peak": setup["peak"], "table_mod": setup["table_mod"],
           "ingest": win.get("ingest")}
    values = {}
    for name in names:
        value = metric_reader(name)(ctx)
        if value is not None:
            values[name] = value
    return {"values": values, "device": device}


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def set_up(workload: str, seed: int, expect_platform: str,
           rows: Optional[int], data_root: str, control: bool,
           seconds: float = 0.0, windows: int = 1) -> Dict[str, Any]:
    """Everything before the window's first request. Returns the served
    cluster and what the window and the comparison need. ``seconds`` and
    ``windows`` size a hybrid table's stream for the oracle."""
    setup = load_cell(workload)
    config, traffic = setup["config"], setup["traffic"]
    rows = rows or config["rows"]
    cycle = schedule.build_cycle(traffic, seed)
    work = os.path.join(data_root, "work", workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    setup.update(rows=rows, cycle=cycle, work=work, seed=seed,
                 table_mod=importlib.import_module(
                     f"benchmarks.tables.{config['table']}"))
    phases: Dict[str, float] = {}
    job = dict(table=config["table"], num_segments=config["segments"],
               rows=rows, seed=seed, cycle=cycle, control=control)
    producer = None
    if config.get("realtime"):
        from benchmarks.lib import hybrid

        try:
            setup["pins"] = hybrid.check(config, cycle)
        except hybrid.ConfigRefused as e:
            raise RunFailed(f"hybrid config refused: {e}") from None
        job["hybrid"] = hybrid.oracle_job(config, setup["pins"], seconds,
                                          windows)
        producer = Child("producer.py", hybrid.producer_job(config, seed),
                         work, "producer")

    # the plain reference, beside the build; numpy only, never the chip
    oracle = Child("oracle.py", job, work, "oracle")
    served = None
    try:
        from benchmarks.lib import serve

        device = serve.device_info(setup["cell"]["chips"], expect_platform)
        setup["device"] = device
        setup["peak"] = (peak_for(device["kind"])
                         if expect_platform == "tpu" else {})
        log(f"device {device}; {rows} rows, {len(cycle)} strings, "
            f"{traffic['clients']} clients ({traffic['runner']})")
        seg_dirs, phases["build_s"] = serve.segment_store(
            setup["cell"]["config"], config, rows, seed, data_root)
        t = time.perf_counter()
        if producer is None:
            served = serve.Served(config, seg_dirs, work)
        else:
            setup["producer"] = hybrid.Producer(producer.ready(60.0))
            served = serve.Served(config, seg_dirs, work,
                                  setup["producer"].url)
        phases["load_and_stage_s"] = time.perf_counter() - t
        if producer is not None:    # the catch-up rows and their seals
            t = time.perf_counter()
            got = served.wait_caught_up(config["realtime"]["catch_up_rows"])
            phases["catch_up_s"] = time.perf_counter() - t
            phases["seals_at_setup"] = got["committed"]
        setup["counters_at_start"] = served.counters()
        t = time.perf_counter()
        with phase("warm"):
            phases.update(warm(served, setup))
        phases["warm_s"] = time.perf_counter() - t
        served.wait_staged()
        # the oracle ran beside the build and the warm-up; by now it has
        # as a rule ended, and the window never opens before it has.
        # oracle_s is the child's own time, table and answers: where it
        # passes the rest of the set-up, setup_s measures the reference
        t = time.perf_counter()
        answers = oracle.join(1200.0)
        phases["oracle_join_s"] = time.perf_counter() - t
        phases["oracle_s"] = answers["oracle"]["seconds"]
        setup["want"] = answers["want"]
        setup["control"] = answers.get("control")
        if producer is not None:
            setup["stream"] = hybrid.Stream.load(
                oracle.out + ".npz", config["realtime"]["partition_column"])
            setup["totals"] = answers["hybrid"]["totals"]
            log(f"oracle: boundary {answers['hybrid']['boundary']}, "
                f"{answers['hybrid']['stream_rows']} stream rows of the "
                f"pinned members past it")
        gc.collect()
        gc.freeze()     # set-up's objects are out of the collector's way
    except BaseException:
        oracle.kill()
        if served is not None:
            served.close()
        if producer is not None:
            producer.kill()
        raise
    setup["phases"] = phases
    setup["served"] = served
    setup["producer_child"] = producer
    return setup


def ingest_window(setup: Dict[str, Any], win: Dict[str, Any],
                  seconds: float) -> None:
    """A hybrid table's window, after the client: the lag at its end,
    the drain, one count-and-sum query a partition over the REALTIME
    half, the seals, the producer's log and lateness."""
    from benchmarks.lib import hybrid

    config, served, producer = (setup["config"], setup["served"],
                                setup["producer"])
    rt = config["realtime"]
    i = setup["windows_done"] = setup.get("windows_done", 0) + 1

    def lag() -> Dict[str, Any]:
        got = served.ingest()
        return {"appended": got["appended"], "consumed": got["consumed"],
                "lag": sum(max(0, a - c) for a, c in zip(got["appended"],
                                                         got["consumed"]))}

    win["ingest_end"] = lag()
    deadline = time.monotonic() + 2 * hybrid.WINDOW_LEAD_S + seconds
    while producer.log(batches=False)["lives_done"] < i:   # its last rows
        if time.monotonic() > deadline:
            raise RunFailed("the producer's live phase did not end")
        time.sleep(0.05)
    t = time.perf_counter()
    drained = lag()
    while drained["lag"] and time.perf_counter() - t < rt["drain_s"]:
        time.sleep(0.05)
        drained = lag()
    drained["drain_s"] = time.perf_counter() - t
    expected = hybrid.appended_after(
        config, i * hybrid.live_rows(config, seconds))
    if drained["appended"] != expected:
        raise RunFailed(f"the producer appended {drained['appended']}, the "
                        f"oracle's stream holds {expected}")
    off = 0
    for p, want in enumerate(setup["totals"][i - 1]):
        sql = hybrid.rows_exact_sql(config, served.realtime_table, p)
        rows = served.check_rows(sql)
        got = [int(v) for v in rows[0]] if rows else []
        if got != want:
            off += 1
            log(f"rows_exact: partition {p} reads {got} (count, sums), the "
                f"reference {want}")
    full = producer.log()
    seals = (win["after"]["ingest"]["committed"]
             - win["before"]["ingest"]["committed"])
    late = full["late_ms"][setup.get("late_seen", 0):]
    setup["late_seen"] = len(full["late_ms"])
    win["log"] = hybrid.Log(full["batches"], rt["partitions"])
    win["guarantees"] = hybrid.guarantees(config, win["ingest_end"],
                                          drained, seals, off)
    # for the per-layer readers of a hybrid cell (their ctx["ingest"])
    win["ingest"] = {"end": win["ingest_end"], "drained": drained,
                     "seals": seals, "producer_late_ms": late}
    setup["last_records"] = win["records"]
    log(f"ingest over the window: appended {sum(drained['appended'])} "
        f"consumed {sum(win['ingest_end']['consumed'])} at its end "
        f"(lag {win['ingest_end']['lag']}), seals {seals}, drain "
        f"{drained['drain_s']:.2f}s (lag {drained['lag']}), "
        f"producer_late_p50_ms "
        f"{stats.percentile(late, 50.0) if late else float('nan'):.3f}")


def hold_guarantees(setup: Dict[str, Any], win: Dict[str, Any],
                    verdict: Dict[str, Any]) -> None:
    """The ingest's guarantees join the numbers compared; a breach is a
    failed run that names it."""
    from benchmarks.lib import hybrid

    compared = verdict["compared"]
    last = compared.pop("responses_compared")
    compared.update(win["guarantees"], responses_compared=last)
    broken = hybrid.breached(win["guarantees"])
    if broken:
        for name, c in compared.items():
            print(f"bench: compared {name} = {c['value']} (limit "
                  f"{c['limit']})", file=sys.stderr)
        raise RunFailed("ingest guarantee broken: " + "; ".join(broken))


def serve_peak() -> int:
    from benchmarks.lib import serve

    return serve.memory_peak_bytes()


def warm(served, setup: Dict[str, Any]) -> Dict[str, float]:
    """Every shape the window will use: the whole cycle once with one
    client, bursts of same-flight variants at every group size, then the
    cell's own schedule until a slice of it compiles nothing new. Returns
    what each of the three took."""
    traffic, cycle, work = setup["traffic"], setup["cycle"], setup["work"]
    sqls = [q["sql"] for q in cycle]
    clients = traffic["clients"]
    t0 = time.perf_counter()

    def script(plans: List[List[int]], tag: str) -> None:
        out = Child("client.py", client_job(
            served, sqls, runner="script", clients=len(plans), plans=plans,
            lockstep=len(plans) > 1), work, tag).join(1800.0)
        bad = [r for r in out["records"] if r["status"] != 200]
        if bad:
            raise RunFailed(f"warm-up {tag}: {len(bad)} requests failed, "
                            f"first {bad[0]['error'] or bad[0]['body'][:300]}")

    script([walk_order(cycle)], "warm_walk")
    t1 = time.perf_counter()
    log(f"device peak after the walk: {serve_peak()} bytes")
    for attempt in range(3 if clients > 1 else 0):
        before = served.counters()
        script(burst_plans(cycle, clients), f"warm_burst_{attempt}")
        if not served.compiled_between(before, served.counters()):
            break
    t2 = time.perf_counter()
    log(f"device peak after the bursts: {serve_peak()} bytes")
    for attempt in range(5):
        before = served.counters()
        Child("client.py",
              own_job(served, cycle, sqls, traffic, WARM_OWN_SECONDS),
              work, f"warm_own_{attempt}").join(WARM_OWN_SECONDS + 600.0)
        added = served.compiled_between(before, served.counters())
        if not added:
            break
        log(f"warm-up slice {attempt} compiled {len(added)} programs; again")
    log(f"device peak after the cell's own slices: {serve_peak()} bytes")
    return {"warm_walk_s": t1 - t0, "warm_burst_s": t2 - t1,
            "warm_own_s": time.perf_counter() - t2}


def measure(setup: Dict[str, Any], seconds: float, trace: bool, tag: str,
            platform: str, strict: bool = True) -> str:
    """One window, judged and reduced to its result line."""
    from benchmarks.lib import serve

    served = setup["served"]
    hybrid_table = bool(setup["config"].get("realtime"))
    with phase("window"):
        win = run_window(served, setup, seconds, trace, tag)
        if hybrid_table:
            ingest_window(setup, win, seconds)
    if "setup_s" not in setup:      # process start to the first request
        setup["setup_s"] = win["t0_wall"] - _T0
        log("set-up " + " ".join(f"{k}={v:.1f}" for k, v in
                                 setup["phases"].items())
            + f" oracle_strings={len(setup['cycle'])}"
            + f" setup_s={setup['setup_s']:.1f}")
    peak_bytes = serve.memory_peak_bytes()
    with phase("compare"):
        verdict = judge(served, setup, win, platform)
        unsound = []
        if hybrid_table:
            hold_guarantees(setup, win, verdict)
        if verdict["compiled_in_window"] and hybrid_table:
            # segments born in the window bring new shapes: counted
            log(f"{len(verdict['compiled_in_window'])} programs were "
                f"compiled inside the window (compiled_in_window): "
                f"{verdict['compiled_in_window'][:6]}")
        elif verdict["compiled_in_window"]:
            unsound.append(
                f"{len(verdict['compiled_in_window'])} programs were "
                f"compiled inside the measured window: a shape was not "
                f"warm, the run is no measurement "
                f"({verdict['compiled_in_window'][:6]})")
        if verdict["single_flight_hits"]:
            unsound.append(
                f"{verdict['single_flight_hits']} requests were merged with "
                f"a twin in flight (the broker's single-flight): the window "
                f"held less work than its schedule, the run is no "
                f"measurement")
        for msg in unsound:
            if strict:
                raise RunFailed(msg)
            log(msg)
    with phase("reduce"):
        return result_line(setup, win, verdict, peak_bytes, trace, platform)


def result_line(setup: Dict[str, Any], win: Dict[str, Any],
                verdict: Dict[str, Any], peak_bytes: int, trace: bool,
                platform: str) -> str:
    records = ok_records(win, setup["cycle"])
    late = [r["late_ms"] for r in win["records"] if "late_ms" in r]
    if late:
        log(f"generator lateness ms: median "
            f"{stats.percentile(late, 50.0):.2f} max {max(late):.2f}")
    units = {m["name"]: m for m in setup["end_to_end"] + setup["per_layer"]}
    device = dict(setup["device"], memory_peak_bytes=peak_bytes)
    breakdown = None
    if trace:
        layer = per_layer(win, records, setup,
                          [m["name"] for m in setup["per_layer"]])
        values = layer["values"]
        if layer["device"]:
            device.update(busy_s=layer["device"]["busy_s"],
                          window_s=layer["device"]["window_s"])
            breakdown = {"device_ops": layer["device"]["device_ops"],
                         "idle_gaps": layer["device"]["idle_gaps"]}
    else:
        values = end_to_end(win, records, setup, peak_bytes)
    measured = platform == "tpu"
    if not measured:
        device["memory_peak_bytes"] = NOT_MEASURED
    metrics = {name: {"value": (value if measured or units[name]["source"]
                                in COUNT_SOURCES else NOT_MEASURED),
                      "unit": units[name]["unit"]}
               for name, value in values.items() if name in units}
    for name, c in verdict["compared"].items():
        print(f"bench: compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"bench: correct = {verdict['correct']}", file=sys.stderr,
          flush=True)
    line: Dict[str, Any] = {
        "correct": verdict["correct"], "attempted": verdict["attempted"],
        "failed": verdict["failed"], "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    if setup["config"].get("realtime"):
        line["compiled_in_window"] = len(verdict["compiled_in_window"])
    line["compared"] = verdict["compared"]      # last, as the contract asks
    return json.dumps(line)


def control_reading(setup: Dict[str, Any]) -> Dict[str, Any]:
    """The control, judged as a run is: the reference's answers summed in
    float32 put in the program's place (a hybrid table's: at the newest
    prefix each response of the last window could have read)."""
    if setup["config"].get("realtime"):
        from benchmarks.lib import hybrid

        return hybrid.control_window(
            setup["last_records"], setup["cycle"], setup["want"],
            setup["control"], setup["stream"], setup["pins"],
            setup["config"], setup["table_mod"])
    records = [{"index": q["id"], "status": 200, "body": json.dumps({
        "exceptions": [], "numServersQueried": 1, "numServersResponded": 1,
        "partialResult": False,
        "resultTable": {"rows": setup["control"][str(q["id"])]}})}
        for q in setup["cycle"]]
    return compare.compare(records, setup["cycle"], setup["want"])


def run(workload: str, seed: int, seconds: float, trace: bool,
        expect_platform: str = "tpu", rows: Optional[int] = None,
        data_root: Optional[str] = None, windows: int = 1,
        control: bool = False, keep_trace: Optional[str] = None,
        strict: Optional[bool] = None) -> List[str]:
    """Set up once, measure ``windows`` windows, return their result lines
    (the last is the run's). A window that compiled something fails the
    run; a noise study of several windows only says so and goes on."""
    strict = windows == 1 if strict is None else strict
    data_root = data_root or os.path.join(HERE, ".data")
    setup = set_up(workload, seed, expect_platform, rows, data_root, control,
                   seconds, windows)
    lines = []
    try:
        for i in range(windows):
            lines.append(measure(setup, seconds, trace, f"window_{i}",
                                 expect_platform, strict))
            if windows > 1:
                log(f"window {i}: {lines[-1]}")
        if control:
            reading = control_reading(setup)
            log(f"control (float32 sums): {json.dumps(reading)}")
            print("CONTROL " + json.dumps(
                {"workload": workload, "seed": seed, **reading}), flush=True)
        if keep_trace and setup.get("trace_layout"):
            os.makedirs(keep_trace, exist_ok=True)
            with open(os.path.join(keep_trace, "layout.txt"), "w") as f:
                f.write("\n".join(setup["trace_layout"]))
            for path in glob.glob(os.path.join(
                    setup["work"], "profile", "plugins", "profile", "*",
                    "*.xplane.pb")):
                shutil.copy(path, keep_trace)
    finally:
        setup["served"].close()
        shutil.rmtree(os.path.join(setup["work"], "profile"),
                      ignore_errors=True)
        if setup["producer_child"] is not None:
            try:
                setup["producer"].stop()
                setup["producer_child"].join(60.0)
            finally:
                setup["producer_child"].kill()
    return lines


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the builder's own, never passed by the driver
    ap.add_argument("--windows", type=int, default=1,
                    help="noise study: windows back to back after one "
                         "set-up, each printed")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge the float32 control (CONTROL line)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb here")
    args = ap.parse_args(argv)
    # the contract's bare directory (BENCHMARK.json and benchmarks/ alone)
    # exits non-zero and prints no line; without this the program's
    # ImportError would print the failed_run line below
    if importlib.util.find_spec("pinot_tpu") is None:
        raise SystemExit("bench: the program (pinot_tpu) is not in this "
                         "checkout; nothing to measure")
    try:
        lines = run(args.workload, args.seed, args.seconds, bool(args.trace),
                    windows=args.windows, control=bool(args.control),
                    keep_trace=args.keep_trace)
    except Exception as e:      # not SystemExit: no chip prints no line
        if not isinstance(e, RunFailed):
            traceback.print_exc()
        print(f"bench: run failed: {e}", file=sys.stderr, flush=True)
        print(failed_line(e), flush=True)
        return 1
    print(lines[-1], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
