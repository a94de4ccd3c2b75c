"""chip_smoke.py — the quickest proof that pinot-tpu still starts on the chip.

One process drives the main path once, through the entry points a user
calls: SSB flat ``lineorder`` (SF4 = 24,000,000 rows, 8 segments, the full
flat schema, the default five-tree config) built from ``--seed``, loaded
into an ``EmbeddedCluster`` with one server, and queried over HTTP through
``pinot_tpu.client`` — client -> REST -> broker compile/route/scatter ->
server admission/scheduler -> device -> gather -> reduce -> response.
Every answer is compared with the independent pandas oracle
(``tools/ssb_baseline``), and the program's own records (decision ledger,
residency snapshot, launch counters, span trees, device memory stats) must
show that the device served — no quiet fallback finishes green.

The phases are plain functions taking the platform they expect, so a
tier-1 test drives them at toy size on the forced CPU mesh. ``main``
always expects ``tpu``; nothing on the command line or in the environment
changes that, and the script never sets ``JAX_PLATFORMS``.

Output: one JSON document (``chiprun_out/chip_smoke.json``, also printed)
and, as the LAST line of stdout, ``{"ok": true, "device": {...}}``. Any
failed phase raises: non-zero exit, no result line.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import time
import urllib.request

from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))

FULL_ROWS = 24_000_000        # SF4, the scale of the benchmark's one-chip cells
MIN_ROWS = 6_000_000          # SF1: never cut below
NUM_SEGMENTS = 8
WARM_RUNS = 3
TABLE = "ssb_lineorder_OFFLINE"

# (b) flights forced off the star-trees: scalar scan, dense group-by, and
# the two whose key space needs the group-range probe
FORCED_SCAN = ("Q1.1", "Q2.1", "Q3.2", "Q4.3")
# (c) ordered selection: the selected columns ARE the sort keys, so rows
# that tie are identical and the answer is one exact list
SELECTION_SQL = (
    "SELECT lo_revenue, lo_supplycost FROM ssb_lineorder "
    "WHERE s_region = 'ASIA' AND d_year = 1997 "
    "ORDER BY lo_revenue DESC, lo_supplycost LIMIT 100")
# (d) burst: forced-scan Q1.1 with eight different literals. The literal
# that varies is lo_quantity, not d_year: SSB has seven years, and a year
# literal prunes to a different segment set — a different batch, so
# the eight would not be one kernel's group at the dispatcher
BURST_QUANTITIES = tuple(range(18, 26))
BURST_SQL = (
    "SELECT sum(lo_extendedprice * lo_discount) FROM ssb_lineorder "
    "WHERE d_year = 1993 AND lo_discount BETWEEN 1 AND 3 "
    "AND lo_quantity < {q}")
SCAN = ("useStarTree=false",)

# ledger reasons that mean "the device did not serve": never on this path
FORBIDDEN_REASONS = frozenset((
    "cpu_default_backend", "pallas_disabled_on_backend",
    "pallas_exec_failed", "pallas_build_failed", "pallas_shape_blocked"))
# ... of which a CPU run (the tier-1 toy drive) legitimately records these
CPU_BACKEND_REASONS = frozenset((
    "cpu_default_backend", "pallas_disabled_on_backend"))

_T0 = time.time()


class SmokeFailure(RuntimeError):
    """A phase found the system not doing what the smoke requires."""


def log(msg: str) -> None:
    print(f"smoke[{time.time() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def require(ok: Any, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def device_phase(expect_platform: str) -> Dict[str, Any]:
    """Initialise the backend; anything but the expected platform ends the
    run at this line."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"jax {jax.__version__} devices {device}")
    require(device["platform"] == expect_platform,
            f"expected platform {expect_platform!r}, JAX found {device}")
    from pinot_tpu.engine import ensure_compile_cache, ensure_x64

    ensure_x64()
    ensure_compile_cache()
    return device


def cache_dir() -> str:
    import jax

    return jax.config.jax_compilation_cache_dir


def cache_entries() -> int:
    """Compiled programs in the persistent cache (access-time stamps JAX
    keeps beside them are not entries)."""
    try:
        return sum(1 for n in os.listdir(cache_dir())
                   if not n.endswith("-atime"))
    except FileNotFoundError:
        return 0


def native_phase() -> Dict[str, Any]:
    """The C++ host runtime must be in use, built from the tracked source
    (a spawned segment builder that found no library would race this
    process to compile it)."""
    from pinot_tpu import native

    started = time.time()
    # load() compiles native/pinot_native.cpp when the library is missing
    # or older than it, and never loads a library without that source
    require(native.available(),
            "native library unavailable: g++ failed or "
            "native/pinot_native.cpp is missing (see the warning above)")
    lib = os.path.join("native", "build", "libpinot_native.so")
    info = {"library": lib,
            "built_in_this_run":
                os.path.getmtime(os.path.join(ROOT, lib)) >= started - 1.0}
    log(f"native ok {info}")
    return info


def data_phase(data_dir: str, rows: int, num_segments: int,
               seed: int) -> Tuple[List[str], float]:
    """SSB segments under a fixed name in ``data_dir`` (a manifest keyed on
    what determines the bytes lets a second run in the same directory load
    instead of rebuild). Builders run in a spawn pool; they are numpy-only
    and initialise no JAX backend, so the chip stays with this process."""
    from pinot_tpu.tools import ssb

    out = os.path.join(data_dir, f"ssb_seed{seed}_rows{rows}_x{num_segments}")
    manifest = os.path.join(out, "manifest.json")
    want = {"rows": rows, "segments": num_segments, "seed": seed,
            "treeConfig": "v2-multitree"}
    dirs = [os.path.join(out, f"ssb_{i}") for i in range(num_segments)]
    try:
        with open(manifest) as f:
            have = json.load(f)
    except (FileNotFoundError, ValueError):
        have = None
    if have == want:
        log(f"data: {num_segments} prebuilt segments in {out}")
        return dirs, 0.0
    os.makedirs(out, exist_ok=True)
    log(f"data: building {rows} rows x {num_segments} segments "
        f"({os.cpu_count()} cpus)")
    t0 = time.perf_counter()
    ssb.build_segments(0, out, num_segments=num_segments, rows=rows,
                       seed=seed)
    build_s = time.perf_counter() - t0
    with open(manifest, "w") as f:
        json.dump(want, f)
    log(f"data: built in {build_s:.1f}s")
    return dirs, build_s


def with_options(sql: str, options: Tuple[str, ...]) -> str:
    return f"{sql} OPTION({', '.join(options)})" if options else sql


def workload() -> List[Tuple[str, str, str, Tuple[str, ...]]]:
    """(name, kind, sql, query options) in run order; ``kind`` is what
    must serve it."""
    from pinot_tpu.tools import ssb

    limit = " LIMIT 100000"
    out = [(qid, "startree", sql + limit, ())
           for qid, sql in ssb.QUERIES.items()]
    out += [(f"{qid}/scan", "scan", ssb.QUERIES[qid] + limit, SCAN)
            for qid in FORCED_SCAN]
    out.append(("selection", "selection", SELECTION_SQL, ()))
    return out


def oracle_phase(rows: int, num_segments: int,
                 seed: int) -> Dict[str, List[tuple]]:
    """Every expected answer from pandas over the generated table — the
    same rows the segments index, none of the engine's code. The table is
    dictionary-encoded one segment frame at a time: SF4 as raw strings is
    10 GB, twice that while it concatenates."""
    import numpy as np
    import pandas as pd

    from pandas.api.types import union_categoricals

    from pinot_tpu.tools import ssb, ssb_baseline

    parts = [ssb_baseline.make_frame(frame) for frame in
             ssb.generate_segment_frames(num_segments, rows, seed)]
    df = pd.DataFrame({
        name: (union_categoricals([p[name] for p in parts])
               if isinstance(parts[0][name].dtype, pd.CategoricalDtype)
               else np.concatenate([p[name].to_numpy() for p in parts]))
        for name in parts[0].columns})
    del parts
    cols = {name: df[name].to_numpy() for name in
            ("lo_extendedprice", "lo_discount", "lo_quantity", "d_year")}
    want: Dict[str, List[tuple]] = {}
    for qid in ssb.QUERIES:
        want[qid] = ssb_baseline.run_query(df, qid)
    for qid in FORCED_SCAN:
        want[f"{qid}/scan"] = want[qid]
    m = ((df.s_region == "ASIA") & (df.d_year == 1997)).to_numpy()
    top = (df.loc[m, ["lo_revenue", "lo_supplycost"]]
           .sort_values(["lo_revenue", "lo_supplycost"],
                        ascending=[False, True], kind="stable").head(100))
    want["selection"] = [(int(a), int(b)) for a, b in
                         top.itertuples(index=False)]
    price = cols["lo_extendedprice"] * cols["lo_discount"]
    base = ((cols["d_year"] == 1993) & (cols["lo_discount"] >= 1)
            & (cols["lo_discount"] <= 3))
    for q in BURST_QUANTITIES:
        want[f"burst/{q}"] = [(float(np.sum(
            price[base & (cols["lo_quantity"] < q)])),)]
    return want


class Served:
    """The cluster as a user reaches it: REST endpoints on loopback and a
    client connection, plus the one server's admin API for the debug
    endpoints."""

    def __init__(self, data_dir: str, seg_dirs: List[str]):
        from pinot_tpu import client
        from pinot_tpu.spi.table import TableConfig, TableType
        from pinot_tpu.tools import ssb
        from pinot_tpu.tools.cluster import EmbeddedCluster
        from pinot_tpu.transport import rest

        self.cluster = EmbeddedCluster(
            num_servers=1, data_dir=os.path.join(data_dir, "cluster"),
            query_timeout_s=900.0)
        self.apis: List[Any] = []
        try:
            self.cluster.create_table(
                TableConfig("ssb_lineorder", TableType.OFFLINE,
                            indexing_config=ssb.ssb_indexing_config()),
                ssb.ssb_schema())
            for d in seg_dirs:
                self.cluster.upload_segment_dir(TABLE, d)
            require(self.cluster.wait_for_ev_converged(TABLE,
                                                       timeout_s=600.0),
                    "external view did not converge on the ideal state")
            self.server = next(iter(self.cluster.servers.values()))
            self.apis = list(rest.serve_cluster(self.cluster))
            admin = rest.ServerAdminApi(self.server)
            admin.start()
            self.apis.append(admin)
        except BaseException:
            self.close()
            raise
        self.admin_url = f"http://127.0.0.1:{admin.port}"
        self.conn = client.connect(
            [f"127.0.0.1:{self.apis[1].port}"], timeout_s=900.0)

    def query(self, sql: str):
        """One request over HTTP; the response must stand on every server
        it was scattered to."""
        group = self.conn.execute(sql)     # raises on query exceptions
        raw = group.raw
        require(raw["numServersResponded"] == raw["numServersQueried"]
                and not raw["partialResult"],
                f"partial response for {sql!r}: {group.stats}")
        return group

    def debug(self, path: str) -> Dict[str, Any]:
        with urllib.request.urlopen(self.admin_url + path, timeout=60) as r:
            return json.loads(r.read().decode("utf-8"))

    def close(self) -> None:
        for api in self.apis:
            api.stop()
        self.cluster.shutdown()


def check_rows(name: str, got: List[list], want: List[tuple]) -> None:
    """Integer aggregates exactly; a float aggregate to rel 1e-6 (f64 is
    emulated on the chip). Grouped rows match by key, order-free (ORDER BY
    ties may legally differ); selection rows match as one exact list."""
    from pinot_tpu.tools import ssb_baseline

    if name == "selection":
        ok = [tuple(r) for r in got] == want
    else:
        integral = all(float(r[-1]).is_integer() for r in want)
        ok = ssb_baseline.rows_match(got, want,
                                     rel=0.0 if integral else 1e-6)
    require(ok, f"{name}: {len(got)} rows differ from the oracle's "
                f"{len(want)}; first got {got[:2]} want {want[:2]}")


def _spans(node: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    found = [node] if node.get("name") == name else []
    for child in node.get("children", ()):
        found += _spans(child, name)
    return found


def served_by(group, kind: str) -> Dict[str, Any]:
    """What served a traced query, read off its span tree: the rung of
    every per-segment span, or the sharded combine's kernel and mesh."""
    root = group.raw["traceInfo"]["spans"][0]
    combine = _spans(root, "ShardedCombine")
    if combine:
        return {"rung": "sharded_combine",
                "kernel": "/".join(sorted({s["kernel"] for s in combine})),
                "mesh": "/".join(sorted({s["mesh"] for s in combine}))}
    segs = _spans(root, "SegmentGroupBy") + _spans(root, "SegmentAggregate")
    if segs:
        return {"rung": "/".join(sorted({str(s.get("path"))
                                         for s in segs})),
                "kernel": "jnp"}
    return {"rung": kind, "kernel": "jnp"}


def query_phase(served: Served, want: Dict[str, List[tuple]],
                expect_platform: str, device_count: int
                ) -> Dict[str, Dict[str, Any]]:
    """Each query once cold, WARM_RUNS times warm, once traced; every
    response checked against the oracle."""
    kernel = "pallas" if expect_platform == "tpu" else "jnp"
    report: Dict[str, Dict[str, Any]] = {}
    for name, kind, sql, options in workload():
        ms = []
        for _ in range(1 + WARM_RUNS):
            t0 = time.perf_counter()
            group = served.query(with_options(sql, options))
            ms.append((time.perf_counter() - t0) * 1e3)
            check_rows(name, group.result_set.rows, want[name])
        traced = served.query(with_options(sql, options + ("trace=true",)))
        check_rows(name, traced.result_set.rows, want[name])
        rec = served_by(traced, kind)
        rec.update(cold_ms=round(ms[0], 1),
                   warm_ms=[round(v, 1) for v in ms[1:]])
        report[name] = rec
        log(f"{name}: {rec}")
        if kind == "startree":
            require(rec["rung"] == "startree_device",
                    f"{name} served by {rec['rung']}, not startree_device")
        elif kind == "scan":
            require(rec.get("kernel") == kernel
                    and rec.get("mesh") == f"{device_count}x1",
                    f"{name}: sharded combine expected kernel={kernel} "
                    f"mesh={device_count}x1, span says {rec}")
    return report


def burst_conservation(before: Dict[str, Any], after: Dict[str, Any],
                       sent: int) -> Dict[str, int]:
    """One burst round on ``/debug/launches``: every request sent passed
    the dispatcher once, and each was launched or shared the launch of a
    rider with the same device params."""
    delta = {k: after[k] - before[k]
             for k in ("requests", "launches", "launchesSaved")}
    require(delta["requests"] == sent,
            f"{sent} burst requests sent, the dispatcher counted {delta}")
    require(delta["launches"] + delta["launchesSaved"] == delta["requests"],
            f"launches + launchesSaved != requests over a burst: {delta}")
    return delta


def burst_phase(served: Served, want: Dict[str, List[tuple]]
                ) -> Dict[str, Any]:
    """Eight concurrent same-shape requests with different literals: every
    answer against the oracle, and the dispatcher's counters conserved
    over each round (how many of them meet one drain is the host's arrival
    timing, and nothing here waits for it)."""
    sqls = {q: with_options(BURST_SQL.format(q=q), SCAN)
            for q in BURST_QUANTITIES}
    rounds = []
    with concurrent.futures.ThreadPoolExecutor(len(sqls)) as pool:
        for _ in range(1 + WARM_RUNS):
            before = served.debug("/debug/launches")
            t0 = time.perf_counter()
            futures = {q: pool.submit(served.query, sql)
                       for q, sql in sqls.items()}
            for q, fut in futures.items():
                check_rows(f"burst/{q}", fut.result().result_set.rows,
                           want[f"burst/{q}"])
            rounds.append(round((time.perf_counter() - t0) * 1e3, 1))
            after = served.debug("/debug/launches")
            delta = burst_conservation(before, after, len(sqls))
    rec = {"rounds_ms": rounds, "last_round": delta, "launches": after}
    log(f"burst: {rec}")
    return rec


def placement(executor) -> Dict[str, Any]:
    """Bytes of the sharded batch's device arrays per device, read through
    ``addressable_shards``."""
    import jax

    per_device: Dict[str, int] = {}
    with executor._device_cols_lock:
        staged = list(executor._device_cols.values())
    for leaf in jax.tree_util.tree_leaves(staged):
        for shard in getattr(leaf, "addressable_shards", ()):
            key = str(shard.device.id)
            per_device[key] = per_device.get(key, 0) + shard.data.nbytes
    return per_device


def evidence_phase(served: Served, expect_platform: str, device_count: int,
                   ledger_mark: Dict[str, int]) -> Dict[str, Any]:
    """The program's own records must say the device served
    (``ledger_mark``: the process ledger before the cluster came up)."""
    import jax

    from pinot_tpu.common.tracing import LEDGER, parse_decision_key
    from pinot_tpu.tools import preflight

    executor = served.server.executor
    ledger = LEDGER.delta(ledger_mark)
    forbidden = set(FORBIDDEN_REASONS)
    if expect_platform == "cpu":
        forbidden -= CPU_BACKEND_REASONS
    bad = []
    for key in ledger:
        point, chosen, _declined, reason = parse_decision_key(key)
        if (reason in forbidden or reason.startswith("pallas_preflight_")
                or point == "launch" or chosen == "host_engine"):
            bad.append(key)
    require(not bad, f"the device did not serve everything: {bad} "
                     f"(ledger: {ledger})")
    declines = {k: n for k, n in ledger.items()
                if parse_decision_key(k)[0] == "pallas"}

    memory = served.debug("/debug/memory")
    require(memory["counters"]["spills"] == 0,
            f"queries spilled to the host: {memory['counters']}")
    if expect_platform != "cpu":
        require(isinstance(memory["budgetBytes"], int),
                f"HBM budget unresolved: {memory['budgetBytes']!r}")

    specs = [k[-2] if k[0] == "probe" else k[0]
             for k in executor._pallas_sharded]
    if expect_platform == "tpu":
        require(specs, "no sharded Pallas kernel was compiled")
    model = (preflight.model_for(jax.devices()[0].device_kind)
             if expect_platform == "tpu" else preflight.TPU_V5E)
    verdicts = [preflight.preflight_spec(s, model, shape=f"compiled{i}",
                                         source="ssb").row()
                for i, s in enumerate(specs)]

    per_device = placement(executor)
    total = sum(per_device.values())
    require(len(per_device) == device_count
            and (device_count == 1
                 or max(per_device.values()) * 2 <= total),
            f"sharded batch not spread over {device_count} devices: "
            f"{per_device}")
    return {
        "ledger": ledger,
        "pallas_declines": declines,
        "pallas_sharded_kernels": len(specs),
        "preflight_on_compiled": verdicts,
        "budget_bytes": memory["budgetBytes"],
        "residency": {"stagedBytes": memory["stagedBytes"],
                      "peakBytes": memory["peakBytes"],
                      "counters": memory["counters"]},
        "sharded_batch_bytes_per_device": per_device,
        "bytes_in_use_per_device": {
            str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()},
    }


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def run(expect_platform: str, rows: int, num_segments: int, seed: int,
        data_dir: str) -> Dict[str, Any]:
    device = device_phase(expect_platform)
    entries_before = cache_entries()
    native = native_phase()
    with concurrent.futures.ThreadPoolExecutor(1) as side:
        # the oracle is pandas-only: it computes beside the segment build
        # and the cold compiles, in this process
        oracle = side.submit(oracle_phase, rows, num_segments, seed)
        seg_dirs, build_s = data_phase(data_dir, rows, num_segments, seed)
        from pinot_tpu.common.tracing import LEDGER

        ledger_mark = LEDGER.snapshot()
        t0 = time.perf_counter()
        served = Served(data_dir, seg_dirs)
        try:
            load_s = time.perf_counter() - t0
            log(f"cluster up, {num_segments} segments online "
                f"({load_s:.1f}s)")
            want = oracle.result()
            log("oracle ready")
            queries = query_phase(served, want, expect_platform,
                                  device["count"])
            burst = burst_phase(served, want)
            evidence = evidence_phase(served, expect_platform,
                                      device["count"], ledger_mark)
        finally:
            served.close()
    return {
        "device": device,
        "rows": rows,
        "rows_cut_from": FULL_ROWS if rows < FULL_ROWS else None,
        "segments": num_segments,
        "seed": seed,
        "native": native,
        "build_s": round(build_s, 1),
        "cluster_load_s": round(load_s, 1),
        "queries": queries,
        "burst": burst,
        **evidence,
        "compile_cache": {"dir": cache_dir(),
                          "entries_before": entries_before,
                          "entries_after": cache_entries()},
        "wall_s": round(time.time() - _T0, 1),
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42,
                    help="the data (and so every expected answer) is made "
                         "from this")
    ap.add_argument("--rows", type=int, default=FULL_ROWS,
                    help=f"lineorder rows; a cut is printed, and never "
                         f"goes below SF1 ({MIN_ROWS})")
    args = ap.parse_args(argv)
    if args.rows < MIN_ROWS:
        ap.error(f"--rows {args.rows} is below SF1 ({MIN_ROWS})")
    if args.rows < FULL_ROWS:
        log(f"CUT: {args.rows} rows instead of {FULL_ROWS}")
    report = run("tpu", args.rows, NUM_SEGMENTS, args.seed,
                 os.path.join(ROOT, "scratch", "chip_smoke"))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
