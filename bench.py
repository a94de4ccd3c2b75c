"""Benchmark: SSB on the TPU query path vs an external CPU baseline.

One process: it initialises the JAX backend, exits non-zero unless that
backend is a TPU (a CPU time is never recorded under a device metric's
name), builds/loads the SSB table (parallel segment builder,
manifest-keyed reuse), runs the sub-suites there and exits non-zero if
any of them recorded an error. No child process initialises a JAX
backend (the spawned segment builders are numpy-only) — a chip belongs to
one process.

Workloads (BASELINE.json configs):
- **SSB** (headline, config #5): Q1.1-Q4.3 over a multi-segment table via
  the sharded device combine; p50 AND p99 per query; parity-gated against
  the EXTERNAL pandas baseline (pinot_tpu/tools/ssb_baseline.py — the
  vs_baseline denominator; ref harness pair:
  contrib/pinot-druid-benchmark/README.md:1-60, pinot-perf BenchmarkQueryEngine).
- **QPS** (ref: pinot-tools/.../perf/QueryRunner.java): closed-loop
  multi-thread throughput + latency percentiles on three SSB flights.
- **micro** (configs #1/#2): the round-2/3 7-query suite vs the host engine
  (kept ONLY for cross-round continuity; not the headline baseline).
- **star-tree** (config #3) and **sketches** (config #4).
- **cluster**: broker scatter-gather over the full wire path, scaled
  2 -> 8 servers over partition-aligned segments; records per-query
  scatter fan-out + prune ratio and loud-fails if a partition-filtered
  query prunes <=50% of the 8 servers (BENCH_ALLOW_NO_PRUNE escapes).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}:
value = device p50 SSB ms/query, vs_baseline = pandas_baseline / device.
"""

from __future__ import annotations

import json
import os
import re
import sys
import threading
import time
import traceback

import numpy as np

_T_START = time.time()
TIME_BUDGET_S = float(os.environ.get("BENCH_TIME_BUDGET_S", 2100))
# SSB scale on the chip: SF 4
TPU_SSB_ROWS = int(os.environ.get("BENCH_SSB_ROWS", 24_000_000))
NUM_SEGMENTS = int(os.environ.get("BENCH_SSB_SEGMENTS", 8))
WARMUP = 1
ITERS = 5

SUITES = ("ssb", "qps", "micro", "startree", "sketches", "residency",
          "cluster", "reduce", "realtime", "userfacing")


def _log(msg: str) -> None:
    print(f"bench[{time.time() - _T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


# ==========================================================================
# result record
# ==========================================================================

def emit(results: dict, device: dict) -> None:
    ssb = results.get("ssb", {})
    out = {
        "metric": "ssb_suite_p50_latency",
        "value": ssb.get("p50_ms_per_query"),
        "unit": "ms/query",
        "vs_baseline": ssb.get("vs_baseline"),
        "backend": ssb.get("backend", "none"),
        "device": device,
        "baseline_engine": ssb.get("baseline_engine"),
        "suite_backends": {s: results.get(s, {}).get("backend", "missing")
                           for s in SUITES},
        # mesh shape per suite record: >1 on a real multi-chip slice OR
        # the conftest-forced virtual CPU mesh; 1 means every sharded
        # combine psum in that suite was a single-device no-op
        "mesh_devices": {s: results.get(s, {}).get("mesh_devices",
                                                   "missing")
                         for s in SUITES},
    }
    for s in SUITES:
        if s in results:
            out[s] = results[s]
    # per-SSB-query partials: when the full SSB record is missing (the
    # suite failed part-way) the completed queries still ship, with their rungs
    # and pallas kernel counts — the record shows exactly which queries
    # fired pallas before the loss
    partial = {k.split(":", 1)[1]: v for k, v in results.items()
               if k.startswith("ssb:")}
    if partial and ("ssb" not in results or "error" in results.get(
            "ssb", {})):
        out["ssb_partial"] = {
            "queries_completed": sorted(partial),
            "per_query_ms": {q: v.get("p50_ms") for q, v in
                             sorted(partial.items())},
            "rungs": {q: v.get("rung") for q, v in sorted(partial.items())},
            "pallas_kernels": {q: v.get("pallas_kernels") for q, v in
                               sorted(partial.items())},
        }
    out["trajectory"] = trajectory_gate(results)
    print(json.dumps(out), flush=True)


# ==========================================================================
# trajectory gate: this round vs every prior BENCH_r*.json
# ==========================================================================

# suite -> (headline scalar key, higher_is_better): the per-suite number
# the cross-round trajectory is computed over
_TRAJECTORY_KEYS = {
    "ssb": ("p50_ms_per_query", False),
    "qps": ("qps", True),
    "micro": ("p50_ms_per_query", False),
    "startree": ("ms", False),
    "sketches": ("p50_ms_per_query", False),
    "residency": ("sliced_p50_ms_per_query", False),
    "cluster": ("p50_ms_per_query", False),
    # headline = vectorized group-by reduce wall time on the 180k-group
    # merge (the suite's own parity/speedup gates run inside bench_reduce)
    "reduce": ("p50_ms", False),
    # headline = consuming-segment write throughput; freshness/seal gates
    # run inside bench_realtime (finite p99, no unexplained host spills)
    "realtime": ("write_qps", True),
    # headline = 4-thread point-filter QPS; the index-rung SLO gates
    # (selective filters must not scan, declines must be registered)
    # run inside bench_userfacing
    "userfacing": ("qps", True),
}
REGRESSION_X = 1.3


def load_prior_rounds(root: str = None) -> dict:
    """round tag ('r05') -> that round's final bench JSON. Rounds are the
    checked-in ``BENCH_r*.json`` wrappers (the driver stores the worker's
    stdout in ``tail``); a bare result JSON parses too."""
    import glob
    import re as _re

    root = root or os.path.dirname(os.path.abspath(__file__))
    rounds = {}
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        m = _re.search(r"BENCH_(r\d+)\.json$", path)
        if m is None:
            continue
        try:
            with open(path) as f:
                wrapper = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(wrapper, dict) and "metric" in wrapper:
            rounds[m.group(1)] = wrapper
            continue
        for line in reversed(str(wrapper.get("tail", "")).splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "metric" in rec:
                rounds[m.group(1)] = rec
                break
    return rounds


def _comparable(suite: str, cur: dict, prior: dict) -> bool:
    """Cross-round numbers only compare like-for-like: same backend, and
    — where the suite records a scale — the same row count (a 24M-row TPU
    round vs a 3M-row CPU round is not a regression signal)."""
    if cur.get("backend") != prior.get("backend"):
        return False
    if "rows" in cur or "rows" in prior:
        return cur.get("rows") == prior.get("rows")
    return True


def trajectory_gate(results: dict, rounds: dict = None) -> dict:
    """The cross-round delta table nobody was computing: per suite, this
    round's headline scalar vs the best comparable prior round, with a
    LOUD warning on a >1.3x p50 regression (or >1.3x QPS drop).
    ``BENCH_ALLOW_REGRESSION=1`` downgrades the warning to a note (capped
    budgets, tiny hosts). Never throws — a broken history must not cost
    the round its numbers."""
    try:
        rounds = load_prior_rounds() if rounds is None else rounds
    except Exception:
        return {"error": "prior-round load failed"}
    table: dict = {}
    regressions = []
    for suite, (key, higher_better) in _TRAJECTORY_KEYS.items():
        cur = results.get(suite) or {}
        value = cur.get(key)
        if not isinstance(value, (int, float)):
            continue
        best = None
        best_round = None
        for tag, rec in sorted(rounds.items()):
            prior = rec.get(suite) or {}
            pv = prior.get(key)
            if not isinstance(pv, (int, float)) or pv <= 0 \
                    or not _comparable(suite, cur, prior):
                continue
            if best is None or (pv > best if higher_better else pv < best):
                best, best_round = pv, tag
        row = {"current": value, "unit": key}
        if best is not None:
            ratio = (best / value) if higher_better else (value / best)
            row.update(best_prior=best, best_round=best_round,
                       ratio=round(ratio, 3),
                       regressed=bool(value and ratio > REGRESSION_X))
            if row["regressed"]:
                regressions.append(
                    f"{suite}: {key} {value} vs {best} in {best_round} "
                    f"({row['ratio']}x worse)")
        table[suite] = row
    out = {"vs_rounds": sorted(rounds), "suites": table}
    if regressions:
        allowed = bool(os.environ.get("BENCH_ALLOW_REGRESSION"))
        out["regressions"] = regressions
        out["allowed"] = allowed
        banner = ("TRAJECTORY REGRESSION (allowed by "
                  "BENCH_ALLOW_REGRESSION): " if allowed else
                  f"TRAJECTORY REGRESSION (> {REGRESSION_X}x vs best "
                  f"prior round): ")
        for r in regressions:
            _log(banner + r)
    return out


# ==========================================================================
# worker
# ==========================================================================

def _require_tpu() -> dict:
    """Initialise the backend; anything but a TPU ends the run here — a
    time from another backend is not a device number."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"bench: backend is {device}, not a TPU; nothing measured",
              file=sys.stderr, flush=True)
        sys.exit(2)
    return device


def _data_dir() -> str:
    """Fixed data directory (segment reuse is keyed by its manifest):
    BENCH_DATA_DIR, else ``scratch/bench_data`` under the checkout."""
    path = os.environ.get("BENCH_DATA_DIR") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scratch", "bench_data")
    os.makedirs(path, exist_ok=True)
    return path


class _Worker:
    def __init__(self):
        self.device = _require_tpu()
        self.backend = self.device["platform"]
        self.rows = TPU_SSB_ROWS
        self.deadline = _T_START + TIME_BUDGET_S
        self.data_dir = _data_dir()
        self.results: dict = {}
        from pinot_tpu.engine import ServerQueryExecutor
        from pinot_tpu.parallel import ShardedQueryExecutor

        self.dev = ShardedQueryExecutor()
        self.host = ServerQueryExecutor(use_device=False)
        self.ssb_segs = None
        self.build_s = 0.0

    def over(self, need: float = 30.0) -> bool:
        return time.time() + need > self.deadline

    # -- HBM residency accounting (engine/residency.py) ---------------------
    def _staging_mark(self) -> dict:
        return self.dev.residency.stats_snapshot()

    def _staging_delta(self, mark: dict) -> dict:
        """Per-suite staging counters: hit/miss/eviction/spill deltas since
        ``mark``, plus the current/peak staged bytes."""
        now = self.dev.residency.stats_snapshot()
        out = {k: now[k] - mark.get(k, 0)
               for k in ("hits", "misses", "evictions",
                         "pinBlockedEvictions", "spills", "demotions",
                         "promotions", "hostDrops", "slicedQueries")}
        out["stagedBytes"] = now["stagedBytes"]
        out["peakBytes"] = now["peakBytes"]
        out["hostBytes"] = now["hostBytes"]
        out["hostPeakBytes"] = now["hostPeakBytes"]
        return out

    # -- path-decision ledger (common/tracing.py) ---------------------------
    def _decision_mark(self) -> dict:
        from pinot_tpu.common.tracing import LEDGER

        return LEDGER.snapshot()

    def _decision_delta(self, mark: dict) -> dict:
        """Per-suite decline-reason histogram: every point where execution
        declined a faster rung during this suite, keyed
        "point:declined->chosen:reason"."""
        from pinot_tpu.common.tracing import LEDGER

        return LEDGER.delta(mark)

    @staticmethod
    def _validate_decisions(suite: str, decisions: dict) -> None:
        """Every reason in a suite's decision histogram must be registered
        in tracing.reason_registry() (per-tree ``treeN`` picks are the one
        dynamic namespace). The lint `decisions` family proves literal
        reasons statically; this is the runtime mirror that also catches
        reasons built from variables/f-strings. BENCH_ALLOW_UNREGISTERED_
        REASON=1 downgrades the failure to a log line for bring-up runs."""
        from pinot_tpu.common import tracing

        registered = tracing.registered_reason_codes()
        bad = []
        for key in decisions or {}:
            try:
                _point, _chosen, _declined, reason = \
                    tracing.parse_decision_key(key)
            except Exception:
                bad.append(key)
                continue
            if reason not in registered \
                    and not re.fullmatch(r"tree\d+", reason):
                bad.append(key)
        if not bad:
            return
        msg = (f"{suite}: unregistered decision reason(s) in the ledger: "
               f"{sorted(bad)[:8]} — register them in the matching "
               f"tracing reason namespace or fix the recording site")
        if os.environ.get("BENCH_ALLOW_UNREGISTERED_REASON"):
            _log(f"WARNING {msg}")
            return
        raise AssertionError(msg)

    def record(self, suite: str, rec: dict) -> None:
        rec = dict(rec, backend=rec.get("backend", self.backend))
        # device count the sharded combine's mesh spans
        rec.setdefault("mesh_devices", self.device["count"])
        self.results[suite] = rec
        # suites without a per-query p50 log their own headline scalar
        # (star-tree: ms; qps: queries/sec — the r05 log had an empty
        # "recorded qps:" line because neither key existed there;
        # residency: the sliced-combine p50)
        scalar = rec.get("p50_ms_per_query",
                         rec.get("ms", rec.get(
                             "qps", rec.get("sliced_p50_ms_per_query",
                                            rec.get("p50_ms", "")))))
        _log(f"recorded {suite}: {scalar}")

    def run(self) -> None:
        for suite, fn in (("ssb", self.bench_ssb),
                          ("qps", self.bench_qps),
                          ("micro", self.bench_micro),
                          ("startree", self.bench_startree),
                          ("sketches", self.bench_sketches),
                          ("residency", self.bench_residency),
                          ("cluster", self.bench_cluster),
                          ("reduce", self.bench_reduce),
                          ("realtime", self.bench_realtime),
                          ("userfacing", self.bench_userfacing)):
            if self.over(60):
                _log(f"{suite}: budget exhausted, stopping worker")
                break
            try:
                mark = self._staging_mark()
                dmark = self._decision_mark()
                rec = fn()
                rec.setdefault("staging", self._staging_delta(mark))
                # every suite records its decline-reason histogram: the
                # BENCH JSON must EXPLAIN every non-device fallback, not
                # just count it (the "why is pallas_kernels 0" evidence)
                rec.setdefault("decisions", self._decision_delta(dmark))
                # ... and the histogram must parse against the reason
                # registry, whatever suite produced it (the userfacing
                # suite's loud-fail, promoted to all suites)
                self._validate_decisions(suite, rec.get("decisions"))
                self.record(suite, rec)
            except Exception as exc:
                traceback.print_exc(file=sys.stderr)
                self.record(suite, {
                    "error": f"{type(exc).__name__}: {exc}"[:300]})

    def _pallas_kernel_counts(self) -> dict:
        """Fused-kernel counters: compiled sharded-combine programs (incl.
        group-range probes) + the per-segment run_segment kernel cache."""
        return {"sharded": len(self.dev._pallas_sharded),
                "segment": len(self.dev.pallas_kernels),
                "total": (len(self.dev._pallas_sharded)
                          + len(self.dev.pallas_kernels))}

    # -- data ---------------------------------------------------------------
    def segments(self):
        from pinot_tpu.segment import load_segment
        from pinot_tpu.tools import ssb

        if self.ssb_segs is not None:
            return self.ssb_segs
        manifest = os.path.join(self.data_dir, "manifest.json")
        # treeConfig bumps when the default SSB tree set changes shape, so
        # prebuilt segments from an older round rebuild instead of serving
        # stale (fewer/smaller) trees: v2 = the 5-tree all-13-flights set
        want = {"rows": self.rows, "segments": NUM_SEGMENTS,
                "treeConfig": "v2-multitree"}
        have = None
        try:
            with open(manifest) as f:
                have = json.load(f)
        except (FileNotFoundError, ValueError):
            pass
        if have == want:
            _log(f"loading {NUM_SEGMENTS} prebuilt SSB segments")
            self.ssb_segs = [
                load_segment(os.path.join(self.data_dir, f"ssb_{i}"))
                for i in range(NUM_SEGMENTS)]
            self.build_s = 0.0
        else:
            _log(f"building SSB segments ({self.rows} rows, "
                 f"{NUM_SEGMENTS} segments, {os.cpu_count()} cpus)")
            t0 = time.perf_counter()
            self.ssb_segs = ssb.build_segments(
                0, self.data_dir, num_segments=NUM_SEGMENTS, rows=self.rows)
            self.build_s = time.perf_counter() - t0
            with open(manifest, "w") as f:
                json.dump(want, f)
            _log(f"built in {self.build_s:.1f}s")
        return self.ssb_segs

    def baseline_frame(self):
        from pinot_tpu.tools import ssb, ssb_baseline

        return ssb_baseline.make_frame(
            ssb.generate_table(NUM_SEGMENTS, self.rows))

    # -- sub-suites ---------------------------------------------------------
    def bench_ssb(self) -> dict:
        from pinot_tpu.common.tracing import parse_decision_key
        from pinot_tpu.query import compile_query
        from pinot_tpu.tools import ssb, ssb_baseline

        staging_mark = self._staging_mark()
        decision_mark = self._decision_mark()
        segs = self.segments()
        # explicit LIMIT: the engine applies the reference's default
        # group-by LIMIT 10 otherwise, and the baseline computes FULL
        # group sets (the SSB flights' intended result)
        ctxs = {qid: compile_query(q + " LIMIT 100000")
                for qid, q in ssb.QUERIES.items()}

        # plan-space kernel preflight: every flight's extracted spec (+
        # the fuzz grid) through the static lowering model written for
        # this device kind. A prediction only — it seeds no blocklist, so
        # the compiler's own verdict on every shape stays visible. The
        # verdict table rides the round JSON AND /debug/pallas.
        _log("ssb: kernel preflight (plan-space verdicts)")
        from pinot_tpu.tools import preflight as _preflight

        pf = _preflight.serializable_table(_preflight.run_preflight(
            segs, model=_preflight.model_for(self.device["kind"])))
        self.dev.preflight_verdicts = pf
        self.record("preflight", {
            "passed": pf["passed"], "failed": pf["failed"],
            "ssb_failed": pf["ssb_failed"],
            "model": pf["model"], "shapes": pf["shapes"]})

        _log("ssb: pandas baseline (build frame)")
        df = self.baseline_frame()
        base_ms = {}
        parity_fail = []
        rungs = {}
        docs_scanned = {}
        tree_index = {}
        for qid, ctx in ctxs.items():
            _log(f"ssb {qid}: baseline + device compile + parity")
            want = ssb_baseline.run_query(df, qid)
            t0 = time.perf_counter()
            want = ssb_baseline.run_query(df, qid)
            base_ms[qid] = (time.perf_counter() - t0) * 1e3
            got, qstats = self.dev.execute(ctx, segs)   # compiles + warms
            rungs[qid] = _ssb_rung(qstats)
            docs_scanned[qid] = qstats.num_docs_scanned
            tree_index[qid] = qstats.startree_tree_index
            if not ssb_baseline.rows_match(got.rows, want, rel=1e-6):
                parity_fail.append(qid)
        if parity_fail:
            raise AssertionError(f"SSB parity vs pandas failed: "
                                 f"{parity_fail}")
        # the Q3.2/Q3.3 latency story depends on the hash rung (or the
        # narrowed dense rung): a silent regression back to the sort rung
        # must fail the suite LOUDLY, not ship a slow number
        regressed = [q for q in ("Q3.2", "Q3.3")
                     if rungs.get(q) in ("sort", "host")]
        if regressed:
            raise AssertionError(
                f"group-by rung regression: {regressed} fell back to "
                f"{[rungs[q] for q in regressed]} (rungs: {rungs})")
        # with the default multi-tree lineorder config, ALL 13 flights
        # must serve from pre-aggregated node slices on DEVICE — any
        # flight regressing to the scan (or the host walker) silently
        # re-pays the full-table scan this tree set removed. The ledger
        # must also carry ZERO of the two coverage-gap reasons the tree
        # set exists to close. BENCH_ALLOW_SCAN_RUNG=1 opts out (tree-less
        # experiments / capped-memory runs).
        if segs and segs[0].metadata.star_tree_count \
                and not os.environ.get("BENCH_ALLOW_SCAN_RUNG"):
            off_tree = [q for q in ctxs
                        if rungs.get(q) != "startree_device"]
            if off_tree:
                raise AssertionError(
                    f"star-tree rung regression: {off_tree} served by "
                    f"{[rungs[q] for q in off_tree]} instead of "
                    f"startree_device (rungs: {rungs})")
            # docs_scanned per query: the pre-agg rung must stay orders of
            # magnitude under the scan (a tree serving most of its records
            # means the split order no longer matches the flight)
            over = {q: n for q, n in docs_scanned.items()
                    if n >= max(1, self.rows // 10)}
            if over:
                raise AssertionError(
                    f"star-tree docs_scanned regression: {over} vs "
                    f"{self.rows} rows — the sub-scan rung is not sub-scan")
            closed = ("startree_expression_agg_no_pair",
                      "startree_group_off_split_order")
            reopened = [k for k in self._decision_delta(decision_mark)
                        if parse_decision_key(k)[0] == "startree"
                        and parse_decision_key(k)[3] in closed]
            if reopened:
                raise AssertionError(
                    f"star-tree coverage gap reopened: {reopened} — the "
                    "default tree set must fit every SSB flight")

        per_q50, per_q99 = {}, {}
        for qid, ctx in ctxs.items():
            _log(f"ssb {qid}: timing device path")
            samples = []
            for _ in range(WARMUP):
                self.dev.execute(ctx, segs)
            for _ in range(ITERS):
                t0 = time.perf_counter()
                self.dev.execute(ctx, segs)
                samples.append((time.perf_counter() - t0) * 1e3)
            per_q50[qid] = float(np.percentile(samples, 50))
            per_q99[qid] = float(np.percentile(samples, 99))
            # partial record PER QUERY: a mid-suite chip loss still ships
            # every completed query with its rung + pallas kernel counts
            # (exactly which queries fired pallas before the loss)
            self.record(f"ssb:{qid}", {
                "p50_ms": round(per_q50[qid], 3),
                "p99_ms": round(per_q99[qid], 3),
                "rung": rungs.get(qid),
                "docs_scanned": docs_scanned.get(qid),
                "tree_index": tree_index.get(qid),
                "pallas_kernels": self._pallas_kernel_counts(),
            })
        n = len(ctxs)
        dev50 = sum(per_q50.values()) / n
        base50 = sum(base_ms.values()) / n
        staging = self._staging_delta(staging_mark)
        # the SSB working set must be HBM-resident under the default
        # budget: a spill means the headline number silently timed the
        # HOST engine — fail loudly instead of shipping it
        # (BENCH_ALLOW_SPILL=1 opts out for capped-budget experiments)
        if staging["spills"] and not os.environ.get("BENCH_ALLOW_SPILL"):
            raise AssertionError(
                f"SSB spilled {staging['spills']} queries to the host "
                f"engine (budget "
                f"{self.dev.residency.budget_bytes}, peak "
                f"{staging['peakBytes']} B staged); the device number "
                f"would be a lie")
        # every pallas decline during the SSB suite must carry a
        # CLASSIFIED reason code: an "unknown" means a decline path the
        # ledger cannot explain, and the next TPU-fight PR would be
        # aiming blind — fail loudly instead of shipping it
        decisions = self._decision_delta(decision_mark)
        unknown = [k for k in decisions
                   if parse_decision_key(k)[0] == "pallas"
                   and parse_decision_key(k)[3] == "unknown"]
        if unknown:
            raise AssertionError(
                f"SSB pallas declines with unclassified reason codes: "
                f"{unknown} — every decline must be classified "
                f"(decisions: {decisions})")
        # a kernel the compiler or the chip refused must never be
        # absorbed by the jnp fallback: the times below would be the
        # fallback's, under the fused kernel's name
        exec_failed = [k for k in decisions
                       if parse_decision_key(k)[0] == "pallas"
                       and parse_decision_key(k)[3] == "pallas_exec_failed"]
        if exec_failed:
            raise AssertionError(
                f"pallas_exec_failed recorded: {exec_failed} — repair the "
                f"kernel, or make the shape a plan-time eligibility "
                f"decline in extract_plan")
        return {
            "preflight": {"passed": pf["passed"], "failed": pf["failed"],
                          "ssb_failed": pf["ssb_failed"]},
            "decisions": decisions,
            "staging": staging,
            "rows": self.rows,
            "sf": round(self.rows / ssb.ROWS_PER_SF, 3),
            "build_s": round(self.build_s, 1),
            "p50_ms_per_query": round(dev50, 3),
            "p99_ms_per_query": round(sum(per_q99.values()) / n, 3),
            "vs_baseline": round(base50 / dev50, 3),
            "baseline_engine": "pandas-vectorized-categorical",
            "baseline_ms_per_query": round(base50, 2),
            "per_query_ms": {q: round(v, 2) for q, v in per_q50.items()},
            "per_query_p99_ms": {q: round(v, 2) for q, v in per_q99.items()},
            "group_by_rung": rungs,
            "docs_scanned": docs_scanned,
            # which tree served each flight + what each tree cost to build
            # (wall seconds summed across segments; creator-measured)
            "startree_tree_index": tree_index,
            "startree_build_s": _tree_build_times(segs),
            # BOTH pallas counters: the sharded combine kernels (what the
            # serving path fires) AND the per-segment run_segment cache
            # (star-tree-free per-segment flights) — the old record
            # counted only the sharded dict, hiding per-segment firings
            "pallas_kernels": self._pallas_kernel_counts(),
            "parity": "ok",
        }

    def bench_qps(self) -> dict:
        """Closed-loop multi-thread throughput sweep (ref: QueryRunner.java
        multiThreadedQueryRunner: numThreads issuing back-to-back, report
        QPS + latency percentiles). Sweeps 1/2/4/8 client threads so the
        record carries the SCALING story, not one point: ``qps_scaling`` =
        4-thread QPS / 1-thread QPS and ``qps_scaling_8`` = 8-thread /
        1-thread, plus per-level launch-coalescing, adaptive-window,
        kernel single-flight, and admission deltas. Gates (escape:
        BENCH_ALLOW_FLAT_QPS=1 for 1-2 core hosts / capped experiments):
        4-thread scaling >= 1.5x on >=4 cores, 8-thread scaling > 1.5x on
        >=8 cores — the scheduler tier must keep scaling past the old
        gate level, not plateau at it. A final SATURATION level drives
        2x the admission capacity through a deliberately tight gate and
        records that overload degrades to bounded-latency REJECTION
        (p99 < 2x p50 with rejections > 0), not convoy collapse."""
        import concurrent.futures

        from pinot_tpu.engine.errors import QueryRejectedError
        from pinot_tpu.query import compile_query
        from pinot_tpu.tools import ssb

        segs = self.segments()
        qids = ("Q1.1", "Q2.1", "Q3.2")
        ctxs = [compile_query(ssb.QUERIES[q] + " LIMIT 100000")
                for q in qids]
        for ctx in ctxs:
            self.dev.execute(ctx, segs)   # compile/warm
        launcher = getattr(self.dev, "launcher", None)
        admission = getattr(self.dev, "admission", None)
        flight = getattr(self.dev, "_kernel_flight", None)
        qflight = getattr(self.dev, "_query_flight", None)
        seconds = 5.0
        levels = {}
        lock = threading.Lock()

        def run_level(threads: int) -> dict:
            lat: list = []
            rejected = [0]
            stop_at = time.perf_counter() + seconds

            def pump(i: int) -> int:
                done = 0
                while time.perf_counter() < stop_at:
                    ctx = ctxs[(i + done) % len(ctxs)]
                    t0 = time.perf_counter()
                    try:
                        self.dev.execute(ctx, segs)
                    except QueryRejectedError:
                        # typed retriable rejection: back off and retry —
                        # the client half of bounded-latency degradation
                        # (rejected attempts are counted, not folded into
                        # admitted-query latency; the backoff keeps the
                        # retry storm from stealing cpu from admitted
                        # queries)
                        with lock:
                            rejected[0] += 1
                        time.sleep(0.02)
                        continue
                    dt = (time.perf_counter() - t0) * 1e3
                    with lock:
                        lat.append(dt)
                    done += 1
                return done

            mark = launcher.stats_snapshot() if launcher else {}
            adm_mark = admission.stats_snapshot() if admission else {}
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(threads) as pool:
                total = sum(pool.map(pump, range(threads)))
            wall = time.perf_counter() - t0
            arr = np.asarray(lat) if lat else np.asarray([0.0])
            out = {
                "qps": round(total / wall, 2),
                "p50_ms": round(float(np.percentile(arr, 50)), 3),
                "p95_ms": round(float(np.percentile(arr, 95)), 3),
                "p99_ms": round(float(np.percentile(arr, 99)), 3),
                "rejected": rejected[0],
            }
            if launcher:
                now = launcher.stats_snapshot()
                out["launch"] = {
                    k: round(now[k] - mark.get(k, 0), 3)
                    for k in ("requests", "launches", "coalescedLaunches",
                              "launchesSaved", "dedupedRequests",
                              "windowWaits", "windowGathered")}
                out["launch"]["maxBatchSize"] = now["maxBatchSize"]
            if admission:
                now = admission.stats_snapshot()
                out["admission"] = {
                    k: round(now[k] - adm_mark.get(k, 0), 3)
                    for k in ("admitted", "rejected", "rejectedQueueFull",
                              "rejectedWaitExpired")}
            if flight:
                out["kernelFlight"] = flight.snapshot()
            if qflight:
                out["queryFlight"] = qflight.snapshot()
            return out

        for threads in (1, 2, 4, 8):
            _log(f"qps: sweeping {threads} thread(s)")
            levels[str(threads)] = run_level(threads)

        qps1 = levels["1"]["qps"]
        qps4 = levels["4"]["qps"]
        qps8 = levels["8"]["qps"]
        scaling = round(qps4 / qps1, 3) if qps1 else None
        scaling8 = round(qps8 / qps1, 3) if qps1 else None
        cpus = os.cpu_count() or 1
        allow_flat = os.environ.get("BENCH_ALLOW_FLAT_QPS")
        if cpus >= 4 and scaling is not None and scaling < 1.5 \
                and not allow_flat:
            raise AssertionError(
                f"QPS scaling regressed: 4-thread {qps4} vs 1-thread "
                f"{qps1} ({scaling}x < 1.5x on a {cpus}-core "
                f"host) — the launch scheduler is serializing instead of "
                f"coalescing (levels: {levels})")
        # 8-thread gate: the scheduler tier (single-flight + adaptive
        # window + SEWF + admission) must keep scaling PAST the 4-thread
        # gate level — an 8-thread result at/below 1.5x means queueing
        # above the fan-out still dominates
        if cpus >= 8 and scaling8 is not None and scaling8 <= 1.5 \
                and not allow_flat:
            raise AssertionError(
                f"8-thread QPS scaling stuck at the 4-thread gate: "
                f"{qps8} vs {qps1} ({scaling8}x <= 1.5x on a {cpus}-core "
                f"host) — the request tier is convoying (levels: "
                f"{levels})")

        saturation = self._qps_saturation(run_level, admission)

        four = levels["4"]
        return {
            "queries": list(qids),
            "threads": 4,
            "qps": four["qps"],
            "p50_ms": four["p50_ms"],
            "p95_ms": four["p95_ms"],
            "p99_ms": four["p99_ms"],
            "qps_scaling": scaling,
            "qps_scaling_8": scaling8,
            "qps_by_threads": levels,
            "saturation": saturation,
        }

    def _qps_saturation(self, run_level, admission) -> dict:
        """Overload-degradation probe: bound the admission gate to
        ``slots`` concurrent queries + an equal-depth queue, then drive
        4x slots closed-loop clients (>= 2x capacity including the
        queue). Healthy degradation = nonzero REJECTIONS with admitted
        p99 still bounded (< 2x p50) because no query ever waits behind
        more than ``slots`` others; convoy collapse would show p99
        stretching with zero rejections."""
        if admission is None:
            return {"skipped": "no admission gate"}
        snap = admission.snapshot()
        slots = min(8, max(2, (os.cpu_count() or 2) // 2))
        threads = 4 * slots
        _log(f"qps: saturation probe ({threads} threads vs {slots} slots)")
        admission.configure(max_concurrent=slots, max_queue=slots,
                            max_wait_ms=2000)
        try:
            out = run_level(threads)
        finally:
            admission.configure(max_concurrent=snap["maxConcurrent"],
                                max_queue=snap["maxQueue"],
                                max_wait_ms=snap["maxWaitMs"])
        out["threads"] = threads
        out["slots"] = slots
        p50, p99 = out["p50_ms"], out["p99_ms"]
        out["p99_over_p50"] = round(p99 / p50, 2) if p50 else None
        out["bounded"] = bool(p50 and p99 < 2 * p50
                              and out["rejected"] > 0)
        if out["rejected"] == 0 and not os.environ.get(
                "BENCH_ALLOW_FLAT_QPS"):
            # 4x-slots closed-loop clients vs a slots-deep queue MUST
            # produce rejections; zero means the admission gate is not
            # actually bounding — the overload story would be a lie
            raise AssertionError(
                f"saturation probe saw 0 rejections at {threads} threads "
                f"vs {slots} slots — admission gate not engaging ({out})")
        return out

    def bench_micro(self) -> dict:
        from pinot_tpu.query import compile_query

        tmp = os.path.join(self.data_dir, "micro")
        os.makedirs(tmp, exist_ok=True)
        segs = _build_micro(tmp)
        ctxs = [compile_query(q) for q in MICRO_QUERIES]
        for ctx in ctxs:
            drt, _ = self.dev.execute(ctx, segs)
            hrt, _ = self.host.execute(ctx, segs)
            _assert_parity(ctx.sql, drt.rows, hrt.rows)
        # r2/r3 methodology (WARMUP=2/ITERS=7) for the DEVICE number's
        # cross-round comparability; the host engine is ~200x slower, so
        # its denominator gets 2 passes (r5: 9 host passes burned ~4 min
        # of the bench budget for a ratio that matched to 3 digits)
        dev_p50, _ = _time_suite(lambda c: self.dev.execute(c, segs),
                                 ctxs, iters=7, warmup=2)
        host_p50, _ = _time_suite(lambda c: self.host.execute(c, segs),
                                  ctxs, iters=2, warmup=0)
        return {"p50_ms_per_query": round(dev_p50 / len(ctxs) * 1e3, 3),
                "vs_host_engine": round(host_p50 / dev_p50, 3)}

    def bench_startree(self) -> dict:
        from pinot_tpu.query import compile_query

        tmp = os.path.join(self.data_dir, "startree")
        os.makedirs(tmp, exist_ok=True)
        segs = _build_startree(tmp)
        self._st_segs = segs
        st_ctx = compile_query(STARTREE_QUERY)
        st_rt, st_stats = self.dev.execute(st_ctx, segs)
        scan_ctx = compile_query(STARTREE_QUERY
                                 + " OPTION(useStarTree=false)")
        scan_rt, _ = self.dev.execute(scan_ctx, segs)
        _assert_parity("startree", st_rt.rows, scan_rt.rows)
        st_p50, _ = _time_suite(lambda c: self.dev.execute(c, segs),
                                [st_ctx])
        scan_p50, _ = _time_suite(lambda c: self.dev.execute(c, segs),
                                  [scan_ctx])
        out = {"ms": round(st_p50 * 1e3, 3),
               "scan_ms": round(scan_p50 * 1e3, 3),
               "group_by_rung": st_stats.group_by_rung,
               "docs_scanned": st_stats.num_docs_scanned}
        # tentpole (c) measurement: the default SSB tree set built by the
        # lexsort engine at scale (BENCH_TREEBUILD_ROWS, e.g. 24_000_000)
        # — per-tree wall seconds + record counts in the round JSON
        scale_rows = int(os.environ.get("BENCH_TREEBUILD_ROWS", "0") or 0)
        if scale_rows:
            out["build_at_scale"] = _tree_build_at_scale(scale_rows)
        return out

    def bench_sketches(self) -> dict:
        from pinot_tpu.query import compile_query

        segs = getattr(self, "_st_segs", None)
        if segs is None:
            tmp = os.path.join(self.data_dir, "sketches")
            os.makedirs(tmp, exist_ok=True)
            segs = _build_startree(tmp)
        ctxs = [compile_query(q) for q in SKETCH_QUERIES]
        for ctx in ctxs:
            self.dev.execute(ctx, segs)
        p50, _ = _time_suite(lambda c: self.dev.execute(c, segs), ctxs,
                             iters=3)
        return {"p50_ms_per_query": round(p50 / len(ctxs) * 1e3, 3)}

    def bench_residency(self) -> dict:
        """Tiered residency under memory pressure: pin the HBM budget to
        ~1/4 of the measured working set of three non-star-tree SSB
        flights and serve them via the budget-sliced sharded combine,
        against two baselines:

        - **host-spill**: the SAME budget with slicing + the host tier
          disabled (the pre-tier fit-or-fail behavior) — the over-budget
          queries fall to the host engine;
        - **restage vs rebuild**: one segment staged cold (full column
          build) vs re-staged from a host-tier image (plain H2D).

        Records sliced-vs-spill p50s, restage/rebuild stage latency, and
        the promoted/demoted/dropped byte counters. Fails LOUDLY if an
        over-budget query spilled to the host engine while the host tier
        + slicing could have served it (BENCH_ALLOW_TIER_SPILL=1 escape
        hatch for hosts whose segments individually exceed the budget)."""
        from pinot_tpu.parallel import ShardedQueryExecutor
        from pinot_tpu.query import compile_query
        from pinot_tpu.spi.config import (
            CommonConstants,
            PinotConfiguration,
        )
        from pinot_tpu.tools import ssb

        segs = self.segments()
        qids = ("Q1.1", "Q3.2", "Q4.2")
        # useStarTree=false: since the multi-tree default covers ALL 13
        # flights, the residency suite must opt out explicitly — it
        # exercises the budget-sliced sharded combine over forward
        # columns, not the per-segment node-slice path
        ctxs = [compile_query(ssb.QUERIES[q]
                              + " LIMIT 100000 OPTION(useStarTree=false)")
                for q in qids]

        # 1) working set of THIS query set, measured uncapped
        probe = ShardedQueryExecutor()
        oracle_rows = []
        for ctx in ctxs:
            rt, _ = probe.execute(ctx, segs)
            oracle_rows.append(rt.rows)
        ws = probe.residency.staged_bytes()
        probe.residency.clear()
        probe.close()
        budget = max(1, ws // 4)

        # 2) sliced-combine serving at budget = ws/4
        capped = ShardedQueryExecutor(hbm_budget_bytes=budget)
        parity_fail = []
        for qid, ctx, want in zip(qids, ctxs, oracle_rows):
            rt, _ = capped.execute(ctx, segs)
            if rt.rows != want:
                parity_fail.append(qid)
        if parity_fail:
            raise AssertionError(
                f"sliced combine diverged from the uncapped oracle: "
                f"{parity_fail}")
        sliced_p50, _ = _time_suite(
            lambda c: capped.execute(c, segs), ctxs, iters=3, warmup=0)
        snap = capped.residency.stats_snapshot()
        if snap["spills"] and not os.environ.get("BENCH_ALLOW_TIER_SPILL"):
            raise AssertionError(
                f"over-budget queries fell to the host engine "
                f"({snap['spills']} spills) while the host tier + sliced "
                f"combine could have served them (budget {budget} B, "
                f"working set {ws} B)")
        capped_counters = {
            k: snap[k] for k in
            ("demotions", "promotions", "hostDrops", "slicedQueries",
             "spills", "demotedBytes", "promotedBytes",
             "hostDroppedBytes", "hostPeakBytes", "estimateScale")}
        capped.residency.clear()
        capped.close()

        # 3) host-spill baseline: same budget, tier + slicing disabled
        cfg = PinotConfiguration(
            {CommonConstants.HBM_SLICING_ENABLED_KEY: "false",
             CommonConstants.HOSTRAM_ENABLED_KEY: "false"}, use_env=False)
        spill = ShardedQueryExecutor(hbm_budget_bytes=budget, config=cfg)
        spill_p50, _ = _time_suite(
            lambda c: spill.execute(c, segs), ctxs, iters=3, warmup=0)
        spill_snap = spill.residency.stats_snapshot()
        spill.residency.clear()
        spill.close()

        # 4) restage-from-host vs cold rebuild, one segment
        from pinot_tpu.engine.residency import ResidencyManager

        cols = [c for c in
                ("lo_orderdate", "lo_extendedprice", "lo_discount",
                 "lo_quantity")
                if c in segs[0].metadata.columns]
        rm = ResidencyManager(budget_bytes=0)
        t0 = time.perf_counter()
        st = rm.stage(segs[0])
        for c in cols:
            st.column(c)
        rebuild_ms = (time.perf_counter() - t0) * 1e3
        assert rm.demote(segs[0].segment_name)
        t0 = time.perf_counter()
        st = rm.stage(segs[0])
        for c in cols:
            st.column(c)
        restage_ms = (time.perf_counter() - t0) * 1e3
        promoted = rm.stats_snapshot()["promotions"]
        rm.clear()

        n = len(ctxs)
        return {
            "queries": list(qids),
            "working_set_bytes": ws,
            "budget_bytes": budget,
            "over_budget_x": round(ws / budget, 2),
            "sliced_p50_ms_per_query": round(sliced_p50 / n * 1e3, 3),
            "host_spill_p50_ms_per_query": round(spill_p50 / n * 1e3, 3),
            "sliced_vs_spill": round(spill_p50 / sliced_p50, 3)
            if sliced_p50 else None,
            "spill_baseline_spills": spill_snap["spills"],
            "restage_ms": round(restage_ms, 3),
            "rebuild_ms": round(rebuild_ms, 3),
            "restage_vs_rebuild": round(rebuild_ms / restage_ms, 3)
            if restage_ms else None,
            "restage_promotions": promoted,
            "tier_counters": capped_counters,
            "parity": "ok",
        }

    def bench_cluster(self) -> dict:
        """SSB through the FULL distributed path, scaled 2 -> 8 servers:
        broker parse -> partition-aware routing -> scatter -> DataTable
        wire -> broker reduce. Segments are partition-aligned (one d_year
        per segment, Modulo partition metadata recorded at build), the
        table config enables the broker partition pruner, and every query
        records its scatter fan-out (numServersQueried) + prune ratio.
        LOUD-FAIL: at 8 servers a partition-filtered SSB query must prune
        >50% of the scatter targets (BENCH_ALLOW_NO_PRUNE records anyway)."""
        from pinot_tpu.spi.table import (
            RoutingConfig,
            SegmentsValidationConfig,
            TableConfig,
        )
        from pinot_tpu.tools import ssb
        from pinot_tpu.tools.cluster import EmbeddedCluster

        rows = min(self.rows, 500_000)
        n_segs = 8
        seg_dir = os.path.join(self.data_dir, "cluster_segs_part")
        if not os.path.isdir(os.path.join(seg_dir,
                                          f"ssb_part_{n_segs - 1}")):
            ssb.build_segments(0, seg_dir, num_segments=n_segs, rows=rows,
                               partitioned=True)
        qids = ("Q1.1", "Q2.1", "Q4.2")
        # queries with a d_year eq/IN predicate the partition pruner eats
        partition_filtered = ("Q1.1", "Q4.2")
        iters = 5
        per_servers = {}
        for n_servers in (2, 8):
            # device_reduce: broker and servers share this process, so
            # group-by partials merge on device (PR-16); per-query
            # reduce_path below records which rung actually served
            cluster = EmbeddedCluster(
                num_servers=n_servers,
                data_dir=os.path.join(self.data_dir,
                                      f"cluster_{n_servers}"),
                device_reduce=True)
            try:
                cluster.create_table(
                    TableConfig(
                        "ssb_lineorder",
                        validation_config=SegmentsValidationConfig(
                            time_column_name="d_yearmonthnum"),
                        routing_config=RoutingConfig(
                            segment_pruner_types=["partition"])),
                    ssb.ssb_schema())
                for i in range(n_segs):
                    cluster.upload_segment_dir(
                        "ssb_lineorder_OFFLINE",
                        f"{seg_dir}/ssb_part_{i}")
                assert cluster.wait_for_ev_converged(
                    "ssb_lineorder_OFFLINE"), \
                    "external view did not converge: refusing a partial bench"
                hosting = cluster.hosting_servers("ssb_lineorder_OFFLINE")
                fanout, prune_ratio, p50 = {}, {}, {}
                reduce_p50, reduce_path, docs_scanned = {}, {}, {}
                for qid in qids:
                    sql = ssb.QUERIES[qid]
                    cluster.query(sql)  # warm: staging + kernel compile
                    samples = []
                    reduce_samples = []
                    queried = 0
                    for _ in range(iters):
                        t0 = time.perf_counter()
                        resp = cluster.query(sql)
                        samples.append(time.perf_counter() - t0)
                        assert not resp.exceptions, resp.exceptions
                        assert (resp.num_servers_responded
                                == resp.num_servers_queried), \
                            f"{qid}: partial gather in a healthy cluster"
                        queried = resp.num_servers_queried
                        # broker reduce phase (the PR-9 Reduce span's
                        # timer) — the array-native reduce's own cost,
                        # recorded per query so reduce-tier regressions
                        # show up independent of scatter/server time
                        reduce_samples.append(
                            resp.phase_times_ms.get("REDUCE", 0.0))
                        # which reduce rung served (device / vectorized
                        # / oracle) — trajectory rounds attribute reduce
                        # wins to the path, not just the timing
                        reduce_path[qid] = resp.stats.reduce_path
                        # per-query scan footprint (PR-18): with an index
                        # rung in the ladder, docs_scanned is the selectivity
                        # story — trajectory rounds can spot a query falling
                        # off the index back to a full scan
                        docs_scanned[qid] = resp.stats.num_docs_scanned
                    fanout[qid] = queried
                    prune_ratio[qid] = round(
                        1.0 - queried / max(len(hosting), 1), 3)
                    p50[qid] = round(
                        float(np.percentile(samples, 50)) * 1e3, 3)
                    reduce_p50[qid] = round(
                        float(np.percentile(reduce_samples, 50)), 3)
                per_servers[str(n_servers)] = {
                    "servers_hosting": len(hosting),
                    "scatter_fanout": fanout,
                    "prune_ratio": prune_ratio,
                    "p50_ms": p50,
                    "reduce_p50_ms": reduce_p50,
                    "reduce_path": reduce_path,
                    "docs_scanned": docs_scanned,
                }
            finally:
                cluster.shutdown()
        top = per_servers["8"]
        for qid in partition_filtered:
            if top["prune_ratio"][qid] <= 0.5 \
                    and not os.environ.get("BENCH_ALLOW_NO_PRUNE"):
                raise AssertionError(
                    f"cluster: partition-filtered {qid} pruned only "
                    f"{top['prune_ratio'][qid]:.0%} of 8 servers' scatter "
                    f"targets (want >50%) — routing regressed; set "
                    f"BENCH_ALLOW_NO_PRUNE=1 to record anyway")
        return {"rows": rows, "servers": 8, "servers_scaled": [2, 8],
                "p50_ms_per_query": round(
                    sum(top["p50_ms"].values()) / len(qids), 3),
                "partition_filtered": list(partition_filtered),
                "per_servers": per_servers}

    def bench_reduce(self) -> dict:
        """Broker reduce micro-suite: 8 synthesized servers' DataTables
        through the REAL binary wire into BrokerReduceService, vectorized
        vs the row-path oracle. Two shapes: a high-cardinality group-by
        merge (>=100k distinct groups after the merge) and a 100k-row
        ORDER BY LIMIT selection of pre-trimmed, pre-sorted server
        blocks. The group-by merge is ALSO pushed through the PR-16
        device rung (in-process constructor tables over the mesh) and
        must both serve (reduce_path == 'device') and match the oracle
        bit-wise. LOUD-FAIL: vectorized group-by < 5x the oracle,
        selection < 3x, device losing to the vectorized host on a
        multi-device mesh, or ANY row diverging bit-wise from the oracle
        (BENCH_ALLOW_SLOW_REDUCE records the numbers anyway; parity has
        no escape hatch)."""
        import random

        from pinot_tpu.broker.reduce import BrokerReduceService
        from pinot_tpu.common.datatable import DataTable
        from pinot_tpu.engine.results import DataSchema, QueryStats
        from pinot_tpu.query import compile_query

        rng = random.Random(20240814)
        n_servers = 8
        iters = 5
        vec = BrokerReduceService(vectorized=True)
        ora = BrokerReduceService(vectorized=False)
        dev = BrokerReduceService(vectorized=True, device_reduce=True)

        def timed(svc, ctx, raws):
            best = None
            rows = None
            for _ in range(iters):
                tables = [DataTable.from_bytes(r) for r in raws]
                t0 = time.perf_counter()
                table, _, _ = svc.reduce(ctx, tables)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
                rows = table.rows
            return best * 1e3, rows

        # -- group-by: 8 servers x 40k groups -> ~150k-group merge ------
        gb_ctx = compile_query(
            "SELECT k1, k2, sum(v), count(*) FROM t GROUP BY k1, k2 "
            "ORDER BY sum(v) DESC LIMIT 1000")
        gb_tables = []
        for s in range(n_servers):
            groups = {}
            for _ in range(40_000):
                k = ("brand%04d" % rng.randint(0, 499),
                     rng.randint(0, 499))
                groups[k] = [float(rng.randint(0, 10**6)),
                             rng.randint(1, 100)]
            gb_tables.append(DataTable.for_group_by(
                groups, {"k1": "STRING", "k2": "INT"}, QueryStats()))
        gb_raws = [t.to_bytes() for t in gb_tables]
        merged_groups = len({k for r in gb_raws
                             for k in DataTable.from_bytes(r)
                             .group_by_groups()})
        vec_gb_ms, vec_gb_rows = timed(vec, gb_ctx, gb_raws)
        ora_gb_ms, ora_gb_rows = timed(ora, gb_ctx, gb_raws)
        assert vec_gb_rows == ora_gb_rows, \
            "reduce: vectorized group-by diverged from the row-path oracle"

        # -- device rung over the SAME merge (PR-16): the constructor
        # tables stand in for in-process server partials (the embedded
        # cluster topology — wire_decoded=False, the route's premise).
        # Columns pre-sniffed + one warm pass so the timing covers the
        # MERGE, not kernel compilation; parity vs the oracle has NO
        # escape hatch, and the path must actually be 'device'.
        for t in gb_tables:
            t.group_columns()
        dev.reduce(gb_ctx, gb_tables)  # warm: mesh + kernel cache
        dev_gb_ms, dev_gb_rows, dev_gb_path = None, None, None
        for _ in range(iters):
            t0 = time.perf_counter()
            table, dstats, _ = dev.reduce(gb_ctx, gb_tables)
            dms = (time.perf_counter() - t0) * 1e3
            dev_gb_ms = dms if dev_gb_ms is None else min(dev_gb_ms, dms)
            dev_gb_rows, dev_gb_path = table.rows, dstats.reduce_path
        assert dev_gb_rows == ora_gb_rows, \
            "reduce: device group-by diverged from the row-path oracle"
        assert dev_gb_rows == vec_gb_rows, \
            "reduce: device group-by diverged from the vectorized host path"
        assert dev_gb_path == "device", (
            f"reduce: device rung declined to '{dev_gb_path}' "
            f"({dstats.decisions}) — the bench merge shape must SERVE")
        import jax

        bench_devices = len(jax.devices())
        device_speedup = vec_gb_ms / max(dev_gb_ms, 1e-9)
        if bench_devices > 1 and dev_gb_ms > vec_gb_ms:
            print(f"reduce: WARN device merge {dev_gb_ms:.1f}ms LOSES to "
                  f"the vectorized host path {vec_gb_ms:.1f}ms on a "
                  f"{bench_devices}-device mesh",
                  file=sys.stderr)
            if not os.environ.get("BENCH_ALLOW_SLOW_REDUCE"):
                raise AssertionError(
                    f"reduce: device merge {dev_gb_ms:.1f}ms > vectorized "
                    f"host {vec_gb_ms:.1f}ms on a {bench_devices}-device "
                    f"mesh; set BENCH_ALLOW_SLOW_REDUCE=1 to record "
                    f"anyway (speed only — parity never waives)")

        # -- selection: 100k rows total, ORDER BY LIMIT, pre-sorted -----
        per_server = 100_000 // n_servers
        sel_ctx = compile_query(
            "SELECT a, b FROM t ORDER BY b, a LIMIT %d" % per_server)
        schema = DataSchema(["a", "b"], ["STRING", "LONG"])
        sel_raws = []
        for s in range(n_servers):
            rows = sorted(
                [["city%03d" % rng.randint(0, 299),
                  rng.randint(0, 10**6)] for _ in range(per_server)],
                key=lambda r: (r[1], r[0]))
            sel_raws.append(DataTable.for_selection(
                schema, rows, QueryStats(),
                sorted_rows=True).to_bytes())
        vec_sel_ms, vec_sel_rows = timed(vec, sel_ctx, sel_raws)
        ora_sel_ms, ora_sel_rows = timed(ora, sel_ctx, sel_raws)
        assert vec_sel_rows == ora_sel_rows, \
            "reduce: vectorized selection diverged from the row-path oracle"

        gb_speedup = ora_gb_ms / max(vec_gb_ms, 1e-9)
        sel_speedup = ora_sel_ms / max(vec_sel_ms, 1e-9)
        rec = {
            "servers": n_servers,
            "groupby": {"merged_groups": merged_groups,
                        "vectorized_ms": round(vec_gb_ms, 3),
                        "oracle_ms": round(ora_gb_ms, 3),
                        "speedup": round(gb_speedup, 2),
                        "device_ms": round(dev_gb_ms, 3),
                        "device_speedup": round(device_speedup, 2),
                        "device_path": dev_gb_path,
                        "mesh_devices": bench_devices},
            "selection": {"rows": per_server * n_servers,
                          "vectorized_ms": round(vec_sel_ms, 3),
                          "oracle_ms": round(ora_sel_ms, 3),
                          "speedup": round(sel_speedup, 2)},
            "p50_ms": round(vec_gb_ms, 3),
        }
        if not os.environ.get("BENCH_ALLOW_SLOW_REDUCE"):
            assert merged_groups >= 100_000, \
                f"reduce: merge shape shrank to {merged_groups} groups"
            assert gb_speedup >= 5.0, (
                f"reduce: vectorized group-by only {gb_speedup:.1f}x over "
                f"the row-path oracle (want >=5x) — the array-native "
                f"merge regressed; set BENCH_ALLOW_SLOW_REDUCE=1 to "
                f"record anyway")
            assert sel_speedup >= 3.0, (
                f"reduce: vectorized selection only {sel_speedup:.1f}x "
                f"over the row-path oracle (want >=3x); set "
                f"BENCH_ALLOW_SLOW_REDUCE=1 to record anyway")
        return rec

    def bench_realtime(self) -> dict:
        """Realtime serving tier (PR-17): consuming-segment write QPS,
        ingest-to-queryable freshness p50/p99 under a concurrent query
        cadence (the serve path's per-row freshness histogram), device
        group-by latency on the consuming segment, and the
        mutable->immutable seal wall-time through the real commit path
        (default star-tree stamped at seal). LOUD-FAIL: every
        device-eligible query on the consuming segment must serve from
        the mutable_device rung — a host spill means the staging tier
        regressed (BENCH_ALLOW_MUTABLE_HOST=1 records anyway), and the
        sealed segment must serve from startree_device."""
        import math

        from pinot_tpu.common.telemetry import TELEMETRY
        from pinot_tpu.engine import ServerQueryExecutor
        from pinot_tpu.ingestion import MemoryStream
        from pinot_tpu.ingestion.realtime import (
            ConsumerState,
            RealtimeSegmentDataManager,
        )
        from pinot_tpu.ingestion.stream import StreamOffset
        from pinot_tpu.query import compile_query
        from pinot_tpu.segment import load_segment
        from pinot_tpu.segment.mutable import MutableSegment
        from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema
        from pinot_tpu.spi.table import (
            SegmentsValidationConfig,
            StreamIngestionConfig,
            TableConfig,
            TableType,
        )

        schema = Schema("rtbench", [
            FieldSpec("city", DataType.STRING, FieldType.DIMENSION),
            FieldSpec("clicks", DataType.LONG, FieldType.METRIC),
            FieldSpec("price", DataType.DOUBLE, FieldType.METRIC),
            FieldSpec("ts", DataType.LONG, FieldType.DATE_TIME),
        ])
        cities = [f"city{i:03d}" for i in range(64)]
        rng = np.random.default_rng(7)
        n_rows = int(os.environ.get("BENCH_REALTIME_ROWS", 40_000))
        query_every = max(1, n_rows // 10)

        def make_row(i):
            return {"city": cities[int(rng.integers(64))],
                    "clicks": int(rng.integers(1000)),
                    "price": float(rng.integers(10_000)) / 4.0,
                    "ts": 1_600_000_000_000 + i}

        dev = ServerQueryExecutor(use_device=True)
        sql = ("SELECT city, count(*), sum(clicks) FROM rtbench "
               "GROUP BY city LIMIT 100")
        q = compile_query(sql)

        # -- write QPS + freshness under a query cadence ----------------
        seg = MutableSegment(schema, "rtbench__0__0__b",
                             capacity=max(n_rows, 1024))
        rungs, query_ms, index_s = [], [], 0.0
        for start in range(0, n_rows, query_every):
            t0 = time.perf_counter()
            for i in range(start, min(start + query_every, n_rows)):
                seg.index(make_row(i))
            index_s += time.perf_counter() - t0
            t0 = time.perf_counter()
            _, qstats = dev.execute(q, [seg])
            query_ms.append((time.perf_counter() - t0) * 1e3)
            rungs.append(qstats.group_by_rung)
        write_qps = n_rows / max(index_s, 1e-9)

        spills = [r for r in rungs if r != "mutable_device"]
        if spills and not os.environ.get("BENCH_ALLOW_MUTABLE_HOST"):
            from pinot_tpu.common.tracing import LEDGER

            declines = {k: v for k, v in LEDGER.reason_histogram().items()
                        if k.startswith("mutable_")}
            raise AssertionError(
                f"realtime: {len(spills)}/{len(rungs)} consuming-segment "
                f"queries spilled to {sorted(set(spills))} instead of "
                f"mutable_device (mutable declines: {declines}) — the "
                f"device staging tier regressed; set "
                f"BENCH_ALLOW_MUTABLE_HOST=1 to record anyway")

        fresh = TELEMETRY.histo("rtbench", "freshness").lifetime.snapshot()
        assert fresh["count"] > 0, \
            "realtime: serve path recorded no freshness observations"
        assert math.isfinite(fresh["p99"]), fresh

        # -- seal wall-time through the real commit path ----------------
        seal_rows = min(n_rows, 20_000)
        MemoryStream.create("bench_rt", 1)
        try:
            stream = MemoryStream.get("bench_rt")
            for i in range(seal_rows):
                stream.produce(make_row(i), partition=0)
            cfg = TableConfig(
                "rtbench", TableType.REALTIME,
                validation_config=SegmentsValidationConfig(
                    time_column_name="ts"),
                stream_config=StreamIngestionConfig(
                    stream_type="memory", topic="bench_rt",
                    segment_flush_threshold_rows=seal_rows))
            mgr = RealtimeSegmentDataManager(
                "rtbench__0__0__s", cfg, schema, partition=0,
                start_offset=StreamOffset(0),
                output_dir=os.path.join(self.data_dir, "bench_rt_seal"))
            res = mgr.consume_until_committed()
            assert res.state is ConsumerState.COMMITTED, res.state
            sealed = load_segment(res.segment_dir)
            _, sstats = dev.execute(q, [sealed])
            if sstats.group_by_rung != "startree_device" \
                    and not os.environ.get("BENCH_ALLOW_MUTABLE_HOST"):
                raise AssertionError(
                    f"realtime: sealed segment served from "
                    f"{sstats.group_by_rung!r}, not startree_device — the "
                    f"seal-time default star-tree stamp regressed")
            seal_ms = mgr.seal_wall_ms
        finally:
            MemoryStream.delete("bench_rt")

        return {
            "rows": n_rows,
            "write_qps": round(write_qps, 1),
            "freshness_p50_ms": fresh["p50"],
            "freshness_p99_ms": fresh["p99"],
            "freshness_rows": fresh["count"],
            "query_p50_ms": round(float(np.percentile(query_ms, 50)), 3),
            "consuming_rung": sorted(set(rungs)),
            "seal_rows": seal_rows,
            "seal_ms": round(seal_ms, 1),
            "sealed_rung": sstats.group_by_rung,
        }

    def bench_userfacing(self) -> dict:
        """User-facing analytics: Zipf point-filter group-bys over the wide
        user-event table at 1/2/4/8 closed-loop client threads (ref:
        Pinot's user-facing serving story — BitmapInvertedIndexReader /
        RangeIndexReader-served point lookups at strict latency SLOs).
        Every query in the mix is <1%-selective, so the PR-18 index rung
        must serve ALL of them; the suite records p50/p95/p99/QPS per
        level plus the per-query docs-scanned footprint and the rung
        histogram from the decision ledger. LOUD-FAIL (escapes noted):

        - a selective filter that leaves the index rung for a scan
          (``BENCH_ALLOW_SCAN_SELECTIVE=1`` records anyway) — the SLO
          story collapses if tail-user lookups pay full-scan latency;
        - any index decline reason in the ledger that is NOT in
          ``tracing.registered_reason_codes()`` — an unregistered decline
          is an unexplained fallback, and the BENCH JSON must explain
          every one."""
        import concurrent.futures

        from pinot_tpu.common import tracing
        from pinot_tpu.query import compile_query
        from pinot_tpu.tools import usertable

        rows = min(self.rows, 2_000_000)
        n_segs = 4
        seg_dir = os.path.join(self.data_dir, "user_segs")
        if not os.path.isdir(os.path.join(seg_dir, f"user_{n_segs - 1}")):
            _log(f"userfacing: building user table ({rows} rows)")
            segs = usertable.build_segments(seg_dir, num_segments=n_segs,
                                            rows=rows)
        else:
            from pinot_tpu.segment import load_segment
            segs = [load_segment(os.path.join(seg_dir, f"user_{i}"))
                    for i in range(n_segs)]
        users = usertable.tail_users(rows, num_segments=n_segs)
        assert users, "userfacing: no tail users sampled"
        ctxs = [compile_query(q) for q in usertable.point_queries(users)]

        # verification pass: every query is selective by construction, so
        # every one must ride the index rung on every segment — and every
        # decline the ledger recorded anywhere in the run must be a
        # registered reason code
        allow_scan = os.environ.get("BENCH_ALLOW_SCAN_SELECTIVE")
        docs_scanned = []
        scan_leaks = []
        for ctx in ctxs:
            _, st = self.dev.execute(ctx, segs)   # doubles as compile/warm
            docs_scanned.append(st.num_docs_scanned)
            served = sum(v for k, v in st.decisions.items()
                         if k.endswith(":index_served"))
            if served < len(segs):
                scan_leaks.append((ctx.sql, dict(st.decisions)))
        if scan_leaks and not allow_scan:
            raise AssertionError(
                f"userfacing: {len(scan_leaks)} selective (<1%) point "
                f"filter(s) left the index rung for a scan — first: "
                f"{scan_leaks[0]}; set BENCH_ALLOW_SCAN_SELECTIVE=1 to "
                f"record anyway")

        seconds = 4.0
        levels = {}
        lock = threading.Lock()

        def run_level(threads: int) -> dict:
            lat: list = []
            stop_at = time.perf_counter() + seconds

            def pump(i: int) -> int:
                done = 0
                while time.perf_counter() < stop_at:
                    ctx = ctxs[(i + done) % len(ctxs)]
                    t0 = time.perf_counter()
                    self.dev.execute(ctx, segs)
                    dt = (time.perf_counter() - t0) * 1e3
                    with lock:
                        lat.append(dt)
                    done += 1
                return done

            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(threads) as pool:
                total = sum(pool.map(pump, range(threads)))
            wall = time.perf_counter() - t0
            arr = np.asarray(lat) if lat else np.asarray([0.0])
            return {
                "qps": round(total / wall, 2),
                "p50_ms": round(float(np.percentile(arr, 50)), 3),
                "p95_ms": round(float(np.percentile(arr, 95)), 3),
                "p99_ms": round(float(np.percentile(arr, 99)), 3),
                "queries": total,
            }

        dmark = self._decision_mark()
        for threads in (1, 2, 4, 8):
            _log(f"userfacing: sweeping {threads} thread(s)")
            levels[str(threads)] = run_level(threads)
        decisions = self._decision_delta(dmark)

        # rung histogram: where did the sweep's queries actually serve
        rungs = {}
        registered = tracing.registered_reason_codes()
        unregistered = []
        for key, count in decisions.items():
            point, chosen, _declined, reason = \
                tracing.parse_decision_key(key)
            if point != "index":
                continue
            rungs[chosen] = rungs.get(chosen, 0) + count
            if reason not in registered:
                unregistered.append(key)
        if unregistered:
            raise AssertionError(
                f"userfacing: unregistered index decline reason(s) in the "
                f"ledger: {unregistered} — register them in "
                f"tracing.INDEX_DECISION_REASONS or fix the recording site")

        four = levels["4"]
        return {
            "rows": rows,
            "num_queries": len(ctxs),
            "threads": 4,
            "qps": four["qps"],
            "p50_ms": four["p50_ms"],
            "p95_ms": four["p95_ms"],
            "p99_ms": four["p99_ms"],
            "qps_by_threads": levels,
            "docs_scanned_p50": int(np.percentile(docs_scanned, 50)),
            "docs_scanned_max": int(max(docs_scanned)),
            "selectivity_p50": round(
                float(np.percentile(docs_scanned, 50)) / max(rows, 1), 6),
            "rung_histogram": rungs,
            "scan_leaks": len(scan_leaks),
        }


# ==========================================================================
# micro/star-tree fixtures (configs #1-#4; unchanged from round 4)
# ==========================================================================

MICRO_SEGMENTS = 8
MICRO_DOCS = 131_072

MICRO_QUERIES = [
    "SELECT count(*), sum(qty) FROM sales WHERE region = 'east'",
    "SELECT sum(price) FROM sales WHERE year BETWEEN 2017 AND 2021 AND kind != 'c'",
    "SELECT region, sum(qty), count(*) FROM sales GROUP BY region ORDER BY region",
    "SELECT region, kind, sum(price), avg(price), min(qty), max(qty) FROM sales "
    "GROUP BY region, kind ORDER BY region, kind",
    "SELECT year, min(price), max(price) FROM sales WHERE kind = 'a' "
    "GROUP BY year ORDER BY year",
    "SELECT distinctcount(region) FROM sales WHERE qty > 25",
    "SELECT sum(qty * price) FROM sales WHERE region IN ('west', 'south')",
]

STARTREE_QUERY = ("SELECT region, kind, sum(qty), count(*) FROM sales_st "
                  "GROUP BY region, kind ORDER BY region, kind")
SKETCH_QUERIES = [
    "SELECT distinctcounthll(user_id) FROM sales_st WHERE qty > 10",
    "SELECT percentiletdigest95(price) FROM sales_st",
]


def _micro_frame(n: int, seed: int, with_user: bool = False):
    rng = np.random.default_rng(seed)
    regions = np.array(["east", "west", "north", "south"])
    kinds = np.array(["a", "b", "c"])
    frame = {
        "region": regions[rng.integers(0, 4, n)],
        "kind": kinds[rng.integers(0, 3, n)],
        "year": rng.integers(2015, 2024, n).astype(np.int64),
        "qty": rng.integers(1, 50, n).astype(np.int64),
        "price": np.round(rng.normal(100.0, 25.0, n), 2),
    }
    if with_user:
        frame["user_id"] = rng.integers(0, 200_000, n).astype(np.int64)
    return frame


def _micro_schema(with_user: bool = False):
    from pinot_tpu.spi import DataType, FieldSpec, FieldType, Schema

    specs = [
        FieldSpec("region", DataType.STRING),
        FieldSpec("kind", DataType.STRING),
        FieldSpec("year", DataType.INT),
        FieldSpec("qty", DataType.LONG, FieldType.METRIC),
        FieldSpec("price", DataType.DOUBLE, FieldType.METRIC),
    ]
    if with_user:
        specs.insert(3, FieldSpec("user_id", DataType.LONG))
    name = "sales_st" if with_user else "sales"
    return Schema(name, specs)


def _build_micro(tmpdir: str):
    from pinot_tpu.segment import SegmentBuilder, load_segment

    schema = _micro_schema()
    segs = []
    for i in range(MICRO_SEGMENTS):
        b = SegmentBuilder(schema, f"sales_{i}")
        b.build(_micro_frame(MICRO_DOCS, seed=100 + i), tmpdir)
        segs.append(load_segment(f"{tmpdir}/sales_{i}"))
    return segs


def _tree_build_at_scale(rows: int) -> dict:
    """Build the DEFAULT SSB tree set (all 5 trees) with the lexsort
    engine over ``rows`` rows in ONE shot — dictIds factorized the same
    way the segment creator does — and record per-tree build wall seconds
    + record counts. The 24M-row number the ROADMAP asks for: build cost
    must be measured where it scales, not inferred from 120k-row tests.
    Each tree gets fresh metric dicts so derived-pair evaluation is
    counted inside its own build time."""
    from pinot_tpu.segment.creator import _sorted_factorize
    from pinot_tpu.segment.startree import StarTreeConfig
    from pinot_tpu.segment.startree import StarTreeBuilder
    from pinot_tpu.tools import ssb

    _log(f"startree: generating {rows} rows for the at-scale tree build")
    t0 = time.perf_counter()
    cols = ssb.generate_table(NUM_SEGMENTS, rows)
    gen_s = time.perf_counter() - t0
    configs = [StarTreeConfig.from_spi(c) for c in
               ssb.ssb_indexing_config().star_tree_index_configs]
    dims_needed = sorted({d for c in configs
                          for d in c.dimensions_split_order})
    t0 = time.perf_counter()
    dict_ids = {d: _sorted_factorize(np.asarray(cols[d]))[1].astype(np.int32)
                for d in dims_needed}
    fact_s = time.perf_counter() - t0
    metric_cols = ("lo_revenue", "lo_supplycost", "lo_extendedprice",
                   "lo_discount")
    metrics = {m: np.asarray(cols[m]) for m in metric_cols}
    del cols  # the string columns are ~GBs at 24M rows; trees never read them
    per_tree = {}
    for i, cfg in enumerate(configs):
        t0 = time.perf_counter()
        tree = StarTreeBuilder(cfg).build(dict(dict_ids), dict(metrics),
                                          rows)
        per_tree[f"tree{i}"] = {
            "build_s": round(time.perf_counter() - t0, 2),
            "records": tree.num_records,
            "dims": len(cfg.dimensions_split_order)}
        _log(f"startree: tree{i} {per_tree[f'tree{i}']}")
        del tree
    return {"rows": rows, "engine": "lexsort",
            "generate_s": round(gen_s, 2), "factorize_s": round(fact_s, 2),
            "per_tree": per_tree}


def _ssb_rung(qstats) -> str:
    """The rung that served one SSB flight. Group-bys carry it directly;
    scalar flights (Q1.x) derive it from the ledger's chosen-tree record
    (startree:scan-><rung>:tree<i>) — a scalar query has no
    group_by_rung but absolutely has a rung."""
    if qstats.group_by_rung:
        return qstats.group_by_rung
    for k in qstats.decisions:
        if k.startswith("startree:scan->startree_device:"):
            return "startree_device"
    for k in qstats.decisions:
        if k.startswith("startree:scan->startree:"):
            return "startree"
    return "scalar"


def _tree_build_times(segs) -> dict:
    """Per-tree build wall seconds summed across segments (the creator
    stamps them into segment metadata at build time)."""
    out: dict = {}
    for s in segs:
        for i, b in enumerate(getattr(s.metadata, "star_tree_build_s", [])):
            out[f"tree{i}"] = round(out.get(f"tree{i}", 0.0) + float(b), 3)
    return out


def _build_startree(tmpdir: str):
    """sales_st: star-tree on (region, kind) + a high-card user_id column
    for the sketch queries (BASELINE configs #3/#4)."""
    from pinot_tpu.segment import SegmentBuilder, load_segment
    from pinot_tpu.spi.table import IndexingConfig, StarTreeIndexConfig

    cfg = IndexingConfig(star_tree_index_configs=[StarTreeIndexConfig(
        dimensions_split_order=["region", "kind"],
        function_column_pairs=["SUM__qty", "SUM__price", "COUNT__*"],
        max_leaf_records=1000)])
    schema = _micro_schema(with_user=True)
    segs = []
    for i in range(4):
        b = SegmentBuilder(schema, f"sales_st_{i}", indexing_config=cfg)
        b.build(_micro_frame(MICRO_DOCS, seed=300 + i, with_user=True),
                tmpdir)
        segs.append(load_segment(f"{tmpdir}/sales_st_{i}"))
    return segs


def _assert_parity(name, dev_rows, host_rows):
    assert len(dev_rows) == len(host_rows), \
        f"{name}: {len(dev_rows)} vs {len(host_rows)} rows"
    for dr, hr in zip(dev_rows, host_rows):
        for d, h in zip(dr, hr):
            if isinstance(h, float):
                assert abs(d - h) <= 1e-4 * max(1.0, abs(h)), (name, d, h)
            else:
                assert d == h, (name, d, h)


def _time_suite(run, ctxs, iters=ITERS, warmup=WARMUP):
    """(p50, p99) seconds over full-suite passes."""
    for _ in range(warmup):
        for ctx in ctxs:
            run(ctx)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for ctx in ctxs:
            run(ctx)
        samples.append(time.perf_counter() - t0)
    return (float(np.percentile(samples, 50)),
            float(np.percentile(samples, 99)))


# ==========================================================================

def main() -> int:
    worker = _Worker()
    worker.run()
    emit(worker.results, worker.device)
    failed = sorted(s for s, rec in worker.results.items()
                    if "error" in rec)
    if failed:
        _log(f"suites recorded an error: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
