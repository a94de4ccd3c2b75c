"""A hybrid table in the harness: a REALTIME half fed by the producer.

A config with a ``realtime`` section is a hybrid table: the OFFLINE half
from the segment store as any config's, and a REALTIME half that the
program consumes from ``lib/producer.py``, a process of the benchmark's
own. Everything here is keyed on that section; a config without it never
reaches this module.

The section (every key required):

- ``partitions``, ``partition_column``, ``partition_function`` (``Modulo``
  alone): the stream's partitions and how the producer keys a row;
- ``time_column``: the column of the broker's time boundary;
- ``rate_rows_per_s``, ``flush_threshold_rows``: the live phase's rate and
  the rows a consuming segment takes before it seals;
- ``catch_up_rows``: a partition's rows appended before the window, which
  the server consumes during set-up (staggered, they put the seals at
  different moments of the window);
- ``freshness_limit_s``: a response may miss rows appended less than this
  long before its request left; ``drain_s``: how long after the window the
  consumers have to read the last row; ``min_seals_in_window``;
- ``tableIndexConfig``: the REALTIME table's own.

Correct, for a response: its rows equal the reference at some prefix ``w``
of the stream partition its string pins, ``lo <= w <= hi``, where ``lo``
is what the producer had appended ``freshness_limit_s`` before the request
left and ``hi`` what it had appended when the answer came back. One that
matches only a prefix below ``lo`` is stale. A string that pins no single
value of the partition column is refused when the cycle is built: the
comparison knows one partition a string. The guarantees of the ingest are
numbers beside limits too (``guarantees``).

Standard library and numpy only, as the oracle.
"""

from __future__ import annotations

import json
import urllib.request

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmarks.lib import compare, oracle

KEYS = ("partitions", "partition_column", "partition_function",
        "time_column", "rate_rows_per_s", "flush_threshold_rows",
        "catch_up_rows", "freshness_limit_s", "drain_s",
        "min_seals_in_window", "tableIndexConfig")
WINDOW_LEAD_S = 1.0     # the client's and the producer's common start


class ConfigRefused(ValueError):
    """A hybrid config or cycle the harness cannot judge."""


def check(config: Dict[str, Any], cycle: List[Dict[str, Any]]
          ) -> Dict[int, int]:
    """The realtime section in full, and the value of the partition column
    each string pins, by string id."""
    rt = config["realtime"]
    missing = [k for k in KEYS if k not in rt]
    if missing:
        raise ConfigRefused(f"realtime section lacks {missing}")
    if rt["partition_function"] != "Modulo":
        raise ConfigRefused(f"partition function {rt['partition_function']!r}"
                            f": the producer keys rows by Modulo alone")
    if len(rt["catch_up_rows"]) != rt["partitions"]:
        raise ConfigRefused("catch_up_rows needs one count a partition")
    pins = {}
    for q in cycle:
        values = {int(v) for column, op, *lits in q["where"]
                  if column == rt["partition_column"] and op in ("=", "in")
                  and len(lits) == 1 for v in lits}
        if len(values) != 1:
            raise ConfigRefused(
                f"string {q['id']} ({q['flight']}) does not pin one value of "
                f"{rt['partition_column']}: a hybrid cell's strings must")
        pins[q["id"]] = values.pop()
    return pins


def live_rows(config: Dict[str, Any], seconds: float) -> int:
    """Rows one live phase appends (``lib/producer.py``)."""
    return int(float(config["realtime"]["rate_rows_per_s"]) * seconds)


def appended_after(config: Dict[str, Any], live: int) -> List[int]:
    """A partition's rows once ``live`` live rows are in: its catch-up, and
    live row ``g`` in partition ``g % partitions``."""
    rt = config["realtime"]
    return [n + len(range(p, live, rt["partitions"]))
            for p, n in enumerate(rt["catch_up_rows"])]


def producer_job(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    rt = config["realtime"]
    return {"table": config["table"], "seed": seed,
            "topic": config["table_name"], "partitions": rt["partitions"],
            "rate_rows_per_s": rt["rate_rows_per_s"],
            "catch_up_rows": rt["catch_up_rows"]}


def oracle_job(config: Dict[str, Any], pins: Dict[int, int], seconds: float,
               windows: int) -> Dict[str, Any]:
    """The ``hybrid`` argument of ``oracle.answers_for``: the stream as it
    stands at the end of each window."""
    rt = config["realtime"]
    metrics = [f["name"] for f in config["schema"].get("metricFieldSpecs",
                                                       [])]
    return {"partitions": rt["partitions"],
            "partition_column": rt["partition_column"],
            "time_column": rt["time_column"],
            "members": sorted(set(pins.values())), "metrics": metrics,
            "appended": [appended_after(config, i * live_rows(config,
                                                              seconds))
                         for i in range(1, windows + 1)]}


class Producer:
    """The parent's side of ``lib/producer.py``'s control endpoints."""

    def __init__(self, port: int):
        self.url = f"http://127.0.0.1:{port}"

    def _call(self, path: str, body: Optional[Dict[str, Any]] = None
              ) -> Dict[str, Any]:
        data = None if body is None else json.dumps(body).encode()
        with urllib.request.urlopen(urllib.request.Request(
                self.url + path, data=data), timeout=60) as r:
            return json.loads(r.read().decode())

    def live(self, start_wall: float, seconds: float) -> None:
        self._call("/live", {"start_wall": start_wall, "seconds": seconds})

    def log(self, batches: bool = True) -> Dict[str, Any]:
        """Appended counts and live phases ended; with ``batches`` the
        whole batch log and every live tick's lateness too."""
        return self._call("/log" if batches else "/log?batches=0")

    def stop(self) -> None:
        self._call("/stop", {})


class Log:
    """The producer's batches: what partition ``p`` held at wall time
    ``t``."""

    def __init__(self, batches: List[List[float]], partitions: int):
        self.times: List[np.ndarray] = []
        self.ends: List[np.ndarray] = []
        rows = np.asarray(batches, dtype=np.float64).reshape(-1, 3)
        for p in range(partitions):
            mine = rows[rows[:, 1] == p]
            self.times.append(mine[:, 0])
            self.ends.append(mine[:, 2].astype(np.int64))

    def at(self, p: int, t: float) -> int:
        i = int(np.searchsorted(self.times[p], t, "right"))
        return int(self.ends[p][i - 1]) if i else 0


class Stream:
    """The oracle's stream rows past the boundary, by pinned member."""

    def __init__(self, arrays: Dict[str, np.ndarray], column: str):
        self.offset = arrays["offset"]
        self.cols = {k: v for k, v in arrays.items() if k != "offset"}
        keys = self.cols[column]
        uniq, first = np.unique(keys, return_index=True)
        last = np.append(first[1:], len(keys))
        self.spans = {int(u): (int(a), int(b))
                      for u, a, b in zip(uniq, first, last)}

    @classmethod
    def load(cls, path: str, column: str) -> "Stream":
        with np.load(path) as arrays:
            return cls(dict(arrays), column)

    def of(self, member: int) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
        a, b = self.spans.get(member, (0, 0))
        return {k: v[a:b] for k, v in self.cols.items()}, self.offset[a:b]


def _total(rows) -> float:
    return sum(float(r[-1]) for r in rows)


class Prefixes:
    """One string's answers at every prefix of its partition: the offline
    part plus the stream's matching rows up to ``k``."""

    def __init__(self, table_mod, query: Dict[str, Any], offline: list,
                 cols: Dict[str, np.ndarray], offsets: np.ndarray):
        self.table_mod, self.query, self.cols = table_mod, query, cols
        self.offline = [list(r) for r in offline]
        mask = oracle._mask(table_mod, cols, query["where"])
        self.idx = (np.arange(len(offsets)) if mask is None
                    else np.flatnonzero(mask))
        self.offsets = offsets[self.idx]
        vals = oracle._value(cols, query["value"], self.idx, np.float64)
        self.totals = _total(self.offline) + np.concatenate(
            [[0.0], np.cumsum(vals)])

    def count_below(self, offset: int) -> int:
        """Matching rows at offsets under ``offset``."""
        return int(np.searchsorted(self.offsets, offset, "left"))

    def at(self, k: int, dtype=np.float64) -> list:
        """The answer with the first ``k`` matching stream rows, in the
        query's order (``oracle.answer``'s); float32 is the control's
        accumulation."""
        merged: Dict[tuple, Any] = {tuple(r[:-1]): dtype(r[-1])
                                    for r in self.offline}
        for r in oracle.answer(self.table_mod, self.cols, self.query, dtype,
                               idx=self.idx[:k]):
            key = tuple(r[:-1])
            merged[key] = merged.get(key, dtype(0)) + dtype(r[-1])
        rows = [list(k) + [float(v)] for k, v in merged.items()]
        if self.query["order"] == "last_key_then_value_desc":
            rows.sort(key=lambda r: (r[-2], -r[-1]))
        else:
            rows.sort(key=lambda r: r[:-1])
        return rows

    def match(self, got: list, k_lo: int, k_hi: int
              ) -> Tuple[Optional[int], float]:
        """The prefix whose answer ``got`` is (those in ``[k_lo, k_hi]``
        first, then older ones), or None and the gap to the answer at
        ``k_hi``. Candidates are the prefixes whose sum is ``got``'s."""
        cands = np.flatnonzero(self.totals == _total(got)).tolist()
        cands.sort(key=lambda k: (not k_lo <= k <= k_hi, -k))
        for k in cands:
            same, gap = compare.rows_gap(got, self.at(k), self.query["order"])
            if same and gap == 0:
                return k, 0.0
        return None, compare.rows_gap(got, self.at(k_hi),
                                      self.query["order"])[1]


def compare_window(records: List[Dict[str, Any]], cycle: List[Dict[str, Any]],
                   want: Dict[str, list], stream: Stream, pins: Dict[int, int],
                   log: Log, config: Dict[str, Any], table_mod
                   ) -> Dict[str, Any]:
    """``compare.compare`` for a hybrid table: each response at a prefix of
    its partition between ``lo`` and ``hi``. Marks each record ``ok`` and
    notes its prefix range and match (``prefix``)."""
    rt = config["realtime"]
    failed: List[str] = []
    wrong: List[str] = []
    stale: List[str] = []
    worst = 0.0
    cache: Dict[int, Prefixes] = {}
    for rec in records:
        q = cycle[rec["index"]]
        raw, why = compare.check_response(rec["status"], rec["body"])
        rec["raw"] = raw
        if why is not None:
            rec["ok"] = False
            failed.append(f"{q['flight']}#{q['id']}: "
                          f"{rec.get('error') or why}")
            continue
        if q["id"] not in cache:
            cache[q["id"]] = Prefixes(table_mod, q, want[str(q["id"])],
                                      *stream.of(pins[q["id"]]))
        pre = cache[q["id"]]
        p = pins[q["id"]] % rt["partitions"]
        lo = log.at(p, rec["sent_epoch"] - rt["freshness_limit_s"])
        hi = log.at(p, rec["done_epoch"])
        k_lo, k_hi = pre.count_below(lo), pre.count_below(hi)
        k, gap = pre.match(raw["resultTable"]["rows"], k_lo, k_hi)
        rec["prefix"] = {"partition": p, "lo": lo, "hi": hi, "k_lo": k_lo,
                         "k_hi": k_hi, "k": k}
        worst = max(worst, gap)
        rec["ok"] = k is not None and k_lo <= k <= k_hi
        if k is not None and k < k_lo:
            stale.append(f"{q['flight']}#{q['id']}: {k_lo - k} rows "
                         f"appended over {rt['freshness_limit_s']} s before "
                         f"it left are missing")
        elif not rec["ok"]:
            wrong.append(f"{q['flight']}#{q['id']}: gap {gap}, prefix "
                         f"{k} not in [{k_lo}, {k_hi}]")
    return {"responses_compared": len(records) - len(failed),
            "responses_failed": len(failed),
            "responses_wrong": len(wrong),
            "responses_stale": len(stale),
            "max_abs_diff": worst,
            "first_failed": failed[:3], "first_wrong": wrong[:3],
            "first_stale": stale[:3]}


def control_window(records: List[Dict[str, Any]],
                   cycle: List[Dict[str, Any]], want: Dict[str, list],
                   control: Dict[str, list], stream: Stream,
                   pins: Dict[int, int], config: Dict[str, Any], table_mod
                   ) -> Dict[str, Any]:
    """The control for a hybrid table: for each compared response, the
    reference summed in float32 (the offline part as the oracle summed it,
    the stream's rows added to it) at the newest prefix the response could
    have read, against the exact answer there."""
    recs, cache = [], {}
    for rec in records:
        if rec.get("prefix") is None:
            continue
        q = cycle[rec["index"]]
        if q["id"] not in cache:
            rows = stream.of(pins[q["id"]])
            cache[q["id"]] = (
                Prefixes(table_mod, q, want[str(q["id"])], *rows),
                Prefixes(table_mod, q, control[str(q["id"])], *rows))
        exact, low = cache[q["id"]]
        k = rec["prefix"]["k_hi"]
        recs.append((q, low.at(k, np.float32), exact.at(k)))
    wrong = [f"{q['flight']}#{q['id']}" for q, got, exp in recs
             if compare.rows_gap(got, exp, q["order"]) != (True, 0.0)]
    return {"responses_compared": len(recs), "responses_wrong": len(wrong),
            "max_abs_diff": max((compare.rows_gap(got, exp, q["order"])[1]
                                 for q, got, exp in recs), default=0.0),
            "first_wrong": wrong[:3]}


def guarantees(config: Dict[str, Any], end: Dict[str, Any],
               drained: Dict[str, Any], seals: int,
               rows_off: int) -> Dict[str, Dict[str, Any]]:
    """The ingest's guarantees as numbers beside their limits: the lag at
    the window's end and after the drain (rows appended and not consumed,
    over all partitions), partitions whose count or metric sums over the
    REALTIME half differ from the reference's after the drain, and the
    seals inside the window (a floor: ``at_least``)."""
    rt = config["realtime"]
    return {
        "keeps_up_lag_rows": {
            "value": end["lag"],
            "limit": int(float(rt["rate_rows_per_s"])
                         * float(rt["freshness_limit_s"]))},
        "keeps_up_drained_lag_rows": {"value": drained["lag"], "limit": 0},
        "rows_exact_partitions_off": {"value": rows_off, "limit": 0},
        "seals_in_window": {"value": seals,
                            "limit": rt["min_seals_in_window"],
                            "at_least": True},
    }


def breached(guarantees: Dict[str, Dict[str, Any]]) -> List[str]:
    """Each guarantee a run broke, named, with its number and limit."""
    out = []
    for name, c in guarantees.items():
        least = c.get("at_least", False)
        if c["value"] < c["limit"] if least else c["value"] > c["limit"]:
            out.append(f"{name} {c['value']} (limit "
                       f"{'at least ' if least else ''}{c['limit']})")
    return out


def rows_exact_sql(config: Dict[str, Any], table: str, p: int) -> str:
    """One partition's count and metric sums over the REALTIME half."""
    rt = config["realtime"]
    metrics = [f["name"] for f in config["schema"].get("metricFieldSpecs",
                                                       [])]
    sums = "".join(f", sum({m})" for m in metrics)
    return (f"SELECT count(*){sums} FROM {table} WHERE "
            f"mod({rt['partition_column']}, {rt['partitions']}) = {p}")
