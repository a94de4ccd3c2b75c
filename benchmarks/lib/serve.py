"""The system under test, brought up as a user reaches it.

The only module of the benchmark that imports the program: segment
builder, embedded cluster (controller, broker, one server with its
``ShardedQueryExecutor`` over the process's devices), REST endpoints on
loopback, the decision ledger. After ``chip_smoke.Served``.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import shutil
import time
import urllib.request

from typing import Any, Dict, List, Tuple

STORE_VERSION = 1
# a consuming segment with no row yet, the moment after a seal: the host
# "answers" it with nothing; no device work was declined
NOTHING_TO_SERVE = frozenset({"mutable_empty_watermark"})
STORES_CAP_BYTES = 16e9    # of segment stores kept under one data root
# rows the builders hold between them: 8 x 3M fit the one-chip machine's
# 40 GiB beside the oracle, 8 x 9M ran it out of memory (PERF.md, call 10)
BUILD_ROWS_AT_ONCE = 24_000_000


def _build_one(table: str, schema: Dict[str, Any], index: Dict[str, Any],
               i: int, num_segments: int, n: int, seed: int,
               out_dir: str) -> str:
    """Pool worker: one segment from the benchmark's own rows through the
    program's builder. numpy only, initialises no backend."""
    from pinot_tpu.segment import SegmentBuilder
    from pinot_tpu.spi import Schema
    from pinot_tpu.spi.table import IndexingConfig

    table_mod = importlib.import_module(f"benchmarks.tables.{table}")
    frame = table_mod.decode(table_mod.segment_codes(i, num_segments, n,
                                                     seed))
    name = f"seg_{i}"
    SegmentBuilder(Schema.from_dict(schema), name,
                   indexing_config=IndexingConfig.from_dict(index)
                   ).build(frame, out_dir)
    return name


def segment_store(config_name: str, config: Dict[str, Any], rows: int,
                  seed: int, data_root: str) -> Tuple[List[str], float]:
    """The cell's segments under a fixed name; a manifest keyed on what
    determines the bytes lets a second run of one seed load them."""
    import multiprocessing as mp

    table_mod = importlib.import_module(f"benchmarks.tables.{config['table']}")
    sizes = table_mod.segment_sizes(config["segments"], rows)
    out = os.path.join(data_root, f"{config_name}_seed{seed}_rows{rows}")
    digest = hashlib.sha256(json.dumps(
        [config["schema"], config["tableIndexConfig"]],
        sort_keys=True).encode()).hexdigest()[:16]
    want = {"version": STORE_VERSION, "table": config["table"],
            "rows": rows, "segments": len(sizes), "seed": seed,
            "tableConfig": digest}
    dirs = [os.path.join(out, f"seg_{i}") for i in range(len(sizes))]
    manifest = os.path.join(out, "manifest.json")
    try:
        with open(manifest) as f:
            if json.load(f) == want:
                return dirs, 0.0
    except (FileNotFoundError, ValueError):
        pass
    os.makedirs(out, exist_ok=True)
    t0 = time.perf_counter()
    jobs = [(config["table"], config["schema"], config["tableIndexConfig"],
             i, len(sizes), n, seed, out) for i, n in enumerate(sizes)]
    workers = max(1, min(len(jobs), os.cpu_count() or 1,
                         BUILD_ROWS_AT_ONCE // max(sizes)))
    if workers > 1:
        with mp.get_context("spawn").Pool(workers) as pool:
            pool.starmap(_build_one, jobs)
    else:
        for job in jobs:
            _build_one(*job)
    with open(manifest, "w") as f:
        json.dump(want, f)
    prune_stores(data_root, keep=out)
    return dirs, time.perf_counter() - t0


def prune_stores(data_root: str, keep: str,
                 cap_bytes: float = STORES_CAP_BYTES) -> None:
    """The stores of earlier seeds go, oldest first, until all that is
    left fits the cap: a star-tree store is 6 GB a seed, and a check runs
    a dozen seeds in one checkout."""
    stores = []
    for name in os.listdir(data_root):
        path = os.path.join(data_root, name)
        manifest = os.path.join(path, "manifest.json")
        if os.path.isfile(manifest):
            size = sum(os.path.getsize(os.path.join(d, f))
                       for d, _, files in os.walk(path) for f in files)
            stores.append((os.path.getmtime(manifest), path, size))
    total = sum(size for _, _, size in stores)
    for _, path, size in sorted(stores):
        if total <= cap_bytes:
            break
        if path != keep:
            shutil.rmtree(path, ignore_errors=True)
            total -= size


def device_info(chips: int, expect_platform: str) -> Dict[str, Any]:
    """Initialise the backend. Anything but the platform and the chips the
    cell asks for ends the run here."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != expect_platform or info["count"] < chips:
        raise SystemExit(f"bench: the cell needs {chips} {expect_platform} "
                         f"chip(s); JAX found {info}")
    from pinot_tpu.engine import ensure_compile_cache, ensure_x64

    ensure_x64()
    ensure_compile_cache()
    return info


def cache_entries() -> List[str]:
    """Compiled programs in the persistent cache (the access-time stamps
    JAX keeps beside them are not entries)."""
    import jax

    try:
        return sorted(n for n in
                      os.listdir(jax.config.jax_compilation_cache_dir)
                      if not n.endswith("-atime"))
    except (FileNotFoundError, TypeError):
        return []


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class Served:
    """Cluster, REST endpoints and the one server's admin API."""

    def __init__(self, config: Dict[str, Any], seg_dirs: List[str],
                 work_dir: str, stream_url: str = ""):
        """``stream_url``: the producer of a hybrid config (one with a
        ``realtime`` section), whose REALTIME table consumes from it."""
        from pinot_tpu.common.tracing import LEDGER
        from pinot_tpu.spi import Schema
        from pinot_tpu.spi.table import (IndexingConfig, TableConfig,
                                         TableType)
        from pinot_tpu.tools.cluster import EmbeddedCluster
        from pinot_tpu.transport import rest

        self.ledger_mark = LEDGER.snapshot()
        self.checks: Dict[str, int] = {}    # the harness's own queries'
        self.table = f"{config['table_name']}_OFFLINE"
        self.realtime = config.get("realtime")
        self.cluster = EmbeddedCluster(
            num_servers=config["servers"],
            data_dir=os.path.join(work_dir, "cluster"),
            query_timeout_s=900.0)
        self.apis: List[Any] = []
        try:
            offline = TableConfig(config["table_name"], TableType.OFFLINE,
                                  indexing_config=IndexingConfig.from_dict(
                                      config["tableIndexConfig"]))
            if self.realtime:
                offline.validation_config = self._time_split()
            self.cluster.create_table(offline,
                                      Schema.from_dict(config["schema"]))
            for d in seg_dirs:
                self.cluster.upload_segment_dir(self.table, d)
            if not self.cluster.wait_for_ev_converged(self.table,
                                                      timeout_s=900.0):
                raise RuntimeError("external view did not converge")
            self.server = next(iter(self.cluster.servers.values()))
            if self.realtime:
                self._add_realtime(config, stream_url)
            self.apis = list(rest.serve_cluster(self.cluster))
            admin = rest.ServerAdminApi(self.server)
            admin.start()
            self.apis.append(admin)
        except BaseException:
            self.close()
            raise
        self.broker_port = self.apis[1].port
        self.urls = {"server": f"http://127.0.0.1:{admin.port}",
                     "broker": f"http://127.0.0.1:{self.broker_port}"}

    # -- the REALTIME half of a hybrid config ---------------------------------
    def _time_split(self):
        """Both halves name the time column: the broker splits a query
        at the boundary only where the OFFLINE half has one."""
        from pinot_tpu.spi.table import SegmentsValidationConfig

        return SegmentsValidationConfig(
            time_column_name=self.realtime["time_column"], time_type="DAYS")

    def _add_realtime(self, config: Dict[str, Any], stream_url: str) -> None:
        # registers stream.type "socket" with the program's stream SPI
        from pinot_tpu.ingestion.socketstream import BROKER_URL_PROP
        from pinot_tpu.spi.table import (IndexingConfig,
                                         StreamIngestionConfig, TableConfig,
                                         TableType)

        rt = self.realtime
        self.realtime_table = f"{config['table_name']}_REALTIME"
        self.stream_meta = (f"{stream_url}/topics/{config['table_name']}"
                            f"/metadata")
        self.cluster.controller.add_table(TableConfig(
            config["table_name"], TableType.REALTIME,
            validation_config=self._time_split(),
            indexing_config=IndexingConfig.from_dict(rt["tableIndexConfig"]),
            stream_config=StreamIngestionConfig(
                stream_type="socket", topic=config["table_name"],
                segment_flush_threshold_rows=rt["flush_threshold_rows"],
                properties={BROKER_URL_PROP: stream_url})))

    def ingest(self) -> Dict[str, Any]:
        """The REALTIME half: a partition's rows appended (the stream's
        own metadata, read first) and consumed (its consuming segment's
        offset, else its last committed end offset; read in process), the
        LLC manager's committed segments and how many of them the server
        serves ONLINE, the consuming segments' docs."""
        from pinot_tpu.controller.state import ONLINE

        with urllib.request.urlopen(self.stream_meta, timeout=60) as r:
            appended = json.loads(r.read().decode())["latest"]
        store = self.cluster.store
        tdm = self.server.data_manager.get(self.realtime_table)
        view = store.get_external_view(self.realtime_table)
        consumed = [0] * self.realtime["partitions"]
        committed = online = docs = 0
        for md in store.segment_metadata_list(self.realtime_table):
            p = md.partition or 0
            if md.status == ONLINE:
                committed += 1
                online += (view.get(md.segment_name, {}).get(
                    self.server.instance_id) == ONLINE)
                consumed[p] = max(consumed[p], int(md.end_offset or 0))
                continue
            mgr = tdm.consuming_manager(md.segment_name) if tdm else None
            if mgr is not None:
                consumed[p] = max(consumed[p], mgr.current_offset.value)
                docs += mgr.segment.num_docs
        return {"appended": appended, "consumed": consumed,
                "committed": committed, "committedOnline": online,
                "consumingDocs": docs}

    def check_rows(self, sql: str) -> List[list]:
        """A query of the harness's own (not the window's traffic): its
        decisions are kept apart from ``ledger_breaches``."""
        from pinot_tpu.common.tracing import LEDGER

        mark = LEDGER.snapshot()
        try:
            return self.cluster.query_rows(sql)
        finally:
            for key, n in LEDGER.delta(mark).items():
                self.checks[key] = self.checks.get(key, 0) + n

    def wait_caught_up(self, rows: List[int], timeout_s: float = 900.0
                       ) -> Dict[str, Any]:
        """Until every partition has consumed ``rows[p]`` and the seals
        those rows caused are ONLINE."""
        flush = self.realtime["flush_threshold_rows"]
        seals = sum(n // flush for n in rows)
        deadline = time.monotonic() + timeout_s
        while True:
            got = self.ingest()
            if (all(c >= n for c, n in zip(got["consumed"], rows))
                    and got["committedOnline"] >= seals):
                return got
            if time.monotonic() > deadline:
                raise RuntimeError(f"the REALTIME half did not catch up in "
                                   f"{timeout_s}s: {got}, want {rows} "
                                   f"consumed and {seals} seals")
            time.sleep(0.05)

    def debug(self, role: str, path: str) -> Dict[str, Any]:
        with urllib.request.urlopen(self.urls[role] + path, timeout=60) as r:
            return json.loads(r.read().decode("utf-8"))

    def counters(self) -> Dict[str, Any]:
        """The program's own counts, read before and after a window."""
        out = {"launches": self.debug("server", "/debug/launches"),
               "memory": self.debug("server", "/debug/memory"),
               "scheduler": self.debug("server", "/debug/scheduler"),
               "broker": self.debug("broker", "/debug/scheduler"),
               "cache_entries": cache_entries()}
        if self.realtime:
            out["ingest"] = dict(self.ingest(), freshness=self.debug(
                "server", "/debug/freshness"))
        return out

    @staticmethod
    def compiled_between(before: Dict[str, Any], after: Dict[str, Any]
                         ) -> List[str]:
        """Programs the persistent cache gained between two readings."""
        return sorted(set(after["cache_entries"])
                      - set(before["cache_entries"]))

    def wait_staged(self, timeout_s: float = 600.0) -> Dict[str, Any]:
        """Background staging has finished when every segment is resident
        and the staged bytes stand still."""
        deadline = time.monotonic() + timeout_s
        last = -1
        while True:
            mem = self.debug("server", "/debug/memory")
            if mem["stagedBytes"] == last or time.monotonic() > deadline:
                return mem
            last = mem["stagedBytes"]
            time.sleep(0.5)

    def ledger_breaches(self, forbidden: List[str], platform: str
                        ) -> List[str]:
        """Decision-ledger keys over this cluster's life that say the
        device did not serve."""
        from pinot_tpu.common.tracing import LEDGER, parse_decision_key

        banned = set(forbidden)
        if platform == "cpu":     # the toy drive on the CPU records these
            banned -= {"cpu_default_backend", "pallas_disabled_on_backend"}
        bad = []
        for key, n in LEDGER.delta(self.ledger_mark).items():
            if n <= self.checks.get(key, 0):
                continue
            point, chosen, _declined, reason = parse_decision_key(key)
            if self.realtime and reason in NOTHING_TO_SERVE:
                continue
            if (reason in banned or reason.startswith("pallas_preflight_")
                    or point == "launch" or chosen == "host_engine"):
                bad.append(key)
        return bad

    def close(self) -> None:
        for api in self.apis:
            api.stop()
        self.cluster.shutdown()
