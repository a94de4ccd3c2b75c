"""A toy hybrid cell: ``userfacing``'s ``member_views`` at toy rows with a
REALTIME half of two stream partitions, and trailing-window traffic.

``drive`` runs it through ``run.run`` (``toy_cell`` in place of
``run.load_cell``) with one of ``FAULTS`` planted or none. Run as

    python3 -m benchmarks.tests.hybrid_toy '<job json>'

it drives in a process of its own and prints what the drive saw as its
last line: the tests drive it so on the CPU, and the same command with
``"platform": "tpu"`` drives it on the chip.
"""

import copy
import json
import sys
import time

from benchmarks import run as bench
from benchmarks.tables import member_views as mv

NAME = "member_views_hybrid.toy"
BASE = "userfacing.zipf_open_r80"
_load_cell = bench.load_cell    # taken before a drive puts toy_cell's in
ROWS = 16_000
SEGMENTS = 4


def toy_config(rate: float = 1_000.0, catch_up=(2_000, 2_600),
               drain_s: float = 20.0, freshness_s: float = 2.0) -> dict:
    """``userfacing.json`` with ``day`` the time column (DATE_TIME, days)
    and a ``realtime`` section: 2 partitions, a flush threshold low enough
    for a seal or more in each partition in a 6 s window."""
    config = copy.deepcopy(_load_cell(BASE)["config"])
    schema = config["schema"]
    schema["dimensionFieldSpecs"] = [
        f for f in schema["dimensionFieldSpecs"] if f["name"] != "day"]
    schema["dateTimeFieldSpecs"] = [{
        "name": "day", "dataType": "INT", "format": "1:DAYS:EPOCH",
        "granularity": "1:DAYS"}]
    index = copy.deepcopy(config["tableIndexConfig"])
    index["segmentPartitionConfig"]["columnPartitionMap"]["member_id"][
        "numPartitions"] = 2
    config.update(rows=ROWS, segments=SEGMENTS, realtime={
        "partitions": 2, "partition_column": "member_id",
        "partition_function": "Modulo", "time_column": "day",
        "rate_rows_per_s": rate, "flush_threshold_rows": 3_000,
        "catch_up_rows": list(catch_up), "freshness_limit_s": freshness_s,
        "drain_s": drain_s, "min_seals_in_window": 2,
        "tableIndexConfig": index})
    return config


def trailing_windows() -> list:
    """Windows that end today, the day after the offline half's last."""
    today = mv.STREAM_DAYS[1]
    return [[today - span, today] for span in mv.WINDOW_SPANS]


def toy_traffic(variants: int = 32) -> dict:
    """The lookup cell's four families at ``variants`` literal sets
    each, over windows that end today: an open loop of 20 requests/s on 4
    connections."""
    traffic = copy.deepcopy(_load_cell(BASE)["traffic"])
    traffic["domains"]["windows"] = trailing_windows()
    traffic.update(rate=20.0, clients=4, variants_per_flight=variants)
    return traffic


def toy_cell(config: dict = None):
    """A ``run.load_cell`` for the toy cell alone."""
    base = _load_cell(BASE)

    def load_cell(workload: str) -> dict:
        assert workload == NAME, workload
        return dict(base, cell=dict(base["cell"], name=NAME,
                                    config="member_views_hybrid",
                                    traffic="trailing_toy"),
                    config=config or toy_config(),
                    traffic=toy_traffic())

    return load_cell


SEED = 2 ** 31 + 4242
FAULTS = ("altered", "stale", "held_back", "dropped")


def plant(fault: str, freshness_s: float) -> None:
    """The timed path broken underneath, for the rest of the process:
    ``altered`` adds one to the first sum of every answer the broker
    reduces; ``stale`` has every response of a window answer from the
    stream as it stood 3 s more than ``freshness_s`` before it was sent
    (its stamps moved on); ``held_back`` has the consumers read nothing
    from the window on; ``dropped`` has every seal lose its segment's
    last row."""
    from pinot_tpu.broker.reduce import ReduceAccumulator
    from pinot_tpu.ingestion.realtime import RealtimeSegmentDataManager
    from pinot_tpu.segment.mutable import MutableSegment

    window = bench.run_window
    if fault == "altered":
        finish = ReduceAccumulator.finish

        def altered(self):
            table, stats, exceptions = finish(self)
            if table is not None and table.rows:
                row = list(table.rows[0])
                row[-1] = row[-1] + 1
                table.rows[0] = row
            return table, stats, exceptions

        ReduceAccumulator.finish = altered
    elif fault == "stale":
        def old(*a, **kw):
            win = window(*a, **kw)
            for rec in win["records"]:
                rec["sent_epoch"] += freshness_s + 3.0
                rec["done_epoch"] += freshness_s + 3.0
            return win

        bench.run_window = old
    elif fault == "held_back":
        def nothing(self, limit_offset=None):
            time.sleep(0.05)
            return 0

        def held(*a, **kw):
            RealtimeSegmentDataManager._index_batch = nothing
            return window(*a, **kw)

        bench.run_window = held
    elif fault == "dropped":
        build = MutableSegment.build_immutable

        def dropped(self, *a, **kw):
            self._num_docs -= 1
            try:
                return build(self, *a, **kw)
            finally:
                self._num_docs += 1

        MutableSegment.build_immutable = dropped
    else:
        raise ValueError(f"no fault {fault!r}")


def drive(data_root: str, fault: str = None, config: dict = None,
          seconds: float = 6.0, seed: int = SEED, trace: bool = False,
          platform: str = "cpu") -> dict:
    """The toy cell through ``run.run``: its result line, or the failed
    run's line, and each window's ingest at its end, the prefixes its
    responses were judged at and the judge's verdict."""
    seen = {"windows": []}
    judge = bench.judge

    def watched(served, setup, win, platform):
        verdict = judge(served, setup, win, platform)
        seen["windows"].append({
            "ingest_end": win["ingest_end"], "verdict": verdict,
            "prefixes": [r.get("prefix") for r in win["records"]]})
        return verdict

    config = config or toy_config()
    bench.load_cell = toy_cell(config)
    bench.judge = watched
    if fault:
        plant(fault, config["realtime"]["freshness_limit_s"])
    try:
        seen["line"] = json.loads(bench.run(
            NAME, seed, seconds, trace, expect_platform=platform, rows=ROWS,
            data_root=data_root, strict=False)[-1])
    except bench.RunFailed as e:
        seen["line"] = json.loads(bench.failed_line(e))
    return seen


if __name__ == "__main__":
    job = json.loads(sys.argv[1])
    if job.get("config") is not None:
        job["config"] = toy_config(**job["config"])
    print(json.dumps(drive(**job)), flush=True)
