"""The stream of a hybrid table: a process of its own that never imports JAX.

``producer.py <job.json> <out.json>``. It serves, on loopback, the protocol
the program's ``socket`` stream plugin consumes (``ingestion/socketstream``:
``GET /topics/<topic>/metadata`` and ``GET /topics/<topic>/fetch``), and
appends rows to the topic's partitions in three phases:

- catch-up: at start, ``catch_up_rows[p]`` rows to partition ``p`` (the
  server consumes them during set-up);
- paused: nothing is appended (the warm-up);
- live: ``POST /live`` with ``start_wall`` and ``seconds`` appends
  ``int(rate_rows_per_s * seconds)`` rows as an open loop, what is due
  every ``BATCH_S`` from ``start_wall`` on (late ticks append what is due
  by then), and pauses again at ``start_wall + seconds``. Row ``g`` of the
  live rows (counted over every live phase) goes to partition
  ``g % partitions``.

A partition's rows are ``stream_codes(partition, offset, n, seed,
partitions)`` of the table module, generated when they are fetched: the
oracle regenerates the same rows. Every batch is logged as ``(wall time,
partition, end offset)``, the time read under the lock that makes the rows
visible, so that no fetch saw them before it; ``GET /log`` returns the
appended counts and, unless ``batches=0``, the log and each live tick's
lateness. ``POST /stop`` ends the process, which
writes its summary to ``<out.json>``. ``<out.json>.ready`` carries the port
once the catch-up rows are in.

Standard library and numpy only.
"""

from __future__ import annotations

import bisect
import collections
import importlib
import json
import os
import sys
import threading
import time
import urllib.parse

from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List

BATCH_S = 0.01
BLOCKS_KEPT = 8         # rendered blocks a partition keeps for the fetches


class Stream:
    """The partitions' appended counts, the batch log and the rows."""

    def __init__(self, job: Dict[str, Any]):
        self.table_mod = importlib.import_module(
            f"benchmarks.tables.{job['table']}")
        self.seed, self.topic = job["seed"], job["topic"]
        self.partitions = job["partitions"]
        self.rate = float(job["rate_rows_per_s"])
        self.lock = threading.Lock()
        self.appended = [0] * self.partitions
        self.batches: List[List[float]] = []     # [wall, partition, end]
        # a partition's batch ends and their stamps (ms), for the fetches
        self.ends: List[List[int]] = [[] for _ in range(self.partitions)]
        self.stamps: List[List[int]] = [[] for _ in range(self.partitions)]
        self.late_ms: List[float] = []
        self.live_rows = 0          # live rows appended over every phase
        self.lives_done = 0         # live phases ended
        self.blocks: "collections.OrderedDict" = collections.OrderedDict()
        self.render_lock = threading.Lock()     # guards blocks
        self.windows: "collections.deque" = collections.deque()
        self.wake = threading.Event()
        self.stopping = threading.Event()

    def append(self, counts: List[int]) -> None:
        """One batch: ``counts[p]`` more rows visible in partition ``p``."""
        with self.lock:
            now = time.time()
            for p, n in enumerate(counts):
                if n:
                    self.appended[p] += n
                    self.batches.append([now, p, self.appended[p]])
                    self.ends[p].append(self.appended[p])
                    self.stamps[p].append(int(now * 1e3))

    def append_live(self, upto: int) -> None:
        """Live rows ``[live_rows, upto)``, each to partition ``g % P``."""
        counts = [0] * self.partitions
        for p in range(self.partitions):
            counts[p] = (len(range(p, upto, self.partitions))
                         - len(range(p, self.live_rows, self.partitions)))
        self.live_rows = upto
        self.append(counts)

    # -- the live phase, on the main thread ----------------------------------
    def run(self) -> None:
        while not self.stopping.is_set():
            self.wake.wait(0.1)
            self.wake.clear()
            while self.windows and not self.stopping.is_set():
                start, seconds = self.windows.popleft()
                self.live(start, seconds)
                with self.lock:
                    self.lives_done += 1

    def live(self, start: float, seconds: float) -> None:
        base, total = self.live_rows, int(self.rate * seconds)
        tick = 1
        time.sleep(max(0.0, start - time.time()))
        while not self.stopping.is_set():
            due_at = start + tick * BATCH_S
            time.sleep(max(0.0, due_at - time.time()))
            now = time.time()
            with self.lock:
                self.late_ms.append((now - due_at) * 1e3)
            due = min(total, int(self.rate * (now - start)))
            if due > self.live_rows - base:
                self.append_live(base + due)
            if due >= total:
                return
            tick = max(tick + 1, int((now - start) / BATCH_S) + 1)

    # -- the protocol ---------------------------------------------------------
    def _block(self, p: int, block: int) -> List[str]:
        """Payloads of a block of offsets, rendered once."""
        key = (p, block)
        with self.render_lock:
            if key in self.blocks:
                self.blocks.move_to_end(key)
                return self.blocks[key]
            size = self.table_mod.STREAM_BLOCK
            cols = self.table_mod.decode(self.table_mod.stream_codes(
                p, block * size, size, self.seed, self.partitions))
            names = list(cols)
            values = [cols[k].tolist() for k in names]
            rendered = [json.dumps(dict(zip(names, row)))
                        for row in zip(*values)]
            self.blocks[key] = rendered
            while len(self.blocks) > BLOCKS_KEPT * self.partitions:
                self.blocks.popitem(last=False)
            return rendered

    def fetch(self, p: int, offset: int, max_messages: int) -> bytes:
        with self.lock:
            end = min(self.appended[p], offset + max(0, max_messages))
            ends, stamps = self.ends[p], self.stamps[p]
            s = bisect.bisect_right(ends, offset)
            ts = [stamps[bisect.bisect_right(ends, off, s)]
                  for off in range(offset, end)]
        size = self.table_mod.STREAM_BLOCK
        parts = []
        for off, stamp in zip(range(offset, end), ts):
            payload = self._block(p, off // size)[off % size]
            parts.append(f'{{"payload": {payload}, "offset": {off}, '
                         f'"ts": {stamp}}}')
        return (f'{{"messages": [{", ".join(parts)}], '
                f'"nextOffset": {max(end, offset)}}}').encode()

    def metadata(self) -> Dict[str, Any]:
        with self.lock:
            return {"numPartitions": self.partitions,
                    "earliest": [0] * self.partitions,
                    "latest": list(self.appended)}

    def log(self, batches: bool = True) -> Dict[str, Any]:
        with self.lock:
            out = {"appended": list(self.appended),
                   "lives_done": self.lives_done}
            if batches:
                out.update(batches=list(self.batches),
                           late_ms=list(self.late_ms))
            return out


def handler(stream: Stream):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            pass

        def _send(self, code: int, raw: bytes) -> None:
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(raw)))
            self.end_headers()
            self.wfile.write(raw)

        def do_GET(self):
            url = urllib.parse.urlparse(self.path)
            parts = url.path.strip("/").split("/")
            q = urllib.parse.parse_qs(url.query)
            if parts == ["topics", stream.topic, "metadata"]:
                self._send(200, json.dumps(stream.metadata()).encode())
            elif parts == ["topics", stream.topic, "fetch"]:
                self._send(200, stream.fetch(
                    int(q["partition"][0]), int(q["offset"][0]),
                    int(q.get("max", ["5000"])[0])))
            elif parts == ["log"]:
                self._send(200, json.dumps(stream.log(
                    q.get("batches", ["1"])[0] != "0")).encode())
            else:
                self._send(404, b'{"error": "no such endpoint"}')

        def do_POST(self):
            n = int(self.headers.get("Content-Length") or 0)
            body = json.loads(self.rfile.read(n).decode()) if n else {}
            if self.path == "/live":
                stream.windows.append((float(body["start_wall"]),
                                       float(body["seconds"])))
                stream.wake.set()
                self._send(200, b"{}")
            elif self.path == "/stop":
                stream.stopping.set()
                stream.wake.set()
                self._send(200, b"{}")
            else:
                self._send(404, b'{"error": "no such endpoint"}')

    return Handler


def main(argv: List[str]) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    with open(argv[0]) as f:
        job = json.load(f)
    stream = Stream(job)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler(stream))
    httpd.daemon_threads = True
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    try:
        stream.append(list(job["catch_up_rows"]))
        with open(argv[1] + ".ready.tmp", "w") as f:
            json.dump({"port": httpd.server_address[1]}, f)
        os.replace(argv[1] + ".ready.tmp", argv[1] + ".ready")
        stream.run()
    finally:
        httpd.shutdown()
        httpd.server_close()
    if "jax" in sys.modules:
        raise RuntimeError("the producer process imported JAX")
    out = dict(stream.log(), live_rows=stream.live_rows)
    with open(argv[1] + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(argv[1] + ".tmp", argv[1])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
