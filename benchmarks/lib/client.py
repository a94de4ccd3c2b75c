"""The load generator: a process of its own that never imports JAX.

``client.py <job.json> <out.json>``. One thread and one keep-alive
connection per client; latency is this process's clock from the moment a
request is sent (open loop: from the moment it was due) to the last byte of
its response. Every response body is kept and written out when the run is
over; the parent compares them after the window. A job with ``start_wall``
(a hybrid table's window, which a producer fills from that moment on)
starts then, and each record also carries ``sent_epoch`` and ``done_epoch``,
``time.time()`` read beside the two clock readings: the producer's log is
on that clock.

Runners:

- ``script``: client ``i`` sends the strings ``plans[i]`` names, in order,
  once (set-up uses it: the walk of the whole cycle, the warming bursts).
  With ``lockstep`` the clients send each step together.
- ``closed``: a queue walks the cycle from its entry of ``offsets``,
  again and again; client ``i`` takes the next string of queue
  ``i % len(offsets)`` when its last request has answered, until
  ``seconds`` have passed. One queue a client is each client on a walk
  of its own; one queue for all is upstream's runner. Requests in flight
  at the close are awaited.
- ``open``: requests are due at ``rate`` a second from the first, cycle
  order; ``clients`` connections take them as they come due. A request
  that finds no free connection goes out late and its wait counts.

Standard library only.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

from typing import Any, Dict, List


def _post(conn: http.client.HTTPConnection, path: str, sql: str):
    body = json.dumps({"sql": sql})
    conn.request("POST", path, body=body,
                 headers={"Content-Type": "application/json",
                          "Connection": "keep-alive"})
    resp = conn.getresponse()
    return resp.status, resp.read()


class Run:
    def __init__(self, job: Dict[str, Any]):
        self.job = job
        self.sqls: List[str] = job["sqls"]
        self.records: List[Dict[str, Any]] = []
        self.lock = threading.Lock()
        self.t0_wall = 0.0
        self.t0 = 0.0
        self.next_due = 0       # open loop: index of the next request due
        self.drawn = list(job.get("offsets", ()))   # closed: a queue's next
        self.barrier = (threading.Barrier(job["clients"])
                        if job.get("lockstep") else None)
        self.walls = job.get("start_wall") is not None

    def send(self, conn, client: int, step: int, index: int,
             due: float = None) -> None:
        sql = self.sqls[index]
        sent = time.perf_counter()
        sent_wall = time.time() if self.walls else None
        try:
            status, raw = _post(conn, self.job["path"], sql)
            error = None
        except (OSError, http.client.HTTPException) as e:
            status, raw, error = 0, b"", f"{type(e).__name__}: {e}"
            conn.close()
        done_wall = time.time() if self.walls else None
        done = time.perf_counter()
        start = sent if due is None else due
        rec = {"client": client, "step": step, "index": index,
               "sent_s": sent - self.t0, "done_s": done - self.t0,
               "latency_ms": (done - start) * 1e3, "status": status,
               "body": raw.decode("utf-8", "replace"), "error": error}
        if due is not None:
            rec["late_ms"] = (sent - due) * 1e3
        if self.walls:
            rec.update(sent_epoch=sent_wall, done_epoch=done_wall)
        with self.lock:
            self.records.append(rec)

    def connect(self) -> http.client.HTTPConnection:
        return http.client.HTTPConnection(
            self.job["host"], self.job["port"],
            timeout=self.job.get("timeout_s", 300.0))

    def closed(self, client: int) -> None:
        conn = self.connect()
        queue = client % len(self.drawn)
        deadline = self.t0 + self.job["seconds"]
        step = 0
        while time.perf_counter() < deadline:
            with self.lock:
                k = self.drawn[queue]
                self.drawn[queue] += 1
            self.send(conn, client, step, k % len(self.sqls))
            step += 1
        conn.close()

    def open(self, client: int) -> None:
        conn = self.connect()
        gap = 1.0 / self.job["rate"]
        total = int(self.job["seconds"] * self.job["rate"])
        while True:
            with self.lock:
                k = self.next_due
                self.next_due += 1
            if k >= total:
                break
            due = self.t0 + k * gap
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.send(conn, client, k, k % len(self.sqls), due=due)
        conn.close()

    def script(self, client: int) -> None:
        conn = self.connect()
        for step, index in enumerate(self.job["plans"][client]):
            if self.barrier is not None:
                self.barrier.wait()
            self.send(conn, client, step, index)
        conn.close()

    def run(self) -> Dict[str, Any]:
        job = self.job
        if job.get("start_wall") is not None:
            time.sleep(max(0.0, job["start_wall"] - time.time()))
        self.t0_wall = time.time()
        self.t0 = time.perf_counter()
        targets = {"closed": self.closed, "open": self.open,
                   "script": self.script}
        threads = [threading.Thread(target=targets[job["runner"]],
                                    args=(i,), name=f"client-{i}")
                   for i in range(job["clients"])]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return {"t0_wall": self.t0_wall,
                "elapsed_s": time.perf_counter() - self.t0,
                "records": sorted(self.records,
                                  key=lambda r: (r["sent_s"], r["client"]))}


def main(argv: List[str]) -> int:
    with open(argv[0]) as f:
        job = json.load(f)
    out = Run(job).run()
    if "jax" in sys.modules:
        raise RuntimeError("the client process imported JAX")
    with open(argv[1] + ".tmp", "w") as f:
        json.dump(out, f)
    os.replace(argv[1] + ".tmp", argv[1])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
