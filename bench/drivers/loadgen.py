"""Open-loop load generator for the served cells: a child process that
never imports JAX.

    python loadgen.py < params.json

It reads one JSON object of parameters from its first line of standard
input, talks to the daemon over loopback TCP through
``SchedulerClient`` (``connections`` of them), and speaks on standard
output one JSON object per line:

1. Set-up: it replays the first ``prefill_jobs`` jobs in simulated time
   on one connection, as fast as replies come back (submit each job at
   its arrival, ``done`` each placed job when its duration has
   elapsed), then prints ``{"event": "ready", ...}`` and waits for a
   line on standard input.
2. Window: simulated time then runs ``compression`` times faster than
   the wall clock. Submits are due at their arrivals; a ``done`` is due
   when a placed job's duration has elapsed since the reply that placed
   it. A dispatcher hands each op to the first free connection when it
   is due; ops due after ``window_s`` are not sent. After the close it
   waits up to ``grace_s`` for every op already due.
3. It prints ``{"event": "done", "ops": [...], "prefill": [...]}`` and
   exits. Each op is ``[op, job_id, due, sent, replied, rid, reply]``
   on ``time.perf_counter`` (one clock for every process of the
   machine); ``replied`` is null for an op that got no reply.
"""
from __future__ import annotations

import heapq
import itertools
import json
import os
import queue
import sys
import threading
import time
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                     # bench/
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from benchlib import philly  # noqa: E402
from repro.serve.scheduler.client import SchedulerClient  # noqa: E402

PLACED = "placed"


def _strip(reply: Dict[str, Any]) -> Dict[str, Any]:
    """The reply without its transport fields (sequence number, epoch)."""
    return {k: v for k, v in reply.items() if k not in ("seq", "epoch")}


def _call(client: SchedulerClient, op: str, job) -> Dict[str, Any]:
    """One op's reply. A refusal by the daemon comes back as a reply
    with ``ok`` false; a failure of the transport (timeout, lost
    connection) raises."""
    fields = ({"shape": list(job.shape), "job_id": job.job_id}
              if op == "submit" else {"job_id": job.job_id})
    try:
        return client.call(op, **fields)
    except RuntimeError as e:
        return {"ok": False, "error": str(e), "seq": client._seq}


def _started(op: str, reply: Dict[str, Any]) -> List[int]:
    """Jobs this reply says were placed."""
    if op == "submit":
        return [reply["job_id"]] if reply.get("outcome") == PLACED else []
    return [s["job_id"] for s in reply.get("started", [])
            if s.get("outcome") == PLACED]


def prefill(client: SchedulerClient, jobs, by_id) -> Dict[str, Any]:
    """Replay ``jobs`` in simulated time; returns the ops made and the
    jobs still running, as (simulated finish, job id)."""
    finishing: List = []
    ops: List = []
    for job in jobs:
        now = job.arrival
        while finishing and finishing[0][0] <= now:
            _, jid = heapq.heappop(finishing)
            reply = _call(client, "done", by_id[jid])
            ops.append(["done", jid, None, None, None,
                        f"{client.client_id}:{reply['seq']}", _strip(reply)])
            for sid in _started("done", reply):
                heapq.heappush(finishing, (now + by_id[sid].duration, sid))
        reply = _call(client, "submit", job)
        ops.append(["submit", job.job_id, None, None, None,
                    f"{client.client_id}:{reply['seq']}", _strip(reply)])
        for sid in _started("submit", reply):
            heapq.heappush(finishing, (now + job.duration, sid))
    return {"ops": ops, "finishing": finishing,
            "sim_now": jobs[-1].arrival if jobs else 0.0}


class Window:
    """The open-loop window: a dispatcher and one worker per connection."""

    def __init__(self, clients: List[SchedulerClient], by_id, params):
        self.clients = clients
        self.by_id = by_id
        self.compression = float(params["compression"])
        self.window_s = float(params["window_s"])
        self.grace_s = float(params["grace_s"])
        self._heap: List = []
        self._seq = itertools.count()
        self._cv = threading.Condition()
        self._work: "queue.Queue" = queue.Queue()
        self.records: List[List[Any]] = []
        self._outstanding = 0
        self._lock = threading.Lock()

    def _schedule(self, due: float, op: str, job_id: int) -> None:
        with self._cv:
            heapq.heappush(self._heap, (due, next(self._seq), op, job_id))
            self._cv.notify()

    def _worker(self, client: SchedulerClient) -> None:
        while True:
            rec = self._work.get()
            if rec is None:
                return
            op, job_id = rec[0], rec[1]
            rec[3] = time.perf_counter()
            try:
                reply = _call(client, op, self.by_id[job_id])
                replied = time.perf_counter()
                for sid in _started(op, reply):
                    self._schedule(replied + self.by_id[sid].duration
                                   / self.compression, "done", sid)
                rec[5] = f"{client.client_id}:{reply['seq']}"
                rec[6] = _strip(reply)
                rec[4] = replied
            except Exception as e:  # noqa: BLE001 -- counted as failed
                rec[6] = {"error": f"{type(e).__name__}: {e}"}
            with self._lock:
                self._outstanding -= 1

    def run(self, start: float, submits, running) -> None:
        """``submits``: (simulated arrival, job id) after the set-up;
        ``running``: (simulated finish, job id) still running; simulated
        time ``sim0`` maps to wall time ``start``."""
        for arrival, jid in submits:
            self._schedule(start + arrival / self.compression, "submit", jid)
        for finish, jid in running:
            self._schedule(start + finish / self.compression, "done", jid)
        close = start + self.window_s
        workers = [threading.Thread(target=self._worker, args=(c,),
                                    daemon=True) for c in self.clients]
        for w in workers:
            w.start()
        while True:
            with self._cv:
                if self._heap and self._heap[0][0] > close:
                    self._heap.clear()     # due after the close: not sent
                if not self._heap:
                    with self._lock:
                        idle = self._outstanding == 0
                    if idle or time.perf_counter() > close + self.grace_s:
                        break
                    self._cv.wait(0.01)
                    continue
                due = self._heap[0][0]
                wait = due - time.perf_counter()
                if wait > 0:
                    self._cv.wait(wait)
                    continue
                _, _, op, jid = heapq.heappop(self._heap)
            rec = [op, jid, due, None, None, None, None]
            with self._lock:
                self._outstanding += 1
                self.records.append(rec)
            self._work.put(rec)
        # An op that never reached a connection, or whose reply did not
        # come within the grace, keeps ``replied`` null: it failed.
        while True:
            try:
                self._work.get_nowait()
            except queue.Empty:
                break
        for _ in workers:
            self._work.put(None)
        for w in workers:
            w.join(timeout=1.0)


def main() -> int:
    params = json.loads(sys.stdin.readline())
    assert "jax" not in sys.modules, "the load generator must not import JAX"
    pool = philly.jobs(params["philly"], params["num_jobs"], params["seed"],
                       head=params["prefill_jobs"], order=params["order"])
    by_id = {j.job_id: j for j in pool}
    address = tuple(params["address"])
    clients = [SchedulerClient(address, op_timeout=params["op_timeout_s"])
               for _ in range(params["connections"])]
    try:
        n_pre = params["prefill_jobs"]
        t0 = time.perf_counter()
        pre = prefill(clients[0], pool[:n_pre], by_id)
        sim0 = pre["sim_now"]
        print(json.dumps({"event": "ready", "prefill_s":
                          time.perf_counter() - t0,
                          "prefill_ops": len(pre["ops"]),
                          "running": len(pre["finishing"])}), flush=True)
        if not sys.stdin.readline():
            return 1
        window = Window(clients, by_id, params)
        start = time.perf_counter() + params["lead_s"]
        window.run(start,
                   [(j.arrival - sim0, j.job_id) for j in pool[n_pre:]],
                   [(f - sim0, jid) for f, jid in pre["finishing"]])
        print(json.dumps({"event": "done", "start": start,
                          "close": start + params["window_s"],
                          "ops": window.records,
                          "prefill": pre["ops"]}), flush=True)
    finally:
        for c in clients:
            c.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
