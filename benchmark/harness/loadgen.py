"""The load generator: sends a plan to the server over HTTP at the
instants the plan gives, from this (parent) process, standard library
only. One timer thread hands each request, when it is due, to a few
sender threads; nothing polls the server for a request's state.

Clocks: ``time.perf_counter`` is CLOCK_MONOTONIC on Linux, one clock for
every process of the host, so a due instant here and a token stamp taken
in the server child are on the same axis.
"""

from __future__ import annotations

import http.client
import json
import queue
import threading
import time
from typing import Any, Dict, List, Optional

from benchmark.harness.plan import body_of

SENDERS = 8


class Sender:
    """A pool of threads that POST requests and note, per request, the
    instant it was sent, the round trip and the status."""

    def __init__(self, host: str, port: int, n: int = SENDERS) -> None:
        self.host, self.port = host, port
        self._q: "queue.Queue[Optional[Dict]]" = queue.Queue()
        self.sent: List[Dict[str, Any]] = []
        self._mu = threading.Lock()
        self._threads = [threading.Thread(target=self._run, daemon=True,
                                          name=f"bench-send-{i}")
                         for i in range(n)]
        for t in self._threads:
            t.start()

    def submit(self, req: Dict[str, Any]) -> None:
        self._q.put(req)

    def _post(self, req: Dict[str, Any]) -> Dict[str, Any]:
        user = req.get("conversation_id") or "bench"
        data = json.dumps(body_of(req, user_id=user)).encode()
        t0 = time.perf_counter()
        status, err = 0, ""
        try:
            conn = http.client.HTTPConnection(self.host, self.port,
                                              timeout=30.0)
            try:
                conn.request("POST", "/api/v1/messages", body=data,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                raw = resp.read()
                status = resp.status
                if status != 202:
                    err = raw.decode("utf-8", "replace")[:200]
            finally:
                conn.close()
        except OSError as e:
            err = f"{type(e).__name__}: {e}"
        t1 = time.perf_counter()
        return {"id": req["id"], "t_sent": t0, "rtt_s": t1 - t0,
                "status": status, "error": err}

    def _run(self) -> None:
        while True:
            req = self._q.get()
            if req is None:
                return
            out = self._post(req)
            with self._mu:
                self.sent.append(out)

    def results(self) -> Dict[str, Dict[str, Any]]:
        """What has been sent so far, by request id."""
        with self._mu:
            return {s["id"]: s for s in self.sent}

    def close(self) -> None:
        for _ in self._threads:
            self._q.put(None)
        for t in self._threads:
            t.join(timeout=35.0)


def run_open(plan: List[Dict[str, Any]], sender: Sender, t_open: float,
             stop: threading.Event) -> threading.Thread:
    """Send every request of ``plan`` at ``t_open + due`` (``due`` may
    be negative: the ramp). Returns the timer thread."""

    def timer() -> None:
        for req in plan:
            when = t_open + req["due"]
            while True:
                left = when - time.perf_counter()
                if left <= 0 or stop.is_set():
                    break
                time.sleep(min(left, 0.05) if left > 0.002 else 0)
            if stop.is_set():
                return
            sender.submit(req)

    th = threading.Thread(target=timer, daemon=True, name="bench-timer")
    th.start()
    return th


class ClosedLoop:
    """Each client sends its next request when its last one ended. The
    server child says so with one line on its reply pipe (``on_done``
    is called from the pipe's reader thread): no request is polled."""

    def __init__(self, clients: List[List[Dict[str, Any]]],
                 sender: Sender) -> None:
        self.clients = clients
        self.sender = sender
        self.next_of = [0] * len(clients)
        self.owner: Dict[str, int] = {}
        self.stopped = False
        self.exhausted = 0
        self._mu = threading.Lock()

    def _send_next(self, c: int) -> None:
        with self._mu:
            k = self.next_of[c]
            if self.stopped:
                return
            if k >= len(self.clients[c]):
                self.exhausted += 1
                return
            self.next_of[c] = k + 1
            req = self.clients[c][k]
            self.owner[req["id"]] = c
        self.sender.submit(req)

    def start(self) -> None:
        for c in range(len(self.clients)):
            self._send_next(c)

    def on_done(self, rid: str) -> None:
        c = self.owner.get(rid)
        if c is not None:
            self._send_next(c)

    def stop(self) -> None:
        with self._mu:
            self.stopped = True


def wait_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))
