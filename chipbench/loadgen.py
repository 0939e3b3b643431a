"""The open-loop load generator: one dispatcher thread that starts a client
thread for each request at its due time, whatever the server is doing. Every
latency is timed from the due time, so a stalled server costs the requests
behind the stall; how late each request really left is recorded too, so a
starved generator is not read as a fast server."""

import http.client
import json
import threading
import time


class OpenLoop:
    def __init__(self, host, port, path, schedule, timeout_s):
        self.host, self.port, self.path = host, port, path
        self.timeout_s = timeout_s
        self.schedule = schedule
        self._bodies = [
            json.dumps({"prompt": r["prompt"],
                        "max_new_tokens": r["max_new_tokens"]}).encode()
            for r in schedule
        ]
        # one record a request, written by its own client thread only
        self.records = [None] * len(schedule)
        self._threads = [None] * len(schedule)
        self._stop = threading.Event()
        self._dispatcher = threading.Thread(
            target=self._dispatch, name="chipbench-dispatch", daemon=True)
        self.t0 = None

    def start(self):
        self.t0 = time.perf_counter()
        self._dispatcher.start()
        return self.t0

    def _dispatch(self):
        for i, r in enumerate(self.schedule):
            wait = self.t0 + r["due_s"] - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            t = threading.Thread(target=self._client, args=(i,), daemon=True)
            t.start()
            self._threads[i] = t  # published once started: join() may join it

    def _client(self, i):
        rec = {"sent_s": time.perf_counter() - self.t0, "status": None,
               "tokens": 0, "done_s": None, "error": None}
        try:
            conn = http.client.HTTPConnection(
                self.host, self.port, timeout=self.timeout_s)
            try:
                conn.request("POST", self.path, body=self._bodies[i],
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                body = resp.read()
                rec["status"] = resp.status
                if resp.status == 200:
                    rec["tokens"] = len(json.loads(body)["tokens"])
                else:
                    rec["error"] = body[:200].decode("utf-8", "replace")
            finally:
                conn.close()
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["error"] = repr(e)
        rec["done_s"] = time.perf_counter() - self.t0
        self.records[i] = rec

    def stop_dispatch(self):
        self._stop.set()
        self._dispatcher.join()

    def join(self, indices, deadline_s):
        """Wait for the clients of `indices`, until `deadline_s` seconds from
        now at the latest. True if all ended."""
        end = time.perf_counter() + deadline_s
        for i in indices:
            # a request the dispatcher has not reached yet is waited for too
            while (self._threads[i] is None and self._dispatcher.is_alive()
                   and time.perf_counter() < end):
                time.sleep(0.01)
            t = self._threads[i]
            if t is not None:
                t.join(max(0.0, end - time.perf_counter()))
        return all(self.records[i] is not None for i in indices)

    def started(self):
        return [i for i, t in enumerate(self._threads) if t is not None]
