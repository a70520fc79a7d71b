"""The load generator: a child process, standard library only (it must not
import JAX: the parent holds the chip): ``http.client`` connections that
stamp every SSE event with the monotonic clock as it is read.

    python -m chipbench.loadgen          # spec as one JSON line on stdin

Closed loop: ``clients`` threads, each sends its next request when the last
one ends (after ``think_time_s``). The loop starts at once (the ramp); when
every client has had a first token the child prints ``{"event": "ramped"}``
and reads one more JSON line, ``{"window": [t0, t1]}`` on the system-wide
monotonic clock (``time.monotonic`` is CLOCK_MONOTONIC, shared by parent and
child). No request is started after t1. The child then waits (up to
``first_token_wait_s``) for the first token of every request sent inside
the window, closes what is still streaming, prints one JSON line of raw
records and exits. It reduces nothing: the parent does. Beside the records
it says what it cost itself over the window (``generator``: its CPU seconds
and context switches from the window's line to t1, the machine's core
count), so that a generator that competes with the server for a core shows.
"""

import http.client
import json
import os
import socket
import sys
import threading
import time

from chipbench import traffic
from chipbench.harness import process_usage  # standard library only, like this file


class _Client(threading.Thread):
    def __init__(self, idx, spec, plan, state):
        super().__init__(daemon=True, name=f"client-{idx}")
        self.idx, self.spec, self.plan, self.state = idx, spec, plan, state
        self.records = []
        self.sock = None

    def run(self):
        spec, state = self.spec, self.state
        n_clients = spec["traffic"]["clients"]
        turn = 0
        while not state["stop"].is_set():
            index = self.idx + turn * n_clients
            p_len, o_len = self.plan[index % len(self.plan)]
            if turn == 0 and spec["traffic"].get("stagger_first"):
                # clients start together; cutting each one's FIRST answer to
                # another fraction of its length spreads their phases at
                # once, as they are spread in a loop that has run for long
                o_len = max(4, round(o_len * (self.idx + 1) / n_clients))
            turn += 1
            if state["t1"] is not None and time.monotonic() >= state["t1"]:
                return
            self.one(index, p_len, o_len)
            if spec["traffic"].get("think_time_s"):
                time.sleep(spec["traffic"]["think_time_s"])

    def one(self, index, p_len, o_len):
        spec = self.spec
        prompt = traffic.prompt_tokens(spec["seed"], index, p_len, spec["vocab_size"])
        body = json.dumps({"prompt": prompt, "max_tokens": o_len, "stream": True})
        rec = {"index": index, "client": self.idx, "prompt_len": p_len, "max_tokens": o_len,
               "status": None, "t_first": None, "events": [], "done": False}
        self.records.append(rec)
        rec["t_ready"] = time.monotonic()
        conn = http.client.HTTPConnection("127.0.0.1", spec["port"], timeout=600)
        try:
            conn.connect()
            self.sock = conn.sock  # kept: http.client hands it to the response and forgets it
            rec["t_send"] = time.monotonic()
            conn.request("POST", "/v1/completions", body, {"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                resp.read()
                return
            while True:
                line = resp.readline()
                if not line:
                    break
                if not line.startswith(b"data: "):
                    continue
                if b"[DONE]" in line:
                    rec["done"] = True
                    break
                now = time.monotonic()
                n = len(json.loads(line[6:])["choices"][0]["token_ids"])
                if n:
                    if rec["t_first"] is None:
                        rec["t_first"] = now
                        self.state["first"].set()
                    rec["events"].append((now, n))
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["error"] = repr(e)  # a closed connection at the end of the run lands here
        finally:
            rec["t_end"] = time.monotonic()
            conn.close()


def main():
    spec = json.loads(sys.stdin.readline())
    plan = traffic.request_plan(spec["traffic"], spec["seed"])
    state = {"stop": threading.Event(), "t1": None, "first": threading.Event()}
    clients = [_Client(i, spec, plan, state) for i in range(spec["traffic"]["clients"])]
    for c in clients:
        c.start()
    deadline = time.monotonic() + spec["ramp_timeout_s"]
    while not all(any(r["t_first"] for r in c.records) for c in clients):
        if time.monotonic() > deadline:
            print(json.dumps({"event": "ramp_timeout"}), flush=True)
            return 1
        time.sleep(0.05)
    print(json.dumps({"event": "ramped", "t": time.monotonic()}), flush=True)
    t0, t1 = json.loads(sys.stdin.readline())["window"]
    state["t1"] = t1
    usage_at_t0 = process_usage()
    while time.monotonic() < t1:
        time.sleep(min(0.05, max(0.0, t1 - time.monotonic())))
    usage = process_usage()
    generator = dict({k: usage[k] - usage_at_t0[k] for k in usage}, cpu_count=os.cpu_count(),
                     threads=len(clients))
    # every request sent inside the window gets its chance at a first token
    wait_until = t1 + spec["first_token_wait_s"]

    def pending():
        return [r for c in clients for r in c.records
                if t0 <= r.get("t_send", -1) < t1 and r["t_first"] is None
                and "t_end" not in r]
    while pending() and time.monotonic() < wait_until:
        time.sleep(0.02)
    state["stop"].set()
    t_stop = time.monotonic()
    for c in clients:  # cut what still streams; the gateway sees the disconnect
        if c.sock is not None:
            try:
                c.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass  # already closed: that request had ended
    for c in clients:
        c.join(timeout=30)
    records = [r for c in clients for r in c.records]
    print(json.dumps({"event": "records", "t_stop": t_stop, "generator": generator,
                      "records": records}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
