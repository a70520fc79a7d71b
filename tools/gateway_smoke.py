#!/usr/bin/env python
"""CI gateway smoke: the full lifecycle of ``python -m deepspeed_tpu.serving``
as a black box, on an ephemeral port with the tiny model.

Asserts, in one server process:
  1. the GATEWAY_READY line appears with a bound port;
  2. a streamed completion returns the requested number of SSE token chunks
     and a terminating ``data: [DONE]``;
  3. under a full queue (1 slot, queue depth 1, 3 concurrent requests) at
     least one request sheds with 429 + an integer ``Retry-After`` — and
     every non-shed request completes;
  4. ``/v1/metrics`` reports a bounded compiled-program count (the O(1)
     fused-path guard holds through the gateway, not just in unit tests);
  5. SIGTERM drains cleanly: the server finishes admitted work and exits 0.

Exit code 0 = all good (one OK line per check); nonzero with a message
otherwise. No third-party deps (stdlib http.client only).
"""

import http.client
import json
import signal
import subprocess
import sys
import threading
import time


def fail(msg):
    print(f"GATEWAY_SMOKE FAIL: {msg}", flush=True)
    sys.exit(1)


def request(port, body, out, timeout=180):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out.append((resp.status, dict(resp.getheaders()), resp.read()))
    except Exception as e:  # noqa: BLE001 — collected, asserted by the caller
        out.append(("error", {}, str(e).encode()))
    finally:
        conn.close()


def main():
    # the server is a child and takes whatever backend JAX finds: CI pins
    # JAX_PLATFORMS=cpu itself (tools/ci_check.sh); this parent never touches
    # JAX, so on a machine with a chip the child gets the chip
    proc = subprocess.Popen(
        [sys.executable, "-m", "deepspeed_tpu.serving", "--model", "tiny",
         "--dtype", "float32", "--port", "0", "--num-slots", "1",
         "--max-queue-depth", "1"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    try:
        port = None
        deadline = time.time() + 180
        while time.time() < deadline:
            line = proc.stdout.readline()
            if not line:
                fail("server exited before GATEWAY_READY")
            if "GATEWAY_READY" in line:
                port = json.loads(line[line.index("{"):])["port"]
                break
        if port is None:
            fail("no GATEWAY_READY within 180s")
        print(f"ok: ready on port {port}", flush=True)

        # -- streamed completion ------------------------------------------
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=180)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": [5, 6, 7, 8, 9], "max_tokens": 8,
                                 "stream": True}), {})
        resp = conn.getresponse()
        if resp.status != 200:
            fail(f"stream status {resp.status}")
        raw = resp.read().decode()
        conn.close()
        # an event carries a row's tokens of one landing (1 to steps_per_sync
        # of them): count the tokens inside the events, not the events
        events = [json.loads(line[6:])["choices"][0]["token_ids"]
                  for line in raw.splitlines()
                  if line.startswith("data: {")]
        n_tokens = sum(len(toks) for toks in events)
        if n_tokens != 8 or "data: [DONE]" not in raw:
            fail(f"stream returned {n_tokens} tokens in {len(events)} events, "
                 f"DONE={'[DONE]' in raw}")
        print(f"ok: streamed 8 tokens in {len(events)} SSE events + [DONE]", flush=True)

        # -- shed under a full queue --------------------------------------
        # Deterministic, not a thread race: park a long request in the single
        # slot (its first SSE chunk proves it was ADMITTED), then burst 3
        # more at the depth-1 queue — one queues, the rest MUST 429 while
        # the occupier is still decoding. 100 tokens ~ the longest budget the
        # tiny model's 128-token KV slot fits.
        occ = http.client.HTTPConnection("127.0.0.1", port, timeout=180)
        occ.request("POST", "/v1/completions",
                    json.dumps({"prompt": [1, 2, 3], "max_tokens": 100,
                                "stream": True}), {})
        occ_resp = occ.getresponse()
        if occ_resp.status != 200 or not occ_resp.readline().startswith(b"data:"):
            fail("slot-occupier request did not start streaming")
        results = []
        threads = [threading.Thread(target=request, args=(
            port, {"prompt": [1, 2, 3], "max_tokens": 16}, results))
            for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        occ_resp.read()  # drain the occupier to completion
        occ.close()
        codes = [status for status, _, _ in results]
        if codes.count(429) < 1:
            fail(f"no 429 under overload: {codes}")
        for status, headers, body in results:
            if status == 429:
                retry = headers.get("Retry-After")
                if retry is None or not retry.isdigit() or int(retry) < 1:
                    fail(f"429 without sane Retry-After: {retry!r}")
            elif status == 200:
                if len(json.loads(body)["choices"][0]["token_ids"]) != 16:
                    fail("accepted request truncated")
            else:
                fail(f"unexpected status {status}: {body[:200]}")
        print(f"ok: overload shed {codes.count(429)}/3 with Retry-After",
              flush=True)

        # -- compile-count guard through the gateway ----------------------
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/v1/metrics")
        metrics = json.loads(conn.getresponse().read())
        conn.close()
        compiled = metrics["scheduler"]["compiled_programs"]
        # the bound is the server's start-up warm (warm_programs(ladder=False)):
        # (K, C), (1, C) and (K, 1), greedy and sampled, and the slot copy.
        # The traffic above must add none
        if not (1 <= compiled <= 7):
            fail(f"compiled-program bound violated: {compiled}")
        print(f"ok: compiled programs bounded ({compiled} <= 7)", flush=True)

        # -- SIGTERM drain -------------------------------------------------
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        if rc != 0:
            fail(f"drain exit code {rc}")
        print("ok: SIGTERM drained, exit 0", flush=True)
        print("GATEWAY_SMOKE PASS", flush=True)
        return 0
    finally:
        if proc.poll() is None:
            proc.kill()


if __name__ == "__main__":
    sys.exit(main())
