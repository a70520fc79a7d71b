"""Serving-gateway tests: e2e localhost HTTP over the scheduler.

Covers the acceptance criteria the scheduler tests can't: SSE streaming
parity with direct ``submit()`` (bit-identical tokens through a real
socket), overload shedding (429 + sane ``Retry-After``, bounded queue),
deadline/disconnect cancellation freeing KV slots, DRR fairness under
tenant skew, and graceful drain. All CPU-runnable on the tiny model; the
HTTP client side is stdlib ``http.client`` — same dependency budget as the
gateway itself.
"""

import http.client
import json
import threading
import time

import numpy as np
import pytest
import jax

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.serving import FairQueue, Gateway, QueueFull

PROMPT = [5, 6, 7, 8, 9]


def make_engine(params=None, num_slots=2, **cfg):
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
    config = {"dtype": "float32",
              "continuous_batching": {"enabled": True, "num_slots": num_slots}}
    config.update(cfg)
    return deepspeed_tpu.init_inference("tiny", config=config, params=params)


@pytest.fixture(scope="module")
def baseline():
    """Shared weights + the direct-submit reference tokens."""
    eng = make_engine()
    params = jax.device_get(eng.params)
    ref = eng.scheduler().submit(PROMPT, max_new_tokens=8).result()
    return params, np.asarray(ref)


def start_gateway(params, num_slots=2, **gw_overrides):
    eng = make_engine(params=params, num_slots=num_slots)
    gw = Gateway(eng, port=0, **gw_overrides)
    gw.start_background()
    return gw


def post(port, body, timeout=120):
    """One blocking completion request; returns (status, headers, body)."""
    body = dict(body)
    headers = {"Content-Type": "application/json", **body.pop("_headers", {})}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body), headers)
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def sse_tokens(raw):
    """Parse an SSE byte stream into (token list, finish_reason, saw_done)."""
    toks, reason, done = [], None, False
    for line in raw.decode().splitlines():
        if not line.startswith("data: "):
            continue
        if line == "data: [DONE]":
            done = True
            continue
        chunk = json.loads(line[6:])["choices"][0]
        toks.extend(chunk["token_ids"])
        if chunk["finish_reason"] is not None:
            reason = chunk["finish_reason"]
    return toks, reason, done


# ------------------------------------------------------------------ parity
def test_streaming_parity_with_direct_submit(baseline):
    """Acceptance criterion: an HTTP client receives SSE tokens identical
    to a direct submit() run — and the unary path agrees."""
    params, ref = baseline
    gw = start_gateway(params)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=120)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": PROMPT, "max_tokens": 8, "stream": True}), {})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("content-type") == "text/event-stream"
        toks, reason, done = sse_tokens(resp.read())
        conn.close()
        assert toks == list(ref), "SSE tokens diverged from direct submit()"
        assert reason == "length" and done

        status, _, body = post(gw.port, {"prompt": PROMPT, "max_tokens": 8})
        assert status == 200
        out = json.loads(body)
        assert out["choices"][0]["token_ids"] == list(ref)
        assert out["usage"] == {"prompt_tokens": len(PROMPT),
                                "completion_tokens": 8,
                                "total_tokens": len(PROMPT) + 8}
    finally:
        assert gw.close(timeout=60)


def test_health_ready_metrics_endpoints(baseline):
    params, _ = baseline
    gw = start_gateway(params)
    try:
        assert get(gw.port, "/healthz")[0] == 200
        assert get(gw.port, "/readyz")[0] == 200
        post(gw.port, {"prompt": PROMPT, "max_tokens": 4})
        status, _, body = get(gw.port, "/v1/metrics")
        assert status == 200
        metrics = json.loads(body)
        assert metrics["gateway"]["completed"] == 1
        assert metrics["gateway"]["tokens"] == 4
        assert metrics["scheduler"]["num_slots"] == 2
        assert metrics["scheduler"]["compiled_programs"] >= 1
        # fused decode-block gate verdict: this fp32 engine is excluded,
        # and the reasons list says exactly why
        assert metrics["scheduler"]["fused_decode_block"] is False
        assert any("int8" in r
                   for r in metrics["scheduler"]["fused_decode_reasons"])
        assert get(gw.port, "/nope")[0] == 404
    finally:
        assert gw.close(timeout=60)
        # draining/closed gateway: readiness flipped before exit
        assert gw.draining and not gw.ready


def test_bad_requests_rejected(baseline):
    params, _ = baseline
    gw = start_gateway(params)
    try:
        for body in ({"prompt": []}, {"prompt": "not ids"}, {"max_tokens": 4},
                     {"prompt": PROMPT, "max_tokens": -1},
                     {"prompt": PROMPT, "max_tokens": 10_000_000},
                     # a client may not opt OUT of the deadline policy
                     {"prompt": PROMPT, "timeout_s": 0},
                     {"prompt": PROMPT, "timeout_s": -5},
                     {"prompt": PROMPT, "timeout_s": "soon"},
                     # non-numeric sampling params must 400, not drop the
                     # connection (TypeError inside the parser)
                     {"prompt": PROMPT, "top_k": [1, 2]},
                     {"prompt": PROMPT, "temperature": "hot"}):
            status, _, raw = post(gw.port, dict(body))
            assert status == 400, (body, raw)
            assert "error" in json.loads(raw)
        # null sampling params mean "default", not a dropped connection
        status, _, raw = post(gw.port, {"prompt": PROMPT, "max_tokens": 2,
                                        "top_k": None, "temperature": None,
                                        "seed": None, "top_p": None})
        assert status == 200, raw
        # oversized bodies answer 413 BEFORE buffering (Content-Length gate)
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=30)
        conn.putrequest("POST", "/v1/completions")
        conn.putheader("Content-Length", str(1 << 30))
        conn.endheaders()
        assert conn.getresponse().status == 413
        conn.close()
        # decimal-string prompts are accepted (no tokenizer in the engine)
        status, _, raw = post(gw.port, {"prompt": "5 6 7 8 9", "max_tokens": 2})
        assert status == 200
        assert json.loads(raw)["usage"]["prompt_tokens"] == 5
    finally:
        assert gw.close(timeout=60)


def test_overrides_do_not_mutate_engine_config(baseline):
    """Keyword overrides apply to THIS gateway only — a later Gateway(engine)
    must see the engine config's own values, not a previous caller's."""
    params, _ = baseline
    eng = make_engine(params=params)
    before = eng._config.gateway.max_queue_depth
    gw = Gateway(eng, max_queue_depth=before + 7)
    assert gw.config.max_queue_depth == before + 7
    assert eng._config.gateway.max_queue_depth == before
    assert Gateway(eng).config.max_queue_depth == before


# ------------------------------------------------------------------ admission control
def test_overload_sheds_with_429_and_retry_after(baseline):
    """At sustained overload the gateway sheds with 429 + a sane integer
    Retry-After instead of queueing unboundedly; every accepted request
    still completes in full."""
    params, _ = baseline
    gw = start_gateway(params, num_slots=1, max_queue_depth=2)
    results = []

    def worker():
        results.append(post(gw.port, {"prompt": PROMPT, "max_tokens": 16}))

    try:
        threads = [threading.Thread(target=worker) for _ in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        codes = sorted(status for status, _, _ in results)
        assert codes.count(429) >= 1, codes
        assert codes.count(200) >= 3, codes
        assert codes.count(200) + codes.count(429) == 10, codes
        for status, headers, body in results:
            if status == 429:
                retry = headers.get("Retry-After")
                assert retry is not None and 1 <= int(retry) <= 30
                assert json.loads(body)["error"]["type"] == "overloaded"
            else:
                assert len(json.loads(body)["choices"][0]["token_ids"]) == 16
        assert gw.stats["shed_429"] == codes.count(429)
        # the bounded queue never grew past its depth
        assert gw.scheduler.cache.active_slots == 0
    finally:
        assert gw.close(timeout=60)


def test_deadline_expiry_cancels_and_frees_slot(baseline):
    """A queued request whose deadline lapses returns 504 without consuming
    a slot; an ACTIVE request whose deadline lapses mid-decode cancels its
    slot (scheduler frees it, decode stops early)."""
    params, _ = baseline
    gw = start_gateway(params, num_slots=1)
    try:
        results = {}

        def run(name, body):
            results[name] = post(gw.port, body)

        # a long request holds the single slot; the queued one expires
        t1 = threading.Thread(target=run, args=("long", {"prompt": PROMPT,
                                                         "max_tokens": 48}))
        t1.start()
        time.sleep(0.1)
        t2 = threading.Thread(target=run, args=("dead", {"prompt": [1, 2, 3],
                                                         "max_tokens": 8,
                                                         "timeout_s": 0.02}))
        t2.start()
        t2.join()
        t1.join()
        assert results["long"][0] == 200
        assert results["dead"][0] == 504
        assert gw.stats["deadline_expired"] == 1
        assert gw.scheduler.cache.active_slots == 0
    finally:
        assert gw.close(timeout=60)


def test_active_deadline_cancels_mid_decode(baseline):
    """An ADMITTED request whose deadline lapses mid-decode is cancelled:
    partial tokens return with finish_reason 'deadline' and the slot frees.
    Deterministic on a COLD gateway: the first fused-step compile alone
    outlasts the 0.5 s deadline, so the 120-token budget can never finish
    first, while the compile's first sync still delivers some tokens."""
    params, _ = baseline
    gw = start_gateway(params, num_slots=1)
    try:
        status, _, raw = post(gw.port, {"prompt": PROMPT, "max_tokens": 120,
                                        "timeout_s": 0.5})
        out = json.loads(raw)
        assert status == 200 and out["choices"][0]["finish_reason"] == "deadline"
        assert 0 < len(out["choices"][0]["token_ids"]) < 120
        deadline = time.time() + 10
        while time.time() < deadline and gw.scheduler.cache.active_slots:
            time.sleep(0.02)
        assert gw.scheduler.cache.active_slots == 0
        assert gw.stats["deadline_expired"] == 1
    finally:
        assert gw.close(timeout=60)


def test_client_disconnect_cancels_slot(baseline):
    """Closing the socket mid-stream propagates into handle.cancel(): the
    request's slot frees instead of decoding for a dead client."""
    params, _ = baseline
    gw = start_gateway(params, num_slots=1)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=60)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": PROMPT, "max_tokens": 100,
                                 "stream": True}), {})
        resp = conn.getresponse()
        assert resp.status == 200
        resp.read(40)  # a couple of SSE events...
        resp.close()   # ...then vanish (closes the socket: will_close response)
        conn.close()
        deadline = time.time() + 15
        while time.time() < deadline and (gw.scheduler.cache.active_slots
                                          or not gw.stats["disconnects"]):
            time.sleep(0.02)
        assert gw.stats["disconnects"] == 1
        assert gw.scheduler.cache.active_slots == 0
        # pool stays serviceable after the cancellation
        status, _, raw = post(gw.port, {"prompt": PROMPT, "max_tokens": 4})
        assert status == 200
        assert len(json.loads(raw)["choices"][0]["token_ids"]) == 4
    finally:
        assert gw.close(timeout=60)


# ------------------------------------------------------------------ fairness
def test_fair_queue_drr_interleaves_tenants():
    """Deterministic DRR unit test: a 10:1 offered-load skew pops
    interleaved — the light tenant's 2 requests surface within the first
    few pops, not behind the heavy tenant's 20."""
    fq = FairQueue(max_depth=64, quantum=8)
    for i in range(20):
        fq.push(("A", i), "heavy", "standard", cost=8)
    for i in range(2):
        fq.push(("B", i), "light", "standard", cost=8)
    order = []
    while len(fq):
        order.append(fq.pop())
    assert len(order) == 22
    b_ranks = [i for i, item in enumerate(order) if item[0] == "B"]
    assert b_ranks[0] <= 2 and b_ranks[1] <= 4, order[:6]
    # per-flow FIFO preserved
    assert [it[1] for it in order if it[0] == "A"] == list(range(20))


def test_fair_queue_weights_and_priorities():
    """Weights scale service: a weight-2 tenant drains ~2x the requests of
    a weight-1 tenant per round; unknown priority classes sink to the
    floor weight (no self-service fast lane)."""
    fq = FairQueue(max_depth=64, quantum=4,
                   tenant_weights={"gold": 2.0},
                   priority_weights={"interactive": 4.0, "batch": 1.0})
    for i in range(8):
        fq.push(("gold", i), "gold", "batch", cost=4)
        fq.push(("base", i), "base", "batch", cost=4)
    first8 = [fq.pop()[0] for _ in range(8)]
    assert first8.count("gold") > first8.count("base")
    while len(fq):
        fq.pop()
    # invented priority class: floor weight, never above configured classes
    fq.push(("x", 0), "t", "make-me-fast", cost=4)
    fq.push(("y", 0), "t2", "interactive", cost=4)
    assert fq.pop()[0] in ("x", "y")  # but weighting applied without KeyError
    fq.pop()
    with pytest.raises(QueueFull):
        small = FairQueue(max_depth=1)
        small.push("a", "t", "standard")
        small.push("b", "t", "standard")


def test_gateway_drr_light_tenant_not_starved(baseline):
    """e2e fairness: tenant B's single request, submitted behind tenant A's
    10-deep backlog (10:1 skew), is admitted within a few slot turns — its
    completion does not trail A's whole backlog."""
    params, _ = baseline
    # quantum ~ one request's cost so turns alternate request-by-request
    # (a quantum >> cost batches a flow's turn, deferring B by that batch)
    gw = start_gateway(params, num_slots=1, max_queue_depth=32,
                       quantum_tokens=8)
    finish_order = []
    lock = threading.Lock()

    def run(tag, tenant):
        status, _, _ = post(gw.port, {"prompt": PROMPT, "max_tokens": 8,
                                      "_headers": {"x-tenant-id": tenant}})
        with lock:
            finish_order.append((tag, status))

    try:
        threads = [threading.Thread(target=run, args=(f"A{i}", "heavy"))
                   for i in range(10)]
        for t in threads:
            t.start()
            time.sleep(0.005)  # keep A's arrival order stable
        time.sleep(0.05)
        tb = threading.Thread(target=run, args=("B", "light"))
        tb.start()
        tb.join()
        for t in threads:
            t.join()
        assert all(s == 200 for _, s in finish_order)
        b_rank = [i for i, (tag, _) in enumerate(finish_order) if tag == "B"][0]
        # DRR alternates heavy/light once B arrives; without it B lands last
        assert b_rank < len(finish_order) - 3, finish_order
    finally:
        assert gw.close(timeout=120)


# ------------------------------------------------------------------ lifecycle
def test_drain_completes_in_flight_then_refuses(baseline):
    """Acceptance criterion: drain finishes every admitted request (full
    token budget, not truncated), sheds new ones with 503, and the server
    thread exits."""
    params, _ = baseline
    gw = start_gateway(params, num_slots=2)
    results = []
    # budgets long enough that the requests are still decoding when drain
    # starts (8-token budgets can all finish inside the sleep on a warm
    # machine, closing the server before the 503 probe lands)
    budget = 64

    def run():
        results.append(post(gw.port, {"prompt": PROMPT, "max_tokens": budget}))

    try:
        threads = [threading.Thread(target=run) for _ in range(3)]
        for t in threads:
            t.start()
        # all three are the gateway's (two in slots, one queued) before the
        # drain starts: by its own books, not by a sleep that a loaded
        # machine outlasts (a request arriving behind the drain is shed)
        deadline = time.monotonic() + 60
        while len(gw._active) + len(gw._fair) < 3:
            assert time.monotonic() < deadline, (len(gw._active), len(gw._fair), gw.stats)
            time.sleep(0.005)
        gw.begin_drain()
        status, headers, _ = post(gw.port, {"prompt": PROMPT, "max_tokens": 2})
        assert status == 503 and int(headers.get("Retry-After", 0)) >= 1
        for t in threads:
            t.join()
        for status, _, raw in results:
            assert status == 200
            # the full budget, not truncated: drain FINISHES admitted work
            assert len(json.loads(raw)["choices"][0]["token_ids"]) == budget
        assert gw.wait_drained(60)
        assert gw.stats["shed_503"] == 1
        assert gw.scheduler.cache.active_slots == 0
    finally:
        gw.close(timeout=60)


def test_tenant_telemetry_and_queue_wait(tmp_path, baseline):
    """Gateway telemetry reaches the PR-1 sink: queue-wait/TTFB histograms,
    shed counters, per-tenant token counters."""
    params, _ = baseline
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
    eng = deepspeed_tpu.init_inference(
        "tiny", config={"dtype": "float32",
                        "continuous_batching": {"enabled": True, "num_slots": 2},
                        "telemetry": {"enabled": True, "output_path": str(tmp_path)}},
        params=params)
    gw = Gateway(eng, port=0, max_queue_depth=1)
    gw.start_background()
    try:
        post(gw.port, {"prompt": PROMPT, "max_tokens": 4,
                       "_headers": {"x-tenant-id": "acme"}})
        post(gw.port, {"prompt": PROMPT, "max_tokens": 6,
                       "_headers": {"x-tenant-id": "globex"}})
        tel = eng.telemetry
        assert tel.counter_total("gateway/requests") == 2
        assert tel.counter_total("gateway/tenant/acme/tokens") == 4
        assert tel.counter_total("gateway/tenant/globex/tokens") == 6
        snap = tel.snapshot()
        assert snap["histograms"]["gateway/queue_wait_ms"]["count"] == 2
        assert snap["histograms"]["gateway/ttfb_ms"]["count"] == 2
        # the metrics endpoint serves the same snapshot
        _, _, raw = get(gw.port, "/v1/metrics")
        served = json.loads(raw)["telemetry"]
        assert served["counters"]["gateway/completed"]["total"] == 2
    finally:
        assert gw.close(timeout=60)


# ----------------------------------------------------------- multi-LoRA e2e
def test_adapter_id_threads_gateway_to_scheduler(baseline):
    """`adapter_id` in the completion body routes through the fair queue's
    adapter-scoped flow, the replica router, and DecodeScheduler.submit:
    the adapter stream completes with DIFFERENT tokens than base on the
    same prompt, base traffic is untouched, per-adapter counters reach
    /v1/metrics, and an unknown adapter answers 400 before queueing."""
    params, ref = baseline
    eng = make_engine(params=params,
                      continuous_batching={"enabled": True, "num_slots": 2,
                                           "prefill_chunk": 8})
    from deepspeed_tpu.runtime.lora import LoRAModel
    lora = LoRAModel(eng.module, r=2, alpha=4.0)
    tree = lora.init_lora(jax.device_get(eng.params), jax.random.key(3))

    def bump(node, i=[0]):
        if isinstance(node, dict) and "a" in node and "b" in node \
                and not isinstance(node["a"], dict):
            i[0] += 1
            return {"a": node["a"],
                    "b": jax.random.normal(jax.random.key(i[0]), node["b"].shape) * 0.1}
        return {k: bump(v) for k, v in node.items()}
    eng.register_adapter("acme", lora_tree=bump(tree), alpha=4.0)
    gw = Gateway(eng, port=0)
    gw.start_background()
    try:
        st, _, body = post(gw.port, {"prompt": PROMPT, "max_tokens": 8,
                                     "adapter_id": "acme"})
        assert st == 200
        acme_toks = json.loads(body)["choices"][0]["token_ids"]
        st, _, body = post(gw.port, {"prompt": PROMPT, "max_tokens": 8})
        assert st == 200
        base_toks = json.loads(body)["choices"][0]["token_ids"]
        assert base_toks == list(ref)        # base path untouched
        assert acme_toks != base_toks        # the adapter actually served
        # "model" doubles as the OpenAI-shaped spelling when registered
        st, _, body = post(gw.port, {"prompt": PROMPT, "max_tokens": 8,
                                     "model": "acme"})
        assert st == 200
        assert json.loads(body)["choices"][0]["token_ids"] == acme_toks
        # unknown adapter: 400 at the door, never queued
        st, _, body = post(gw.port, {"prompt": PROMPT, "max_tokens": 4,
                                     "adapter_id": "nope"})
        assert st == 400
        assert "unknown adapter" in json.loads(body)["error"]["message"]
        st, _, body = get(gw.port, "/v1/metrics")
        metrics = json.loads(body)
        # the store's stats surface on /v1/metrics even with the sink off
        # (the per-adapter counters ride the sink and are covered by
        # tests/unit/adapters/test_batched_lora.py)
        assert metrics["adapters"]["registered"] == 1
        assert metrics["adapters"]["loads"] == 1
        assert metrics["adapters"]["resident"] == 1
    finally:
        gw.close()


def test_fair_queue_adapter_flows_share_tenant_weight():
    """Review fix: a tenant spreading its backlog across N adapter flows
    must NOT earn N quanta per rotation — the (tenant, priority) pair's
    credit is split across its live flows, so an equal-weight base-only
    tenant keeps ~half the bandwidth."""
    q = FairQueue(max_depth=64, quantum=1)
    for i in range(8):
        q.push(("a", "x", i), "tenant-a", "standard", cost=1, adapter="v1")
        q.push(("a", "y", i), "tenant-a", "standard", cost=1, adapter="v2")
        q.push(("b", i), "tenant-b", "standard", cost=1)
    first12 = [q.pop() for _ in range(12)]
    b_share = sum(1 for it in first12 if it[0] == "b")
    assert 4 <= b_share <= 8, f"tenant-b got {b_share}/12 despite equal weight"
    # drain fully; sibling accounting must empty cleanly
    while q.pop() is not None:
        pass
    assert len(q) == 0 and not q._siblings and not q._flows


# ------------------------------------------------- the event loop's delivery
@pytest.mark.parametrize("sink_on", [True, False], ids=["sink-on", "sink-off"])
def test_delivery_account_of_four_streams(tmp_path, baseline, monkeypatch, sink_on):
    """Four streaming clients. Sink on: every delivered token is counted
    posted (a landing's at once: the pump still posts its 3-tuples), every
    posted event is written, one send in 64 under a ``gateway/send``
    annotation, the bytes are the clients', no lag is negative, and every
    landed sync observes each of the account's new histograms once. Sink off:
    the pump posts the 3-tuples it always did and the loop makes no span."""
    import asyncio
    params, _ = baseline
    cfg = {"telemetry": {"enabled": True, "output_path": str(tmp_path)}} if sink_on else {}
    eng = make_engine(params=params, num_slots=4, **cfg)
    spans, posted = [], []
    real_span, real_put = eng.telemetry.span, asyncio.Queue.put_nowait

    def span(name, *a, **kw):
        spans.append(name)
        return real_span(name, *a, **kw)

    def put_nowait(self, ev):
        if isinstance(ev, tuple) and ev[0] == "token":
            posted.append(ev)
        return real_put(self, ev)

    monkeypatch.setattr(eng.telemetry, "span", span)
    monkeypatch.setattr(asyncio.Queue, "put_nowait", put_nowait)
    gw = Gateway(eng, port=0)
    gw.start_background()
    raws = [None] * 4

    def client(i):
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=120)
        conn.request("POST", "/v1/completions", json.dumps(
            {"prompt": [5 + i, 6, 7, 8, 9], "max_tokens": 24, "stream": True}), {})
        raws[i] = conn.getresponse().read()
        conn.close()

    try:
        threads = [threading.Thread(target=client, args=(i, )) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        assert gw.close(timeout=60)
    n = 4 * 24
    assert all(sse_tokens(raw)[2] for raw in raws) and len(posted) == n
    assert sum(len(sse_tokens(raw)[0]) for raw in raws) == n
    assert all(len(ev) == 3 for ev in posted)    # nothing rides a token event, sink on or off
    if not sink_on:
        assert "gateway/send" not in spans and gw._delivery is None
        return
    d = gw._delivery
    assert spans.count("gateway/send") == n // 64   # one send in 64 is annotated
    assert d.posted() == d.events == d.writes == n and d.taken == d.unread == 0
    assert d.bytes == sum(len(raw) for raw in raws) - 4 * len(b"data: [DONE]\n\n")
    assert d.lag_s >= 0.0
    hists = eng.telemetry.snapshot()["histograms"]
    landed = hists["serving/pump_busy_ms"]["count"]
    for name in ("serving/pump_cpu_ms", "serving/loop_cpu_ms", "serving/host_threads_cpu_pct",
                 "gateway/backlog_events"):
        assert hists[name]["count"] == landed > 0, name
    for name in ("gateway/delivery_lag_ms", "gateway/delivery_lag_max_ms",
                 "gateway/loop_cpu_us_per_event"):   # one a landed sync that wrote anything
        assert 0 < hists[name]["count"] <= landed, name
        assert hists[name]["min"] >= 0.0
    assert hists["gateway/backlog_events"]["min"] >= 0
    assert hists["gateway/backlog_events"]["max"] <= n
    # the pump's CPU is within the wall time of its account's periods (the
    # step programs were built in them: ``compile`` is a part of its own)
    total = eng.telemetry.counter_total
    wall = sum(total(f"serving/pump/{part}_ms") for part in ("busy", "wait", "idle", "compile"))
    assert 0.0 < total("serving/pump/cpu_ms") <= 1.02 * wall + 5.0
    assert 0.0 < total("gateway/loop/cpu_ms") <= 1.02 * wall + 5.0
    assert 0 < eng.telemetry.counter_total("gateway/sse_events") <= n
