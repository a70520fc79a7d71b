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
    """Four streaming clients at ``steps_per_sync`` 4. An event is a row's
    tokens of one landing: every token is read, in events of at most four
    tokens and about a quarter as many events as tokens. Sink on: every
    event is counted posted (a landing's at once, where its batch is handed
    over) and every posted event is written, one send in 64 under a
    ``gateway/send`` annotation, the bytes and the tokens written are the
    clients', no lag is negative, and every landed sync observes each of the
    account's histograms once. Sink off: the same 3-tuples cross and the loop
    makes no span, no account exists."""
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
    assert all(sse_tokens(raw)[2] for raw in raws)
    assert sum(len(sse_tokens(raw)[0]) for raw in raws) == n
    assert sum(len(ev[1]) for ev in posted) == n     # every token crossed, inside an event
    assert all(len(ev) == 3 and 1 <= len(ev[1]) <= 4 for ev in posted)  # nothing rides an event
    events = len(posted)
    assert n // 4 <= events <= n // 4 + 8    # a landing's four a row; a final chunk may split one
    assert sum(raw.count(b'"token_ids"') for raw in raws) == events     # one document an event
    if not sink_on:
        assert "gateway/send" not in spans and gw._delivery is None
        return
    d = gw._delivery
    assert spans.count("gateway/send") == events // 64   # one send in 64 is annotated
    assert d.posted() == d.events == d.writes == events and d.taken == d.unread == 0
    assert d.tokens == n
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
    assert hists["gateway/backlog_events"]["max"] <= events
    # the pump's CPU is within the wall time of its account's periods (the
    # step programs were built in them: ``compile`` is a part of its own)
    total = eng.telemetry.counter_total
    wall = sum(total(f"serving/pump/{part}_ms") for part in ("busy", "wait", "idle", "compile"))
    assert 0.0 < total("serving/pump/cpu_ms") <= 1.02 * wall + 5.0
    assert 0.0 < total("gateway/loop/cpu_ms") <= 1.02 * wall + 5.0
    # the counters take every close: what they hold so far is the loop's
    assert 0 < total("gateway/sse_events") <= events
    assert total("gateway/sse_events") <= total("gateway/sse_tokens") <= n


# ------------------------------------------- a landing crosses in one wake-up
def stream(port, body, timeout=120):
    """One streamed completion; returns the events' (token_ids, finish_reason)
    in order and whether [DONE] closed the stream."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(dict(body, stream=True)), {})
        resp = conn.getresponse()
        assert resp.status == 200
        raw = resp.read().decode()
    finally:
        conn.close()
    chunks = [json.loads(line[6:])["choices"][0] for line in raw.splitlines()
              if line.startswith("data: {")]
    return ([(c["token_ids"], c["finish_reason"]) for c in chunks],
            "".join(c["text"] for c in chunks), raw.rstrip().endswith("data: [DONE]"))


SAMPLED = {"prompt": PROMPT, "max_tokens": 12, "temperature": 1.0, "seed": 7}


@pytest.mark.parametrize("ksteps", [4, 1])
def test_stream_is_the_unary_answer_in_events_of_a_landing(baseline, ksteps):
    """A streamed request gives the unary answer's and the direct submit's
    tokens, in order, as events of at most ``steps_per_sync`` tokens with the
    finish reason on the last: about a quarter as many events as tokens at
    4, one a token at 1; and the concatenated text is the tokens'."""
    params, _ = baseline
    eng = make_engine(params=params, continuous_batching={
        "enabled": True, "num_slots": 2, "steps_per_sync": ksteps})
    ref = [int(t) for t in eng.scheduler().submit(
        PROMPT, max_new_tokens=12, do_sample=True, temperature=1.0, seed=7).result()]
    assert len(set(ref)) > 8     # a sampled row: the order of distinct tokens is checked
    gw = Gateway(eng, port=0)
    gw.start_background()
    try:
        events, text, done = stream(gw.port, SAMPLED)
        status, _, body = post(gw.port, SAMPLED)
    finally:
        assert gw.close(timeout=60)
    toks = [t for ids, _ in events for t in ids]
    assert status == 200 and json.loads(body)["choices"][0]["token_ids"] == ref
    assert toks == ref and done
    assert text == "".join(f"{t} " for t in ref)
    assert [r for _, r in events] == [None] * (len(events) - 1) + ["length"]
    assert all(1 <= len(ids) <= ksteps for ids, _ in events)
    assert len(events) == (12 if ksteps == 1 else 3)


@pytest.mark.parametrize("eos_at", [2, 5], ids=["first-landing", "second-landing"])
def test_stream_ends_on_an_eos_in_the_middle_of_a_landing(baseline, eos_at):
    """The row stops at its EOS though the landing computed tokens past it:
    the stream's last event ends with that token and says ``stop``."""
    params, _ = baseline
    eng = make_engine(params=params)
    ref = [int(t) for t in eng.scheduler().submit(
        PROMPT, max_new_tokens=12, do_sample=True, temperature=1.0, seed=7).result()]
    gw = Gateway(eng, port=0)
    gw.start_background()
    try:
        events, _, done = stream(gw.port, dict(SAMPLED, eos_token_id=ref[eos_at]))
    finally:
        assert gw.close(timeout=60)
    assert done and [t for ids, _ in events for t in ids] == ref[:eos_at + 1]
    assert events[-1] == (ref[4 * (eos_at // 4):eos_at + 1], "stop")


class _CountingLoop:
    """A stand-in for the gateway's event loop: counts the wake-ups and keeps
    the callbacks, which ``run`` calls in the order they were posted."""

    def __init__(self, closed=False):
        self.calls, self.closed = [], closed

    def call_soon_threadsafe(self, fn, *args):
        if self.closed:
            raise RuntimeError("Event loop is closed")
        self.calls.append((fn, args))

    def run(self):
        calls, self.calls = self.calls, []
        for fn, args in calls:
            fn(*args)


def _request(gw, rid, **kw):
    from deepspeed_tpu.serving.gateway import _GatewayRequest
    fields = dict(max_new_tokens=8, eos_token_id=None, do_sample=False, temperature=1.0,
                  top_k=0, top_p=1.0, seed=0, tenant="t", priority="standard",
                  deadline=None, stream=True)
    fields.update(kw)
    return _GatewayRequest(rid, list(PROMPT), **fields)


def _drain(greq):
    out = []
    while not greq.events.empty():
        out.append(greq.events.get_nowait())
    return out


@pytest.fixture()
def idle_gateway(baseline):
    """A gateway that was never started (no loop, no pump): its hooks, its
    batch and its posts driven by hand against a counting loop."""
    params, _ = baseline
    gw = Gateway(make_engine(params=params, num_slots=4), port=0)
    gw._loop = _CountingLoop()
    return gw


def test_a_landing_of_n_rows_is_one_wake_up(idle_gateway):
    """The scheduler's landings, stepped by hand with the gateway's hooks on
    three requests: every step that delivers makes ONE
    ``call_soon_threadsafe`` whatever the rows (the fused landing of a final
    chunk and the decode rows too), and its callback puts one event a row on
    that row's queue, the row's tokens in order."""
    gw, loop = idle_gateway, idle_gateway._loop
    sched = gw.scheduler
    sched.on_landing = gw._flush_landing
    greqs = [_request(gw, i) for i in range(3)]
    handles = [sched.submit([5 + i, 6, 7], max_new_tokens=8,
                            on_token=gw._make_on_token(g)) for i, g in enumerate(greqs)]
    got = {g: [] for g in greqs}
    for _ in range(64):
        if all(h.done for h in handles) and not sched.in_flight:
            break
        delivered = sched.step()
        assert len(loop.calls) == (1 if delivered else 0)
        loop.run()
        rows = 0
        for g in greqs:
            events = _drain(g)
            assert len(events) <= 1          # one event a row a landing
            for kind, toks, reason in events:
                assert kind == "token" and 1 <= len(toks) <= 4
                got[g].extend(toks)
                rows += 1
                assert (reason == "length") == (len(got[g]) == 8)
        assert bool(rows) == bool(delivered)
    assert [got[g] for g in greqs] == [list(h.result()) for h in handles]
    assert not gw._landing.rows and [g.n_tokens for g in greqs] == [8, 8, 8]


def test_two_pumps_never_share_a_batch_and_a_closed_loop_never_raises(idle_gateway):
    gw, loop = idle_gateway, idle_gateway._loop
    a, b = _request(gw, 1), _request(gw, 2)
    seen = {}

    def pump(name, greq, toks):
        hook = gw._make_on_token(greq)
        for t in toks:
            hook(t, False)
        seen[name] = dict(gw._landing.rows)     # this thread's batch, before its flush
        if name == "a":
            gw._flush_landing()

    for name, greq, toks in (("a", a, [1, 2, 3]), ("b", b, [7, 8])):
        t = threading.Thread(target=pump, args=(name, greq, toks))
        t.start()
        t.join()
    assert seen == {"a": {a: [[1, 2, 3], None]}, "b": {b: [[7, 8], None]}}
    assert not gw._landing.rows                  # and this thread's holds neither
    assert len(loop.calls) == 1                  # pump a's flush took pump a's rows alone
    loop.run()
    assert _drain(a) == [("token", [1, 2, 3], None)] and _drain(b) == []
    # the loop closed in the middle of a drain: the pump's side never raises,
    # and the batch does not pile up behind it
    gw._loop = _CountingLoop(closed=True)
    gw._make_on_token(a)(4, True)
    gw._flush_landing()
    gw._post(a, ("cancelled", "disconnect"))
    assert not gw._landing.rows and a.finished


@pytest.mark.parametrize("event", [
    ("cancelled", "disconnect"), ("cancelled", "deadline"), ("failed", 500, "replica step failed"),
    ("handoff", {"key": "k", "kv_len": 3})], ids=["cancel", "deadline", "failure", "hand-off"])
def test_no_event_overtakes_a_token(idle_gateway, event):
    """Whatever ends a request on the pump's side arrives behind every token
    delivered before it: the post of any other event first hands on what the
    batch holds, another row's tokens with it."""
    gw, loop = idle_gateway, idle_gateway._loop
    ending, other = _request(gw, 1), _request(gw, 2)
    gw._active.update((ending, other))
    for t in (11, 12):
        gw._make_on_token(ending)(t, False)
    gw._make_on_token(other)(21, False)
    gw._finish(ending, event)
    assert len(loop.calls) == 2                  # the batch, then the event
    loop.run()
    assert _drain(ending) == [("token", [11, 12], None), event]
    assert _drain(other) == [("token", [21], None)]
    assert ending.finished and ending not in gw._active


def test_delivery_identity_in_events_with_streams_unary_and_a_disconnect(tmp_path, baseline):
    """Sink on, two streams, two unary requests and a stream whose client
    leaves after its first event, all at once: posted = written + taken +
    unread once nothing is owed, counted in EVENTS (far fewer than the
    tokens); the tokens written are at least those the staying clients read,
    and once later landings have closed the account's periods the counter
    ``gateway/sse_tokens`` holds exactly the loop's total."""
    params, _ = baseline
    eng = make_engine(params=params, num_slots=4,
                      telemetry={"enabled": True, "output_path": str(tmp_path)})
    gw = Gateway(eng, port=0)
    gw.start_background()
    out = {}

    def streamer(i):
        out[i] = stream(gw.port, {"prompt": [5 + i, 6, 7], "max_tokens": 40})

    def unary(i):
        out[i] = post(gw.port, {"prompt": [5 + i, 6, 7], "max_tokens": 40})

    def leaver(i):
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=60)
        conn.request("POST", "/v1/completions", json.dumps(
            {"prompt": [5 + i, 6, 7], "max_tokens": 100, "stream": True}), {})
        resp = conn.getresponse()
        resp.readline()     # the first event ...
        resp.close()        # ... then gone
        conn.close()

    try:
        threads = [threading.Thread(target=fn, args=(i, )) for i, fn in enumerate(
            (streamer, streamer, unary, unary, leaver))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        deadline = time.time() + 30
        while time.time() < deadline and (gw._active or not gw.stats["disconnects"]):
            time.sleep(0.02)
        assert not gw._active and gw.stats["disconnects"] == 1
        d = gw._delivery
        read = sum(len(ids) for i in (0, 1) for ids, _ in out[i][0])
        assert read == 80 and all(out[i][2] for i in (0, 1))
        assert all(len(json.loads(out[i][2])["choices"][0]["token_ids"]) == 40 for i in (2, 3))
        deadline = time.time() + 10
        while time.time() < deadline and d.posted() != d.events + d.taken + d.unread:
            time.sleep(0.02)         # the leaver's last events: the loop is still counting them
        assert d.posted() == d.events + d.taken + d.unread
        assert d.taken >= 20 and d.events >= 20          # the unary pair's, the streams'
        assert d.posted() <= (gw.stats["tokens"] + 3) // 2   # events, not tokens
        assert read <= d.tokens <= gw.stats["tokens"] - 80
        # two more landings close the periods the events were written in
        post(gw.port, {"prompt": PROMPT, "max_tokens": 8})
        assert eng.telemetry.counter_total("gateway/sse_tokens") == d.tokens
        assert eng.telemetry.counter_total("gateway/sse_events") == d.events
        _, _, raw = get(gw.port, "/v1/metrics")
        served = json.loads(raw)["capacity"]["delivery"]
        assert served["tokens"] == d.tokens and served["written"] == d.events
    finally:
        assert gw.close(timeout=60)
