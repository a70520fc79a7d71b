"""Long-context serving tests: multi-extent paged KV + seq-parallel prefill.

Covers the PR-18 acceptance bars: a request spanning several KV extents
decodes BIT-identically (tokens AND logits) to the same request on one
big slot, sequence-parallel chunked prefill matches the single-shard
chunked scheduler exactly (greedy + sampled, forced multi-device),
mid-decode extent demotion -> detect-miss-and-restore leaves the stream
bit-identical, the lossy sliding-window mode is gated off by default and
asserted NON-identical when enabled, and a fresh length mix over chained
extents compiles ZERO new XLA programs after warmup (jax.monitoring).

Cross-geometry bit-identity holds because the flash block walk is aligned:
every engine here pins ``decode_block_kv=32`` so the single-slot kernel
and the extent walk accumulate the same logical 32-key blocks in the same
order.
"""

import dataclasses

import numpy as np
import pytest
import jax

import deepspeed_tpu
from deepspeed_tpu.comm import comm
from deepspeed_tpu.models.transformer import TransformerConfig, CausalLMModel

PROMPT = [int(t) for t in np.resize(np.arange(3, 40), 100)]
# 256-horizon tiny variant: chains reach 3+ extents (the stock 128-horizon
# tiny caps at 2, where extent 0 is pinned and extent 1 is the write head —
# nothing is ever demotable)
LPROMPT = [int(t) for t in np.resize(np.arange(3, 40), 150)]
LCFG = TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2,
                         num_heads=4, num_kv_heads=2, max_seq_len=256,
                         intermediate_size=128, attention_impl="flash",
                         scan_layers=False, decode_block_kv=32)


def make_engine(params=None, mesh_kw=None, model=None, telemetry=None, **cb):
    comm._state["mesh"] = None
    if mesh_kw:
        comm.initialize_mesh(**mesh_kw)
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
    cfg = {"dtype": "float32", "decode_block_kv": 32,
           "continuous_batching": {"enabled": True, "num_slots": 4,
                                   "collect_logits": True, **cb}}
    if telemetry:
        cfg["telemetry"] = telemetry
    if model is None:
        cfg["kernel_inject"] = True  # preset path: flips tiny to flash
        model = "tiny"
    return deepspeed_tpu.init_inference(model, config=cfg, params=params)


def make_long_engine(params=None, **kw):
    return make_engine(params=params, model=CausalLMModel(LCFG), **kw)


@pytest.fixture(scope="module")
def baseline():
    """Tiny weights + the single-slot chunked reference (tokens, logits)."""
    eng = make_engine()
    params = jax.device_get(eng.params)
    s = eng.scheduler(max_len=128, prefill_chunk=16)
    h = s.submit(PROMPT, max_new_tokens=24)
    hs = s.submit(PROMPT, max_new_tokens=24, temperature=0.8, top_k=20, seed=7)
    return params, h.result(), h.result_logits(), hs.result()


@pytest.fixture(scope="module")
def long_baseline():
    """256-horizon weights + the single-slot chunked reference."""
    eng = make_long_engine()
    params = jax.device_get(eng.params)
    s = eng.scheduler(max_len=192, prefill_chunk=16)
    h = s.submit(LPROMPT, max_new_tokens=24)
    return params, h.result(), h.result_logits()


def test_multi_extent_decode_bit_identical_to_single_extent(baseline):
    """A request spanning a 2-extent chain (slot 64 rows, prompt 100 + 24
    new) emits BIT-identical tokens and logits to the same request on one
    128-row slot, greedy AND sampled; the chain frees with the request."""
    params, tok, logits, stok = baseline
    eng = make_engine(params)
    s = eng.scheduler(max_len=32, prefill_chunk=16, max_extents=4)
    # max_len rounds up to the 64-row pool floor; the model's 128-token
    # horizon then caps the chain at 2 extents
    assert s.max_len == 64 and s.cache.max_extents == 2
    assert s.cache.spannable_len == 128
    h = s.submit(PROMPT, max_new_tokens=24)
    hs = s.submit(PROMPT, max_new_tokens=24, temperature=0.8, top_k=20, seed=7)
    assert (h.result() == tok).all()
    assert all((a == b).all() for a, b in zip(h.result_logits(), logits))
    assert (hs.result() == stok).all()
    assert s.cache.active_slots == 0 and not s.cache.chain


def test_seq_parallel_prefill_bit_identical_to_single_shard(baseline, tmp_path):
    """Sequence-parallel chunked prefill (seq mesh axis 4, wide fused
    chunks sharded over devices) == the single-shard chunked scheduler,
    tokens AND logits, greedy + sampled; the per-prefill counter fires."""
    params, tok, logits, stok = baseline
    eng = make_engine(params, mesh_kw={"seq": 4},
                      telemetry={"enabled": True, "output_path": str(tmp_path)})
    s = eng.scheduler(max_len=128, prefill_chunk=16, seq_parallel_min_tokens=32)
    assert s._seq_shards == 4 and s._seq_chunk == 64
    h = s.submit(PROMPT, max_new_tokens=24)
    hs = s.submit(PROMPT, max_new_tokens=24, temperature=0.8, top_k=20, seed=7)
    assert (h.result() == tok).all()
    assert all((a == b).all() for a, b in zip(h.result_logits(), logits))
    assert (hs.result() == stok).all()
    assert eng.telemetry.counter_total("serving/seq_parallel_prefills") == 2


def test_seq_parallel_composes_with_extent_chains(baseline):
    """Seq-parallel prefill over a chained request: both long-context
    mechanisms active in one dispatch stay bit-identical."""
    params, tok, _, _ = baseline
    eng = make_engine(params, mesh_kw={"seq": 4})
    s = eng.scheduler(max_len=32, prefill_chunk=16, max_extents=4,
                      seq_parallel_min_tokens=32)
    assert s.cache.max_extents == 2 and s._seq_shards == 4
    assert (s.submit(PROMPT, max_new_tokens=24).result() == tok).all()


def test_demote_restore_bit_identity(long_baseline):
    """Mid-decode cold-extent demotion to the hierarchical host tier, then
    detect-miss-and-restore: the emitted stream stays BIT-identical, and
    the paging counters fire."""
    params, tok, logits = long_baseline
    eng = make_long_engine(params, hierarchical_kv={"enabled": True,
                                                    "host_capacity_mb": 64})
    s = eng.scheduler(max_len=64, prefill_chunk=16, max_extents=4)
    assert s.cache.max_extents == 4
    h = s.submit(LPROMPT, max_new_tokens=24)
    while not s.active:
        s.step()
    slot = next(iter(s.active))
    n_dem = 0
    for _ in range(30):  # advance until the row has cold extents, then page
        s.step()
        if slot not in s.active:
            break
        n_dem = s.demote_cold_extents(slot)
        if n_dem:
            break
    assert n_dem >= 1
    assert s.cache.missing_extents(slot)
    assert (h.result() == tok).all()
    assert all((a == b).all() for a, b in zip(h.result_logits(), logits))
    assert s.longctx_demotes >= 1 and s.longctx_restores >= 1
    assert s.cache.active_slots == 0 and not s._parked and not s._ext_parked


def test_lossless_demote_requires_kv_tier(baseline):
    """Without the hierarchical tier there is nowhere to park a lossless
    extent: demote_cold_extents must refuse loudly, not drop KV."""
    params = baseline[0]
    eng = make_engine(params)
    s = eng.scheduler(max_len=32, prefill_chunk=16, max_extents=4)
    h = s.submit(PROMPT, max_new_tokens=24)
    while not s.active:
        s.step()
    slot = next(iter(s.active))
    for _ in range(10):
        s.step()
        if int(s.cache.lengths[slot]) >= 64 + 1:
            break
    with pytest.raises(ValueError, match="hierarchical"):
        s.demote_cold_extents(slot, keep_recent=0)
    assert (h.result() == baseline[1]).all()  # refusal left the row intact


def test_lossy_window_gated_and_not_identical(long_baseline):
    """kv_window submits are rejected unless allow_lossy_kv is on; when
    enabled, out-of-window extents auto-drop and the stream is asserted
    NON-identical to full attention (the mode is approximate by design)."""
    params, tok, logits = long_baseline
    eng = make_long_engine(params)
    s = eng.scheduler(max_len=64, prefill_chunk=16, max_extents=4)
    with pytest.raises(ValueError, match="allow_lossy_kv"):
        s.submit(LPROMPT, max_new_tokens=8, kv_window=(4, 16))
    eng2 = make_long_engine(params)
    s2 = eng2.scheduler(max_len=64, prefill_chunk=16, max_extents=4,
                        allow_lossy_kv=True)
    h = s2.submit(LPROMPT, max_new_tokens=24, kv_window=(4, 16))
    got_tok, got_log = h.result(), h.result_logits()
    assert len(got_tok) == 24
    ident = (got_tok == tok).all() and all(
        (a == b).all() for a, b in zip(got_log, logits))
    assert not ident
    assert s2.longctx_demotes >= 1  # the window slid past extent 1: auto-drop


def test_fresh_length_mix_zero_new_programs(baseline):
    """jax.monitoring compile guard: after one warm request, a fresh mix of
    chained/unchained prompt lengths dispatches ZERO new XLA programs —
    the extent count rides the operands, never the program shape."""
    params = baseline[0]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    eng = make_engine(params)
    s = eng.scheduler(max_len=32, prefill_chunk=16, max_extents=4)
    s.submit(PROMPT, max_new_tokens=4).result()
    n0 = len(compiles)
    lens = [40, 61, 70, 90, 100, 110, 124]
    hs = [s.submit([int(t) for t in np.resize(np.arange(2, 50), n)],
                   max_new_tokens=4) for n in lens]
    for h in hs:
        assert len(h.result()) == 4
    assert len(compiles) == n0, \
        f"fresh length mix compiled {len(compiles) - n0} new XLA programs"


def test_submit_rejects_beyond_spannable_capacity(baseline):
    """Prompt + budget beyond the whole extent chain fails at submit()
    with a clear message naming the spannable capacity."""
    params = baseline[0]
    eng = make_engine(params)
    s = eng.scheduler(max_len=32, prefill_chunk=16, max_extents=4)
    cap = s.cache.spannable_len
    with pytest.raises(ValueError, match="per-slot KV capacity"):
        s.submit(list(range(1, cap + 2)), max_new_tokens=1)
    with pytest.raises(ValueError, match="extent"):
        s.submit([1] * (cap - 1), max_new_tokens=8)
    assert s.cache.total_allocs == 0 and not s.queue


def test_long_request_completes_through_gateway(baseline):
    """Acceptance: a request exceeding one extent completes end-to-end
    through the HTTP gateway, and a spannable-capacity violation 400s at
    the door instead of queueing."""
    import http.client
    import json
    from deepspeed_tpu.serving import Gateway
    params, tok, _, _ = baseline
    eng = make_engine(params)
    eng.scheduler(max_len=32, prefill_chunk=16, max_extents=4)
    gw = Gateway(eng, port=0)
    gw.start_background()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=300)
        body = {"prompt": PROMPT, "max_tokens": 24, "stream": False}
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
        assert resp.status == 200, out
        assert out["choices"][0]["token_ids"] == [int(t) for t in tok]
        conn.close()
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=60)
        too_long = {"prompt": list(range(1, 200)), "max_tokens": 8}
        conn.request("POST", "/v1/completions", json.dumps(too_long),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        err = json.loads(resp.read())
        assert resp.status == 400
        assert "per-slot KV capacity" in err["error"]["message"]
        conn.close()
    finally:
        gw.close(timeout=60)


def test_longctx_telemetry_reaches_sink(long_baseline, tmp_path):
    """The extent histogram and paging counters land in the telemetry
    stream: kv_extents_per_request, longctx_demote/restore_tokens."""
    params = long_baseline[0]
    eng = make_long_engine(params,
                           telemetry={"enabled": True,
                                      "output_path": str(tmp_path)},
                           hierarchical_kv={"enabled": True,
                                            "host_capacity_mb": 64})
    s = eng.scheduler(max_len=64, prefill_chunk=16, max_extents=4)
    h = s.submit(LPROMPT, max_new_tokens=24)
    while not s.active:
        s.step()
    slot = next(iter(s.active))
    for _ in range(30):
        s.step()
        if slot not in s.active or s.demote_cold_extents(slot):
            break
    h.result()
    tel = eng.telemetry
    assert tel.counter_total("serving/longctx_demote_tokens") >= s.max_len
    assert tel.counter_total("serving/longctx_restore_tokens") >= s.max_len
    tel.flush()
    text = (tmp_path / "telemetry.jsonl").read_text()
    assert "serving/kv_extents_per_request" in text


def test_config_validation():
    """Compose rules fail loudly at construction: seq-parallel needs tp=1;
    the long-context machinery needs the flash paged path."""
    eng3 = make_engine(mesh_kw={"seq": 2, "tensor": 2})
    with pytest.raises(ValueError, match="tp=1"):
        eng3.scheduler(prefill_chunk=16, seq_parallel_min_tokens=32)
    # xla-impl model: the extent walk lives in the Pallas path only
    comm._state["mesh"] = None
    from deepspeed_tpu.telemetry import set_sink
    set_sink(None)
    xcfg = dataclasses.replace(LCFG, attention_impl="xla")
    eng4 = deepspeed_tpu.init_inference(
        CausalLMModel(xcfg),
        config={"dtype": "float32",
                "continuous_batching": {"enabled": True, "num_slots": 4}})
    with pytest.raises(ValueError, match="flash"):
        eng4.scheduler(max_len=64, prefill_chunk=16, max_extents=4)


def test_long_context_config_section_threads_to_scheduler(baseline):
    """The continuous_batching.long_context config block reaches the
    scheduler without per-field plumbing in user code."""
    params = baseline[0]
    eng = make_engine(params,
                      long_context={"max_extents": 4,
                                    "seq_parallel_min_tokens": 0,
                                    "allow_lossy_kv": True})
    s = eng.scheduler(max_len=32, prefill_chunk=16)
    assert s.cache.max_extents == 2  # horizon-capped from the configured 4
    assert s.allow_lossy_kv and s.seq_parallel_min_tokens == 0
