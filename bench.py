"""Benchmark: training throughput/MFU on the local chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Headline: GPT-2 large (774M) — the largest zoo model whose fp32 Adam state
fits a single 16 GB chip without offload, where MFU is meaningful (BASELINE.md
north star: >=40% MFU; the reference's published efficiency is 50-65% MFU on
A100 clusters, `docs/_posts/2022-07-26-deepspeed-azure.md:97`). vs_baseline
reports achieved_MFU / 0.40. The GPT-2 125M config benched in earlier rounds
is re-measured and reported in "extra" for continuity.

Every entry point needs the chip: a CPU run gives no time or rate, so with
no TPU the command exits non-zero, and so does a failing leg. Timed windows
end in ``jax.block_until_ready`` (it fences on the chip: ``chip_smoke.py``'s
train line reports the value fetch after it, about half a millisecond a value).
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

def _require_chip():
    """The devices, on a TPU; anything else ends the run with a non-zero
    code. Also places the compile cache, before the first compile."""
    import jax
    from deepspeed_tpu.utils import compile_cache

    compile_cache.configure()
    devices = jax.devices()  # a backend that cannot start raises here
    if devices[0].platform != "tpu":
        sys.exit(f"bench.py measures the chip and JAX found platform="
                 f"{devices[0].platform!r}: a CPU run gives no time or rate")
    return devices


def _telemetry_cfg():
    """Structured telemetry for bench runs: set BENCH_TELEMETRY=<dir> to get
    telemetry.jsonl + trace.json alongside the printed JSON line (summarize
    with tools/trace_summary.py)."""
    path = os.environ.get("BENCH_TELEMETRY")
    return {"enabled": True, "output_path": path} if path else {}


def _mfu(cfg, tok_per_sec, seq, peak):
    # PaLM-style MFU: 6*N_nonemb + 12*L*H*T matmul flops per token
    n_emb = cfg.vocab_size * cfg.hidden_size + (cfg.max_seq_len * cfg.hidden_size
                                                if cfg.pos_embedding == "learned" else 0)
    n_nonemb = cfg.num_params() - n_emb
    flops_per_token = 6 * n_nonemb + 12 * cfg.num_layers * cfg.hidden_size * seq
    return flops_per_token * tok_per_sec / peak


def _run(model_name, micro_bs, steps, seq=1024, attention_impl="flash", **model_kwargs):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.models import get_model

    comm._state["mesh"] = None
    # fastest measured config for these sizes (sweep on v5e): unrolled
    # layers, no remat, Pallas flash attention in bhtd
    model = get_model(model_name, remat_policy=None, scan_layers=False,
                      attention_impl=attention_impl, **model_kwargs)
    cfg = model.cfg
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "optimizer": {"type": "AdamW", "params": {"lr": 3e-4, "weight_decay": 0.01}},
            "bf16": {"enabled": True},
            "gradient_clipping": 1.0,
            "steps_per_print": 10**9,
            "telemetry": _telemetry_cfg(),
        })

    rng = np.random.default_rng(0)
    global_bs = engine.train_batch_size()
    raw = {"input_ids": rng.integers(0, cfg.vocab_size, (1, global_bs, seq)).astype(np.int32)}
    placed = engine._shard_batch(raw, leading_scan_dim=True)
    step_fn = engine._get("train_batch", engine._build_train_batch_fn)
    state = engine.state

    with engine.mesh:
        for _ in range(3):  # warmup + compile
            state, metrics = step_fn(state, placed)
        jax.block_until_ready(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step_fn(state, placed)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        final_loss = float(metrics["loss"])

    tokens = steps * global_bs * seq
    return cfg, tokens / dt, dt / steps, final_loss, global_bs


def _decode_bench(model_name="gpt2-large", bs=8, prompt=32, dtype="int8"):
    """Inference decode: steady-state ms/token-step + HBM utilization — the
    serving half of the tracked configs (reference kernel-injected inference:
    ``pt_binding.cpp:1745`` softmax_context decode). The benched serving
    config is int8 kernel-inject (the reference's int8 decode path): fused
    per-layer Pallas blocks + the batched decode-attention kernel halve the
    weight bytes of the memory-bound loop. Two run lengths split the fixed
    cost (prefill + dispatch + fetch RPC) from the marginal decode step;
    e2e is measured at serving length (440 new tokens) so the per-call
    fixed cost is amortized the way a real serving request amortizes it.

    ``decode_hbm_utilization`` is EFFECTIVE-bf16-basis: bf16 weight bytes
    over the measured step vs nominal HBM BW — i.e. speedup-normalized
    against serving bf16 weights naively (how quantized serving is usually
    scored); ``decode_hbm_utilization_actual`` uses the bytes actually read
    (int8 weights + fp32 scales + the live KV window)."""
    import deepspeed_tpu
    engine = deepspeed_tpu.init_inference(model_name, config={"dtype": dtype,
                                                              "max_out_tokens": 512,
                                                              "kernel_inject": True})
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 50257, (bs, prompt)).astype(np.int32)
    times = {}
    for new in (16, 144, 440):
        engine.generate(prompts, max_new_tokens=new)  # compile + warm
        trials = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = engine.generate(prompts, max_new_tokens=new)
            trials.append(time.perf_counter() - t0)
        times[new] = min(trials)
    step = (times[144] - times[16]) / 128
    # pipelined serving: keep 4 requests in flight via submit() so fetch
    # RPCs overlap the next request's execution (continuous serving)
    t0 = time.perf_counter()
    handles = [engine.submit(prompts, max_new_tokens=144) for _ in range(4)]
    piped = [h.result() for h in handles]
    t_piped = time.perf_counter() - t0
    piped_tps = sum(len(r) for res in piped for r in res) / t_piped
    n_params = engine.model_config.num_params()
    from deepspeed_tpu.accelerator import get_accelerator
    hbm_bw = get_accelerator().peak_hbm_bandwidth()
    wb = 1 if dtype == "int8" else 2
    # actual bytes/step: weights + scales (1/128 groups, f32) + KV window
    mc = engine.model_config
    kv_live = (2 * mc.num_layers * bs * mc.kv_heads * 256 * mc.head_size * 2)
    actual = n_params * wb * (1 + (4 / 128 if dtype == "int8" else 0)) + kv_live
    e2e = bs * 440 / times[440]  # no eos: every row emits all 440 tokens
    return {
        "decode_ms_per_token_step": step * 1e3,
        "decode_tokens_per_sec_steady": bs / step,
        "decode_tokens_per_sec_e2e": e2e,
        "decode_e2e_over_steady": e2e / (bs / step),
        "decode_tokens_per_sec_pipelined": piped_tps,
        "decode_hbm_utilization": 2 * n_params / step / hbm_bw,
        "decode_hbm_utilization_actual": actual / step / hbm_bw,
        "decode_dtype": dtype,
    }


def _guard_leg(results, name, fn):
    """Run one bench leg into ``results``. A failing leg ends the run: a
    result with a hole in it is not a result."""
    results[name] = fn()
    return results[name]


def _serving_bench(model_name="gpt2-large", dtype="int8", num_slots=8, n_requests=32,
                   max_new=64, arrival_rate=None, seed=0, max_prompt=192,
                   kernel_inject=True, steps_per_sync=4, prefill_chunk=None):
    """Serving-mode benchmark: a Poisson-arrival mixed-length request stream
    through the continuous-batching scheduler vs the same stream served by
    sequential ``generate()`` calls (the pre-scheduler serving loop).

    ``arrival_rate``: mean requests/sec for the Poisson process; None =
    open-loop saturation (all requests queued at t=0 — the concurrency
    sweep's high end). Reports aggregate decode tokens/sec, TTFT p50/p95,
    and mean slot occupancy, per concurrency level. Every leg is
    fault-isolated: one leg's failure records an error entry and the rest
    of the round's numbers persist."""
    import deepspeed_tpu
    from deepspeed_tpu.comm import comm as _comm
    rng = np.random.default_rng(seed)
    # mixed prompt lengths spanning prefill buckets
    prompt_lens = rng.integers(8, max_prompt, n_requests)
    prompts = [rng.integers(0, 50257, n).astype(np.int32) for n in prompt_lens]
    gaps = (rng.exponential(1.0 / arrival_rate, n_requests) if arrival_rate
            else np.zeros(n_requests))

    def make(continuous, telemetry=None, cfg_extra=None):
        _comm._state["mesh"] = None
        cfg = {"dtype": dtype, "max_out_tokens": 512, "kernel_inject": kernel_inject,
               "continuous_batching": {"enabled": continuous, "num_slots": num_slots,
                                       "steps_per_sync": steps_per_sync}}
        if telemetry:
            cfg["telemetry"] = telemetry
        if cfg_extra:
            cb = cfg_extra.pop("continuous_batching", None)
            cfg.update(cfg_extra)
            if cb:
                cfg["continuous_batching"].update(cb)
        return deepspeed_tpu.init_inference(model_name, config=cfg)

    results = {}

    # --- scheduler path, per concurrency level -------------------------------
    def run_level(slots):
        eng = make(True)
        # PR2-comparable leg: monolithic bucketed prefill (this sweep's
        # random stream shares no prefixes, and its warm pass warms per
        # bucket); the chunked-prefill + radix path is measured against this
        # same baseline in the shared_prefix section below
        sched = eng.scheduler(num_slots=slots, prefill_chunk=0, prefix_cache=False)
        # warm ALL compiled programs the stream will hit (one prefill per
        # bucket + the decode step), mirroring the sequential baseline's
        # warm pass — otherwise bucket compiles land in the timed region
        from deepspeed_tpu.inference.scheduler import _bucket_len
        warm_buckets = sorted({_bucket_len(n, sched.prefill_bucket, sched.max_len)
                               for n in prompt_lens})
        for wb in warm_buckets:
            warm_len = min(wb, sched.max_len - 2 * sched.steps_per_sync)
            # budget 2: token 0 comes from prefill, token 1 forces one
            # decode multi-step so the decode program compiles here too
            sched.submit(np.ones(warm_len, np.int32), max_new_tokens=2).result()
        ttfts = []
        occ = []  # sampled after EVERY step, arrival phase included
        t0 = time.perf_counter()
        handles = []
        arrival = 0.0
        for gap, p in zip(gaps, prompts):
            arrival += gap
            if gap:
                # drive the loop while waiting out the absolute arrival time
                while time.perf_counter() < t0 + arrival:
                    stepped = sched.step()
                    occ.append(sched.cache.occupancy())
                    if not stepped:
                        time.sleep(max(0.0, t0 + arrival - time.perf_counter()))
                        break
            handles.append((time.perf_counter(), sched.submit(p, max_new_tokens=max_new)))
        while any(not h.done for _, h in handles):
            sched.step()
            occ.append(sched.cache.occupancy())
        dt = time.perf_counter() - t0
        toks = sum(len(h.result()) for _, h in handles)
        for ts, h in handles:
            req = h._req
            if req.first_token_ts is not None:
                ttfts.append((req.first_token_ts - req.submit_ts) * 1e3)
        ttfts.sort()
        return {
            "tokens_per_sec": round(toks / dt, 1),
            "ttft_ms_p50": round(ttfts[len(ttfts) // 2], 1) if ttfts else None,
            "ttft_ms_p95": round(ttfts[int(0.95 * (len(ttfts) - 1))], 1) if ttfts else None,
            "mean_slot_occupancy": round(float(np.mean(occ)), 3) if occ else 0.0,
        }

    for slots in sorted({1, max(2, num_slots // 2), num_slots}):
        _guard_leg(results, f"slots{slots}", lambda s=slots: run_level(s))

    # --- sequential generate() baseline (same stream, one request at a time,
    # honoring the same arrival schedule so rate-limited runs compare like
    # for like). Two passes: the cold pass pays one whole-decode-loop
    # compile per distinct prompt shape (the static-batch pathology the
    # scheduler removes); the warm pass is the fair steady-state comparison.
    def run_sequential():
        eng = make(False)
        seq = {}
        for label in ("sequential_generate_cold", "sequential_generate"):
            t0 = time.perf_counter()
            toks = 0
            arrival = 0.0
            for gap, p in zip(gaps, prompts):
                arrival += gap
                wait = t0 + arrival - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                out = eng.generate([p], max_new_tokens=max_new)
                toks += sum(len(r) for r in out)
            seq[label] = {"tokens_per_sec": round(toks / (time.perf_counter() - t0), 1)}
        return seq

    seq = _guard_leg(results, "sequential", run_sequential)
    if isinstance(seq, dict) and "sequential_generate" in seq:
        results.update(seq)
        del results["sequential"]
        slot_tps = [v["tokens_per_sec"] for k, v in results.items()
                    if k.startswith("slots") and "tokens_per_sec" in v]
        if slot_tps:
            results["speedup_vs_sequential"] = round(
                max(slot_tps) / results["sequential_generate"]["tokens_per_sec"], 3)
    _guard_leg(results, "shared_prefix",
               lambda: _shared_prefix_bench(make, num_slots, n_requests, max_new,
                                            seed, prefill_chunk))
    _guard_leg(results, "replicas",
               lambda: _replicas_bench(make, num_slots, max_new, seed,
                                       n_replicas=int(os.environ.get(
                                           "BENCH_SERVING_REPLICAS", "2"))))
    _guard_leg(results, "hier_kv",
               lambda: _hier_kv_bench(make, num_slots, max_new, seed))
    _guard_leg(results, "moe",
               lambda: _moe_serving_bench(num_slots, max_new, seed,
                                          n_requests=int(os.environ.get(
                                              "BENCH_SERVING_MOE", "8"))))
    _guard_leg(results, "disagg",
               lambda: _disagg_bench(make, num_slots, max_new, seed,
                                     prefill_reqs=int(os.environ.get(
                                         "BENCH_SERVING_DISAGG", "4"))))
    _guard_leg(results, "multi_lora",
               lambda: _multi_lora_bench(make, num_slots, max_new, seed,
                                         n_adapters=int(os.environ.get(
                                             "BENCH_SERVING_MULTILORA", "4"))))
    _guard_leg(results, "speculative",
               lambda: _speculative_bench(make, num_slots, n_requests, max_new, seed))
    _guard_leg(results, "fused_block",
               lambda: _fused_block_bench(num_slots, max_new, seed,
                                          n_requests=int(os.environ.get(
                                              "BENCH_SERVING_FUSED", "8"))))
    _guard_leg(results, "kv_int8",
               lambda: _kv_int8_bench(make, num_slots, max_new, seed))
    _guard_leg(results, "observability",
               lambda: _observability_bench(make, max_new, seed))
    _guard_leg(results, "capacity",
               lambda: _capacity_bench(make, max_new, seed,
                                       sample_every=int(os.environ.get(
                                           "BENCH_SERVING_CAPACITY", "8"))))
    _guard_leg(results, "long_context",
               lambda: _long_context_bench(seed,
                                           max_ctx=int(os.environ.get(
                                               "BENCH_SERVING_LONGCTX", "4096"))))
    _guard_leg(results, "autoscale",
               lambda: _autoscale_bench(make, num_slots, max_new, seed,
                                        n_spike=int(os.environ.get(
                                            "BENCH_SERVING_AUTOSCALE", "6"))))
    return results


def _long_context_bench(seed, max_ctx=4096, max_new=32):
    """Long-context leg (BENCH_SERVING_LONGCTX = max context, 0 disables):
    TTFT and mean ITL vs context length 256 -> max_ctx served over chained
    KV extents deliberately sized far below the horizon (the multi-extent
    paged path is on for every length), plus the compile guard the tentpole
    promises: after the FIRST context length warms the stream, every longer
    context reuses the same programs — extent count is an operand, so
    ``new_programs_after_first_ctx`` must stay 0. A seq-parallel arm
    re-measures the largest context's TTFT with prefill sharded over the
    ``seq`` mesh axis when the host exposes enough devices (the
    single-process CPU default skips it with a note)."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.comm import comm as _comm
    from deepspeed_tpu.models.transformer import TransformerConfig, CausalLMModel

    if max_ctx < 256:
        return {"skipped": f"BENCH_SERVING_LONGCTX={max_ctx} < 256"}
    extent = 512  # tiny extents: a 4k context spans an 8-extent chain
    mcfg = TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2,
                             num_heads=4, num_kv_heads=2, max_seq_len=max_ctx,
                             intermediate_size=128, attention_impl="flash",
                             scan_layers=False, decode_block_kv=64)
    rng = np.random.default_rng(seed + 57)
    ctxs = [c for c in (256, 512, 1024, 2048, 4096, 8192) if c <= max_ctx]

    def build(mesh_kw=None, **sched_kw):
        _comm._state["mesh"] = None
        if mesh_kw:
            _comm.initialize_mesh(**mesh_kw)
        eng = deepspeed_tpu.init_inference(
            CausalLMModel(mcfg),
            config={"dtype": "float32", "decode_block_kv": 64,
                    "continuous_batching": {"enabled": True, "num_slots": 4}})
        sched = eng.scheduler(max_len=min(extent, max_ctx), prefill_chunk=64,
                              max_extents=max(1, max_ctx // extent), **sched_kw)
        return eng, sched

    def run_one(sched, ctx):
        prompt = rng.integers(0, 256, ctx - max_new).astype(np.int32)
        t0 = time.perf_counter()
        h = sched.submit(prompt, max_new_tokens=max_new)
        toks = h.result()
        dt = time.perf_counter() - t0
        req = h._req
        ttft = ((req.first_token_ts - req.submit_ts) * 1e3
                if req.first_token_ts is not None else None)
        itl = ((dt * 1e3 - (ttft or 0.0)) / max(1, len(toks) - 1))
        return {"ttft_ms": round(ttft, 1) if ttft is not None else None,
                "itl_ms": round(itl, 2),
                "extents_spanned": -(-ctx // sched.max_len)}

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    _, sched = build()
    out = {"extent_tokens": sched.max_len, "max_extents": sched.cache.max_extents,
           "max_new": max_new, "per_context": {}}
    run_one(sched, ctxs[0])  # warm pass: every program the stream needs
    n0 = len(compiles)
    for ctx in ctxs:
        out["per_context"][str(ctx)] = run_one(sched, ctx)
    out["new_programs_after_first_ctx"] = len(compiles) - n0

    # seq-parallel arm: shard the largest context's prefill over the seq axis
    n_dev = len(jax.devices())
    seq = max(d for d in (1, 2, 4, 8) if d <= n_dev and n_dev % d == 0)
    if seq < 2:
        out["seq_parallel"] = {"skipped": f"{n_dev} device(s): no seq axis"}
    else:
        _, sp = build(mesh_kw={"seq": seq}, seq_parallel_min_tokens=128)
        run_one(sp, ctxs[0])  # warm (incl. the seqp program set)
        out["seq_parallel"] = dict(run_one(sp, ctxs[-1]), seq_shards=seq,
                                   single_shard_ttft_ms=out["per_context"]
                                   [str(ctxs[-1])]["ttft_ms"])
    _comm._state["mesh"] = None
    return out


def _observability_bench(make, max_new, seed):
    """Telemetry-overhead leg: one warmed decode request with the sink OFF
    vs ON (full request tracing + windowed histograms + flight recorder +
    SLO engine idle), reporting the per-request tax — the number the
    observability lane's CI guard bounds — plus proof the artifacts
    (trace.json, flight dump) actually land."""
    from deepspeed_tpu.telemetry import set_sink
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, 50257, 32).astype(np.int32)

    def run(tel_cfg):
        set_sink(None)
        eng = make(True, telemetry=tel_cfg)
        sched = eng.scheduler(num_slots=2)
        sched.submit(prompt, max_new_tokens=16).result()  # warm the programs
        t0 = time.perf_counter()
        sched.submit(prompt, max_new_tokens=max_new).result()
        return eng, time.perf_counter() - t0

    try:
        _, base_s = run(None)
        tdir = tempfile.mkdtemp(prefix="bench_obs_")
        eng, traced_s = run({"enabled": True, "output_path": tdir,
                             "request_tracing": True})
        dump = eng.telemetry.dump_flight("bench_probe")
        eng.telemetry.close()  # forces trace rewrite + flight finalize
        return {
            "decode_s_untraced": round(base_s, 4),
            "decode_s_traced": round(traced_s, 4),
            "tracing_overhead_x": round(traced_s / max(base_s, 1e-9), 3),
            "trace_json_written": os.path.exists(eng.telemetry.trace_path),
            "flight_dump_written": bool(dump) and os.path.exists(dump),
        }
    finally:
        set_sink(None)


def _capacity_bench(make, max_new, seed, sample_every=8, n_requests=6):
    """Capacity-observability leg (telemetry/capacity.py): the same warmed
    decode stream with fenced roofline sampling effectively NEVER vs every
    1/``sample_every`` syncs (BENCH_SERVING_CAPACITY) — sink enabled in
    BOTH arms, so the ratio isolates the fencing tax from the sink's
    pre-existing per-step cost (which the observability leg already
    reports). The instrumented-vs-off tokens/sec ratio carries the
    acceptance bar (>= 0.87x), alongside the live serving MFU /
    HBM-bandwidth-utilization / goodput gauges and the host-gap share of
    wall time the run measured."""
    from deepspeed_tpu.telemetry import set_sink
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 50257, 32).astype(np.int32)
               for _ in range(n_requests)]

    def run(tel_cfg):
        set_sink(None)
        eng = make(True, telemetry=tel_cfg)
        sched = eng.scheduler(num_slots=4)
        sched.submit(prompts[0], max_new_tokens=8).result()  # warm programs
        t0 = time.perf_counter()
        hs = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
        toks = sum(len(h.result()) for h in hs)
        return eng, sched, toks / (time.perf_counter() - t0)

    try:
        off_dir = tempfile.mkdtemp(prefix="bench_cap_off_")
        off_eng, _, off_tps = run({"enabled": True, "output_path": off_dir,
                                   "capacity_sample_every": 1 << 20})
        off_eng.telemetry.close()
        tdir = tempfile.mkdtemp(prefix="bench_cap_")
        eng, sched, on_tps = run({"enabled": True, "output_path": tdir,
                                  "capacity_sample_every": sample_every})
        snap = eng.telemetry.snapshot()
        gauges = snap.get("gauges", {})
        cap = sched.capacity
        out = {
            "tokens_per_sec_off": round(off_tps, 1),
            "tokens_per_sec_instrumented": round(on_tps, 1),
            # the contract number: sampled fencing must cost < 13%
            "instrumented_ratio": round(on_tps / max(off_tps, 1e-9), 3),
            "sample_every": sample_every,
            "capacity_samples": cap.samples if cap is not None else 0,
            "mfu": round(gauges.get("serving/mfu", 0.0), 6),
            "hbm_bw_util": round(gauges.get("serving/hbm_bw_util", 0.0), 6),
            "goodput_fraction": round(gauges.get("serving/goodput_fraction",
                                                 1.0), 4),
            "host_gap_total_s": (round(sched._gap.total_gap_s, 4)
                                 if sched._gap is not None else None),
            "programs_registered": (len(cap.programs) if cap is not None
                                    else 0),
        }
        eng.telemetry.close()
        return out
    finally:
        set_sink(None)


def _speculative_bench(make, num_slots, n_requests, max_new, seed, spec_tokens=4):
    """Self-speculative decoding leg: a repetitive request stream (the
    agent-loop/template shape prompt-lookup drafting targets) served with
    ``spec_tokens`` drafted-and-verified tokens per step vs the identical
    stream through the non-speculative scheduler. Reports tokens/sec both
    ways, the acceptance rate, and mean tokens per (row, verify step) —
    > 1.0 means speculation is netting multi-token steps."""
    out = {}
    prompts = None
    for label, overrides in (("baseline", {}),
                             ("speculative", {"spec_tokens": spec_tokens})):
        eng = make(True)
        sched = eng.scheduler(num_slots=num_slots, **overrides)
        if prompts is None:  # both legs serve the SAME stream
            rng = np.random.default_rng(seed + 13)
            V = eng.model_config.vocab_size
            cap = sched.max_len - max_new - 2 * sched.steps_per_sync - spec_tokens - 1
            if cap < 16:
                return {"skipped": f"slot capacity {sched.max_len} too small for the "
                                   f"speculative stream at max_new={max_new}"}
            pattern = rng.integers(0, V, 7).astype(np.int32)
            plen = min(96, cap)
            prompts = [np.concatenate([np.resize(pattern, plen - 2),
                                       rng.integers(0, V, 2).astype(np.int32)])
                       for _ in range(n_requests)]
        sched.submit(prompts[0], max_new_tokens=max_new).result()  # warm programs
        t0 = time.perf_counter()
        handles = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
        toks = sum(len(h.result()) for h in handles)
        dt = time.perf_counter() - t0
        entry = {"tokens_per_sec": round(toks / dt, 1)}
        if label == "speculative":
            entry.update({
                "spec_steps": sched.spec_steps,
                "drafted": sched.spec_drafted,
                "accepted": sched.spec_accepted,
                "acceptance_rate": round(
                    sched.spec_accepted / max(1, sched.spec_drafted), 3),
                # delivered tokens per (row, verify step): accepted drafts
                # + the always-produced column-0 token — NOT an accepted
                # count (which acceptance_rate already covers)
                "mean_tokens_per_step": round(
                    sched.mean_spec_tokens_per_step(), 3),
            })
        out[label] = entry
    out["speedup_vs_baseline"] = round(
        out["speculative"]["tokens_per_sec"]
        / max(out["baseline"]["tokens_per_sec"], 1e-9), 3)
    out["spec_tokens"] = spec_tokens
    return out


def _replicas_bench(make, num_slots, max_new, seed, n_replicas=2):
    """Replica-scaling leg: the same prompt-family stream served by 1
    scheduler replica vs ``n_replicas`` behind the ReplicaSet's dispatch
    (prefix-sticky + least-loaded), single-threaded closed-loop pump.

    The stream is built so its FAMILY working set (long shared prefixes,
    cyclic access — LRU's worst case) overflows one replica's slot pool but
    fits the fleet's: on this serial-CPU smoke the replica win is therefore
    aggregate KV capacity — sticky routing keeps each replica's families
    radix-RESIDENT, so prefill compute (the dominant cost at these prompt
    lengths) collapses to prefix copies. On a pod each replica is its own
    tensor-sharded chip group stepping in parallel (the gateway runs one
    pump thread per replica), so compute scales on top of the capacity win
    measured here. Reports per-leg tok/s, TTFT p95, aggregate prefix-cache
    hit rate, the fleet speedup, and per-chip-style scaling efficiency."""
    from deepspeed_tpu.serving import ReplicaSet

    chunk = 16
    # working set sized to overflow ONE pool (families ~= slots, plus the
    # live rows competing for them) while a fleet of n holds families/n
    # comfortably resident per replica
    families = max(num_slots, 2 * n_replicas)
    rounds = 3
    out = {"replica_counts": sorted({1, n_replicas}), "families": families,
           "rounds": rounds}
    prompts = None
    for n in sorted({1, n_replicas}):
        eng = make(True)
        rs = ReplicaSet.build(eng, n, num_slots=num_slots, prefill_chunk=chunk)
        sched = rs.primary
        if sched.radix is None or sched.prefill_chunk == 0:
            return {"skipped": "replica leg needs the chunked radix path"}
        budget = 2 * sched.steps_per_sync
        cap = sched.max_len - max_new - budget
        n_chunks = min(5, (cap - 8) // sched.prefill_chunk)
        if n_chunks < 2:
            return {"skipped": f"slot capacity {sched.max_len} too small for a "
                               f"multi-chunk family prefix at max_new={max_new}"}
        if prompts is None:
            rng = np.random.default_rng(seed + 11)
            V = eng.model_config.vocab_size
            pre_len = n_chunks * sched.prefill_chunk
            sfx_cap = min(8, cap - pre_len)
            prefixes = [rng.integers(0, V, pre_len).astype(np.int32)
                        for _ in range(families)]
            # cyclic family order: each round revisits every family —
            # exactly the access pattern that defeats one pool's LRU while
            # a resident fleet serves it from the trie
            prompts = [np.concatenate([prefixes[f % families],
                                       rng.integers(0, V, int(rng.integers(2, sfx_cap)))
                                       .astype(np.int32)])
                       for f in range(families * rounds)]
            out["prefix_tokens"] = int(pre_len)
        # warm the program set on replica 0 (shared by every replica): one
        # cold request + one repeat for the copy program, off the sticky map
        warm = np.concatenate([np.full(pre_len, 3, np.int32), [7, 8, 9]])
        sched.submit(warm, max_new_tokens=budget + 2).result()
        sched.submit(warm, max_new_tokens=budget + 2).result()
        for rep in rs:
            if rep.scheduler.radix is not None:
                rep.scheduler.radix.hits = rep.scheduler.radix.misses = 0
                rep.scheduler.radix.evictions = 0
        # closed-loop pump at the SAME offered concurrency for every leg
        # (2 clients per FLEET-SIZED replica count): the single-replica leg
        # serves the whole client population from one pool — live rows and
        # retained prefixes fight for its slots — while the fleet spreads
        # ~2 clients per replica and keeps families resident
        live_cap = 2 * n_replicas
        handles = []
        i = 0
        t0 = time.perf_counter()
        while i < len(prompts) or any(not h.done for h in handles):
            while (i < len(prompts)
                   and sum(1 for h in handles if not h.done) < live_cap):
                rep, h = rs.dispatch(prompts[i], max_new_tokens=max_new)
                if h is None:
                    break
                handles.append(h)
                i += 1
            progressed = False
            for rep in rs:
                if not rep.idle():
                    rep.step()
                    progressed = True
            if not progressed and i >= len(prompts):
                break
        dt = time.perf_counter() - t0
        toks = sum(len(h.result()) for h in handles)
        ttfts = sorted((h._req.first_token_ts - h._req.submit_ts) * 1e3
                       for h in handles if h._req.first_token_ts is not None)
        hits = sum(r.scheduler.radix.hits for r in rs)
        misses = sum(r.scheduler.radix.misses for r in rs)
        out[f"replicas{n}"] = {
            "tokens_per_sec": round(toks / dt, 1),
            "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 2) if ttfts else None,
            "ttft_ms_p95": round(float(np.percentile(ttfts, 95)), 2) if ttfts else None,
            "aggregate_hit_rate": round(hits / max(1, hits + misses), 3),
            "evictions": sum(r.scheduler.radix.evictions for r in rs),
            "dispatched_per_replica": [r.dispatched for r in rs],
            "compiled_programs": rs.compiled_program_count(),
        }
    lo = out.get("replicas1", {})
    hi = out.get(f"replicas{n_replicas}", {})
    if lo.get("tokens_per_sec") and hi.get("tokens_per_sec"):
        out["speedup"] = round(hi["tokens_per_sec"] / lo["tokens_per_sec"], 3)
        out["scaling_efficiency"] = round(out["speedup"] / n_replicas, 3)
        if lo.get("ttft_ms_p95") and hi.get("ttft_ms_p95"):
            out["ttft_p95_speedup"] = round(lo["ttft_ms_p95"] / hi["ttft_ms_p95"], 3)
    return out


def _autoscale_bench(make, num_slots, max_new, seed, n_spike=6):
    """Elastic-fleet leg (BENCH_SERVING_AUTOSCALE = spike request count, 0
    disables): one ramp -> spike -> decay open-loop arrival trace served
    twice — a static single replica vs the FleetController closing the
    loop (queue-wait scale-up at the spike, brownout shedding of
    batch-tier work once the fleet is at max_replicas, calm-window
    two-phase scale-down after the decay). Reports per-leg completions,
    sheds, arrival-to-first-token p95, the replica-count trace, the
    controller's decision tally, and the zero-new-XLA-programs guard
    across the whole grow/shed/shrink cycle (the elastic-fleet contract:
    a resize costs HBM, never a compile)."""
    from deepspeed_tpu.inference.config import AutoscalerConfig
    from deepspeed_tpu.serving import FleetController, FleetSignals, ReplicaSet

    if n_spike <= 0:
        return {"skipped": "BENCH_SERVING_AUTOSCALE=0"}
    rng = np.random.default_rng(seed + 23)
    ramp = max(2, n_spike // 3)
    plan = []  # (arrival_s, tier) — the spike floods at one instant
    t = 0.0
    for _ in range(ramp):
        plan.append((t, "standard"))
        t += 0.4
    for i in range(n_spike):
        plan.append((t, "batch" if i % 2 else "standard"))
    for _ in range(ramp):
        t += 0.4
        plan.append((t, "standard"))
    prompts = [rng.integers(0, 50257, int(rng.integers(8, 24))).astype(np.int32)
               for _ in plan]
    mnt = min(max_new, 24)
    out = {"requests": len(plan), "spike_requests": n_spike}

    for leg in ("static", "autoscaled"):
        eng = make(True)
        rs = ReplicaSet.build(eng, 1, num_slots=num_slots)
        budget = 2 * rs.primary.steps_per_sync
        # warm the shared program set: every stream prompt shares the warm
        # prompt's prefill bucket, and budget+2 forces the decode multi-step
        rs.primary.submit(np.ones(24, np.int32), max_new_tokens=budget + 2).result()
        warm_programs = rs.compiled_program_count()
        ctl = None
        if leg == "autoscaled":
            ctl = FleetController(AutoscalerConfig({
                "enabled": True, "interval_s": 0.05, "min_replicas": 1,
                "max_replicas": 2, "queue_wait_up_s": 0.4,
                "cooldown_up_s": 1.0, "cooldown_down_s": 2.0,
                "scale_down_occupancy": 0.5, "brownout_tiers": ["standard"],
                "brownout_step_s": 0.3, "brownout_cooldown_s": 0.6}))
            ctl.scale_up_fn = lambda: rs.add_replica() is not None

            def _scale_down():
                for rep in reversed(list(rs)):
                    if rep.idx and not rep.pending_drain and not rep.retired:
                        rs.begin_scale_down(rep.idx)
                        return True
                return False
            ctl.scale_down_fn = _scale_down
            # the level lives on the controller; the pump below reads it
            ctl.brownout_fn = lambda level: True
        pending = sorted(zip(plan, prompts), key=lambda it: it[0][0])
        handles = []   # (arrival_s, handle)
        shed = 0
        trace = []
        t0 = time.perf_counter()

        def _pump_tick():
            nonlocal shed, pending
            now = time.perf_counter() - t0
            # brownout door: an engaged ladder sheds the sub-bar tier from
            # the queue (what the gateway's evict/503 path does)
            if ctl is not None and ctl.brownout_level >= 1:
                keep = []
                for item in pending:
                    if item[0][0] <= now and item[0][1] == "batch":
                        shed += 1
                    else:
                        keep.append(item)
                pending = keep
            while pending and pending[0][0][0] <= now:
                rep, h = rs.dispatch(pending[0][1], max_new_tokens=mnt)
                if h is None:
                    break
                # queue wait is arrival -> dispatch in this loop's clock;
                # submit -> first token rides the scheduler's own stamps
                # (the telemetry clock has a different epoch)
                handles.append((now - pending[0][0][0], h))
                pending.pop(0)
            if ctl is not None:
                ready = [it for it in pending if it[0][0] <= now]
                ctl.tick(FleetSignals(
                    now=now, queue_depth=len(ready),
                    oldest_wait_s=(now - min(it[0][0] for it in ready))
                    if ready else 0.0,
                    occupancy=float(np.mean(
                        [r.scheduler.cache.occupancy()
                         for r in rs if not r.retired])),
                    replicas=rs.active_count(),
                    replicas_active=sum(1 for r in rs if r.available()),
                    inflight=sum(1 for _, h in handles if not h.done)))
            trace.append(rs.active_count())
            if not rs.pump_once() and not ready_sleepless(now):
                time.sleep(0.01)

        def ready_sleepless(now):
            return (pending and pending[0][0][0] <= now) or any(
                not h.done for _, h in handles)

        while pending or any(not h.done for _, h in handles):
            _pump_tick()
        dt = time.perf_counter() - t0
        # calm window: let the controller de-escalate and retire the spare
        # pool (two-phase pending-drain -> retire rides pump_once)
        if ctl is not None:
            calm_deadline = time.perf_counter() + 8.0
            while ((rs.active_count() > 1 or ctl.brownout_level > 0)
                   and time.perf_counter() < calm_deadline):
                _pump_tick()
                time.sleep(0.02)
        toks = sum(len(h.result()) for _, h in handles)
        ttfts = sorted((wait + h._req.first_token_ts - h._req.submit_ts) * 1e3
                       for wait, h in handles
                       if h._req.first_token_ts is not None)
        out[leg] = {
            "completed": len(handles), "shed": shed,
            "tokens_per_sec": round(toks / dt, 1),
            "ttft_from_arrival_ms_p95":
                round(float(np.percentile(ttfts, 95)), 1) if ttfts else None,
            "max_replicas": max(trace), "final_replicas": rs.active_count(),
            "new_programs": rs.compiled_program_count() - warm_programs,
        }
        if ctl is not None:
            out[leg]["decisions"] = {k: int(v) for k, v in ctl.counters.items()}
    lo, hi = out.get("static", {}), out.get("autoscaled", {})
    if lo.get("ttft_from_arrival_ms_p95") and hi.get("ttft_from_arrival_ms_p95"):
        out["ttft_p95_static_over_autoscaled"] = round(
            lo["ttft_from_arrival_ms_p95"] / hi["ttft_from_arrival_ms_p95"], 3)
    return out


def _multi_lora_bench(make, num_slots, max_new, seed, n_adapters=4, rounds=2):
    """multi_lora leg: an N-adapter round-robin tenant stream (every request
    names a different tenant's LoRA variant than the last) served two ways:

    - **paged** (this PR): one base tree + the rank-bucketed adapter store;
      heterogeneous-adapter batches decode CONCURRENTLY through one fused
      program (per-row page gather).
    - **rotation** (the only pre-PR alternative): merged weights per tenant,
      rotated through the PR 9 pause/flush/swap_weights protocol — every
      tenant switch drains the pool, invalidates all KV, and serializes.

    Reports aggregate tok/s, OPEN-LOOP TTFT p95 (first token since leg
    start — the whole round-robin burst arrives at t=0, so queue/serialize
    time counts for both legs; rotation's serial tenant runs pay it in
    full), adapter/page hit rates, swap counts,
    and the swap-AMORTIZATION table: rotation throughput as the per-tenant
    run length k grows (1 = strict round robin). ``crossover_k`` is the
    smallest measured k where rotation reaches >= 90% of the paged
    throughput — the operating region where merged-weight rotation stops
    being catastrophically behind (higher = paged wins over more traffic).

    Runs both legs at the model compute dtype, forcing bf16 when the bench
    dtype is int8 (rotation needs host-mergeable weights; the paged leg
    alone would be an unfair comparison across tiers)."""
    import jax as _jax
    from deepspeed_tpu.runtime.lora import LoRAModel

    chunk = 16
    cfg_extra = {"continuous_batching": {"prefill_chunk": chunk}}
    eng = make(True, cfg_extra=dict(cfg_extra, dtype="bf16"))
    params = _jax.device_get(eng.params)
    rng = np.random.default_rng(seed + 57)
    out = {"n_adapters": int(n_adapters), "rounds": rounds,
           "prefill_chunk": chunk, "dtype": "bf16"}

    # per-tenant adapters (rank 8 bucket) with nonzero deltas
    lora = LoRAModel(eng.module, r=8, alpha=16.0)

    def bump(node, key):
        if isinstance(node, dict) and "a" in node and "b" in node \
                and not isinstance(node["a"], dict):
            key[0] += 1
            import jax.numpy as jnp
            return {"a": node["a"],
                    "b": _jax.random.normal(_jax.random.key(key[0]),
                                            node["b"].shape) * 0.02}
        return {k: bump(v, key) for k, v in node.items()}

    tenants = [f"tenant-{i}" for i in range(n_adapters)]
    trees = {t: bump(lora.init_lora(params, _jax.random.key(i + 1)),
                     [1000 * (i + 1)]) for i, t in enumerate(tenants)}
    merged = {t: _jax.device_get(lora.merge({"base": params, "lora": tr}))
              for t, tr in trees.items()}

    # ---- paged (batched mixed-adapter) leg ---------------------------------
    peng = make(True, cfg_extra=dict(
        cfg_extra, dtype="bf16",
        continuous_batching={"prefill_chunk": chunk,
                             "multi_lora": {"enabled": True,
                                            "pool_slots": max(2, n_adapters),
                                            "rank_buckets": [8]}}))
    peng.params = _jax.device_put(params)  # identical weights across legs
    for t, tr in trees.items():
        peng.register_adapter(t, lora_tree=tr, alpha=16.0)
    sched = peng.scheduler(num_slots=num_slots, prefill_chunk=chunk)

    # round-robin stream: per-tenant system prefix (as long as slot capacity
    # allows, up to 4 chunks) + a fresh short suffix. The long prefix is the
    # structural contrast: rotation's swap invalidates ALL KV per tenant
    # switch, so it re-prefills the prefix on every revisit; the paged path
    # retains it per adapter
    V = eng.model_config.vocab_size
    budget = 2 * sched.steps_per_sync
    n_chunks = min(4, (sched.max_len - max_new - budget - 8) // chunk)
    if n_chunks < 1:
        return {"skipped": f"slot capacity {sched.max_len} too small for a "
                           f"chunked tenant prefix at max_new={max_new}"}
    pre_len = n_chunks * chunk
    out["prefix_tokens"] = int(pre_len)
    prefixes = {t: rng.integers(0, V, pre_len).astype(np.int32) for t in tenants}
    n_reqs = n_adapters * rounds * 2
    stream = [(tenants[i % n_adapters],
               np.concatenate([prefixes[tenants[i % n_adapters]],
                               rng.integers(0, V, 3).astype(np.int32)]))
              for i in range(n_reqs)]
    # warm: base + two adapters mixed (lora program variants + page loads)
    warmup = [sched.submit(np.full(8, 3, np.int32), max_new_tokens=2)]
    warmup += [sched.submit(np.full(8, 3, np.int32), max_new_tokens=2,
                            adapter_id=t) for t in tenants[:2]]
    for h in warmup:
        h.result()
    store = peng.adapter_store()
    store.acquires = store.resident_hits = 0
    t0 = time.perf_counter()
    t0_tel = sched.telemetry.now()  # first_token_ts rides the telemetry clock
    handles = [sched.submit(p, max_new_tokens=max_new, adapter_id=t)
               for t, p in stream]
    toks = sum(len(h.result()) for h in handles)
    dt = time.perf_counter() - t0
    ttfts = sorted((h._req.first_token_ts - t0_tel) * 1e3
                   for h in handles if h._req.first_token_ts is not None)
    paged_tps = toks / dt
    out["paged"] = {
        "tokens_per_sec": round(paged_tps, 1),
        "ttft_ms_p95": round(float(np.percentile(ttfts, 95)), 2) if ttfts else None,
        "adapter_hit_rate": round(store.hit_rate(), 3),
        "adapter_loads": store.loads, "adapter_evicts": store.evicts,
        "prefix_hit_rate": round(sched.radix.hit_rate(), 3),
    }

    # ---- merged-weight swap-rotation baseline ------------------------------
    def rotation(run_len):
        reng = make(True, cfg_extra=dict(cfg_extra, dtype="bf16"))
        rsched = reng.scheduler(num_slots=num_slots, prefill_chunk=chunk)
        # group the SAME stream into per-tenant runs of run_len
        by_tenant = {t: [p for tt, p in stream if tt == t] for t in tenants}
        runs = []
        cursor = {t: 0 for t in tenants}
        while any(cursor[t] < len(by_tenant[t]) for t in tenants):
            for t in tenants:
                i = cursor[t]
                if i < len(by_tenant[t]):
                    runs.append((t, by_tenant[t][i:i + run_len]))
                    cursor[t] = i + run_len
        rsched.submit(np.full(8, 3, np.int32), max_new_tokens=2).result()  # warm
        swaps = 0
        version = 0
        ttfts = []
        t0 = time.perf_counter()
        t0_tel = rsched.telemetry.now()
        toks = 0
        for t, prompts in runs:
            version += 1
            rsched.pause()
            rsched.flush()
            rsched.swap_weights(_jax.device_put(merged[t]), version=version)
            rsched.resume()
            swaps += 1
            hs = [rsched.submit(p, max_new_tokens=max_new) for p in prompts]
            toks += sum(len(h.result()) for h in hs)
            ttfts += [(h._req.first_token_ts - t0_tel) * 1e3
                      for h in hs if h._req.first_token_ts is not None]
        dt = time.perf_counter() - t0
        return {"tokens_per_sec": round(toks / dt, 1),
                "ttft_ms_p95": round(float(np.percentile(sorted(ttfts), 95)), 2)
                if ttfts else None,
                "swaps": swaps}

    out["rotation"] = rotation(1)  # strict round robin: swap every request
    out["speedup_vs_rotation"] = round(
        paged_tps / max(1e-9, out["rotation"]["tokens_per_sec"]), 3)
    out["ttft_p95_ratio_rotation_over_paged"] = (
        round(out["rotation"]["ttft_ms_p95"] / out["paged"]["ttft_ms_p95"], 3)
        if out["rotation"]["ttft_ms_p95"] and out["paged"]["ttft_ms_p95"] else None)
    # swap-amortization: rotation at growing per-tenant run lengths
    amort = {"1": out["rotation"]["tokens_per_sec"]}
    crossover = None
    for k in (2, rounds * 2):
        r = rotation(k)
        amort[str(k)] = r["tokens_per_sec"]
        if crossover is None and r["tokens_per_sec"] >= 0.9 * paged_tps:
            crossover = k
    out["rotation_amortization_tok_s"] = amort
    out["crossover_k"] = crossover  # None: rotation never caught up
    return out


def _moe_serving_bench(num_slots, max_new, seed, n_requests=8):
    """MoE serving leg: top-k expert-parallel continuous-batching decode vs
    a DENSE model of equal ACTIVATED FLOPs (intermediate = top_k x expert
    ffn). The ratio QUANTIFIES the dispatch cost honestly: the
    deterministic capacity-free serving dispatch computes the full expert
    batch and masks at the combine (E/top_k x activated FLOPs — the
    standard small-batch dense-MoE-inference trade under XLA static
    shapes), so dense-equiv is an upper bound, not a target. Then the
    cold-expert residency sweep (all-hot vs half-resident paged pools,
    same weights) with load/evict/replay counters and the
    zero-mid-stream-recompile check. Self-contained tiny models: the leg
    measures the dispatch/paging machinery, not model quality."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.comm import comm as _comm
    from deepspeed_tpu.models import get_model
    from deepspeed_tpu.telemetry import set_sink

    E, topk = 8, 2
    slots = min(num_slots, 4)
    rng = np.random.default_rng(seed + 47)
    prompts = [rng.integers(0, 255, int(n)).astype(np.int32)
               for n in rng.integers(8, 96, n_requests)]

    def build(model, offload=None, params=None):
        _comm._state["mesh"] = None
        set_sink(None)
        cb = {"enabled": True, "num_slots": slots}
        if offload:
            cb["expert_offload"] = {"enabled": True, "resident_experts": offload}
        return deepspeed_tpu.init_inference(
            model, config={"dtype": "float32", "continuous_batching": cb},
            params=params)

    def run(eng):
        sched = eng.scheduler()
        # warm the program set outside the timed region (offload engines
        # additionally warmed every ladder variant at build): a multi-chunk
        # prompt covers the (K, C) and idle-pool (1, C) fused variants, a
        # budget past one sync reaches the pure-decode (K, 1) program, the
        # repeat covers the radix copy program, and a sampled request the
        # sampling variants
        warm = (sched.prefill_chunk or 16) + 8
        budget = 2 * sched.steps_per_sync
        sched.submit(np.ones(warm, np.int32), max_new_tokens=budget).result()
        sched.submit(np.ones(warm, np.int32), max_new_tokens=budget).result()
        sched.submit(np.ones(16, np.int32), max_new_tokens=budget,
                     do_sample=True).result()
        programs_before = sched.compiled_program_count()
        # baseline the churn counters too: the warm submits above hot-load
        # pages themselves, and reporting lifetime totals would conflate
        # warm-up traffic with the timed stream
        if sched.experts is not None:
            loads0, evicts0 = sched.experts.loads, sched.experts.evicts
            replays0 = sched.expert_replays
        token_ts = {i: [] for i in range(len(prompts))}
        t0 = time.perf_counter()
        handles = [
            sched.submit(p, max_new_tokens=max_new, seed=seed + i,
                         on_token=lambda t, d, i=i:
                         token_ts[i].append(time.perf_counter()))
            for i, p in enumerate(prompts)]
        while any(not h.done for h in handles):
            sched.step()
        dt = time.perf_counter() - t0
        toks = sum(len(h.result()) for h in handles)
        ttfts = sorted((ts[0] - t0) * 1e3 for ts in token_ts.values() if ts)
        itls = sorted(d for ts in token_ts.values()
                      for d in np.diff(np.asarray(ts)) * 1e3)

        def pct(v, q):
            return round(v[min(len(v) - 1, int(q * (len(v) - 1)))], 2) if v else None

        res = {"tokens_per_sec": round(toks / dt, 1),
               "ttft_ms_p50": pct(ttfts, 0.5), "ttft_ms_p95": pct(ttfts, 0.95),
               "itl_ms_p95": pct(itls, 0.95),
               "new_programs_mid_stream":
                   sched.compiled_program_count() - programs_before}
        if sched.experts is not None:
            res.update({"expert_loads": sched.experts.loads - loads0,
                        "expert_evicts": sched.experts.evicts - evicts0,
                        "expert_replays": sched.expert_replays - replays0,
                        "resident_fraction": sched.experts.resident_fraction()})
        return res

    def moe_model():
        return get_model("tiny-moe", num_experts=E, moe_top_k=topk)

    base_ffn = moe_model().cfg.ffn_size
    out = {"config": {"num_experts": E, "top_k": topk, "expert_ffn": base_ffn,
                      "num_slots": slots, "requests": len(prompts),
                      "max_new": max_new}}
    moe_eng = build(moe_model())
    params = jax.device_get(moe_eng.params)
    out["moe"] = run(moe_eng)
    out["dense_equiv_flops"] = run(build(
        get_model("tiny-moe", num_experts=0, intermediate_size=base_ffn * topk)))
    out["offload_all_hot"] = run(build(moe_model(), offload=E, params=params))
    out["offload_half_cold"] = run(build(moe_model(), offload=E // 2,
                                         params=params))
    out["moe_over_dense_equiv_tok_s"] = round(
        out["moe"]["tokens_per_sec"]
        / out["dense_equiv_flops"]["tokens_per_sec"], 3)
    out["all_hot_over_half_cold_tok_s"] = round(
        out["offload_all_hot"]["tokens_per_sec"]
        / out["offload_half_cold"]["tokens_per_sec"], 3)
    out["half_cold_zero_recompiles"] = (
        out["offload_half_cold"]["new_programs_mid_stream"] == 0)
    return out


def _fused_block_bench(num_slots, max_new, seed, n_requests=8):
    """Fused decode-block leg (BENCH_SERVING_FUSED): llama-shaped int8
    serving through the fused per-layer kernels (3 resident kernels/layer,
    ``fused_block`` step programs) vs the SAME weights served through the
    per-projection int8 programs (``fused_decode_block=False``). Reports
    per-mode decode ``step_ms`` p50/p95 and tokens/sec, the max-abs logit
    gap on a shared greedy request (the numeric-parity contract the kernel
    tests pin at 1e-4 in fp32 — here in serving dtype), the program kinds
    actually compiled, and the zero-mid-stream-recompile check. Tiny
    self-contained models: the leg measures the kernel fusion win on the
    scheduler hot path, not model quality."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.comm import comm as _comm
    from deepspeed_tpu.telemetry import set_sink

    slots = min(num_slots, 4)
    rng = np.random.default_rng(seed + 53)
    prompts = [rng.integers(0, 255, int(n)).astype(np.int32)
               for n in rng.integers(8, 96, n_requests)]
    probe = np.asarray([5, 6, 7, 8, 9], np.int32)  # shared logit probe

    def build(fused, params=None):
        _comm._state["mesh"] = None
        set_sink(None)
        cfg = {"dtype": "int8", "kernel_inject": True,
               "fused_decode_block": fused,
               "continuous_batching": {"enabled": True, "num_slots": slots,
                                       "collect_logits": True}}
        return deepspeed_tpu.init_inference("tiny", config=cfg, params=params)

    def run(eng):
        sched = eng.scheduler()
        # warm the program set outside the timed region: a multi-chunk
        # prompt covers the (K, C) and idle-pool (1, C) step variants, a
        # budget past one sync reaches the pure-decode (K, 1) program, the
        # repeat covers the radix copy program, and a sampled request the
        # sampling variants
        warm = (sched.prefill_chunk or 16) + 8
        budget = 2 * sched.steps_per_sync
        sched.submit(np.ones(warm, np.int32), max_new_tokens=budget).result()
        sched.submit(np.ones(warm, np.int32), max_new_tokens=budget).result()
        sched.submit(np.ones(16, np.int32), max_new_tokens=budget,
                     do_sample=True).result()
        programs_before = sched.compiled_program_count()
        probe_logits = sched.submit(probe, max_new_tokens=8).result_logits()
        step_ms = []
        t0 = time.perf_counter()
        handles = [sched.submit(p, max_new_tokens=max_new, seed=seed + i)
                   for i, p in enumerate(prompts)]
        while any(not h.done for h in handles):
            ts = time.perf_counter()
            sched.step()
            step_ms.append((time.perf_counter() - ts) * 1e3)
        dt = time.perf_counter() - t0
        toks = sum(len(h.result()) for h in handles)
        step_ms.sort()

        def pct(v, q):
            return round(v[min(len(v) - 1, int(q * (len(v) - 1)))], 3) if v else None

        return {"tokens_per_sec": round(toks / dt, 1),
                "step_ms_p50": pct(step_ms, 0.5),
                "step_ms_p95": pct(step_ms, 0.95),
                "compiled_programs": sched.compiled_program_count(),
                "program_kinds": sorted({k[0] for k in sched._compiled
                                         if isinstance(k, tuple)}),
                "new_programs_mid_stream":
                    sched.compiled_program_count() - programs_before}, probe_logits

    fused_eng = build(True)
    elig = fused_eng._fused_decode_eligible()
    if not elig:
        return {"skipped": "; ".join(elig.reasons)}
    params = jax.device_get(fused_eng.params)
    out = {"config": {"model": "tiny", "num_slots": slots,
                      "requests": len(prompts), "max_new": max_new}}
    out["fused"], fused_logits = run(fused_eng)
    out["per_projection"], ref_logits = run(build(False, params=params))
    out["fused_over_per_projection_tok_s"] = round(
        out["fused"]["tokens_per_sec"]
        / out["per_projection"]["tokens_per_sec"], 3)
    n = min(len(fused_logits), len(ref_logits))
    out["logit_max_abs_err"] = round(float(np.max(np.abs(
        np.asarray(fused_logits[:n], np.float32)
        - np.asarray(ref_logits[:n], np.float32)))), 6)
    out["fused_zero_recompiles"] = (
        out["fused"]["new_programs_mid_stream"] == 0)
    out["fused_path_active"] = "fused_block" in out["fused"]["program_kinds"]
    return out


def _hier_kv_bench(make, num_slots, max_new, seed, rounds=3):
    """Hierarchical-KV leg: an LRU-thrashing revisit stream with NO
    contrived prompt families (the PR 10 replicas leg had to size family
    working sets to fleet capacity to dodge cold replicas — this leg is the
    honest version of that traffic). A working set of W distinct long
    prompts (W ≈ 2x the slot pool) is revisited cyclically with a fresh
    short suffix per visit — every revisit is a device-LRU miss by
    construction. Device-only retention recomputes every prefix; the host
    tier demotes evicted prefixes and restores them on revisit. Reports
    tok/s, TTFT p50/p95, combined tier hit rate, demote/restore counts, and
    a restore_ms-vs-cold_prefill_ms crossover table by prefix length (the
    restore-vs-recompute threshold evidence for SERVING.md)."""
    from deepspeed_tpu.memory.prefix_store import GlobalPrefixStore

    chunk = 16
    W = 2 * num_slots + 2
    rng = np.random.default_rng(seed + 31)
    out = {"working_set": W, "rounds": rounds, "prefill_chunk": chunk}
    prompts = None
    for label in ("device_only", "hier_kv"):
        eng = make(True)
        overrides = dict(num_slots=num_slots, prefill_chunk=chunk)
        if label == "hier_kv":
            overrides["prefix_store"] = GlobalPrefixStore(
                capacity_bytes=512 << 20, telemetry=eng.telemetry)
        sched = eng.scheduler(**overrides)
        if sched.radix is None:
            return {"skipped": "hier_kv leg needs the chunked radix path"}
        budget = 2 * sched.steps_per_sync
        cap = sched.max_len - max_new - budget
        n_chunks = min(5, (cap - 8) // chunk)
        if n_chunks < 2:
            return {"skipped": f"slot capacity {sched.max_len} too small for a "
                               f"multi-chunk prefix at max_new={max_new}"}
        pre_len = n_chunks * chunk
        if prompts is None:
            bases = [rng.integers(0, eng.model_config.vocab_size, pre_len)
                     .astype(np.int32) for _ in range(W)]
            # cyclic revisits, fresh 2-6 token suffix per visit: prefix KV is
            # the only reusable part, exactly the follow-up-turn shape
            prompts = [np.concatenate([bases[i % W],
                                       rng.integers(0, eng.model_config.vocab_size,
                                                    int(rng.integers(2, 7)))
                                       .astype(np.int32)])
                       for i in range(W * rounds)]
            out["prefix_tokens"] = int(pre_len)
        # warm every program the stream touches: cold + repeat (copy program)
        # + an eviction/restore cycle on the hier leg (slice/restore programs)
        warm = np.concatenate([np.full(pre_len, 3, np.int32), [7, 8, 9]])
        sched.submit(warm, max_new_tokens=budget + 2).result()
        sched.submit(warm, max_new_tokens=budget + 2).result()
        if label == "hier_kv":
            for k in range(num_slots + 1):
                sched.submit(np.full(pre_len + k + 1, 11 + k, np.int32),
                             max_new_tokens=2).result()
            sched.submit(warm, max_new_tokens=2).result()  # restore warms
        sched.radix.hits = sched.radix.misses = sched.radix.evictions = 0
        if sched.kv_tier is not None:
            sched.kv_tier.restores = sched.kv_tier.demotes = 0
            sched.kv_tier.restored_tokens = 0
        n_programs = sched.compiled_program_count()
        t0 = time.perf_counter()
        handles = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
        toks = sum(len(h.result()) for h in handles)
        dt = time.perf_counter() - t0
        ttfts = sorted((h._req.first_token_ts - h._req.submit_ts) * 1e3
                       for h in handles if h._req.first_token_ts is not None)
        hits, misses = sched.radix.hits, sched.radix.misses
        restores = sched.kv_tier.restores if sched.kv_tier is not None else 0
        entry = {
            "tokens_per_sec": round(toks / dt, 1),
            "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 2) if ttfts else None,
            "ttft_ms_p95": round(float(np.percentile(ttfts, 95)), 2) if ttfts else None,
            "device_hit_rate": round(hits / max(1, hits + misses), 3),
            "tier_hit_rate": round((hits + restores)
                                   / max(1, hits + misses + restores), 3),
            "evictions": sched.radix.evictions,
            "compiled_programs_after_stream": sched.compiled_program_count(),
            "new_programs_in_stream": sched.compiled_program_count() - n_programs,
        }
        if sched.kv_tier is not None:
            entry.update({"demotes": sched.kv_tier.demotes, "restores": restores,
                          "restored_tokens": sched.kv_tier.restored_tokens,
                          "host_tier": sched.kv_tier.store.stats()})
            # restore-vs-recompute crossover: TTFT of a restored admission vs
            # a cold prefill of the same prefix length (per prefix length)
            crossover = {}
            for nc in sorted({2, max(2, n_chunks // 2), n_chunks}):
                plen = nc * chunk
                base = rng.integers(0, eng.model_config.vocab_size, plen).astype(np.int32)
                cold_ms, restore_ms = [], []
                for rep in range(3):
                    p = np.concatenate([base, [int(rep) + 1, 77]])
                    h = sched.submit(p, max_new_tokens=2)  # cold (new prefix rep 0)
                    h.result()
                    if rep == 0:
                        continue  # rep 0 built the registration; skip timing
                    # evict base's slot so the next submit restores
                    for k in range(num_slots + 1):
                        sched.submit(np.full(plen + k + 1, 200 + rep + k, np.int32),
                                     max_new_tokens=2).result()
                    r0 = sched.kv_tier.restores
                    h = sched.submit(np.concatenate([base, [int(rep) + 50, 78]]),
                                     max_new_tokens=2)
                    h.result()
                    (restore_ms if sched.kv_tier.restores > r0 else cold_ms).append(
                        (h._req.first_token_ts - h._req.submit_ts) * 1e3)
                    q = np.concatenate([rng.integers(0, eng.model_config.vocab_size,
                                                     plen).astype(np.int32), [9, 9]])
                    h = sched.submit(q, max_new_tokens=2)  # genuinely cold prefill
                    h.result()
                    cold_ms.append((h._req.first_token_ts - h._req.submit_ts) * 1e3)
                crossover[f"prefix{plen}"] = {
                    "cold_prefill_ms": round(float(np.median(cold_ms)), 2) if cold_ms else None,
                    "restore_ms": round(float(np.median(restore_ms)), 2) if restore_ms else None,
                }
            entry["crossover"] = crossover
        out[label] = entry
    lo, hi = out.get("device_only", {}), out.get("hier_kv", {})
    if lo.get("tokens_per_sec") and hi.get("tokens_per_sec"):
        out["speedup"] = round(hi["tokens_per_sec"] / lo["tokens_per_sec"], 3)
        if lo.get("ttft_ms_p95") and hi.get("ttft_ms_p95"):
            out["ttft_p95_speedup"] = round(lo["ttft_ms_p95"] / hi["ttft_ms_p95"], 3)
    return out


def _disagg_bench(make, num_slots, max_new, seed, prefill_reqs=4):
    """Disaggregated prefill/decode leg: a mixed long-prefill/short-decode
    open-loop stream served by a 2-replica MIXED fleet vs a 1-prefill +
    1-decode fleet, at a base prefill load and at DOUBLE that load.

    The acceptance signal: decode ITL p95 on the disaggregated fleet stays
    flat (<= ~1.1x) when the offered prefill load doubles, while the mixed
    fleet's decode rows eat the extra chunk syncs. ITL is measured as the
    per-delivered-token duration of each replica's own scheduler syncs,
    restricted to the replicas hosting decode rows (the disagg fleet's
    decode replica never runs a prefill chunk) — the pod-side ITL each
    replica would expose, free of the serial-CPU pump-interleave artifact
    (a single host steps the replicas in turn; on a pod each steps its own
    chip group). TTFT is real wall clock. Also reports the migration_ms
    histogram (handoff-start -> decode-resume) and a migrate-vs-colocate
    threshold sweep (migrate_min_tokens 0 / mid / colocate-everything)."""
    chunk = 32  # wide chunks: a fused chunk sync costs visibly more than a
    # pure decode sync even on the tiny CPU model, so the mixed fleet's
    # interference share is measurable, not noise

    def streams(n_prefill, long_dec):
        # decode-heavy: max_new-token budgets (the ITL population) on
        # alternating short/multi-chunk prompts (so the threshold sweep
        # splits a real population; ``long_dec`` adapts to what the slot
        # capacity leaves beside the decode budget); prefill-heavy:
        # 3-chunk prompts whose budget equals ONE sync (they finish inside
        # their final fused sync and never migrate — pure interference).
        # The rng is FRESH per call and seeded only by the cell's load, so
        # every fleet/repeat/sweep cell at one load serves the IDENTICAL
        # request population — the ratios compare fleets, not lengths draws
        rng = np.random.default_rng(seed + 47 + n_prefill)
        dec = [rng.integers(0, 1000,
                            int(rng.integers(6, 14)) if i % 2 == 0
                            else long_dec + int(rng.integers(0, 8)))
               .astype(np.int32) for i in range(6)]
        pre = [rng.integers(0, 1000, 3 * chunk + int(rng.integers(0, 16)))
               .astype(np.int32) for _ in range(n_prefill)]
        return dec, pre

    def run(roles, n_prefill, migrate_min=0, telemetry=None):
        eng = make(True, telemetry=telemetry,
                   cfg_extra={"continuous_batching": {
                       "disaggregation": {"enabled": True,
                                          "roles": roles or []}}}
                   if roles is not None else None)
        from deepspeed_tpu.serving import ReplicaSet
        rs = ReplicaSet.build(eng, 2, num_slots=num_slots, prefill_chunk=chunk)
        if rs.primary.radix is None:
            return None
        rs.migrate_min_tokens = migrate_min
        budget = 2 * rs.primary.steps_per_sync
        # long-decode prompts take whatever capacity the decode budget
        # leaves, at least one chunk (2 chunks when the slot allows)
        long_dec = min(2 * chunk, rs.primary.max_len - max_new - budget - 8)
        if (rs.primary.max_len < 3 * chunk + 16 + budget or long_dec < chunk):
            return None
        # warm every program the stream touches (cold, repeat/copy; the
        # tier programs warmed at role install)
        warm = np.concatenate([np.full(3 * chunk, 3, np.int32), [7, 8, 9]])
        for _ in range(2):
            _, h = rs.dispatch(warm, max_new_tokens=budget + 2)
            rs.drain_all_work()
            h.result()
        dec, pre = streams(n_prefill, long_dec)
        mig0 = sum(r.scheduler.migrations_out for r in rs)  # warm handoffs
        handles = []
        step_samples = {rep.idx: [] for rep in rs}  # (dt, delivered)
        t0 = time.perf_counter()
        for i, p in enumerate(dec + pre):
            is_dec = i < len(dec)
            while True:
                _, h = rs.dispatch(
                    p, seed=i,
                    max_new_tokens=(max_new if is_dec
                                    else rs.primary.steps_per_sync))
                if h is not None:
                    break
                _pump_timed(rs, step_samples)
            handles.append((is_dec, h))
        while any(not h.done for _, h in handles) or rs.pending_migrations():
            if not _pump_timed(rs, step_samples):
                for rep in rs:
                    if rep.scheduler.kv_tier is not None:
                        rep.scheduler.kv_tier.executor.drain_fetches()
        dt = time.perf_counter() - t0
        toks = sum(len(h.result()) for _, h in handles)
        ttfts = sorted((h._req.first_token_ts - h._req.submit_ts) * 1e3
                       for _, h in handles if h._req.first_token_ts is not None)
        # ITL population: decode-hosting replicas' sync times, normalized
        # per TOKEN PER ROW (each live row advances up to steps_per_sync
        # tokens per sync, so a row's user-visible ITL is sync_time / K —
        # normalizing by TOTAL delivered tokens would reward batching
        # density and punish a lightly-batched decode replica for an
        # artifact, not interference). Falls back to the whole fleet when
        # the decode side saw no work (the colocate-everything sweep point
        # decodes on the prefill replica, and null ITL there would hide
        # exactly the interference the sweep exists to show).
        K = rs.primary.steps_per_sync

        def samples(idxs):
            return sorted(s[0] * 1e3 / min(K, s[1])
                          for idx in idxs for s in step_samples[idx]
                          if s[1] > 0)

        dec_reps = ([rep.idx for rep in rs if rep.phase_role != "prefill"]
                    if rs.disaggregated() else [rep.idx for rep in rs])
        itl = samples(dec_reps) or samples(list(step_samples))
        entry = {
            "tokens_per_sec": round(toks / dt, 1),
            "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 2) if ttfts else None,
            "ttft_ms_p95": round(float(np.percentile(ttfts, 95)), 2) if ttfts else None,
            "decode_itl_ms_mean": round(float(np.mean(itl)), 3) if itl else None,
            "decode_itl_ms_p50": round(float(np.percentile(itl, 50)), 3) if itl else None,
            "decode_itl_ms_p95": round(float(np.percentile(itl, 95)), 3) if itl else None,
            "migrations": sum(r.scheduler.migrations_out for r in rs) - mig0,
            "migrations_failed": rs.migrations_failed,
            "compiled_programs": rs.compiled_program_count(),
        }
        if telemetry:
            snap = eng.telemetry.snapshot()
            hist = snap.get("histograms", {}).get("serving/migration_ms")
            if hist:
                entry["migration_ms"] = {k: round(v, 2) for k, v in hist.items()
                                         if k in ("p50", "p90", "p99", "count",
                                                  "mean")}
            eng.telemetry.close()
            from deepspeed_tpu.telemetry import set_sink
            set_sink(None)
        return entry

    def _pump_timed(rs, samples):
        progressed = False
        for rep in rs:
            if rs.admit_migrations(rep):
                progressed = True
            if not rep.idle() and not rep.sick:
                s0 = time.perf_counter()
                d = rep.step()
                samples[rep.idx].append((time.perf_counter() - s0, d))
                progressed = True
        return progressed

    def _best(a, b):
        """Noise-floor merge of two runs of one cell (the box is shared:
        min for latency metrics, max for throughput — the same anti-noise
        rule the offload bench's min-step-time uses); counts/hists come
        from the first run that has them."""
        out = dict(a)
        for k, v in b.items():
            if v is None or not isinstance(v, (int, float)) or k not in a \
                    or a[k] is None:
                out[k] = out.get(k) if out.get(k) is not None else v
            elif "_ms" in k:
                out[k] = min(a[k], v)
            elif k == "tokens_per_sec":
                out[k] = max(a[k], v)
        return out

    import tempfile
    out = {"prefill_chunk": chunk, "prefill_reqs": [prefill_reqs, 2 * prefill_reqs]}
    tel_dir = tempfile.mkdtemp()
    for label, roles in (("mixed", None), ("disagg", ["prefill", "decode"])):
        for load, n_pre in (("load1", prefill_reqs), ("load2", 2 * prefill_reqs)):
            tel = ({"enabled": True, "output_path": tel_dir}
                   if (label, load) == ("disagg", "load2") else None)
            entry = run(roles, n_pre, telemetry=tel)
            if entry is None:
                return {"skipped": "disagg leg needs the chunked radix path and "
                                   "slot room for multi-chunk prompts"}
            entry = _best(entry, run(roles, n_pre))  # 2 quiet-run repeats
            out[f"{label}_{load}"] = entry
    for label in ("mixed", "disagg"):
        for stat in ("p95", "mean"):
            lo = out[f"{label}_load1"].get(f"decode_itl_ms_{stat}")
            hi = out[f"{label}_load2"].get(f"decode_itl_ms_{stat}")
            if lo and hi:
                out[f"itl_{stat}_degradation_{label}"] = round(hi / lo, 3)
    dd = out.get("itl_p95_degradation_disagg")
    out["itl_flat_under_prefill_load"] = bool(dd is not None and dd <= 1.1)
    # the stable cross-fleet signal on a serial shared box: the decode
    # side's ABSOLUTE ITL advantage (>1 = the disaggregated decode pool's
    # syncs are cheaper than the mixed fleet's chunk-carrying ones; the
    # degradation ratios above show the load-scaling side of it)
    for load in ("load1", "load2"):
        m = out[f"mixed_{load}"].get("decode_itl_ms_p95")
        d = out[f"disagg_{load}"].get("decode_itl_ms_p95")
        if m and d:
            out[f"itl_p95_mixed_over_disagg_{load}"] = round(m / d, 3)
    # migrate-vs-colocate: the same disagg fleet at rising migrate_min_tokens
    # (inf = every prompt colocates on the prefill replica — the handoff
    # disabled, roles still steering placement)
    sweep = {}
    for thr_label, thr in (("migrate_all", 0), ("threshold_mid", chunk),
                           ("colocate_all", 1 << 30)):
        entry = run(["prefill", "decode"], prefill_reqs, migrate_min=thr)
        if entry is not None:
            sweep[thr_label] = {k: entry[k] for k in
                                ("tokens_per_sec", "decode_itl_ms_p95",
                                 "decode_itl_ms_mean", "ttft_ms_p95",
                                 "migrations")}
    out["migrate_vs_colocate"] = sweep
    return out


def _kv_int8_bench(make, num_slots, max_new, seed):
    """int8 paged-KV leg: resident-slot density at equal HBM budget (the
    acceptance bar is >= 1.9x a bf16 pool of the same geometry) plus the
    decode logit error the quantized tier costs, measured against the bf16
    pool on the same greedy request."""
    rng = np.random.default_rng(seed + 21)
    eng_b = make(True)
    sb = eng_b.scheduler(num_slots=num_slots, kv_cache_dtype="bf16",
                         collect_logits=True)
    V = eng_b.model_config.vocab_size
    cap = sb.max_len - max_new - 2 * sb.steps_per_sync
    prompt = rng.integers(0, V, max(8, min(64, cap))).astype(np.int32)
    ref = sb.submit(prompt, max_new_tokens=max_new).result_logits()
    bpt_b = sb.cache.bytes_per_token()

    eng_q = make(True)
    sq = eng_q.scheduler(num_slots=num_slots, kv_cache_dtype="int8",
                         collect_logits=True)
    got = sq.submit(prompt, max_new_tokens=max_new).result_logits()
    bpt_q = sq.cache.bytes_per_token()
    budget = sb.cache.capacity_bytes()
    n = min(len(ref), len(got))
    return {
        "bytes_per_token_bf16": bpt_b,
        "bytes_per_token_int8": bpt_q,
        "slots_at_equal_hbm_bf16": int(num_slots),
        "slots_at_equal_hbm_int8": int(budget // max(1, bpt_q * sq.cache.max_len)),
        "slot_ratio_at_equal_hbm": round(bpt_b / max(1, bpt_q), 3),
        "max_abs_logit_err": round(float(np.abs(got[:n] - ref[:n]).max()), 5) if n else None,
        "ref_logit_absmax": round(float(np.abs(ref).max()), 4) if n else None,
        "top1_agreement": round(float(
            (got[:n].argmax(-1) == ref[:n].argmax(-1)).mean()), 4) if n else None,
    }


def _shared_prefix_bench(make, num_slots, n_requests, max_new, seed,
                         prefill_chunk=None):
    """Shared-system-prompt workload (the agent/chat serving shape
    RadixAttention targets): every request = one common system prefix + a
    short unique suffix. Served twice — chunked prefill + radix prefix cache
    (the default) vs the monolithic-prefill/no-cache baseline — reporting
    prefix-cache hit rate, TTFT, aggregate tokens/sec, and the p95 step
    stall co-resident decode rows eat while admissions prefill (the
    Sarathi-Serve interference number)."""
    out = {}
    prompts = None
    # chunk size is THE Sarathi tradeoff knob — deployments tune it to the
    # workload (here: the un-shared suffix length, since the radix cache
    # absorbs the shared prefix); None = scheduler default
    chunked_cfg = {} if prefill_chunk is None else {"prefill_chunk": prefill_chunk}
    for label, overrides in (("chunked", chunked_cfg),
                             ("monolithic", {"prefill_chunk": 0,
                                             "prefix_cache": False})):
        eng = make(True)
        sched = eng.scheduler(num_slots=num_slots, **overrides)
        if label == "chunked" and sched.prefill_chunk == 0:
            # chunking disabled outright: a "chunked vs monolithic" leg
            # would compare two identical monolithic runs — skip honestly
            return {"skipped": "prefill_chunk=0 disables the chunked leg"}
        if prompts is None:  # both legs serve the SAME request stream
            rng = np.random.default_rng(seed + 7)
            V = eng.model_config.vocab_size
            budget = 2 * sched.steps_per_sync
            cap = sched.max_len - max_new - budget  # prompt rows a slot always fits
            # the shared prefix must span >= one chunk AND leave >= 5 rows
            # of unique suffix: radix matches round DOWN to chunk boundaries
            # (hit/cold bit-identity), so a sub-chunk system prompt could
            # never produce a hit — skip rather than report a meaningless 0
            if cap - 5 < sched.prefill_chunk:
                return {"skipped": f"slot capacity {sched.max_len} too small for a "
                                   f"{sched.prefill_chunk}-token shared prefix with "
                                   f"max_new={max_new}"}
            sys_len = min(max(sched.prefill_chunk,
                              min(2 * sched.prefill_chunk, cap // 2)),
                          cap - 5)
            system = rng.integers(0, V, sys_len).astype(np.int32)
            sfx_cap = min(48, cap - sys_len)
            prompts = [np.concatenate([system, rng.integers(0, V, int(n)).astype(np.int32)])
                       for n in rng.integers(4, sfx_cap, n_requests)]
        # warm every program the stream hits (both fused-sync step-count
        # variants + the K-step decode + copy on the chunked path, one
        # prefill per pow2 bucket on the monolithic one); the warm budget
        # must outlive the admission iteration so a decode-only K-step
        # sync runs too (prompt sizing reserved max_new+budget rows)
        if sched.prefill_chunk:
            sched.submit(prompts[0], max_new_tokens=budget + 2).result()
            sched.submit(prompts[0], max_new_tokens=budget + 2).result()  # copy program
            sched.radix.hits = sched.radix.misses = sched.radix.evictions = 0
        else:
            from deepspeed_tpu.inference.scheduler import _bucket_len
            for wb in sorted({_bucket_len(len(p), sched.prefill_bucket, sched.max_len)
                              for p in prompts}):
                warm = np.ones(min(wb, sched.max_len - max_new - budget), np.int32)
                sched.submit(warm, max_new_tokens=2).result()
        t0 = time.perf_counter()
        handles = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
        stall_ms = []  # durations of steps that carried admission/prefill work
        while any(not h.done for h in handles):
            pf0, q0 = sched._prefill is not None, len(sched.queue)
            t1 = time.perf_counter()
            sched.step()
            dt = (time.perf_counter() - t1) * 1e3
            if pf0 or sched._prefill is not None or len(sched.queue) < q0:
                stall_ms.append(dt)
        dt_total = time.perf_counter() - t0
        toks = sum(len(h.result()) for h in handles)
        ttfts = sorted((h._req.first_token_ts - h._req.submit_ts) * 1e3
                       for h in handles if h._req.first_token_ts is not None)
        entry = {
            "tokens_per_sec": round(toks / dt_total, 1),
            "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 2) if ttfts else None,
            "ttft_ms_p95": round(float(np.percentile(ttfts, 95)), 2) if ttfts else None,
            "decode_step_ms_p95_during_prefill":
                round(float(np.percentile(stall_ms, 95)), 2) if stall_ms else None,
        }
        if sched.prefill_chunk:
            entry["prefix_cache_hit_rate"] = round(sched.radix.hit_rate(), 3)
            entry["prefix_cache_evictions"] = sched.radix.evictions
        out[label] = entry
    ch, mono = out["chunked"], out["monolithic"]
    if ch["decode_step_ms_p95_during_prefill"] and mono["decode_step_ms_p95_during_prefill"]:
        out["prefill_stall_p95_speedup"] = round(
            mono["decode_step_ms_p95_during_prefill"]
            / ch["decode_step_ms_p95_during_prefill"], 3)
    return out


def _gateway_bench(model_name="gpt2-large", dtype="int8", num_slots=8,
                   n_requests=32, max_new=64, kernel_inject=True, seed=0):
    """Serving-gateway benchmark: the same engine serving over localhost
    HTTP (SSE streaming) vs the in-process scheduler loop, then an
    open-loop client swarm at 2x the measured capacity to exercise
    admission control.

    Legs:
    - ``direct``: the request stream through ``scheduler.submit()`` in
      process (the PR 2/3 serving loop) — the no-HTTP baseline.
    - ``gateway``: the same stream as concurrent streamed HTTP requests;
      per-token SSE timestamps give TTFT and inter-token latency (ITL)
      percentiles, and ``vs`` the direct leg prices the HTTP+streaming tax.
    - ``overload_2x``: open-loop Poisson-less arrivals at 2x the measured
      request capacity with a bounded queue: reports the shed rate (429s),
      that every ACCEPTED request completed in full, and accepted-TTFT p95
      (the admission-control contract: past capacity you shed fast, you
      don't build an unbounded queue)."""
    import http.client
    import threading

    import deepspeed_tpu
    from deepspeed_tpu.comm import comm as _comm
    from deepspeed_tpu.serving import Gateway

    _comm._state["mesh"] = None
    rng = np.random.default_rng(seed)
    eng = deepspeed_tpu.init_inference(
        model_name, config={"dtype": dtype, "max_out_tokens": 512,
                            "kernel_inject": kernel_inject,
                            "continuous_batching": {"enabled": True,
                                                    "num_slots": num_slots}})
    sched = eng.scheduler()
    cap = max(8, sched.max_len - max_new - 2 * sched.steps_per_sync)
    prompts = [rng.integers(0, eng.model_config.vocab_size,
                            int(n)).astype(np.int32).tolist()
               for n in rng.integers(8, min(160, cap), n_requests)]

    # --- direct in-process baseline (also warms every compiled program) --
    sched.submit(prompts[0], max_new_tokens=max_new).result()  # compile
    t0 = time.perf_counter()
    handles = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
    direct_toks = sum(len(h.result()) for h in handles)
    direct = {"tokens_per_sec": round(direct_toks / (time.perf_counter() - t0), 1)}

    gw = Gateway(eng, port=0, max_queue_depth=max(4, n_requests // 2),
                 request_timeout_s=600)
    gw.start_background()

    def stream_one(prompt, rec):
        """One streamed completion; records (status, ttft_s, itls_s, n_tok)."""
        t_send = time.perf_counter()
        conn = http.client.HTTPConnection("127.0.0.1", gw.port, timeout=600)
        try:
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": prompt, "max_tokens": max_new,
                                     "stream": True}), {})
            resp = conn.getresponse()
            if resp.status != 200:
                rec.append((resp.status, None, [], 0))
                resp.read()
                return
            ttft, last, itls, n_tok = None, t_send, [], 0
            while True:
                line = resp.readline()
                if not line:
                    break
                if not line.startswith(b"data: ") or b"[DONE]" in line:
                    continue
                now = time.perf_counter()
                if ttft is None:
                    ttft = now - t_send
                else:
                    itls.append(now - last)
                last = now
                n_tok += 1
            rec.append((200, ttft, itls, n_tok))
        except Exception:  # noqa: BLE001 — a failed client records as an error
            rec.append(("error", None, [], 0))
        finally:
            conn.close()

    # --- gateway closed-loop: num_slots concurrent streamed clients ------
    rec = []
    t0 = time.perf_counter()
    threads = [threading.Thread(target=stream_one, args=(p, rec)) for p in prompts]
    for i in range(0, len(threads), num_slots):
        batch = threads[i:i + num_slots]
        for t in batch:
            t.start()
        for t in batch:
            t.join()
    dt = time.perf_counter() - t0
    ok = [r for r in rec if r[0] == 200]
    toks = sum(r[3] for r in ok)
    ttfts = sorted(r[1] * 1e3 for r in ok if r[1] is not None)
    itls = sorted(x * 1e3 for r in ok for x in r[2])
    gateway = {
        "tokens_per_sec": round(toks / dt, 1),
        "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 2) if ttfts else None,
        "ttft_ms_p95": round(float(np.percentile(ttfts, 95)), 2) if ttfts else None,
        "itl_ms_p50": round(float(np.percentile(itls, 50)), 2) if itls else None,
        "itl_ms_p95": round(float(np.percentile(itls, 95)), 2) if itls else None,
        "http_tax_vs_direct": round(
            (toks / dt) / direct["tokens_per_sec"], 3) if toks else None,
    }

    # --- 2x overload: open-loop arrivals at twice the measured capacity --
    capacity_rps = (toks / dt) / max_new if toks else 1.0
    offered_rps = 2.0 * capacity_rps
    n_over = min(2 * n_requests, 64)
    rec2 = []
    threads = []
    t0 = time.perf_counter()
    for i in range(n_over):
        arrival = t0 + i / offered_rps
        wait = arrival - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        t = threading.Thread(target=stream_one,
                             args=(prompts[i % len(prompts)], rec2))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    ok2 = [r for r in rec2 if r[0] == 200]
    shed = sum(1 for r in rec2 if r[0] == 429)
    ttfts2 = sorted(r[1] * 1e3 for r in ok2 if r[1] is not None)
    overload = {
        "offered_rps": round(offered_rps, 2),
        "requests": n_over,
        "accepted": len(ok2),
        "shed_429": shed,
        "shed_rate": round(shed / n_over, 3),
        "accepted_complete": all(r[3] == max_new for r in ok2),
        "ttft_ms_p95_accepted": round(float(np.percentile(ttfts2, 95)), 2)
        if ttfts2 else None,
    }
    drained = gw.close(timeout=120)
    return {"direct": direct, "gateway": gateway, "overload_2x": overload,
            "num_slots": num_slots, "max_new": max_new,
            "drained_clean": bool(drained)}


def gateway_main():
    """`python bench.py gateway`: one BENCH_GATEWAY JSON line (needs the chip;
    any failure is fatal)."""
    model = os.environ.get("BENCH_GATEWAY_MODEL", "gpt2-large")
    dtype = os.environ.get("BENCH_GATEWAY_DTYPE", "int8")
    headline = f"gateway: streamed HTTP decode tokens/sec ({model} {dtype})"
    unit = "tokens/sec"
    _require_chip()
    res = _gateway_bench(
        model_name=model,
        dtype=dtype,
        num_slots=int(os.environ.get("BENCH_GATEWAY_SLOTS", "8")),
        n_requests=int(os.environ.get("BENCH_GATEWAY_REQUESTS", "32")),
        max_new=int(os.environ.get("BENCH_GATEWAY_MAX_NEW", "64")),
        kernel_inject=os.environ.get("BENCH_GATEWAY_KERNEL_INJECT", "1") != "0")
    print(json.dumps({
        "metric": headline,
        "value": res["gateway"]["tokens_per_sec"],
        "unit": unit,
        # the HTTP+SSE tax: gateway throughput over the in-process loop
        "vs_baseline": res["gateway"]["http_tax_vs_direct"] or 0.0,
        "extra": res,
    }))


def serving_main():
    """`python bench.py serving`: one BENCH_SERVING JSON line (needs the chip;
    any failure is fatal)."""
    model = os.environ.get("BENCH_SERVING_MODEL", "gpt2-large")
    dtype = os.environ.get("BENCH_SERVING_DTYPE", "int8")
    headline = f"serving: continuous-batching aggregate decode tokens/sec ({model} {dtype})"
    unit = "tokens/sec"
    _require_chip()
    # env knobs so the bench is smoke-testable on a CPU box (tiny model)
    res = _serving_bench(
        model_name=model,
        dtype=dtype,
        n_requests=int(os.environ.get("BENCH_SERVING_REQUESTS", "32")),
        max_new=int(os.environ.get("BENCH_SERVING_MAX_NEW", "64")),
        max_prompt=int(os.environ.get("BENCH_SERVING_MAX_PROMPT", "192")),
        kernel_inject=os.environ.get("BENCH_SERVING_KERNEL_INJECT", "1") != "0",
        steps_per_sync=int(os.environ.get("BENCH_SERVING_STEPS", "4")),
        prefill_chunk=int(os.environ["BENCH_SERVING_PREFILL_CHUNK"])
        if os.environ.get("BENCH_SERVING_PREFILL_CHUNK") else None,
        arrival_rate=float(os.environ["BENCH_SERVING_RATE"])
        if os.environ.get("BENCH_SERVING_RATE") else None)
    slot_tps = [res[k]["tokens_per_sec"] for k in res
                if k.startswith("slots") and "tokens_per_sec" in res[k]]
    print(json.dumps({
        "metric": headline,
        "value": max(slot_tps) if slot_tps else 0.0,
        "unit": unit,
        "vs_baseline": res.get("speedup_vs_sequential", 0.0),
        "extra": res,
    }))


def _offload_stream_bench(model_name="tiny", steps=5, seq=64, bs=None,
                          depths=(0, 1, 2)):
    """ZeRO-Infinity streamed-step benchmark: the same model + batch trained
    at ``prefetch_depth`` 0 (unpipelined: synchronous fenced point-of-use
    puts — stricter than any pre-pipeline configuration), 1 (~the legacy
    behavior: 1-deep async look-ahead, forward only back then), and 2 (the
    default double-buffered bidirectional pipeline). Reports min-of-N step
    time per depth, the realized-overlap telemetry (``overlap_efficiency``
    = fraction of fenced transfer time the pipeline hid off the critical
    path), and a bit-identity check across all legs (the executor moves
    bytes, never math)."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.comm import comm as _comm
    from deepspeed_tpu.models import get_model

    if bs is None:  # one sample per data-parallel rank, floor 4
        bs = max(4, len(jax.devices()))
    rng = np.random.default_rng(0)
    batch = None
    host_params = None
    res = {}
    for depth in depths:
        _comm._state["mesh"] = None
        model = get_model(model_name)
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
            "train_batch_size": bs,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "gradient_clipping": 1.0,
            "zero_optimization": {
                "stage": 3,
                "offload_param": {"device": "cpu"},
                "offload_optimizer": {"prefetch_depth": depth,
                                      "fetch_window": 4 if depth else 1}},
            "steps_per_print": 10**9,
            "telemetry": _telemetry_cfg(),
        })
        if host_params is None:  # both legs start from identical masters
            host_params = engine.param_stream.get_params_tree()
            batch = {"input_ids": rng.integers(
                0, model.cfg.vocab_size,
                (engine.train_batch_size(), seq)).astype(np.int32)}
        else:
            engine.param_stream.set_params_from_tree(host_params)
        engine.train_batch(batch=batch)  # warm: compiles land here
        times, phases, losses = [], [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            losses.append(float(engine.train_batch(batch=batch)))
            times.append(time.perf_counter() - t0)
            phases.append(engine.param_stream.last_phase_times or {})
        best = int(np.argmin(times))
        res[f"depth{depth}"] = {
            "step_ms_min": round(times[best] * 1e3, 2),
            "losses": losses,  # raw: the bit-identity check must not round
            "overlap_efficiency": round(phases[best].get("overlap_efficiency", 0.0), 4),
            "put_wait_ms": round(phases[best].get("put_s", 0.0) * 1e3, 2),
            "put_dispatch_ms": round(phases[best].get("put_dispatch_s", 0.0) * 1e3, 2),
            "put_realized_ms": round(phases[best].get("put_realized_s", 0.0) * 1e3, 2),
            "fetch_wait_ms": round(phases[best].get("drain_s", 0.0) * 1e3, 2),
        }
    d0, dk = res.get("depth0"), res[f"depth{depths[-1]}"]
    if d0 is not None:
        res["losses_bit_identical"] = all(
            res[f"depth{d}"]["losses"] == d0["losses"] for d in depths)
        res["speedup_depth_vs_0"] = round(d0["step_ms_min"] / dk["step_ms_min"], 3)
    if "depth1" in res:  # vs the legacy 1-deep unfenced look-ahead
        res["speedup_vs_depth1"] = round(
            res["depth1"]["step_ms_min"] / dk["step_ms_min"], 3)
    res["model"] = model_name
    res["seq"] = seq
    return res


def offload_stream_main():
    """`python bench.py offload_stream`: one BENCH_OFFLOAD_STREAM JSON line
    — streamed-train step time at prefetch_depth 0 vs 2 + realized-overlap
    telemetry (needs the chip; any failure is fatal)."""
    model = os.environ.get("BENCH_OFFLOAD_MODEL", "tiny")
    headline = (f"offload_stream: ZeRO-Infinity streamed train step "
                 f"({model}, prefetch_depth 2 vs 0)")
    unit = "ms/step"
    _require_chip()
    res = _offload_stream_bench(
        model_name=model,
        steps=int(os.environ.get("BENCH_OFFLOAD_STEPS", "5")),
        seq=int(os.environ.get("BENCH_OFFLOAD_SEQ", "64")),
        bs=int(os.environ["BENCH_OFFLOAD_BS"])
        if os.environ.get("BENCH_OFFLOAD_BS") else None)
    print(json.dumps({
        "metric": headline,
        "value": res["depth2"]["step_ms_min"],
        "unit": unit,
        # >1.0 means the pipeline beat the unpipelined step
        "vs_baseline": res.get("speedup_depth_vs_0", 0.0),
        "extra": res,
    }))


def _rlhf_bench(model_name="tiny", n_prompts=16, prompt_len=96, max_new=32,
                cycles=2, num_slots=8, seed=0):
    """RLHF hybrid-engine benchmark: in-memory weight publication vs the
    checkpoint round-trip it replaces, and rollout throughput through the
    continuous-batching scheduler vs the legacy stub's raw static-batch
    ``generate()``. Every leg is fault-isolated via ``_guard_leg``."""
    import tempfile as _tf

    import jax
    import jax.numpy as jnp
    import flax.serialization

    import deepspeed_tpu
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.models import get_model

    comm._state["mesh"] = None
    model = get_model(model_name, dtype=jnp.float32, max_seq_len=256)
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "steps_per_print": 100000,
           "telemetry": _telemetry_cfg(),
           "hybrid_engine": {"enabled": True, "max_out_tokens": 256,
                             "rollout": {"num_slots": num_slots}}}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg, rng_seed=0)
    rng = np.random.default_rng(seed)
    # RLHF prompt sets share a long task template with mixed-length user
    # tails — the radix cache's case (template > prefill_chunk so matches
    # survive the chunk-multiple rounding)
    template = list(rng.integers(1, 200, max(prompt_len - 16, 1)))
    prompts = [template + list(rng.integers(1, 200, 1 + int(rng.integers(0, 16))))
               for _ in range(n_prompts)]
    batch = {"input_ids": rng.integers(0, 256, (8, 64)).astype(np.int32)}

    results = {"model": model_name, "n_prompts": n_prompts,
               "prompt_len": prompt_len, "max_new_tokens": max_new,
               "num_slots": num_slots, "cycles": cycles}

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0

    def run_publish():
        # warm cycle compiles the cast + step programs; then measure the
        # steady-state cycle the RLHF loop actually pays every step
        engine.rlhf_step(prompts, max_new_tokens=max_new)
        sched = engine.rollout_scheduler()
        n_programs_warm = sched.compiled_program_count()
        per_cycle = []
        for _ in range(cycles):
            engine.train_batch(batch=batch)
            _, dt = timed(engine.publish_weights)
            per_cycle.append(dt * 1e3)
        return {"publish_ms_min": round(min(per_cycle), 3),
                "publish_ms": [round(x, 3) for x in per_cycle],
                "weights_version": sched.weights_version,
                "new_scheduler_programs_after_warm":
                    sched.compiled_program_count() - n_programs_warm}

    def run_checkpoint_roundtrip():
        # the legacy handoff this subsystem deletes: serialize the full
        # tree, hit disk, read it back, install + materialize on device
        pub_params = engine._infer.params
        per_cycle = []
        for _ in range(cycles):
            t0 = time.perf_counter()
            host = jax.device_get(pub_params)
            blob = flax.serialization.to_bytes(host)
            with _tf.NamedTemporaryFile(delete=False) as f:
                f.write(blob)
                path = f.name
            with open(path, "rb") as f:
                blob2 = f.read()
            restored = flax.serialization.from_bytes(host, blob2)
            placed = jax.device_put(restored)
            jax.block_until_ready(placed)  # whole tree: async backends land leaves independently
            per_cycle.append((time.perf_counter() - t0) * 1e3)
            os.unlink(path)
        return {"roundtrip_ms_min": round(min(per_cycle), 3),
                "roundtrip_ms": [round(x, 3) for x in per_cycle],
                "bytes": len(blob)}

    def run_rollout_throughput():
        # scheduler-served rollouts (chunked prefill + radix hits on the
        # shared template) vs the seed-era stub's raw static generate
        engine.publish_weights()
        engine.collect_rollouts(prompts, max_new_tokens=max_new)  # warm
        buf, dt = timed(lambda: engine.collect_rollouts(prompts,
                                                        max_new_tokens=max_new))
        sched_tok_s = buf.total_tokens() / dt
        engine._infer.generate(prompts, max_new_tokens=max_new)  # warm
        out, dt_raw = timed(lambda: engine._infer.generate(prompts,
                                                           max_new_tokens=max_new))
        raw_tok_s = sum(len(r) for r in out) / dt_raw
        sched = engine.rollout_scheduler()
        return {"scheduler_tok_s": round(sched_tok_s, 1),
                "legacy_generate_tok_s": round(raw_tok_s, 1),
                "speedup_vs_legacy": round(sched_tok_s / max(raw_tok_s, 1e-9), 3),
                "prefix_cache_hit_rate": round(sched.radix.hit_rate(), 3)
                if sched.radix is not None else 0.0}

    _guard_leg(results, "publish", run_publish)
    _guard_leg(results, "checkpoint_roundtrip", run_checkpoint_roundtrip)
    _guard_leg(results, "rollout", run_rollout_throughput)
    pub = results.get("publish", {})
    rt = results.get("checkpoint_roundtrip", {})
    if "publish_ms_min" in pub and "roundtrip_ms_min" in rt:
        results["roundtrip_over_publish"] = round(
            rt["roundtrip_ms_min"] / max(pub["publish_ms_min"], 1e-9), 2)
    return results


def rlhf_main():
    """`python bench.py rlhf`: one BENCH_RLHF JSON line — in-memory weight
    publication vs checkpoint round-trip wall time, and scheduler-served
    rollout tok/s vs the legacy raw generate (needs the chip; any failure
    is fatal)."""
    model = os.environ.get("BENCH_RLHF_MODEL", "tiny")
    headline = f"rlhf: in-memory publish vs checkpoint round-trip ({model})"
    unit = "ms/publish"
    _require_chip()
    res = _rlhf_bench(
        model_name=model,
        n_prompts=int(os.environ.get("BENCH_RLHF_PROMPTS", "16")),
        prompt_len=int(os.environ.get("BENCH_RLHF_PROMPT_LEN", "96")),
        max_new=int(os.environ.get("BENCH_RLHF_MAX_NEW", "32")),
        cycles=int(os.environ.get("BENCH_RLHF_CYCLES", "2")),
        num_slots=int(os.environ.get("BENCH_RLHF_SLOTS", "8")))
    value = res.get("publish", {}).get("publish_ms_min", 0.0)
    print(json.dumps({
        "metric": headline,
        "value": value,
        "unit": unit,
        # >1.0 means the in-memory swap beat the checkpoint round-trip
        "vs_baseline": res.get("roundtrip_over_publish", 0.0),
        "extra": res,
    }))


def main():
    devices = _require_chip()
    from deepspeed_tpu.accelerator import get_accelerator

    n_chips = len(devices)
    peak = get_accelerator().peak_flops()
    seq = 1024
    extra = {}

    # a failing leg ends the run
    cfg_l, tok_l, step_l, loss_l, bs_l = _run("gpt2-large", micro_bs=4, steps=40,
                                              seq=seq)
    mfu_l = _mfu(cfg_l, tok_l / n_chips, seq, peak)
    extra.update({
        "gpt2_large_tokens_per_sec_chip": round(tok_l / n_chips, 1),
        "gpt2_large_ms_per_step": round(step_l * 1000, 1),
        "gpt2_large_final_loss": round(loss_l, 4),
    })

    cfg_s, tok_s, step_s, _, _ = _run("gpt2-125m", micro_bs=16, steps=60, seq=seq)
    extra.update({
        "gpt2_125m_tokens_per_sec_chip": round(tok_s / n_chips, 1),
        "gpt2_125m_mfu": round(_mfu(cfg_s, tok_s / n_chips, seq, peak), 4),
        "gpt2_125m_ms_per_step": round(step_s * 1000, 1),
    })

    decode = _decode_bench()
    extra.update({
        "gpt2_large_decode_tokens_per_sec": round(decode["decode_tokens_per_sec_steady"], 1),
        "gpt2_large_decode_tokens_per_sec_e2e": round(decode["decode_tokens_per_sec_e2e"], 1),
        "gpt2_large_decode_e2e_over_steady": round(decode["decode_e2e_over_steady"], 3),
        "gpt2_large_decode_tokens_per_sec_pipelined": round(
            decode["decode_tokens_per_sec_pipelined"], 1),
        "gpt2_large_ms_per_decode_step": round(decode["decode_ms_per_token_step"], 2),
        "gpt2_large_decode_hbm_utilization": round(decode["decode_hbm_utilization"], 3),
        "gpt2_large_decode_hbm_utilization_actual": round(
            decode["decode_hbm_utilization_actual"], 3),
        "gpt2_large_decode_dtype": decode["decode_dtype"],
    })

    # small-MoE single-chip training number (expert-parallel math exercised
    # at ep=1: batched expert dispatch/combine + gating aux loss)
    _, tok_moe, step_moe, _, _ = _run("gpt2-125m", micro_bs=4, steps=12, seq=512,
                                      num_experts=4, moe_top_k=2)

    dev = devices[0]
    extra.update({
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": n_chips},
        "peak_tflops": round(peak / 1e12, 1),
        "n_chips": n_chips,
        "moe_gpt2s_4e_top2_tokens_per_sec_chip": round(tok_moe / n_chips, 1),
        "moe_gpt2s_4e_top2_ms_per_step": round(step_moe * 1000, 1),
    })

    print(json.dumps({
        "metric": f"gpt2-large(774M) train MFU (bf16, seq{seq}, bs{bs_l}, fp32 Adam on-chip)",
        "value": round(mfu_l * 100, 2),
        "unit": "% MFU",
        "vs_baseline": round(mfu_l / 0.40, 4),
        "extra": extra,
    }))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "serving":
        serving_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "gateway":
        gateway_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "offload_stream":
        offload_stream_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "rlhf":
        rlhf_main()
    else:
        main()
