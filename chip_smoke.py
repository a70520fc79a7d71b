#!/usr/bin/env python
"""Quickest proof that deepspeed_tpu still starts on the chip.

    python chip_smoke.py              # one chip: device, kernels, train, serve
    python chip_smoke.py --multichip  # four chips: ZeRO-3 data=4 vs one device

Drives the two main paths once, through the entry points a user calls, at
gpt2-large's full width and depth with seeded random weights, and checks
what comes out. One JSON line per phase; the last line of standard output
is ``{"ok": true, "device": {...}}`` with the device as JAX reports it.
Any phase that raises, times out or compares wrong ends the script with a
non-zero code and no ``"ok": true`` line; with no TPU it ends at once.
This is a smoke, not a benchmark: the times it prints are information.

One process per chip. This parent never imports ``jax`` or
``deepspeed_tpu``. The phases that need the chip run in ONE child
(``device`` -> ``kernels`` -> ``train``) that has exited before the ``serve``
phase starts the server — the README quick-start command, a second child —
which this parent then talks to over HTTP with the standard library. The
device block of the last line is the child's ``device`` line. With
``--multichip`` a single child drives all four chips and nothing else runs.
"""

import argparse
import gc
import http.client
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL = "gpt2-large"
SEED = 0

# Kernel outputs are bf16 computed from bf16/int8 inputs with f32
# accumulation on both sides, so kernel and XLA differ by the output's
# rounding (2^-9 relative) plus summation order. The error is taken
# relative to the reference's largest magnitude; 1e-2 is five bf16 ulps
# there. The fused out+MLP kernel chains three matmuls through bf16
# intermediates (and accumulates its up-projection k-blocks in bf16), and
# the attention backward multiplies bf16 probabilities, so they get 3e-2.
# A wrong mask, scale, head mapping or block walk is O(1).
TOL = 1e-2
TOL_CHAINED = 3e-2


_T0 = time.perf_counter()


def emit(phase, **fields):
    """One JSON line per phase; ``at_s`` is the process's age when it ended."""
    print(json.dumps({"phase": phase, **fields,
                      "at_s": round(time.perf_counter() - _T0, 1)}), flush=True)


# =========================================================================
# phases that run in the child that holds the chip
# =========================================================================
def phase_device(min_devices=1):
    import importlib.metadata as md

    import jax

    from deepspeed_tpu.utils import compile_cache

    cache = compile_cache.configure()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform={dev.platform!r}); "
                 f"this script proves the chip path and does not run without one")
    if len(jax.devices()) < min_devices:
        sys.exit(f"chip_smoke: needs {min_devices} chips, JAX sees {len(jax.devices())}")
    stats = dev.memory_stats()
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()), jax=jax.__version__,
         jaxlib=md.version("jaxlib"), libtpu=md.version("libtpu"),
         compile_cache_dir=cache,
         memory_stats_keys=sorted(stats) if stats else None)


def _rel_err(got, ref):
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))


def _ref_span_attention(q, k, v, start, base):
    """Plain XLA: column j of row i attends keys [start_i, base_i + j]."""
    import jax
    import jax.numpy as jnp
    B, H, T, D = q.shape
    nkv, S = k.shape[1], k.shape[2]
    qg = q.astype(jnp.float32).reshape(B, nkv, H // nkv, T, D)
    s = jnp.einsum("bngtd,bnsd->bngts", qg, k.astype(jnp.float32),
                   precision="highest") / math.sqrt(D)
    pos = jnp.arange(S)[None, None, :]
    end = base[:, None, None] + jnp.arange(T)[None, :, None]
    mask = (pos >= start[:, None, None]) & (pos <= end)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bngts,bnsd->bngtd", p, v.astype(jnp.float32),
                      precision="highest").reshape(B, H, T, D)


def _ref_qmm(x, w, scales):
    """Plain XLA: x @ dequantize(w) with per-group scales along K."""
    import jax.numpy as jnp
    G = scales.shape[0]
    wf = (w.astype(jnp.float32).reshape(G, -1, w.shape[1])
          * scales[:, None, :]).reshape(w.shape)
    return jnp.matmul(x.astype(jnp.float32), wf, precision="highest")


def _ref_layernorm(x32, scale, bias, eps):
    import jax
    import jax.numpy as jnp
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def phase_kernels(model=MODEL, slots=8, chunk=64, pool_len=1024, train_batch=4,
                  seq=1024):
    """Every main-path Pallas kernel, COMPILED, against plain XLA on the
    same inputs, at the model's widths and the serving/training defaults."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import get_model
    from deepspeed_tpu.models.transformer import kv_packs
    from deepspeed_tpu.ops.pallas import decode_attention as da
    from deepspeed_tpu.ops.pallas import decode_block as db
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.quant_matmul import quant_matmul
    from deepspeed_tpu.ops.quantizer import quantize_kv_rows

    cfg = get_model(model).cfg
    H, nh, nkv, hd, F = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                         cfg.head_size, cfg.ffn_size or 4 * cfg.hidden_size)
    eps, block = cfg.layernorm_epsilon, cfg.decode_block_kv
    keys = iter(jax.random.split(jax.random.key(SEED), 64))
    bf16 = jnp.bfloat16

    def normal(shape, dtype=bf16, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dtype)

    def int8_proj(K, N):
        group = 128 if K % 128 == 0 else K  # quantize_params' grouping rule
        w = jax.random.randint(next(keys), (K, N), -127, 128, jnp.int8)
        scales = jax.random.uniform(next(keys), (K // group, N), jnp.float32,
                                    1e-4, 3e-4)
        return w, scales, normal((N, ), jnp.float32, 0.02)

    checks = []

    def check(name, tol, kernel, ref, *args):
        """One program per check: the compiled kernel, the plain XLA
        reference on the same operands (all passed as arguments: a closed-
        over array would be baked into the program), their difference."""
        errs = jax.jit(lambda *a: jax.tree_util.tree_map(
            _rel_err, kernel(*a), ref(*a)))(*args)
        for suffix, err in zip(name if isinstance(name, list) else [name],
                               jax.tree_util.tree_leaves(errs)):
            err = float(err)
            checks.append({"kernel": suffix, "rel_err": err, "tol": tol})
            if not err <= tol:  # also catches nan
                raise AssertionError(f"kernels: {suffix} differs from XLA by "
                                     f"{err} (tolerance {tol})")

    # ---- flash attention, forward and backward (the train step's kernel)
    q = normal((train_batch, nh, seq, hd))
    k = normal((train_batch, nkv, seq, hd))
    v = normal((train_batch, nkv, seq, hd))
    w = normal((train_batch, nh, seq, hd), jnp.float32)
    base0 = jnp.zeros((train_batch, ), jnp.int32)

    def flash(q, k, v, w, base0):
        return flash_attention(q, k, v, True, cfg.attention_block_q,
                               cfg.attention_block_kv)

    def ref_flash(q, k, v, w, base0):
        return _ref_span_attention(q, k, v, base0, base0)

    def weighted(fn):
        return jax.grad(lambda q, k, v, w, base0: jnp.sum(fn(q, k, v, w, base0) * w),
                        (0, 1, 2))

    check("flash_attention.fwd", TOL, flash, ref_flash, q, k, v, w, base0)
    check([f"flash_attention.bwd.d{n}" for n in "qkv"], TOL_CHAINED,
          weighted(flash), weighted(ref_flash), q, k, v, w, base0)

    # ---- decode attention over the slot pool: dense, paged, span, int8 KV
    kc = normal((slots, nkv, pool_len, hd))
    vc = normal((slots, nkv, pool_len, hd))
    start = jnp.asarray([0, 3] * (slots // 2), jnp.int32)
    base = jax.random.randint(next(keys), (slots, ), 8, pool_len - chunk, jnp.int32)
    q1 = normal((slots, nh, hd))
    qT = normal((slots, nh, chunk, hd))
    end = jnp.asarray(pool_len // 2, jnp.int32)

    def ref_decode(q, k, v, start, base):
        return _ref_span_attention(q[:, :, None], k, v, start, base)[:, :, 0]

    def served(k, v):
        """K and V as the pool of this head size holds them: one packed
        leaf (keys, then values, on the last axis) at head size 64."""
        return (jnp.concatenate([k, v], axis=-1), None) if kv_packs(hd) else (k, v)

    check("decode_attention", TOL,
          lambda q, k, v, s, e: da.decode_attention(q, *served(k, v), s, e, block_kv=block),
          lambda q, k, v, s, e: ref_decode(q, k, v, s, jnp.full((slots, ), e - 1)),
          q1, kc, vc, start, end)
    check("paged_decode_attention", TOL,
          lambda q, k, v, s, b: da.paged_decode_attention(q, *served(k, v), s, b + 1,
                                                          block_kv=block),
          ref_decode, q1, kc, vc, start, base)
    check("paged_span_attention", TOL,
          lambda q, k, v, s, b: da.paged_span_attention(q, *served(k, v), s, b,
                                                        block_kv=block),
          _ref_span_attention, qT, kc, vc, start, base)
    k8, v8, sc = jax.jit(quantize_kv_rows)(kc, vc)

    def dequant(ref):
        return lambda q, k, v, s, b, sc: ref(
            q, k.astype(jnp.float32) * sc.astype(jnp.float32),
            v.astype(jnp.float32) * sc.astype(jnp.float32), s, b)

    check("paged_decode_attention.int8kv", TOL,
          lambda q, k, v, s, b, sc: da.paged_decode_attention(
              q, *served(k, v), s, b + 1, block_kv=block, k_scale=sc, v_scale=sc),
          dequant(ref_decode), q1, k8, v8, start, base, sc)
    check("paged_span_attention.int8kv", TOL,
          lambda q, k, v, s, b, sc: da.paged_span_attention(
              q, *served(k, v), s, b, block_kv=block, k_scale=sc, v_scale=sc),
          dequant(_ref_span_attention), qT, k8, v8, start, base, sc)

    # ---- fused decode blocks and the logits head, decode and span rows
    norms = jnp.stack([1 + normal((H, ), jnp.float32, 0.1), normal((H, ), jnp.float32, 0.1),
                       1 + normal((H, ), jnp.float32, 0.1), normal((H, ), jnp.float32, 0.1)])
    qkv, o = int8_proj(H, (nh + 2 * nkv) * hd), int8_proj(nh * hd, H)
    up, down = int8_proj(H, F), int8_proj(F, H)
    vocab = -(-cfg.vocab_size // 128) * 128
    logits_w, logits_s, _ = int8_proj(H, vocab)

    def ref_qkv(x, norms, qkv):
        xln = _ref_layernorm(x.astype(jnp.float32), norms[0], norms[1], eps)
        return _ref_qmm(xln.astype(bf16), qkv[0], qkv[1]) + qkv[2]

    def ref_out_mlp(attn, x, norms, o, up, down):  # rounds where the kernel's scratch is bf16
        res = _ref_qmm(attn, o[0], o[1]) + o[2] + x.astype(jnp.float32)
        ln2 = _ref_layernorm(res, norms[2], norms[3], eps).astype(bf16)
        hid = jax.nn.gelu(_ref_qmm(ln2, up[0], up[1]) + up[2], approximate=True)
        return res + _ref_qmm(hid.astype(bf16), down[0], down[1]) + down[2]

    for rows in (slots, slots * chunk):
        x = normal((rows, H))
        attn = normal((rows, nh * hd))
        check(f"fused_qkv_ln.rows{rows}", TOL,
              lambda x, norms, qkv: db.fused_qkv_ln(x, norms, qkv, eps=eps,
                                                    norm=cfg.norm),
              ref_qkv, x, norms, qkv)
        check(f"fused_out_mlp.rows{rows}", TOL_CHAINED,
              lambda a, x, norms, o, up, down: db.fused_out_mlp(
                  a, x, norms, o, up, down, activation=cfg.activation, eps=eps,
                  norm=cfg.norm),
              ref_out_mlp, attn, x, norms, o, up, down)
        check(f"quant_matmul.logits.rows{rows}", TOL,
              lambda x, w, sc: quant_matmul(x, w, sc, block_m=min(rows, 256)),
              _ref_qmm, x, logits_w, logits_s)
    emit("kernels", model=model, checks=checks)


def _count_compiles():
    """jax.monitoring counters: programs XLA was asked for, and how the
    persistent cache answered."""
    import jax
    seen = {"programs": 0, "cache_hits": 0, "cache_misses": 0}
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: seen.__setitem__("programs", seen["programs"] + 1)
        if name == "/jax/core/compile/backend_compile_duration" else None)

    def on_event(name, *_a, **_k):
        for key in ("cache_hits", "cache_misses"):
            if name == f"/jax/compilation_cache/{key}":
                seen[key] += 1
    jax.monitoring.register_event_listener(on_event)
    return seen


def _train(model_name, seq, micro_batch, steps, mesh_kw, zero_stage, seen):
    """``deepspeed_tpu.initialize`` + ``engine.train_batch`` for 1 + steps
    steps on one seeded batch. Returns (engine, losses, seconds per step of
    the fenced window, compiles inside that window, ms the value fetch took
    after ``block_until_ready`` returned)."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.models import get_model

    comm._state["mesh"] = None
    if mesh_kw is not None:
        comm.initialize_mesh(**mesh_kw)
    model = get_model(model_name, attention_impl="flash", scan_layers=False,
                      remat_policy=None)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": micro_batch,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
            "bf16": {"enabled": True},
            "gradient_clipping": 1.0,
            "zero_optimization": {"stage": zero_stage},
            "steps_per_print": 10**9,
        })
    rng = np.random.default_rng(SEED)
    batch = {"input_ids": rng.integers(
        0, model.cfg.vocab_size, (engine.train_batch_size(), seq)).astype(np.int32)}
    losses = [float(engine.train_batch(batch=batch))]  # compiles
    before = seen["programs"]
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch)
        losses.append(loss)
    jax.block_until_ready(loss)
    t1 = time.perf_counter()
    losses = [float(x) for x in losses]  # value fetch: ~0 if the fence fenced
    fetch_ms = (time.perf_counter() - t1) * 1e3
    return engine, losses, (t1 - t0) / steps, seen["programs"] - before, fetch_ms


def _check_losses(phase, losses, vocab, band=1.0):
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{phase}: non-finite loss in {losses}")
    if abs(losses[0] - math.log(vocab)) > band:
        raise AssertionError(
            f"{phase}: first loss {losses[0]} is not within {band} of "
            f"ln({vocab}) = {math.log(vocab):.3f} (random init predicts uniformly)")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{phase}: loss did not fall on a repeated batch: {losses}")


def _compiled_step_text(engine, batch_leading):
    """Text of the train step as compiled (a cache hit after the run)."""
    import numpy as np
    fn = engine._compiled["train_batch"]
    placed = engine._shard_batch({"input_ids": np.zeros(batch_leading, np.int32)},
                                 leading_scan_dim=True)
    with engine.mesh:
        return fn.lower(engine.state, placed).compile().as_text()


def phase_train(model=MODEL, seq=1024, micro_batch=4, steps=5):
    seen = _count_compiles()
    engine, losses, dt, late_compiles, fetch_ms = _train(
        model, seq, micro_batch, steps, None, 0, seen)
    _check_losses("train", losses, engine.module.cfg.vocab_size)
    if late_compiles:
        raise AssertionError(f"train: {late_compiles} programs compiled after "
                             f"the first step")
    counted = dict(seen)
    text = _compiled_step_text(engine, (1, engine.train_batch_size(), seq))
    if "tpu_custom_call" not in text:
        raise AssertionError("train: the compiled step holds no Pallas kernel "
                             "(tpu_custom_call)")
    tokens = engine.train_batch_size() * seq
    emit("train", model=model, params=engine.module.cfg.num_params(), seq=seq,
         micro_batch=micro_batch, losses=losses, ln_vocab=math.log(
             engine.module.cfg.vocab_size), ms_per_step=dt * 1e3,
         tokens_per_s=tokens / dt, fetch_after_fence_ms=fetch_ms,
         compiles_after_first_step=late_compiles,
         tpu_custom_call=True, **counted)


def _shard_census(tree):
    """(bytes held per device id, bytes on the fullest device)."""
    import jax
    per_dev = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            per_dev[shard.device.id] = per_dev.get(shard.device.id, 0) + shard.data.nbytes
    return per_dev, max(per_dev.values())


def phase_multichip(model=MODEL, seq=1024, micro_batch=1, steps=5, chips=4,
                    early_tol=5e-3, late_tol=0.1):
    """ZeRO-3 over ``data=chips`` against the same seed, global batch and
    steps on ONE of the devices. The two runs do the same arithmetic in a
    different reduction order on bf16 activations, and training amplifies
    that: on the chip the losses differed by 7e-5, 3e-5, 2.6e-4, 1.5e-3,
    4.9e-3, 1.9e-2 (about 4x a step while a step moves the loss by 0.3; 3e-5
    throughout at the rehearsal's tiny size). So the first three losses —
    same weights, then one and two updates — must agree within ``early_tol``,
    which a wrong shard, a dropped gradient or a different batch cannot (a 4x
    batch showed as 6e-3 at once), and the rest within ``late_tol``."""
    import jax

    seen = _count_compiles()
    one_dev = {"devices": jax.devices()[:1]}
    engine, ref_losses, ref_dt, _, _ = _train(model, seq, micro_batch * chips,
                                              steps, one_dev, 0, seen)
    _, ref_param_bytes = _shard_census(engine.state.params)
    _, ref_opt_bytes = _shard_census(engine.state.opt_state)
    del engine  # its state must leave device 0 before the sharded run
    gc.collect()

    engine, losses, dt, late_compiles, _ = _train(model, seq, micro_batch, steps,
                                                  {"data": chips}, 3, seen)
    _check_losses("multichip", losses, engine.module.cfg.vocab_size)
    diffs = [abs(a - b) for a, b in zip(losses, ref_losses)]
    if max(diffs[:3]) > early_tol or max(diffs) > late_tol:
        raise AssertionError(f"multichip: ZeRO-3 data={chips} losses {losses} "
                             f"differ from one device's {ref_losses} by {diffs}")
    if late_compiles:
        raise AssertionError(f"multichip: {late_compiles} programs compiled "
                             f"after the first step")
    p_dev, p_max = _shard_census(engine.state.params)
    o_dev, o_max = _shard_census(engine.state.opt_state)
    shares = {"param": p_max / ref_param_bytes, "optimizer": o_max / ref_opt_bytes}
    if len(p_dev) != chips or len(o_dev) != chips:
        raise AssertionError(f"multichip: shards sit on {sorted(p_dev)} / "
                             f"{sorted(o_dev)}, not on {chips} devices")
    for what, share in shares.items():
        # a quarter, plus the few small leaves ZeRO-3 keeps whole
        if not 1 / chips <= share < 1 / chips + 0.05:
            raise AssertionError(f"multichip: fullest device holds {share:.3f} of "
                                 f"one device's {what} bytes, expected ~1/{chips}")
    text = _compiled_step_text(engine, (1, engine.train_batch_size(), seq))
    collectives = {name: text.count(name)
                   for name in ("all-gather", "reduce-scatter", "all-reduce")}
    # the TPU compiler turns ZeRO's gradient reduction into reduce-scatter;
    # the CPU backend of the rehearsal leaves it as all-reduce + slice
    reduction = ("reduce-scatter" if jax.devices()[0].platform == "tpu"
                 else "all-reduce")
    if not (collectives["all-gather"] and collectives[reduction]):
        raise AssertionError(f"multichip: compiled step lacks all-gather or "
                             f"{reduction}: {collectives}")
    emit("multichip", model=model, chips=chips, zero_stage=3, seq=seq,
         global_batch=engine.train_batch_size(), losses=losses,
         one_device_losses=ref_losses, loss_diffs=diffs, early_tol=early_tol,
         late_tol=late_tol,
         shard_devices=sorted(p_dev), per_device_share=shares,
         collectives=collectives, ms_per_step=dt * 1e3,
         one_device_ms_per_step=ref_dt * 1e3,
         compiles_after_first_step=late_compiles)


CHILD_PHASES = {"device": phase_device, "kernels": phase_kernels,
                "train": phase_train, "multichip": phase_multichip}


# =========================================================================
# the parent: no jax from here on
# =========================================================================
def _pump(stream, sink, echo):
    for line in stream:
        sink.append(line)
        echo.write(line)
        echo.flush()


def run_chip_child(code, timeout):
    """Run ``code`` in a fresh interpreter at the repo root, echo its output,
    and return its stdout lines. Non-zero exit or timeout ends the smoke."""
    proc = subprocess.Popen([sys.executable, "-c", code], cwd=HERE,
                            stdout=subprocess.PIPE, text=True)
    lines = []
    reader = threading.Thread(target=_pump, args=(proc.stdout, lines, sys.stdout))
    reader.start()
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        sys.exit(f"chip_smoke: child exceeded {timeout}s: {code}")
    finally:
        reader.join()
    if rc != 0:
        sys.exit(f"chip_smoke: child exited with code {rc}: {code}")
    return lines


def device_block(lines):
    for line in lines:
        dev = json.loads(line) if line.startswith("{") else {}
        if dev.get("phase") == "device":
            return {key: dev[key] for key in ("platform", "kind", "count")}
    sys.exit("chip_smoke: the child printed no device line")


def _http(port, method, path, body=None, timeout=600):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, None if body is None else json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _completion(port, prompt, stream, max_tokens=32):
    status, raw = _http(port, "POST", "/v1/completions",
                        {"prompt": prompt, "max_tokens": max_tokens, "stream": stream})
    if status != 200:
        raise AssertionError(f"serve: /v1/completions answered {status}: {raw[:300]!r}")
    if not stream:
        return json.loads(raw)["choices"][0]["token_ids"]
    text = raw.decode()
    if "data: [DONE]" not in text:
        raise AssertionError("serve: stream ended without data: [DONE]")
    toks = []
    for line in text.splitlines():
        if line.startswith("data: {"):
            toks += json.loads(line[6:])["choices"][0]["token_ids"]
    return toks


def phase_serve(model=MODEL, dtype="int8", vocab=50257, prompt_lens=(32, 96, 160, 192),
                ready_timeout=900):
    """The README quick-start, as written, answered over HTTP."""
    import random
    cmd = [sys.executable, "-m", "deepspeed_tpu.serving", "--model", model,
           "--dtype", dtype, "--kernel-inject", "--num-slots", "8", "--port", "0"]
    t0 = time.perf_counter()
    # the server logs to its stdout: merge the streams, keep reading for its
    # whole life (a full pipe would block it) and echo to OUR stderr, so this
    # script's stdout carries phase lines only
    proc = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    log = []
    logger = threading.Thread(target=_pump, args=(proc.stdout, log, sys.stderr))
    logger.start()
    try:
        port = None
        while port is None and proc.poll() is None \
                and time.perf_counter() - t0 < ready_timeout:
            time.sleep(0.5)
            for line in list(log):
                if "GATEWAY_READY" in line:
                    port = json.loads(line[line.index("{"):])["port"]
        if port is None:
            raise AssertionError(f"serve: no GATEWAY_READY within {ready_timeout}s "
                                 f"(server exit code {proc.poll()})")
        ready_s = time.perf_counter() - t0
        status, _ = _http(port, "GET", "/readyz")
        if status != 200:
            raise AssertionError(f"serve: /readyz answered {status}")

        rng = random.Random(SEED)
        prompts = [[rng.randrange(vocab) for _ in range(n)] for n in prompt_lens]
        t1 = time.perf_counter()
        outs = [_completion(port, p, stream=i % 2 == 0) for i, p in enumerate(prompts)]
        first_four_s = time.perf_counter() - t1
        again = _completion(port, prompts[1], stream=False)
        for toks in outs + [again]:
            if len(toks) != 32 or not all(0 <= t < vocab for t in toks):
                raise AssertionError(f"serve: expected 32 token ids below {vocab}, "
                                     f"got {toks}")
        if again != outs[1]:
            raise AssertionError(f"serve: greedy decoding of one prompt gave "
                                 f"{outs[1]} then {again}")
        status, raw = _http(port, "GET", "/v1/metrics")
        if status != 200:
            raise AssertionError(f"serve: /v1/metrics answered {status}")
        expired = json.loads(raw)["gateway"]["deadline_expired"]
        if expired:
            raise AssertionError(f"serve: {expired} requests hit their deadline")
        sched = json.loads(raw)["scheduler"]
        if not sched["fused_decode_block"] or sched["fused_decode_reasons"]:
            raise AssertionError(f"serve: fused decode path declined: "
                                 f"{sched['fused_decode_reasons']}")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        if rc != 0:
            raise AssertionError(f"serve: server exited {rc} after SIGTERM")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        logger.join()
    if any("fused decode-block disabled" in line for line in log):
        raise AssertionError("serve: the server's log says the fused decode "
                             "path was declined")
    emit("serve", command=" ".join(cmd[1:]), ready_s=ready_s,
         first_four_requests_s=first_four_s, requests=5, new_tokens=32,
         prompt_tokens=[len(p) for p in prompts], greedy_repeat_identical=True,
         fused_decode_block=True, compiled_programs=sched["compiled_programs"],
         drained_exit_code=rc)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only ZeRO-3 data=4 against one device")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    if args.multichip:
        lines = run_chip_child(
            "import chip_smoke as s; s.phase_device(4); s.phase_multichip()", 1100)
    else:
        lines = run_chip_child(
            "import chip_smoke as s; s.phase_device(); s.phase_kernels(); "
            "s.phase_train()", 800)
        phase_serve()
    emit("total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device_block(lines)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
