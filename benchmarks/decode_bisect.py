"""Bisect gpt2-large int8 decode-step cost using the REAL engine fast-tree
pieces: kernel A (ln1+qkv), decode_attention, kernel C (o+mlp), logits.
Marginal timing (many-vs-few calls) cancels the fixed per-call fetch."""
import sys, time
sys.path.insert(0, "/root/repo")
import jax, jax.numpy as jnp, numpy as np
import deepspeed_tpu

eng = deepspeed_tpu.init_inference("gpt2-large", config={"dtype": "int8",
    "max_out_tokens": 512, "kernel_inject": True})
layers, head = eng._fast_tree()
mc = eng.model_config
B, H, S = 8, mc.hidden_size, 256
nh, hd = mc.num_heads, mc.head_size
r = np.random.default_rng(0)
x0 = jnp.asarray(r.standard_normal((B, H)), jnp.bfloat16)
kc = jnp.asarray(r.standard_normal((B, nh, S, hd)), jnp.bfloat16)
vc = jnp.asarray(r.standard_normal((B, nh, S, hd)), jnp.bfloat16)
starts = jnp.zeros((B,), jnp.int32)

from deepspeed_tpu.ops.pallas.decode_block import fused_qkv_ln, fused_out_mlp
from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
from deepspeed_tpu.ops.pallas.quant_matmul import quant_matmul


def timeit(f, *args, tag=""):
    g = jax.jit(f)
    t0 = time.perf_counter()
    y = g(*args); float(jnp.sum(y))
    print(f"  [{tag}] compile {time.perf_counter()-t0:.0f}s", flush=True)
    def t(n):
        best = 1e9
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(n): y = g(*args)
            float(jnp.sum(y))
            best = min(best, time.perf_counter()-t0)
        return best
    per = (t(33) - t(1)) / 32
    print(f"{tag}: {per*1e3:.3f} ms per 36-layer pass", flush=True)
    return per


def f_qkv(x):
    for (norms, qkv, o, up, down) in layers:
        y = fused_qkv_ln(x, norms, qkv, eps=mc.layernorm_epsilon)
        x = (x + 1e-6 * y[:, :H]).astype(x.dtype)
    return x

def f_attn(x):
    q0 = jnp.tile(x[:, None, :hd], (1, nh, 1))
    acc = jnp.zeros((B, nh, hd), jnp.float32)
    for i in range(36):
        o = decode_attention((q0 + 1e-6*acc).astype(jnp.bfloat16), kc, vc, starts, 177,
                             block_kv=mc.decode_block_kv)
        acc = acc + o
    return acc

def f_mlp(x):
    attn = jnp.tile(x[:, :hd], (1, nh))
    for (norms, qkv, o, up, down) in layers:
        x = fused_out_mlp((attn + 1e-6 * jnp.tile(x[:, :hd], (1, nh))).astype(jnp.bfloat16),
                          x, norms, o, up, down,
                          activation=mc.activation, eps=mc.layernorm_epsilon)
    return x

def f_logits(x):
    y = quant_matmul(x, head["logits_q"], head["logits_scale"], block_m=8)
    return (x + 1e-9 * y[:, :H]).astype(x.dtype)

which = sys.argv[1:] or ["qkv", "attn", "mlp", "logits"]
if "qkv" in which: timeit(f_qkv, x0, tag="qkv(A)x36")
if "attn" in which: timeit(f_attn, x0, tag="attn x36")
if "mlp" in which: timeit(f_mlp, x0, tag="o+mlp(C)x36")
if "logits" in which: timeit(f_logits, x0, tag="logits x1")
