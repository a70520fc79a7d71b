"""Operations and bytes the algorithms need, from shapes alone. The yardstick
for ``*_mfu_pct`` and ``*_roofline_pct``: kept with the benchmark so that a PR
that changes the program cannot change what counts as required work.

All counts are of REQUIRED work: a multiply-add is two operations, causal
attention needs half of the full T x T score matrix, recomputed operations
and padding rows do not count.
"""


def layer_matmul_params(cfg):
    """Weights a token multiplies in one layer: q, k, v, o and the two (or
    three, gated) feed-forward matrices."""
    h, d = cfg.hidden_size, cfg.head_size
    attn = h * d * (cfg.num_heads + 2 * cfg.kv_heads) + cfg.num_heads * d * h
    gated = cfg.activation in ("swiglu", "geglu")
    return attn + (3 if gated else 2) * h * cfg.ffn_size


def train_flops_per_token(cfg, seq_len):
    """Forward + backward operations one trained token requires.

    6 per matmul weight (2 forward, 4 backward) over the layers' matrices
    AND the vocabulary projection (a real H x V matmul per token; the
    embedding lookup and learned positions are gathers and cost none), plus
    causal attention: scores and values are 2 x 2 x (T/2) x H operations per
    token per layer forward, three times that with the backward pass:
    6 x L x H x T. PaLM's arithmetic counts the full T x T attention
    (12 x L x H x T) and leaves the head and the norms out; this count is
    2.1% above it at gpt2-large, seq 1024 (4,915,822,080 against
    4,813,524,480 operations a token)."""
    weights = cfg.num_layers * layer_matmul_params(cfg) + cfg.hidden_size * cfg.vocab_size
    attention = 6 * cfg.num_layers * cfg.num_heads * cfg.head_size * seq_len
    return 6 * weights + attention


def flash_attention_call(batch, heads, kv_heads, seq_len, head_size, itemsize, backward):
    """(operations, bytes) of one causal flash-attention call over
    (batch, heads, seq_len, head_size). Forward: QK^T and PV, 2 matmuls of
    2 x T x T x d each, halved by the causal mask; q, k, v read and o
    written once. Backward (dq and dk/dv kernels together): 5 such matmuls
    (recomputed scores, dP, dq, dk, dv), halved; q, k, v, o, do read, dq, dk,
    dv written."""
    matmuls = 5 if backward else 2
    ops = matmuls * 2 * batch * heads * seq_len * seq_len * head_size / 2
    q_like = batch * heads * seq_len * head_size * itemsize
    kv_like = batch * kv_heads * seq_len * head_size * itemsize
    nbytes = (3 * q_like + 4 * kv_like) if backward else (2 * q_like + 2 * kv_like)
    return ops, nbytes


def roofline_seconds(ops, nbytes, peaks, int8=False):
    """The least time the chip could take, and which roof sets it."""
    t_ops = ops / (peaks["int8_ops"] if int8 else peaks["bf16_flops"])
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
