"""Model presets.

Covers the reference's model families (its inference containers,
``module_inject/containers/*``: gpt2, opt, bloom, gptj, gptneox, megatron,
llama-style) plus the BASELINE.json tracked configs (GPT-2 125M, Llama-3
8B/70B, Mixtral 8x7B, OPT-66B, Llama-2-7B).
"""

import jax.numpy as jnp

from .transformer import TransformerConfig, CausalLM, CausalLMModel, sambay_layers

_PRESETS = {}


def register(name, overrides_first=False):
    """``overrides_first``: the preset's function takes ``get_model``'s
    overrides itself and builds ONE configuration from the published sizes
    with them applied, for a model whose published configuration the program
    refuses whole (parts of it are not served) and serves cut."""

    def deco(fn):
        fn.overrides_first = overrides_first
        _PRESETS[name] = fn
        return fn

    return deco


def available_models():
    return sorted(_PRESETS)


def get_model(name, **overrides):
    if name not in _PRESETS:
        raise ValueError(f"Unknown model {name}; available: {available_models()}")
    preset = _PRESETS[name]
    if preset.overrides_first:
        return CausalLMModel(preset(**overrides))
    cfg = preset()
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    return CausalLMModel(cfg)


def _gpt2(hidden, layers, heads, vocab=50257, seq=1024):
    return TransformerConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
                             max_seq_len=seq, pos_embedding="learned", norm="layernorm",
                             activation="gelu", tie_embeddings=True)


def _llama(hidden, layers, heads, kv_heads, ffn, vocab=128256, seq=8192, theta=500000.0):
    return TransformerConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
                             num_kv_heads=kv_heads, intermediate_size=ffn, max_seq_len=seq,
                             pos_embedding="rope", norm="rmsnorm", activation="swiglu",
                             tie_embeddings=False, rope_theta=theta)


def _opt(hidden, layers, heads, vocab=50272, seq=2048):
    return TransformerConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
                             max_seq_len=seq, pos_embedding="learned", norm="layernorm",
                             activation="relu", tie_embeddings=True)


@register("gpt2-125m")
def gpt2_125m():
    return _gpt2(768, 12, 12)


@register("gpt2-medium")
def gpt2_medium():
    return _gpt2(1024, 24, 16)


@register("gpt2-large")
def gpt2_large():
    return _gpt2(1280, 36, 20)


@register("gpt2-xl")
def gpt2_xl():
    return _gpt2(1600, 48, 25)


@register("llama3-8b")
def llama3_8b():
    return _llama(4096, 32, 32, 8, 14336)


@register("llama3-70b")
def llama3_70b():
    return _llama(8192, 80, 64, 8, 28672)


@register("llama2-7b")
def llama2_7b():
    return _llama(4096, 32, 32, 32, 11008, vocab=32000, seq=4096, theta=10000.0)


@register("mixtral-8x7b")
def mixtral_8x7b():
    import dataclasses
    cfg = _llama(4096, 32, 32, 8, 14336, vocab=32000, seq=4096, theta=1000000.0)
    return dataclasses.replace(cfg, num_experts=8, moe_top_k=2)


@register("opt-125m")
def opt_125m():
    return _opt(768, 12, 12)


@register("opt-66b")
def opt_66b():
    return _opt(9216, 64, 72)


@register("tiny")
def tiny():
    """Test-scale llama-style model."""
    return TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                             num_kv_heads=2, max_seq_len=128, intermediate_size=128)


@register("tiny-gpt2")
def tiny_gpt2():
    """Test-scale gpt2-style model (learned positions, layernorm, gelu,
    MHA) — the shape the fused int8 decode-block kernel serves."""
    return TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                             max_seq_len=128, intermediate_size=128,
                             pos_embedding="learned", norm="layernorm",
                             activation="gelu", tie_embeddings=True)


@register("tiny-moe")
def tiny_moe():
    return TransformerConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                             num_kv_heads=2, max_seq_len=128, intermediate_size=128,
                             num_experts=4, moe_top_k=2)


def _mla_moe(hidden, layers, heads, q_rank, kv_rank, nope, rope, v_dim, experts, top_k,
             expert_ffn, shared, vocab, seq, original_max_len, factor):
    """Latent attention + routed experts with shared ones (``mistral4`` /
    DeepSeek-V2 style): YaRN frequencies, interleaved rotary pairs, the
    position-dependent query scale, untied head, dropless routing."""
    return TransformerConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
        head_dim=nope + rope, max_seq_len=seq, pos_embedding="rope", norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, layernorm_epsilon=1e-6, rope_theta=10000.0,
        kv_lora_rank=kv_rank, q_lora_rank=q_rank, qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=v_dim, rope_interleave=True,
        rope_factor=factor, rope_beta_fast=32.0, rope_beta_slow=1.0,
        rope_original_max_len=original_max_len, rope_mscale=1.0, rope_mscale_all_dim=1.0,
        attn_temp_beta=0.1, num_experts=experts, moe_top_k=top_k, moe_ffn_size=expert_ffn,
        moe_shared_experts=shared, moe_routed_scale=1.0, moe_dropless=True)


@register("mistral-small-4-119b")
def mistral_small_4_119b():
    """Mistral-Small-4-119B-2603's language model at its published sizes
    (huggingface.co/mistralai/Mistral-Small-4-119B-2603 config.json,
    ``model_type: mistral4``): 36 layers, all 128 experts held. The vision
    encoder is not built. Served only (no capacity-buffered training path)."""
    return _mla_moe(4096, 36, 32, 1024, 256, 64, 64, 128, 128, 4, 2048, 1, 131072,
                    1048576, 8192, 128.0)


@register("tiny-mla-moe")
def tiny_mla_moe():
    """Test-scale latent-attention MoE; ``rope_original_max_len`` 16 so that
    the YaRN blend and g(t) are exercised within 128 positions."""
    return _mla_moe(64, 2, 4, 32, 16, 8, 8, 16, 8, 2, 32, 1, 256, 128, 16, 8.0)


def _hybrid(hidden, layers, heads, head_dim, ffn, lin_heads, lin_dk, lin_dv, vocab, seq,
            period=4):
    """Gated-delta-rule linear attention with one full-attention layer a
    ``period`` (``olmo_hybrid`` style): post-norm residuals, QK norm, no
    rotary rotation, SwiGLU, untied head. Unrolled: the layers differ."""
    types = tuple("full_attention" if (i + 1) % period == 0 else "linear_attention"
                  for i in range(layers))
    return TransformerConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
        head_dim=head_dim, intermediate_size=ffn, max_seq_len=seq, pos_embedding="none",
        norm="rmsnorm", activation="swiglu", tie_embeddings=False, layernorm_epsilon=1e-6,
        attn_bias=False, layer_types=types, linear_num_heads=lin_heads,
        linear_key_head_dim=lin_dk, linear_value_head_dim=lin_dv, linear_neg_eigval=True, post_norm=True, qk_norm=True, scan_layers=False)


@register("olmo-hybrid-7b")
def olmo_hybrid_7b():
    """Olmo-Hybrid-7B at its published sizes (huggingface.co/allenai/
    Olmo-Hybrid-7B config.json, ``model_type: olmo_hybrid``): 32 layers,
    three gated-delta-rule layers (30 heads, keys of 96, values of 192,
    convolution of 4) to each full-attention layer (30 heads of 128).
    Served only. ``num_layers`` is overridden together with ``layer_types``."""
    return _hybrid(3840, 32, 30, 128, 11008, 30, 96, 192, 100352, 65536)


@register("tiny-hybrid")
def tiny_hybrid():
    """Test-scale hybrid: one period of three linear-attention layers and a
    full-attention one."""
    return _hybrid(64, 4, 4, 16, 128, 4, 8, 16, 256, 256)


def _sambay(hidden, layers, heads, kv_heads, ffn, window, vocab, seq, d_state=16, mb_per_layer=2):
    """A decoder-hybrid-decoder stack (SambaY, arXiv:2507.06607, ``phi4flash``):
    Mamba layers alternate with differential attention, windowed in the first
    half; one full-attention layer, whose K/V the second half's
    cross-attention layers share, and gated memory units over the last Mamba
    layer's output. Pre-LN with LayerNorm, SwiGLU without bias, no positional
    encoding, tied head. Unrolled: the layers differ."""
    types, windows = sambay_layers(layers, mb_per_layer, window)
    return TransformerConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
        num_kv_heads=kv_heads, intermediate_size=ffn, max_seq_len=seq, pos_embedding="none",
        norm="layernorm", activation="swiglu", tie_embeddings=True, layernorm_epsilon=1e-5,
        attn_bias=True, mlp_bias=False, layer_types=types, layer_windows=windows,
        ssm_state_size=d_state, ssm_conv_kernel=4, ssm_expand=2, ssm_dt_rank=-(-hidden // 16),
        scan_layers=False)


@register("phi-4-mini-flash-reasoning")
def phi_4_mini_flash_reasoning():
    """Phi-4-mini-flash-reasoning at its published sizes (huggingface.co/
    microsoft/Phi-4-mini-flash-reasoning config.json, ``model_type:
    phi4flash``): 32 layers, 40 query and 20 key/value heads of 64, a window
    of 512, Mamba with 16 states a channel, 3.85 B parameters. Served only."""
    return _sambay(2560, 32, 40, 20, 10240, 512, 200064, 262144)


@register("tiny-sambay")
def tiny_sambay():
    """Test-scale SambaY: 8 layers (mamba, window, mamba, window, mamba, full,
    gmu, cross), head size 64, a window of 16 keys."""
    return _sambay(256, 8, 4, 2, 128, 16, 256, 256, d_state=4)


_NEMOTRON_H_KINDS = {"M": "mamba2", "E": "moe", "*": "attention", "-": "mlp"}


def nemotron_h_layers(pattern):
    """``layer_types`` from a ``hybrid_override_pattern``: every layer is ONE
    sublayer, ``M`` a Mamba-2 mixer, ``E`` an expert FFN, ``*`` attention,
    ``-`` a dense FFN."""
    return tuple(_NEMOTRON_H_KINDS[c] for c in pattern)


def _nemotron_h(hidden, pattern, heads, kv_heads, head_dim, ssm_heads, ssm_head_dim, ssm_state,
                ssm_groups, experts, top_k, expert_ffn, shared_ffn, routed_scale, vocab, seq):
    """A ``nemotron_h`` stack: every layer ``x + f(RMSNorm(x))`` with ``f`` a
    Mamba-2 mixer, an expert layer (relu2 experts of two matrices under a
    sigmoid router with a selection bias, one shared expert of its own
    width) or attention without any positional term; untied head.
    Unrolled: the layers differ. Served only."""
    return TransformerConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=len(pattern), num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, intermediate_size=expert_ffn, max_seq_len=seq,
        pos_embedding="none", norm="rmsnorm", activation="relu2", tie_embeddings=False,
        layernorm_epsilon=1e-5, attn_bias=False, mlp_bias=False,
        layer_types=nemotron_h_layers(pattern), num_experts=experts, moe_top_k=top_k,
        moe_ffn_size=expert_ffn, moe_shared_experts=1, moe_shared_ffn_size=shared_ffn,
        moe_routed_scale=routed_scale, moe_scoring="sigmoid", moe_dropless=True,
        ssm_state_size=ssm_state, ssm_conv_kernel=4, ssm_num_heads=ssm_heads,
        ssm_head_dim=ssm_head_dim, ssm_groups=ssm_groups, ssm_chunk_size=128, scan_layers=False)


@register("nemotron-3-nano-30b-a3b")
def nemotron_3_nano_30b_a3b():
    """NVIDIA-Nemotron-3-Nano-30B-A3B at its published sizes (huggingface.co/
    nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json, ``model_type:
    nemotron_h``): 52 one-sublayer blocks (23 Mamba-2, 23 expert, 6
    attention), 64 Mamba-2 heads of 64 with a state of 128 in 8 groups, 32
    query and 2 key/value heads of 128, 128 experts of 1,856 top-6 with a
    shared one of 3,712, 31.6 B parameters. ``num_layers`` is overridden
    together with ``layer_types``."""
    return _nemotron_h(2688, "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME", 32, 2,
                       128, 64, 64, 128, 8, 128, 6, 1856, 3712, 2.5, 131072, 262144)


@register("tiny-nemotron-h")
def tiny_nemotron_h():
    """Test-scale ``nemotron_h``: 8 one-sublayer blocks, every kind present
    (the dense FFN too), 4 Mamba-2 heads of 8 with a state of 16 in 2 groups,
    8 experts top-2 with a shared one twice as wide, chunks of 8."""
    import dataclasses
    cfg = _nemotron_h(64, "MEM*E-ME", 4, 2, 16, 4, 8, 16, 2, 8, 2, 32, 64, 2.5, 256, 256)
    return dataclasses.replace(cfg, ssm_chunk_size=8)


def _falcon_h1(hidden, layers, heads, kv_heads, head_dim, ffn, ssm_heads, ssm_head_dim,
               ssm_state, ssm_groups, chunk, vocab, seq, theta, embedding, lm_head, attn_in,
               attn_out, key, ssm_in, ssm_out, ssm_zxbcdt, mlp_gate, mlp_down):
    """A ``falcon_h1`` stack (TII Falcon-H1): every block runs a Mamba-2 mixer
    AND grouped-query attention side by side on ONE normed input, ``h = x +
    m_s SSM(m_si a) + m_a Attn(m_ai a)`` with ``a = RMSNorm(x)``, then ``y = h
    + MLP(RMSNorm(h))``, a SwiGLU whose gate and output are scaled; keys
    scaled before rotation (whole head, halves rotated), the embedding and
    the logits scaled, the Mamba-2 in-projection's output scaled by five
    constants over ``[z ; x ; B ; C ; dt]``; no bias but the convolution's,
    untied head. Unrolled (``layer_types``). Served only."""
    return TransformerConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, intermediate_size=ffn, max_seq_len=seq,
        pos_embedding="rope", rope_theta=theta, norm="rmsnorm", activation="swiglu",
        tie_embeddings=False, layernorm_epsilon=1e-5, attn_bias=False, mlp_bias=False,
        layer_types=("parallel_hybrid", ) * layers, ssm_state_size=ssm_state, ssm_conv_kernel=4,
        ssm_num_heads=ssm_heads, ssm_head_dim=ssm_head_dim, ssm_groups=ssm_groups,
        ssm_chunk_size=chunk, embedding_multiplier=embedding, lm_head_multiplier=lm_head,
        attention_in_multiplier=attn_in, attention_out_multiplier=attn_out, key_multiplier=key,
        ssm_in_multiplier=ssm_in, ssm_out_multiplier=ssm_out, ssm_multipliers=ssm_zxbcdt,
        mlp_gate_multiplier=mlp_gate, mlp_down_multiplier=mlp_down, scan_layers=False)


@register("falcon-h1-34b-instruct")
def falcon_h1_34b_instruct():
    """Falcon-H1-34B-Instruct at its published sizes (huggingface.co/tiiuae/
    Falcon-H1-34B-Instruct config.json, ``model_type: falcon_h1``): 72
    two-mixer blocks of hidden 5,120, 20 query and 4 key/value heads of 128
    (theta 1e11) beside 32 Mamba-2 heads of 128 with a state of 256 in 2
    groups (chunks of 128), a SwiGLU of 21,504, vocabulary 261,120 untied,
    the twelve published multipliers, 33.6 B parameters. ``num_layers`` is
    overridden together with ``layer_types``."""
    return _falcon_h1(5120, 72, 20, 4, 128, 21504, 32, 128, 256, 2, 128, 261120, 262144, 1e11,
                      embedding=5.656854249492381, lm_head=0.0078125, attn_in=1.0,
                      attn_out=0.0375, key=0.011048543456039804, ssm_in=0.25,
                      ssm_out=0.08838834764831845,
                      ssm_zxbcdt=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                                  0.3535533905932738),
                      mlp_gate=0.1767766952966369, mlp_down=0.011160714285714284)


@register("tiny-falcon-h1")
def tiny_falcon_h1():
    """Test-scale ``falcon_h1``: 4 two-mixer blocks, 5 query heads to ONE
    key/value head of 16 (the published odd group of five), 4 Mamba-2 heads
    of 8 with a state of 16 in 2 groups, chunks of 8, every multiplier off 1
    and no two alike, so that each one left out or misplaced shows."""
    return _falcon_h1(64, 4, 5, 1, 16, 128, 4, 8, 16, 2, 8, 256, 256, 1e4,
                      embedding=3.0, lm_head=0.5, attn_in=1.5, attn_out=0.6, key=0.4,
                      ssm_in=0.7, ssm_out=1.3, ssm_zxbcdt=(0.8, 1.2, 0.9, 1.1, 1.4),
                      mlp_gate=1.6, mlp_down=0.75)


def _exaone_moe(hidden, layers, heads, kv_heads, head_dim, dense_ffn, window, period, experts,
                top_k, expert_ffn, routed_scale, vocab, seq, theta=1e6, first_dense=1, mtp=1):
    """An ``exaone_moe`` stack (K-EXAONE): blocks ``h = x + RMSNorm(Attn(x))``,
    ``y = h + RMSNorm(FFN(h))`` (Exaone 4's residual form); of every
    ``period`` layers the last sees every key and takes NO positional term,
    the others see a ``window`` of keys, rotated; RMSNorm over each head of
    q and k; the first ``first_dense`` layers a dense SwiGLU, above them
    gated experts under a sigmoid router with a selection bias and one shared
    expert of the experts' width; untied head; a multi-token-prediction
    module behind the stack. Unrolled: the layers differ. Served only."""
    windows = tuple(0 if (i + 1) % period == 0 else window for i in range(layers))
    return TransformerConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, intermediate_size=dense_ffn, max_seq_len=seq,
        pos_embedding="rope", rope_theta=theta, rope_windowed_only=True, norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, layernorm_epsilon=1e-5, attn_bias=False,
        mlp_bias=False, layer_types=("full_attention", ) * layers, layer_windows=windows,
        post_norm=True, qk_norm=True, qk_norm_per_head=True, num_experts=experts,
        moe_top_k=top_k, moe_ffn_size=expert_ffn, moe_shared_experts=1,
        moe_routed_scale=routed_scale, moe_scoring="sigmoid", moe_dropless=True,
        moe_first_dense=first_dense, mtp_layers=mtp, scan_layers=False)


@register("k-exaone-236b-a23b")
def k_exaone_236b_a23b():
    """K-EXAONE-236B-A23B at its published sizes (huggingface.co/LGAI-EXAONE/
    K-EXAONE-236B-A23B config.json, ``model_type: exaone_moe``): 48 layers,
    three with a window of 128 keys to each full one, 64 query and 8
    key/value heads of 128, layer 0 a dense SwiGLU of 18,432, above it 128
    experts of 2,048 top-8 with a shared one, 236.6 B parameters in the stack
    and one multi-token-prediction module behind it. ``num_layers`` is
    overridden together with ``layer_windows``."""
    return _exaone_moe(6144, 48, 64, 8, 128, 18432, 128, 4, 128, 8, 2048, 2.5, 153600, 262144)


@register("tiny-exaone-moe")
def tiny_exaone_moe():
    """Test-scale ``exaone_moe``: a dense layer and one period of the layers
    above it (window, window, full, window as K-EXAONE's layers 1-4 fall), a
    window of 8 keys, 8 experts top-2, the module behind."""
    import dataclasses
    cfg = _exaone_moe(64, 5, 4, 2, 16, 128, 8, 4, 8, 2, 32, 2.5, 256, 256)
    return dataclasses.replace(cfg, layer_windows=(8, 8, 8, 0, 8))


# the published ``layer_types`` of LFM2-8B-A1B: three gated short convolutions
# to each attention layer, 18 : 6 (attention in layers 2, 6, 10, 14, 18, 21)
_LFM2_8B_LAYERS = "ccac" * 5 + "cacc"


def lfm2_layers(pattern):
    """``layer_types`` from a string of ``c`` (a gated short convolution) and
    ``a`` (attention), one letter a layer."""
    return tuple({"c": "short_conv", "a": "full_attention"}[ch] for ch in pattern)


def _lfm2_moe(hidden, pattern, heads, kv_heads, head_dim, dense_ffn, first_dense, experts, top_k,
              expert_ffn, conv_taps, vocab, seq, theta=1e6):
    """An ``lfm2_moe`` stack (LiquidAI LFM2-8B-A1B): pre-norm blocks ``h = x +
    Op(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``, ``Op`` a gated short
    convolution of ``conv_taps`` taps or grouped-query attention with RMSNorm
    over each head of q and k and rotary positions on every attention layer;
    the first ``first_dense`` layers a dense SwiGLU, above them gated experts
    under a sigmoid router with a selection bias, the chosen scores
    renormalised with the published 1e-6, NO shared expert; tied head.
    Unrolled: the layers differ. Served only."""
    return TransformerConfig(
        vocab_size=vocab, hidden_size=hidden, num_layers=len(pattern), num_heads=heads,
        num_kv_heads=kv_heads, head_dim=head_dim, intermediate_size=dense_ffn, max_seq_len=seq,
        pos_embedding="rope", rope_theta=theta, norm="rmsnorm", activation="swiglu",
        tie_embeddings=True, layernorm_epsilon=1e-5, attn_bias=False, mlp_bias=False,
        layer_types=lfm2_layers(pattern), short_conv_kernel=conv_taps, qk_norm=True,
        qk_norm_per_head=True, num_experts=experts, moe_top_k=top_k, moe_ffn_size=expert_ffn,
        moe_shared_experts=0, moe_routed_scale=1.0, moe_scoring="sigmoid",
        moe_renorm_eps=1e-6, moe_dropless=True, moe_first_dense=first_dense, scan_layers=False)


@register("lfm2-8b-a1b")
def lfm2_8b_a1b():
    """LFM2-8B-A1B at its published sizes (huggingface.co/LiquidAI/LFM2-8B-A1B
    config.json, ``model_type: lfm2_moe``): 24 layers, 18 gated short
    convolutions of 3 taps and 6 attention layers (32 query and 8 key/value
    heads of 64), layers 0 and 1 a dense SwiGLU of 7,168, above them 32
    experts of 1,792 top-4, vocabulary 65,536 tied, 8.34 B parameters, 1.56 B
    a token. ``num_layers`` is overridden together with ``layer_types``."""
    return _lfm2_moe(2048, _LFM2_8B_LAYERS, 32, 8, 64, 7168, 2, 32, 4, 1792, 3, 65536, 128000)


@register("tiny-lfm2-moe")
def tiny_lfm2_moe():
    """Test-scale ``lfm2_moe``: the published list's first six layers (conv,
    conv, attention, conv, conv, conv: two dense layers and one whole period
    of expert layers), head size 64 (so K and V rest packed beside the
    convolutions' windows, as at the published sizes), 8 experts top-2, 3
    taps."""
    return _lfm2_moe(256, _LFM2_8B_LAYERS[:6], 4, 2, 64, 256, 2, 8, 2, 64, 3, 256, 256)


def bailing_hybrid_layers(layers, group_size):
    """``layer_types`` by ``bailing_hybrid``'s rule: layer ``i`` is latent
    attention (``full_attention``) where ``(i + 1) % layer_group_size == 0``
    and Kimi delta attention (``linear_attention``) elsewhere. The rule is of
    the PUBLISHED depth: a cut states its kinds outright."""
    return tuple("full_attention" if (i + 1) % group_size == 0 else "linear_attention"
                 for i in range(layers))


def _bailing_hybrid(hidden, layers, heads, lin_dk, kv_rank, nope, rope, v_dim, dense_ffn,
                    first_dense, experts, groups, groups_kept, top_k, expert_ffn, routed_scale,
                    vocab, seq, theta, group_size=6, mtp=1, limits=(), shared_limits=(),
                    **overrides):
    """A ``bailing_hybrid`` stack (inclusionAI Ling-3.0): pre-norm blocks ``h
    = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; of every
    ``group_size`` layers the last is latent attention with ONE query
    projection (no low-rank step) and a head-wise sigmoid output gate, the
    others Kimi delta attention (the gated delta rule with a decay a key
    channel from a full-rank projection, bounded below by -5, sigmoid output
    gate, as many key as value heads of ``lin_dk``); the first
    ``first_dense`` layers a dense SwiGLU, above them gated experts under a
    sigmoid router with a selection bias, chosen inside ``groups_kept`` of
    ``groups`` groups, and one shared expert of the experts' width; untied
    head; a multi-token-prediction module behind the stack and a clamp on
    the top layers' gated activations, both published and NOT served
    (``TransformerConfig`` refuses them by name: cut them off with
    ``overrides``, which are applied before anything is checked). Unrolled:
    the layers differ. Served only."""
    kw = dict(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers, num_heads=heads,
        head_dim=nope + rope, intermediate_size=dense_ffn, max_seq_len=seq,
        pos_embedding="rope", rope_theta=theta, rope_interleave=True, norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, layernorm_epsilon=1e-6, attn_bias=False,
        mlp_bias=False, kv_lora_rank=kv_rank, q_lora_rank=0, qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=v_dim, attn_head_gate=True,
        layer_types=bailing_hybrid_layers(layers, group_size), linear_num_heads=heads,
        linear_key_head_dim=lin_dk, linear_value_head_dim=lin_dk, linear_conv_kernel=4,
        linear_channel_decay=True, linear_decay_lower_bound=-5.0, linear_out_gate="sigmoid",
        num_experts=experts, moe_top_k=top_k, moe_ffn_size=expert_ffn, moe_shared_experts=1,
        moe_shared_ffn_size=expert_ffn, moe_routed_scale=routed_scale, moe_scoring="sigmoid",
        moe_n_group=groups, moe_topk_group=groups_kept, moe_dropless=True,
        moe_first_dense=first_dense, mtp_layers=mtp, moe_swiglu_limits=tuple(limits),
        moe_shared_swiglu_limits=tuple(shared_limits), scan_layers=False)
    return TransformerConfig(**{**kw, **overrides})


@register("ling-3.0-flash", overrides_first=True)
def ling_3_flash(**overrides):
    """Ling-3.0-flash at its published sizes (huggingface.co/inclusionAI/
    Ling-3.0-flash config.json, ``model_type: bailing_hybrid``): 42 layers,
    five Kimi-delta layers (32 heads, keys and values of 128, convolution of
    4) to each latent-attention layer (32 heads, latent 512 + 64 rotated,
    nope 128, value 128), layers 0 and 1 a dense SwiGLU of 6,144, above them
    512 experts of 768 top-8 inside 4 of 8 groups with a shared one, scale
    2.5, vocabulary 157,184 untied, ~125 B parameters. WHOLE it is refused:
    layers 34-41 clamp their gated activations
    (``share_expert_swiglu_limit_list`` from 34, ``expert_swiglu_limit_list``
    from 35) at a limit published by value and not by form, and the
    multi-token-prediction module cannot draft over a pool of latent rows
    and recurrent state. A cut below layer 34 with ``mtp_layers=0``
    (``chipbench/configs/ling-3.0-flash.json``) is served."""
    return _bailing_hybrid(2560, 42, 32, 128, 512, 128, 64, 128, 6144, 2, 512, 8, 4, 8, 768,
                           2.5, 157184, 262144, 6e6, limits=(0, ) * 35 + (4, ) * 7,
                           shared_limits=(0, ) * 34 + (5, ) * 6 + (7, ) * 2, **overrides)


@register("tiny-ling", overrides_first=True)
def tiny_ling(**overrides):
    """Test-scale ``bailing_hybrid``: 7 layers (KDA, KDA, latent, KDA, KDA,
    KDA, latent: both kinds, a period of 3 so that a latent layer lies among
    the expert layers twice), one leading dense layer, 16 experts in 4
    groups, 2 groups kept, top-4, 4 heads of 16, nothing refused."""
    kw = dict(layer_types=("linear_attention", "linear_attention", "full_attention",
                           "linear_attention", "linear_attention", "linear_attention",
                           "full_attention"))
    return _bailing_hybrid(64, 7, 4, 16, 16, 8, 8, 16, 128, 1, 16, 4, 2, 4, 32, 2.5, 256, 256,
                           1e4, mtp=0, **{**kw, **overrides})
