"""Decoder-only transformer family.

The reference ships no trainable model zoo of its own (it wraps user torch
modules; its model surface is the inference injection containers,
``module_inject/containers/*`` — bert/bloom/gpt2/gptj/gptneox/megatron/opt).
A standalone TPU framework needs first-party models, so this module provides
one configurable causal-LM covering the reference's model families:

- GPT-2 / OPT style: learned positions, LayerNorm, gelu/relu MLP
- Llama style: RoPE, RMSNorm, SwiGLU, grouped-query attention
- Mixtral style: + top-k routed MoE MLP (see ``deepspeed_tpu.moe``)

TPU-first choices: layers are stacked with ``nn.scan`` (one compiled block,
weights get a leading layer dim — compile time stays flat in depth);
activations default bf16 with fp32 LayerNorm/softmax accumulations; remat via
``jax.checkpoint`` policies; attention pluggable between a pure-XLA einsum
path and the Pallas flash kernel (``ops.pallas.flash_attention``).
"""

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..comm import comm as dist
from ..runtime.zero.gather_order import GatherOrder


# the SambaY kinds (arXiv:2507.06607): a selective state-space layer, a
# differential-attention layer (arXiv:2410.05258; windowed or full by
# ``layer_windows``), a gated memory unit over the nearest SSM output below
# it, and a differential cross-attention over the nearest full differential
# layer's K/V rows below it
SAMBAY_TYPES = ("mamba", "diff_attention", "gmu", "cross_attention")
# the kinds whose block is ONE sublayer, ``x + f(norm(x))`` (``nemotron_h``):
# a Mamba-2 mixer, attention alone, an expert FFN alone, a dense FFN alone.
# Each maps to (mixer, FFN), one of them absent
ONE_SUBLAYER_TYPES = {"mamba2": ("mamba2", None), "attention": ("full_attention", None),
                      "moe": (None, "moe"), "mlp": (None, "mlp")}
# "short_conv" (``lfm2``): a gated short convolution in attention's place of
# a mixer-and-FFN block (:class:`ShortConv`).
# "parallel_hybrid" (``falcon_h1``): a block of TWO mixers and an FFN, grouped-
# query attention and a Mamba-2 mixer side by side on ONE normed input, each
# scaled by a published constant and summed into the residual. Its slot holds
# both mixers' leaves, in this order
PARALLEL_HYBRID_MIXERS = ("full_attention", "mamba2")
LAYER_TYPES = (("full_attention", "linear_attention", "short_conv", "parallel_hybrid")
               + SAMBAY_TYPES + tuple(ONE_SUBLAYER_TYPES))


def sambay_layers(num_layers, mb_per_layer, sliding_window):
    """``(layer_types, layer_windows)`` of a decoder-hybrid-decoder stack
    (``phi4flash``), from the three numbers its config gives: every
    ``mb_per_layer``-th layer from 0 is a Mamba layer and the ones between
    attend. In the first half the attention layers see a sliding window; the
    second half opens with one more Mamba layer and ONE full-attention
    layer, and after them Mamba's places hold gated memory units over that
    Mamba layer's output and attention's places cross-attend the full
    layer's keys and values."""
    half = num_layers // 2
    types, windows = [], []
    for i in range(num_layers):
        ssm = i % mb_per_layer == 0
        if i >= half + 2:
            types.append("gmu" if ssm else "cross_attention")
        else:
            types.append("mamba" if ssm else "diff_attention")
        windows.append(sliding_window if types[-1] == "diff_attention" and i < half else 0)
    return tuple(types), tuple(windows)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: Optional[int] = None  # default 4x (or 8/3 x for swiglu)
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # GQA; None = MHA
    head_dim: Optional[int] = None
    max_seq_len: int = 1024
    # family switches
    pos_embedding: str = "rope"  # "rope" | "learned" | "none" | "alibi"
    norm: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    # "swiglu" | "gelu" (tanh) | "gelu_exact" (erf) | "relu" | "geglu" | "relu2" (relu squared)
    activation: str = "swiglu"
    tie_embeddings: bool = True
    rope_theta: float = 10000.0
    rotary_dim: Optional[int] = None  # partial rotary (GPT-J/NeoX); None = full head
    parallel_residual: bool = False  # x + attn(n1(x)) + mlp(n2(x)) (GPT-J/NeoX)
    embed_norm: bool = False  # layernorm right after the embedding (BLOOM)
    lm_head_bias: bool = False  # untied lm_head with bias (GPT-J)
    attn_bias: Optional[bool] = None  # None = follow norm (layernorm -> biased); GPT-J: False
    # QAT activation quantization (compression.activation_quantization):
    # fake-quantize each block's input with a straight-through gradient
    act_quant_bits: Optional[int] = None
    act_quant_symmetric: bool = True
    # attention-score scale: None = 1/sqrt(head_size); GPT-Neo uses 1.0
    # (HF GPTNeoSelfAttention applies no scaling)
    attn_scale: Optional[float] = None
    # GPT-Neo alternating local attention: layers listed in
    # local_attention_layers see a sliding window of local_attention_window
    # keys (reference containers/gptneo.py; HF attention_types)
    local_attention_window: int = 0
    local_attention_layers: Tuple[int, ...] = ()
    layernorm_epsilon: float = 1e-5
    dropout: float = 0.0
    # MoE (0 experts = dense)
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coef: float = 0.01
    # Megatron-style biased expert FFNs. EXPLICIT on purpose (ADVICE r5):
    # inferring from norm == 'layernorm' silently changed the param tree of
    # every layernorm MoE model. Megatron-DeepSpeed MoE checkpoints carry
    # expert biases — set True when loading them (MegatronPolicy.convert
    # enforces it); HF Mixtral-family experts are bias-less (default).
    moe_expert_bias: bool = False
    # experts this layer HOLDS: the contiguous ids [moe_first_expert,
    # moe_first_expert + moe_experts_held) of the num_experts the router
    # scores (None = all). One chip's share of an expert-parallel deployment:
    # the router keeps its width and top-k, the layer computes its own
    # experts' part of the result and leaves out what absent experts would add
    moe_experts_held: Optional[int] = None
    moe_first_expert: int = 0
    moe_ffn_size: Optional[int] = None  # expert width; None = ffn_size
    moe_shared_experts: int = 0  # always-on experts of width moe_ffn_size, computed once
    # the shared experts' joint width where it is published apart from the
    # routed experts' (moe_shared_expert_intermediate_size); None =
    # moe_shared_experts x the routed width
    moe_shared_ffn_size: Optional[int] = None
    moe_routed_scale: float = 1.0  # routed_scaling_factor on the renormalised top-k weights
    # what a sigmoid router adds to the chosen scores' sum before it divides by
    # it (DeepSeek-V3's code 1e-20; ``lfm2_moe`` publishes 1e-6)
    moe_renorm_eps: float = 1e-20
    # a sigmoid router's group limit (n_group / topk_group): the experts lie
    # in moe_n_group groups of equal size, a group's score is the sum of its
    # two largest s + bias, and a token chooses its k among the experts of
    # its moe_topk_group best groups. 1 group = no limit
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # per layer, the limit of the routed and of the shared experts' clamped
    # gated activation (expert_swiglu_limit_list, share_expert_swiglu_limit_list;
    # 0 = none). Published by VALUE and not by form: a layer with a non-zero
    # limit is refused, not guessed. () = none anywhere
    moe_swiglu_limits: Tuple[float, ...] = ()
    moe_shared_swiglu_limits: Tuple[float, ...] = ()
    # how the router scores: "softmax" (probabilities, top-k, renormalised),
    # or "sigmoid" (DeepSeek-V3's rule: s = sigmoid(logits); the k experts
    # are the top of s + a stored selection bias, their weights s itself,
    # renormalised). Serving dispatch only
    moe_scoring: str = "softmax"
    # no capacity buffers anywhere: the full (no-cache) forward routes per
    # token like serving does and reports no aux loss (serving-only presets)
    moe_dropless: bool = False
    # latent attention (MLA, kv_lora_rank > 0): low-rank q and kv projections
    # with their RMSNorms, per-head [nope ; rope] query/key parts, ONE rotated
    # key part for all heads, and a cache of kv_lora_rank + qk_rope_head_dim
    # values a position (see LatentAttention). q_lora_rank 0 = the query is ONE
    # projection, no low-rank step (``bailing_hybrid``). Under layer_types the
    # full_attention layers are the latent ones
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False  # rotate dims (2j, 2j+1) together, not (j, j + d/2)
    # a latent layer's output gated a HEAD before W_o: o_i * sigmoid(a W_gate)_i, W_gate
    # hidden -> heads (gated_attention_proj_granularity_type: head_wise)
    attn_head_gate: bool = False
    # YaRN frequencies (rope_factor > 1) and the position-dependent query scale
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_max_len: int = 0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    attn_temp_beta: float = 0.0  # g(t) = 1 + beta * ln(1 + floor(t / rope_original_max_len))
    # per-layer blocks: one of LAYER_TYPES for each layer ("full_attention":
    # Attention; "linear_attention": the gated delta rule, GatedDeltaNet,
    # which holds a per-slot recurrent state and convolution window and no
    # cache rows; "short_conv": ShortConv, which holds the last
    # short_conv_kernel - 1 inputs of its convolution per slot and no cache
    # rows; SAMBAY_TYPES: Mamba, DiffAttention, GatedMemoryUnit and
    # DiffAttention(cross), which hold state, rows or a ring of rows, nothing,
    # and nothing; ONE_SUBLAYER_TYPES: a block of ONE sublayer, a Mamba-2
    # mixer, attention, an expert FFN or a dense FFN alone, which hold state,
    # rows, nothing and nothing; "parallel_hybrid": Attention AND Mamba2 on one
    # normed input, then a dense FFN, which holds rows and state in ONE
    # layer). () = every layer full attention. Needs unrolled layers
    layer_types: Tuple[str, ...] = ()
    # keys a layer's query sees, its own included (0 = all), for the kinds whose
    # mixer attends over rows of its own: diff_attention and full_attention
    # (Exaone's ``sliding_attention`` is a full_attention layer with a window).
    # Such a layer's slot holds a ring of about that many rows, its keys
    # rotated at rest where the layer rotates. () = none
    layer_windows: Tuple[int, ...] = ()
    # a mamba layer (Mamba-1): d_inner = ssm_expand * hidden_size
    ssm_state_size: int = 0
    ssm_conv_kernel: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0
    # a mamba2 layer (models/mamba2.py): ssm_num_heads heads of ssm_head_dim
    # channels (d_inner is their product, NOT ssm_expand x hidden), a state of
    # ssm_head_dim x ssm_state_size a head, B and C shared by ssm_groups
    # groups of heads, the chunked matrix form in chunks of ssm_chunk_size
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_chunk_size: int = 128
    # the constants a ``falcon_h1`` config publishes (maximal-update
    # parametrisation), each applied to the ACTIVATION where the published
    # forward applies it, in float32 and rounded once to the serving dtype: on
    # the embedding's output and the logits; on the attention branch's input,
    # its keys before rotation and its output; on the Mamba-2 branch's input
    # and output; on the in-projection's output over [z ; x ; B ; C ; dt]
    # before the convolution (ssm_multipliers: five, in that order; () = none);
    # on the MLP's gate before SiLU and its down-projection's output. 1 = not
    # applied: the program is the one without it. They go with
    # parallel_hybrid layers
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = ()
    mlp_gate_multiplier: float = 1.0
    mlp_down_multiplier: float = 1.0
    mlp_bias: Optional[bool] = None  # None = follow norm (layernorm -> biased)
    linear_num_heads: int = 0  # key heads = value heads of a linear layer
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4  # causal depthwise convolution over q, k, v
    linear_neg_eigval: bool = False  # beta in (0, 2): negative eigenvalues of the transition
    # the decay of a linear layer's state: one value a HEAD, g = -exp(A_log)
    # softplus(x W_a + dt_bias) (Gated DeltaNet), or with linear_channel_decay
    # one a KEY CHANNEL from a full-rank projection (Kimi delta attention,
    # arXiv:2510.26692: W_a hidden -> heads x dk, dt_bias one a channel, A_log
    # one a head). linear_decay_lower_bound < 0 bounds the log decay:
    # g = bound * sigmoid(exp(A_log) (x W_a + dt_bias)), in (bound, 0)
    # (kda_safe_gate, kda_lower_bound); 0 = the softplus form
    linear_channel_decay: bool = False
    linear_decay_lower_bound: float = 0.0
    linear_out_gate: str = "silu"  # the output gate's activation: "silu" | "sigmoid" (KDA)
    # a short_conv layer: taps of its causal depthwise convolution (conv_L_cache)
    short_conv_kernel: int = 0
    # h = x + norm(mixer(x)), y = h + norm(ffn(h)): mixer and FFN (dense or
    # routed experts) read the residual stream itself and their OUTPUTS are
    # normalised (Olmo 2/3, Exaone 4)
    post_norm: bool = False
    # RMSNorm on q and k: over the whole projection before the split into
    # heads (Olmo), or with qk_norm_per_head over the head_size values of
    # ONE head, one weight vector shared by the heads (Exaone 4, Qwen 3)
    qk_norm: bool = False
    qk_norm_per_head: bool = False
    # rotary positions on the layers with a window only: a layer that sees
    # every key takes no positional term (Exaone 4's hybrid stacks)
    rope_windowed_only: bool = False
    # the first layers' FFN is a dense MLP of intermediate_size and the
    # experts start above them (first_k_dense_replace)
    moe_first_dense: int = 0
    # multi-token-prediction modules behind the stack (num_nextn_predict_layers;
    # models/mtp.py): one block over [norm(embed(next token)) ; norm(hidden)],
    # with the model's embedding and head. Serving drafts with it on the device
    mtp_layers: int = 0
    # systems
    dtype: Any = jnp.bfloat16
    scan_layers: bool = True
    remat_policy: Optional[str] = None  # None | "nothing_saveable" | "dots_saveable" | ...
    # chunked cross-entropy: None = auto (on when vocab_size >= 4096 — the
    # fp32 (B,T,V) logits buffer only dominates HBM at real vocab sizes);
    # 0 = always dense logits; N = chunk rows of N
    ce_chunk_size: Optional[int] = None
    attention_impl: str = "xla"  # "xla" | "flash"
    # under sequence_parallel_size > 1: "ulysses" re-shards heads (all-to-all,
    # full sequence per head on-chip); "ring" keeps O(T/n) per chip and
    # rotates KV over ICI (ops/pallas/ring_attention; requires flash + causal)
    sequence_parallel_impl: str = "ulysses"  # "ulysses" | "ring"
    attention_block_q: int = 512
    attention_block_kv: int = 512
    decode_block_kv: int = 256  # KV block per decode-kernel step
    # int8 weight serving (reference csrc int8 dequant-GEMM inference path):
    # projections read int8 weights + per-group scales through the Pallas
    # quant matmul — halves the HBM bytes of the memory-bound decode loop.
    # Serving-only: params must come from CausalLMModel.quantize_params.
    int8_weights: bool = False
    int8_group_size: int = 0  # 0 = one scale group per contraction dim
    # fuse q/k/v into ONE int8 matmul (fewer, larger Pallas calls — the
    # decode loop is per-call-overhead-sensitive). tp=1 serving only: the
    # fused N axis concatenates [q;k;v] so a plain column shard would split
    # across component boundaries. The engine enables it when tp==1.
    int8_fused_qkv: bool = False
    # bitwise tensor-parallel SERVING layout (the inference engine sets this
    # when the mesh's ``tensor`` axis > 1): only column-parallel projections
    # shard (qkv/up/gate on their output-head/ffn axes, the vocab head on
    # vocab) and activations re-replicate before every row-parallel
    # (contraction-split) matmul (o_proj/down_proj stay replicated). Every
    # cross-shard transfer is then an all-gather — pure concatenation, never
    # a partial-sum reduction — so tp>1 logits are BIT-IDENTICAL to tp=1.
    # The price is that o/down weight reads don't scale with tp; the wins
    # that matter for decode (KV cache HBM, attention, qkv/up/head reads)
    # do. Training never sets this (training shards row-parallel too and
    # tolerates reduction-order noise; serving's contract is bit-identity).
    bitwise_tp: bool = False

    def __post_init__(self):
        if self.attention_impl not in ("xla", "flash"):
            raise ValueError(f"attention_impl must be 'xla' or 'flash', got {self.attention_impl!r}")
        if self.pos_embedding not in ("rope", "learned", "none", "alibi"):
            raise ValueError(f"pos_embedding must be 'rope'/'learned'/'none'/'alibi', "
                             f"got {self.pos_embedding!r}")
        if self.sequence_parallel_impl not in ("ulysses", "ring"):
            raise ValueError(f"sequence_parallel_impl must be 'ulysses' or 'ring', "
                             f"got {self.sequence_parallel_impl!r}")
        if self.sequence_parallel_impl == "ring" and self.attention_impl != "flash":
            raise ValueError("sequence_parallel_impl='ring' requires attention_impl='flash'")
        if self.local_attention_layers and self.scan_layers:
            raise ValueError("local_attention_layers (per-layer windows) requires "
                             "scan_layers=False — scanned layers share one program")
        if self.layer_types:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))  # a JSON list
            unknown = sorted(set(self.layer_types) - set(LAYER_TYPES))
            if unknown or len(self.layer_types) != self.num_layers:
                raise ValueError(f"layer_types names one of {LAYER_TYPES} for each of the "
                                 f"{self.num_layers} layers, got {len(self.layer_types)} "
                                 f"entries" + (f" with unknown {unknown}" if unknown else ""))
            if self.scan_layers:
                raise ValueError("layer_types (per-layer mixers) requires scan_layers=False "
                                 "— scanned layers share one program")
            if "linear_attention" in self.layer_types and not (
                    self.linear_num_heads and self.linear_key_head_dim
                    and self.linear_value_head_dim and self.linear_conv_kernel > 1):
                raise ValueError("linear_attention layers need linear_num_heads, "
                                 "linear_key_head_dim, linear_value_head_dim and a "
                                 "convolution of width > 1")
            if "short_conv" in self.layer_types and self.short_conv_kernel < 2:
                raise ValueError("short_conv layers need short_conv_kernel (the taps of their "
                                 "convolution) of 2 or more")
            sambay = set(self.layer_types) & set(SAMBAY_TYPES)
            object.__setattr__(self, "layer_windows", tuple(self.layer_windows))
            if self.layer_windows and (len(self.layer_windows) != self.num_layers or any(
                    w and t not in ("diff_attention", "full_attention")
                    for w, t in zip(self.layer_windows, self.layer_types))):
                raise ValueError("layer_windows gives a window (0 = none) for each layer, and "
                                 "only a diff_attention or full_attention layer takes one")
            if any(self.layer_windows) and (self.local_attention_layers
                                            or self.attn_scale is not None
                                            or self.pos_embedding == "alibi"):
                raise ValueError("a windowed layer's ring composes with rope or no positions "
                                 "and the default score scale only (no local_attention_layers, "
                                 "attn_scale or alibi)")
            if sambay:
                if set(self.layer_types) - set(SAMBAY_TYPES):
                    raise ValueError(f"the kinds {SAMBAY_TYPES} carry values from layer to "
                                     f"layer and do not mix with the others")
                if {"mamba", "gmu"} & sambay and not (
                        self.ssm_state_size and self.ssm_dt_rank and self.ssm_conv_kernel > 1):
                    raise ValueError("mamba and gmu layers need ssm_state_size, ssm_dt_rank "
                                     "and a convolution of width > 1")
                if self.pos_embedding != "none" or self.post_norm or self.qk_norm \
                        or self.num_heads % 2 or self.kv_heads % 2 \
                        or (self.num_heads // 2) % (self.kv_heads // 2):
                    raise ValueError("differential attention pairs heads (even counts of "
                                     "query and key/value heads, whole groups of pairs) and "
                                     "composes with pos_embedding='none', pre-norm blocks "
                                     "and no qk_norm only")
                seen = set()
                for i, t in enumerate(self.layer_types):
                    needs = {"gmu": "mamba", "cross_attention": "full"}.get(t)
                    if needs and needs not in seen:
                        raise ValueError(f"layer {i} ({t}) has no {needs} layer below it "
                                         f"to read")
                    seen.add("full" if t == "diff_attention" and not self.layer_window(i)
                             else t)
            one = set(self.layer_types) & set(ONE_SUBLAYER_TYPES)
            if one:
                if set(self.layer_types) - set(ONE_SUBLAYER_TYPES):
                    raise ValueError(f"the one-sublayer kinds {tuple(ONE_SUBLAYER_TYPES)} do "
                                     f"not mix with kinds whose block is a mixer and an FFN")
                if ("moe" in one) != bool(self.num_experts):
                    raise ValueError("moe layers need num_experts, and experts under "
                                     "layer_types need moe layers to live in")
                if self.post_norm or self.dropout > 0:
                    raise ValueError("a one-sublayer block is x + f(norm(x)): no post_norm, "
                                     "no dropout")
            two = "parallel_hybrid" in self.layer_types
            if two and (set(self.layer_types) != {"parallel_hybrid"} or any(self.layer_windows)
                        or self.post_norm or self.num_experts or self.kv_lora_rank
                        or self.dropout > 0 or self.pos_embedding not in ("rope", "none")):
                raise ValueError("a parallel_hybrid block is x + m_s SSM(norm(x)) + m_a "
                                 "Attn(norm(x)), then a dense FFN: every layer of the stack is "
                                 "one, and it composes with no other kind, layer_windows, "
                                 "post_norm, experts beside it, latent attention, dropout, "
                                 "learned positions or alibi")
            if ("mamba2" in one or two) and not (
                    self.ssm_num_heads and self.ssm_head_dim and self.ssm_state_size
                    and self.ssm_conv_kernel > 1 and self.ssm_chunk_size > 0
                    and self.ssm_groups > 0 and self.ssm_num_heads % self.ssm_groups == 0):
                raise ValueError("mamba2 and parallel_hybrid layers need ssm_num_heads (a "
                                 "multiple of ssm_groups), ssm_head_dim, ssm_state_size and a "
                                 "convolution of width > 1")
            experts_beside = self.num_experts and not one
            if experts_beside and (sambay or not self.moe_dropless):
                raise ValueError("experts in a mixer-and-FFN block under layer_types go with "
                                 "full_attention, linear_attention and short_conv layers and "
                                 "the dropless dispatch only (elsewhere they live in "
                                 "one-sublayer moe layers)")
            if self.parallel_residual or self.int8_weights:
                raise ValueError("layer_types composes with a float dtype and sequential "
                                 "residuals only (no parallel residual or int8 weights)")
            if self.kv_lora_rank and (sambay or one or any(self.layer_windows)
                                      or self.post_norm or self.qk_norm):
                raise ValueError("latent attention under layer_types is the full_attention "
                                 "layer of a pre-norm mixer-and-FFN stack: no SambaY or "
                                 "one-sublayer kinds, windows, post_norm or qk_norm")
        elif self.rope_windowed_only:
            raise ValueError("rope_windowed_only goes by layer_windows, which need layer_types")
        object.__setattr__(self, "ssm_multipliers", tuple(self.ssm_multipliers))  # a JSON list
        if len(self.ssm_multipliers) not in (0, 5):
            raise ValueError("ssm_multipliers scales the Mamba-2 in-projection's output over "
                             "[z ; x ; B ; C ; dt]: five constants, or none")
        if self.has_multipliers and "parallel_hybrid" not in self.layer_types:
            raise ValueError("the published multipliers (embedding, lm_head, attention in / "
                             "out, key, ssm in / out, ssm_multipliers, mlp gate / down) are "
                             "applied by a stack of parallel_hybrid layers, served")
        if self.post_norm and (self.parallel_residual or self.dropout > 0
                               or (self.num_experts and not self.moe_dropless)):
            raise ValueError("post_norm composes with sequential residuals, no dropout and, "
                             "for experts, the dropless dispatch only")
        if self.qk_norm_per_head and not self.qk_norm:
            raise ValueError("qk_norm_per_head says which qk_norm: set qk_norm too")
        if self.moe_first_dense and not (self.num_experts and not self.scan_layers
                                         and 0 < self.moe_first_dense < self.num_layers
                                         and not set(self.layer_types) & set(ONE_SUBLAYER_TYPES)):
            raise ValueError("moe_first_dense puts a dense MLP in the first layers of a stack "
                             "of mixer-and-expert blocks: it needs num_experts, unrolled "
                             "layers and at least one expert layer above")
        for name in ("moe_swiglu_limits", "moe_shared_swiglu_limits"):
            limits = tuple(getattr(self, name))
            object.__setattr__(self, name, limits)
            if limits and len(limits) != self.num_layers:
                raise ValueError(f"{name} gives a limit (0 = none) for each of the "
                                 f"{self.num_layers} layers, got {len(limits)}")
            clamped = [i for i, x in enumerate(limits) if x]
            if clamped:
                raise ValueError(
                    f"{name}: layers {clamped[0]}-{clamped[-1]} clamp their gated activation "
                    f"(expert_swiglu_limit_list / share_expert_swiglu_limit_list) at a limit "
                    f"the configuration publishes by value and not by form: not served. Cut "
                    f"the depth below layer {clamped[0]} (num_layers with layer_types and "
                    f"both lists)")
        if self.mtp_layers not in (0, 1):
            raise ValueError("one multi-token-prediction module at most (mtp_layers 0 or 1)")
        if self.mtp_layers and (self.scan_layers or self.kv_lora_rank or self.int8_weights
                                or self.carries_across_layers
                                or set(self.layer_types) & ({"short_conv", "parallel_hybrid"}
                                                            | set(ONE_SUBLAYER_TYPES))):
            raise ValueError("the multi-token-prediction module (mtp_layers: "
                             "num_nextn_predict_layers) is a block of attention and an "
                             "FFN behind an unrolled stack of such blocks (no latent "
                             "attention, int8 weights, SambaY, parallel_hybrid, short_conv "
                             "or one-sublayer kinds)")
        if self.kv_lora_rank and not (self.qk_nope_head_dim and self.qk_rope_head_dim
                                      and self.v_head_dim and self.pos_embedding == "rope"):
            raise ValueError("latent attention (kv_lora_rank > 0) needs qk_nope_head_dim, "
                             "qk_rope_head_dim, v_head_dim and rope positions")
        if self.attn_head_gate and not self.kv_lora_rank:
            raise ValueError("attn_head_gate is the latent layers' head-wise output gate: it "
                             "needs kv_lora_rank")
        if self.linear_out_gate not in ("silu", "sigmoid"):
            raise ValueError(f"linear_out_gate must be 'silu' or 'sigmoid', got "
                             f"{self.linear_out_gate!r}")
        if self.linear_decay_lower_bound > 0:
            raise ValueError("linear_decay_lower_bound bounds a LOG decay from below: 0 (the "
                             "softplus form) or negative")
        if self.moe_n_group < 1 or not 1 <= self.moe_topk_group <= self.moe_n_group:
            raise ValueError("moe_topk_group of moe_n_group groups: 1 <= kept <= groups")
        if self.moe_n_group > 1:
            size = self.num_experts // self.moe_n_group if self.num_experts else 0
            if (self.moe_scoring != "sigmoid" or not size or self.num_experts % self.moe_n_group
                    or size < 2 or self.moe_topk_group * size < self.moe_top_k
                    or self.moe_first_expert % size or self.experts_held % size):
                raise ValueError("a group-limited router (moe_n_group > 1) is the sigmoid "
                                 "router's: groups of equal size (2 or more experts), the kept "
                                 "groups hold at least top-k experts, and a layer holds WHOLE "
                                 "groups (moe_first_expert and moe_experts_held multiples of "
                                 "the group's size)")
        if (self.rope_factor > 1 or self.attn_temp_beta) and self.rope_original_max_len <= 0:
            raise ValueError("YaRN frequencies and the position-dependent query scale "
                             "need rope_original_max_len")
        if self.num_experts and not (
                0 <= self.moe_first_expert
                and self.moe_first_expert + self.experts_held <= self.num_experts):
            raise ValueError(f"held experts [{self.moe_first_expert}, "
                             f"{self.moe_first_expert + self.experts_held}) lie outside the "
                             f"router's {self.num_experts}")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_scoring must be 'softmax' or 'sigmoid', got "
                             f"{self.moe_scoring!r}")
        if self.num_experts and self.moe_scoring == "sigmoid" and not self.moe_dropless:
            raise ValueError("the sigmoid router with a selection bias has no "
                             "capacity-buffered path: set moe_dropless")
        if self.experts_held != self.num_experts and not self.moe_dropless:
            raise ValueError("a layer that holds a share of the experts has no "
                             "capacity-buffered path: set moe_dropless")
        if self.attention_impl == "flash":
            import importlib.util
            if importlib.util.find_spec("deepspeed_tpu.ops.pallas.flash_attention") is None:
                raise NotImplementedError(
                    "attention_impl='flash' requires the Pallas kernel "
                    "(deepspeed_tpu.ops.pallas.flash_attention); use attention_impl='xla'")

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def head_size(self):
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def ffn_size(self):
        if self.intermediate_size is not None:
            return self.intermediate_size
        if self.activation in ("swiglu", "geglu"):
            # llama convention: 8/3 * hidden rounded to multiple of 256
            d = int(8 * self.hidden_size / 3)
            return (d + 255) // 256 * 256
        return 4 * self.hidden_size

    @property
    def experts_held(self):
        return self.num_experts if self.moe_experts_held is None else self.moe_experts_held

    @property
    def expert_ffn_size(self):
        return self.moe_ffn_size or self.ffn_size

    def layer_type(self, layer_idx):
        """The mixer of layer ``layer_idx`` (one of :data:`LAYER_TYPES`)."""
        return self.layer_types[layer_idx] if self.layer_types else "full_attention"

    def layer_parts(self, layer_idx):
        """``(mixer, FFN)`` of layer ``layer_idx``'s block, either None where
        the block has no such half (:data:`ONE_SUBLAYER_TYPES`). The mixer is
        the kind's own name for the two-sublayer kinds; the FFN is ``"moe"``
        or ``"mlp"``."""
        kind = self.layer_type(layer_idx)
        # (a scanned stack's layers carry no index, and no leading dense layer)
        sparse = self.num_experts and not 0 <= layer_idx < self.moe_first_dense
        return ONE_SUBLAYER_TYPES.get(kind, (kind, "moe" if sparse else "mlp"))

    def layer_mixers(self, layer_idx):
        """The mixers layer ``layer_idx``'s block runs, in the order its slot
        holds their leaves: none (an FFN alone), :meth:`layer_parts`' one, or
        a ``parallel_hybrid`` block's two."""
        mixer = self.layer_parts(layer_idx)[0]
        if mixer == "parallel_hybrid":
            return PARALLEL_HYBRID_MIXERS
        return () if mixer is None else (mixer, )

    @property
    def has_multipliers(self):
        """Whether any published multiplier is other than 1."""
        return bool(self.ssm_multipliers) or any(m != 1.0 for m in (
            self.embedding_multiplier, self.lm_head_multiplier, self.attention_in_multiplier,
            self.attention_out_multiplier, self.key_multiplier, self.ssm_in_multiplier,
            self.ssm_out_multiplier, self.mlp_gate_multiplier, self.mlp_down_multiplier))

    def layer_rotates(self, layer_idx):
        """Whether layer ``layer_idx``'s attention rotates its queries and
        keys by position (``pos_embedding == "rope"``; with
        ``rope_windowed_only`` the layers with a window alone)."""
        return self.pos_embedding == "rope" and (
            not self.rope_windowed_only or bool(self.layer_window(layer_idx)))

    def layer_window(self, layer_idx):
        """Keys layer ``layer_idx``'s queries see, their own included (0 = all)."""
        return self.layer_windows[layer_idx] if self.layer_windows else 0

    def ring_rows(self, layer_idx):
        """Rows of the ring a windowed layer's slot holds: its window, rounded
        up to the decode kernel's KV block (to 8 rows under one block). The
        paged decode kernel masks a ring by a row count alone, so it serves
        the ring whose rows ARE the window (512 = 2 blocks)."""
        w = self.layer_window(layer_idx)
        blk = self.decode_block_kv if w >= self.decode_block_kv else 8
        return -(-w // blk) * blk

    @property
    def ssm_inner(self):
        """Channels of a Mamba layer's state-space model (``d_inner``)."""
        return self.ssm_expand * self.hidden_size

    @property
    def mamba2_inner(self):
        """Channels of a Mamba-2 layer (``d_inner``): heads x head size."""
        return self.ssm_num_heads * self.ssm_head_dim

    @property
    def mamba2_conv_channels(self):
        """Channels of a Mamba-2 layer's convolution: x, B and C side by side."""
        return self.mamba2_inner + 2 * self.ssm_groups * self.ssm_state_size

    @property
    def shared_ffn_size(self):
        """The shared experts' joint width."""
        return self.moe_shared_ffn_size or self.moe_shared_experts * self.expert_ffn_size

    @property
    def carries_across_layers(self):
        """Whether the layer loop hands values from layer to layer beside the
        residual stream (a Mamba layer's SSM output to the gated memory
        units above it, the full differential layer's K/V rows to the
        cross-attention layers above it)."""
        return bool(set(self.layer_types) & set(SAMBAY_TYPES))

    @property
    def linear_conv_channels(self):
        """Channels of a linear layer's convolution: its q, k and v side by side."""
        return self.linear_num_heads * (2 * self.linear_key_head_dim
                                        + self.linear_value_head_dim)

    @property
    def latent_width(self):
        """Values a position holds in a latent cache (0 = per-head K and V)."""
        return self.kv_lora_rank + self.qk_rope_head_dim if self.kv_lora_rank else 0

    def num_params(self):
        """Approximate parameter count (for MFU math); experts held here."""
        h, v, L = self.hidden_size, self.vocab_size, self.num_layers
        if self.kv_lora_rank:
            nh, qk = self.num_heads, self.qk_nope_head_dim + self.qk_rope_head_dim
            q_proj = (h * self.q_lora_rank + self.q_lora_rank * nh * qk if self.q_lora_rank
                      else h * nh * qk)
            attn = (q_proj + h * self.latent_width
                    + self.kv_lora_rank * nh * (self.qk_nope_head_dim + self.v_head_dim)
                    + nh * self.v_head_dim * h)
        else:
            attn = (h * self.head_size * (self.num_heads + 2 * self.kv_heads)
                    + self.num_heads * self.head_size * h)
        per_h = 3 * h if self.activation in ("swiglu", "geglu") else 2 * h
        mlp = per_h * self.ffn_size
        if self.num_experts > 0:
            shared = self.shared_ffn_size if self.moe_shared_experts else 0
            mlp = (per_h * (self.expert_ffn_size * self.experts_held + shared)
                   + h * self.num_experts)
        emb = v * h * (1 if self.tie_embeddings else 2)
        pos = self.max_seq_len * h if self.pos_embedding == "learned" else 0
        if self.qk_norm:
            attn += (2 * self.head_size if self.qk_norm_per_head
                     else self.head_size * (self.num_heads + self.kv_heads))
        if self.num_experts > 0 and self.moe_scoring == "sigmoid" \
                and not set(self.layer_types) & set(ONE_SUBLAYER_TYPES):
            mlp += self.num_experts  # the selection bias
        if self.carries_across_layers:
            hd2, di = 2 * self.head_size, self.ssm_inner
            q_o = 2 * (h * h + h) + 2 * hd2 + hd2  # W_q, W_o with bias; lambdas; sub-norm
            per = {"mamba": (2 * h * di + di * (self.ssm_conv_kernel + 1)
                             + di * (self.ssm_dt_rank + 2 * self.ssm_state_size)
                             + self.ssm_dt_rank * di + di + di * self.ssm_state_size + di
                             + di * h),
                   "diff_attention": q_o + 2 * self.kv_heads * self.head_size * (h + 1),
                   "gmu": 2 * h * di, "cross_attention": q_o}
            return (sum(per[t] for t in self.layer_types) + L * (mlp + 4 * h)
                    + emb + pos + 2 * h)
        # a Mamba-2 mixer: W_in, the taps and their bias, dt_bias / A_log / D,
        # the gated norm's scale, W_out
        di, cc, nh = self.mamba2_inner, self.mamba2_conv_channels, self.ssm_num_heads
        mamba2 = h * (di + cc + nh) + cc * (self.ssm_conv_kernel + 1) + 3 * nh + di + di * h
        if set(self.layer_types) & set(ONE_SUBLAYER_TYPES):
            per = {"mamba2": mamba2,
                   "attention": attn, "mlp": per_h * self.ffn_size,
                   "moe": mlp + (self.num_experts if self.moe_scoring == "sigmoid" else 0)}
            return sum(per[t] + h for t in self.layer_types) + emb + pos + h
        if "parallel_hybrid" in self.layer_types:
            # both mixers, the FFN, two norms
            return L * (attn + mamba2 + mlp + 2 * h) + emb + pos + h
        n_lin = sum(t == "linear_attention" for t in self.layer_types)
        if n_lin:
            # q, k; v, gate, out; the two per-head gates with A_log and
            # dt_bias; the convolution; the gated norm's scale
            nl, dk, dv = self.linear_num_heads, self.linear_key_head_dim, self.linear_value_head_dim
            decay = nl * dk if self.linear_channel_decay else nl  # W_a's outputs, and dt_bias
            lin = (2 * h * nl * dk + 3 * h * nl * dv + h * nl + h * decay + decay + nl
                   + self.linear_conv_channels * self.linear_conv_kernel + dv)
            if self.kv_lora_rank:
                # the two latents' norms (a query of one projection has none), a head-wise gate
                attn += (self.kv_lora_rank + self.q_lora_rank
                         + (h * self.num_heads if self.attn_head_gate else 0))
            ffn = self.moe_first_dense * per_h * self.ffn_size + (L - self.moe_first_dense) * mlp
            return (ffn + L * 2 * h + (L - n_lin) * attn + n_lin * lin) + emb + pos + h
        dense = self.moe_first_dense * (per_h * self.ffn_size - mlp)
        # the module: one expert block, W_eh over [embedding ; hidden], three norms
        mtp = self.mtp_layers * (attn + mlp + 2 * h + 2 * h * h + 3 * h)
        # a short_conv layer's operator in attention's place: W_in (B, C, X), the taps, W_out
        n_conv = sum(t == "short_conv" for t in self.layer_types)
        conv = n_conv * (h * 3 * h + h * self.short_conv_kernel + h * h - attn)
        return L * (attn + mlp + 2 * h) + conv + dense + mtp + emb + pos + h


def resolve_remat_policy(name):
    """Map a policy name to a ``jax.checkpoint`` policy. Beyond the stock
    ``jax.checkpoint_policies`` names: ``dots_and_attn_saveable`` also pins
    the Pallas flash-attention outputs (tagged via ``checkpoint_name``), so
    backward reuses the forward kernel's result instead of re-running it."""
    if name is None or name == "nothing_saveable":
        return None
    cp = jax.checkpoint_policies
    if name == "dots_and_attn_saveable":
        return cp.save_from_both_policies(
            cp.dots_saveable, cp.save_only_these_names("flash_out", "flash_lse"))
    policy = getattr(cp, name, None)
    if policy is None:
        known = [n for n in dir(cp) if not n.startswith("_")]
        raise ValueError(
            f"unknown remat policy {name!r} (a typo would silently mean full "
            f"recompute); use 'nothing_saveable', 'dots_and_attn_saveable', or one of "
            f"jax.checkpoint_policies: {known}")
    return policy


def chunked_cross_entropy(hidden, w, labels, valid, chunk=128, transpose=False):
    """Sum of next-token CE over valid positions WITHOUT materializing the
    full fp32 ``(B, T, V)`` logits (at bs16/seq1024/vocab50k that tensor is
    ~3.3 GB and, saved for backward, dominates HBM).

    ``hidden``: (B, T, H) compute dtype; ``w``: (V, H) when ``transpose``
    (tied-embedding ``attend``) else (H, V); ``labels``/``valid``: (B, T).
    Scans T in chunks of ``chunk`` rows with a hand-written VJP: forward
    keeps only the running loss sum; backward rebuilds each logits block and
    emits d(hidden)/d(w) directly from softmax(p) - onehot, so live memory is
    one (B, chunk, V) block in either direction and the scan is never
    differentiated through (scan-of-matmul transposition also trips an abort
    in the CPU XLA runtime used by the test mesh). The scan runs over the
    (replicated) time axis while the batch axis keeps its DP sharding.

    Where d(w) is reduced. d(w) contracts the batch, so with the batch
    sharded over ``dp`` chips it is a cross-chip sum. Written as one product
    a chunk (``einsum("bcv,bch->vh").astype(float32)`` added to a float32
    accumulator) the partitioner put an all-reduce on EVERY chunk's product,
    and the ``astype`` between product and ``+`` kept the compiler from
    merging them: the whole vocabulary-sized gradient crossed the chips once
    a chunk (eight times a step at 2,048 positions, 1.65 GB in bf16 for a
    50,272 x 2,048 head). Now the batch is seen as ``(dp, local)`` and a
    chunk's product keeps ``dp`` as a leading axis sharded like the batch, so
    each chip accumulates its own rows' partial sums in float32 and ONE sum
    over that axis, behind the loop and in float32, crosses the chips: one
    all-reduce a step, whatever the ZeRO stage (under stages 2 and 3 the
    gradient's shard is sliced from its result). On one data-parallel chip,
    under the pipeline and for a batch the chips do not divide the backward
    is the plain one (``_ce_batch_axes``).
    """
    B, T, H = hidden.shape
    pad = (-T) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    # labels/valid enter the custom_vjp as f32 so their cotangents are plain
    # zero arrays — float0 cotangents inside the pipeline's shard_map AD are
    # a known sharp edge
    return _chunked_ce(hidden, w, labels.astype(jnp.float32), valid.astype(jnp.float32),
                       T, chunk, transpose)


def _ce_stack(hidden, labels, valid, chunk):
    B, Tp, H = hidden.shape
    nch = Tp // chunk
    xs = hidden.reshape(B, nch, chunk, H).swapaxes(0, 1)  # (nch, B, chunk, H)
    ls = labels.reshape(B, nch, chunk).swapaxes(0, 1)
    vs = valid.reshape(B, nch, chunk).swapaxes(0, 1)
    return xs, ls, vs


def _ce_logits(xc, w, transpose):
    eq = "bch,vh->bcv" if transpose else "bch,hv->bcv"
    return jnp.einsum(eq, xc, w.astype(xc.dtype)).astype(jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _chunked_ce(hidden, w, labels, valid, T, chunk, transpose):
    total, _ = _chunked_ce_fwd(hidden, w, labels, valid, T, chunk, transpose)
    return total


def _chunked_ce_fwd(hidden, w, labels, valid, T, chunk, transpose):
    # python loop, not lax.scan: the chunk count is small and static, and a
    # while-loop here costs sequentialization XLA can't schedule around
    # (it also trips a rare abort in the multi-device CPU runtime the
    # test mesh uses)
    xs, ls, vs = _ce_stack(hidden, labels, valid, chunk)
    total = jnp.zeros((), jnp.float32)
    for i in range(xs.shape[0]):
        logits = _ce_logits(xs[i], w, transpose)
        lse = jax.nn.logsumexp(logits, axis=-1)  # (B, chunk)
        lc = ls[i].astype(jnp.int32)
        corr = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        total = total + jnp.sum((lse - corr) * vs[i])
    return total, (hidden, w, labels, valid)


def _ce_batch_axes(B):
    """``(axes, dp)``: the mesh axes a loss's batch axis is taken to be
    sharded over, and their size. Taken from the mesh, not read from the
    operand: the axes the engine's ``_shard_batch`` puts a batch on
    (``expert`` and ``data`` where they are more than one), which is where
    every training path's batch rests. A batch that rests elsewhere (whole on
    every chip) is resharded to them by the backward's constraints: the same
    gradient, each chip computing d(w) from its share of the rows. ``axes``
    is ``()`` where the backward keeps its plain form: one data-parallel
    chip, a batch the axes do not divide, and a mesh with ``pipe`` > 1,
    whose microbatch stream is not batch-major and whose ``shard_map`` is a
    manual region already."""
    if not dist.has_mesh():
        return (), 1
    mesh, axes = dist.get_mesh(), dist.dp_axes()
    dp = math.prod(mesh.shape[a] for a in axes)
    if B % dp or mesh.shape[dist.PIPE_AXIS] > 1:
        axes = ()
    return axes, dp


def _chunked_ce_bwd(T, chunk, transpose, res, g):
    hidden, w, labels, valid = res
    B, Tp, H = hidden.shape
    xs, ls, vs = _ce_stack(hidden, labels, valid, chunk)
    V = w.shape[0] if transpose else w.shape[1]

    # d(w) contracts the batch: where the batch is sharded over dp chips each
    # chip adds up its own rows' partial sums, under a leading axis ``d``, and
    # ONE sum over ``d`` crosses the chips, behind the loop (see
    # chunked_cross_entropy)
    axes, dp = _ce_batch_axes(B)
    d = "d" if axes else ""
    eq = f"{d}bcv,{d}bch->{d}vh" if transpose else f"{d}bch,{d}bcv->{d}hv"
    own = P(axes, P.UNCONSTRAINED, P.UNCONSTRAINED)  # ``d`` sharded like the batch
    # the sums across chips that this code itself asks for, and their bytes
    # (the whole array's, in float32)
    dist.tally_head_grad(*((1, w.size * 4) if axes else (0, 0)))

    def by_chip(a):
        if not axes:
            return a
        a = a.reshape((dp, B // dp) + a.shape[1:])
        return dist.constrain(a, P(axes, *[P.UNCONSTRAINED] * (a.ndim - 1)))

    dw = jnp.zeros(((dp, ) if axes else ()) + w.shape, jnp.float32)
    dx_chunks = []
    for i in range(xs.shape[0]):  # python loop: see _chunked_ce_fwd
        xc, lc, vc = xs[i], ls[i].astype(jnp.int32), vs[i]
        logits = _ce_logits(xc, w, transpose)
        p = jax.nn.softmax(logits, axis=-1)
        dlogit = (p - jax.nn.one_hot(lc, V, dtype=jnp.float32)) * (vc * g)[..., None]
        dlogit = dlogit.astype(xc.dtype)  # matmuls at MXU rate
        dx_chunks.append(jnp.einsum("bcv,vh->bch" if transpose else "bcv,hv->bch",
                                    dlogit, w.astype(xc.dtype)))
        pair = (dlogit, xc) if transpose else (xc, dlogit)
        dw = dw + jnp.einsum(eq, *map(by_chip, pair)).astype(jnp.float32)
        if axes:
            dw = dist.constrain(dw, own)
    if axes:
        dw = dw.sum(0)
    dx = jnp.concatenate(dx_chunks, axis=1).reshape(B, Tp, H)
    return (dx.astype(hidden.dtype), dw.astype(w.dtype),
            jnp.zeros_like(labels), jnp.zeros_like(valid))


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


def scaled(x, multiplier):
    """``x`` times a published constant (``TransformerConfig``: the
    ``*_multiplier`` fields): the product in float32, rounded once to ``x``'s
    dtype; ``x`` itself where the constant is 1."""
    if multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


class RMSNorm(nn.Module):
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1], ), jnp.float32)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.epsilon) * scale
        return y.astype(self.dtype)


def make_norm(cfg, name=None):
    if cfg.norm == "rmsnorm":
        return RMSNorm(epsilon=cfg.layernorm_epsilon, dtype=cfg.dtype, name=name)
    return nn.LayerNorm(epsilon=cfg.layernorm_epsilon, dtype=cfg.dtype, param_dtype=jnp.float32, name=name)


def yarn_mscale(factor, mscale):
    """YaRN's attention magnitude correction: 0.1 m ln(factor) + 1."""
    import math
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(head_size, theta, factor=1.0, beta_fast=32.0, beta_slow=1.0,
                     original_max_len=0):
    """Inverse frequencies of the rotary pairs. ``factor`` > 1 blends them the
    YaRN way (arXiv:2309.00071, as HF ``_compute_yarn_parameters`` does):
    pairs that turn more than ``beta_fast`` times over the original context
    keep their frequency, pairs that turn fewer than ``beta_slow`` times are
    interpolated (divided by ``factor``), a linear ramp in between."""
    import math
    freq = 1.0 / (theta**(jnp.arange(0, head_size, 2, dtype=jnp.float32) / head_size))
    if factor <= 1:
        return freq

    def correction_dim(turns):
        return head_size * math.log(original_max_len / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_size - 1)
    ramp = jnp.clip((jnp.arange(head_size // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq / factor * ramp + freq * (1.0 - ramp)


def rope_table(head_size, max_len, theta, freq=None, magnitude=1.0):
    if freq is None:
        freq = rope_frequencies(head_size, theta)
    pos = jnp.arange(max_len, dtype=jnp.float32)
    angles = jnp.outer(pos, freq)  # (T, hd/2)
    return jnp.sin(angles) * magnitude, jnp.cos(angles) * magnitude


def model_rope_table(cfg):
    """The (sin, cos) table a configuration's attention reads, or (None,
    None) without rotary positions: the rotary width (partial rotary, or a
    latent head's rope part), YaRN frequencies and magnitude."""
    if cfg.pos_embedding != "rope":
        return None, None
    dim = cfg.qk_rope_head_dim if cfg.kv_lora_rank else (cfg.rotary_dim or cfg.head_size)
    if cfg.rope_factor <= 1:
        return rope_table(dim, cfg.max_seq_len, cfg.rope_theta)
    freq = rope_frequencies(dim, cfg.rope_theta, cfg.rope_factor, cfg.rope_beta_fast,
                            cfg.rope_beta_slow, cfg.rope_original_max_len)
    magnitude = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                 / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return rope_table(dim, cfg.max_seq_len, cfg.rope_theta, freq, magnitude)


def alibi_slopes(num_heads):
    """Per-head ALiBi slopes (Press et al.; the HF BLOOM construction): for a
    power-of-two head count, geometric series starting at 2^(-8/n); otherwise
    the closest power of two's series plus interleaved extras."""
    import math
    n = 2**math.floor(math.log2(num_heads))
    base = 2.0**(-(2.0**-(math.log2(n) - 3)))
    slopes = [base**(i + 1) for i in range(n)]
    if n < num_heads:
        extra_base = 2.0**(-(2.0**-(math.log2(2 * n) - 3)))
        slopes += [extra_base**(i + 1) for i in range(0, 2 * (num_heads - n), 2)]
    return jnp.asarray(slopes, jnp.float32)


def apply_rope(x, sin, cos):
    """x: (B, H, T, hd); tables (T, hd/2) shared across the batch or
    (B, T, hd/2) per-row (left-padded generation). Citation: the reference's
    CUDA ``apply_rotary_pos_emb`` (csrc/transformer/inference/csrc/pt_binding.cpp:1765)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if sin.ndim == 2:
        sin = sin[None, None, :, :]
        cos = cos[None, None, :, :]
    else:
        sin = sin[:, None, :, :]
        cos = cos[:, None, :, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def apply_rope_interleaved(x, sin, cos):
    """As :func:`apply_rope`, with dimensions ``2j`` and ``2j + 1`` rotating
    together (``rope_interleave``). The result comes out de-interleaved
    ([evens ; odds]): queries and keys get the same permutation, so their
    dot products are those of the interleaved layout."""
    x32 = x.astype(jnp.float32)
    return apply_rope(jnp.concatenate([x32[..., 0::2], x32[..., 1::2]], axis=-1),
                      sin, cos).astype(x.dtype)


def _ulysses_specs(B, nh, nkv=None):
    """Ulysses-style sequence parallelism as placement (DeepSpeed-Ulysses;
    absent in the v0.9.2 reference — SURVEY §2.3 makes SP a build
    requirement): inside attention, re-shard from sequence-split activations
    to head-split q/k/v — XLA inserts the all-to-alls over ICI — and back.

    Returns (heads_spec, seq_q_spec, seq_kv_spec) for bhtd tensors, or None
    when the mesh cannot split this shape. The projection-side seq specs
    keep heads sharded by ``tensor`` (the Megatron-TP layout the projection
    kernels already produce) and T by ``seq``: each boundary reshard then
    moves exactly ONE axis (the seq all-to-all) — a combined move is an
    involuntary full rematerialization in the SPMD partitioner."""
    # a PARTIAL manual region (pipeline: manual over pipe only) still wants
    # these constraints — dist.constrain resolves them over the auto axes
    if not dist.has_mesh() or dist.SEQ_AXIS in dist.get_manual_axes():
        return None
    mesh = dist.get_mesh()
    if mesh.shape[dist.SEQ_AXIS] == 1:
        return None
    dp_axes, head_axes = dist.attention_partition_axes(B, nh)
    if dist.SEQ_AXIS not in head_axes:
        return None  # heads not divisible: leave sequence-sharded (all-gather)
    heads = P(dp_axes or None, head_axes, None, None)
    t = mesh.shape[dist.TENSOR_AXIS]

    def seq_spec(n_heads):
        on_heads = dist.TENSOR_AXIS if (t > 1 and n_heads % t == 0) else None
        return P(dp_axes or None, on_heads, dist.SEQ_AXIS, None)

    return heads, seq_spec(nh), seq_spec(nkv if nkv is not None else nh)


def _constrain(x, spec):
    return dist.constrain(x, spec)


def _tp_mesh_size():
    """Size of the ``tensor`` mesh axis usable from this trace context (1
    when no mesh is installed or the axis is under manual partitioning)."""
    if not dist.has_mesh() or dist.TENSOR_AXIS in dist.get_manual_axes():
        return 1
    return dist.get_mesh().shape[dist.TENSOR_AXIS]


def _paged_kernel_kw(kv_scale, ext_ops, tp_shard):
    """Optional operands of the paged decode/span kernels: the int8 pool's
    per-token-row scales, the long-context extent table with its lossy
    knobs, and the tensor mesh axis to shard the head walk over."""
    kw = {"k_scale": kv_scale, "v_scale": kv_scale}
    if ext_ops is not None:
        kw["ext"], _, _, kw["sink"], kw["window"] = ext_ops
    if tp_shard:
        kw.update(mesh=dist.get_mesh(), axis=dist.TENSOR_AXIS)
    return kw


def _attended_ends(write_index, q_spans):
    """One past each row's write head as the paged kernels take it: 0 for a
    slot whose span is 0 (idle, cached, a prefill row riding another
    forward), so the kernel's walk reads nothing for a row nobody reads.
    Without spans every row is live."""
    ends = write_index + 1
    return ends if q_spans is None else jnp.where(q_spans > 0, ends, 0)


def kv_packs(head_size):
    """THE rule of the K/V pool's geometry: a layer's K and V rest side by
    side in one leaf ``(slots, kv_heads, S, 2 * head_size)`` where that
    fills whole 128-lane tiles and a leaf of ``head_size`` lanes alone
    would not (head size 64). A 64-lane leaf rests position-major on the
    chip, no kernel reads it so, and the compiler relays every leaf on
    entry to and exit from every program; the pair's rows rest as the
    kernels read them. Head sizes 128 and 256 are dense split, 80 and 96
    dense in neither form: they stay split."""
    return (2 * head_size) % 128 == 0 and head_size % 128 != 0


def kv_layer_leaves(cfg, layer_cache):
    """One layer's cache as ``(K/V leaves, scale leaf or None, split)``,
    from what a trace can see: the first leaf's last dimension against
    ``cfg.head_size``. Packed (``split == cfg.head_size``: keys in lanes
    ``[0, split)``, values after them): ``(kv, )`` or ``(kv, scale)``.
    Split (``split == 0``): ``(k, v)`` or ``(k, v, scale)``. The number of
    leaves alone does not tell: a packed int8 layer has two, as a split
    float one has."""
    hd, lanes = cfg.head_size, layer_cache[0].shape[-1]
    n = 1 if lanes == 2 * hd else 2
    if lanes not in (hd, 2 * hd) or not n <= len(layer_cache) <= n + 1:
        raise ValueError(f"KV cache leaves {[c.shape for c in layer_cache]} are neither the "
                         f"split nor the packed geometry of head size {hd}")
    scale = layer_cache[n] if len(layer_cache) > n else None
    return tuple(layer_cache[:n]), scale, (hd if n == 1 else 0)


def kv_pool_geometry(cfg, kv_cache):
    """``"latent"``, ``"packed"`` or ``"split"``: which of ``init_cache``'s
    three geometries a cache tree's ROWS have (read from the first layer that
    holds rows: a linear-attention layer holds state instead; differential
    layers' rows, and their rings, are always split)."""
    if cfg.latent_width:
        return "latent"
    if not cfg.layer_types:
        leaf = jax.tree_util.tree_leaves(kv_cache[0])[0]
    elif cfg.carries_across_layers:
        return "split"  # a pair's keys, and its values, as one head of 2 x head size
    else:
        leaf = kv_cache[0][next(i for i in range(cfg.num_layers)
                                if "full_attention" in cfg.layer_mixers(i))]
    return "packed" if leaf.shape[-1] == 2 * cfg.head_size else "split"


def _kv_writes(cfg, layer_cache, k, v):
    """A layer's cache and its fresh ``(B, nkv, T, hd)`` K and V rows as the
    ``(pool leaf, fresh rows)`` pairs of a cache write, K/V leaves first:
    the rows quantized where the layer has a scale leaf (int8 tier), and
    joined on the last axis where its K and V rest packed. Returns
    ``(writes, split, quantized)``, ``split`` as :func:`kv_layer_leaves`."""
    kv_leaves, scale, split = kv_layer_leaves(cfg, layer_cache)
    fresh, tail = (k, v), []
    if scale is not None:
        from ..ops.quantizer import quantize_kv_rows
        *fresh, sc_new = quantize_kv_rows(k, v)
        tail = [(scale, sc_new)]
    if split:
        fresh = (jnp.concatenate(fresh, axis=-1), )
    return list(zip(kv_leaves, fresh)) + tail, split, scale is not None


def _written_kv(written, split, quantized):
    """``(k_cache, v_cache, scale)`` of a layer's written leaves as the
    paged kernels' entry points take them: the packed leaf whole, as
    ``k_cache`` with ``v_cache=None``."""
    ck, cv = (written[0], None) if split else written[:2]
    return ck, cv, (written[-1] if quantized else None)


def _commit_span_rows(writes, write_index, q_spans, paged_kernels):
    """The span write of the slot pool, for :class:`Attention` and
    ``fused_paged_step`` alike: column ``j`` of row ``i`` lands at position
    ``write_index_i + j``; columns past the row's live span, and positions
    past the pool's ``S``, are DROPPED — padding never writes, so retained
    prefix slots and co-resident decode rows stay byte-stable. ``writes``:
    ``(pool leaf, fresh rows)`` pairs, the K/V leaves first (the packed
    leaf with its joined rows, or K and V), then an int8 pool's scale leaf
    (its S axis matches theirs).

    ``paged_kernels``: the caller attends through the paged Pallas kernels
    on one device. Then the K/V leaves (the leading writes of one shape)
    commit through the in-place kernel (``ops/pallas/kv_commit.py``), which
    takes the pool row-major as those kernels do, so the compiler has no
    layout to convert between; elsewhere (the XLA attention fallback, a
    tensor-parallel pool, leaves the kernel does not tile, the kilobyte
    scale leaf) the scatter stays. Both leave the same bytes. The latent
    leaf is not a leaf of rows and does not come here: its columns commit
    through :func:`_commit_span_columns`, which shares this function's
    semantics and none of its code (rows row-major for the paged kernels,
    positions minor for the latent walk: the two layouts conflict).
    The choice is tallied per trace for the scheduler's
    ``serving/kv_commit_*_programs`` counters."""
    from ..ops.pallas import kv_commit
    ck, k = writes[0]
    n_kv = next((j for j, (c, _) in enumerate(writes) if c.shape != ck.shape), len(writes))
    in_place = (paged_kernels and _tp_mesh_size() == 1
                and kv_commit.commits_in_place(ck))
    kv_commit.tally(in_place)
    T = k.shape[2]
    tgt = write_index[:, None] + jnp.arange(T)[None, :]
    tgt = jnp.where(jnp.arange(T)[None, :] < q_spans[:, None], tgt, ck.shape[2])
    upd = lambda c, kk, i: c.at[:, i, :].set(kk.astype(c.dtype), mode="drop")
    with jax.named_scope("kv_commit"):
        written = list(kv_commit.commit_kv_rows(
            [c for c, _ in writes[:n_kv]], [kk for _, kk in writes[:n_kv]],
            write_index, q_spans)) if in_place else []
        written += [jax.vmap(upd)(c, kk, tgt) for c, kk in writes[len(written):]]
    return written


def _commit_span_columns(pool, fresh, write_index, q_spans):
    """:func:`_commit_span_rows` for the latent leaf, which rests
    position-last (``cache_spec``'s ``"columns"``): ``pool`` ``(B, 1, D, S)``,
    ``fresh`` ``(B, 1, T, D)`` as the projections make it. The same bytes
    land: column ``j`` of row ``i`` at position ``write_index_i + j``;
    columns past the row's span and positions past ``S`` are dropped; a slot
    with span 0 is not touched. Through the in-place column kernel
    (``ops/pallas/kv_commit.py: commit_kv_columns``) wherever it tiles the
    leaf (``S`` whole 128-position blocks; the engine serves latent attention
    on one device), tallied ``in_place``:
    an XLA scatter wants the window it writes (one position's ``D`` values)
    minor whichever way the leaf is shaped, and the compiler then relays the
    whole leaf around every commit and once more for every forward's block
    walk (ISSUE 55: six moves of the leaf a four-step sync). Elsewhere the
    scatter stays, tallied as such."""
    from ..ops.pallas import kv_commit
    in_place = kv_commit.commits_columns_in_place(pool)
    kv_commit.tally(in_place)
    with jax.named_scope("kv_commit"):
        if in_place:
            return kv_commit.commit_kv_columns(pool, fresh, write_index, q_spans)
        T, S = fresh.shape[2], pool.shape[3]
        tgt = write_index[:, None] + jnp.arange(T)[None, :]
        tgt = jnp.where(jnp.arange(T)[None, :] < q_spans[:, None], tgt, S)
        upd = lambda c, kk, i: c.at[:, :, i].set(
            jnp.swapaxes(kk, 1, 2).astype(c.dtype), mode="drop")
        return jax.vmap(upd)(pool, fresh, tgt)


def _tp_replicate(x):
    """Re-replicate a tensor-sharded activation (bitwise-TP serving layout):
    the constraint lowers to an all-gather over ``tensor`` — pure
    concatenation of the shards, no arithmetic — so the downstream
    row-parallel matmul runs its FULL contraction on every shard and its
    result is bit-identical to tp=1. Identity when no tensor axis is live
    (tp=1 programs stay byte-stable)."""
    if _tp_mesh_size() > 1:
        return dist.constrain(x, P(*([None] * x.ndim)))
    return x


def _embed_layout(x):
    """Route the embedding-gather output into the canonical activation layout
    (batch over dp, T over seq, H replicated) in single-axis moves. The
    gather inherits the table's tensor-tiled H; jumping straight to
    (dp, seq, None) is a combined move the partitioner can only do by full
    rematerialization, so step via (dp, seq, tensor) — a free slice — then
    all-gather H over tensor alone.

    TRAINING/full-forward path only. The KV-cache (serving) forward skips
    this routing: its batch axis is the scheduler's SLOT POOL, not a
    data-parallel batch (replica sets are serving's data parallelism), and
    both the dp constraint and the tensor reshard round-trip measurably
    perturb XLA's fusion choices across mesh shapes — ulp drift that would
    break the serving contract (tp>1 and any-mesh decode bit-identical to
    tp=1)."""
    import math
    if not dist.has_mesh():
        return x
    mesh = dist.get_mesh()
    B, T, H = x.shape
    dp = tuple(a for a in (dist.EXPERT_AXIS, dist.DATA_AXIS) if mesh.shape[a] > 1)
    if dp and B % math.prod(mesh.shape[a] for a in dp) != 0:
        dp = ()
    seq = dist.SEQ_AXIS if (mesh.shape[dist.SEQ_AXIS] > 1
                            and T % mesh.shape[dist.SEQ_AXIS] == 0) else None
    t = dist.TENSOR_AXIS if (mesh.shape[dist.TENSOR_AXIS] > 1
                             and H % mesh.shape[dist.TENSOR_AXIS] == 0) else None
    if not dp and seq is None and t is None:
        return x
    x = _constrain(x, P(dp or None, seq, t))
    return _constrain(x, P(dp or None, seq, None))


def _lora_rank_delta(x2, A, Bm):
    """One rank-bucket low-rank delta for a batch of per-row adapters
    (batched mixed-adapter serving, ``deepspeed_tpu/adapters/``): ``x2`` is
    the site input flattened to (B, T, K); ``A`` (B, K..., r) is the
    scale-folded down-projection gathered per row from the paged adapter
    pool (rows with no adapter carry the all-zero slot 0), ``Bm``
    (B, r, out...) the up-projection. fp32 math end to end — the rounding
    contract every reference path (solo scheduler run, ``runtime/lora.py``
    decomposed ops) must share for bit-identity. Returns (B, T, O) fp32."""
    Bsz = x2.shape[0]
    A2 = A.reshape(Bsz, -1, A.shape[-1]).astype(jnp.float32)
    B2 = Bm.reshape(Bsz, Bm.shape[1], -1).astype(jnp.float32)
    t = jnp.einsum("btk,bkr->btr", x2.astype(jnp.float32), A2)
    return jnp.einsum("btr,bro->bto", t, B2)


def _lora_site_delta(x2, lora_ops, site):
    """Summed per-row delta over every rank bucket adapting ``site``, or
    None when no bucket does. ``lora_ops``: tuple of per-bucket dicts
    ``site -> (A, B)`` (see :class:`Attention` docstring); buckets a row
    doesn't belong to contribute its all-zero slot-0 pages, so the sum is
    exactly that row's single adapter's delta."""
    delta = None
    for bucket in lora_ops:
        ab = bucket.get(site)
        if ab is None:
            continue
        d = _lora_rank_delta(x2, ab[0], ab[1])
        delta = d if delta is None else delta + d
    return delta


def _sdpa_xla(q, k, v, mask_bias, dtype, interior_spec=None):
    """Pure-XLA attention in bhtd: softmax in fp32, big-negative causal bias.

    ``interior_spec``: optional PartitionSpec pinned onto scores/probs (and,
    via the constraint's transpose rule, their cotangents). Under Ulysses the
    interior must stay head-sharded end to end — without the pin the
    partitioner mixes the seq-sharded cotangent layout into the softmax
    backward and falls into involuntary full rematerialization."""
    hd = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(hd)
    scores = scores + mask_bias
    if interior_spec is not None:
        scores = _constrain(scores, interior_spec)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    if interior_spec is not None:
        probs = _constrain(probs, interior_spec)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _cached_attention_xla(q, ck, cv, cache_index, cache_mask, dtype, alibi=None, window=0):
    """Grouped-query attention against a KV cache, no head expansion.

    q: (B, nh, T, hd); ck/cv: (B, nkv, S, hd); cache_mask: optional (B, S)
    bool marking valid cache slots (left-pad masking). Query position ``i`` of
    this call sits at absolute cache position ``cache_index + i``;
    ``cache_index`` is a shared scalar or a per-row (B,) array (slot-pool
    decode: every cache slot sits at its own position). ``alibi``: optional
    (nh,) slopes adding ``-slope * (qpos - kpos)`` to the scores. ``window``:
    >0 restricts each query to the last ``window`` keys (GPT-Neo local
    attention).
    """
    B, nh, T, hd = q.shape
    nkv, S = ck.shape[1], ck.shape[2]
    g = nh // nkv
    qg = q.reshape(B, nkv, g, T, hd)
    scores = jnp.einsum("bkgtd,bksd->bkgts", qg, ck).astype(jnp.float32) / jnp.sqrt(hd)
    per_row = getattr(cache_index, "ndim", 0) == 1
    base = cache_index[:, None] if per_row else jnp.full((1, 1), cache_index)
    qpos = base + jnp.arange(T)[None, :]  # (B or 1, T)
    kpos = jnp.arange(S)[None, None, :]
    keep = kpos <= qpos[..., None]  # (B or 1, T, S)
    if window:
        keep = keep & (qpos[..., None] - kpos < window)
    bias = jnp.where(keep, 0.0, -1e30)  # (B or 1, T, S)
    if alibi is not None:
        rel = (qpos[..., None] - kpos).astype(jnp.float32)  # (B or 1, T, S)
        # (B or 1, nkv, g, T, S)
        bias = bias[:, None, None] - alibi.reshape(nkv, g)[None, :, :, None, None] * rel[:, None, None]
        if cache_mask is not None:
            bias = bias + jnp.where(cache_mask, 0.0, -1e30)[:, None, None, None, :]
    else:
        bias = bias[:, None, None]  # (B or 1, 1, 1, T, S)
        if cache_mask is not None:
            bias = bias + jnp.where(cache_mask, 0.0, -1e30)[:, None, None, None, :]
    probs = jax.nn.softmax(scores + bias, axis=-1).astype(dtype)
    out = jnp.einsum("bkgts,bksd->bkgtd", probs, cv)
    return out.reshape(B, nh, T, hd)


from ..ops.pallas import gdn_step
from ..ops.pallas.quant_matmul import pick_block as _pick_block

import os as _os

_QMM_IMPL = _os.environ.get("DSTPU_QMM_IMPL", "pallas")


def _qmm2d(x2d, qw, scales, out_dtype=None):
    """int8 matmul: ``x @ (dequant(qw))`` without a persistent bf16 weight.

    Default path is the Pallas w8a16 kernel (one-pass s8->bf16 widen, group
    scales applied to the (M, N) partials after the dot; XLA's lowering only
    half-fuses the dequant into the dot). Set DSTPU_QMM_IMPL=xla to compare.

    Under tensor parallelism the XLA path is used instead: pallas_call is
    opaque to the GSPMD partitioner, so tensor-sharded kernel_q operands
    would be all-gathered per call rather than computed shard-local."""
    M, K = x2d.shape
    G, N = scales.shape
    tp_sharded = dist.has_mesh() and not dist.in_manual_region() \
        and dist.get_mesh().shape[dist.TENSOR_AXIS] > 1
    if _QMM_IMPL == "pallas" and not tp_sharded:
        from ..ops.pallas.quant_matmul import quant_matmul
        return quant_matmul(x2d, qw, scales,
                            block_m=_pick_block(M, 256, 8),
                            out_dtype=out_dtype or x2d.dtype)
    w = qw.astype(x2d.dtype)
    if G == 1:
        w = w * scales[0].astype(x2d.dtype)
    else:
        w = (w.reshape(G, K // G, N) * scales[:, None, :].astype(x2d.dtype)).reshape(K, N)
    return jnp.matmul(x2d, w, preferred_element_type=jnp.float32).astype(
        out_dtype or x2d.dtype)


def _q_groups(k, group_size):
    """Scale-group count for a contraction of k: group_size (default 128)
    when it divides k, else one group — the same rule quantize_params uses,
    so module param shapes and quantized trees always agree."""
    gs = group_size or 128
    return k // gs if k % gs == 0 else 1


def _q_param(mod, name, k, n, group_size):
    """Declare (int8 weight, fp32 scales) params for a (k, n) contraction."""
    qw = mod.param(name + "_q", nn.initializers.zeros, (k, n), jnp.int8)
    sc = mod.param(name + "_scale", nn.initializers.ones,
                   (_q_groups(k, group_size), n), jnp.float32)
    return qw, sc


def _product(spec, x, w, order, path="kernel"):
    """``einsum(spec, x, w)``; under a ZeRO-3 gather order that holds the
    weight's ``path``, the product whose gathers the program places."""
    return jnp.einsum(spec, x, w) if order is None else order.product(spec, x, w, path)


class OrderedDense(nn.Module):
    """``nn.Dense`` (same parameter names, shapes and initialisers) for a
    kernel that rests as a ZeRO-3 shard: the product states which product
    its weight's gathers are due behind (``order``: a ``GatherOrder``)."""
    features: int
    use_bias: bool
    dtype: Any
    dx_behind_dw: bool = False  # the backward's regather is due behind this product's dW

    @nn.compact
    def __call__(self, x, order):
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (x.shape[-1], self.features), jnp.float32)
        y = order.product("...k,kn->...n", x.astype(self.dtype), kernel.astype(self.dtype),
                          "kernel", self.dx_behind_dw)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (self.features, ), jnp.float32)
            y = y + bias.astype(self.dtype)
        return y


class HeadProjection(nn.Module):
    """q/k/v projection emitting head-major ``(B, heads, T, head_dim)``
    directly — the matmul's output layout IS the attention layout, so no
    transpose sits between the projection and the flash kernel. Param
    shapes/names match ``nn.DenseGeneral(features=(heads, head_dim))``."""
    heads: int
    head_dim: int
    use_bias: bool
    dtype: Any
    int8: bool = False
    int8_groups: int = 0  # scale-group SIZE (0 = default rule)

    @nn.compact
    def __call__(self, x, order=None):  # (B, T, H) -> (B, heads, T, head_dim)
        """``order``: the ZeRO-3 gather order of this module's leaves
        (``runtime/zero/gather_order.py: GatherOrder``), or None."""
        B, T, H = x.shape
        if self.int8:
            qw, sc = _q_param(self, "kernel", H, self.heads * self.head_dim,
                              self.int8_groups)
            y = _qmm2d(x.reshape(B * T, H).astype(self.dtype), qw, sc)
            y = y.reshape(B, T, self.heads, self.head_dim).transpose(0, 2, 1, 3)
        else:
            kernel = self.param("kernel", nn.initializers.normal(0.02),
                                (x.shape[-1], self.heads, self.head_dim), jnp.float32)
            y = _product("bth,hnd->bntd", x, kernel.astype(self.dtype), order)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (self.heads, self.head_dim), jnp.float32)
            y = y + bias.astype(self.dtype)[None, :, None, :]
        return y


class OutProjection(nn.Module):
    """Attention output projection consuming bhtd. Param shapes/names match
    ``nn.DenseGeneral(features=H, axis=(-2, -1))`` on (B, T, heads, hd)."""
    features: int
    use_bias: bool
    dtype: Any
    int8: bool = False
    int8_groups: int = 0  # scale-group SIZE (0 = default rule)

    @nn.compact
    def __call__(self, x, order=None):  # (B, heads, T, hd) -> (B, T, features)
        B, n, T, d = x.shape
        if self.int8:
            qw, sc = _q_param(self, "kernel", n * d, self.features, self.int8_groups)
            x2 = x.transpose(0, 2, 1, 3).reshape(B * T, n * d).astype(self.dtype)
            y = _qmm2d(x2, qw, sc).reshape(B, T, self.features)
        else:
            kernel = self.param("kernel", nn.initializers.normal(0.02),
                                (n, d, self.features), jnp.float32)
            y = _product("bntd,ndh->bth", x, kernel.astype(self.dtype), order)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (self.features, ), jnp.float32)
            y = y + bias.astype(self.dtype)
        return y


class ProjectionRMSNorm(nn.Module):
    """RMSNorm over a WHOLE head-major projection ``(B, heads, T, hd)``: the
    mean square over all of a position's ``heads * hd`` values, one learned
    scale each (``qk_norm``: the norm comes before the split into heads)."""
    epsilon: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        n, d = x.shape[1], x.shape[3]
        scale = self.param("scale", nn.initializers.ones, (n * d, ), jnp.float32)
        x32 = x.astype(jnp.float32)
        ms = jnp.mean(x32 * x32, axis=(1, 3), keepdims=True)
        y = x32 * jax.lax.rsqrt(ms + self.epsilon) * scale.reshape(n, 1, d)
        return y.astype(self.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig
    layer_idx: int = -1  # set on unrolled layers; drives local-window lookup

    @nn.compact
    def __call__(self, x, sin, cos, attn_mask=None, kv_cache=None, cache_index=None,
                 position_ids=None, write_index=None, q_spans=None, lora_ops=None,
                 ext_ops=None, seq_shard=False, order=None):
        """``attn_mask`` semantics: without a cache it is (B, T) over the
        current tokens; with a cache it is (B, S) over cache slots (True =
        attendable, used for left-pad masking during generation).

        ``lora_ops``: optional per-row batched-LoRA operands (multi-tenant
        adapter serving, ``deepspeed_tpu/adapters/``): a tuple of per-rank-
        bucket dicts ``site -> (A, B)`` with A (B, in..., r) scale-folded
        and B (B, r, out...), already GATHERED per batch row from the paged
        adapter pools (this layer's slice of the (L, B, ...) stack). Each
        adapted projection adds ``(x @ A_row) @ B_row`` in fp32 after its
        base matmul; rows with no adapter carry the all-zero slot-0 pages,
        so their delta is exactly zero. Sites: q/k/v/o here, gate/up/down
        in :class:`MLP`.

        ``write_index``: optional (B,) int32 per-row cache write positions
        (continuous-batching slot pool — every sequence sits at its own
        length). Overrides ``cache_index`` for both the cache write and the
        causal window, and positions must then come from ``position_ids``.
        Without ``q_spans`` it is decode-only (T == 1).

        ``q_spans``: optional (B,) int32 live query counts per row (chunked
        prefill fused into the decode step: decode rows carry span 1, the
        in-flight prefill row up to a chunk of T). Column ``j`` of row ``i``
        sits at absolute position ``write_index_i + j``; columns at or past
        the span are padding — their KV write is dropped and their outputs
        are garbage the caller never reads.

        ``ext_ops``: optional long-context extent operands ``(ext_table,
        wslot, ext_base, sinks, windows)`` — ``ext_table`` (B, E) int32 maps
        each row's logical extent i (tokens ``[i*S, (i+1)*S)``) to its pool
        row (-1 = demoted), ``wslot``/``ext_base`` locate the CURRENT write:
        the pool row holding the write head's extent and that extent's
        logical base, so the in-slot write target is ``write_index -
        ext_base``. ``write_index``/``q_spans``/``position_ids`` stay
        LOGICAL (may exceed S). ``sinks``/``windows`` (B,) int32 or None
        drive the lossy attention-sink/sliding-window mask (0 = lossless).
        Requires the flash span/decode paths — alibi, per-layer local
        windows, and the XLA fallback raise at trace time.

        ``seq_shard``: run the span attention sequence-parallel over the
        ``seq`` mesh axis (chunked prefill of long prompts); the KV write
        stays replicated so every shard's pool is byte-identical. Explicitly
        opt-in per program — ambient mesh detection would silently shard
        the reference chunked path.

        ``order``: the ZeRO-3 gather order of this module's projections
        (``GatherOrder``; training's unrolled forward only), or None.
        """
        cfg = self.cfg
        B, T, H = x.shape
        nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_size
        sub = (lambda name: None) if order is None else order.sub
        # under a ZeRO-3 gather order each projection's weight is gathered
        # behind the projection before it (a product hides ONE gather): q's
        # behind the layer below's attention (Block), then k's, v's and the
        # output projection's in turn
        behind = ((lambda y, name: y) if order is None else
                  (lambda y, name: order.due_behind(y, {f"{name}/kernel": self.variables[
                      "params"][name]["kernel"].astype(cfg.dtype)})))
        use_bias = cfg.attn_bias if cfg.attn_bias is not None else cfg.norm == "layernorm"
        # bhtd layout end-to-end: projections emit head-major
        i8, i8g = cfg.int8_weights, cfg.int8_group_size
        if i8 and cfg.int8_fused_qkv:
            # one [q;k;v] int8 matmul (reference fused qkv_gemm_int8,
            # pt_binding.cpp): 3 small pallas calls -> 1 wide one
            qw, sc = _q_param(self, "qkv", H, (nh + 2 * nkv) * hd, i8g)
            y = _qmm2d(x.reshape(B * T, H).astype(cfg.dtype), qw, sc)
            if use_bias:
                qkv_b = self.param("qkv_bias", nn.initializers.zeros,
                                   ((nh + 2 * nkv) * hd, ), jnp.float32)
                y = y + qkv_b.astype(y.dtype)
            q, k, v = jnp.split(y, [nh * hd, (nh + nkv) * hd], axis=-1)
            q = q.reshape(B, T, nh, hd).transpose(0, 2, 1, 3)
            k = k.reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)
            v = v.reshape(B, T, nkv, hd).transpose(0, 2, 1, 3)
        else:
            with jax.named_scope("attn_proj"):
                q = HeadProjection(nh, hd, use_bias, cfg.dtype, i8, i8g, name="q_proj")(
                    x, sub("q_proj"))
                q = behind(q, "k_proj")
                k = HeadProjection(nkv, hd, use_bias, cfg.dtype, i8, i8g, name="k_proj")(
                    x, sub("k_proj"))
                k = scaled(behind(k, "v_proj"), cfg.key_multiplier)
                v = HeadProjection(nkv, hd, use_bias, cfg.dtype, i8, i8g, name="v_proj")(
                    x, sub("v_proj"))
                v = behind(v, "o_proj")

        if lora_ops:
            # per-row adapter deltas land on the projection OUTPUTS (before
            # rope/attention), head-major to match; fp32 math inside the
            # helper, cast at the add
            def head_delta(site, heads):
                d = _lora_site_delta(x, lora_ops, site)
                if d is None:
                    return None
                return d.reshape(B, T, heads, hd).transpose(0, 2, 1, 3)
            dq, dk, dv = head_delta("q", nh), head_delta("k", nkv), head_delta("v", nkv)
            if dq is not None:
                q = q + dq.astype(q.dtype)
            if dk is not None:
                k = k + dk.astype(k.dtype)
            if dv is not None:
                v = v + dv.astype(v.dtype)

        if cfg.qk_norm:
            # over one head's values with the heads' shared weights, or over
            # the whole projection
            qk_norm = RMSNorm if cfg.qk_norm_per_head else ProjectionRMSNorm
            q = qk_norm(cfg.layernorm_epsilon, cfg.dtype, name="q_norm")(q)
            k = qk_norm(cfg.layernorm_epsilon, cfg.dtype, name="k_norm")(k)

        if cfg.layer_rotates(self.layer_idx):
            if position_ids is not None:
                pos_sin, pos_cos = sin[position_ids], cos[position_ids]  # (B, T, hd/2)
            elif cache_index is not None:
                pos_sin = jax.lax.dynamic_slice_in_dim(sin, cache_index, T, axis=0)
                pos_cos = jax.lax.dynamic_slice_in_dim(cos, cache_index, T, axis=0)
            else:
                pos_sin, pos_cos = sin[:T], cos[:T]
            rot = cfg.rotary_dim or hd
            if rot < hd:  # partial rotary (GPT-J/NeoX): pass-through tail dims
                rope_part = lambda x: jnp.concatenate(
                    [apply_rope(x[..., :rot], pos_sin, pos_cos), x[..., rot:]], axis=-1)
            else:
                rope_part = lambda x: apply_rope(x, pos_sin, pos_cos)
            q = rope_part(q)
            k = rope_part(k)
        alibi = alibi_slopes(nh) if cfg.pos_embedding == "alibi" else None
        if cfg.attn_scale is not None:
            # every downstream path divides scores by sqrt(hd); pre-scaling q
            # by attn_scale*sqrt(hd) nets the configured scale (GPT-Neo: 1.0)
            q = q * jnp.asarray(cfg.attn_scale * (hd ** 0.5), q.dtype)
        # sliding-window (local) attention for this layer (GPT-Neo pattern)
        window = (cfg.local_attention_window
                  if (cfg.local_attention_window and self.layer_idx >= 0
                      and self.layer_idx in cfg.local_attention_layers) else 0)
        # ... or by layer_windows, whose layer's slot holds a RING of rows
        ring_window = cfg.layer_window(self.layer_idx) if self.layer_idx >= 0 else 0

        if kv_cache is not None and ring_window:
            # a slot's ring of (rotated) keys and values, position p in row
            # p mod R (cache_spec): attended and committed by _ring_attention,
            # under the scope the windowed layers' share of a trace is read by
            _serves_by_spans("windowed full_attention", kv_cache, write_index, q_spans)
            if lora_ops or ext_ops is not None or seq_shard or attn_mask is not None:
                raise NotImplementedError("a windowed layer's ring serves without adapters, "
                                          "extent chains, sequence-parallel spans or padding "
                                          "masks")
            with jax.named_scope("swa_attn"):
                out, new_cache = _ring_attention(
                    cfg, q, k, v, kv_cache, write_index, q_spans, ring_window,
                    cfg.attention_impl == "flash" and _tp_mesh_size() == 1,
                    dict(block_kv=cfg.decode_block_kv, scale=hd ** -0.5))
            out = out.astype(cfg.dtype)
        elif kv_cache is not None:
            # cache layout (B, nkv, S, hd): contiguous (S, hd) slabs per head,
            # the shape the Pallas decode kernel streams (reference KV-cache
            # arena: csrc/transformer/inference/includes/inference_context.h).
            # k/v are already bhtd, so the cache write needs no transpose.
            #
            # At head size 64 the layer's K and V rest PACKED in one leaf
            # (B, nkv, S, 2 * hd), keys in lanes [0, hd) and values after
            # them (kv_packs): fresh rows are joined on the last axis
            # before the write, the paged kernels read the leaf as it is,
            # and the XLA paths take its two lane slices.
            #
            # int8 paged KV tier: a scale leaf beside the K/V leaves stores
            # group-quantized rows — ONE symmetric scale per written token
            # row, shared by K and V across every head (group = the row),
            # scale leaf (B, 1, S, 1) fp16. Fresh K/V quantize at write
            # time; the paged Pallas kernels dequantize in-register (bf16
            # KV never lands in HBM), the XLA fallback dequantizes before
            # attending.
            writes, kv_split, quant_kv = _kv_writes(cfg, kv_cache, k, v)
            if ext_ops is not None and write_index is not None and q_spans is not None:
                # long-context extent write: the chunk lands in the pool row
                # holding the write head's extent (wslot), at in-slot offset
                # write_index - ext_base. The scheduler clamps chunk takes to
                # the extent boundary, so one chunk never straddles extents.
                # Advanced-index axes move to the front: value is (B, T, ...)
                ext_table, wslot, ext_base, _snk, _wnd = ext_ops
                tgt = (write_index - ext_base)[:, None] + jnp.arange(T)[None, :]
                tgt = jnp.where(jnp.arange(T)[None, :] < q_spans[:, None], tgt,
                                writes[0][0].shape[2])
                with jax.named_scope("kv_commit"):
                    written = [
                        c.at[wslot[:, None], :, tgt].set(
                            kk.transpose(0, 2, 1, 3).astype(c.dtype), mode="drop")
                        for c, kk in writes]
                cache_index = write_index
            elif write_index is not None and q_spans is not None:
                # fused chunk/decode span write, in place where the paged
                # kernels below attend (the same conditions as their
                # branches, on one device)
                written = _commit_span_rows(
                    writes, write_index, q_spans,
                    paged_kernels=(cfg.attention_impl == "flash" and alibi is None
                                   and not seq_shard and (T == 1 or not window)))
                cache_index = write_index  # per-row causal window below
            elif write_index is not None:
                # slot-pool decode: each row appends at its own position
                upd = lambda c, kk, i: jax.lax.dynamic_update_slice_in_dim(
                    c, kk.astype(c.dtype), i, axis=1)
                written = [jax.vmap(upd)(c, kk, write_index) for c, kk in writes]
                cache_index = write_index  # per-row causal window below
            else:
                written = [jax.lax.dynamic_update_slice_in_dim(
                    c, kk.astype(c.dtype), cache_index, axis=2) for c, kk in writes]
            ck, cv, csc = _written_kv(written, kv_split, quant_kv)
            # bitwise-TP serving: the paged kernels shard over the tensor
            # axis (kv-head split, shard-local KV block walk) via shard_map
            # when the head counts divide; otherwise the plain call runs and
            # the engine's divisibility fallback keeps the pool replicated
            tp_kernel_shard = (cfg.bitwise_tp and _tp_mesh_size() > 1
                               and nkv % _tp_mesh_size() == 0
                               and nh % _tp_mesh_size() == 0)
            if ext_ops is not None or seq_shard:
                # long-context operands only compose with the fused flash
                # span/decode paths; a silent fall-through to the XLA
                # fallback (which knows nothing of extents) would read the
                # wrong rows, so unsupported combinations fail at trace time
                if (cfg.attention_impl != "flash" or alibi is not None or window
                        or write_index is None or q_spans is None):
                    raise ValueError(
                        "ext_ops/seq_shard require the fused flash span path "
                        "(attention_impl='flash', rope/none positions, no "
                        "per-layer local window, write_index + q_spans)")
                if seq_shard and tp_kernel_shard:
                    raise ValueError("seq-parallel prefill requires tensor "
                                     "parallelism of 1 (seq and tensor kernel "
                                     "sharding don't compose)")
            if (cfg.attention_impl == "flash" and T == 1 and alibi is None
                    and not seq_shard
                    and (write_index is not None or not quant_kv)):
                from ..ops.pallas.decode_attention import decode_attention, \
                    paged_decode_attention
                if attn_mask is not None:
                    starts = jnp.argmax(attn_mask.astype(jnp.int32), axis=1)
                else:
                    starts = jnp.zeros((B, ), jnp.int32)
                if window:
                    # a sliding window is just a raised start for one query
                    starts = jnp.maximum(starts, cache_index + 1 - window)
                if write_index is not None:
                    out = paged_decode_attention(
                        q[:, :, 0], ck, cv, starts, _attended_ends(write_index, q_spans),
                        block_kv=cfg.decode_block_kv,
                        **_paged_kernel_kw(csc, ext_ops, tp_kernel_shard))[:, :, None]
                else:
                    out = decode_attention(q[:, :, 0], ck, cv, starts, cache_index + 1,
                                           block_kv=cfg.decode_block_kv)[:, :, None]
            elif (cfg.attention_impl == "flash" and write_index is not None
                  and q_spans is not None and alibi is None and not window):
                # fused chunked-prefill + decode step over the slot pool:
                # per-row query spans through the span variant of the paged
                # decode kernel (each row's causal window advances with its
                # query column)
                from ..ops.pallas.decode_attention import \
                    paged_span_attention, seq_sharded_span_attention
                if attn_mask is not None:
                    starts = jnp.argmax(attn_mask.astype(jnp.int32), axis=1)
                else:
                    starts = jnp.zeros((B, ), jnp.int32)
                kw = _paged_kernel_kw(csc, ext_ops, tp_kernel_shard)
                base = _attended_ends(write_index, q_spans) - 1
                if seq_shard:
                    # sequence-parallel chunked prefill: shards split the
                    # chunk's query columns over the seq axis; KV (already
                    # written, replicated) streams whole on every shard
                    out = seq_sharded_span_attention(
                        q, ck, cv, starts, base, mesh=dist.get_mesh(),
                        axis=dist.SEQ_AXIS, block_kv=cfg.decode_block_kv, **kw)
                else:
                    out = paged_span_attention(q, ck, cv, starts, base,
                                               block_kv=cfg.decode_block_kv, **kw)
            elif (cfg.attention_impl == "flash" and attn_mask is None and T >= 128
                  and isinstance(cache_index, int) and cache_index == 0 and alibi is None
                  and not window):
                # unpadded prefill: nothing earlier in the cache, so attention
                # over the current tokens only — the flash kernel path
                # (GQA-native: no head expansion)
                from ..ops.pallas.flash_attention import sharded_flash_attention
                out = sharded_flash_attention(q, k, v, causal=True,
                                              block_q=cfg.attention_block_q,
                                              block_kv=cfg.attention_block_kv)
            else:
                if kv_split:
                    ck, cv = ck[..., :kv_split], ck[..., kv_split:]
                if quant_kv:
                    from ..ops.quantizer import dequantize_kv_rows
                    ck = dequantize_kv_rows(ck, csc, dtype=cfg.dtype)
                    cv = dequantize_kv_rows(cv, csc, dtype=cfg.dtype)
                out = _cached_attention_xla(q, ck, cv, cache_index, attn_mask,
                                            cfg.dtype, alibi=alibi, window=window)
            out = out.astype(cfg.dtype)
            new_cache = tuple(written)
        else:
            new_cache = None
            window = window or ring_window
            use_flash = (cfg.attention_impl == "flash" and T >= 128 and attn_mask is None
                         and alibi is None and not window)
            ring_possible = (cfg.sequence_parallel_impl == "ring" and dist.has_mesh()
                             and not dist.in_manual_region()
                             and dist.get_mesh().shape[dist.SEQ_AXIS] > 1)
            use_ring = use_flash and ring_possible
            if ring_possible and not use_flash:
                from ..utils.logging import warning_once
                warning_once("sequence_parallel_impl='ring' requested but this attention "
                             "call cannot use it (needs the flash path: T >= 128 and no "
                             "attention_mask) — falling back to full-sequence attention")
            if use_ring:
                from ..ops.pallas.ring_attention import ring_attention
                out = ring_attention(q, k, v, causal=True,
                                     block_q=cfg.attention_block_q,
                                     block_kv=cfg.attention_block_kv)
            else:
                if nkv != nh and not use_flash:  # the flash kernel is GQA-native
                    k = jnp.repeat(k, nh // nkv, axis=1)
                    v = jnp.repeat(v, nh // nkv, axis=1)
                S = k.shape[2]
                ulysses = _ulysses_specs(B, nh, k.shape[1])
                if ulysses is not None:
                    heads_spec, seq_q, seq_kv = ulysses
                    # pin BOTH sides of the all-to-all boundary: seq layout at
                    # the projection side (so the weight-grad contraction sees
                    # matching seq-sharded operands), head layout inside — the
                    # constraint's transpose rule pins the cotangents likewise
                    q = _constrain(q, seq_q)
                    k, v = _constrain(k, seq_kv), _constrain(v, seq_kv)
                    q = _constrain(q, heads_spec)
                    if k.shape[1] == nh:
                        k, v = _constrain(k, heads_spec), _constrain(v, heads_spec)
                if use_flash:
                    from ..ops.pallas.flash_attention import sharded_flash_attention
                    out = sharded_flash_attention(q, k, v, causal=True,
                                                  block_q=cfg.attention_block_q,
                                                  block_kv=cfg.attention_block_kv)
                else:
                    keep = jnp.tril(jnp.ones((T, S), dtype=bool))
                    if window:
                        rel = jnp.arange(T)[:, None] - jnp.arange(S)[None, :]
                        keep = keep & (rel < window)
                    bias = jnp.where(keep, 0.0, -1e30)[None, None]
                    if alibi is not None:
                        rel = (jnp.arange(T)[:, None] - jnp.arange(S)[None, :]).astype(jnp.float32)
                        bias = bias - alibi[None, :, None, None] * rel[None, None]
                    if attn_mask is not None:
                        bias = bias + jnp.where(attn_mask, 0.0, -1e30)[:, None, None, :].astype(jnp.float32)
                    interior = ulysses[0] if ulysses is not None else None
                    out = _sdpa_xla(q, k, v, bias, cfg.dtype, interior_spec=interior)
                    if ulysses is not None:
                        out = _constrain(out, heads_spec)
                if ulysses is not None:
                    out = _constrain(out, seq_q)

        if cfg.bitwise_tp:
            # bitwise-TP layout: gather the head-sharded attention output
            # (exact concat) so the replicated o_proj contracts its full
            # head*hd axis locally — no partial-sum reduction anywhere
            out = _tp_replicate(out)
        d_o = None
        if lora_ops:
            # o_proj delta reads the same bhtd input o_proj consumes
            o_in = out.transpose(0, 2, 1, 3).reshape(out.shape[0], out.shape[2],
                                                     nh * hd)
            d_o = _lora_site_delta(o_in, lora_ops, "o")
        with jax.named_scope("attn_proj"):
            out = OutProjection(H, use_bias, cfg.dtype, cfg.int8_weights,
                                cfg.int8_group_size, name="o_proj")(out, sub("o_proj"))
        if d_o is not None:
            out = out + d_o.reshape(out.shape).astype(out.dtype)
        return out, new_cache


def _latent_attention_xla(qf, lat, qpos, live_end, key_mask, score_scale, *, rank, block_kv,
                          dtype):
    """Absorbed latent attention against a latent cache, in XLA.

    ``qf``: (B, nh, T, rank + rope) absorbed queries ``[q_nope W_kvb^K ;
    RoPE(q_rope)]``; ``lat``: (B, rank + rope, S) the cache as it rests, a
    position a column ``[c_kv ; k_r]``, one for all heads; ``qpos``: (B or 1,
    T) absolute position of each query (its causal end); ``live_end``: one
    past the last position any live query attends; ``key_mask``: optional
    (B, S) attendable positions; ``score_scale``: (B or 1, T) fp32 ``scale *
    g(t)``. Returns ``sum_s p_s c_kv,s``: (B, nh, T, rank), for the value
    up-projection.

    An online softmax over key blocks, walked to the batch's longest live
    row only (a masked block leaves every accumulator bit-unchanged, so a
    row's result does not depend on how far its neighbours reach). The block
    narrows for wide spans so that one score plane stays near 256 MB. Both
    products take a block ``(B, D, blk)`` with its positions minor, the form
    the leaf rests in: nothing is transposed for them."""
    B, nh, T, _ = qf.shape
    S = lat.shape[2]
    blk = min(block_kv, S)
    while blk > 64 and B * nh * T * blk > (1 << 26) and S % (blk // 2) == 0:
        blk //= 2
    if S % blk:
        blk = S
    n_live = jnp.clip((live_end + blk - 1) // blk, 1, S // blk)

    def body(j, carry):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(lat, j * blk, blk, axis=2)  # (B, D, blk)
        s = jnp.einsum("bntd,bds->bnts", qf, kb, preferred_element_type=jnp.float32)
        s = s * score_scale[:, None, :, None]
        kpos = j * blk + jnp.arange(blk)
        keep = kpos[None, None, :] <= qpos[:, :, None]  # (B or 1, T, blk)
        if key_mask is not None:
            keep = keep & jax.lax.dynamic_slice_in_dim(key_mask, j * blk, blk, axis=1)[:, None, :]
        s = jnp.where(keep[:, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bnts,brs->bntr", p.astype(dtype), kb[:, :rank],
                        preferred_element_type=jnp.float32)
        return m_new, l * alpha + jnp.sum(p, axis=-1), acc * alpha[..., None] + pv

    init = (jnp.full((B, nh, T), -jnp.inf, jnp.float32), jnp.zeros((B, nh, T), jnp.float32),
            jnp.zeros((B, nh, T, rank), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_live, body, init)
    return (acc / jnp.where(l == 0, 1.0, l)[..., None]).astype(dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434, as
    ``mistral4`` configures it). Per position the cache holds ONE vector of
    ``kv_lora_rank + qk_rope_head_dim`` values for all heads: the normalised
    kv latent ``c_kv`` and the rotated shared key part ``k_r``; per-head K
    and V are never stored. At rest it is a COLUMN of the layer's leaf ``(B,
    1, rank + rope, S)``: the absorbed form's two products contract and
    produce over a block's positions, and take them minor; the span commit
    sets columns in place (:func:`_commit_span_columns`), so a sync carries
    the leaf in one form from entry to exit.

        c_q = RMSNorm(a W_qa) ; q_i = c_q W_qb,i = [q_nope_i ; q_rope_i]
        [c_kv ; k_r] = a W_kva ; c_kv = RMSNorm(c_kv) ; k_r = RoPE(k_r)
        [k_nope_i ; v_i] = c_kv W_kvb,i
        s_ts,i = (q_nope_i . k_nope_i + RoPE(q_rope_i) . k_r) scale g(t)

    With ``q_lora_rank`` 0 the query is ONE projection ``q_i = a W_q,i``
    (``bailing_hybrid``); with ``attn_head_gate`` a head's output is gated
    before ``W_o``: ``y = [sigmoid(a W_gate)_i o_i]_i W_o``, one gate a head
    (traced under ``mla_proj``, with the other projections). Under
    ``layer_types`` the ``full_attention`` layers of a configuration with
    ``kv_lora_rank`` are latent, and their one leaf lies beside the other
    layers' state in the cache tree.

    Without a cache (full forward) the EXPANDED form computes per-head K
    and V from ``c_kv``. With a cache (static generate, slot-pool decode and
    chunked-prefill spans alike) the ABSORBED form attends the latent columns
    directly: ``q~_i = q_nope_i W_kvb,i^K^T`` against ``c_kv``, and ``o_i =
    (sum p c_kv) W_kvb,i^V``. The call signature is :class:`Attention`'s;
    adapters, extent chains and sequence-parallel spans are refused."""
    cfg: TransformerConfig
    layer_idx: int = -1

    @nn.compact
    def __call__(self, x, sin, cos, attn_mask=None, kv_cache=None, cache_index=None,
                 position_ids=None, write_index=None, q_spans=None, lora_ops=None,
                 ext_ops=None, seq_shard=False):
        import math
        cfg = self.cfg
        B, T, H = x.shape
        nh, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        if lora_ops or ext_ops is not None or seq_shard:
            raise NotImplementedError("latent attention serves without adapters, extent "
                                      "chains or sequence-parallel spans")
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32,
                        kernel_init=nn.initializers.normal(0.02))
        norm = partial(RMSNorm, epsilon=cfg.layernorm_epsilon, dtype=cfg.dtype)
        with jax.named_scope("mla_proj"):
            if cfg.q_lora_rank:
                c_q = norm(name="q_a_norm")(dense(cfg.q_lora_rank, name="q_a_proj")(x))
                q = HeadProjection(nh, nope + rope, False, cfg.dtype, name="q_b_proj")(c_q)
            else:  # ONE query projection
                q = HeadProjection(nh, nope + rope, False, cfg.dtype, name="q_proj")(x)
            if cfg.attn_head_gate:  # (B, nh, T, 1): one gate a head
                head_gate = jax.nn.sigmoid(dense(nh, name="g_proj")(x).astype(jnp.float32))
                head_gate = head_gate.transpose(0, 2, 1)[..., None].astype(cfg.dtype)
            kv_a = dense(rank + rope, name="kv_a_proj")(x)
            c_kv = norm(name="kv_a_norm")(kv_a[..., :rank])  # (B, T, rank)
            # (rank, nh, nope + v): k_nope and v of every head from the latent
            w_kvb = self.param("kv_b_proj", nn.initializers.normal(0.02),
                               (rank, nh, nope + vd), jnp.float32).astype(cfg.dtype)

            if position_ids is not None:
                pos = position_ids  # (B, T)
            elif write_index is not None:
                pos = write_index[:, None] + jnp.arange(T)[None, :]
            else:
                pos = ((0 if cache_index is None else cache_index) + jnp.arange(T))[None, :]
            rotate = apply_rope_interleaved if cfg.rope_interleave else apply_rope
            pos_sin, pos_cos = sin[pos], cos[pos]  # (B or 1, T, rope/2)
            q_rope = rotate(q[..., nope:], pos_sin, pos_cos)  # (B, nh, T, rope)
            k_r = rotate(kv_a[:, None, :, rank:], pos_sin, pos_cos)  # (B, 1, T, rope)

            # softmax scale: head width and YaRN's magnitude (squared: it is
            # meant for q and k alike), then the position-dependent g(t)
            scale = (nope + rope) ** -0.5 * yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
            score_scale = jnp.full(pos.shape, scale, jnp.float32)
            if cfg.attn_temp_beta:
                score_scale = score_scale * (1.0 + cfg.attn_temp_beta * jnp.log1p(
                    jnp.floor(pos.astype(jnp.float32) / cfg.rope_original_max_len)))

        if kv_cache is None:
            # expanded form: per-head K and V of this call's own tokens
            with jax.named_scope("mla_attn"):
                kv = jnp.einsum("btr,rnd->bntd", c_kv, w_kvb)
                k = jnp.concatenate([kv[..., :nope],
                                     jnp.broadcast_to(k_r, (B, nh, T, rope))], axis=-1)
                qf = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
                s = jnp.einsum("bnqd,bnkd->bnqk", qf, k, preferred_element_type=jnp.float32)
                s = s * score_scale[:, None, :, None]
                keep = jnp.tril(jnp.ones((T, T), bool))[None, None]
                if attn_mask is not None:
                    keep = keep & attn_mask[:, None, None, :]
                probs = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1).astype(cfg.dtype)
                out = jnp.einsum("bnqk,bnkd->bnqd", probs, kv[..., nope:])
            new_cache = None
        else:
            # (B, 1, rank + rope, S), a position a column; a wider tree's
            # other places hold nothing
            pool = kv_cache[0]
            fresh = jnp.concatenate([c_kv[:, None], k_r], axis=-1).astype(pool.dtype)
            if write_index is not None and q_spans is not None:
                pool = _commit_span_columns(pool, fresh, write_index, q_spans)
            elif write_index is not None:
                pool = jax.vmap(lambda c, r, i: jax.lax.dynamic_update_slice_in_dim(
                    c, r, i, axis=2))(pool, jnp.swapaxes(fresh, 2, 3), write_index)
            else:
                pool = jax.lax.dynamic_update_slice_in_dim(
                    pool, jnp.swapaxes(fresh, 2, 3), cache_index, axis=3)
            with jax.named_scope("mla_attn"):
                q_lat = jnp.einsum("bntd,rnd->bntr", q[..., :nope], w_kvb[..., :nope])
                qf = jnp.concatenate([q_lat, q_rope], axis=-1)  # (B, nh, T, rank + rope)
                # padding columns past a row's span attend garbage nobody
                # reads: the block walk stops at the last LIVE query
                live_end = (jnp.max(pos) + 1 if q_spans is None
                            else jnp.max(write_index + jnp.maximum(q_spans, 1)))
                o_lat = _latent_attention_xla(
                    qf, pool[:, 0].astype(cfg.dtype), pos, live_end, attn_mask, score_scale,
                    rank=rank, block_kv=cfg.decode_block_kv, dtype=cfg.dtype)
                out = jnp.einsum("bntr,rnd->bntd", o_lat, w_kvb[..., nope:])
            new_cache = (pool, ) + (None, ) * (len(kv_cache) - 1)
        with jax.named_scope("mla_proj"):
            out = out.astype(cfg.dtype)
            if cfg.attn_head_gate:
                out = out * head_gate
            out = OutProjection(H, False, cfg.dtype, name="o_proj")(out)
        return out, new_cache


GDN_CHUNK = 64  # positions the gated-delta scan solves together
KDA_CHUNK = 16  # ... with a decay a key channel, bounded below by -5 a step: e^(16 x 5) < 3.4e38
GDN_L2_EPS = 1e-6  # under the root of q's and k's L2 norm: a zero vector stays zero


def gdn_conv_init(key, shape, dtype=jnp.float32):
    """U(-W^-1/2, W^-1/2) over ``(channels, W)``: a depthwise convolution's
    usual start."""
    bound = shape[-1] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def gdn_a_log_init(key, shape, dtype=jnp.float32):
    """``A_log`` of a gated-delta layer as the layer is published to start:
    the log of A ~ U(0, 16] a head, so heads forget at different rates."""
    return jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape, jnp.float32))).astype(dtype)


def gdn_dt_bias_init(key, shape, dtype=jnp.float32):
    """``dt_bias`` of a gated-delta layer as published: the inverse softplus
    of dt, log-uniform in [0.001, 0.1]."""
    lo, hi = jnp.log(0.001), jnp.log(0.1)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (hi - lo) + lo), 1e-4)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def gated_delta_step(S, q, k, v, g, beta):
    """The gated delta rule for ONE token, float32: ``S`` (B, n, dk, dv),
    ``q``/``k`` (B, n, dk), ``v`` (B, n, dv), ``g`` (log decay) and ``beta``
    (B, n). ``S' = a S + beta k (v - a S^T k)^T`` with ``a = exp(g)``;
    returns ``(S'^T q, S')``. ``g`` (B, n, dk) is a decay a KEY CHANNEL (Kimi
    delta attention): ``a S`` is then ``Diag(a) S``, row ``d`` of the state
    scaled by ``a_d``. Elementwise products and sums over ``dk``: a
    slot's state is read and written once, nothing runs on tiny matrices."""
    S = S * (jnp.exp(g)[..., None] if g.ndim == k.ndim else jnp.exp(g)[..., None, None])
    u = beta[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
    S = S + k[..., None] * u[..., None, :]
    return jnp.sum(S * q[..., None], axis=-2), S


def gated_delta_chunked(S, q, k, v, g, beta, chunk=None):
    """The same recurrence over ``T`` tokens, chunk by chunk, float32:
    ``q``/``k`` (B, n, T, dk), ``v`` (B, n, T, dv), ``g``/``beta`` (B, n,
    T), ``S`` the incoming state. With ``G_t`` the running sum of ``g``
    inside a chunk and ``u_t = beta_t (v_t - a_t S_{t-1}^T k_t)``:

        (I + A) U = diag(beta) (V - diag(e^G) K S_0),
        A[t, s] = beta_t e^(G_t - G_s) k_t . k_s   (s < t: unit lower triangular)
        O = diag(e^G) Q S_0 + tril(Q K^T * e^(G_t - G_s)) U
        S_C = e^(G_C) S_0 + (K * e^(G_C - G_s))^T U

    A token with ``beta`` 0 and ``g`` 0 (padding up to a whole chunk, a
    column past a row's span) leaves the state as it is. Returns ``(O (B, n,
    T, dv), S_T)``. The small products run at ``highest`` precision: their
    operands are float32 that bfloat16 passes would round.

    ``g`` (B, n, T, dk) is a decay a KEY CHANNEL: ``e^(G_t - G_s)`` no longer
    factors out of ``k_t . k_s``, whose terms each carry their channel's.
    The same equations then hold with the decay inside the products,

        A[t, s] = beta_t (k_t * e^(G_t - G_m)) . (k_s * e^(G_m - G_s)),   diag(e^G) K S_0 -> (K * e^G) S_0,

    (``m`` the chunk's middle position) in chunks of :data:`KDA_CHUNK`
    positions: an exponent reaches 8 x 5 at the bounded gate's floor of -5 a
    step (16 x 5 in a chunk's masked corner), inside float32, where 64
    positions' ``e^320`` would not be. A sum of 40 is known to 4e-6 in
    float32, so at the floor the factors carry that much relative error;
    nearer 0 they are exact to rounding."""
    B, n, T, dk = q.shape
    channel = g.ndim == 4
    chunk = chunk or (KDA_CHUNK if channel else GDN_CHUNK)
    pad = -T % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))) for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0), ) * (x.ndim - 3))
                   for x in (g, beta))
    nc = (T + pad) // chunk
    # (nc, B, n, chunk, ...): the scan walks the chunks
    split = lambda x: jnp.moveaxis(x.reshape(x.shape[:2] + (nc, chunk) + x.shape[3:]), 2, 0)
    mm = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    eye = jnp.eye(chunk, dtype=jnp.float32)

    def body(S, xs):
        qc, kc, vc, gc, bc = xs
        G = jnp.cumsum(gc, axis=-1)
        decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
        gam = jnp.exp(G)[..., None]
        A = jnp.where(strict, bc[..., None] * decay * mm("bntd,bnsd->bnts", kc, kc), 0.0)
        rhs = bc[..., None] * (vc - gam * mm("bntd,bndv->bntv", kc, S))
        U = jax.scipy.linalg.solve_triangular(eye + A, rhs, lower=True, unit_diagonal=True)
        o = (gam * mm("bntd,bndv->bntv", qc, S)
             + mm("bnts,bnsv->bntv", decay * mm("bntd,bnsd->bnts", qc, kc), U))
        S = (gam[..., -1:, :] * S
             + mm("bnsd,bnsv->bndv", kc * jnp.exp(G[..., -1:] - G)[..., None], U))
        return S, o

    def body_channel(S, xs):
        qc, kc, vc, gc, bc = xs
        G = jnp.cumsum(gc, axis=-2)  # (B, n, chunk, dk)
        # e^(G_t - G_s) = e^(G_t - G_m) e^(G_m - G_s) about the chunk's middle
        # position m: exponents of half the chunk's reach, half the rounding
        mid = G[..., chunk // 2:chunk // 2 + 1, :]
        gam, up, down = jnp.exp(G), jnp.exp(G - mid), jnp.exp(mid - G)
        A = jnp.where(strict, bc[..., None] * mm("bntd,bnsd->bnts", kc * up, kc * down), 0.0)
        rhs = bc[..., None] * (vc - mm("bntd,bndv->bntv", kc * gam, S))
        U = jax.scipy.linalg.solve_triangular(eye + A, rhs, lower=True, unit_diagonal=True)
        o = (mm("bntd,bndv->bntv", qc * gam, S)
             + mm("bnts,bnsv->bntv",
                  jnp.where(lower, mm("bntd,bnsd->bnts", qc * up, kc * down), 0.0), U))
        S = (gam[..., -1, :, None] * S
             + mm("bnsd,bnsv->bndv", kc * jnp.exp(G[..., -1:, :] - G), U))
        return S, o

    S, o = jax.lax.scan(body_channel if channel else body, S,
                        tuple(split(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, n, T + pad, -1)
    return o[:, :, :T], S


def last_live_inputs(seq, q_spans, n):
    """Rows ``[span, span + n)`` of ``seq`` (B, n + T, C), a convolution's ``n``
    carried inputs in front of a call's T columns: the last ``n`` LIVE inputs
    of a row whose first ``span`` columns are live. A one-hot product (a
    per-row gather would rest with the ``n`` rows in the lanes, padded
    forty-fold)."""
    rows = q_spans[:, None] + jnp.arange(n)[None, :]
    pick = (rows[:, :, None] == jnp.arange(seq.shape[1])[None, None, :])
    return jnp.einsum("bjt,btc->bjc", pick.astype(seq.dtype), seq,
                      precision=jax.lax.Precision.HIGHEST)


class GatedDeltaNet(nn.Module):
    """Linear attention by the gated delta rule (Gated DeltaNet,
    arXiv:2412.06464, as ``olmo_hybrid``'s ``linear_*`` keys configure it):
    the mixer of a ``linear_attention`` layer. Per head of ``dk`` key and
    ``dv`` value dimensions it carries a state ``S`` (dk, dv) and no rows:

        [q~ ; k~ ; v~] = x [W_q ; W_k ; W_v] ; u_t = SiLU(sum_j w[:, j] u~_(t-W+1+j))
        q = q / |q| dk^-1/2 ; k = k / |k|
        beta = sigmoid(x W_b) (x 2 with linear_neg_eigval) ; g = -exp(A_log) softplus(x W_a + dt_bias)
        S_t = e^g S_(t-1) + beta k (v - e^g S_(t-1)^T k)^T ; o_t = S_t^T q_t
        y = [RMSNorm_dv(o) * SiLU(x W_g)] W_o

    Kimi delta attention (KDA, arXiv:2510.26692, as ``bailing_hybrid``
    configures it) is the same mixer with the decay one value a KEY CHANNEL
    (``linear_channel_decay``: ``W_a`` hidden -> n dk, full rank, ``dt_bias`` one
    a channel, ``A_log`` one a head), the log decay bounded
    (``linear_decay_lower_bound`` lb < 0: ``g = lb sigmoid(exp(A_log) (x W_a +
    dt_bias))``, in (lb, 0)), and a sigmoid output gate (``linear_out_gate``):

        S_t = (I - beta k k^T) Diag(e^g) S_(t-1) + beta k v^T ; y = [RMSNorm_dv(o) * sigmoid(x W_g)] W_o

    One mixer, one set of scopes and counters, one one-token kernel for both
    decays (the head's scalar is the constant vector).

    What a slot holds for such a layer (``init_cache``): the state and the
    convolution's last ``W - 1`` inputs ``(B, 1, W - 1, n (2 dk + dv))``,
    both at rest in the serving dtype, loaded to float32 and rounded once on
    the store. The state rests as ``(B, n / p, dk, p dv)``, ``p`` heads side
    by side in the lanes (``ops/pallas/gdn_step.py: state_packing``: the
    smallest ``p`` that makes a row whole 128-lane tiles, 2 at the published
    ``dv`` 192; 1, the plain ``(B, n, dk, dv)``, where there is none): a row
    of 192 lanes rests and moves padded to 256. The ONE-TOKEN update (``T ==
    1``: the decode column and its substeps) of such a leaf runs as the
    Pallas kernel of that file, in place, where :class:`Attention` would
    take its paged kernels (one device, ``attention_impl == "flash"``) and
    the head shape tiles (``gdn_step.tiles``); everything else (a chunk's
    scan, which converts its own slot's state on load and store; a sharded
    pool; heads that do not tile) is :func:`gated_delta_step` /
    :func:`gated_delta_chunked`, the definition. Without a cache (full
    forward) the scan starts from zero. With one it is served through the
    slot pool's span programs only (``write_index`` + ``q_spans``): a row
    advances over exactly its ``q_spans`` live columns (later columns get
    beta 0 and g 0, the window is taken from the last live inputs), a span-0
    row's leaves come out bit for bit as they went in, and a row whose span
    starts at position 0 starts from a zero state and window whatever the
    slot held.
    The call signature is :class:`Attention`'s; adapters, extent chains,
    sequence-parallel spans and padding masks are refused."""
    cfg: TransformerConfig
    layer_idx: int = -1

    @nn.compact
    def __call__(self, x, sin, cos, attn_mask=None, kv_cache=None, cache_index=None,
                 position_ids=None, write_index=None, q_spans=None, lora_ops=None,
                 ext_ops=None, seq_shard=False):
        cfg = self.cfg
        B, T, H = x.shape
        n, dk, dv = cfg.linear_num_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
        W = cfg.linear_conv_kernel
        if lora_ops or ext_ops is not None or seq_shard or attn_mask is not None:
            raise NotImplementedError("a linear-attention layer serves without adapters, "
                                      "extent chains, sequence-parallel spans or padding masks")
        if kv_cache is not None and (write_index is None or q_spans is None):
            raise NotImplementedError(
                "a linear-attention layer's state is served through the slot pool's span "
                "programs (the continuous-batching scheduler): the static-batch cache "
                "paths have no per-row spans to advance it by")
        f32 = jnp.float32
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype, param_dtype=f32,
                        kernel_init=nn.initializers.normal(0.02))
        with jax.named_scope("gdn_proj"):
            mixed = jnp.concatenate([dense(n * dk, name="q_proj")(x), dense(n * dk, name="k_proj")(x),
                                     dense(n * dv, name="v_proj")(x)], axis=-1)
            gate = dense(n * dv, name="g_proj")(x)
            beta = jax.nn.sigmoid(dense(n, name="b_proj")(x).astype(f32))
            if cfg.linear_neg_eigval:
                beta = 2.0 * beta
            a_log = self.param("A_log", gdn_a_log_init, (n, ), f32)
            # the log decay: one a head (B, T, n), or one a key channel (B, T, n, dk)
            wide = n * dk if cfg.linear_channel_decay else n
            dt_bias = self.param("dt_bias", gdn_dt_bias_init, (wide, ), f32)
            # (the rate before the projection, as the head's form always traced it:
            # cell 5's programs are held to their lowered text, test_tpu_compile.py)
            neg_rate = -jnp.exp(a_log)
            raw = dense(wide, name="a_proj")(x).astype(f32) + dt_bias
            if cfg.linear_channel_decay:
                raw, neg_rate = raw.reshape(B, T, n, dk), neg_rate[:, None]
            if cfg.linear_decay_lower_bound:
                g = cfg.linear_decay_lower_bound * jax.nn.sigmoid(-neg_rate * raw)
            else:
                g = neg_rate * jax.nn.softplus(raw)
            conv_w = self.param("conv", gdn_conv_init, (cfg.linear_conv_channels, W), f32)
            in_place = False
            if kv_cache is None:
                state = jnp.zeros((B, n, dk, dv), f32)
                window = jnp.zeros((B, W - 1, mixed.shape[-1]), cfg.dtype)
            else:
                state_rest, window_rest = kv_cache
                packing = gdn_step.state_packing(n, dv)
                live_row = q_spans > 0
                fresh = live_row & (write_index == 0)
                # the rule _commit_span_rows takes its in-place kernel by
                in_place = (T == 1 and cfg.attention_impl == "flash" and _tp_mesh_size() == 1
                            and gdn_step.tiles(state_rest, n, dk, dv, cfg.linear_channel_decay))
                if T == 1:
                    gdn_step.tally(in_place)
                if not in_place:
                    state = jnp.where(fresh[:, None, None, None], 0.0,
                                      gdn_step.unpack_state(state_rest, packing).astype(f32))
                window = jnp.where(fresh[:, None, None], 0, window_rest[:, 0]).astype(cfg.dtype)
                live = (jnp.arange(T)[None, :] < q_spans[:, None])[..., None]
                beta = jnp.where(live, beta, 0.0)
                g = jnp.where(live[..., None] if cfg.linear_channel_decay else live, g, 0.0)
            # causal depthwise convolution over [window ; this call's inputs]
            seq = jnp.concatenate([window, mixed.astype(cfg.dtype)], axis=1)
            conv = sum(seq[:, j:j + T].astype(f32) * conv_w[:, j] for j in range(W))
            u = jax.nn.silu(conv).astype(cfg.dtype)
            heads = lambda y, d: y.reshape(B, T, n, d).transpose(0, 2, 1, 3).astype(f32)
            q, k = heads(u[..., :n * dk], dk), heads(u[..., n * dk:2 * n * dk], dk)
            v = heads(u[..., 2 * n * dk:], dv)
            l2 = lambda y: y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + GDN_L2_EPS)
            q, k = l2(q) * dk ** -0.5, l2(k)
            # (B, n, T), a channel decay (B, n, T, dk)
            g, beta = jnp.swapaxes(g, 1, 2), beta.transpose(0, 2, 1)
        with jax.named_scope("gdn_state"):
            if T == 1:
                column = (q[:, :, 0], k[:, :, 0], v[:, :, 0], g[:, :, 0], beta[:, :, 0])
                if in_place:
                    o, new_state = gdn_step.gated_delta_update(state_rest, *column,
                                                               live_row, fresh)
                else:
                    o, state = gated_delta_step(state, *column)
                o = o[:, :, None]
            else:
                o, state = gated_delta_chunked(state, q, k, v, g, beta)
            if kv_cache is None:
                new_cache = None
            else:
                # the last W - 1 LIVE inputs: rows [span, span + W - 1) of seq.
                # One column: the old window or the one a step on
                if T == 1:
                    tail = jnp.where(live_row[:, None, None], seq[:, 1:], seq[:, :-1])
                else:
                    tail = last_live_inputs(seq, q_spans, W - 1)
                if not in_place:
                    new_state = jnp.where(
                        live_row[:, None, None, None],
                        gdn_step.pack_state(state.astype(state_rest.dtype), packing), state_rest)
                new_cache = (
                    new_state,
                    jnp.where(live_row[:, None, None, None],
                              tail[:, None].astype(window_rest.dtype), window_rest))
        with jax.named_scope("gdn_out"):
            o = RMSNorm(epsilon=cfg.layernorm_epsilon, dtype=cfg.dtype, name="o_norm")(o)
            out_gate = jax.nn.sigmoid if cfg.linear_out_gate == "sigmoid" else jax.nn.silu
            o = o * out_gate(gate.reshape(B, T, n, dv).transpose(0, 2, 1, 3))
            out = OutProjection(H, False, cfg.dtype, name="o_proj")(o)
        return out, new_cache


def mamba_a_log_init(key, shape, dtype=jnp.float32):
    """``A_log`` of a Mamba layer as published (the S4D-real start): the log
    of 1..d_state for every channel, so a channel's 16 states forget at 16
    rates."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
                            shape).astype(dtype)


def selective_scan_step(h, delta, x, Bm, Cm, A, D):
    """Mamba-1's recurrence for ONE token, float32: ``h`` (B, ds, di) the
    state, ``delta``/``x`` (B, di), ``Bm``/``Cm`` (B, ds), ``A`` (ds, di),
    ``D`` (di,). ``h' = exp(delta A) h + (delta x) (x) B``; returns ``(h' C +
    D x, h')``. A token with ``delta`` 0 leaves the state as it is."""
    h = jnp.exp(delta[:, None, :] * A) * h + (delta * x)[:, None, :] * Bm[:, :, None]
    return jnp.sum(h * Cm[:, :, None], axis=1) + D * x, h


def _grouped_attention_xla(q, k, v, keep, scale, dtype):
    """Masked grouped-query attention in XLA: ``q`` (B, nh, T, D), ``k``/``v``
    (B, nkv, S, D), ``keep`` (B or 1, T, S) bool. Softmax in float32; a
    query that keeps nothing (padding) reads a mean, never a NaN."""
    B, nh, T, D = q.shape
    nkv = k.shape[1]
    qg = q.reshape(B, nkv, nh // nkv, T, D)
    scores = jnp.einsum("bkgtd,bksd->bkgts", qg, k).astype(jnp.float32) * scale
    scores = jnp.where(keep[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bkgts,bksd->bkgtd", probs, v).reshape(B, nh, T, D)


def _serves_by_spans(kind, kv_cache, write_index, q_spans):
    """The SambaY mixers take a cache through the slot pool's span programs only."""
    if kv_cache is not None and (write_index is None or q_spans is None):
        raise NotImplementedError(
            f"a {kind} layer's slot is served through the slot pool's span programs (the "
            f"continuous-batching scheduler): the static-batch cache paths have no "
            f"per-row spans to advance it by")


class Mamba(nn.Module):
    """A selective state-space layer (Mamba-1, arXiv:2312.00752), the mixer
    of a ``mamba`` layer:

        [x~ ; z] = u W_in ; x_t = SiLU(sum_j w[:, j] x~_(t-W+1+j) + b_c)
        [d ; B ; C] = x W_x ; Delta = softplus(d W_dt + b_dt) ; A = -exp(A_log)
        h_t = exp(Delta_t A) h_(t-1) + (Delta_t x_t) (x) B_t ; y_t = h_t C_t + D x_t
        out = (y * SiLU(z)) W_out

    It hands ``y`` (before the gate) on to the gated memory units above it
    (``carry["m"]``). What a slot holds for it: the state, at rest ``(B, 1,
    d_state, d_inner)`` (the channels in the lanes), and the convolution's
    last ``W - 1`` inputs ``(B, 1, W - 1, d_inner)``, both in the serving
    dtype, loaded to float32 and rounded once on the store. The rules of a
    span program are :class:`GatedDeltaNet`'s: a row advances over exactly
    its ``q_spans`` live columns (later columns get Delta 0), a span-0 row's
    leaves come out bit for bit, a span at position 0 starts from zero."""
    cfg: TransformerConfig
    layer_idx: int = -1

    @nn.compact
    def __call__(self, x, kv_cache=None, write_index=None, q_spans=None, carry=None):
        cfg = self.cfg
        B, T, H = x.shape
        di, ds, W, r = cfg.ssm_inner, cfg.ssm_state_size, cfg.ssm_conv_kernel, cfg.ssm_dt_rank
        _serves_by_spans("mamba", kv_cache, write_index, q_spans)
        f32 = jnp.float32
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype, param_dtype=f32,
                        kernel_init=nn.initializers.normal(0.02))
        with jax.named_scope("ssm_proj"):
            xz = dense(2 * di, name="in_proj")(x)
            conv_w = self.param("conv", gdn_conv_init, (di, W), f32)
            conv_b = self.param("conv_bias", nn.initializers.zeros, (di, ), f32)
            if kv_cache is None:
                state = jnp.zeros((B, ds, di), f32)
                window = jnp.zeros((B, W - 1, di), cfg.dtype)
            else:
                state_rest, window_rest = kv_cache
                live_row = q_spans > 0
                fresh = live_row & (write_index == 0)
                state = jnp.where(fresh[:, None, None], 0.0, state_rest[:, 0].astype(f32))
                window = jnp.where(fresh[:, None, None], 0, window_rest[:, 0]).astype(cfg.dtype)
            seq = jnp.concatenate([window, xz[..., :di].astype(cfg.dtype)], axis=1)
            conv = sum(seq[:, j:j + T].astype(f32) * conv_w[:, j] for j in range(W)) + conv_b
            u = jax.nn.silu(conv).astype(cfg.dtype)
            dbc = dense(r + 2 * ds, name="x_proj")(u)
            dt_bias = self.param("dt_bias", gdn_dt_bias_init, (di, ), f32)
            delta = jax.nn.softplus(dense(di, name="dt_proj")(dbc[..., :r]).astype(f32) + dt_bias)
            if kv_cache is not None:
                delta = jnp.where((jnp.arange(T)[None, :] < q_spans[:, None])[..., None],
                                  delta, 0.0)
            Bm, Cm = dbc[..., r:r + ds].astype(f32), dbc[..., r + ds:].astype(f32)
            A = -jnp.exp(self.param("A_log", mamba_a_log_init, (di, ds), f32)).T
            D = self.param("D", nn.initializers.ones, (di, ), f32)
        with jax.named_scope("ssm_state"):
            uf = u.astype(f32)
            if T == 1:
                y, state = selective_scan_step(state, delta[:, 0], uf[:, 0], Bm[:, 0], Cm[:, 0],
                                               A, D)
                y = y[:, None]
            else:
                def body(h, xs):
                    y_t, h = selective_scan_step(h, *xs, A, D)
                    return h, y_t
                state, y = jax.lax.scan(
                    body, state, tuple(jnp.moveaxis(a, 1, 0) for a in (delta, uf, Bm, Cm)),
                    unroll=min(T, 8))
                y = jnp.moveaxis(y, 0, 1)
            if kv_cache is None:
                new_cache = None
            else:
                # the last W - 1 LIVE inputs: rows [span, span + W - 1) of seq
                if T == 1:
                    tail = jnp.where(live_row[:, None, None], seq[:, 1:], seq[:, :-1])
                else:
                    tail = last_live_inputs(seq, q_spans, W - 1)
                keep = live_row[:, None, None, None]
                new_cache = (
                    jnp.where(keep, state[:, None].astype(state_rest.dtype), state_rest),
                    jnp.where(keep, tail[:, None].astype(window_rest.dtype), window_rest))
            m = y.astype(cfg.dtype)
        with jax.named_scope("ssm_out"):
            out = dense(H, name="out_proj")(m * jax.nn.silu(xz[..., di:]))
        return out, new_cache, dict(carry, m=m)


class ShortConv(nn.Module):
    """A gated short convolution (``lfm2``'s ``conv`` operator), the mixer of a
    ``short_conv`` layer:

        [B ; C ; X] = u W_in ; z_t = B_t * X_t
        c_t = sum_j w[:, j] z_(t-W+1+j) ; out = (C_t * c_t) W_out

    a causal depthwise convolution of ``W = short_conv_kernel`` taps over the
    gated input, no bias, no activation, gated again on the way out. No
    recurrence beyond the taps: what a slot holds for it is ``z`` of its last
    ``W - 1`` positions, ONE leaf ``(B, 1, W - 1, hidden)`` at rest in the
    serving dtype (``cache_spec``). Products in the compute dtype, the sum
    over the taps in float32. Without a cache (full forward) the positions
    before 0 read zeros. With one it is served through the slot pool's span
    programs only, by :class:`GatedDeltaNet`'s rules: a row advances over
    exactly its ``q_spans`` live columns (the rows left behind are the last
    ``W - 1`` LIVE inputs, so a chunk of one live position keeps one old
    row), a span-0 row's leaf comes out bit for bit as it went in, and a row
    whose span starts at position 0 starts from zeros whatever the slot held.
    The call signature is :class:`Attention`'s; adapters, extent chains,
    sequence-parallel spans and padding masks are refused."""
    cfg: TransformerConfig
    layer_idx: int = -1

    @nn.compact
    def __call__(self, x, sin, cos, attn_mask=None, kv_cache=None, cache_index=None,
                 position_ids=None, write_index=None, q_spans=None, lora_ops=None,
                 ext_ops=None, seq_shard=False):
        cfg = self.cfg
        B, T, H = x.shape
        W = cfg.short_conv_kernel
        if lora_ops or ext_ops is not None or seq_shard or attn_mask is not None:
            raise NotImplementedError("a short_conv layer serves without adapters, extent "
                                      "chains, sequence-parallel spans or padding masks")
        _serves_by_spans("short_conv", kv_cache, write_index, q_spans)
        f32 = jnp.float32
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype, param_dtype=f32,
                        kernel_init=nn.initializers.normal(0.02))
        with jax.named_scope("conv_proj"):
            bcx = dense(3 * H, name="in_proj")(x)
            z = bcx[..., :H] * bcx[..., 2 * H:]
        with jax.named_scope("conv_state"):
            conv_w = self.param("conv", gdn_conv_init, (H, W), f32)
            if kv_cache is None:
                window = jnp.zeros((B, W - 1, H), cfg.dtype)
            else:
                window_rest = kv_cache[0]
                live_row = q_spans > 0
                fresh = live_row & (write_index == 0)
                window = jnp.where(fresh[:, None, None], 0, window_rest[:, 0]).astype(cfg.dtype)
            seq = jnp.concatenate([window, z], axis=1)
            c = sum(seq[:, j:j + T].astype(f32) * conv_w[:, j] for j in range(W))
            if kv_cache is None:
                new_cache = None
            else:
                # the last W - 1 LIVE inputs: rows [span, span + W - 1) of seq
                # (one column shifts one in)
                if T == 1:
                    tail = seq[:, 1:]
                else:
                    tail = last_live_inputs(seq, q_spans, W - 1)
                # (the layer's places in a wider cache tree hold nothing)
                new_cache = (jnp.where(live_row[:, None, None, None],
                                       tail[:, None].astype(window_rest.dtype), window_rest),
                             ) + (None, ) * (len(kv_cache) - 1)
        with jax.named_scope("conv_out"):
            out = dense(H, name="out_proj")(bcx[..., H:2 * H] * c.astype(cfg.dtype))
        return out, new_cache


class GatedMemoryUnit(nn.Module):
    """The mixer of a ``gmu`` layer: ``out = (SiLU(u W_1) * m) W_2``, ``m``
    the SSM output of the same token from the nearest Mamba layer below
    (``carry["m"]``). A slot holds nothing for it."""
    cfg: TransformerConfig
    layer_idx: int = -1

    @nn.compact
    def __call__(self, x, kv_cache=None, write_index=None, q_spans=None, carry=None):
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32,
                        kernel_init=nn.initializers.normal(0.02))
        with jax.named_scope("gmu"):
            gate = jax.nn.silu(dense(cfg.ssm_inner, name="in_proj")(x))
            out = dense(x.shape[-1], name="out_proj")(gate * carry["m"])
        return out, (None if kv_cache is None else (None, None)), carry


def diff_lambda_init(layer_idx):
    """Differential attention's ``lambda_init`` by 0-based layer index."""
    import math
    return 0.8 - 0.6 * math.exp(-0.3 * layer_idx)


class DiffAttention(nn.Module):
    """Differential attention (arXiv:2410.05258), the mixer of a
    ``diff_attention`` layer and, with ``cross``, of a ``cross_attention``
    layer. Heads of size ``d`` pair up: query pair ``p`` is ``(q_2p,
    q_2p+1)``, its key/value pair ``g = p // (pairs a group)`` gives ``K1 =
    K_2g``, ``K2 = K_2g+1``, ``V = [V_2g ; V_2g+1]``:

        A1 = softmax(q_2p K1^T / sqrt(d)) ; A2 = softmax(q_2p+1 K2^T / sqrt(d))
        o_p = RMSNorm_2d((A1 - lambda A2) V) (1 - lambda_init)
        lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init

    under the layer's mask: causal, and with a window (``layer_windows``)
    only a query's last ``window`` keys, its own included. There is no
    positional encoding, so which row of a cache a key rests in does not
    matter, only whether it is attended.

    How it is computed: a pair's keys rest side by side, ``[K_2g | K_2g+1]``,
    as ONE key of ``2d`` lanes, and so do its values; a query is
    zero-extended to ``2d`` lanes on its own side (``[q | 0]`` even, ``[0 |
    q]`` odd), so ``q' . K'`` is exactly ``q . K1`` or ``q . K2`` and ``A
    V'`` is the 2d-wide read-out of either map. That is plain grouped-query
    attention over ``kv_heads / 2`` heads of size ``2d`` at scale
    ``1/sqrt(d)``, which the paged kernels (``dstpu_decode_attn``, head size
    128) and the XLA fallback both serve; the two maps are combined after.

    What a slot holds: a full layer, rows ``(B, kv_heads / 2, max_len, 2d)``
    for K and for V; a windowed layer, a RING of ``cfg.ring_rows`` rows each
    (position ``p`` rests in row ``p mod R``); a cross layer, nothing: it
    projects queries only and reads the rows the nearest full layer below
    wrote in this very forward (``carry["kv"]``), the chunk's own included.
    A ring is attended BEFORE the chunk's rows are committed, over [ring ;
    fresh rows], so a chunk's queries see the keys that its own writes
    displace; one decode column on the kernel path commits first (its one
    row displaces the key that just left the window). Span-0 rows write
    nothing."""
    cfg: TransformerConfig
    layer_idx: int = -1
    cross: bool = False

    @nn.compact
    def __call__(self, x, kv_cache=None, write_index=None, q_spans=None, carry=None):
        cfg = self.cfg
        B, T, H = x.shape
        nh, nkv, d = cfg.num_heads, cfg.kv_heads // 2, cfg.head_size
        window = cfg.layer_window(self.layer_idx)
        kind = "cross_attention" if self.cross else "diff_attention"
        _serves_by_spans(kind, kv_cache, write_index, q_spans)
        use_bias = cfg.attn_bias if cfg.attn_bias is not None else cfg.norm == "layernorm"
        f32 = jnp.float32
        with jax.named_scope("attn_proj"):
            q = HeadProjection(nh, d, use_bias, cfg.dtype, name="q_proj")(x)
            even = (jnp.arange(nh) % 2 == 0)[None, :, None, None]
            zero = jnp.zeros_like(q)
            q = jnp.concatenate([jnp.where(even, q, zero), jnp.where(even, zero, q)], axis=-1)
            if not self.cross:
                k = HeadProjection(nkv, 2 * d, use_bias, cfg.dtype, name="k_proj")(x)
                v = HeadProjection(nkv, 2 * d, use_bias, cfg.dtype, name="v_proj")(x)
        scale = d ** -0.5
        cached = kv_cache is not None
        kernels = cached and cfg.attention_impl == "flash" and _tp_mesh_size() == 1
        kernel_kw = dict(block_kv=cfg.decode_block_kv, scale=scale)
        new_cache = (None, None) if cached else None
        with jax.named_scope("swa_attn" if window else "shared_attn"):
            if not cached:
                if self.cross:
                    k, v = carry["kv"]
                rel = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
                keep = (rel >= 0) & (rel < window) if window else rel >= 0
                out = _grouped_attention_xla(q, k, v, keep[None], scale, cfg.dtype)
                kv = (k, v)
            elif window:
                out, new_cache = _ring_attention(cfg, q, k, v, kv_cache, write_index, q_spans,
                                                 window, kernels, kernel_kw)
                kv = None
            else:
                if self.cross:
                    kv = carry["kv"]
                else:
                    kv = new_cache = tuple(_commit_span_rows(
                        [(kv_cache[0], k), (kv_cache[1], v)], write_index, q_spans, kernels))
                ck, cv = kv
                if kernels:
                    from ..ops.pallas.decode_attention import paged_decode_attention, \
                        paged_span_attention
                    starts = jnp.zeros((B, ), jnp.int32)
                    ends = _attended_ends(write_index, q_spans)
                    if T == 1:
                        out = paged_decode_attention(q[:, :, 0], ck, cv, starts, ends,
                                                     **kernel_kw)[:, :, None]
                    else:
                        out = paged_span_attention(q, ck, cv, starts, ends - 1, **kernel_kw)
                else:
                    qpos = write_index[:, None] + jnp.arange(T)[None, :]
                    keep = jnp.arange(ck.shape[2])[None, None, :] <= qpos[:, :, None]
                    out = _grouped_attention_xla(q, ck.astype(cfg.dtype), cv.astype(cfg.dtype),
                                                 keep, scale, cfg.dtype)
            lam_init = diff_lambda_init(self.layer_idx)
            lq1, lk1, lq2, lk2 = (self.param(name, nn.initializers.normal(0.1), (d, ), f32)
                                  for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
            lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam_init
            out = out.astype(f32)
            out = out[:, 0::2] - lam * out[:, 1::2]  # (B, nh / 2, T, 2d)
            out = RMSNorm(epsilon=cfg.layernorm_epsilon, dtype=f32, name="sub_norm")(out)
            out = (out * (1.0 - lam_init)).astype(cfg.dtype)
        with jax.named_scope("attn_proj"):
            out = OutProjection(H, use_bias, cfg.dtype, name="o_proj")(out)
        if not (self.cross or window):
            carry = dict(carry, kv=kv)
        return out, new_cache, carry


def _ring_attention(cfg, q, k, v, kv_cache, write_index, q_spans, window, kernels, kernel_kw):
    """Windowed attention over a slot's ring, and the ring with this call's
    live rows committed. ``q`` (B, nh, T, D), fresh ``k``/``v`` (B, nkv, T,
    D), ``kv_cache`` the ring's K and V leaves (B, nkv, R, D). Ring row ``r``
    of a slot whose write head is at ``p0`` holds position ``p0 - 1 - ((p0 - 1
    - r) mod R)``, if that is not negative (the slot's request has not written
    the row yet). Keys are at rest as they are attended (rotated, where the
    layer rotates): softmax over a set of keys does not care which row each
    rests in, only whether it is attended.

    One column on the kernel path, where the ring's rows ARE the window,
    commits first: its row displaces the key that just left the window. A
    wider call attends over [ring ; fresh rows] BEFORE the commit, so that a
    span's queries see the keys its own writes displace, and masks every
    ring row by the position it holds. That is also what rolls a ring back by
    position: a row written for a column that was not committed (a rejected
    draft of a verify step, past the span the NEXT call's write head stands
    at) is taken to hold the position ``R`` before the one it was written for,
    which no later query's window reaches, until the position's true row
    overwrites it."""
    B, nh, T, _ = q.shape
    rk, rv = kv_cache
    R = rk.shape[2]
    if kernels and T == 1 and R == window:
        from ..ops.pallas.decode_attention import paged_decode_attention
        ck, cv = _commit_span_rows([(rk, k), (rv, v)], write_index % R, q_spans, True)
        out = paged_decode_attention(q[:, :, 0], ck, cv, jnp.zeros((B, ), jnp.int32),
                                     jnp.minimum(_attended_ends(write_index, q_spans), R),
                                     **kernel_kw)
        return out[:, :, None], (ck, cv)
    p0, j = write_index[:, None], jnp.arange(T)[None, :]
    held = p0 - 1 - ((p0 - 1 - jnp.arange(R)[None, :]) % R)  # (B, R)
    keep_ring = (held[:, None, :] >= 0) & (held[:, None, :] > (p0 + j)[:, :, None] - window)
    rel = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    keep_fresh = jnp.broadcast_to((rel >= 0) & (rel < window), (B, T, T))
    out = _grouped_attention_xla(
        q, jnp.concatenate([rk.astype(cfg.dtype), k], axis=2),
        jnp.concatenate([rv.astype(cfg.dtype), v], axis=2),
        jnp.concatenate([keep_ring, keep_fresh], axis=-1), kernel_kw["scale"], cfg.dtype)
    # the last R live columns land at their positions mod R; the others
    # (padding, and what a chunk wider than the ring would overwrite at
    # once) are dropped
    live = (j < q_spans[:, None]) & (j >= q_spans[:, None] - R)
    tgt = jnp.where(live, (p0 + j) % R, R)
    upd = lambda c, kk, i: c.at[:, i, :].set(kk.astype(c.dtype), mode="drop")
    with jax.named_scope("kv_commit"):
        ring = (jax.vmap(upd)(rk, k, tgt), jax.vmap(upd)(rv, v, tgt))
    return out, ring


class QuantDense(nn.Module):
    """nn.Dense over (int8 weight, fp32 group scales) via the Pallas quant
    matmul (serving path; params come from ``quantize_params``)."""

    features: int
    use_bias: bool
    dtype: Any
    groups: int = 0  # scale-group SIZE (0 = default rule)

    @nn.compact
    def __call__(self, x):
        K = x.shape[-1]
        qw, sc = _q_param(self, "kernel", K, self.features, self.groups)
        y = _qmm2d(x.reshape(-1, K).astype(self.dtype), qw, sc)
        y = y.reshape(x.shape[:-1] + (self.features, ))
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (self.features, ), jnp.float32)
            y = y + bias.astype(self.dtype)
        return y


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, lora_ops=None, order=None):
        """``order``: the ZeRO-3 gather order of this module's kernels
        (``GatherOrder``; training's unrolled forward only), or None."""
        cfg = self.cfg

        def lora_add(y, site, x_in):
            if not lora_ops:
                return y
            d = _lora_site_delta(x_in, lora_ops, site)
            return y if d is None else y + d.reshape(y.shape).astype(y.dtype)

        use_bias = cfg.norm == "layernorm" if cfg.mlp_bias is None else cfg.mlp_bias
        if cfg.int8_weights:
            dense = partial(QuantDense, use_bias=use_bias, dtype=cfg.dtype,
                            groups=cfg.int8_group_size)
        else:
            def dense(features, name):
                ordered = None if order is None else order.sub(name)
                if ordered is not None:
                    # the layer's last product is its backward's first: its
                    # weight's regather has only the layer above's attention
                    # products in front of it, a fifth of its length, so its
                    # dX is due behind its own dW. Every other regather
                    # stands behind a product of its size already, and a
                    # barrier on up_proj's dy would write out what the
                    # compiler fuses into both its consumers
                    return partial(OrderedDense(features, use_bias, cfg.dtype,
                                                name == "down_proj", name=name), order=ordered)
                return nn.Dense(features, use_bias=use_bias, dtype=cfg.dtype, name=name,
                                param_dtype=jnp.float32,
                                kernel_init=nn.initializers.normal(0.02))
        if cfg.activation in ("swiglu", "geglu"):
            gate = lora_add(dense(cfg.ffn_size, name="gate_proj")(x), "gate", x)
            gate = scaled(gate, cfg.mlp_gate_multiplier)
            up = lora_add(dense(cfg.ffn_size, name="up_proj")(x), "up", x)
            act = nn.silu(gate) if cfg.activation == "swiglu" else nn.gelu(gate)
            h = act * up
        else:
            h = lora_add(dense(cfg.ffn_size, name="up_proj")(x), "up", x)
            if cfg.activation == "gelu":
                h = nn.gelu(h)  # tanh approximation (HF "gelu_new")
            elif cfg.activation == "gelu_exact":
                h = nn.gelu(h, approximate=False)  # erf (HF "gelu")
            elif cfg.activation == "quick_gelu":
                h = h * nn.sigmoid(1.702 * h)  # CLIP's QuickGELU
            elif cfg.activation == "relu2":
                h = jnp.square(nn.relu(h))  # nemotron_h's relu2: two matrices, no gate
            else:
                h = nn.relu(h)
        if cfg.bitwise_tp:
            # bitwise-TP layout: gather the ffn-sharded activation (exact
            # concat) so the replicated down_proj contracts fully locally
            h = _tp_replicate(h)
        return scaled(lora_add(dense(cfg.hidden_size, name="down_proj")(h), "down", h),
                      cfg.mlp_down_multiplier)


class Block(nn.Module):
    cfg: TransformerConfig
    layer_idx: int = -1

    @nn.compact
    def __call__(self, x, sin, cos, attn_mask=None, deterministic=True, kv_cache=None,
                 cache_index=None, position_ids=None, write_index=None, q_spans=None,
                 lora_ops=None, expert_ops=None, ext_ops=None, seq_shard=False, carry=None,
                 order=None):
        """``carry``: what the layers below hand on beside the residual
        stream (``cfg.carries_across_layers``: a dict); with one, the block
        returns ``(x, new_cache, carry)``. ``order``: the ZeRO-3 gather order
        of the layer's attention and MLP leaves (``GatherOrder``), or None."""
        cfg = self.cfg
        drop = nn.Dropout(rate=cfg.dropout) if cfg.dropout > 0 else None
        if cfg.act_quant_bits:  # QAT activation fake-quant (compression)
            from ..compression.helper import fake_quantize
            x = fake_quantize(x, bits=cfg.act_quant_bits, groups=1,
                              symmetric=cfg.act_quant_symmetric)
        kind = cfg.layer_type(self.layer_idx)
        if kind in SAMBAY_TYPES:
            if lora_ops or ext_ops is not None or seq_shard or attn_mask is not None:
                raise NotImplementedError(f"a {kind} layer serves without adapters, extent "
                                          f"chains, sequence-parallel spans or padding masks")
            sambay = {"mamba": partial(Mamba, name="mamba"),
                      "gmu": partial(GatedMemoryUnit, name="gmu"),
                      "diff_attention": partial(DiffAttention, name="attn"),
                      "cross_attention": partial(DiffAttention, cross=True, name="attn")}
            h, new_cache, carry = sambay[kind](cfg, layer_idx=self.layer_idx)(
                make_norm(cfg, name="attn_norm")(x), kv_cache, write_index, q_spans, carry)
            x = x + h
            return x + MLP(cfg, name="mlp")(make_norm(cfg, name="mlp_norm")(x)), new_cache, carry
        mixer_kind, ffn_kind = cfg.layer_parts(self.layer_idx)
        handed = lambda h: h
        # a one-sublayer block has ONE norm and no parameters of the absent half
        mixer_norm, ffn_norm = (("attn_norm", "mlp_norm") if mixer_kind and ffn_kind
                                else ("norm", "norm"))
        if mixer_kind is None:
            # an FFN alone: the layer's slot holds nothing
            h, new_cache = None, (None if kv_cache is None else (None, None))
            ff_in = make_norm(cfg, name=ffn_norm)(x)
        elif mixer_kind == "mamba2":
            from .mamba2 import Mamba2
            narrow = Mamba2(cfg, layer_idx=self.layer_idx, name="mamba2")

            def mixer(h, sin, cos, attn_mask, kv_cache, cache_index, position_ids, write_index,
                      q_spans, lora_ops, ext_ops, seq_shard):
                if lora_ops or ext_ops is not None or seq_shard or attn_mask is not None:
                    raise NotImplementedError(
                        "a mamba2 layer serves without adapters, extent chains, "
                        "sequence-parallel spans or padding masks")
                return narrow(h, kv_cache, write_index, q_spans, carry)[:2]
        elif mixer_kind == "parallel_hybrid":
            from .mamba2 import Mamba2
            ssm = Mamba2(cfg, layer_idx=self.layer_idx, name="mamba2")
            attn = Attention(cfg, layer_idx=self.layer_idx, name="attn")

            def mixer(a, sin, cos, attn_mask, kv_cache, cache_index, position_ids, write_index,
                      q_spans, lora_ops, ext_ops, seq_shard):
                """Both mixers on the one normed input ``a``; the slot's
                leaves are attention's rows, then the Mamba-2 state and window
                (``PARALLEL_HYBRID_MIXERS``, ``cache_spec``)."""
                if lora_ops or ext_ops is not None or seq_shard or attn_mask is not None:
                    raise NotImplementedError(
                        "a parallel_hybrid layer serves without adapters, extent chains, "
                        "sequence-parallel spans or padding masks")
                rows, state = (None, None) if kv_cache is None else (kv_cache[:2], kv_cache[2:])
                with jax.named_scope("hybrid_mixer"):
                    s, state = ssm(a, state, write_index, q_spans)[:2]
                    t, rows = attn(scaled(a, cfg.attention_in_multiplier), sin, cos, None, rows,
                                   cache_index, position_ids, write_index, q_spans)
                    h = (scaled(s, cfg.ssm_out_multiplier)
                         + scaled(t, cfg.attention_out_multiplier))
                return h, (None if kv_cache is None else tuple(rows) + tuple(state))
        elif mixer_kind == "linear_attention":
            mixer = GatedDeltaNet(cfg, layer_idx=self.layer_idx, name="gdn")
        elif mixer_kind == "short_conv":
            mixer = ShortConv(cfg, layer_idx=self.layer_idx, name="conv")
        else:
            attention = LatentAttention if cfg.kv_lora_rank else Attention
            mixer = attention(cfg, layer_idx=self.layer_idx, name="attn")
            if order is not None and attention is Attention:
                mixer = partial(mixer, order=order.sub("attn"))
                # the layer above's first projection rides this layer's last
                # attention product
                handed = order.hand_down
        if cfg.post_norm:
            # the mixer and the FFN read the residual stream itself; what
            # they return is normalised on its way into it
            h, new_cache = mixer(x, sin, cos, attn_mask, kv_cache, cache_index, position_ids,
                                 write_index, q_spans, lora_ops, ext_ops, seq_shard)
            x = x + make_norm(cfg, name="attn_norm")(h)
            ff_in = x
        elif mixer_kind is not None:
            h = make_norm(cfg, name=mixer_norm)(x)
            h, new_cache = mixer(
                h, sin, cos, attn_mask, kv_cache, cache_index, position_ids, write_index,
                q_spans, lora_ops, ext_ops, seq_shard)
            h = handed(h)
            if drop is not None:
                h = drop(h, deterministic=deterministic)
            if ffn_kind is None:
                return x + h, new_cache
            if cfg.parallel_residual:
                # GPT-J/NeoX: attn and mlp both read the pre-attn stream and add
                # into ONE residual (GPT-J ties attn_norm == mlp_norm weights —
                # the conversion duplicates them)
                ff_in = make_norm(cfg, name=ffn_norm)(x)
            else:
                x = x + h
                ff_in = make_norm(cfg, name=ffn_norm)(x)
        if ffn_kind == "moe":
            from ..moe.layer import MoE
            if kv_cache is not None or cfg.moe_dropless:
                # KV-cache (serving/decode) forward: deterministic per-token
                # capacity-free dispatch, NO aux-loss sow — the gating
                # intermediates are training-only, and collecting them here
                # would force mutable step programs + per-step host traffic
                ff = MoE(cfg, name="moe")(ff_in, serving=True, q_spans=q_spans,
                                          expert_ops=expert_ops)
            else:
                ff, aux = MoE(cfg, name="moe")(ff_in)
                self.sow("intermediates", "moe_aux_loss", aux)
        else:
            ff = MLP(cfg, name="mlp")(ff_in, lora_ops, None if order is None else order.sub("mlp"))
        if cfg.post_norm:
            return x + make_norm(cfg, name="mlp_norm")(ff), new_cache
        if drop is not None:
            ff = drop(ff, deterministic=deterministic)
        if cfg.parallel_residual:
            return x + h + ff, new_cache
        return x + ff, new_cache


class CausalLM(nn.Module):
    cfg: TransformerConfig

    def _shard(self, path):
        """``{path: the compute-dtype leaf}`` of this model's parameter at
        ``path``, empty if there is none: what a ``GatherOrder`` gathers ahead."""
        w = self.variables["params"]
        for key in path.split("/"):
            w = w.get(key) if hasattr(w, "get") else None
        return {} if w is None else {path: w.astype(self.cfg.dtype)}

    @nn.compact
    def __call__(self, input_ids, attn_mask=None, deterministic=True, kv_cache=None,
                 cache_index=None, position_ids=None, return_hidden=False,
                 pld_theta=None, pld_rng=None, ltd_keep=None, ltd_layers=(), ltd_rng=None,
                 write_index=None, q_spans=None, lora_ops=None, expert_ops=None,
                 ext_ops=None, seq_shard=False, with_hidden=False, gather_order=None):
        """``with_hidden``: return the final-norm hidden states BEHIND the
        logits (and the cache), as a multi-token-prediction module takes them.

        ``gather_order``: under ZeRO stage 3, ``{leaf path: gathered
        placement}`` of the weights whose gathers the program places
        (``ShardingPlanner.gathered_placements``, handed down by the engine).
        Only the unrolled training forward (no cache, no rematerialised
        blocks) reads it.

        ``kv_cache``: optional cache tree of ``init_cache`` (split K and V
        leaves, the packed K/V leaf or the latent leaf, each component with
        a leading layer dim (L, B, kv_heads, S, lanes) or as a per-layer
        tuple) — scanned alongside the layer stack. Returns logits, or (logits, new_kv_cache) when caching, or the
        final-norm hidden states when ``return_hidden`` (the loss path fuses
        the vocab projection into a chunked cross-entropy instead).

        ``pld_theta``/``pld_rng``: progressive layer drop (reference
        ``runtime/progressive_layer_drop.py``) — stochastic depth where layer
        ``i`` of ``L`` is kept with probability ``1 - (i/L)(1 - theta)``
        (deeper layers dropped more, per the PLD paper)."""
        cfg = self.cfg
        B, T = input_ids.shape
        emb = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                       embedding_init=nn.initializers.normal(0.02), name="embed")
        x = emb(input_ids) if kv_cache is not None else _embed_layout(emb(input_ids))
        x = scaled(x, cfg.embedding_multiplier)
        if cfg.embed_norm:  # BLOOM's word_embeddings_layernorm
            x = make_norm(cfg, name="embed_norm")(x)
        if cfg.pos_embedding == "learned":
            pos_emb = self.param("pos_embed", nn.initializers.normal(0.02),
                                 (cfg.max_seq_len, cfg.hidden_size), jnp.float32)
            if position_ids is not None:
                x = x + pos_emb[position_ids].astype(cfg.dtype)
            elif cache_index is not None:
                x = x + jax.lax.dynamic_slice_in_dim(pos_emb, cache_index, T, axis=0).astype(cfg.dtype)
            else:
                x = x + jax.lax.dynamic_slice_in_dim(pos_emb, 0, T, axis=0).astype(cfg.dtype)
        sin, cos = model_rope_table(cfg)

        block = Block
        if cfg.remat_policy:
            block = nn.remat(Block, policy=resolve_remat_policy(cfg.remat_policy),
                             prevent_cse=not cfg.scan_layers,
                             static_argnums=())
        def apply_pld(y, x_in, layer_idx):
            if pld_theta is None or pld_rng is None:
                return y
            keep_p = 1.0 - (layer_idx / cfg.num_layers) * (1.0 - pld_theta)
            keep = jax.random.bernoulli(jax.random.fold_in(pld_rng, layer_idx), keep_p)
            return jnp.where(keep, y, x_in)

        # random layerwise token dropping (reference data_routing/basic_layer.py
        # RandomLayerTokenDrop): selected layers process a random sorted subset
        # of ltd_keep tokens; dropped tokens ride the residual stream. Sorted
        # gather preserves causal order, and RoPE uses the original positions
        # via position_ids. Requires rope/none positions (learned pos are
        # added before the layer stack, so they survive the gather too).
        ltd_active = (ltd_keep is not None and ltd_rng is not None and ltd_keep < T
                      and kv_cache is None)

        def ltd_apply(block_fn, x, layer_idx):
            idx = jnp.sort(jax.random.permutation(jax.random.fold_in(ltd_rng, layer_idx), T)[:ltd_keep])
            pos = jnp.broadcast_to(idx[None], (B, ltd_keep))
            x_sub = jnp.take(x, idx, axis=1)
            m_sub = None if attn_mask is None else jnp.take(attn_mask, idx, axis=1)
            y_sub, c = block_fn(x_sub, m_sub, pos)
            return x.at[:, idx].set(y_sub.astype(x.dtype)), c

        new_cache = None
        if cfg.scan_layers:
            def scan_body(mdl, carry, xs):
                layer_cache, layer_idx, layer_lora, layer_experts = xs
                if ltd_active:
                    # scan shares one program across layers, so LTD applies to
                    # every scanned layer (per-layer opt-out needs
                    # scan_layers=False)
                    y, c = ltd_apply(
                        lambda xs_, ms_, ps_: mdl(xs_, sin, cos, ms_, deterministic,
                                                  layer_cache, cache_index, ps_),
                        carry, layer_idx)
                else:
                    # ext_ops/seq_shard are layer-invariant (like
                    # write_index/q_spans): closed over, not scanned
                    y, c = mdl(carry, sin, cos, attn_mask, deterministic,
                               layer_cache, cache_index, position_ids, write_index,
                               q_spans, layer_lora, layer_experts, ext_ops,
                               seq_shard)
                return apply_pld(y, carry, layer_idx), c

            x, new_cache = nn.scan(
                scan_body,
                variable_axes={"params": 0, "intermediates": 0, "expert_stats": 0,
                               "expert_choice": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.num_layers,
                metadata_params={"partition_name": "layers"},
            )(block(cfg, name="layers"), x,
              (kv_cache, jnp.arange(cfg.num_layers), lora_ops, expert_ops))
        else:
            caches = []
            carry = {} if cfg.carries_across_layers else None
            # one scanned body has no "next layer" to name, a served step
            # shards no weight and a rematerialised block runs its forward
            # twice: those keep the partitioner's placement
            orders = (gather_order and kv_cache is None and not cfg.remat_policy
                      and not self.is_initializing())
            order = GatherOrder(gather_order) if orders else None
            for i in range(cfg.num_layers):
                # per-layer tuple cache (init_cache, unrolled form); stacked
                # arrays also index correctly for backward compatibility.
                # 1 to 3 components: the K/V leaves (packed, split or latent)
                # and the int8 tier's scale leaf; None where a layer declares
                # fewer than the tree has (cache_spec)
                layer_cache = (None if kv_cache is None
                               else tuple(comp[i] for comp in kv_cache))
                layer_lora = (None if lora_ops is None else
                              jax.tree_util.tree_map(lambda leaf: leaf[i], lora_ops))
                layer_experts = (None if expert_ops is None else
                                 jax.tree_util.tree_map(lambda leaf: leaf[i], expert_ops))
                blk = block(cfg, layer_idx=i, name=f"layer_{i}")
                if ltd_active and i in ltd_layers:
                    y, c = ltd_apply(
                        lambda xs_, ms_, ps_, blk=blk, lc=layer_cache: blk(
                            xs_, sin, cos, ms_, deterministic, lc, cache_index, ps_),
                        x, i)
                elif carry is not None:
                    y, c, carry = blk(x, sin, cos, attn_mask, deterministic,
                                      layer_cache, cache_index, position_ids, write_index,
                                      q_spans, layer_lora, layer_experts, ext_ops,
                                      seq_shard, carry)
                elif orders:
                    y, c = blk(x, sin, cos, attn_mask, deterministic,
                               layer_cache, cache_index, position_ids, write_index,
                               q_spans, layer_lora, layer_experts, ext_ops,
                               seq_shard, order=order.sub(f"layer_{i}", self._shard(
                                   f"layer_{i + 1}/attn/q_proj/kernel")))
                    # the layer above's first MLP weight, as long a gather as
                    # this layer's last product, rides it: left to the
                    # partitioner it rides the one product in front of its
                    # consumer, the attention output's, a fifth of its length
                    first = "gate_proj" if cfg.activation in ("swiglu", "geglu") else "up_proj"
                    y = order.due_behind(y, self._shard(f"layer_{i + 1}/mlp/{first}/kernel"))
                else:
                    y, c = blk(x, sin, cos, attn_mask, deterministic,
                               layer_cache, cache_index, position_ids, write_index,
                               q_spans, layer_lora, layer_experts, ext_ops,
                               seq_shard)
                x = apply_pld(y, x, jnp.asarray(i))
                caches.append(c)
            if kv_cache is not None:
                new_cache = tuple(tuple(c[j] for c in caches)
                                  for j in range(len(caches[0])))

        # the head's mark in a served step's device trace: the final norm and
        # the vocabulary product (the step samples right behind it, under
        # `sample`). Training's head is under `loss_ce`, and keeps its text.
        with jax.named_scope("lm_head") if kv_cache is not None else contextlib.nullcontext():
            x = make_norm(cfg, name="final_norm")(x)
            if return_hidden:
                return x
            # logits matmul runs in compute dtype (MXU rate); CE upcasts to fp32
            if cfg.int8_weights:
                # one int8 vocab projection covers both tied and untied heads
                # (vocab padded to a 2048 multiple so the quant-matmul kernel
                # gets wide n-blocks — 50304's largest divisor under the block
                # cap is a DMA-starving 384; quantize_params builds the padding)
                Vpad = -(-cfg.vocab_size // 2048) * 2048
                qw = self.param("logits_q", nn.initializers.zeros,
                                (cfg.hidden_size, Vpad), jnp.int8)
                sc = self.param("logits_scale", nn.initializers.ones,
                                (_q_groups(cfg.hidden_size, cfg.int8_group_size), Vpad),
                                jnp.float32)
                Bx, Tx, Hx = x.shape
                logits = _qmm2d(x.reshape(Bx * Tx, Hx), qw, sc)
                logits = logits.reshape(Bx, Tx, Vpad)[..., :cfg.vocab_size]
                if cfg.lm_head_bias:
                    lb = self.param("logits_bias", nn.initializers.zeros,
                                    (cfg.vocab_size, ), jnp.float32)
                    logits = logits + lb.astype(logits.dtype)
            elif cfg.tie_embeddings:
                logits = emb.attend(x)
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=cfg.lm_head_bias, dtype=cfg.dtype,
                                  param_dtype=jnp.float32, name="lm_head")(x)
            logits = scaled(logits, cfg.lm_head_multiplier)
        hidden = (x, ) if with_hidden else ()
        if kv_cache is not None:
            return (logits, new_cache) + hidden
        return (logits, ) + hidden if hidden else logits


class CausalLMModel:
    """Engine-facing wrapper: init_params / loss / tp_rules / expert_pattern."""

    supports_pld = True  # consumes the engine's progressive-layer-drop theta
    supports_random_ltd = True  # consumes the engine's random-LTD keep length

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.module = CausalLM(cfg)
        self._ltd_keep = None  # static per-compile; engine clears its cache on change
        self._ltd_layers = ()

    def set_random_ltd(self, keep, layers):
        """Engine hook (data_efficiency.data_routing.random_ltd): train-time
        token keep-count for the selected layers. Static under jit — the
        engine invalidates its compiled step when the schedule advances."""
        self._ltd_keep = None if keep is None else int(keep)
        self._ltd_layers = tuple(layers or ())

    def set_remat_policy(self, policy):
        """Engine hook for the ``activation_checkpointing`` config section:
        rebuild the module with the given ``jax.checkpoint`` policy name."""
        self.cfg = dataclasses.replace(self.cfg, remat_policy=policy)
        self.module = CausalLM(self.cfg)

    def set_activation_quantization(self, bits, symmetric=True):
        """Compression hook (``compression.activation_quantization``):
        rebuild the module with per-block input fake-quantization."""
        self.cfg = dataclasses.replace(self.cfg, act_quant_bits=bits,
                                       act_quant_symmetric=symmetric)
        self.module = CausalLM(self.cfg)

    def _mtp_module(self):
        from .mtp import MTPModule
        return MTPModule(self.cfg)

    def init_params(self, rng):
        """The stack's tree; with a multi-token-prediction module, its tree
        beside it under ``"mtp"`` (the stack's own forward never reads it)."""
        B, T = 2, min(self.cfg.max_seq_len, 128)
        ids = jnp.zeros((B, T), jnp.int32)
        params = self.module.init({"params": rng}, ids)["params"]
        if self.cfg.mtp_layers:
            h = jnp.zeros((B, T, self.cfg.hidden_size), self.cfg.dtype)
            params = dict(params, mtp=self._mtp_module().init(
                {"params": jax.random.fold_in(rng, 1)}, h, h)["params"])
        return params

    def apply(self, params, input_ids, attn_mask=None):
        return self.module.apply({"params": params}, input_ids, attn_mask)

    def apply_with_mtp(self, params, input_ids):
        """One full causal forward, no cache: ``(logits, draft logits)``, both
        (B, T, V). Position ``i``'s draft logits predict token ``i + 2`` from
        the stack's output at ``i`` and token ``i + 1`` (the last position
        has no next token: its draft row reads a wrapped id and means
        nothing)."""
        logits, hidden = self.module.apply({"params": params}, input_ids, with_hidden=True)
        return logits, self.mtp_forward(params, hidden, jnp.roll(input_ids, -1, axis=1))

    def mtp_forward(self, params, hidden, next_ids, kv_cache=None, position_ids=None,
                    write_index=None, q_spans=None, expert_stats=False, expert_choice=False):
        """The multi-token-prediction module (``models/mtp.py``) over the
        stack's normed output ``hidden`` (B, T, H) and each position's next
        token ``next_ids`` (B, T), with the model's embedding and head: draft
        logits (B, T, V). With ``kv_cache`` (the WHOLE cache tree: the module's
        leaves are the entry behind the stack's layers) it serves through the
        slot pool's spans and returns ``(draft logits, the tree with the
        module's leaves written)``, then its expert layer's ``(1, E)`` routed
        counts and ``(1, B, T, k)`` chosen experts where asked for, as
        :meth:`apply_with_cache` returns the stack's. On the device the whole
        of it runs under the ``mtp_draft`` scope."""
        cfg = self.cfg
        L = cfg.num_layers
        with jax.named_scope("mtp_draft"):
            emb = jnp.take(params["embed"]["embedding"], next_ids, axis=0).astype(cfg.dtype)
            mutable = ((["expert_stats"] if expert_stats else [])
                       + (["expert_choice"] if expert_choice else [])) or False
            own = None if kv_cache is None else tuple(comp[L] for comp in kv_cache)
            out = self._mtp_module().apply({"params": params["mtp"]}, hidden, emb, own,
                                           position_ids, write_index, q_spans, mutable=mutable)
            out, mut = out if mutable else (out, {})
            z, written = out if kv_cache is not None else (out, None)
            with jax.named_scope("lm_head"):
                if cfg.tie_embeddings:
                    logits = jnp.einsum("bth,vh->btv", z, params["embed"]["embedding"].astype(
                        cfg.dtype))
                else:
                    head = params["lm_head"]
                    logits = jnp.dot(z, head["kernel"].astype(cfg.dtype))
                    if cfg.lm_head_bias:
                        logits = logits + head["bias"].astype(cfg.dtype)
        if kv_cache is None:
            return logits
        tree = tuple(comp[:L] + (written[j], ) + comp[L + 1:]
                     for j, comp in enumerate(kv_cache))
        leaf = lambda name: jax.tree_util.tree_leaves(mut.get(name, {}))[0]
        extra = ()
        if expert_stats:
            extra += (leaf("expert_stats").reshape(1, cfg.num_experts), )
        if expert_choice:
            extra += (leaf("expert_choice").reshape((1, ) + next_ids.shape + (cfg.moe_top_k, )), )
        return (logits, tree) + extra

    # ---- generation (KV cache) -------------------------------------------
    def quantize_params(self, params, group_size=None, dtype=None):
        """bf16/fp32 param tree -> the int8 serving tree an
        ``int8_weights=True`` model expects: every projection kernel becomes
        (int8 weight, fp32 per-group scales) in matmul layout, the vocab
        projection becomes a padded ``logits_q``, and everything else casts
        to the compute dtype. Host-side numpy — call before device placement
        (reference ``replace_module`` int8 path / ``weight_quantizer``)."""
        import numpy as np
        cfg = self.cfg
        gs_cfg = group_size if group_size is not None else (cfg.int8_group_size or 128)
        dtype = np.dtype(jnp.dtype(dtype or cfg.dtype).name)

        def quant(w):  # (..., K, N) -> int8 same shape + (..., G, N) scales
            w = np.asarray(w, np.float32)
            K = w.shape[-2]
            gs = gs_cfg if gs_cfg and K % gs_cfg == 0 else K
            G = K // gs
            grouped = w.reshape(w.shape[:-2] + (G, gs, w.shape[-1]))
            scale = np.abs(grouped).max(axis=-2, keepdims=True) / 127.0
            scale = np.where(scale == 0, 1.0, scale)
            q = np.clip(np.round(grouped / scale), -127, 127).astype(np.int8)
            return (q.reshape(w.shape),
                    np.ascontiguousarray(scale[..., 0, :], dtype=np.float32))

        def to_dtype(x):
            x = np.asarray(x)
            return x.astype(dtype) if np.issubdtype(x.dtype, np.floating) else x

        def conv_layer(sub):
            out = {}
            for k, v in sub.items():
                if isinstance(v, dict):
                    out[k] = conv_layer(v)
                else:
                    out[k] = to_dtype(v)
            # rewrite projection kernels in place
            attn_scope = out.get("attn") if "attn" in out else out
            if cfg.int8_fused_qkv and all(
                    "kernel" in attn_scope.get(n, {}) for n in ("q_proj", "k_proj", "v_proj")):
                ws, biases = [], []
                for name in ("q_proj", "k_proj", "v_proj"):
                    node = attn_scope.pop(name)
                    w = np.asarray(node.pop("kernel"), np.float32)
                    ws.append(w.reshape(w.shape[:-2] + (w.shape[-2] * w.shape[-1], )))
                    if "bias" in node:
                        b = np.asarray(node.pop("bias"), np.float32)
                        biases.append(b.reshape(b.shape[:-2] + (-1, )))
                attn_scope["qkv_q"], attn_scope["qkv_scale"] = quant(
                    np.concatenate(ws, axis=-1))
                if biases:
                    attn_scope["qkv_bias"] = np.concatenate(biases, axis=-1)
            else:
                for name in ("q_proj", "k_proj", "v_proj"):
                    node = attn_scope.get(name)
                    if node is not None and "kernel" in node:
                        w = np.asarray(node.pop("kernel"), np.float32)
                        w2 = w.reshape(w.shape[:-2] + (w.shape[-2] * w.shape[-1], ))  # (.., H, n*hd)
                        node["kernel_q"], node["kernel_scale"] = quant(w2)
            node = out.get("attn", {}).get("o_proj") if "attn" in out else out.get("o_proj")
            if node is not None and "kernel" in node:
                w = np.asarray(node.pop("kernel"), np.float32)
                w2 = w.reshape(w.shape[:-3] + (w.shape[-3] * w.shape[-2], w.shape[-1]))
                node["kernel_q"], node["kernel_scale"] = quant(w2)
            mlp = out.get("mlp", out if "up_proj" in out else None)
            if mlp is not None:
                for name in ("gate_proj", "up_proj", "down_proj"):
                    node = mlp.get(name)
                    # isinstance: batched expert kernels are raw (E, K, N)
                    # leaves (handled below), not {kernel: ...} dicts
                    if isinstance(node, dict) and "kernel" in node:
                        w = np.asarray(node.pop("kernel"), np.float32)
                        node["kernel_q"], node["kernel_scale"] = quant(w)
            experts = out.get("moe", {}).get("experts")
            if experts is not None:
                # batched (E, K, N) expert kernels -> per-expert group quant
                # (reference moe_inference int8 experts); the tiny gate stays
                # in the compute dtype
                for name in ("gate_proj", "up_proj", "down_proj"):
                    if name in experts:
                        w = np.asarray(experts.pop(name), np.float32)
                        experts[name + "_q"], experts[name + "_scale"] = quant(w)
            return out

        params = dict(params)
        out = {}
        Vpad = -(-cfg.vocab_size // 2048) * 2048  # wide n-blocks for the kernel
        H = cfg.hidden_size
        if cfg.tie_embeddings:
            table = np.asarray(params["embed"]["embedding"], np.float32)  # (V, H)
            head = table.T
        else:
            head = np.asarray(params["lm_head"]["kernel"], np.float32)  # (H, V)
        head_p = np.zeros((H, Vpad), np.float32)
        head_p[:, :cfg.vocab_size] = head
        out["logits_q"], out["logits_scale"] = quant(head_p)
        if cfg.lm_head_bias and "lm_head" in params and "bias" in params["lm_head"]:
            out["logits_bias"] = np.asarray(params["lm_head"]["bias"], np.float32)
        for k, v in params.items():
            if k == "lm_head":
                continue  # folded into logits_q
            if k == "layers" or k.startswith("layer_"):
                out[k] = conv_layer(v)
            else:
                out[k] = jax.tree_util.tree_map(to_dtype, v)
        return out

    def init_cache(self, batch_size, max_len, dtype=None, quantized=False):
        """Preallocated KV cache — the analogue of the reference's inference
        workspace KV arena (``csrc/transformer/inference/includes/
        inference_context.h``). One of three geometries, chosen here from the
        config's widths alone and recognised by every reader from the leaves
        (:func:`kv_layer_leaves`, :func:`kv_pool_geometry`):

        - **split**: a K and a V leaf a layer, ``(B, kv_heads, S,
          head_size)`` each: the tree ``(k leaves, v leaves)``;
        - **packed** (:func:`kv_packs`: head size 64): ONE leaf a layer,
          ``(B, kv_heads, S, 2 * head_size)``, a position's key in lanes
          ``[0, head_size)`` and its value after it: the tree ``(kv
          leaves, )``. The same bytes at rest, in the row-major form the
          kernels read;
        - **latent** (``kv_lora_rank > 0``): ONE leaf a layer, ``(B, 1,
          latent_width, S)``: the tree ``(latent leaves, )``. POSITION-LAST:
          a position is a column of ``latent_width`` values, because both of
          the block walk's products read a block with its positions minor
          (:func:`_latent_attention_xla`) and the column commit writes it in
          place (:func:`_commit_span_columns`). The same bytes a token as
          ``(B, 1, S, latent_width)``; in that shape the span write is an
          XLA scatter that wants its window (a position's values) minor
          where the walk wants the positions, and a four-step sync moves
          the whole leaf six times between the two (ISSUE 55).

        Scanned models carry each component stacked ``(L, ...)``; unrolled
        models carry per-layer tuples — separate tensors alias IN-PLACE
        through the decode while-loop carry, where a scan's stacked ys
        output is rebuilt (full cache copy) every token.

        ``quantized``: the int8 paged KV tier (serving ``kv_cache_dtype:
        int8``) — the K/V leaves are int8 and each layer carries one more
        leaf, LAST in the tree: one fp16 per-token-row scale shaped (B, 1,
        S, 1), shared by K and V across every head (``(k, v, scale)`` split,
        ``(kv, scale)`` packed). Scales init to 1 (rows past each slot's end
        are never attended), and every leaf keeps its batch/slot axis at
        ``ndim - 4``, so the slot pool's slice/update/copy programs treat
        every geometry uniformly; the position axis is at ``ndim - 2`` of a
        leaf of rows and at ``ndim - 1`` of a leaf of columns, as
        :meth:`cache_spec` declares it.

        A ``linear_attention`` layer (``layer_types``) holds no rows: in the
        same two places of the tree it carries its recurrent state and its
        convolution window, per slot (:meth:`cache_spec`). Where such layers
        stand beside LATENT ones (``kv_lora_rank`` under ``layer_types``) a
        slot holds, by layer, either that pair or one latent leaf: two kinds
        of cache in one tree, the latent layers' second place empty."""
        spec = self.cache_spec(batch_size, max_len, dtype, quantized)
        if self.cfg.scan_layers:
            return tuple(fill((self.cfg.num_layers, ) + shape, t)
                         for _, shape, t, fill in spec[0])
        return self._component_major(spec, lambda kind, shape, t, fill: fill(shape, t))

    @staticmethod
    def _component_major(spec, make):
        """The unrolled cache tree's structure from the per-layer
        declarations: component ``j`` of layer ``i`` at ``tree[j][i]``, None
        where the layer declares fewer than ``j + 1`` components (None is an
        empty subtree: no leaf)."""
        width = max(len(layer) for layer in spec)
        return tuple(tuple(make(*layer[j]) if j < len(layer) else None for layer in spec)
                     for j in range(width))

    def cache_spec(self, batch_size, max_len, dtype=None, quantized=False):
        """What a slot holds, as each layer declares it: for every layer a
        tuple of ``(kind, shape, dtype, fill)`` components, ``kind`` being
        ``"rows"`` (a row axis at ``ndim - 2``, one row a position: K, V,
        the packed pair, the int8 tier's scales) or ``"columns"`` (the
        position axis LAST, one column a position: a latent layer's ``(B, 1,
        latent_width, S)``, the form its readers take and its commit writes
        in place; it grows with a slot's length as rows do) or
        ``"state"`` (per-slot, no row axis: a linear-attention layer's
        recurrent state ``(B, n / p, dk, p dv)``, ``p`` heads side by side in
        the lanes so that a row is whole 128-lane tiles (``gdn_step.
        state_packing``: 2 at the published ``dv`` 192, which would rest
        padded to 256; 1 where no small ``p`` does it), and the ``W - 1``
        last inputs of its convolution ``(B, 1, W - 1, channels)``, at rest
        in the cache dtype; a Mamba layer's ``(B, 1, d_state, d_inner)`` and
        ``(B, 1, W - 1, d_inner)``; a Mamba-2 layer's ``(B, heads, head size, d_state)``
        and ``(B, 1, W - 1, conv channels)``; a short_conv layer's ONE leaf, the
        ``W - 1`` last gated inputs of its convolution ``(B, 1, W - 1, hidden)``) or ``"ring"`` (a row axis at ``ndim - 2`` of
        ``cfg.ring_rows`` rows whatever ``max_len`` is, position ``p`` in row
        ``p mod R``: a windowed differential or full-attention layer's K and
        V, per-slot bytes as a state's are). Every component keeps its slot axis at ``ndim -
        4``. A ``parallel_hybrid`` layer declares BOTH its mixers' components,
        K and V rows and then the Mamba-2 state and window: two kinds in one
        layer, and whoever reads a layer's leaves takes its mixer's slice
        (``Block``) and never counts leaves to tell kinds apart. A layer may declare nothing (a gated memory unit reads the
        forward's carry, a cross-attention layer the rows of the full layer
        below it, a block that is an FFN alone has no mixer).
        :meth:`init_cache` builds the tree from it;
        :meth:`cache_kinds` tells the slot pool which leaves are which. The
        tree is component-major and as wide as the widest declaration; a
        layer that declares fewer has None there."""
        cfg = self.cfg
        dt = dtype or cfg.dtype
        if cfg.latent_width:
            # latent geometry: ONE leaf a layer, a single "head" of
            # kv_lora_rank + qk_rope_head_dim values a position (normalised
            # c_kv and rotated k_r), never expanded to per-head K and V at
            # rest, a position a COLUMN. The slot axis stays at ndim - 4, so
            # slot_slice / slot_update / copy_slot and the radix copy take
            # it as it is.
            if quantized:
                raise NotImplementedError("the latent KV pool has no int8 tier")
            rows = [("columns", (batch_size, 1, cfg.latent_width, max_len), dt, jnp.zeros)]
        else:
            packed = kv_packs(cfg.head_size)
            shape = (batch_size, cfg.kv_heads, max_len,
                     (2 if packed else 1) * cfg.head_size)
            rows = [("rows", shape, jnp.int8 if quantized else dt, jnp.zeros)] * (
                1 if packed else 2)
            if quantized:
                rows.append(("rows", (batch_size, 1, max_len, 1), jnp.float16, jnp.ones))
        if cfg.carries_across_layers:
            if quantized:
                raise NotImplementedError("a pool with ring rows or rows that layers share "
                                          "has no int8 tier")
            pair = lambda kind, n: ((kind, (batch_size, cfg.kv_heads // 2, n,
                                            2 * cfg.head_size), dt, jnp.zeros), ) * 2
            ssm = (("state", (batch_size, 1, cfg.ssm_state_size, cfg.ssm_inner), dt, jnp.zeros),
                   ("state", (batch_size, 1, cfg.ssm_conv_kernel - 1, cfg.ssm_inner), dt,
                    jnp.zeros))

            def declares(i, kind):
                if kind == "mamba":
                    return ssm
                if kind == "diff_attention":
                    return (pair("ring", cfg.ring_rows(i)) if cfg.layer_window(i)
                            else pair("rows", max_len))
                return ()  # gmu, cross_attention: they read the forward's carry

            return [declares(i, t) for i, t in enumerate(cfg.layer_types)]
        mixers = [cfg.layer_mixers(i) for i in range(cfg.num_layers)]
        windows = [cfg.layer_window(i) for i in range(cfg.num_layers)]
        # a multi-token-prediction module holds rows of its own, declared
        # behind the stack's layers like one more full-attention layer
        module = [tuple(rows)] * cfg.mtp_layers
        run = set().union(*mixers)
        two_leaves = {"linear_attention", "mamba2"} & run or () in mixers or any(windows)
        if not two_leaves and "short_conv" not in run:
            return [tuple(rows)] * cfg.num_layers + module
        # (packed rows beside state: a short_conv layer's one leaf alone; the
        # latent row is ONE leaf whatever lies beside it: LatentAttention
        # reads its layer's first place and nothing tells leaves apart by count)
        if quantized or (len(rows) != 2 and two_leaves and not cfg.latent_width):
            raise NotImplementedError("a pool with state or ring leaves has no int8 tier and, "
                                      "but for short_conv layers' windows and latent rows, no "
                                      "packed geometry")
        gdn_pack = gdn_step.state_packing(cfg.linear_num_heads, cfg.linear_value_head_dim)
        state = lambda shape, W, channels: (
            ("state", (batch_size, ) + shape, dt, jnp.zeros),
            ("state", (batch_size, 1, W - 1, channels), dt, jnp.zeros))
        ring = lambda i: (("ring", (batch_size, cfg.kv_heads, cfg.ring_rows(i), cfg.head_size),
                           dt, jnp.zeros), ) * 2
        declares = {
            "full_attention": tuple(rows),
            "linear_attention": state((cfg.linear_num_heads // gdn_pack, cfg.linear_key_head_dim,
                                       gdn_pack * cfg.linear_value_head_dim),
                                      cfg.linear_conv_kernel, cfg.linear_conv_channels),
            "mamba2": state((cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_size),
                            cfg.ssm_conv_kernel, cfg.mamba2_conv_channels),
            "short_conv": (("state", (batch_size, 1, cfg.short_conv_kernel - 1, cfg.hidden_size),
                            dt, jnp.zeros), )}
        # a layer's mixers' leaves in their order: none for an FFN alone, a
        # parallel_hybrid layer's rows and THEN its state and window
        return [ring(i) if w else sum((declares[m] for m in ms), ())
                for i, (ms, w) in enumerate(zip(mixers, windows))] + module

    def cache_kinds(self):
        """``"rows"``, ``"columns"``, ``"ring"`` or ``"state"`` for every leaf
        of :meth:`init_cache`'s tree, in a tree of its structure (sizes do
        not enter)."""
        spec = self.cache_spec(1, 1)
        if self.cfg.scan_layers:
            return tuple(kind for kind, *_ in spec[0])
        return self._component_major(spec, lambda kind, *_: kind)

    def apply_with_cache(self, params, input_ids, kv_cache, cache_index, cache_mask=None,
                         position_ids=None, write_index=None, q_spans=None,
                         lora_ops=None, expert_ops=None, expert_stats=False,
                         ext_ops=None, seq_shard=False, expert_choice=False,
                         with_hidden=False):
        """Forward writing into (and attending over) the KV cache. Returns
        (logits, new_cache). ``cache_mask``: (B, S) attendable cache slots.
        ``write_index``: optional (B,) per-row cache positions (slot-pool
        decode, T == 1 — unless ``q_spans`` widens it); pass ``position_ids``
        alongside it. ``q_spans``: optional (B,) live query counts per row
        (fused chunked-prefill/decode step; see :class:`Attention`).
        ``lora_ops``: optional per-row batched-LoRA operands with a LEADING
        LAYER AXIS — tuple of per-rank-bucket dicts ``site -> (A (L, B,
        in..., r), B (L, B, r, out...))`` (multi-tenant adapter serving;
        see :class:`Attention`); scanned models scan the layer axis
        alongside the cache, unrolled models index it per layer.

        MoE models route through the SERVING dispatch here (per-token
        capacity-free top-k, :meth:`~deepspeed_tpu.moe.layer.MoE._serving`)
        and NEVER collect the training-only gating intermediates — the step
        stays donation-friendly with no mutable-collection host traffic.
        ``expert_ops``: optional cold-expert paging operands with a leading
        layer axis ``(expert->page map (L, E), pools {leaf: (L, R, ...)})``.
        ``expert_stats=True`` additionally returns per-layer routed-token
        counts ``(L, E) int32`` (the scheduler's residency/telemetry
        signal) as a third output; ``expert_choice=True`` the expert ids
        every row chose, ``(L, B, T, k) int32``, as the last output (what a
        reference follows where routing is a near tie).

        ``ext_ops``/``seq_shard``: long-context extent operands and the
        sequence-parallel prefill flag, layer-invariant pass-throughs to
        :class:`Attention` (see there for semantics).

        ``with_hidden=True`` appends the final-norm hidden states (B, T, H)
        as the LAST output. A multi-token-prediction module's leaves (the
        cache tree's entry behind the stack's layers) come back as they went
        in: :meth:`mtp_forward` writes them."""
        mutable = ((["expert_stats"] if expert_stats else [])
                   + (["expert_choice"] if expert_choice else [])) or False
        L = self.cfg.num_layers
        behind = None
        if self.cfg.mtp_layers and not self.cfg.scan_layers:
            behind = tuple(comp[L:] for comp in kv_cache)
            kv_cache = tuple(comp[:L] for comp in kv_cache)
        out = self.module.apply({"params": params}, input_ids, cache_mask, True, kv_cache,
                                cache_index, position_ids, write_index=write_index,
                                q_spans=q_spans, lora_ops=lora_ops,
                                expert_ops=expert_ops, ext_ops=ext_ops,
                                seq_shard=seq_shard, with_hidden=with_hidden,
                                mutable=mutable)
        out, mut = out if mutable else (out, {})
        logits, new_cache, *hidden = out
        if behind is not None:
            new_cache = tuple(comp + rest for comp, rest in zip(new_cache, behind))
        if not mutable:
            return (logits, new_cache) + tuple(hidden)

        def per_layer(collection, tail):
            """One (L, *tail) array from a collection's per-layer leaves."""
            sown = mut.get(collection, {})
            if self.cfg.scan_layers:
                # one stacked leaf under the scanned "layers" scope
                leaves = jax.tree_util.tree_leaves(sown)
            else:
                # unrolled: one leaf per "layer_<i>" scope, walked in NUMERIC
                # layer order (pytree flattening sorts keys lexicographically,
                # which misorders layer_10 vs layer_2)
                leaves = []
                for i in range(self.cfg.num_layers):
                    leaves.extend(jax.tree_util.tree_leaves(sown.get(f"layer_{i}", {})))
            return jnp.concatenate([leaf.reshape((-1, ) + tail) for leaf in leaves], axis=0)

        extra = ()
        if expert_stats:
            extra += (per_layer("expert_stats", (self.cfg.num_experts, )), )
        if expert_choice:
            extra += (per_layer("expert_choice", input_ids.shape + (self.cfg.moe_top_k, )), )
        return (logits, new_cache) + extra + tuple(hidden)

    # ---- fused decode blocks (serving fast path) -------------------------
    def fused_decode_operands(self, params):
        """Per-layer kernel operand tuples for ``ops/pallas/decode_block``,
        derived from the QUANTIZED param tree (``quantize_params`` output
        with ``int8_fused_qkv``). Safe both eagerly (the engine's static
        generate loop caches the result) and in-trace (the scheduler's step
        programs derive per dispatch): the int8 weights and the embedding
        pass through BY REFERENCE — only the small norm/bias/scale leaves
        convert, and missing bias leaves (rmsnorm models carry none)
        synthesize as zeros so the kernels stay uniform.

        Returns ``(layers, head)``: ``layers[i] = (norms (4, H) f32, qkv,
        o, up, down, gate-or-None)`` with each projection a ``(w int8,
        scales f32, bias f32)`` tuple, and ``head`` the final-norm /
        embedding / int8 vocab-projection leaves."""
        cfg = self.cfg
        H = cfg.hidden_size
        f32 = lambda x: jnp.asarray(x, jnp.float32)
        zeros = lambda n: jnp.zeros((n, ), jnp.float32)

        def norm_rows(scope):
            return [f32(scope["scale"]),
                    f32(scope["bias"]) if "bias" in scope else zeros(H)]

        def proj(node, n):
            return (node["kernel_q"], f32(node["kernel_scale"]),
                    f32(node["bias"]) if "bias" in node else zeros(n))

        layers = []
        for i in range(cfg.num_layers):
            lp = params[f"layer_{i}"]
            at, mlp = lp["attn"], lp["mlp"]
            norms = jnp.stack(norm_rows(lp["attn_norm"])
                              + norm_rows(lp["mlp_norm"]))
            Nq = at["qkv_q"].shape[1]
            qkv = (at["qkv_q"], f32(at["qkv_scale"]),
                   f32(at["qkv_bias"]) if "qkv_bias" in at else zeros(Nq))
            F = mlp["up_proj"]["kernel_q"].shape[1]
            gate = proj(mlp["gate_proj"], F) if "gate_proj" in mlp else None
            layers.append((norms, qkv, proj(at["o_proj"], H),
                           proj(mlp["up_proj"], F), proj(mlp["down_proj"], H),
                           gate))
        head = {
            "final_scale": f32(params["final_norm"]["scale"]),
            "embed": params["embed"]["embedding"],
            "logits_q": params["logits_q"],
            "logits_scale": f32(params["logits_scale"]),
        }
        if "bias" in params["final_norm"]:
            head["final_bias"] = f32(params["final_norm"]["bias"])
        if cfg.pos_embedding == "learned":
            head["pos_embed"] = params["pos_embed"]
        if "logits_bias" in params:
            head["logits_bias"] = f32(params["logits_bias"])
        return tuple(layers), head

    def fused_paged_step(self, params, input_ids, kv_cache, position_ids,
                         write_index, q_spans):
        """The fused-decode-block equivalent of the slot-pool
        ``apply_with_cache(params, ids, pool, 0, position_ids=...,
        write_index=..., q_spans=...)`` call the scheduler's step programs
        make: embeds -> per layer (kernel A qkv+norm+rope -> span KV commit
        -> paged attention -> kernel C out/mlp) -> final norm -> int8
        logits. Three resident kernels per layer instead of the
        per-projection path's ~9+ XLA-glued dispatches.

        The KV commit is :class:`Attention`'s own (``_commit_span_rows``)
        and the paged-attention dispatch mirrors its span path (same
        ``paged_decode_attention`` for C == 1 / ``paged_span_attention``
        for C > 1, same int8-KV quantize) so the
        pool stays byte-compatible with the unfused programs — prefill,
        copy_slot, and tier restore interoperate with fused decode on the
        same pool. Only eligible configs reach here (engine
        ``_fused_decode_eligible``): tp=1, so no sharded kernel variants.

        Written for any ``(N, C)`` and any number of pool slots ``N``: the
        scheduler's split chunk sync calls it over all slots' one column and
        over ONE slot's rows of the pool at ``(1, prefill_chunk)``
        (``inference/scheduler.py: _first_forward_live_rows``). The three
        layer kernels and the commit are jitted, so the 36 layers of a
        program share one lowering of each a shape.

        Returns ``(logits (N, C, V) compute-dtype, new_pool)`` with the
        pool structure ``apply_with_cache`` returns."""
        from ..ops.pallas.decode_block import fused_qkv_ln, fused_out_mlp
        from ..ops.pallas.decode_attention import (paged_decode_attention,
                                                   paged_span_attention)
        cfg = self.cfg
        N, C = input_ids.shape
        nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_size
        layers, head = self.fused_decode_operands(params)
        x2d = jnp.take(head["embed"], input_ids.reshape(-1), axis=0)  # (N*C, H)
        pos_flat = position_ids.reshape(-1)
        if cfg.pos_embedding == "learned":
            x2d = x2d + jnp.take(head["pos_embed"], pos_flat,
                                 axis=0).astype(x2d.dtype)
        rope = None
        if cfg.pos_embedding == "rope":
            sin, cos = rope_table(cfg.rotary_dim or hd, cfg.max_seq_len,
                                  cfg.rope_theta)
            rope = (sin[pos_flat], cos[pos_flat], nh + nkv, hd)
        starts = jnp.zeros((N, ), jnp.int32)
        ends = _attended_ends(write_index, q_spans)
        new_layers = []
        for i, (norms, qkv, o, up, down, gate) in enumerate(layers):
            y = fused_qkv_ln(x2d, norms, qkv, eps=cfg.layernorm_epsilon,
                             norm=cfg.norm, rope=rope)
            qf, kf, vf = jnp.split(y, [nh * hd, (nh + nkv) * hd], axis=-1)
            k = kf.reshape(N, C, nkv, hd).transpose(0, 2, 1, 3)
            v = vf.reshape(N, C, nkv, hd).transpose(0, 2, 1, 3)
            writes, kv_split, quant_kv = _kv_writes(
                cfg, tuple(comp[i] for comp in kv_cache), k, v)
            # Attention's span commit; this path attends through the paged
            # kernels unconditionally
            written = _commit_span_rows(writes, write_index, q_spans,
                                        paged_kernels=True)
            ck, cv, csc = _written_kv(written, kv_split, quant_kv)
            if C == 1:
                out = paged_decode_attention(
                    qf.reshape(N, nh, hd), ck, cv, starts, ends,
                    block_kv=cfg.decode_block_kv,
                    k_scale=csc, v_scale=csc)
                attn2d = out.astype(cfg.dtype).reshape(N, nh * hd)
            else:
                q4 = qf.reshape(N, C, nh, hd).transpose(0, 2, 1, 3)
                out = paged_span_attention(
                    q4, ck, cv, starts, ends - 1,
                    block_kv=cfg.decode_block_kv,
                    k_scale=csc, v_scale=csc)
                attn2d = out.astype(cfg.dtype).transpose(0, 2, 1, 3) \
                            .reshape(N * C, nh * hd)
            x2d = fused_out_mlp(attn2d, x2d, norms, o, up, down,
                                activation=cfg.activation,
                                eps=cfg.layernorm_epsilon, norm=cfg.norm,
                                gate=gate)
            new_layers.append(written)
        new_cache = tuple(tuple(lay[j] for lay in new_layers)
                          for j in range(len(new_layers[0])))
        with jax.named_scope("lm_head"):
            x32 = x2d.astype(jnp.float32)
            if "final_bias" in head:  # layernorm head
                mu = jnp.mean(x32, axis=-1, keepdims=True)
                var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
                xn = ((x32 - mu) * jax.lax.rsqrt(var + cfg.layernorm_epsilon)
                      * head["final_scale"] + head["final_bias"])
            else:  # rmsnorm
                ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
                xn = (x32 * jax.lax.rsqrt(ms + cfg.layernorm_epsilon)
                      * head["final_scale"])
            logits = _qmm2d(xn.astype(x2d.dtype), head["logits_q"],
                            head["logits_scale"])
            logits = logits.reshape(N, C, -1)[..., :cfg.vocab_size]
            if "logits_bias" in head:
                logits = logits + head["logits_bias"].astype(logits.dtype)
        return logits, new_cache

    def _apply_kwargs(self, rng):
        """Dropout is active iff a step rng is provided and rate > 0."""
        if rng is not None and self.cfg.dropout > 0:
            return {"rngs": {"dropout": rng}, "deterministic": False}
        return {"deterministic": True}

    def _ce_weight(self, params):
        """(vocab-projection weight, transpose?) for chunked CE."""
        if self.cfg.tie_embeddings:
            return params["embed"]["embedding"], True  # (V, H)
        return params["lm_head"]["kernel"], False  # (H, V)

    def _use_chunked_ce(self):
        """Chunked CE iterates the time axis, which must not be mesh-sharded —
        under sequence parallelism fall back to full logits. Below ~4k vocab
        the dense path is used too: the logits buffer is small there, and the
        jax 0.9 multi-device *CPU* runtime (the test mesh) can rarely abort
        when the chunked program runs many times in one process — at real
        vocab sizes the path runs on TPU, where it is stable."""
        if self.cfg.ce_chunk_size == 0:
            return False
        if self.cfg.ce_chunk_size is None and self.cfg.vocab_size < 4096:
            return False
        if self.cfg.lm_head_bias or self.cfg.lm_head_multiplier != 1.0:
            return False  # chunked CE rebuilds logits from the weight only
        return not (dist.has_mesh() and dist.get_mesh().shape[dist.SEQ_AXIS] > 1)

    def _ce_chunk(self):
        # 256-row chunks measured fastest on v5e (vs 128: −6.7ms/step at
        # bs16/seq1024/vocab50k; 512/1024 are within noise of 256)
        return self.cfg.ce_chunk_size or 256

    def loss(self, params, batch, rng, gather_order=None):
        """Next-token cross entropy. batch: input_ids (B,T), optional labels
        (B,T; -100 = ignore), optional attention_mask (B,T). ``gather_order``:
        the engine's ZeRO-3 plan of the weight gathers the program places
        (``CausalLM.__call__``)."""
        input_ids = batch["input_ids"]
        attn_mask = batch.get("attention_mask")
        kw = self._apply_kwargs(rng)
        if gather_order:
            kw.update(gather_order=gather_order)
        det = kw.pop("deterministic")
        pld_theta = batch.get("__pld_theta__")  # progressive layer drop schedule value
        if pld_theta is not None and rng is not None:
            kw.update(pld_theta=pld_theta, pld_rng=jax.random.fold_in(rng, 0x1D))
        if self._ltd_keep is not None and rng is not None and self._ltd_keep < input_ids.shape[1]:
            kw.update(ltd_keep=self._ltd_keep, ltd_layers=self._ltd_layers,
                      ltd_rng=jax.random.fold_in(rng, 0x17D))
        chunked = self._use_chunked_ce()
        out = self.module.apply({"params": params}, input_ids, attn_mask, det,
                                return_hidden=chunked,
                                mutable=["intermediates"] if self.cfg.num_experts > 0 else False, **kw)
        hidden_or_logits, mutated = out if isinstance(out, tuple) else (out, {})

        if "labels" in batch:
            labels = batch["labels"]
            shift = slice(None)
        else:
            labels = input_ids[:, 1:]
            shift = slice(None, -1)
        valid = (labels >= 0)
        labels_c = jnp.maximum(labels, 0)
        # the vocabulary projection (chunked path) and the cross-entropy,
        # named for the device trace
        with jax.named_scope("loss_ce"):
            if chunked:
                w, transpose = self._ce_weight(params)
                total = chunked_cross_entropy(hidden_or_logits[:, shift], w, labels_c, valid,
                                              chunk=self._ce_chunk(), transpose=transpose)
                loss = total / jnp.maximum(jnp.sum(valid), 1)
            else:
                import optax
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    hidden_or_logits[:, shift].astype(jnp.float32), labels_c)
                loss = jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1)
        if self.cfg.num_experts > 0:
            aux = mutated.get("intermediates", {})
            aux_losses = jax.tree_util.tree_leaves(aux)
            if aux_losses:
                loss = loss + self.cfg.moe_aux_loss_coef * sum(jnp.sum(a) for a in aux_losses)
        return loss

    # ---- pipeline parallelism --------------------------------------------
    def pipeline_loss(self, params, batch, rng, mesh=None):
        """Mean next-token CE over a stream of microbatches, computed through
        the SPMD pipeline (``runtime/pipe/schedule.py``): embed and head run
        replicated over ``pipe`` (tied-embedding grads accumulate without the
        reference's ReduceTiedGrads step, ``pipe/engine.py:223``); the block
        stack is stage-partitioned. ``batch['input_ids']``: (M, b, T)."""
        from ..runtime.pipe.schedule import spmd_pipeline
        cfg = self.cfg
        if not cfg.scan_layers:
            raise ValueError("pipeline parallelism requires scan_layers=True (stacked layer params)")
        ids = batch["input_ids"]
        attn_mask = batch.get("attention_mask")
        M, b, T = ids.shape

        table = params["embed"]["embedding"].astype(cfg.dtype)
        x = table[ids]  # (M, b, T, H)
        if cfg.embed_norm:
            x = make_norm(cfg).apply({"params": params["embed_norm"]}, x)
        if cfg.pos_embedding == "learned":
            x = x + params["pos_embed"][:T].astype(cfg.dtype)
        sin, cos = self._rope()

        block_mod = Block(cfg)
        dropout_on = rng is not None and cfg.dropout > 0

        moe = cfg.num_experts > 0

        def stage_fn(local_layers, h_in, t):
            # h_in: activation, or (activation, mask) when the batch is padded
            h, mask = h_in if isinstance(h_in, tuple) else (h_in, None)
            n_layers = jax.tree_util.tree_leaves(local_layers)[0].shape[0]

            def body(carry, layer):
                h, aux_acc = carry
                lp, li = layer
                kw = {"deterministic": True}
                if dropout_on:
                    # decorrelate dropout per (pipeline step, global layer)
                    kw = {"deterministic": False,
                          "rngs": {"dropout": jax.random.fold_in(jax.random.fold_in(rng, t), li)}}
                if moe:
                    # capture the MoE load-balancing aux loss sown by the
                    # block — the pipeline's aux channel carries it out
                    (y, _), mut = block_mod.apply({"params": lp}, h, sin, cos, mask,
                                                  mutable=["intermediates"], **kw)
                    aux_leaves = jax.tree_util.tree_leaves(mut.get("intermediates", {}))
                    aux_acc = aux_acc + sum(jnp.sum(a) for a in aux_leaves)
                else:
                    y, _ = block_mod.apply({"params": lp}, h, sin, cos, mask, **kw)
                return (y, aux_acc), None

            stage = jax.lax.axis_index(dist.PIPE_AXIS) if dist.in_manual_region() else 0
            global_idx = stage * n_layers + jnp.arange(n_layers)
            aux0 = jnp.zeros((), jnp.float32)
            if dist.in_manual_region():
                # the aux carry becomes stage-varying inside the scan; mark
                # its initial value so the carry types agree (shard_map vma)
                aux0 = jax.lax.pcast(aux0, tuple(dist.get_manual_axes()), to="varying")
            (h, aux), _ = jax.lax.scan(body, (h, aux0), (local_layers, global_idx))
            out = (h, mask) if mask is not None else h
            return (out, aux) if moe else out

        x_stream = (x, attn_mask) if attn_mask is not None else x
        stream = spmd_pipeline(stage_fn, params["layers"], x_stream, mesh=mesh,
                               remat=bool(cfg.remat_policy), with_aux=moe)
        aux_total = jnp.zeros((), jnp.float32)
        if moe:
            stream, aux_total = stream
        if attn_mask is not None:
            stream = stream[0]

        norm_mod = make_norm(cfg)
        stream = norm_mod.apply({"params": params["final_norm"]}, stream)

        if "labels" in batch:
            labels = batch["labels"]
            shift = slice(None)
        else:
            labels = ids[:, :, 1:]
            shift = slice(None, -1)
        valid = labels >= 0
        labels_c = jnp.maximum(labels, 0)
        w, transpose = self._ce_weight(params)
        if self._use_chunked_ce():
            # microbatch stream folds into the batch dim for the chunked CE
            H = stream.shape[-1]
            total = chunked_cross_entropy(stream[:, :, shift].reshape(M * b, -1, H),
                                          w, labels_c.reshape(M * b, -1),
                                          valid.reshape(M * b, -1),
                                          chunk=self._ce_chunk(), transpose=transpose)
            ce_mean = total / jnp.maximum(jnp.sum(valid), 1)
        else:
            import optax
            eq = "mbth,vh->mbtv" if transpose else "mbth,hv->mbtv"
            logits = jnp.einsum(eq, stream[:, :, shift], w.astype(stream.dtype))
            if cfg.lm_head_bias:
                logits = logits + params["lm_head"]["bias"].astype(logits.dtype)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits.astype(jnp.float32),
                                                                 labels_c)
            ce_mean = jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1)
        # aux_total sums per-microbatch aux over the stream; /M matches the
        # non-pipelined per-microbatch mean the engine averages over gas
        return ce_mean + cfg.moe_aux_loss_coef * aux_total / M

    def pipeline_pattern(self):
        """Regex of params whose leading (layer) dim shards over ``pipe``."""
        return r"^layers/" if self.cfg.scan_layers else None

    def pipeline_value_and_grad(self, params, batch, rng, mesh=None):
        """(loss, grads) through the interleaved 1F1B schedule
        (``runtime/pipe/schedule.spmd_pipeline_1f1b``; reference
        ``TrainSchedule`` pipe/schedule.py:189). Memory-bounded alternative
        to differentiating ``pipeline_loss``: per-stage activation liveness
        is O(stages), not O(microbatches). Plain causal-LM streams only
        (no MoE aux channel, no attention-mask ride-along yet)."""
        from ..runtime.pipe.schedule import spmd_pipeline_1f1b
        cfg = self.cfg
        if not cfg.scan_layers:
            raise ValueError("1f1b requires scan_layers=True")
        if cfg.num_experts > 0:
            raise NotImplementedError("1f1b does not carry the MoE aux loss; use the "
                                      "default fill-drain schedule for MoE models")
        if batch.get("attention_mask") is not None:
            raise NotImplementedError("1f1b does not thread attention_mask yet; use the "
                                      "default schedule")
        ids = batch["input_ids"]
        M, b, T = ids.shape
        if "labels" in batch:
            labels = batch["labels"]
            shift = False
        else:
            labels = ids[:, :, 1:]
            shift = True
        valid = labels >= 0
        labels_c = jnp.maximum(labels, 0)
        denom = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)

        sin, cos = self._rope()
        block_mod = Block(cfg)
        dropout_on = rng is not None and cfg.dropout > 0

        # ---- embed (replicated) with a vjp for the stream gradient ----
        embed_keys = [k for k in ("embed", "embed_norm", "pos_embed") if k in params]

        def embed_fwd(ep):
            table = ep["embed"]["embedding"].astype(cfg.dtype)
            x = table[ids]
            if cfg.embed_norm:
                x = make_norm(cfg).apply({"params": ep["embed_norm"]}, x)
            if cfg.pos_embedding == "learned":
                x = x + ep["pos_embed"][:T].astype(cfg.dtype)
            return x

        embed_p = {k: params[k] for k in embed_keys}
        x_stream, embed_vjp = jax.vjp(embed_fwd, embed_p)

        def stage_fn(local_layers, h, t):
            n_layers = jax.tree_util.tree_leaves(local_layers)[0].shape[0]

            def body(h, layer):
                lp, li = layer
                kw = {"deterministic": True}
                if dropout_on:
                    kw = {"deterministic": False,
                          "rngs": {"dropout": jax.random.fold_in(jax.random.fold_in(rng, t), li)}}
                y, _ = block_mod.apply({"params": lp}, h, sin, cos, None, **kw)
                return y, None

            stage = jax.lax.axis_index(dist.PIPE_AXIS) if dist.in_manual_region() else 0
            global_idx = stage * n_layers + jnp.arange(n_layers)
            h, _ = jax.lax.scan(body, h, (local_layers, global_idx))
            return h

        head_keys = ["final_norm"]
        if not cfg.tie_embeddings and "lm_head" in params:
            head_keys.append("lm_head")
        head_p = {k: params[k] for k in head_keys}
        if cfg.tie_embeddings:
            head_p = dict(head_p, embed=params["embed"])  # CE weight is the table

        def loss_head(hp, y, m):
            h = make_norm(cfg).apply({"params": hp["final_norm"]}, y)
            if shift:
                h = h[:, :-1]
            lab = jax.lax.dynamic_index_in_dim(labels_c, m, 0, keepdims=False)
            val = jax.lax.dynamic_index_in_dim(valid, m, 0, keepdims=False)
            if cfg.tie_embeddings:
                w, transpose = hp["embed"]["embedding"], True
            else:
                w, transpose = hp["lm_head"]["kernel"], False
            if self._use_chunked_ce():
                total = chunked_cross_entropy(h, w, lab, val, chunk=self._ce_chunk(),
                                              transpose=transpose)
            else:
                import optax
                eq = "bth,vh->btv" if transpose else "bth,hv->btv"
                logits = jnp.einsum(eq, h, w.astype(h.dtype))
                if cfg.lm_head_bias:
                    logits = logits + hp["lm_head"]["bias"].astype(logits.dtype)
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), lab)
                total = jnp.sum(ce * val)
            # RAW per-microbatch sum: the schedule owns normalization
            # (loss_denom) so the contract can't be mis-specified
            return total

        loss, d_layers, d_head, dxs = spmd_pipeline_1f1b(
            stage_fn, loss_head, params["layers"], head_p, x_stream, mesh=mesh,
            loss_denom=denom)
        (d_embed, ) = embed_vjp(dxs.astype(x_stream.dtype))

        grads = {k: jax.tree_util.tree_map(jnp.zeros_like, v) for k, v in params.items()}
        grads["layers"] = d_layers
        for k in embed_keys:
            grads[k] = d_embed[k]
        for k in head_keys:
            grads[k] = d_head[k]
        if cfg.tie_embeddings:
            # tied table: embedding-lookup grad + CE-weight grad
            grads["embed"] = jax.tree_util.tree_map(jnp.add, grads["embed"],
                                                    d_head["embed"])
        return loss, grads

    # ---- ZeRO-Infinity parameter streaming --------------------------------
    # Layer-granular entry points for the param-offload runner
    # (``runtime/zero/param_offload.py``): host-resident parameter blocks are
    # streamed through these, so HBM never holds more than one block (plus
    # activations). Counterpart of the reference's partitioned-param fetch
    # (``runtime/zero/partitioned_param_swapper.py:36`` + ``stage3.py:463``),
    # with the module-hook machinery replaced by explicit block functions.
    def stream_plan(self, abstract_params):
        """Block partition of the param tree: which top-level keys ride the
        embed block, which the tail block, and the stacked layer key. Tied
        embeddings place "embed" in BOTH blocks (one host copy; the runner
        sums its two grad contributions)."""
        if not self.cfg.scan_layers:
            raise ValueError("parameter streaming requires scan_layers=True "
                             "(stacked layer params)")
        keys = set(abstract_params.keys())
        embed = [k for k in ("embed", "embed_norm", "pos_embed") if k in keys]
        tail = [k for k in ("final_norm", "lm_head") if k in keys]
        if self.cfg.tie_embeddings:
            tail.append("embed")
        extra = keys - set(embed) - set(tail) - {"layers"}
        if extra:
            raise ValueError(f"stream_plan: unrecognized top-level params {sorted(extra)}")
        return {"layer_key": "layers", "embed": embed, "tail": tail}

    def stream_embed(self, embed_tree, input_ids, cache_index=None):
        """Token embedding (+ optional embed norm / learned positions):
        (B, T) ids -> (B, T, H) activations."""
        cfg = self.cfg
        table = embed_tree["embed"]["embedding"].astype(cfg.dtype)
        x = table[input_ids]
        if cfg.embed_norm:
            x = make_norm(cfg).apply({"params": embed_tree["embed_norm"]}, x)
        if cfg.pos_embedding == "learned":
            T = input_ids.shape[1]
            start = 0 if cache_index is None else cache_index
            x = x + jax.lax.dynamic_slice_in_dim(embed_tree["pos_embed"], start, T,
                                                 axis=0).astype(cfg.dtype)
        return x

    def _rope(self):
        return model_rope_table(self.cfg)

    def stream_layer(self, layer_tree, h, attn_mask=None, return_aux=False):
        """One transformer block (deterministic): ``layer_tree`` is a single
        layer's params (the stacked leaves sliced at one index).
        ``return_aux``: also return the MoE load-balancing aux loss (sowed
        intermediates) so the streamed trainer can include its gradient."""
        sin, cos = self._rope()
        if return_aux:
            (y, _), inter = Block(self.cfg).apply({"params": layer_tree}, h, sin, cos,
                                                  attn_mask, mutable=["intermediates"])
            aux = jax.tree_util.tree_leaves(inter)
            aux = sum(jnp.sum(a) for a in aux) if aux else jnp.zeros((), jnp.float32)
            return y, aux
        y, _ = Block(self.cfg).apply({"params": layer_tree}, h, sin, cos, attn_mask)
        return y

    def stream_layer_cached(self, layer_tree, h, kv_cache, cache_index, cache_mask=None):
        """One block in decode mode: attends over (and appends to) this
        layer's KV cache pair (B, kv_heads, S, head_dim)."""
        sin, cos = self._rope()
        y, new_cache = Block(self.cfg).apply({"params": layer_tree}, h, sin, cos,
                                             cache_mask, True, kv_cache, cache_index)
        return y, new_cache

    def stream_tail_loss(self, tail_tree, h, labels, valid, shift=True):
        """final norm + vocab projection + masked CE (mean over valid).
        ``shift``: drop the last hidden position (next-token objective on
        unshifted inputs); grads w.r.t. the FULL ``h`` come out of the vjp
        with zeros there."""
        cfg = self.cfg
        h = make_norm(cfg).apply({"params": tail_tree["final_norm"]}, h)
        if shift:
            h = h[:, :-1]
        labels_c = jnp.maximum(labels, 0)
        if cfg.tie_embeddings:
            w, transpose = tail_tree["embed"]["embedding"], True
        else:
            w, transpose = tail_tree["lm_head"]["kernel"], False
        if self._use_chunked_ce():
            total = chunked_cross_entropy(h, w, labels_c, valid, chunk=self._ce_chunk(),
                                          transpose=transpose)
        else:
            import optax
            eq = "bth,vh->btv" if transpose else "bth,hv->btv"
            logits = jnp.einsum(eq, h, w.astype(h.dtype))
            if cfg.lm_head_bias:
                logits = logits + tail_tree["lm_head"]["bias"].astype(logits.dtype)
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels_c)
            total = jnp.sum(ce * valid)
        return total / jnp.maximum(jnp.sum(valid), 1)

    def stream_logits(self, tail_tree, h):
        """final norm + vocab projection for decode: (B, T, H) -> (B, T, V)."""
        cfg = self.cfg
        h = make_norm(cfg).apply({"params": tail_tree["final_norm"]}, h)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bth,vh->btv", h, tail_tree["embed"]["embedding"].astype(h.dtype))
        else:
            logits = jnp.einsum("bth,hv->btv", h, tail_tree["lm_head"]["kernel"].astype(h.dtype))
            if cfg.lm_head_bias:
                logits = logits + tail_tree["lm_head"]["bias"].astype(logits.dtype)
        return logits

    # ---- sharding rules ---------------------------------------------------
    def tp_rules(self):
        """Megatron row/col sharding over the ``tensor`` axis (the training
        analogue of inference AutoTP, reference ``module_inject/auto_tp.py:84``).
        Note scanned layers carry a leading layer dim.
        """
        t = dist.TENSOR_AXIS
        e = dist.EXPERT_AXIS
        # int8 serving kernels are flattened to matmul layout; the column
        # dim (last) splits over tensor for qkv/gate/up + the vocab head,
        # matching scale columns. Row-split kernels (o/down) stay replicated
        # under int8 (their per-column scales span the full contraction).
        #
        # bitwise_tp (serving): row-parallel kernels (o_proj/down_proj —
        # their tensor shard splits the CONTRACTION dim, forcing a
        # partial-sum all-reduce whose float addition order differs from
        # tp=1) stay replicated; the matching activation re-replication
        # happens in Attention/MLP. Column-parallel rules below are
        # reduction-free (full contraction per shard) and stay.
        bitwise = self.cfg.bitwise_tp
        if self.cfg.scan_layers:
            # scanned layers carry a leading L dim on every block param
            rules = [
                (r"experts/(gate|up)_proj$", (None, e, None, t)),  # (L, E, H, F)
                (r"experts/down_proj$",
                 (None, e, None, None) if bitwise else (None, e, t, None)),  # (L, E, F, H)
                (r"attn/(q|k|v)_proj/kernel$", (None, None, t, None)),  # (L, H, heads, hd)
                (r"attn/o_proj/kernel$",
                 (None, None, None, None) if bitwise
                 else (None, t, None, None)),  # (L, heads, hd, H)
                (r"mlp/(gate|up)_proj/kernel$", (None, None, t)),  # col
                (r"mlp/down_proj/kernel$",
                 (None, None, None) if bitwise else (None, t, None)),  # row
                (r"embed/embedding$", (t, None)),
                (r"lm_head/kernel$", (None, t)),
            ]
            if self.cfg.int8_weights:
                rules += [
                    (r"(q|k|v|gate|up)_proj/kernel_q$", (None, None, t)),  # (L, K, N)
                    (r"(q|k|v|gate|up)_proj/kernel_scale$", (None, None, t)),  # (L, G, N)
                    # int8 expert kernels (L, E, K, N): expert dim over e;
                    # gate/up columns over t (column-parallel, scales match);
                    # down stays t-replicated under bitwise (row-parallel)
                    (r"experts/(gate|up)_proj_(q|scale)$", (None, e, None, t)),
                    (r"experts/down_proj_(q|scale)$",
                     (None, e, None, None) if bitwise else (None, e, t, None)),
                    (r"logits_q$", (None, t)),
                    (r"logits_scale$", (None, t)),
                ]
            return rules
        rules = [
            (r"experts/(gate|up)_proj$", (e, None, t)),
            (r"experts/down_proj$", (e, None, None) if bitwise else (e, t, None)),
            (r"attn/(q|k|v)_proj/kernel$", (None, t, None)),
            (r"attn/o_proj/kernel$",
             (None, None, None) if bitwise else (t, None, None)),
            (r"mlp/(gate|up)_proj/kernel$", (None, t)),
            (r"mlp/down_proj/kernel$", (None, None) if bitwise else (t, None)),
            (r"embed/embedding$", (t, None)),
            (r"lm_head/kernel$", (None, t)),
        ]
        if self.cfg.int8_weights:
            rules += [
                (r"(q|k|v|gate|up)_proj/kernel_q$", (None, t)),  # (K, N)
                (r"(q|k|v|gate|up)_proj/kernel_scale$", (None, t)),  # (G, N)
                (r"experts/(gate|up)_proj_(q|scale)$", (e, None, t)),  # (E, K, N)
                (r"experts/down_proj_(q|scale)$",
                 (e, None, None) if bitwise else (e, t, None)),
                (r"logits_q$", (None, t)),
                (r"logits_scale$", (None, t)),
            ]
        return rules

    def expert_pattern(self):
        return r"moe/experts/" if self.cfg.num_experts > 0 else None
