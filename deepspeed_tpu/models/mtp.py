"""A multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437, eq. 21-24;
``num_nextn_predict_layers`` in the configurations that carry it): one more
block behind the stack that, at position ``i``, reads the stack's output
``g_i`` (after its final norm) and the embedding of the NEXT token ``t_(i+1)``
and predicts the token after that:

    u_i = W_eh [RMSNorm_e(Emb(t_(i+1))) ; RMSNorm_h(g_i)]
    z_i = Block(u)_i ;  p_i = Head(RMSNorm_f(z_i))

``Emb`` and ``Head`` are the model's own (the caller looks the embedding up
and applies the head: :meth:`CausalLMModel.mtp_forward`); the block is the
model's block with full attention over K/V rows of ITS OWN (``cache_spec``
declares them behind the stack's layers) and the model's FFN above the leading
dense layers (routed experts where the model has them). ``argmax p_i`` is a
draft of token ``i + 2``: the serving scheduler verifies it in the stack's
next step (``inference/scheduler.py``, "Drafting on the device").
"""

import dataclasses

import jax.numpy as jnp
import flax.linen as nn

from .transformer import Block, make_norm, model_rope_table


def block_config(cfg):
    """The configuration of the module's one block: a full-attention layer of
    the model's widths (no window, so no rotation where only windowed layers
    rotate) with the FFN of the layers above the dense ones."""
    return dataclasses.replace(cfg, num_layers=1, layer_types=("full_attention", ),
                               layer_windows=(), moe_first_dense=0, mtp_layers=0,
                               scan_layers=False)


class MTPModule(nn.Module):
    cfg: any  # TransformerConfig

    @nn.compact
    def __call__(self, hidden, next_emb, kv_cache=None, position_ids=None, write_index=None,
                 q_spans=None):
        """``hidden`` (B, T, H): the stack's normed output; ``next_emb`` (B, T,
        H): the embedding of each position's next token. With ``kv_cache``
        (the module's K and V leaves) it serves through the slot pool's spans
        like any layer. Returns ``RMSNorm_f(z)`` (and the written leaves)."""
        cfg = self.cfg
        e = make_norm(cfg, name="enorm")(next_emb)
        h = make_norm(cfg, name="hnorm")(hidden)
        u = nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype, param_dtype=jnp.float32,
                     kernel_init=nn.initializers.normal(0.02),
                     name="eh_proj")(jnp.concatenate([e, h], axis=-1))
        bcfg = block_config(cfg)
        sin, cos = model_rope_table(bcfg)
        y, new_cache = Block(bcfg, layer_idx=0, name="block")(
            u, sin, cos, None, True, kv_cache, None, position_ids, write_index, q_spans)
        z = make_norm(cfg, name="final_norm")(y)
        return z if kv_cache is None else (z, new_cache)
