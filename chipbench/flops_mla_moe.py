"""Operations and bytes that latent attention and the routed experts need,
from shapes and counts alone: the yardstick of ``mla_attention_roofline`` and
``moe_experts_roofline``, kept with the benchmark (``flops.py`` holds the
dense models'). All counts are of REQUIRED work: a multiply-add is two
operations; padding rows, rows routed to experts held elsewhere and experts
no row touched count nothing.
"""


def expert_weight_bytes(cfg, itemsize):
    """One routed expert's three matrices (gate, up: H x F; down: F x H)."""
    return 3 * cfg.hidden_size * cfg.expert_ffn_size * itemsize


def moe_experts_call(cfg, experts_touched, pairs_here, itemsize):
    """(operations, bytes) of ONE layer's grouped expert products in one
    forward: the weights of the experts held here that some live row routed
    to, read once; per row-expert pair held here, the row in and the result
    out, and the three products (6 x H x F operations)."""
    ops = 6.0 * cfg.hidden_size * cfg.expert_ffn_size * pairs_here
    nbytes = (experts_touched * expert_weight_bytes(cfg, itemsize)
              + pairs_here * 2 * cfg.hidden_size * itemsize)
    return ops, nbytes


def latent_row_bytes(cfg, itemsize):
    """One position of one layer's latent cache: c_kv and k_r."""
    return cfg.latent_width * itemsize


def mla_attention_call(cfg, context_rows, itemsize):
    """(operations, bytes) of ONE layer's absorbed attention in one decode
    forward, one query a live slot: ``context_rows`` = the live slots'
    context lengths added up. Each latent row is read once and, for every
    head, multiplied into a score (rank + rope wide) and into the value sum
    (rank wide)."""
    per_row = 2.0 * cfg.num_heads * (cfg.latent_width + cfg.kv_lora_rank)
    return per_row * context_rows, float(context_rows) * latent_row_bytes(cfg, itemsize)
