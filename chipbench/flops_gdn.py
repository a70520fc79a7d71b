"""Operations and bytes that a gated-delta-rule layer's state update needs,
from shapes and counts alone: the yardstick of ``gdn_state_roofline``, kept
with the benchmark (``flops.py`` holds the dense models',
``flops_mla_moe.py`` latent attention's and the experts'). All counts are of
REQUIRED work: a multiply-add is two operations; slots with no live request
count nothing.
"""


def state_bytes(cfg, itemsize):
    """One slot's recurrent state in one layer: heads x dk x dv values."""
    return (cfg.linear_num_heads * cfg.linear_key_head_dim * cfg.linear_value_head_dim
            * itemsize)


def gdn_state_call(cfg, live_slots, itemsize):
    """(operations, bytes) of ONE layer's one-token state update and
    read-out in one decode forward, one token a live slot: each live slot's
    state read once and written once (q, k, v and the output are a
    thousandth of that and left out); per state value the decay, the two
    products with k (prediction, update) and the product with q: 1 + 3 x 2
    operations."""
    values = cfg.linear_num_heads * cfg.linear_key_head_dim * cfg.linear_value_head_dim
    return 7.0 * values * live_slots, 2.0 * live_slots * state_bytes(cfg, itemsize)
