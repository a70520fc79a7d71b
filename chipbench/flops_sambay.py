"""Operations and bytes that a SambaY stack's state update and its two kinds
of attention need, from shapes and counts alone: the yardsticks of
``ssm_state_roofline`` and ``attention_rows_roofline``, kept with the
benchmark (``flops.py`` holds the dense models', ``flops_gdn.py`` the gated
delta rule's). All counts are of REQUIRED work: a multiply-add is two
operations; slots with no live request count nothing; the counts are the same
whatever implements the layer, a kernel or XLA.
"""


def ssm_slot_values(cfg):
    """Values one slot holds for one Mamba layer: the state (d_state x
    d_inner) and the convolution's last inputs ((W - 1) x d_inner)."""
    return (cfg.ssm_state_size + cfg.ssm_conv_kernel - 1) * cfg.ssm_inner


def ssm_state_call(cfg, live_slots, itemsize):
    """(operations, bytes) of ONE Mamba layer's one-token state update and
    read-out in one decode forward, one token a live slot: each live slot's
    state and window read once and written once (Delta, x, B, C and y are a
    hundredth of that and left out); per state value the decay's product
    with Delta A, its exponential, the decay, the input's outer product and
    the read-out with C: 1 + 1 + 2 + 2 + 2 operations."""
    states = cfg.ssm_state_size * cfg.ssm_inner
    return 8.0 * states * live_slots, 2.0 * live_slots * ssm_slot_values(cfg) * itemsize


def attention_row_bytes(cfg, itemsize):
    """One attended position's K and V in one layer: 2 x kv_heads x head
    size values (5,120 B at the published sizes in bf16)."""
    return 2 * cfg.kv_heads * cfg.head_size * itemsize


def attention_rows(cfg, rows, itemsize):
    """(operations, bytes) of attending ``rows`` K/V positions (summed over
    layers and slots, each read once a layer: the scheduler's
    ``serving/attn_rows_window`` + ``serving/attn_rows_shared``), one query a
    slot: a position's K and V read once; per position and query head the
    score (2 x head size) and the 2 x head size wide read-out of the pair's
    values (2 x 2 x head size). A chunk's wider queries are left out of the
    operations, so the share reads low, never high."""
    ops = rows * cfg.num_heads * 6.0 * cfg.head_size
    return ops, rows * attention_row_bytes(cfg, itemsize)
