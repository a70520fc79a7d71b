"""Operations and bytes that a ``nemotron_h`` stack's Mamba-2 state update
and its experts of two matrices need, from shapes and counts alone: the
yardsticks of ``ssd_state_roofline`` and ``relu2_experts_roofline``, kept
with the benchmark (``flops_sambay.py`` holds Mamba-1's, ``flops_mla_moe.py``
the experts of three matrices). All counts are of REQUIRED work: a
multiply-add is two operations; slots with no live request, padding rows,
rows routed to experts held elsewhere and experts no row touched count
nothing; the counts are the same whatever implements the layer, a kernel or
XLA. Both functions are linear in their counts: they take one layer call's
or a whole window's.
"""


def ssd_state_values(cfg):
    """Values of one slot's Mamba-2 state in one layer: heads x head size x
    state size (524,288 at the published sizes)."""
    return cfg.ssm_num_heads * cfg.ssm_head_dim * cfg.ssm_state_size


def ssd_slot_values(cfg):
    """Values one slot holds for one Mamba-2 layer: the state and the
    convolution's last ``W - 1`` inputs of x, B and C (542,720: 1,085,440 B
    in bf16)."""
    return ssd_state_values(cfg) + (cfg.ssm_conv_kernel - 1) * cfg.mamba2_conv_channels


def ssd_state_call(cfg, slot_updates, chunk_tokens, itemsize):
    """(operations, bytes) of Mamba-2 state work: ``slot_updates`` one-token
    updates (a live slot in one layer's one-token forward) and
    ``chunk_tokens`` positions of prefill chunks (a position in one layer).

    A one-token update reads the slot's state and window once and writes
    them once (Delta, x, B, C and y are a hundredth of that and left out);
    per state value the decay, the input's outer product and the read-out
    with C: 1 + 2 + 2 operations (the decay's exponential is one a head). A
    chunk's positions need the same operations each (the chunked matrix form
    is the same mathematics: within 5% of it at chunks of 128) and the
    slot's state and window read and written once a ``ssm_chunk_size``
    positions, where the form carries the state on."""
    ops = 5.0 * ssd_state_values(cfg) * (slot_updates + chunk_tokens)
    nbytes = (2.0 * ssd_slot_values(cfg) * itemsize
              * (slot_updates + chunk_tokens / cfg.ssm_chunk_size))
    return ops, nbytes


def relu2_expert_weight_bytes(cfg, itemsize):
    """One routed expert's TWO matrices (up: H x F; down: F x H)."""
    return 2 * cfg.hidden_size * cfg.expert_ffn_size * itemsize


def relu2_experts_call(cfg, experts_touched, pairs_here, itemsize):
    """(operations, bytes) of the grouped products of experts of two
    matrices: the weights of the experts held here that some live row routed
    to, read once a layer call (``experts_touched``: summed over the calls);
    per row-expert pair held here, the row in and the result out, and the
    two products (4 x H x F operations)."""
    ops = 4.0 * cfg.hidden_size * cfg.expert_ffn_size * pairs_here
    nbytes = (experts_touched * relu2_expert_weight_bytes(cfg, itemsize)
              + pairs_here * 2 * cfg.hidden_size * itemsize)
    return ops, nbytes
