"""Operations and bytes that an ``exaone_moe`` stack's gated experts need,
from shapes and counts alone: the yardstick of ``gated_experts_roofline``, kept
with the benchmark (``flops_mla_moe.py`` holds the latent-attention cell's
experts of three matrices under its softmax router, ``flops_nemotron_h.py``
the experts of two). All counts are of REQUIRED work: a multiply-add is two
operations; padding rows, rows routed to experts held elsewhere and experts
no row touched count nothing; the counts are the same whatever evaluates the
layer, the grouped product or the dense one over the experts held. The
function is linear in its counts: it takes one layer call's or a whole
window's, the module's expert layer among them.
"""


def gated_expert_weight_bytes(cfg, itemsize):
    """One routed expert's THREE matrices (gate, up: H x F; down: F x H):
    75,497,472 B at 6,144 x 2,048 in bf16."""
    return 3 * cfg.hidden_size * cfg.expert_ffn_size * itemsize


def gated_experts_call(cfg, experts_touched, pairs_here, itemsize):
    """(operations, bytes) of the routed experts' products: the weights of the
    experts held here that some live row routed to, read once a layer call
    (``experts_touched``: summed over the calls); per row-expert pair held
    here, the row in and the result out, and the three products (6 x H x F
    operations)."""
    ops = 6.0 * cfg.hidden_size * cfg.expert_ffn_size * pairs_here
    nbytes = (experts_touched * gated_expert_weight_bytes(cfg, itemsize)
              + pairs_here * 2 * cfg.hidden_size * itemsize)
    return ops, nbytes
