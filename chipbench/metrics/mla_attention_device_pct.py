"""``mla_attention_device_pct``: device time under the ``mla_attn`` scope
(the absorb of W_kvb into the queries, scores, softmax, values and the value
up-projection of ``models/transformer.py: LatentAttention``) over the traced
window. The low-rank projections are under ``mla_proj`` and not in it."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.in_scope("mla_attn"))
