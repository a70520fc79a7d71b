"""``shared_kv_attention_device_pct``: device time under ``shared_attn`` (the
full differential-attention layer and the cross-attention layers that read
its rows, ``models/transformer.py: DiffAttention``: the commit, the two
maps, their combination and the sub-norm; the projections are outside it),
over the traced window. None where the trace has no such scope."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.in_scope("shared_attn"))
