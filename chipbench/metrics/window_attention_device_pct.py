"""``window_attention_device_pct``: device time under ``swa_attn`` (the
windowed differential-attention layers of ``models/transformer.py:
DiffAttention``: the ring's commit, the two maps, their combination and the
sub-norm; the projections are outside it), over the traced window. None
where the trace has no such scope."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.in_scope("swa_attn"))
