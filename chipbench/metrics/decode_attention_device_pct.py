"""``decode_attention_device_pct``: device time of the ``dstpu_decode_attn``
Pallas calls (``ops/pallas/decode_attention.py``, one- and multi-column) over
the traced window."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.named("dstpu_decode_attn"))
