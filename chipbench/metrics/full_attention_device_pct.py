"""``full_attention_device_pct``: device time of the full-attention layers'
two Pallas calls, ``dstpu_decode_attn`` (``ops/pallas/decode_attention.py``,
one- and multi-column) and ``dstpu_kv_commit`` (``ops/pallas/kv_commit.py``),
by name, over the traced window."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs),
                               xplane.named("dstpu_decode_attn", "dstpu_kv_commit"))
