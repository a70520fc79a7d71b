"""``fused_block_device_pct``: device time of the two fused int8 layer kernels,
``dstpu_fused_qkv_ln`` and ``dstpu_fused_out_mlp`` (``ops/pallas/decode_block.py``),
over the traced window."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs),
                               xplane.named("dstpu_fused_qkv_ln", "dstpu_fused_out_mlp"))
