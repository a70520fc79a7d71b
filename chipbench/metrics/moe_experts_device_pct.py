"""``moe_experts_device_pct``: device time of the grouped products over the
experts held (``moe/layer.py: sparse_expert_ffn``) over the traced window:
the operations traced under the ``moe_experts`` scope and the ``ragged-dot``
calls themselves, which the TPU compiler's expansion of
``jax.lax.ragged_dot`` names ``ragged-dot-none`` / ``ragged-dot-metadata``
and leaves without a scope path."""

from chipbench import mla_moe_trace, xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), mla_moe_trace.expert_products)
