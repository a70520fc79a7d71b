"""What the roofline readers of the latent-attention MoE cell share
(``metrics/moe_experts_roofline.py``, ``metrics/mla_attention_roofline.py``):
seconds under a scope and MoE layer calls in the traced window."""

import re

from chipbench import xplane

# one grouped product on the device (``jax.lax.ragged_dot`` as the TPU
# compiler lowers it; its metadata call is ``ragged-dot-metadata``)
_PRODUCT = re.compile(r"ragged[-_]dot(?![-_]metadata)")
PRODUCTS_PER_LAYER_CALL = 3  # gate, up, down


_IN_EXPERTS = xplane.in_scope("moe_experts")


def expert_products(name, scope):
    """``pick`` for ``xplane.device_share``: the grouped expert products.
    The compiler's expansion of ``ragged_dot`` drops the scope path, so the
    calls are picked by name; what is fused around them (the gate's
    activation) carries the ``moe_experts`` scope."""
    return bool(_IN_EXPERTS(name, scope) or "ragged-dot" in name or "ragged_dot" in name)


def picked_seconds(trace, pick):
    """Device self seconds of the operations ``pick`` takes in the traced
    window, mean over the devices; None where there is none."""
    share = xplane.device_share(trace, pick)
    return None if share is None else share / 100.0 * (trace["t1"] - trace["t0"])


def layer_calls(trace):
    """MoE layer calls the first device started in the traced window:
    grouped products over three. 0 where the trace names none."""
    if trace is None:
        return 0
    t0, t1 = trace["t0"], trace["t1"]
    dev = sorted(trace["devices"])[0]
    n = sum(1 for name, s, _d, sc in trace["devices"][dev]
            if t0 <= s < t1 and (_PRODUCT.search(name) or _PRODUCT.search(sc.rpartition("/")[2])))
    return n / PRODUCTS_PER_LAYER_CALL
