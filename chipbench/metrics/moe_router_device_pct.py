"""``moe_router_device_pct``: device time under the ``moe_router`` scope (the
router's product, top-k, the sort of the row-expert pairs, the gather into
expert order and the weighted scatter back) over the traced window."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.in_scope("moe_router"))
