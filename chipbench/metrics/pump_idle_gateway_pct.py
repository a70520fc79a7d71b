"""``pump_idle_gateway_pct``: the share of the traced window in which the first
chip ran nothing while the serving pump was under ``dstpu/gateway/admit`` or
``dstpu/gateway/idle``, or under no ``dstpu/sched/...`` span at all: the
gateway's part of the pump, between scheduler iterations. With the two other
``pump_idle_*`` it adds up to the cell's idle share
(``chipbench/xplane.py: idle_by_host``)."""

from chipbench import xplane


def reduce(obs):
    return xplane.pump_idle_pct(obs, "gateway")
