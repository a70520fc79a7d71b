"""``pump_idle_dispatch_pct``: the share of the traced window in which the
first chip ran nothing while the serving pump was under ``dstpu/sched/dispatch``
or ``dstpu/sched/fetch``: launch latency, and the tail between the device
finishing and the host holding the tokens. With the two other ``pump_idle_*``
it adds up to the cell's idle share (``chipbench/xplane.py: idle_by_host``)."""

from chipbench import xplane


def reduce(obs):
    return xplane.pump_idle_pct(obs, "dispatch")
