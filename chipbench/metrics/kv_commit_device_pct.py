"""``kv_commit_device_pct``: device time of the operations traced under the
``kv_commit`` scope (the scatters that write new rows into the KV pool,
``models/transformer.py``) over the traced window. A fusion is booked under
the scope of the one operation XLA names it after."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.in_scope("kv_commit"))
