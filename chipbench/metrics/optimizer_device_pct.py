"""``optimizer_device_pct``: device time of the operations traced under the
``optimizer`` and ``grad_norm`` scopes (``runtime/engine.py: _apply_grads``)
over the traced window, mean over chips. A fusion is booked under the scope
of the one operation XLA names it after."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.in_scope("optimizer", "grad_norm"))
