"""``ssd_state_device_pct``: device time under the ``ssd_state`` scope (the
one-token update of a decode step, the chunked matrix form of a prefill
chunk, the read-out and the store of the state and the window,
``models/mamba2.py``) over the traced window. None where the trace has no
such scope."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.in_scope("ssd_state"))
