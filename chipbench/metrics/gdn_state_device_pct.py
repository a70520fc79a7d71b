"""``gdn_state_device_pct``: device time under the ``gdn_state`` scope (the
chunked scan of a prefill chunk, the one-token update of a decode step, the
read-out and the store of the state and the window,
``models/transformer.py: GatedDeltaNet``) over the traced window."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.in_scope("gdn_state"))
