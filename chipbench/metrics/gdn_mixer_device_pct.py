"""``gdn_mixer_device_pct``: device time under the three scopes of a
linear-attention layer's mixer (``models/transformer.py: GatedDeltaNet``):
``gdn_proj`` (the six projections and the convolution), ``gdn_state`` (the
scan or the one-token update, and the read-out) and ``gdn_out`` (the gated
norm and W_o), over the traced window. None where the trace has no such
scope (a program without the layer)."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs),
                               xplane.in_scope("gdn_proj", "gdn_state", "gdn_out"))
