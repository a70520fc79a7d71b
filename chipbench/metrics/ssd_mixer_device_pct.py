"""``ssd_mixer_device_pct``: device time under the three scopes of a Mamba-2
mixer (``models/mamba2.py``): ``ssd_proj`` (the in-projection, the
convolution), ``ssd_state`` (the one-token update or the chunk's matrix
form, the read-out, the store) and ``ssd_out`` (the gated norm, W_out), over
the traced window. None where the trace has no such scope (a program
without the layers)."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs),
                               xplane.in_scope("ssd_proj", "ssd_state", "ssd_out"))
