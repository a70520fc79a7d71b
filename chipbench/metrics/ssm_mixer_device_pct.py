"""``ssm_mixer_device_pct``: device time under the scopes of the state-space
side of a SambaY stack (``models/transformer.py: Mamba``, ``GatedMemoryUnit``):
``ssm_proj`` (the in, convolution, x and dt projections), ``ssm_state`` (the
scan or the one-token update, the read-out and the store), ``ssm_out`` (the
gate and W_out) and ``gmu`` (a gated memory unit's two projections), over the
traced window. None where the trace has no such scope (a program without
the layers)."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs),
                               xplane.in_scope("ssm_proj", "ssm_state", "ssm_out", "gmu"))
