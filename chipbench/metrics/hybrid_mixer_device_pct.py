"""``hybrid_mixer_device_pct``: device time under the ``hybrid_mixer`` scope of
a two-mixer block (``models/transformer.py: Block``, a ``parallel_hybrid``
layer: both branches on the one normed input, attention's projections, key
scale, commit and walk, the Mamba-2 mixer's three scopes, the two output
multipliers and the sum) over the traced window: the share of a step that is
the architecture's own part. ``ssd_mixer_device_pct`` and
``full_attention_device_pct`` read parts of it and cannot add up to more.
None where the trace has no such scope (a program without the layers)."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.in_scope("hybrid_mixer"))
