"""``short_conv_device_pct``: device time under the three scopes of a gated
short convolution (``models/transformer.py: ShortConv``): ``conv_proj`` (the
in-projection and the gate product), ``conv_state`` (the carried rows' read,
the sum over the taps, the store) and ``conv_out`` (the C gate and W_out),
over the traced window. None where the trace has no such scope (a program
without the layers).

A LOWER bound of what the layers cost: the compiled step prefetches an
operator's weights under the operations before it, so the time under its own
scopes leaves out most of their fetch (31 us a layer call where the weights'
33,566,720 B alone need 41 us, ``PERF.md`` section 7). It moves when the work
inside the scopes moves; what the layers cost the step needs a layer-alone
timing, which no reader here makes yet."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs),
                               xplane.in_scope("conv_proj", "conv_state", "conv_out"))
