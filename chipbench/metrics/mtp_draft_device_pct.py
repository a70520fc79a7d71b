"""``mtp_draft_device_pct``: device time under ``mtp_draft`` (the
multi-token-prediction module as the verify-and-draft step runs it:
``models/transformer.py: CausalLMModel.mtp_forward``: the next tokens'
embedding, the two norms and W_eh, the module's block with its own K/V rows
and experts, its final norm and the head), over the traced window. None where
the trace has no such scope (a program without a device drafter)."""

from chipbench import xplane


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs), xplane.in_scope("mtp_draft"))
