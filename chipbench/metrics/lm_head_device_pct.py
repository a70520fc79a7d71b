"""``lm_head_device_pct``: device time of a served step's head over the traced
window: the final norm and the vocabulary product under the ``lm_head`` scope
(``models/transformer.py``; an untied head's ``nn.Dense(name="lm_head")`` puts
the same word in its path), the choice of the token under ``sample`` (XLA
fuses the product with the argmax, and the fusion carries one of the two), and
the ``dstpu_quant_matmul`` calls (an int8 head's product)."""

from chipbench import xplane

_SCOPED = xplane.in_scope("lm_head", "sample")
_INT8 = xplane.named("dstpu_quant_matmul")


def reduce(obs):
    return xplane.device_share(xplane.run_trace(obs),
                               lambda name, scope: _SCOPED(name, scope) or _INT8(name, scope))
