"""``gated_experts_roofline``: the least time the chip could take for the
required work of gated routed experts (three matrices) under a sigmoid router
over the time it spent in their products (``mla_moe_trace.expert_products``:
the ``moe_experts`` scope, the stack's layers and the drafting module's alike,
and any ``ragged-dot`` call by name).

Required (``flops_exaone_moe.gated_experts_call``): the weights of the experts
held here that some live row routed to, read once a layer call, the rows in
and out and 6 x H x F operations for the pairs held here. The counts are the
program's (``serving/moe_experts_touched``, ``serving/moe_pairs_here``, over
``serving/moe_layer_calls`` calls), read by the job where the trace starts and
where it stops (``moe_experts_touched_traced``, ``moe_pairs_here_traced``).
Padding rows and untouched experts count nothing, so the share cannot pass
100%. None where the job read no such counters, the model's router is not a
sigmoid over gated experts, or the trace has no such operations."""

from chipbench import flops, flops_exaone_moe, xplane
from chipbench import mla_moe_trace as _tr


def reduce(obs):
    cfg = obs.get("model_cfg")
    values = obs.get("values") or {}
    touched = values.get("moe_experts_touched_traced")
    pairs = values.get("moe_pairs_here_traced")
    took = _tr.picked_seconds(xplane.run_trace(obs), _tr.expert_products)
    if not (touched and pairs and took and obs.get("peaks")
            and getattr(cfg, "activation", None) == "swiglu"
            and getattr(cfg, "moe_scoring", None) == "sigmoid"):
        return None
    ops, nbytes = flops_exaone_moe.gated_experts_call(cfg, touched, pairs, obs["itemsize"])
    least, _bound = flops.roofline_seconds(ops, nbytes, obs["peaks"])
    return 100.0 * least / took
