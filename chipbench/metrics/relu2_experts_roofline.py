"""``relu2_experts_roofline``: the least time the chip could take for the
required work of routed experts of TWO matrices over the time it spent in
their grouped products (``mla_moe_trace.expert_products``: the ``moe_experts``
scope and the ``ragged-dot`` calls by name).

Required (``flops_nemotron_h.relu2_experts_call``): the weights of the
experts held here that some live row routed to, read once a layer call, the
rows in and out and 4 x H x F operations for the pairs held here. The counts
are the program's (``serving/moe_experts_touched``, ``serving/moe_pairs_here``),
read by the job where the trace starts and where it stops
(``moe_experts_touched_traced``, ``moe_pairs_here_traced``), and not a mean
call times the grouped products in the trace over two: a prefill chunk's
3,072 pairs take their products in two tiles, which would count its layer
call twice. Padding rows and untouched experts count nothing, so the share
cannot pass 100%. None where the job read no such counters or the trace has
no such operations."""

from chipbench import flops, flops_nemotron_h, xplane
from chipbench import mla_moe_trace as _tr


def reduce(obs):
    cfg = obs.get("model_cfg")
    values = obs.get("values") or {}
    touched = values.get("moe_experts_touched_traced")
    pairs = values.get("moe_pairs_here_traced")
    took = _tr.picked_seconds(xplane.run_trace(obs), _tr.expert_products)
    if not (touched and pairs and took and obs.get("peaks")
            and getattr(cfg, "activation", None) == "relu2"):
        return None
    ops, nbytes = flops_nemotron_h.relu2_experts_call(cfg, touched, pairs, obs["itemsize"])
    least, _bound = flops.roofline_seconds(ops, nbytes, obs["peaks"])
    return 100.0 * least / took
