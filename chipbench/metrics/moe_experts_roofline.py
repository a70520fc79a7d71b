"""``moe_experts_roofline``: the least time the chip could take for the
routed experts' required work over the time it spent in them (``mla_moe_trace.expert_products``).

Required, per layer call (``flops_mla_moe.moe_experts_call``): the weights of
the experts held here that some live row routed to, the rows in and out and
6 x H x F operations for the pairs held here. The mean call comes from the
program's counters (``serving/moe_experts_touched``, ``moe_pairs_here`` over
``moe_layer_calls``), the number of calls from the trace (grouped products
over three). Padding rows and untouched experts count nothing, so the share
cannot pass 100%. None where the program has no such counters or spans."""


from chipbench import flops, flops_mla_moe, xplane
from chipbench import mla_moe_trace as _tr


def reduce(obs):
    counters = (obs.get("telemetry") or {}).get("counters") or {}
    total = lambda n: counters.get("serving/" + n, {}).get("total", 0)
    cfg = obs.get("model_cfg")
    trace = xplane.run_trace(obs)
    took, calls = _tr.picked_seconds(trace, _tr.expert_products), _tr.layer_calls(trace)
    if not (total("moe_layer_calls") and took and calls and cfg is not None and obs.get("peaks")):
        return None
    ops, nbytes = flops_mla_moe.moe_experts_call(
        cfg, total("moe_experts_touched") / total("moe_layer_calls"),
        total("moe_pairs_here") / total("moe_layer_calls"), obs["itemsize"])
    least, _bound = flops.roofline_seconds(ops, nbytes, obs["peaks"])
    return 100.0 * calls * least / took
