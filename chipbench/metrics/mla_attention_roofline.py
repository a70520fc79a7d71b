"""``mla_attention_roofline``: the least time the chip could take for the
latent attention's required work over the time it spent under ``mla_attn``.

Required, per layer call (``flops_mla_moe.mla_attention_call``): every live
slot's latent rows read once at 640 B, and 2 x heads x (320 + 256)
operations a row, one query a slot (decode; the wider queries of a prefill
chunk are left out of the required work, so the share reads low, never
high). The live context comes from the job's samples of the pool
(``live_kv_rows``), the number of calls from the trace (one a MoE layer
call). None where there is nothing to read."""

import statistics

from chipbench import flops, flops_mla_moe, xplane
from chipbench import mla_moe_trace as _tr


def reduce(obs):
    cfg, rows = obs.get("model_cfg"), (obs.get("series") or {}).get("live_kv_rows")
    trace = xplane.run_trace(obs)
    took, calls = _tr.picked_seconds(trace, xplane.in_scope("mla_attn")), _tr.layer_calls(trace)
    if not (rows and took and calls and cfg is not None and obs.get("peaks")):
        return None
    ops, nbytes = flops_mla_moe.mla_attention_call(cfg, statistics.fmean(rows), obs["itemsize"])
    least, _bound = flops.roofline_seconds(ops, nbytes, obs["peaks"])
    return 100.0 * calls * least / took
