"""``ssm_state_roofline``: the least time the chip could take for the Mamba
layers' required state traffic over the time it spent under ``ssm_state``.

Required, per layer call (``flops_sambay.ssm_state_call``): every live slot's
state and convolution window read once and written once (194,560 B each way
at the published sizes), one token a slot. The calls: the forwards over the
whole slot block the scheduler fetched while the trace ran
(``column_forwards_traced``, from the job: a sync's column and its substeps)
times the Mamba layers. A prefill chunk's one-slot scan is left out of the
required work and its time is under the scope, so the share reads low, never
high. The live slots come from the job's samples of the pool. None where
there is nothing to read."""

import statistics

from chipbench import flops, flops_sambay, xplane


def reduce(obs):
    cfg = obs.get("model_cfg")
    occupancy = (obs.get("series") or {}).get("slot_occupancy_pct")
    forwards = (obs.get("values") or {}).get("column_forwards_traced")
    trace = xplane.run_trace(obs)
    share = xplane.device_share(trace, xplane.in_scope("ssm_state"))
    layers = sum(t == "mamba" for t in getattr(cfg, "layer_types", ()))
    if not (share and occupancy and forwards and layers and obs.get("peaks")
            and obs.get("num_slots")):
        return None
    took = share / 100.0 * (trace["t1"] - trace["t0"])
    live = statistics.fmean(occupancy) / 100.0 * obs["num_slots"]
    ops, nbytes = flops_sambay.ssm_state_call(cfg, live, obs["itemsize"])
    least, _bound = flops.roofline_seconds(ops, nbytes, obs["peaks"])
    return 100.0 * forwards * layers * least / took
