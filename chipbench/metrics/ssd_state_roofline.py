"""``ssd_state_roofline``: the least time the chip could take for the
Mamba-2 layers' required state work over the time it spent under
``ssd_state``.

Required (``flops_nemotron_h.ssd_state_call``): every one-token update's
state and window read once and written once (1,085,440 B each way at the
published sizes), and a prefill chunk's positions with the state carried once
a 128 of them. Both are counted by the program from the host's spans at every
dispatch (``serving/ssd_state_updates``, ``serving/ssd_chunk_tokens``) and
read by the job where the trace starts and where it stops
(``ssd_state_updates_traced``, ``ssd_chunk_tokens_traced``): the chunk is in
the required work, as its time is under the scope. Slots with no live
request count nothing, so the share cannot pass 100%. None where the program
has no such counters or the trace no such scope."""

from chipbench import flops, flops_nemotron_h, xplane


def reduce(obs):
    cfg = obs.get("model_cfg")
    values = obs.get("values") or {}
    updates = values.get("ssd_state_updates_traced")
    tokens = values.get("ssd_chunk_tokens_traced")
    trace = xplane.run_trace(obs)
    share = xplane.device_share(trace, xplane.in_scope("ssd_state"))
    if not (share and updates and tokens is not None and obs.get("peaks")
            and getattr(cfg, "ssm_num_heads", 0)):
        return None
    took = share / 100.0 * (trace["t1"] - trace["t0"])
    ops, nbytes = flops_nemotron_h.ssd_state_call(cfg, updates, tokens, obs["itemsize"])
    least, _bound = flops.roofline_seconds(ops, nbytes, obs["peaks"])
    return 100.0 * least / took
