"""``attention_rows_roofline``: the least time the chip could take to read
every attended position's K and V once a layer, over the time it spent under
``swa_attn`` and ``shared_attn``.

Required (``flops_sambay.attention_rows``): 5,120 B a position a layer at the
published sizes in bf16, and the scores and read-outs of one query a slot.
The positions are the scheduler's own count while the trace ran
(``attn_rows_window_traced`` + ``attn_rows_shared_traced``, from the job: the
counters ``serving/attn_rows_window`` and ``serving/attn_rows_shared``, which
come from the host's lengths and spans and are the same whatever implements
the attention). The time holds the commits, the combination of the two maps
and the sub-norm too, so the share reads low, never high. None where there
is nothing to read."""

from chipbench import flops, flops_sambay, xplane


def reduce(obs):
    cfg, values = obs.get("model_cfg"), obs.get("values") or {}
    rows = (values.get("attn_rows_window_traced") or 0) + (values.get("attn_rows_shared_traced") or 0)
    trace = xplane.run_trace(obs)
    share = xplane.device_share(trace, xplane.in_scope("swa_attn", "shared_attn"))
    if not (share and rows and cfg is not None and obs.get("peaks")):
        return None
    took = share / 100.0 * (trace["t1"] - trace["t0"])
    ops, nbytes = flops_sambay.attention_rows(cfg, rows, obs["itemsize"])
    least, _bound = flops.roofline_seconds(ops, nbytes, obs["peaks"])
    return 100.0 * least / took
