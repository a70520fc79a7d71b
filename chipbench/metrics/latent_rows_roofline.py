"""``latent_rows_roofline``: the least time the chip could take for the latent
attention layers' required work over the time it spent under ``mla_attn``, for
a stack in which only SOME layers are latent (``layer_types`` with
``kv_lora_rank``; ``mla_attention_roofline`` counts one latent call a MoE
layer call, which holds where every layer is both).

Required, per layer call (``flops_mla_moe.mla_attention_call``, which takes
the widths from the configuration): every live slot's latent rows read once
(1,152 B a row at Ling-3.0-flash's 512 + 64 in bf16) and 2 x heads x (576 +
512) operations a row, one query a slot. The calls: the forwards over the
whole slot block the scheduler ran while the trace ran
(``column_forwards_traced``, from the job: a sync's column and its substeps)
times the latent layers of ``layer_types``. A prefill chunk's wider queries
are left out of the required work and their time is under the scope, so the
share reads low, never high. The live context comes from the job's samples of
the pool (``live_kv_rows``). None where there is nothing to read."""

import statistics

from chipbench import flops, flops_mla_moe, xplane


def reduce(obs):
    cfg, rows = obs.get("model_cfg"), (obs.get("series") or {}).get("live_kv_rows")
    forwards = (obs.get("values") or {}).get("column_forwards_traced")
    trace = xplane.run_trace(obs)
    share = xplane.device_share(trace, xplane.in_scope("mla_attn"))
    layers = (sum(t == "full_attention" for t in getattr(cfg, "layer_types", ()))
              if getattr(cfg, "kv_lora_rank", 0) else 0)
    if not (share and rows and forwards and layers and obs.get("peaks")):
        return None
    took = share / 100.0 * (trace["t1"] - trace["t0"])
    ops, nbytes = flops_mla_moe.mla_attention_call(cfg, statistics.fmean(rows), obs["itemsize"])
    least, _bound = flops.roofline_seconds(ops, nbytes, obs["peaks"])
    return 100.0 * forwards * layers * least / took
