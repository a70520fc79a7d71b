"""``setup_trace_lower_s``: seconds the process spent tracing Python to jaxprs
and lowering them to MLIR modules (Mosaic kernels included), from
``deepspeed_tpu.utils.compile_cache.stats()``."""

from chipbench import xplane


def reduce(obs):
    return xplane.setup_seconds("trace", "lower")
