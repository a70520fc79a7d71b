"""``setup_backend_s``: seconds the process spent in the backend's
compile-or-load (with a warm cache: retrieval, deserialising, loading), from
``deepspeed_tpu.utils.compile_cache.stats()``."""

from chipbench import xplane


def reduce(obs):
    return xplane.setup_seconds("backend")
