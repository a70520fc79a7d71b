"""``setup_cache_read_s``: seconds the process spent retrieving executables
from the persistent cache (part of ``setup_backend_s``), from
``deepspeed_tpu.utils.compile_cache.stats()``."""

from chipbench import xplane


def reduce(obs):
    return xplane.setup_seconds("cache_read")
