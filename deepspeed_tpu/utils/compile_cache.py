"""Where compiled programs are kept between runs.

One rule for every process entry point (``chip_smoke.py``, ``bench.py``,
``python -m deepspeed_tpu.serving``, the launcher's user script): if
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and nothing is set in
code; otherwise the cache is one fixed directory inside the checkout. The
path is part of the cache key, so it never comes from ``tempfile``, a pid
or a clock. Library constructors and the tests configure no cache.
"""

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def cache_dir():
    """The directory in use: the environment's, else ``<checkout>/.jax_cache``."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.environ.get(ENV) or os.path.join(root, ".jax_cache")


def configure():
    """Call before the first compile of a process. Returns the directory."""
    path = cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def export(env):
    """Hand the directory on to a child process through its environment."""
    env.setdefault(ENV, cache_dir())
    return env
