"""Where JAX's persistent compilation cache lives.

One rule for every entry point (`chip_smoke.py`, `bench.py`,
`inference.Predictor`, `tune.cache`): when ``JAX_COMPILATION_CACHE_DIR``
is set the cache is there and nothing in this package names another
directory — JAX reads the variable itself, so the helper only makes sure
the directory exists.  When it is not set the cache is
``<checkout>/.jax_cache``, a path computed from this package's own
location: the directory is part of every cache key, so one derived from
the home directory, a temporary directory, a pid or the clock would
never hit on the next run.
"""

import logging
import os

__all__ = ["compile_cache_dir", "enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
_log = logging.getLogger(__name__)


def compile_cache_dir():
    """The cache directory this process uses once the cache is enabled."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache(cache_dir=None):
    """Turn the persistent cache on and return its directory.

    ``cache_dir`` is honoured only when ``JAX_COMPILATION_CACHE_DIR`` is
    not set; the variable, set from outside, always wins."""
    env = os.environ.get(_ENV)
    if env:
        if cache_dir and os.path.abspath(cache_dir) != os.path.abspath(env):
            _log.warning("%s=%s is set; ignoring cache_dir=%s",
                         _ENV, env, cache_dir)
        os.makedirs(env, exist_ok=True)
        return env
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = cache_dir or compile_cache_dir()
    os.makedirs(path, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # the cache latches its directory at the first compile; reset so
        # enabling works after earlier uncached compiles in this process
        compilation_cache.reset_cache()
    return path
