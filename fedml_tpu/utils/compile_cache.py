"""Persistent XLA compilation cache, placed from outside.

A ResNet-56 round program takes most of a minute to compile on the TPU, and a
chip call keeps nothing but its output directory -- so where the cache
lives decides whether that compile is paid once or on every run.

Precedence for the cache directory:

1. ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX reads the
   variable itself and this module makes NO ``jax_compilation_cache_dir``
   update at all (an explicit ``cache_dir`` argument that disagrees is
   ignored with a warning -- the caller outside the process decides).
2. an explicit ``cache_dir`` argument (``--compile_cache_dir``).
3. :data:`DEFAULT_DIR`: ``<checkout>/.jax_cache``, derived from this
   package's own location -- fixed, so two runs of one checkout share
   it; never ``~``, a temp dir, a pid or a timestamp.

In every case the two persistence thresholds are set in code (every
entry size qualifies; the compile-time gate is
:data:`DEFAULT_MIN_COMPILE_TIME_S` unless overridden).
"""

from __future__ import annotations

import logging
import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


#: Default persistence gate: programs compiling faster than this are not
#: written to the cache (they recompile cheaper than they deserialize on
#: TPU-scale hosts). The warm-restart path and tier-1 tests pass 0.0 so
#: real small programs round-trip the cache on a CPU host -- without the
#: override, nothing sub-1s ever persists and the warm-restart machinery
#: is untestable off-TPU.
DEFAULT_MIN_COMPILE_TIME_S = 1.0


def enable_compilation_cache(cache_dir: str | None = None,
                             min_compile_time_secs: float | None = None,
                             ) -> str:
    """Enable jax's persistent compilation cache and return the directory
    in use (see the module docstring for the precedence). Safe to call
    more than once.

    ``min_compile_time_secs`` overrides the persistence gate (default
    :data:`DEFAULT_MIN_COMPILE_TIME_S`); the env var
    ``FEDML_TPU_COMPILE_MIN_S`` overrides the default when no explicit
    argument is given (the knob tests and the warm-restart smoke use to
    persist sub-second CPU programs)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if min_compile_time_secs is None:
        min_compile_time_secs = float(
            os.environ.get("FEDML_TPU_COMPILE_MIN_S",
                           DEFAULT_MIN_COMPILE_TIME_S))
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        if cache_dir is not None and (os.path.abspath(cache_dir)
                                      != os.path.abspath(env_dir)):
            logging.warning(
                "compile cache: %s=%s is set and wins over the explicit "
                "directory %s", ENV_VAR, env_dir, cache_dir)
        used = env_dir
    else:
        used = cache_dir or DEFAULT_DIR
        os.makedirs(used, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", used)
    # cache every size of entry once it qualifies
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    # jax memoizes its cache-in-use decision at the FIRST compile: a
    # process that compiled anything before this call would silently
    # never read or write the cache (it broke the warm-restart gate under
    # the shared-process test tier). Reset the memo so (re)enabling takes
    # effect.
    compilation_cache.reset_cache()
    return used


__all__ = ["enable_compilation_cache", "DEFAULT_DIR", "ENV_VAR",
           "DEFAULT_MIN_COMPILE_TIME_S"]
