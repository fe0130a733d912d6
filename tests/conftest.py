"""Test config: run everything on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available where the tests run; sharding
correctness is validated on XLA's host platform with 8 forced devices, the
same harness the driver uses for the multichip dry-run. Both settings are
plain environment variables (the tier-1 command passes
``JAX_PLATFORMS=cpu`` itself); they are set here too so a bare ``pytest``
does the same thing.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# the tests place compile caches explicitly (tmp dirs); a variable set
# outside would win over them (fedml_tpu/utils/compile_cache.py)
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import pytest  # noqa: E402


@pytest.fixture
def restore_cache_config():
    """For tests that move jax's process-global compile-cache settings."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
