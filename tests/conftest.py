"""Test environment: the CPU platform with 8 virtual devices for the
sharding tests. The compile cache is the package's own rule
(lightgbm_tpu/__init__.py): JAX_COMPILATION_CACHE_DIR if set, else
<checkout>/.cache/jax.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
