"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding code paths
compile and execute without TPU hardware. JAX_PLATFORMS=cpu set before jax
is imported is all it takes; the chip is reached only through chip_smoke.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from cometbft_tpu.crypto.tpu import aot  # noqa: E402

# Persistent compilation cache: the ed25519 kernel takes minutes to compile
# on CPU; cache it across pytest runs, where every other process keeps it.
aot.compile_cache_dir()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)


from cometbft_tpu.libs.net import free_ports  # noqa: E402,F401  (shared test helper)
