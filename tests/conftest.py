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


import json  # noqa: E402

import pytest  # noqa: E402


def blocksync_apply_toy() -> tuple:
    """(toy config, toy params) of the ``blocksync_apply`` generator, as
    ``tests/benchmark/test_traffic_shapes.py``'s ``TOY`` has the others.
    That table is part of the yardstick and is not edited by the PR that
    brings a generator, so it is completed from here (and, for the light
    fleet, from ``tests/benchmark/conftest.py``)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "traffic",
        "sync-apply-closed.json")
    with open(path) as fh:
        params = json.load(fh)["params"]
    return (
        {"chain_id": "toy-sync", "validators": 7, "replay_blocks": 24,
         "txs_per_block": 3, "tx_bytes": 64},
        dict(params, forged=dict(params["forged"], tail_lane=6),
             request_timeout_s=10),
    )


def blocksync_churn_toy() -> tuple:
    """(toy config, toy params) of the ``blocksync_churn`` generator: 7
    validators, 24 blocks of 3 transactions, a power change at every
    height, a seat replaced every 4th."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "traffic",
        "sync-churn-closed.json")
    with open(path) as fh:
        params = json.load(fh)["params"]
    return (
        {"chain_id": "toy-churn", "validators": 7, "replay_blocks": 24,
         "txs_per_block": 3, "tx_bytes": 64,
         "schedule": {"power_changes": 1, "power_range": [8, 12],
                      "seat_every": 4, "joiner_power": 10}},
        dict(params, forged=dict(params["forged"], tail_lane=6,
                                 seat_block=7),
             request_timeout_s=10),
    )


def mixed_commit_toy() -> tuple:
    """(toy config, toy params) of the ``mixed_commit`` generator: 12
    validators, 6 of them secp256k1, 2 commits in the pool."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "traffic",
        "commit-mixed-closed1.json")
    with open(path) as fh:
        params = json.load(fh)["params"]
    return (
        {"chain_id": "toy-mixed", "validators": 12,
         "secp256k1_validators": 6, "pool_commits": 2},
        dict(params, first_height=100),
    )


def sync_plane(backend):
    """What the ``blocksync_apply`` generator is handed, with no node
    behind it: ``backend`` stands where ``node.crypto_backend`` stands,
    spans and ticks do nothing, no fallback counter moves."""
    import contextlib
    import types

    return types.SimpleNamespace(
        backend=backend, node=types.SimpleNamespace(),
        span=lambda name: contextlib.nullcontext(), tick=lambda: None,
        fallbacks=lambda: 0.0, note=lambda msg: None,
    )


@pytest.fixture(autouse=True)
def _the_sync_generator_has_toy_sizes(request):
    toy = getattr(request.module, "TOY", None)
    if isinstance(toy, dict):
        toy.setdefault("blocksync_apply", blocksync_apply_toy())
        toy.setdefault("blocksync_churn", blocksync_churn_toy())
        toy.setdefault("mixed_commit", mixed_commit_toy())
    yield
