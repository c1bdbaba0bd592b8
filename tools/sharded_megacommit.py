"""Measure the SURVEY §7 stage-10 sharded mega-commit: a 10k-signature
commit verified through _verify_core_compact jitted over an explicit
device mesh with the batch (lane) axis sharded.

Runs on the virtual 8-device CPU mesh (the bench variants stage runs the
same program via _sharded_mega_commit) and prints one JSON record naming
the platform. On 1 physical core the virtual mesh adds no parallelism —
the record's point is that the 8-way sharded program compiles, runs, and
verifies; per-device shard shapes are recorded. It is a CPU-platform
check, never a device number: real chips go through chip_smoke.py.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto.tpu import aot, ed25519_batch

aot.compile_cache_dir()

N = 10_000
PAD = 10_240  # 8 devices × 1280 lanes each

t_start = time.time()
keys = [ed.gen_priv_key_from_secret(bytes([i & 0xFF, i >> 8])) for i in range(128)]
pks, msgs, sigs = [], [], []
for i in range(N):
    k = keys[i % 128]
    m = b"megacommit vote %d" % i
    pks.append(k.pub_key().bytes())
    msgs.append(m)
    sigs.append(k.sign(m))
(*packed, valid) = ed25519_batch.prepare_batch_compact(pks, msgs, sigs)
assert valid.all()
t_prep = time.time() - t_start


def pad_to(a):
    out = np.zeros(a.shape[:-1] + (PAD,), a.dtype)
    out[..., :N] = a
    return out


devs = np.array(jax.devices())
mesh = Mesh(devs, ("batch",))
shardings = tuple(
    NamedSharding(mesh, PS(*([None] * (a.ndim - 1) + ["batch"])))
    for a in packed
)
step = jax.jit(
    ed25519_batch._verify_core_compact,
    in_shardings=shardings,
    out_shardings=NamedSharding(mesh, PS("batch")),
)
args = [
    jax.device_put(jnp.asarray(pad_to(a)), s) for a, s in zip(packed, shardings)
]
t0 = time.time()
mask = np.asarray(step(*args))
t_compile_and_first = time.time() - t0
assert mask[:N].all(), "sharded verification rejected valid signatures"
best = float("inf")
for _ in range(2):
    t0 = time.time()
    np.asarray(step(*args))
    best = min(best, time.time() - t0)

shard_shapes = {
    str(d): [
        tuple(s.data.shape)
        for s in args[0].addressable_shards
        if s.device == d
    ]
    for d in devs[:2]
}
out = {
    "n_signatures": N,
    "padded_batch": PAD,
    "n_devices": len(devs),
    "mesh": "Mesh(8, axis='batch')",
    "per_device_lane_shard": PAD // len(devs),
    "example_per_device_shard_shapes_wire": shard_shapes,
    "host_prepare_s": round(t_prep, 2),
    "compile_plus_first_run_s": round(t_compile_and_first, 2),
    "steady_state_s": round(best, 3),
    "sigs_per_sec": round(N / best, 1),
    "platform": jax.devices()[0].platform,
    "note": (
        "virtual 8-device CPU mesh on 1 physical core: wall time has no "
        "parallel speedup; the artifact demonstrates the 8-way sharded "
        "program (batch axis on lanes, limbs replicated) compiling and "
        "verifying a real 10k commit."
    ),
}
print(json.dumps(out))
