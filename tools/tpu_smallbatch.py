"""Small-batch crossover probe: device vs CPU ed25519 verify at
64..2048 signatures, plus end-to-end VerifyCommit p50 at 150 validators
with the device engaged (CBFT_TPU_MIN_BATCH=1).

The routing threshold CBFT_TPU_MIN_BATCH (crypto/batch.py) was last
measured in round 3 (crossover ~1024 with the pre-rewrite kernel). The
round-4 limb-major kernel changed the cost model; this probe re-measures
the crossover so the default can be retuned from data (done = measured
TPU verify_commit p50 @150 below CPU's number and crossover <= 256
sigs, or the measured evidence that it isn't).

Prints progressive JSON lines naming the platform; the LAST line is the
complete result (the "crossover" key only appears there). This process
is the chip's only owner while it runs; bounded by the caller's timeout.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def make_batch(n: int, msg_len: int = 120):
    from cometbft_tpu.crypto import ed25519 as ed

    rng = np.random.default_rng(7)
    keys = [
        ed.gen_priv_key_from_secret(bytes([i & 0xFF, i >> 8]))
        for i in range(min(n, 128))
    ]
    pks, msgs, sigs = [], [], []
    for i in range(n):
        k = keys[i % len(keys)]
        m = rng.bytes(msg_len)
        pks.append(k.pub_key().bytes())
        msgs.append(m)
        sigs.append(k.sign(m))
    return pks, msgs, sigs


def main():
    import jax

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.tpu import aot, ed25519_batch

    aot.compile_cache_dir()

    out = {"platform": jax.devices()[0].platform}
    sizes = (64, 128, 256, 512, 1024, 2048)
    crossover = None
    for n in sizes:
        pks, msgs, sigs = make_batch(n)
        items = [
            (ed.PubKeyEd25519(pk), m, s) for pk, m, s in zip(pks, msgs, sigs)
        ]
        warm = ed.verify_many(items)  # warm CPU handles
        if not all(warm):
            raise AssertionError("CPU warmup batch must verify")
        # min-of-5 on BOTH sides: an asymmetric best-of vs single-run
        # would bias the crossover toward whichever side gets the reps
        cpu_ms = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            ed.verify_many(items)
            cpu_ms = min(cpu_ms, (time.perf_counter() - t0) * 1e3)

        compiled = ed25519_batch.verify_batch(pks, msgs, sigs)  # compile
        if not all(compiled):
            raise AssertionError("device warmup batch must verify")
        dev_ms = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            ed25519_batch.verify_batch(pks, msgs, sigs)
            dev_ms = min(dev_ms, (time.perf_counter() - t0) * 1e3)
        out[str(n)] = {
            "tpu_ms": round(dev_ms, 2),
            "cpu_ms": round(cpu_ms, 2),
            "tpu_sigs_per_sec": round(n / dev_ms * 1e3, 1),
        }
        if crossover is None and dev_ms < cpu_ms:
            crossover = n
        print(json.dumps(out), flush=True)
    out["crossover"] = crossover

    # end-to-end: VerifyCommit p50 @150 with the device forced on
    os.environ["CBFT_TPU_MIN_BATCH"] = "1"
    from cometbft_tpu.proto.gogo import Timestamp
    from cometbft_tpu.types import test_util

    vals, privs = test_util.deterministic_validator_set(150, 10)
    bid = test_util.make_block_id()
    commit = test_util.make_commit(
        bid, 5, 0, vals, privs, "bench-chain", now=Timestamp(1_700_000_000, 0)
    )
    vals.verify_commit("bench-chain", bid, 5, commit, backend="tpu")  # warm
    times = []
    for _ in range(9):
        t0 = time.perf_counter()
        vals.verify_commit("bench-chain", bid, 5, commit, backend="tpu")
        times.append(time.perf_counter() - t0)
    out["verify_commit_p50_ms_150_tpu_forced"] = round(
        sorted(times)[len(times) // 2] * 1e3, 2
    )
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
