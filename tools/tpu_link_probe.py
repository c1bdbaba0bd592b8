"""Characterize the host↔TPU link: per-transfer latency vs bandwidth.

device_put of u32 buffers from 4 KiB to 8 MiB (min-of-5 each) plus a
trivial kernel round-trip, to split the per-dispatch cost into
(fixed round-trip) + (bytes / bandwidth). This decides which lever
matters next: if the ~40 ms dispatch floor is fixed latency, bigger
single dispatches win (CBFT_TPU_MAX_CHUNK up); if it is bandwidth,
shrinking bytes/sig further (resident validator-set pubkeys) wins.

Prints progressive JSON lines naming the platform; the LAST line is the
complete result. This process is the chip's only owner while it runs;
bounded by the caller's timeout.

``--merge`` additionally persists the measured curve into the
calibration store (crypto/tpu/calibrate.py, table["link"]), seeding the
wire ledger's CostProfile cold-boot predictions (crypto/wire.py); the
merge notice goes to stderr so the last-stdout-line contract holds.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Characterize the TPU link: latency vs bandwidth."
    )
    ap.add_argument(
        "--merge", action="store_true",
        help="persist the measured curve into the calibration store "
             "(seeds crypto/wire.py CostProfile cold boots)",
    )
    ap.add_argument(
        "--calibration", default=None, metavar="PATH",
        help="calibration table path for --merge "
             "(default: CBFT_TPU_CALIBRATION / the store's default)",
    )
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    out = {"platform": dev.platform}

    @jax.jit
    def tiny(x):
        return x.sum()

    # round-trip floor: tiny input, tiny output
    x = jnp.zeros(8, jnp.uint32)
    np.asarray(tiny(x))  # compile
    rtt = float("inf")
    for _ in range(7):
        t0 = time.perf_counter()
        np.asarray(tiny(jnp.zeros(8, jnp.uint32)))
        rtt = min(rtt, time.perf_counter() - t0)
    out["kernel_roundtrip_ms"] = round(rtt * 1e3, 2)
    print(json.dumps(out), flush=True)

    for kib in (4, 64, 512, 2048, 8192):
        buf = np.zeros(kib * 256, np.uint32)  # kib KiB
        t_put = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.device_put(buf, dev))
            t_put = min(t_put, time.perf_counter() - t0)
        out[f"put_{kib}KiB_ms"] = round(t_put * 1e3, 2)
        print(json.dumps(out), flush=True)

    # effective bandwidth from the largest two sizes (latency cancels)
    t_a = out["put_2048KiB_ms"]
    t_b = out["put_8192KiB_ms"]
    if t_b > t_a:
        mbps = (8192 - 2048) / 1024 / ((t_b - t_a) / 1e3)
        out["effective_MBps"] = round(mbps, 1)
    out["fixed_latency_ms_est"] = round(
        max(0.0, t_a - (2048 / 1024) / max(out.get("effective_MBps", 1e9), 1e-9) * 1e3),
        2,
    )
    print(json.dumps(out), flush=True)

    if args.merge:
        from cometbft_tpu.crypto.tpu import calibrate

        table = calibrate.merge_link_profile(out, path=args.calibration)
        path = args.calibration or calibrate.table_path()
        if table is not None:
            print(f"link profile merged into {path}", file=sys.stderr)
        else:
            print(
                f"link profile NOT merged (no usable path: {path!r})",
                file=sys.stderr,
            )
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
