"""Warm the AOT executable registry's persistent caches from the CLI —
any platform, any topology (crypto/tpu/aot.py run_warm_boot: the same
code path node start uses, so what this warms is exactly what a node
loads). Prints per-bucket compile seconds and merges them into the
calibration table when one is configured.

Replaces the old tools/warm_cpu_cache.py, which duplicated node.py's
cache config against a hardcoded CPU-platform .jax_cache path and
warmed by RUNNING batches (paying dispatch) instead of compiling
explicitly.

Usage:
  python tools/warm_cache.py                        # full ladder, default cache
  python tools/warm_cache.py --buckets 64,128       # specific buckets
  python tools/warm_cache.py --platform cpu --devices 8
  python tools/warm_cache.py --cache ~/.cbft/jax_cache \
      --calibration ~/.cbft/data/tpu_calibration.json
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--platform", default=None,
        help="jax platform to warm for (cpu/tpu/...; default: ambient)",
    )
    ap.add_argument(
        "--devices", type=int, default=None,
        help="force an N-device virtual host platform "
             "(XLA_FLAGS --xla_force_host_platform_device_count)",
    )
    ap.add_argument(
        "--cache", default=None,
        help="persistent cache directory: exported as "
             "JAX_COMPILATION_CACHE_DIR for this run (default: that "
             "variable if set, else <checkout>/.jax_cache — "
             "aot.compile_cache_dir)",
    )
    ap.add_argument(
        "--buckets", default=None,
        help="comma-separated bucket sizes (default: the full pow2 "
             "ladder in warm-boot priority order)",
    )
    ap.add_argument(
        "--floor", type=int, default=None,
        help="commit-p50 routing floor steering ladder priority "
             "(default: the resolved ed25519 routing floor)",
    )
    ap.add_argument(
        "--calibration", default=None,
        help="calibration table path to merge per-bucket compile "
             "seconds into (default: CBFT_TPU_CALIBRATION, if set)",
    )
    ap.add_argument(
        "--sharded-only", action="store_true",
        help="skip single-device variants (mesh deployments)",
    )
    args = ap.parse_args()

    # env must be set before jax import — aot pulls jax in lazily
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    if args.devices:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    if args.cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.expanduser(
            args.cache
        )

    import jax

    from cometbft_tpu.crypto.tpu import aot, calibrate

    cache = aot.compile_cache_dir()
    if args.calibration:
        calibrate.set_table_path(args.calibration)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        calibrate.persistent_cache_min_compile_secs(),
    )

    sizes = (
        [int(b) for b in args.buckets.split(",")] if args.buckets else None
    )
    print(
        f"warming {jax.devices()[0].platform} x{len(jax.devices())} "
        f"(topology {aot.topology_fingerprint()}, backend "
        f"{aot.backend_fingerprint()}) -> {cache}",
        flush=True,
    )
    obs = aot.run_warm_boot(
        floor=args.floor,
        sizes=sizes,
        include_single=not args.sharded_only,
    )
    for ob in obs:
        variant = "sharded" if ob["sharded"] else "single"
        state = "cached" if ob["cached"] else f"{ob['compile_s']:.1f}s"
        print(
            f"  {ob['kernel']:<28} bucket {ob['bucket']:>6} "
            f"{variant:<8} {state}",
            flush=True,
        )
    total = sum(ob["compile_s"] for ob in obs)
    fresh = sum(1 for ob in obs if not ob["cached"])
    print(
        f"done: {len(obs)} executables, {fresh} fresh compiles, "
        f"{total:.1f}s compiling"
    )
    if args.calibration or calibrate.table_path():
        table = calibrate.merge_compile_times(obs, args.calibration)
        if table is not None:
            print(
                "merged compile seconds into "
                f"{args.calibration or calibrate.table_path()}: "
                + json.dumps(table.get("compile", {}))
            )
    stats = aot.default_registry().stats()
    print(f"registry: {json.dumps(stats)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
