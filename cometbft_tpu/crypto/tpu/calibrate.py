"""Measured CPU↔device routing calibration.

By-construction routing thresholds lied twice in round 5: the Merkle
device path was gated at 128 leaves but LOSES to the host tree at every
size on the round-5 shared chip (81 ms device vs 18 ms CPU at 10k
leaves — BENCH_onchip_probe.json), and the ed25519 floor was a constant
tuned to one session of a link whose per-dispatch cost jittered 40–75 ms
between sessions. This module replaces both with numbers measured ON
THIS MACHINE: node warmup (node/node.py _warm_tpu_kernels) runs
`record()` after its warm boot, in the node's own process, which times
device vs CPU at several sizes and writes a crossover table; routing
then asks the table.

Failure posture: no table (fresh node, CPU-only CI) means
NO device claim has been proven, so `merkle_min_leaves()` returns None
(host tree — the measured-safe default) and `ed25519_min_batch()` falls
back to the conservative constant. Explicitly-set env knobs
(CBFT_TPU_MERKLE_MIN_LEAVES / CBFT_TPU_MIN_BATCH) keep operator
precedence over the table at the call sites.

This module imports no jax at module level — the table accessors run on
hot consensus paths and must never touch the device plane.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, Optional, Tuple

TABLE_VERSION = 1

_mtx = threading.Lock()
_configured_path: Optional[str] = None
# (path, mtime) -> table; one entry — the path rarely changes
_cache: Optional[Tuple[str, float, Optional[dict]]] = None


def set_table_path(path: Optional[str]) -> None:
    """Install the node's calibration table location (node start sets
    {root}/data/tpu_calibration.json). CBFT_TPU_CALIBRATION wins."""
    global _configured_path, _cache
    with _mtx:
        _configured_path = path
        _cache = None


def table_path() -> Optional[str]:
    return os.environ.get("CBFT_TPU_CALIBRATION") or _configured_path


def load_table() -> Optional[dict]:
    """The calibration table, or None when absent/unreadable/stale-
    versioned. Cached by (path, mtime) so hot routing checks cost one
    stat, and a re-recorded table is picked up without a restart."""
    global _cache
    path = table_path()
    if not path:
        return None
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return None
    with _mtx:
        if _cache is not None and _cache[0] == path and _cache[1] == mtime:
            return _cache[2]
    table: Optional[dict] = None
    try:
        with open(path, "r", encoding="utf-8") as f:
            loaded = json.load(f)
        if isinstance(loaded, dict) and loaded.get("version") == TABLE_VERSION:
            table = loaded
    except (OSError, ValueError):
        table = None
    with _mtx:
        _cache = (path, mtime, table)
    return table


def _floor(table: Optional[dict], key: str) -> Optional[int]:
    if not table:
        return None
    v = table.get(key)
    if isinstance(v, int) and not isinstance(v, bool) and v > 0:
        return v
    return None


def merkle_min_leaves() -> Optional[int]:
    """Measured leaf count above which the device tree beats the host
    tree, or None when the device never won (or nothing was measured) —
    callers must then keep the root on the host."""
    return _floor(load_table(), "merkle_min_leaves")


def ed25519_min_batch() -> Optional[int]:
    """Measured batch size above which the ed25519 device dispatch beats
    the CPU plane, or None when unmeasured."""
    return _floor(load_table(), "ed25519_min_batch")


def _crossover(points: Dict[int, Tuple[float, float]]) -> Optional[int]:
    """Smallest measured size from which the device wins at EVERY
    larger measured size too — a single lucky window in the middle of
    the sweep must not open routing below sizes where the device loses."""
    best: Optional[int] = None
    for size in sorted(points, reverse=True):
        device_ms, cpu_ms = points[size]
        if device_ms < cpu_ms:
            best = size
        else:
            break
    return best


def _sweep(sizes, measure) -> Dict[int, Tuple[float, float]]:
    """``measure(n)`` → (device_ms, other_ms), from the LARGEST size
    down, stopping after the first size at which the device loses:
    _crossover reads nothing below a loss, and every further point is a
    bucket the warm boot may not have compiled (~50-70 s apiece cold on
    a v5e)."""
    points: Dict[int, Tuple[float, float]] = {}
    for n in sorted(sizes, reverse=True):
        device_ms, other_ms = points[n] = measure(n)
        if device_ms >= other_ms:
            break
    return points


def _best_ms(fn, reps: int) -> float:
    fn()  # warm: compile / first-touch — never inside the timing
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def run_calibration(
    merkle_sizes=(1024, 4096, 10_000),
    ed_sizes=(256, 512, 1024, 2048),
    reps: int = 2,
) -> dict:
    """Time device vs CPU at each size and derive the crossovers. Runs
    in the node's own process after the warm boot (a chip has one
    owner); synthetic inputs — both planes' cost is shape-dependent
    only."""
    import numpy as np

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto import merkle as cpu_merkle
    from cometbft_tpu.crypto.tpu import ed25519_batch
    from cometbft_tpu.crypto.tpu import merkle as tpu_merkle

    table: dict = {"version": TABLE_VERSION, "measured_at": time.time()}

    rng = np.random.default_rng(7)

    def merkle_point(n):
        items = [rng.bytes(int(rng.integers(40, 90))) for _ in range(n)]
        return (
            _best_ms(
                lambda: tpu_merkle.hash_from_byte_slices(
                    items, force_device=True
                ),
                reps,
            ),
            _best_ms(lambda: cpu_merkle.hash_from_byte_slices(items), reps),
        )

    merkle_pts = _sweep(merkle_sizes, merkle_point)
    table["merkle"] = {
        str(n): {"device_ms": round(d, 2), "cpu_ms": round(c, 2)}
        for n, (d, c) in merkle_pts.items()
    }
    table["merkle_min_leaves"] = _crossover(merkle_pts)

    key = ed.gen_priv_key_from_secret(b"calibrate")
    pk = key.pub_key()
    msg = b"calibration message, vote-sized padding ........................"
    sig = key.sign(msg)

    def ed_point(n):
        pks, msgs, sigs = [pk.bytes()] * n, [msg] * n, [sig] * n
        items = [(pk, msg, sig)] * n
        return (
            _best_ms(
                lambda: ed25519_batch.verify_batch(pks, msgs, sigs), reps
            ),
            _best_ms(lambda: ed.verify_many(items), reps),
        )

    ed_pts = _sweep(ed_sizes, ed_point)
    table["ed25519"] = {
        str(n): {"device_ms": round(d, 2), "cpu_ms": round(c, 2)}
        for n, (d, c) in ed_pts.items()
    }
    table["ed25519_min_batch"] = _crossover(ed_pts)

    return table


def run_sharded_calibration(
    sizes=(1024, 2048, 4096, 8192),
    reps: int = 2,
) -> Optional[dict]:
    """Time the single-chip route vs the sharded-mesh route at each
    size on the LIVE topology and derive the per-topology crossover —
    the scheduler's third routing rung. → a ``sharded`` table section
    ({topology_fp: {points, shard_min_batch, n_shards}}), or None when
    no multi-device mesh is available (nothing measurable, so no
    sharded claim is recorded)."""
    from cometbft_tpu.crypto.tpu import aot, ed25519_batch, mesh

    plan = mesh.shard_plan()
    if plan is None:
        return None

    from cometbft_tpu.crypto import ed25519 as ed

    key = ed.gen_priv_key_from_secret(b"calibrate-sharded")
    pk = key.pub_key()
    msg = b"calibration message, vote-sized padding ........................"
    sig = key.sign(msg)
    pts: Dict[int, Tuple[float, float]] = {}
    for n in sizes:
        pks = [pk.bytes()] * n
        msgs = [msg] * n
        sigs = [sig] * n

        def single():
            with mesh.route_scope(mesh.ROUTE_SINGLE):
                ed25519_batch.verify_batch(pks, msgs, sigs)

        def sharded():
            with mesh.route_scope(mesh.ROUTE_SHARDED):
                ed25519_batch.verify_batch(pks, msgs, sigs)

        # crossover convention: "device" = the sharded mesh, "cpu" =
        # the single-chip baseline it must beat
        pts[n] = (_best_ms(sharded, reps), _best_ms(single, reps))
    fp = aot.topology_fingerprint()
    return {
        str(fp): {
            "n_shards": plan.n_shards,
            "points": {
                str(n): {"sharded_ms": round(s, 2), "single_ms": round(c, 2)}
                for n, (s, c) in pts.items()
            },
            "shard_min_batch": _crossover(pts),
        }
    }


def shard_min_batch(topology_fp: Optional[str] = None) -> Optional[int]:
    """Measured batch size above which the sharded mesh beats the
    single chip for ``topology_fp`` (the current topology's fingerprint
    when omitted), or None when unmeasured / the mesh never won —
    routing then keeps batches on the single-chip rung."""
    table = load_table()
    if not table or not isinstance(table.get("sharded"), dict):
        return None
    if topology_fp is None:
        from cometbft_tpu.crypto.tpu import aot

        topology_fp = aot.topology_fingerprint()
    section = table["sharded"].get(str(topology_fp))
    if not isinstance(section, dict):
        return None
    v = section.get("shard_min_batch")
    if isinstance(v, int) and not isinstance(v, bool) and v > 0:
        return v
    return None


def _nearest_scaled_ms(
    points: dict, key: str, bucket: int
) -> Optional[float]:
    """``key`` ms at the measured size nearest ``bucket`` (log space),
    scaled linearly by the size ratio — the cold-route seed the priced
    router consumes before any live observation exists."""
    best: Optional[Tuple[int, float]] = None
    for raw_n, row in points.items():
        try:
            n = int(raw_n)
            v = float(row[key])
        except (TypeError, KeyError, ValueError):
            continue
        if n <= 0 or v <= 0.0:
            continue
        if best is None or (
            abs(n.bit_length() - bucket.bit_length())
            < abs(best[0].bit_length() - bucket.bit_length())
        ):
            best = (n, v)
    if best is None:
        return None
    n, v = best
    return v * (bucket / n)


def route_cost_seed_ms(route: str, bucket: int) -> Optional[float]:
    """Predicted wall ms for ``bucket`` lanes on ``route`` from the
    persisted calibration sweep — the THIRD rung of the decision
    ledger's prediction ladder (self EWMA → wire CostProfile → this).
    Answers from the measured per-size points: ``cpu``/``single`` from
    the ed25519 sweep, ``sharded`` from the current topology's sharded
    sweep. The indexed sub-route has no calibration sweep (it only exists against a live
    resident key store), so it prices None until observed live."""
    table = load_table()
    if not table:
        return None
    try:
        bucket = max(1, int(bucket))
    except (TypeError, ValueError):
        return None
    if route in ("cpu", "single"):
        points = table.get("ed25519")
        if not isinstance(points, dict):
            return None
        key = "cpu_ms" if route == "cpu" else "device_ms"
        return _nearest_scaled_ms(points, key, bucket)
    if route == "sharded":
        sharded = table.get("sharded")
        if not isinstance(sharded, dict):
            return None
        try:
            from cometbft_tpu.crypto.tpu import aot

            fp = str(aot.topology_fingerprint())
        except Exception:  # noqa: BLE001 - no device plane, no seed
            return None
        section = sharded.get(fp)
        if not isinstance(section, dict):
            return None
        points = section.get("points")
        if not isinstance(points, dict):
            return None
        return _nearest_scaled_ms(points, "sharded_ms", bucket)
    return None


def save_table(table: dict, path: str) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    os.replace(tmp, path)  # atomic: readers never see a torn table


def record(path: Optional[str] = None, sharded_sizes=None, **kwargs) -> dict:
    """Measure and persist — the warm boot's second step. When a
    multi-device mesh is visible the sharded sweep runs too (its result
    lands under ``table["sharded"][topology_fp]``); pass
    ``sharded_sizes`` to tune it, or let the defaults apply."""
    path = path or table_path()
    table = run_calibration(**kwargs)
    try:
        sh_kwargs = {} if sharded_sizes is None else {"sizes": sharded_sizes}
        section = run_sharded_calibration(**sh_kwargs)
    except Exception:  # noqa: BLE001 - sharded sweep is additive, never fatal
        section = None
    if section:
        table["sharded"] = section
    if path:
        # a fresh calibration must not drop previously-merged compile
        # observations — they key by topology fingerprint, not by the
        # routing sweep this run just re-measured. Same for sharded
        # crossovers of OTHER topologies (this run only re-measured the
        # live one).
        old = load_table()
        if old and isinstance(old.get("compile"), dict):
            table["compile"] = old["compile"]
        if old and isinstance(old.get("sharded"), dict):
            merged = dict(old["sharded"])
            merged.update(table.get("sharded", {}))
            table["sharded"] = merged
        save_table(table, path)
    return table


# --- compile-time economics (crypto/tpu/aot.py warm boot) -------------------
# The warm boot observes the REAL per-(bucket, topology) compile cost of
# every executable it builds. Folding those observations in here makes
# two decisions measurement-driven instead of guessed: the warmup
# LADDER ORDER (cheap buckets first covers more of the ladder before
# traffic arrives — aot.bucket_ladder consults compile_seconds()) and
# the jax persistent-cache admission threshold
# (jax_persistent_cache_min_compile_time_secs — a cache that refuses to
# store this link's actual compiles warms nothing on the next boot).


def merge_compile_times(
    observations, path: Optional[str] = None
) -> Optional[dict]:
    """Fold warm-boot compile observations ({kernel, bucket, sharded,
    topology, compile_s, cached}) into the table under
    ``table["compile"][topology][bucket]`` = total fresh-compile seconds
    across that bucket's kernels/variants. Cached (0-cost) observations
    are skipped — they measure the cache, not the compiler. Creates a
    minimal table when none exists yet; None when there is no path."""
    path = path or table_path()
    if not path:
        return None
    table = load_table()
    if table is None:
        table = {"version": TABLE_VERSION, "measured_at": time.time()}
    compile_tbl = table.setdefault("compile", {})
    touched = False
    for ob in observations:
        if ob.get("cached") or not ob.get("compile_s"):
            continue
        topo = str(ob.get("topology", "?"))
        bucket = str(int(ob.get("bucket", 0)))
        per_topo = compile_tbl.setdefault(topo, {})
        per_topo[bucket] = round(
            float(per_topo.get(bucket, 0.0)) + float(ob["compile_s"]), 3
        )
        touched = True
    if touched:
        save_table(table, path)
    return table


def compile_seconds(topology_fp: Optional[str] = None) -> Dict[int, float]:
    """Measured total compile seconds per bucket for ``topology_fp``
    (the current topology's fingerprint when omitted); {} when nothing
    was ever merged — callers fall back to size order."""
    table = load_table()
    if not table or not isinstance(table.get("compile"), dict):
        return {}
    if topology_fp is None:
        from cometbft_tpu.crypto.tpu import aot

        topology_fp = aot.topology_fingerprint()
    per_topo = table["compile"].get(str(topology_fp))
    if not isinstance(per_topo, dict):
        return {}
    out: Dict[int, float] = {}
    for bucket, secs in per_topo.items():
        try:
            out[int(bucket)] = float(secs)
        except (TypeError, ValueError):
            continue
    return out



# --- device-memory footprints (crypto/tpu/memory.py) -------------------------
# The memory plane's per-(kernel, bucket) bytes/lane model starts from
# the static Straus-table seed; observed allocation peaks correct it.
# Persisting the corrected model here means a restarted node's
# pre-dispatch guard plans with what earlier runs actually measured
# instead of re-learning from the seed.


def merge_memory_footprints(
    footprints: Dict[str, Dict[int, float]], path: Optional[str] = None
) -> Optional[dict]:
    """Fold the memory plane's learned bytes/lane model
    ({kernel: {bucket: bytes_per_lane}}) into the table under
    ``table["memory"][kernel][bucket]``. Later merges overwrite — the
    plane's EWMA already folds history. Creates a minimal table when
    none exists yet; None when there is no path."""
    path = path or table_path()
    if not path or not footprints:
        return None
    table = load_table()
    if table is None:
        table = {"version": TABLE_VERSION, "measured_at": time.time()}
    mem_tbl = table.setdefault("memory", {})
    touched = False
    for kernel, buckets in footprints.items():
        per_kernel = mem_tbl.setdefault(str(kernel), {})
        for bucket, bpl in buckets.items():
            try:
                per_kernel[str(int(bucket))] = round(float(bpl), 1)
            except (TypeError, ValueError):
                continue
            touched = True
    if touched:
        save_table(table, path)
    return table


def load_memory_footprints() -> Dict[str, Dict[int, float]]:
    """The persisted bytes/lane model ({kernel: {bucket: bytes/lane}});
    {} when nothing was ever merged — the plane then runs from the
    static seed."""
    table = load_table()
    if not table or not isinstance(table.get("memory"), dict):
        return {}
    out: Dict[str, Dict[int, float]] = {}
    for kernel, buckets in table["memory"].items():
        if not isinstance(buckets, dict):
            continue
        per_kernel: Dict[int, float] = {}
        for bucket, bpl in buckets.items():
            try:
                per_kernel[int(bucket)] = float(bpl)
            except (TypeError, ValueError):
                continue
        if per_kernel:
            out[str(kernel)] = per_kernel
    return out


# --- link profile (tools/tpu_link_probe.py → crypto/wire.py) -----------------
# The probe's measured H2D latency/bandwidth curve, persisted so the
# wire ledger's CostProfile answers predict_ms() cold — before the
# first live dispatch lands — from what the link actually measured.


_LINK_NUMERIC_KEYS = (
    "kernel_roundtrip_ms",
    "effective_MBps",
    "fixed_latency_ms_est",
)


def merge_link_profile(
    probe: dict, path: Optional[str] = None
) -> Optional[dict]:
    """Fold a tpu_link_probe result document into the table under
    ``table["link"]``. Later merges overwrite — the probe is a fresh
    measurement, not an increment. Creates a minimal table when none
    exists yet; None when there is no path or nothing usable."""
    path = path or table_path()
    if not path or not isinstance(probe, dict):
        return None
    link: Dict[str, object] = {}
    for key, val in probe.items():
        if key in _LINK_NUMERIC_KEYS or (
            key.startswith("put_") and key.endswith("_ms")
        ):
            try:
                link[key] = round(float(val), 4)
            except (TypeError, ValueError):
                continue
        elif key == "platform":
            link[key] = str(val)
    if not any(k in link for k in _LINK_NUMERIC_KEYS):
        return None
    link["measured_at"] = time.time()
    table = load_table()
    if table is None:
        table = {"version": TABLE_VERSION, "measured_at": time.time()}
    table["link"] = link
    save_table(table, path)
    return table


def load_link_profile() -> dict:
    """The persisted link profile ({kernel_roundtrip_ms, effective_MBps,
    fixed_latency_ms_est, put_*_ms, platform, measured_at}); {} when no
    probe was ever merged — the wire ledger then has no cold seed."""
    table = load_table()
    link = table.get("link") if table else None
    return dict(link) if isinstance(link, dict) else {}


def persistent_cache_min_compile_secs(default: float = 5.0) -> float:
    """The jax_persistent_cache_min_compile_time_secs threshold this
    link has EARNED: strictly below the cheapest fresh compile ever
    observed (so every warm-boot executable is cache-admitted), floored
    at 0.1 s (never cache trivia), capped at ``default`` (the
    conservative unmeasured fallback)."""
    table = load_table()
    cheapest: Optional[float] = None
    if table and isinstance(table.get("compile"), dict):
        for per_topo in table["compile"].values():
            if not isinstance(per_topo, dict):
                continue
            for secs in per_topo.values():
                try:
                    s = float(secs)
                except (TypeError, ValueError):
                    continue
                if s > 0 and (cheapest is None or s < cheapest):
                    cheapest = s
    if cheapest is None:
        return default
    return min(default, max(0.1, 0.5 * cheapest))
