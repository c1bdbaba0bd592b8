"""Micro-benchmark harnesses mirroring the reference's in-repo Go
benchmarks (values are machine-dependent; none are stored — the harness
IS the parity surface):

  ed25519        — crypto/ed25519/bench_test.go:11-26 Sign/Verify, plus
                   the 64-sig batch through the BatchVerifier boundary
  validator_set  — types/validator_set_test.go:167,1685 copy/update
  light          — light/client_benchmark_test.go:29-84 sequential vs
                   bisection verification
  mempool        — mempool/v0/bench_test.go:13-82 CheckTx + Reap
  wal            — consensus/wal_test.go write throughput
  scheduler      — VerifyScheduler coalescing contract (no Go analogue:
                   fewer dispatches than concurrent submitters, serial-
                   identical verdicts, deadline-bounded sub-floor flush)

Run: python bench_micro.py [section ...]   (default: all, one JSON line
per section). The headline TPU-vs-CPU bench stays in bench.py.
"""

from __future__ import annotations

import json
import sys
import time


def _rate(n: int, fn) -> float:
    t0 = time.perf_counter()
    fn()
    return round(n / (time.perf_counter() - t0), 1)


def bench_ed25519() -> dict:
    from bench import bench_cpu_batch  # the shared 64-sig boundary bench
    from cometbft_tpu.crypto import ed25519 as ed

    n = 400
    key = ed.gen_priv_key()
    msg = b"x" * 128
    sign_rate = _rate(n, lambda: [key.sign(msg) for _ in range(n)])
    sig = key.sign(msg)
    pub = key.pub_key()
    verify_rate = _rate(
        n, lambda: [pub.verify_signature(msg, sig) for _ in range(n)]
    )
    return {
        "sign_per_sec": sign_rate,
        "verify_per_sec": verify_rate,
        "batch64_verify_per_sec": round(bench_cpu_batch(n=n), 1),
    }


def bench_validator_set() -> dict:
    from cometbft_tpu.types.test_util import deterministic_validator_set

    vals, _ = deterministic_validator_set(100, 10)
    n = 200
    copy_rate = _rate(n, lambda: [vals.copy() for _ in range(n)])

    def updates():
        for i in range(n):
            v = vals.copy()
            v.increment_proposer_priority(1)

    return {
        "copy_100vals_per_sec": copy_rate,
        "increment_priority_per_sec": _rate(n, updates),
        "hash_100vals_ms": round(
            _ms(lambda: vals.hash()), 3
        ),
    }


def _ms(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return (time.perf_counter() - t0) * 1e3


def bench_light() -> dict:
    """Sequential vs bisection verification over a 64-block chain
    (light/client_benchmark_test.go:29-84 shape, in-memory provider).
    Reuses the test suite's chain fixture — the bench is the harness,
    not a second implementation of header signing."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "light_fixtures",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tests", "test_light.py"),
    )
    fx = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fx)

    from cometbft_tpu.libs.db import MemDB
    from cometbft_tpu.light import Client, TrustOptions
    from cometbft_tpu.light.provider import MockProvider
    from cometbft_tpu.light.store import DBStore

    blocks, _, _ = fx._light_chain(64, n_vals=10)
    out = {}
    for mode in ("sequential", "bisection"):
        opts = TrustOptions(
            period_ns=fx.WEEK_NS,
            height=1,
            hash=blocks[1].signed_header.header.hash(),
        )
        client = Client(
            fx.CHAIN_ID,
            opts,
            MockProvider(fx.CHAIN_ID, blocks),
            [],
            DBStore(MemDB()),
            sequential=(mode == "sequential"),
        )
        t0 = time.perf_counter()
        lb = client.verify_light_block_at_height(64, fx._ts(65))
        assert lb.height == 64
        out[f"{mode}_to_h64_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
    return out


def bench_mempool() -> dict:
    from cometbft_tpu.abci.client import LocalClient
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import MempoolConfig
    from cometbft_tpu.mempool.clist_mempool import CListMempool

    client = LocalClient(KVStoreApplication())
    client.start()
    try:
        mp = CListMempool(MempoolConfig(), client, height=0)
        n = 2000

        def checks():
            for i in range(n):
                mp.check_tx(b"k%d=v" % i)
            mp.flush_app_conn()

        check_rate = _rate(n, checks)
        reap_ms = _ms(lambda: mp.reap_max_bytes_max_gas(-1, -1))
        return {
            "check_tx_per_sec": check_rate,
            "reap_2000_ms": round(reap_ms, 2),
        }
    finally:
        client.stop()


def bench_wal() -> dict:
    import tempfile

    from cometbft_tpu.consensus.wal import WAL, EndHeightMessage

    n = 500
    with tempfile.TemporaryDirectory() as d:
        wal = WAL(d + "/wal")
        wal.start()
        t0 = time.perf_counter()
        for i in range(n):
            wal.write(EndHeightMessage(i + 1))
        wal.flush_and_sync()
        rate = n / (time.perf_counter() - t0)
        t0 = time.perf_counter()
        for i in range(100):
            wal.write_sync(EndHeightMessage(n + i + 1))
        sync_rate = 100 / (time.perf_counter() - t0)
        wal.stop()
    return {
        "writes_per_sec": round(rate, 1),
        "write_syncs_per_sec": round(sync_rate, 1),
    }


def bench_routing() -> dict:
    """Measurement-driven routing regressions, asserted on CPU-only CI:

    - verify_commit with a tpu BackendSpec whose floor admits the commit
      must route through the RESIDENT fixed-executable path (the p50
      path — crypto/tpu/ed25519_batch.py verify_valset_resident);
    - 10k merkle leaves must stay on the host tree when no calibration
      table proved the device wins (round 5: device loses 4.5× there);
    - a synthetic crossover table must flip both verdicts, proving
      routing reads the table rather than a constant.

    Keys are positive counts/values so the harness's ">0" invariant
    doubles as the assertion surface.
    """
    import os
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.pop("CBFT_TPU_MIN_BATCH", None)
    os.environ.pop("CBFT_TPU_MERKLE_MIN_LEAVES", None)
    from cometbft_tpu.crypto.tpu import aot

    aot.compile_cache_dir()

    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.tpu import calibrate, ed25519_batch
    from cometbft_tpu.crypto.tpu import merkle as tpu_merkle
    from cometbft_tpu.types import test_util

    out = {}
    try:
        with tempfile.TemporaryDirectory() as d:
            # no table: no device claim proven → merkle stays host and
            # the ed floor falls back to the conservative constant
            calibrate.set_table_path(os.path.join(d, "absent.json"))
            if tpu_merkle.device_wins(10_000):
                raise AssertionError("10k leaves routed to device w/o table")
            out["merkle_10k_on_host"] = 1
            out["ed25519_floor_default"] = cryptobatch.ed25519_routing_floor()

            # synthetic table: both crossover verdicts must be read back
            path = os.path.join(d, "cal.json")
            calibrate.save_table(
                {
                    "version": calibrate.TABLE_VERSION,
                    "merkle_min_leaves": 512,
                    "ed25519_min_batch": 256,
                },
                path,
            )
            calibrate.set_table_path(path)
            if not tpu_merkle.device_wins(10_000):
                raise AssertionError("calibrated merkle crossover ignored")
            if cryptobatch.ed25519_routing_floor() != 256:
                raise AssertionError("calibrated ed25519 floor ignored")
            out["merkle_crossover_respected"] = 1
            out["ed25519_floor_calibrated"] = (
                cryptobatch.ed25519_routing_floor()
            )
    finally:
        calibrate.set_table_path(None)

    # resident p50 routing: small valset, floor lowered via BackendSpec
    # (not env) — the exact plumbing node._setup threads per node
    chain_id = "bench-routing"
    vals, privs = test_util.deterministic_validator_set(4, 10)
    bid = test_util.make_block_id()
    commit = test_util.make_commit(bid, 5, 0, vals, privs, chain_id)
    calls = {"n": 0}
    real = ed25519_batch.verify_valset_resident

    def spy(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    ed25519_batch.verify_valset_resident = spy
    try:
        t0 = time.perf_counter()
        vals.verify_commit(
            chain_id, bid, 5, commit, backend=BackendSpec("tpu", min_batch=1)
        )
        ms = (time.perf_counter() - t0) * 1e3
    finally:
        ed25519_batch.verify_valset_resident = real
    if calls["n"] != 1:
        raise AssertionError(
            f"verify_commit made {calls['n']} resident calls, wanted 1"
        )
    out["resident_route_hits"] = calls["n"]
    out["verify_commit_resident_ms"] = round(ms, 2)
    return out


def bench_scheduler() -> dict:
    """The VerifyScheduler coalescing contract, asserted on CPU-only CI:

    - four threads each submitting a 64-sig request concurrently must
      produce STRICTLY FEWER backend dispatches than four, with
      per-request verdicts identical to running each request serially
      through CPUBatchVerifier (including a poisoned request whose bad
      signature must not leak into its neighbours' verdicts);
    - a lone sub-floor request must complete within 10× flush_us — the
      deadline flush, not the lane budget, is what releases it.

    Keys are positive counts/values so the harness's ">0" invariant
    doubles as the assertion surface.
    """
    import os
    import threading

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from bench import _make_batch
    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import BackendSpec, CPUBatchVerifier
    from cometbft_tpu.crypto.scheduler import VerifyScheduler

    dispatches = {"n": 0}

    class CountingVerifier(CPUBatchVerifier):
        def verify(self):
            dispatches["n"] += 1
            return super().verify()

    cryptobatch.register_backend("counting", CountingVerifier)

    n_callers, per_caller = 4, 64
    reqs = [
        [
            (ed.PubKeyEd25519(pk), m, s)
            for pk, m, s in zip(*_make_batch(per_caller))
        ]
        for _ in range(n_callers)
    ]
    # poison request 2: its verdicts must come back per-slice, leaving
    # the other callers' all-ok untouched
    pk, m, _ = reqs[2][5]
    reqs[2][5] = (pk, m, b"\x00" * 64)

    def serial_verdict(items):
        bv = CPUBatchVerifier()
        for k, msg, sig in items:
            bv.add(k, msg, sig)
        return bv.verify()

    serial = [serial_verdict(items) for items in reqs]

    sched = VerifyScheduler(spec=BackendSpec("counting"), flush_us=5000)
    sched.start()
    try:
        results = [None] * n_callers
        barrier = threading.Barrier(n_callers)

        def worker(i):
            barrier.wait()
            results[i] = sched.submit(reqs[i]).result(timeout=60)

        ts = [
            threading.Thread(target=worker, args=(i,))
            for i in range(n_callers)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if dispatches["n"] >= n_callers:
            raise AssertionError(
                f"{n_callers} concurrent submitters cost {dispatches['n']} "
                f"dispatches — no coalescing"
            )
        if results != serial:
            raise AssertionError("coalesced verdicts diverge from serial")
        if results[2][0] or not all(results[i][0] for i in (0, 1, 3)):
            raise AssertionError("poisoned request leaked into neighbours")
        out = {
            "coalesced_dispatches": dispatches["n"],
            "dispatch_savings": n_callers - dispatches["n"],
            "verdicts_match_serial": 1,
            "poison_isolated": 1,
        }

        # lone sub-floor request: only the deadline can release it
        t0 = time.perf_counter()
        ok, mask = sched.submit(reqs[0][:3]).result(timeout=60)
        dt = time.perf_counter() - t0
        if not (ok and len(mask) == 3):
            raise AssertionError("sub-floor request verdict wrong")
        bound_s = 10 * sched.flush_us / 1e6
        if dt > bound_s:
            raise AssertionError(
                f"sub-floor request took {dt * 1e3:.1f}ms > 10×flush_us "
                f"({bound_s * 1e3:.0f}ms)"
            )
        out["sub_floor_latency_ms"] = round(dt * 1e3, 2)
        out["deadline_bound_ms"] = round(bound_s * 1e3, 1)
    finally:
        sched.stop()
    return out


def bench_telemetry() -> dict:
    """Capacity-telemetry overhead (crypto/telemetry.py), asserted on
    CPU-only CI with the real ed25519 verify cost dominating:

    - an identical scheduler workload (8 requests × 64 real ed25519
      sigs through BackendSpec("cpu")) is timed with the TelemetryHub
      wired in and with telemetry=None, best-of-3 per mode, modes
      interleaved so machine noise hits both equally;
    - hub-on throughput must be within 1% of hub-off throughput — the
      telemetry layer's "hot path is appends and counter bumps"
      contract, measured rather than asserted from the docstring;
    - the hub must actually have metered the work: the snapshot's RED
      table shows every request under the "bench" subsystem.

    ``overhead_margin_pct`` is ``1.0 − overhead_pct`` so the harness's
    ">0" invariant IS the <1% assertion (and survives the common case
    where measured overhead is ≤0 inside noise).
    """
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from bench import _make_batch
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto import telemetry as telemetrylib
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.scheduler import VerifyScheduler

    n_reqs, per_req = 8, 64
    pks, msgs, sigs = _make_batch(per_req)
    items = [
        (ed.PubKeyEd25519(pk), m, s) for pk, m, s in zip(pks, msgs, sigs)
    ]
    reqs = [list(items) for _ in range(n_reqs)]

    def run_workload(hub) -> float:
        sched = VerifyScheduler(
            spec=BackendSpec("cpu"), flush_us=500, telemetry=hub
        )
        sched.start()
        try:
            # warm once outside the timed region (thread spin-up,
            # first-flush costs are identical per mode but noisy)
            sched.submit(reqs[0], subsystem="bench").result(timeout=60)
            t0 = time.perf_counter()
            futs = [
                sched.submit(r, subsystem="bench", height=i + 1)
                for i, r in enumerate(reqs)
            ]
            for f in futs:
                ok, mask = f.result(timeout=60)
                if not (ok and all(mask)):
                    raise AssertionError("telemetry bench verdict wrong")
            return time.perf_counter() - t0
        finally:
            sched.stop()

    hub = telemetrylib.TelemetryHub(
        metrics=telemetrylib.Metrics.nop(), slo_target_ms=100
    )
    off_s, on_s = [], []
    for _ in range(3):  # interleave so drift hits both modes equally
        off_s.append(run_workload(None))
        on_s.append(run_workload(hub))
    base, teled = min(off_s), min(on_s)

    snap = hub.snapshot()
    red = snap["subsystems"].get("bench", {})
    if red.get("requests", 0) < 3 * (n_reqs + 1):
        raise AssertionError(
            f"hub metered {red.get('requests', 0)} bench requests, "
            f"expected {3 * (n_reqs + 1)} — telemetry was not engaged"
        )

    overhead_pct = (teled - base) / base * 100.0
    if overhead_pct >= 1.0:
        raise AssertionError(
            f"telemetry overhead {overhead_pct:.2f}% >= 1% budget "
            f"(off={base * 1e3:.1f}ms on={teled * 1e3:.1f}ms)"
        )
    total_sigs = n_reqs * per_req
    return {
        "baseline_ms": round(base * 1e3, 2),
        "telemetry_ms": round(teled * 1e3, 2),
        "baseline_sigs_per_sec": round(total_sigs / base, 1),
        "telemetry_sigs_per_sec": round(total_sigs / teled, 1),
        "overhead_margin_pct": round(1.0 - overhead_pct, 3),
        "metered_requests": red.get("requests", 0),
    }


def bench_memory() -> dict:
    """Memory-plane overhead (crypto/tpu/memory.py), asserted on
    CPU-only CI with the real ed25519 verify cost dominating:

    - the bench_telemetry workload (8 requests × 64 real ed25519 sigs
      through BackendSpec("cpu")) is timed with a model-only
      MemoryPlane installed as the process default (poll_ms=0, so the
      scheduler's ride-along poll fires on EVERY dispatch — worst case)
      and with no plane installed, best-of-3 per mode, interleaved;
    - plane-on throughput must be within 1% of plane-off throughput —
      the "hot path is a clock compare" contract, measured;
    - the plane must actually have polled: its polls counter grew by at
      least one per plane-on arm (the scheduler coalesces submissions,
      so the dispatch count — not the request count — is the floor).

    ``overhead_margin_pct`` is ``1.0 − overhead_pct`` so the harness's
    ">0" invariant IS the <1% assertion.
    """
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from bench import _make_batch
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.scheduler import VerifyScheduler
    from cometbft_tpu.crypto.tpu import memory as memlib

    n_reqs, per_req = 8, 64
    pks, msgs, sigs = _make_batch(per_req)
    items = [
        (ed.PubKeyEd25519(pk), m, s) for pk, m, s in zip(pks, msgs, sigs)
    ]
    reqs = [list(items) for _ in range(n_reqs)]

    def run_workload() -> float:
        sched = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=500)
        sched.start()
        try:
            sched.submit(reqs[0], subsystem="bench").result(timeout=60)
            t0 = time.perf_counter()
            futs = [sched.submit(r, subsystem="bench") for r in reqs]
            for f in futs:
                ok, mask = f.result(timeout=60)
                if not (ok and all(mask)):
                    raise AssertionError("memory bench verdict wrong")
            return time.perf_counter() - t0
        finally:
            sched.stop()

    plane = memlib.MemoryPlane(poll_ms=0, stats=False)
    off_s, on_s = [], []
    prev = memlib.set_default_plane(None)
    try:
        for _ in range(3):  # interleave so drift hits both modes equally
            memlib.set_default_plane(None)
            off_s.append(run_workload())
            memlib.set_default_plane(plane)
            on_s.append(run_workload())
    finally:
        memlib.set_default_plane(prev)
    base, planed = min(off_s), min(on_s)

    polls = plane.metrics.polls.value()
    if polls < 3:
        raise AssertionError(
            f"plane polled {polls} times, expected >= 3 "
            "— the scheduler ride-along poll was not engaged"
        )

    overhead_pct = (planed - base) / base * 100.0
    if overhead_pct >= 1.0:
        raise AssertionError(
            f"memory-plane overhead {overhead_pct:.2f}% >= 1% budget "
            f"(off={base * 1e3:.1f}ms on={planed * 1e3:.1f}ms)"
        )
    total_sigs = n_reqs * per_req
    return {
        "baseline_ms": round(base * 1e3, 2),
        "memplane_ms": round(planed * 1e3, 2),
        "baseline_sigs_per_sec": round(total_sigs / base, 1),
        "memplane_sigs_per_sec": round(total_sigs / planed, 1),
        "overhead_margin_pct": round(1.0 - overhead_pct, 3),
        "plane_polls": int(polls),
    }


def bench_coldboot() -> dict:
    """AOT warm-boot smoke (crypto/tpu/aot.py), asserted on CPU-only CI
    with the virtual device mesh and the smallest bucket only:

    - run_warm_boot over bucket 64 must leave ≥1 executable resident in
      the process registry;
    - a real 64-sig dispatch AFTER the warm boot must be a registry HIT:
      zero new XLA compilations and zero registry misses (the ROADMAP
      item 2 acceptance contract, smoke-sized) — with verdicts correct.

    The full cold-vs-warm cache timing lives in bench.py's coldboot
    stage; this section fails fast when a registry key drifts away from
    what dispatch_batch actually asks for.
    """
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.tpu import aot, ed25519_batch
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod

    aot.compile_cache_dir()

    reg = aot.default_registry()
    # single-device variants are skipped: with the virtual mesh up,
    # dispatch_batch always takes the sharded path, and the smoke must
    # fit the tier-1 budget (every compile here is a CPU XLA compile)
    include_single = mesh_mod.n_devices() == 1
    t0 = time.perf_counter()
    obs = aot.run_warm_boot(sizes=[64], include_single=include_single)
    warm_ms = (time.perf_counter() - t0) * 1e3
    if not obs:
        raise AssertionError("warm boot planned no targets")

    misses_before = reg.metrics.registry_misses.value()
    compiles_before = reg.compile_count
    key = ed.gen_priv_key_from_secret(b"coldboot-smoke")
    pk, msg = key.pub_key().bytes(), b"warm boot smoke message ......."
    sig = key.sign(msg)
    t0 = time.perf_counter()
    mask = ed25519_batch.verify_batch([pk] * 64, [msg] * 64, [sig] * 64)
    first_ms = (time.perf_counter() - t0) * 1e3
    if not all(mask):
        raise AssertionError("post-warm-boot verdict wrong")
    if reg.compile_count != compiles_before:
        raise AssertionError(
            "dispatch at a warmed bucket paid "
            f"{reg.compile_count - compiles_before} fresh compiles"
        )
    if reg.metrics.registry_misses.value() != misses_before:
        raise AssertionError(
            "dispatch at a warmed bucket missed the executable registry"
        )
    return {
        "warm_targets": len(obs),
        "warm_boot_ms": round(warm_ms, 1),
        "first_verdict_ms": round(first_ms, 1),
        "zero_compile_dispatch": 1,
    }


def bench_wire() -> dict:
    """Wire-ledger overhead (crypto/wire.py), asserted on CPU-only CI
    with the real ed25519 verify cost dominating:

    - the bench_telemetry workload (8 requests × 64 real ed25519 sigs
      through BackendSpec("cpu")) is timed with a WireLedger installed
      as the process default and with no ledger installed, best-of-3
      per mode, interleaved so machine noise hits both equally;
    - ledger-on throughput must be within 1% of ledger-off throughput —
      on the CPU route only the scheduler's demux phase feeds the
      ledger, which is exactly the scheduler-side cost the acceptance
      bound covers (the mesh-side note_chunk rides inside dispatches
      that already cost tens of ms);
    - the ledger must actually have been engaged: every dispatch's
      verdict demux lands one note_demux, so demux_notes must grow by
      at least one per ledger-on arm.

    ``overhead_margin_pct`` is ``1.0 − overhead_pct`` so the harness's
    ">0" invariant IS the <1% assertion.
    """
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from bench import _make_batch
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto import wire as wirelib
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.scheduler import VerifyScheduler

    n_reqs, per_req = 8, 64
    pks, msgs, sigs = _make_batch(per_req)
    items = [
        (ed.PubKeyEd25519(pk), m, s) for pk, m, s in zip(pks, msgs, sigs)
    ]
    reqs = [list(items) for _ in range(n_reqs)]

    def run_workload() -> float:
        sched = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=500)
        sched.start()
        try:
            sched.submit(reqs[0], subsystem="bench").result(timeout=60)
            t0 = time.perf_counter()
            futs = [sched.submit(r, subsystem="bench") for r in reqs]
            for f in futs:
                ok, mask = f.result(timeout=60)
                if not (ok and all(mask)):
                    raise AssertionError("wire bench verdict wrong")
            return time.perf_counter() - t0
        finally:
            sched.stop()

    ledger = wirelib.WireLedger()
    off_s, on_s = [], []
    prev = wirelib.set_default_ledger(None)
    try:
        for _ in range(3):  # interleave so drift hits both modes equally
            wirelib.set_default_ledger(None)
            off_s.append(run_workload())
            wirelib.set_default_ledger(ledger)
            on_s.append(run_workload())
    finally:
        wirelib.set_default_ledger(prev)
    base, led = min(off_s), min(on_s)

    if ledger.demux_notes < 3:
        raise AssertionError(
            f"ledger saw {ledger.demux_notes} demux notes, expected "
            ">= 3 — the scheduler demux feeder was not engaged"
        )

    overhead_pct = (led - base) / base * 100.0
    if overhead_pct >= 1.0:
        raise AssertionError(
            f"wire-ledger overhead {overhead_pct:.2f}% >= 1% budget "
            f"(off={base * 1e3:.1f}ms on={led * 1e3:.1f}ms)"
        )
    total_sigs = n_reqs * per_req
    return {
        "baseline_ms": round(base * 1e3, 2),
        "wire_ms": round(led * 1e3, 2),
        "baseline_sigs_per_sec": round(total_sigs / base, 1),
        "wire_sigs_per_sec": round(total_sigs / led, 1),
        "overhead_margin_pct": round(1.0 - overhead_pct, 3),
        "demux_notes": int(ledger.demux_notes),
    }


def bench_decisions() -> dict:
    """Decision-ledger overhead (crypto/decisions.py), asserted on
    CPU-only CI with the real ed25519 verify cost dominating:

    - the bench_wire workload (8 requests × 64 real ed25519 sigs
      through BackendSpec("cpu")) is timed with a DecisionLedger
      installed as the process default and with no ledger installed,
      best-of-3 per mode, interleaved so machine noise hits both
      equally;
    - ledger-on throughput must be within 1% of ledger-off throughput —
      per flush the decision plane adds one RouteDecision open (inputs
      snapshot + candidate pricing), one thread-local push/pop, and one
      finish (EWMA folds + window deques) against a multi-ms dispatch;
    - the ledger must actually have been engaged: every coalesced flush
      lands exactly one decision record, so the ledger's route counts
      must grow by at least one flush per ledger-on arm.

    ``overhead_margin_pct`` is ``1.0 − overhead_pct`` so the harness's
    ">0" invariant IS the <1% assertion.
    """
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from bench import _make_batch
    from cometbft_tpu.crypto import decisions as declib
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.scheduler import VerifyScheduler

    n_reqs, per_req = 8, 64
    pks, msgs, sigs = _make_batch(per_req)
    items = [
        (ed.PubKeyEd25519(pk), m, s) for pk, m, s in zip(pks, msgs, sigs)
    ]
    reqs = [list(items) for _ in range(n_reqs)]

    def run_workload() -> float:
        sched = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=500)
        sched.start()
        try:
            sched.submit(reqs[0], subsystem="bench").result(timeout=60)
            t0 = time.perf_counter()
            futs = [sched.submit(r, subsystem="bench") for r in reqs]
            for f in futs:
                ok, mask = f.result(timeout=60)
                if not (ok and all(mask)):
                    raise AssertionError("decisions bench verdict wrong")
            return time.perf_counter() - t0
        finally:
            sched.stop()

    ledger = declib.DecisionLedger()
    off_s, on_s = [], []
    prev = declib.set_default_ledger(None)
    try:
        for _ in range(3):  # interleave so drift hits both modes equally
            declib.set_default_ledger(None)
            off_s.append(run_workload())
            declib.set_default_ledger(ledger)
            on_s.append(run_workload())
    finally:
        declib.set_default_ledger(prev)
    base, led = min(off_s), min(on_s)

    n_decisions = sum(ledger.counts().values())
    if n_decisions < 3:
        raise AssertionError(
            f"ledger recorded {n_decisions} decisions, expected >= 3 — "
            "the scheduler's decision feeder was not engaged"
        )

    overhead_pct = (led - base) / base * 100.0
    if overhead_pct >= 1.0:
        raise AssertionError(
            f"decision-ledger overhead {overhead_pct:.2f}% >= 1% budget "
            f"(off={base * 1e3:.1f}ms on={led * 1e3:.1f}ms)"
        )
    total_sigs = n_reqs * per_req
    return {
        "baseline_ms": round(base * 1e3, 2),
        "decisions_ms": round(led * 1e3, 2),
        "baseline_sigs_per_sec": round(total_sigs / base, 1),
        "decisions_sigs_per_sec": round(total_sigs / led, 1),
        "overhead_margin_pct": round(1.0 - overhead_pct, 3),
        "decision_records": int(n_decisions),
    }


def bench_challenges() -> dict:
    """The challenge call of a launch's pack (native.ed25519_challenges,
    PR 37) by lanes and threads: the probe that fixed
    native._CHALLENGE_GRAIN and ed25519_batch._NATIVE_CHALLENGE_MIN on
    the chip's host (``python3 bench_micro.py challenges`` there; the
    chip is not touched). Medians of 11 calls, ms: ``ms_<lanes>_t<threads>``
    for 1 to 13 threads (at most the usable cores), ``threads_<lanes>``
    the count the grain gives, and the hashlib loop (``py_ms_<lanes>``)
    beside the native call on one thread (``native_ms_<lanes>``) around
    the gate."""
    import os
    import random
    import statistics

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from cometbft_tpu import native
    from cometbft_tpu.crypto.tpu import ed25519_batch as eb

    if native.load_challenges() is None:
        raise RuntimeError("native challenges unavailable")
    rng = random.Random(37)

    def lanes(n):
        pk = np.frombuffer(rng.randbytes(32 * n), np.uint8).reshape(n, 32)
        r = np.frombuffer(rng.randbytes(32 * n), np.uint8).reshape(n, 32)
        msgs = [rng.randbytes(100 + i % 23) for i in range(n)]
        return pk, r, msgs, np.ones(n, bool)

    def ms(fn) -> float:
        fn()
        runs = []
        for _ in range(11):
            t0 = time.perf_counter()
            fn()
            runs.append(time.perf_counter() - t0)
        return round(statistics.median(runs) * 1e3, 4)

    out = {"grain": native._CHALLENGE_GRAIN}
    cores = min(13, len(os.sched_getaffinity(0)))
    for n in (1024, 2048, 8192):
        pk, r, msgs, valid = lanes(n)
        out[f"threads_{n}"] = native.challenge_threads(n)
        for t in range(1, cores + 1):
            out[f"ms_{n}_t{t}"] = ms(
                lambda: native.ed25519_challenges(pk, r, msgs, valid, t)
            )
    for n in (8, eb._NATIVE_CHALLENGE_MIN):
        pk, r, msgs, valid = lanes(n)
        sig = np.concatenate([r, np.zeros_like(r)], axis=1)
        out[f"py_ms_{n}"] = ms(
            lambda: eb._challenge_scalars_py(pk, sig, msgs, valid)
        )
        out[f"native_ms_{n}"] = ms(
            lambda: native.ed25519_challenges(pk, r, msgs, valid, 1)
        )
    return out


SECTIONS = {
    "challenges": bench_challenges,
    "coldboot": bench_coldboot,
    "decisions": bench_decisions,
    "ed25519": bench_ed25519,
    "validator_set": bench_validator_set,
    "light": bench_light,
    "memory": bench_memory,
    "mempool": bench_mempool,
    "routing": bench_routing,
    "scheduler": bench_scheduler,
    "telemetry": bench_telemetry,
    "wal": bench_wal,
    "wire": bench_wire,
}


def main(argv):
    names = argv or sorted(SECTIONS)
    for name in names:
        fn = SECTIONS.get(name)
        if fn is None:
            print(json.dumps({"section": name, "error": "unknown section"}))
            continue
        try:
            print(json.dumps({"section": name, **fn()}))
        except Exception as exc:  # noqa: BLE001
            print(json.dumps({"section": name, "error": str(exc)[:200]}))


if __name__ == "__main__":
    main(sys.argv[1:])
