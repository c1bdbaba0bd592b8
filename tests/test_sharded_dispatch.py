"""Multi-device sharded megabatch dispatch.

Contract under test (crypto/tpu/mesh.py dispatch_sharded/shard_plan,
crypto/tpu/topology.py quarantine+generation, crypto/scheduler.py
three-way routing, crypto/supervisor.py _verify_mesh, crypto/faults.py
run_chaos_sharded, crypto/tpu/aot.py sharded warm plan):

  - shard_bucket pads each device's shard to a pow2 bucket (floored at
    min_pad); warm boot uses the SAME arithmetic, so a warmed sharded
    ladder covers every shape dispatch_sharded can produce;
  - shard_plan slices the mesh over the HEALTHY fault domains in stable
    index order, cached per topology generation: quarantining a domain
    bumps the generation and the next dispatch re-slices over the
    survivors (no whole-plane trip);
  - dispatch_sharded honors the dispatch_batch contract: per-device
    chunk caps clamp the per-shard lane count, the thread's cancel
    event is checked at every chunk boundary, verdicts are ground-truth
    exact at non-pow2 n (shard-boundary coverage);
  - the scheduler routes each coalesced flush three ways (cpu / single /
    sharded) on the learned crossover with env > config > calibration
    precedence, and CBFT_MESH_ROUTE overrides;
  - a warmed (kernel, bucket, mesh) triple serves a sharded dispatch
    with ZERO new AOT registry misses;
  - the supervised sharded path verifies bit-identically to the CPU
    backend, attributes a mid-flow device kill to the offending fault
    domain, and keeps serving on the re-sliced mesh within the partial-
    degradation throughput bound (run_chaos_sharded).

Runs on the virtual 8-device CPU mesh the suite conftest forces via
XLA_FLAGS=--xla_force_host_platform_device_count — no hardware needed.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto.batch import BackendSpec, CPUBatchVerifier
from cometbft_tpu.crypto.faults import FaultPlan, install, run_chaos_sharded
from cometbft_tpu.crypto.scheduler import (
    DEFAULT_SHARD_MIN_BATCH,
    VerifyScheduler,
    shard_min_batch_default,
)
from cometbft_tpu.crypto.supervisor import BackendSupervisor
from cometbft_tpu.crypto.tpu import aot, mesh, topology


def _make_items(n, tag=b"", poison_at=()):
    items = []
    for i in range(n):
        k = ed.gen_priv_key_from_secret(tag + bytes([i & 0xFF, i >> 8]))
        msg = b"sharded-msg-" + tag + i.to_bytes(4, "big")
        sig = k.sign(msg)
        if i in poison_at:
            sig = b"\x00" * 64
        items.append((k.pub_key(), msg, sig))
    return items


def _cpu_mask(items):
    bv = CPUBatchVerifier()
    for pk, m, s in items:
        bv.add(pk, m, s)
    _, mask = bv.verify()
    return mask


_seq = [0]


def _faulty_sharded(n_domains, plan=None, **sup_kwargs):
    """A fresh FaultyBackend + supervisor over an n-domain virtual
    topology (unique backend name per call), tuned for sharded tests."""
    _seq[0] += 1
    name = f"test-sharded-{_seq[0]}"
    plan = install(name=name, inner="cpu",
                   plan=plan if plan is not None else FaultPlan(seed=_seq[0]))
    topo = topology.DeviceTopology.virtual(n_domains)
    sup_kwargs.setdefault("dispatch_timeout_ms", 2000)
    sup_kwargs.setdefault("breaker_threshold", 1)
    sup_kwargs.setdefault("audit_pct", 0)
    sup_kwargs.setdefault("hedge_pct", 0)
    sup_kwargs.setdefault("probe_base_ms", 60_000)
    sup_kwargs.setdefault("probe_max_ms", 120_000)
    sup = BackendSupervisor(spec=BackendSpec(name), topology=topo,
                            **sup_kwargs)
    return plan, sup, topo


@pytest.fixture(autouse=True)
def _restore_default_topology():
    """Sharded routing resolves the process-default topology (that is
    what a node installs at start); don't leak one into the suite."""
    before = topology.default_topology()
    yield
    topology.set_default_topology(before)


# a trivially-cheap elementwise kernel: exercises the full sharded
# dispatch/AOT machinery without the minutes-long curve-kernel compile
@jax.jit
def _mod3_kernel(x):
    return (x % 3).astype(jnp.int32) != 1


def _mod3_truth(xs):
    return (np.asarray(xs) % 3) != 1


class TestShardChunks:
    """mesh.shard_chunks is THE rounding rule of a sharded batch: the
    resident commit's rows (_build_resident), shard_bucket (and through
    it dispatch_batch and warm boot) and dispatch_sharded all round
    there. The cap bounds a launch's real lanes in total; a launch pads
    to a power of two, rounded up to a multiple of the shard count."""

    # (lanes, shards) -> (padded size of each launch) at cap 8,192
    CASES = {
        (150, 1): [256], (150, 4): [256], (150, 8): [256],
        (4097, 1): [8192], (4097, 4): [8192], (4097, 8): [8192],
        (10000, 1): [8192, 2048], (10000, 4): [8192, 2048],
        (10000, 8): [8192, 2048],
        (16385, 1): [8192, 8192, 64], (16385, 4): [8192, 8192, 64],
        (16385, 8): [8192, 8192, 64],
        # a mesh re-sliced to three chips: equal shards, not pow2 ones
        (150, 3): [258], (10000, 3): [8193, 2049],
    }

    @pytest.mark.parametrize("n,nsh", sorted(CASES))
    def test_padded_lanes_and_launches(self, n, nsh):
        chunks = mesh.shard_chunks(n, nsh, 8192, 64)
        assert [size for _, _, size in chunks] == self.CASES[(n, nsh)]
        # the launches tile [0, n) in order, each within the cap
        assert chunks[0][0] == 0 and chunks[-1][1] == n
        for (_, end, _), (start, _, _) in zip(chunks, chunks[1:]):
            assert end == start
        for start, end, size in chunks:
            assert 0 < end - start <= 8192
            assert size >= end - start and size % nsh == 0
            # minimal: the next smaller bucket would not hold the lanes
            assert size // 2 < max(end - start, 64) + nsh
        # one launch of the same lanes is what shard_bucket answers
        if len(chunks) == 1:
            assert mesh.shard_bucket(n, nsh, 64) == chunks[0][2]

    # lanes -> (real, padded) lanes of each launch of a keyed ed25519
    # flush on one chip: launches of 2,048 lanes under the 8,192 ceiling,
    # the short launch of a flush of more than one first and at least
    # half a launch
    STREAMED = {
        1111: [(1111, 2048)], 2048: [(2048, 2048)],
        2121: [(73, 1024), (2048, 2048)],
        4141: [(45, 1024), (2048, 2048), (2048, 2048)],
        6464: [(320, 1024)] + [(2048, 2048)] * 3,
        8192: [(2048, 2048)] * 4,
    }

    @staticmethod
    def _keyed_launches(monkeypatch, n, **kw):
        """The launches dispatch_batch hands the stream for ``n`` lanes."""
        handed = []

        def stream(kernel, launches, build, n, **_):
            handed.extend(launches)
            return np.zeros(n, bool), {"chunks": 0}

        monkeypatch.setattr(mesh, "launch_stream", stream)
        monkeypatch.delenv("CBFT_TPU_MAX_CHUNK", raising=False)
        mesh.configure_chunk_cap(None)
        topology.set_default_topology(topology.DeviceTopology.single())
        with mesh.route_scope(mesh.ROUTE_SINGLE):
            mesh.dispatch_batch(_mod3_kernel, [np.zeros(n)], n, 8192, 64, **kw)
        assert handed[0][0] == 0 and handed[-1][1] == n
        assert all(a[1] == b[0] for a, b in zip(handed, handed[1:]))
        return [(end - start, size) for start, end, size in handed]

    @pytest.mark.parametrize("n", sorted(STREAMED))
    def test_a_launch_size_below_the_cap_and_the_tail_floor(
            self, monkeypatch, n):
        from cometbft_tpu.crypto.tpu import ed25519_batch as eb

        assert eb._LAUNCH_LANES == 2048
        got = self._keyed_launches(monkeypatch, n, launch=eb._LAUNCH_LANES)
        assert got == self.STREAMED[n]
        # the entries that hand no launch size launch as they did
        assert self._keyed_launches(monkeypatch, n) == [
            (e - s, size) for s, e, size in mesh.shard_chunks(n, 1, 8192, 64)]

    @pytest.mark.parametrize("n,nsh,cap", [
        (150, 1, 8192), (6464, 1, 2048), (6464, 1, 8192), (10000, 4, 8192),
        (16385, 8, 8192), (10000, 3, 8192),
    ])
    def test_without_a_short_floor_the_rule_is_what_it_was(self, n, nsh, cap):
        """PR 26's rule, written out: every launch its own power of two."""
        want = []
        for start in range(0, n, cap):
            end = min(start + cap, n)
            size = 64
            while size < end - start:
                size *= 2
            want.append((start, end, -(-size // nsh) * nsh))
        assert mesh.shard_chunks(n, nsh, cap, 64) == want
        assert mesh.shard_chunks(n, nsh, cap, 64, short_floor=0) == want
        # with one: the same launches, the short one first and no smaller
        floored = mesh.shard_chunks(n, nsh, cap, 64, short_floor=cap // 2)
        assert len(floored) == len(want)
        assert sorted(e - s for s, e, _ in floored) == sorted(
            e - s for s, e, _ in want)
        assert sum(z for _, _, z in floored) >= sum(z for _, _, z in want)
        if len(want) == 1:
            assert floored == want
        else:
            assert floored[0][1] - floored[0][0] == want[-1][1] - want[-1][0]
            assert len({size for _, _, size in floored}) <= 2

    def test_a_blocksync_flush_reaches_no_shape_its_warm_up_has_not_built(
            self, monkeypatch):
        """The closed set: the deadline can close a flush of the cell's
        window after any k of its 64 blocks of 101 lanes; whatever k,
        its launches have sizes the cell's own warm-up bursts and full
        windows (benchmark/traffic/blocksync_window.warm, as it stands)
        have already run."""
        from benchmark.traffic import blocksync_window
        from cometbft_tpu.crypto.tpu import ed25519_batch as eb

        lanes, blocks, floor, cap = 101, 64, 1024, 8192
        buckets = blocksync_window.reachable_buckets(lanes, blocks, floor, cap)
        full = 1 << (lanes * blocks - 1).bit_length()
        bursts = [k for b, k in sorted(buckets.items()) if b != full]
        assert bursts == [11, 21] and buckets[full] == 41

        def sizes(k):
            return {size for _, size in self._keyed_launches(
                monkeypatch, k * lanes, launch=eb._LAUNCH_LANES)}

        warmed = set().union(*(sizes(k) for k in bursts + [blocks]))
        assert warmed == {1024, 2048}
        for k in range(-(-floor // lanes), blocks + 1):
            assert sizes(k) <= warmed, (k, sizes(k))

    def test_one_function_rounds_rows_bucket_and_warm_plan(self, monkeypatch):
        """Swap the rule for one no real rule could be and all three
        follow it: the resident rows' chunks, shard_bucket, and the
        sharded sizes warm boot compiles."""
        from cometbft_tpu.crypto.tpu import ed25519_batch as eb

        seen = []

        def odd_rule(n, n_shards, cap, min_pad):
            seen.append((n, n_shards, cap, min_pad))
            return [(s, min(s + 40, n), 48 * n_shards)
                    for s in range(0, n, 40)]

        monkeypatch.setattr(mesh, "shard_chunks", odd_rule)
        ndev = mesh.n_devices()
        assert mesh.shard_bucket(100, ndev, 64) == 48 * ndev
        sharded = {t.bucket for t in aot.warmup_plan(sizes=[64, 128])
                   if t.sharded}
        assert sharded == {48 * ndev}
        topology.set_default_topology(topology.DeviceTopology.detect())
        pks = [ed.gen_priv_key_from_secret(bytes([i, 9])).pub_key().bytes()
               for i in range(100)]
        rv = eb._build_resident(pks)
        assert [(s, e, z) for s, e, z, _ in rv.chunks] == [
            (0, 40, 48 * ndev), (40, 80, 48 * ndev), (80, 100, 48 * ndev)]
        assert rv.plan is not None and rv.plan.n_shards == ndev
        assert (100, ndev, mesh.chunk_cap(8192, 64), 64) in seen
        for _, _, _, a_dev in rv.chunks:
            assert len({s.device for s in a_dev.addressable_shards}) == ndev

    @pytest.mark.parametrize("route", ["indexed", "service"])
    def test_the_one_chip_entries_round_here_too(self, monkeypatch, route):
        """The indexed key store and verifyd's rows hand the stream the
        rule's launches with one shard, and round nothing themselves:
        the store under the chunk cap, the rows under the keyed route's
        launch size with its short-launch floor (PR 30)."""
        from cometbft_tpu.crypto import service as servicelib
        from cometbft_tpu.crypto.tpu import ed25519_batch as eb, keystore

        asked = []

        def odd_rule(n, n_shards, cap, min_pad, short_floor=0):
            asked.append((n, n_shards, cap, min_pad, short_floor))
            return [(s, min(s + 40, n), 48) for s in range(0, n, 40)]

        handed = {}

        def stream(kernel, launches, build, n, **kw):
            handed[kw["route"]] = [(s, e, z) for s, e, z, *_ in launches]
            return np.zeros(n, bool), {}

        monkeypatch.setattr(mesh, "shard_chunks", odd_rule)
        monkeypatch.setattr(mesh, "launch_stream", stream)
        monkeypatch.setattr(mesh, "n_devices", lambda: 1)
        topology.set_default_topology(topology.DeviceTopology.single())
        pks = [ed.gen_priv_key_from_secret(bytes([i, 7])).pub_key().bytes()
               for i in range(100)]
        eb._keystore.invalidate()
        try:
            if route == "indexed":
                eb._get_resident(b"odd rule", pks)
                assert keystore.verify_batch_indexed(
                    pks, [b"m"] * 100, [b"s" * 64] * 100) == [False] * 100
            else:
                assert not servicelib.dispatch_rows(
                    np.zeros((128, 100), np.uint8)).any()
        finally:
            eb._keystore.invalidate()
        assert handed == {route: [(0, 40, 48), (40, 80, 48), (80, 100, 48)]}
        assert asked[-1] == {
            "indexed": (100, 1, mesh.chunk_cap(8192, 64), 64, 0),
            "service": (100, 1, eb._LAUNCH_LANES, 64, eb._LAUNCH_LANES // 2),
        }[route]

    def test_warm_plan_and_dispatch_arithmetic_lockstep(self):
        # the zero-compiles-after-warm guarantee: every launch of every
        # batch size pads to a sharded total the ladder warms
        ndev = mesh.n_devices()
        assert ndev == 8  # conftest forces the 8-way virtual plane
        ladder = aot.bucket_ladder(floor=64)
        warmed = {mesh.shard_bucket(b, ndev, 64) for b in ladder}
        cap = max(ladder)
        for n in (64, 65, 150, 771, 4097, 10000, 16385, 40000):
            for _, _, size in mesh.shard_chunks(n, ndev, cap, 64):
                assert size in warmed, (n, size)


class TestShardPlan:
    def test_plan_caches_per_generation(self):
        topo = topology.DeviceTopology.virtual(8)
        p1 = mesh.shard_plan(topo)
        assert p1 is not None and p1.n_shards == 8
        assert mesh.shard_plan(topo) is p1  # same generation: cached

    def test_quarantine_bumps_generation_and_reslices(self):
        topo = topology.DeviceTopology.virtual(8)
        p1 = mesh.shard_plan(topo)
        gen = topo.generation()
        assert topo.set_quarantined(5)  # changed -> True
        assert not topo.set_quarantined(5)  # idempotent -> no change
        assert topo.generation() == gen + 1
        p2 = mesh.shard_plan(topo)
        assert p2 is not p1
        assert p2.n_shards == 7
        assert "dev5" not in p2.labels()
        topo.set_quarantined(5, False)
        assert mesh.shard_plan(topo).n_shards == 8

    def test_healthy_devices_stable_index_order(self):
        topo = topology.DeviceTopology.virtual(8)
        topo.set_quarantined(2)
        topo.set_quarantined(6)
        labels = [h.label for h in topo.healthy_devices()]
        assert labels == ["dev0", "dev1", "dev3", "dev4", "dev5", "dev7"]
        assert labels == [h.label for h in topo.healthy_devices()]

    def test_unavailable_below_two_healthy(self):
        topo = topology.DeviceTopology.virtual(8)
        for i in range(7):
            topo.set_quarantined(i)
        assert mesh.shard_plan(topo) is None
        assert not mesh.sharded_available(topo)
        topo.set_quarantined(0, False)
        assert mesh.sharded_available(topo)


class TestDispatchShardedParity:
    def test_non_pow2_parity_across_shard_boundaries(self):
        # 999 real lanes over 8 shards: 7 full pow2 shards + a ragged
        # tail shard; every boundary must land in the right lane
        topo = topology.DeviceTopology.virtual(8)
        xs = np.arange(999, dtype=np.int32)
        out = mesh.dispatch_sharded(
            _mod3_kernel, [xs], 999, max_chunk=8192, min_pad=64,
            topology=topo,
        )
        assert np.array_equal(out, _mod3_truth(xs))

    def test_multi_chunk_megabatch_parity(self):
        # cap the per-shard lanes so the megabatch spans several
        # sharded chunks (exercises the double-buffered retire loop)
        topo = topology.DeviceTopology.virtual(8)
        xs = np.arange(3000, dtype=np.int32)
        out = mesh.dispatch_sharded(
            _mod3_kernel, [xs], 3000, max_chunk=128, min_pad=64,
            topology=topo,
        )
        assert np.array_equal(out, _mod3_truth(xs))

    def test_one_domain_quarantined_reslice_parity(self):
        topo = topology.DeviceTopology.virtual(8)
        topo.set_quarantined(3)
        plan = mesh.shard_plan(topo)
        assert plan is not None and plan.n_shards == 7
        xs = np.arange(771, dtype=np.int32)
        out = mesh.dispatch_sharded(
            _mod3_kernel, [xs], 771, max_chunk=8192, min_pad=64,
            topology=topo,
        )
        assert np.array_equal(out, _mod3_truth(xs))

    def test_cancel_honored_mid_dispatch(self):
        # the cancel event trips DURING the flow (while packing chunk 1,
        # after chunk 0 already dispatched); the chunk-boundary check
        # before chunk 2 must abandon the rest of the megabatch
        topo = topology.DeviceTopology.virtual(8)
        ev = threading.Event()
        xs = np.arange(1500, dtype=np.int32)
        packs = []

        def packed(start, end):
            packs.append((start, end))
            if start > 0:
                ev.set()
            return [xs[start:end]]

        with mesh.cancel_scope(ev):
            with pytest.raises(mesh.DispatchCancelled):
                mesh.dispatch_sharded(
                    _mod3_kernel, packed, 1500, max_chunk=64, min_pad=64,
                    topology=topo,
                )
        # chunks 0 and 1 of the rounding rule's packed, the cancel fired
        # before chunk 2 was ever packed
        want = mesh.shard_chunks(1500, 8, 64, 64)
        assert len(want) > 2
        assert packs == [(s, e) for s, e, _ in want[:2]]


class TestWarmBootZeroMiss:
    def test_sharded_dispatch_after_warm_has_zero_registry_misses(self):
        name = "test-sharded-zero-miss"
        aot.register_kernel(
            name, _mod3_kernel,
            bucket_shapes=lambda b: [((b,), np.int32)],
        )
        topo = topology.DeviceTopology.virtual(8)
        plan = mesh.shard_plan(topo)
        assert plan is not None and plan.n_shards == 8
        reg = aot.default_registry()
        # the warm-boot ladder stage for this kernel at bucket 512
        targets = [t for t in aot.warmup_plan(sizes=[512])
                   if t.name == name]
        assert any(t.sharded for t in targets)
        for t in targets:
            reg.warm(t.kernel, t.shapes, donate_from=t.donate_from,
                     sharded=t.sharded)
        misses_before = reg.stats()["misses"]
        # 500 real lanes -> pow2 per-shard bucket 64 -> global 512:
        # exactly the warmed executable; the dispatch must not compile
        xs = np.arange(500, dtype=np.int32)
        out = mesh.dispatch_sharded(
            name and _mod3_kernel, [xs], 500, max_chunk=512, min_pad=64,
            topology=topo,
        )
        assert np.array_equal(out, _mod3_truth(xs))
        assert reg.stats()["misses"] == misses_before, (
            "post-warm sharded dispatch took an AOT registry miss"
        )


class TestThreeWayRouting:
    def test_shard_min_batch_precedence(self, monkeypatch):
        # env > config > calibration > built-in default
        monkeypatch.setenv("CBFT_SHARD_MIN_BATCH", "777")
        assert shard_min_batch_default(5000) == 777
        monkeypatch.delenv("CBFT_SHARD_MIN_BATCH")
        assert shard_min_batch_default(1234) == 1234
        from cometbft_tpu.crypto.tpu import calibrate
        monkeypatch.setattr(calibrate, "shard_min_batch", lambda: 2222)
        assert shard_min_batch_default(0) == 2222
        monkeypatch.setattr(calibrate, "shard_min_batch", lambda: None)
        assert shard_min_batch_default(0) == DEFAULT_SHARD_MIN_BATCH
        assert shard_min_batch_default(None) == DEFAULT_SHARD_MIN_BATCH

    def test_route_override_env(self, monkeypatch):
        monkeypatch.delenv("CBFT_MESH_ROUTE", raising=False)
        assert mesh.route_override() is None
        monkeypatch.setenv("CBFT_MESH_ROUTE", "single")
        assert mesh.route_override() == mesh.ROUTE_SINGLE
        monkeypatch.setenv("CBFT_MESH_ROUTE", "sharded")
        assert mesh.route_override() == mesh.ROUTE_SHARDED
        monkeypatch.setenv("CBFT_MESH_ROUTE", "auto")
        assert mesh.route_override() is None
        monkeypatch.setenv("CBFT_MESH_ROUTE", "bogus")
        with pytest.raises(ValueError):
            mesh.route_override()

    def test_scheduler_routes_flush_three_ways(self, monkeypatch):
        monkeypatch.delenv("CBFT_MESH_ROUTE", raising=False)
        monkeypatch.delenv("CBFT_SHARD_MIN_BATCH", raising=False)
        _, sup, topo = _faulty_sharded(8)
        sched = VerifyScheduler(spec=BackendSpec(sup.spec.name),
                                supervisor=sup, shard_min_batch=100)
        try:
            assert sched.shard_min_batch == 100
            # below the crossover -> single-chip; at/above -> sharded
            assert sched._route_for(99) is None
            assert sched._route_for(100) == mesh.ROUTE_SHARDED
            # explicit override beats the size rule, both ways
            monkeypatch.setenv("CBFT_MESH_ROUTE", "single")
            assert sched._route_for(10_000) == mesh.ROUTE_SINGLE
            monkeypatch.setenv("CBFT_MESH_ROUTE", "sharded")
            assert sched._route_for(1) == mesh.ROUTE_SHARDED
            # malformed override: route on size, never raise
            monkeypatch.setenv("CBFT_MESH_ROUTE", "bogus")
            assert sched._route_for(10_000) == mesh.ROUTE_SHARDED
            monkeypatch.delenv("CBFT_MESH_ROUTE")
            # mesh gone (all but one domain quarantined) -> single
            for i in range(1, 8):
                topo.set_quarantined(i)
            assert sched._route_for(10_000) is None
        finally:
            sched.on_stop()
            sup.stop()

    def test_cpu_spec_never_routes_to_mesh(self):
        sched = VerifyScheduler(spec=BackendSpec("cpu"))
        try:
            assert sched._route_for(1_000_000) is None
            snap = sched.queue_snapshot()
            assert snap["routes"] == {
                "cpu": 0, "single": 0, "sharded": 0, "indexed": 0,
                "service": 0,
            }
        finally:
            sched.on_stop()

    def test_sharded_flush_counted_and_ground_truth(self, monkeypatch):
        monkeypatch.delenv("CBFT_MESH_ROUTE", raising=False)
        _, sup, topo = _faulty_sharded(8)
        sched = VerifyScheduler(spec=BackendSpec(sup.spec.name),
                                supervisor=sup, shard_min_batch=4)
        dispatched_before = sup.metrics.sharded_dispatches.value()
        try:
            items = _make_items(64, tag=b"route", poison_at=(7, 40))
            fut = sched.submit(items, subsystem="test", height=1)
            ok, mask = fut.result(timeout=60)
            assert mask == _cpu_mask(items)
            assert not ok
            assert sched.queue_snapshot()["routes"]["sharded"] == 1
            assert (sup.metrics.sharded_dispatches.value()
                    == dispatched_before + 1)
        finally:
            sched.on_stop()
            sup.stop()

    def test_route_falls_back_when_mesh_unavailable(self):
        # one healthy domain: a sharded request must still be served
        # (single-chip fallback), counted as a sharded_fallback
        _, sup, topo = _faulty_sharded(2)
        topo.set_quarantined(1)
        fallbacks_before = sup.metrics.sharded_fallbacks.value()
        try:
            items = _make_items(32, tag=b"fb", poison_at=(5,))
            mask = sup.verify_items(items, reason="test", route="sharded")
            assert mask == _cpu_mask(items)
            assert (sup.metrics.sharded_fallbacks.value()
                    == fallbacks_before + 1)
        finally:
            sup.stop()


class TestSupervisedShardedParity:
    def test_megabatch_ground_truth_with_invalids_attributed(self):
        # the real curve kernel over the full supervised sharded path:
        # non-pow2 n with invalid signatures planted mid-shard and at a
        # shard boundary; verdicts must match the CPU backend exactly
        topo = topology.DeviceTopology.virtual(8)
        topology.set_default_topology(topo)
        sup = BackendSupervisor(
            spec=BackendSpec("tpu"), topology=topo,
            dispatch_timeout_ms=600_000, hedge_pct=0, audit_pct=0,
            probe_base_ms=600_000,
        )
        dispatched_before = sup.metrics.sharded_dispatches.value()
        try:
            items = _make_items(771, tag=b"mega", poison_at=(3, 97, 500))
            mask = sup.verify_items(items, reason="test", route="sharded")
            truth = _cpu_mask(items)
            assert mask == truth
            assert [i for i, v in enumerate(mask) if not v] == [3, 97, 500]
            assert (sup.metrics.sharded_dispatches.value()
                    == dispatched_before + 1)
        finally:
            sup.stop()


class TestChaosSharded:
    def test_chaos_sharded_acceptance(self):
        # the full degradation story: kill one domain mid-sharded-flow,
        # failure attributed to it, plan re-sliced to N-1, verdicts
        # stay ground-truth, throughput >= 0.6 x (N-1)/N of full mesh,
        # canary re-admits and the plan re-slices back to N.
        # run_chaos_sharded asserts every invariant inline.
        summary = run_chaos_sharded(
            devices=8, kill=3, seed=7, inner="cpu", rounds=2,
        )
        assert summary["wrong_verdicts"] == 0
        assert summary["cpu_routed"] == 0
        assert set(summary["quarantines"]) == {"dev3"}
        assert summary["topology_mirrored_quarantine"]
        assert summary["resliced_shards"] == 7
        assert summary["restored_shards"] == 8
        assert summary["throughput_ok"]
