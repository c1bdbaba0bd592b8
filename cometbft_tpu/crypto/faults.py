"""Fault injection for the verification device plane.

``FaultyBackend`` wraps any BatchVerifier and injects the failure modes
a real TPU sidecar exhibits (all observed or hypothesized in rounds 3-5:
wedged links, flapping runtimes, miscompiled kernels):

* ``exception_rate``  — probability a dispatch raises FaultInjected;
* ``hang_rate`` / ``hang_s`` — probability a dispatch wedges (sleeps
  ``hang_s``; wakes early if the supervisor's watchdog abandons it via
  mesh.cancel_scope — the zombie-thread path);
* ``corrupt_rate``    — probability a dispatch returns silently WRONG
  verdicts (every mask entry flipped, no exception raised) — the
  silent-corruption class only the CPU audit can catch;
* ``die_after``       — dispatches after the Nth all raise (a backend
  that dies and stays dead until "repaired" by ``plan.clear()``);
* ``jitter_ms``       — uniform random extra latency per dispatch;
* ``oom_rate``        — probability a dispatch raises a
  RESOURCE_EXHAUSTED-shaped error (classified OOM by the supervisor's
  retry ladder, which halves the chunk cap instead of striking the
  breaker);
* ``oom_above_lanes`` — allocator model for the OOM fault
  (``CBFT_FAULT_OOM_ABOVE=<lanes>``): the injected OOM only fires while
  the dispatch device's EFFECTIVE chunk cap (reactive shrinks + the
  memory plane's pre-dispatch guard, topology.DeviceHandle.chunk_cap)
  exceeds the threshold — a cap at or below it "fits in HBM" and the
  dispatch runs clean. This is what lets the memory-guard rung prove a
  proactive shrink PREVENTS the OOM instead of reacting to it;
* ``transient_n``     — countdown: the next N dispatches raise an
  UNAVAILABLE-shaped error then the backend recovers (the flapping
  runtime the transient-retry rung absorbs);
* ``device``          — scope every fault above to ONE fault domain
  (``CBFT_FAULT_DEVICE=<idx>``): a dispatch whose thread-installed
  topology.device_scope names a different device bypasses injection
  entirely — the multi-device chaos rung kills device k of N and
  asserts the survivors keep serving.

State (dispatch counter, RNG) lives in the shared ``FaultPlan``, not the
verifier instance — new_batch_verifier constructs a fresh verifier per
dispatch, so per-instance state would reset every batch. Mutating a plan
(e.g. ``plan.clear()``) takes effect on the next dispatch, which is how
tests and the chaos soak model repair/recovery.

``run_chaos_soak`` drives a supervised scheduler through a random fault
schedule over N simulated blocks and asserts the node-path invariants:
no future is ever lost, no wrong verdict is ever released (sync audit
mode), and the breaker re-admits the backend once faults stop. The
`slow`-marked soak test and the standalone ``tools/chaos.py`` entry
point both call it.
"""

from __future__ import annotations

import os
import random
import socket
import threading
import time
from typing import List, Optional, Tuple

from cometbft_tpu.crypto import PubKey
from cometbft_tpu.crypto import batch as cryptobatch
from cometbft_tpu.crypto.batch import BatchVerifier


class FaultInjected(RuntimeError):
    """An injected dispatch failure (distinguishable from real bugs)."""


class TransientFault(FaultInjected):
    """Injected transient device error — message is UNAVAILABLE-shaped so
    supervisor.classify_device_error files it under the retry rung."""


class ResourceExhaustedFault(FaultInjected):
    """Injected device OOM — message is RESOURCE_EXHAUSTED-shaped so the
    supervisor's ladder shrinks the chunk cap instead of striking."""


class FaultPlan:
    """Shared, mutable schedule of injected faults. Thread-safe; one
    plan drives every FaultyBackend instance registered against it."""

    def __init__(
        self,
        exception_rate: float = 0.0,
        hang_rate: float = 0.0,
        hang_s: float = 3600.0,
        corrupt_rate: float = 0.0,
        die_after: Optional[int] = None,
        jitter_ms: float = 0.0,
        oom_rate: float = 0.0,
        oom_above_lanes: Optional[int] = None,
        transient_n: int = 0,
        seed: int = 0,
        device: Optional[int] = None,
    ):
        self.exception_rate = exception_rate
        self.hang_rate = hang_rate
        self.hang_s = hang_s
        self.corrupt_rate = corrupt_rate
        self.die_after = die_after
        self.jitter_ms = jitter_ms
        self.oom_rate = oom_rate
        # allocator model: an injected OOM fires only while the dispatch
        # device's effective chunk cap exceeds this many lanes (None =
        # every drawn OOM fires, the pre-guard behavior)
        self.oom_above_lanes = oom_above_lanes
        # countdown: the next N dispatches fail transiently, then the
        # backend recovers on its own (re-armable mid-run by assignment)
        self.transient_n = transient_n
        # fault-domain scope: None = every dispatch; an index = only
        # dispatches whose thread carries that topology.device_scope
        self.device = device
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self.dispatches = 0  # total dispatches seen (incl. faulted ones)
        # RESOURCE_EXHAUSTED faults that actually FIRED (drawn OOMs
        # suppressed by the oom_above_lanes allocator model don't count)
        # — the memory-guard rung asserts this stays flat under guard
        self.ooms_fired = 0
        # dispatches seen per fault-domain index (only for dispatches
        # carrying a device scope) — the multi-device rung reads this to
        # prove the survivors kept serving the device path
        self.per_device: dict = {}

    @classmethod
    def from_env(cls) -> "FaultPlan":
        """Env-driven plan so the chaos soak (and a faulty node) can be
        configured without code: CBFT_FAULT_EXC_RATE, CBFT_FAULT_HANG_RATE,
        CBFT_FAULT_HANG_S, CBFT_FAULT_CORRUPT_RATE, CBFT_FAULT_DIE_AFTER,
        CBFT_FAULT_JITTER_MS, CBFT_FAULT_OOM_RATE, CBFT_FAULT_OOM_ABOVE
        (allocator-model lane threshold), CBFT_FAULT_TRANSIENT_N,
        CBFT_FAULT_SEED, CBFT_FAULT_DEVICE (fault-domain scope)."""
        e = os.environ
        die = e.get("CBFT_FAULT_DIE_AFTER")
        dev = e.get("CBFT_FAULT_DEVICE")
        above = e.get("CBFT_FAULT_OOM_ABOVE")
        return cls(
            exception_rate=float(e.get("CBFT_FAULT_EXC_RATE", "0")),
            hang_rate=float(e.get("CBFT_FAULT_HANG_RATE", "0")),
            hang_s=float(e.get("CBFT_FAULT_HANG_S", "3600")),
            corrupt_rate=float(e.get("CBFT_FAULT_CORRUPT_RATE", "0")),
            die_after=int(die) if die is not None else None,
            jitter_ms=float(e.get("CBFT_FAULT_JITTER_MS", "0")),
            oom_rate=float(e.get("CBFT_FAULT_OOM_RATE", "0")),
            oom_above_lanes=int(above) if above is not None else None,
            transient_n=int(e.get("CBFT_FAULT_TRANSIENT_N", "0")),
            seed=int(e.get("CBFT_FAULT_SEED", "0")),
            device=int(dev) if dev is not None else None,
        )

    def clear(self) -> None:
        """Repair the backend: stop injecting everything (in place, so
        already-registered factories see it on their next dispatch)."""
        self.exception_rate = 0.0
        self.hang_rate = 0.0
        self.corrupt_rate = 0.0
        self.die_after = None
        self.jitter_ms = 0.0
        self.oom_rate = 0.0
        self.transient_n = 0

    def _count_bypass(self, device_idx: Optional[int]) -> int:
        """Count a dispatch that bypassed injection because its device
        scope is outside the plan's target domain."""
        with self._lock:
            self.dispatches += 1
            if device_idx is not None:
                self.per_device[device_idx] = (
                    self.per_device.get(device_idx, 0) + 1
                )
            return self.dispatches

    def _decide(
        self, device_idx: Optional[int] = None
    ) -> Tuple[int, bool, bool, bool, float, bool, bool]:
        """→ (dispatch_no, raise?, hang?, corrupt?, jitter_s, transient?,
        oom?) for one dispatch, under the lock so concurrent dispatches
        draw distinct RNG samples and the counters are exact."""
        with self._lock:
            self.dispatches += 1
            no = self.dispatches
            if device_idx is not None:
                self.per_device[device_idx] = (
                    self.per_device.get(device_idx, 0) + 1
                )
            dead = self.die_after is not None and no > self.die_after
            raise_ = dead or self._rng.random() < self.exception_rate
            hang = self._rng.random() < self.hang_rate
            corrupt = self._rng.random() < self.corrupt_rate
            jitter_s = (
                self._rng.random() * self.jitter_ms / 1e3
                if self.jitter_ms > 0 else 0.0
            )
            transient = False
            if self.transient_n > 0:
                self.transient_n -= 1
                transient = True
            oom = self._rng.random() < self.oom_rate
        return no, raise_, hang, corrupt, jitter_s, transient, oom


class FaultyBackend(BatchVerifier):
    """BatchVerifier wrapper applying a FaultPlan to every verify()."""

    def __init__(self, plan: FaultPlan, inner: BatchVerifier):
        self._plan = plan
        self._inner = inner
        self._n = 0

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        self._inner.add(pub_key, msg, sig)
        self._n += 1

    def count(self) -> int:
        return self._n

    def _sharded_target_label(self) -> Optional[str]:
        """When this dispatch is a sharded multi-device program whose
        current shard plan still contains the plan's target device,
        return the target's label: the injected failure then takes down
        the WHOLE program (one device's death is the program's death)
        and the error names the offender so the supervisor's sharded
        failure attribution can quarantine the right domain. None when
        not sharded, or once the target is quarantined out of the mesh
        (the re-sliced program no longer touches it)."""
        from cometbft_tpu.crypto.tpu import mesh

        if mesh.current_route() != mesh.ROUTE_SHARDED:
            return None
        try:
            plan_obj = mesh.shard_plan()
        except Exception:  # noqa: BLE001 - no mesh, no participation
            return None
        if plan_obj is None:
            return None
        for h in plan_obj.handles:
            if h.index == self._plan.device:
                return h.label
        return None

    def verify(self) -> Tuple[bool, List[bool]]:
        n, self._n = self._n, 0
        from cometbft_tpu.crypto.tpu import topology

        dev = topology.current_device()
        dev_idx = dev.index if dev is not None else None
        target = ""
        if self._plan.device is not None and dev_idx != self._plan.device:
            label = self._sharded_target_label()
            if label is None:
                # this dispatch targets a different fault domain than
                # the plan scopes to — it runs clean (that is the whole
                # point of device-targeted chaos: the survivors must not
                # feel it)
                self._plan._count_bypass(dev_idx)
                return self._inner.verify()
            target = f" on device {label}"
        no, raise_, hang, corrupt, jitter_s, transient, oom = (
            self._plan._decide(dev_idx)
        )
        if jitter_s:
            time.sleep(jitter_s)
        if hang:
            _interruptible_hang(self._plan.hang_s)
        if transient:
            self._inner.verify()  # drop the held items like a real death
            raise TransientFault(
                f"UNAVAILABLE: injected transient runtime flap "
                f"(dispatch #{no}, {n} items){target}"
            )
        if oom and self._plan.oom_above_lanes is not None:
            # allocator model: the OOM only fires while the device would
            # dispatch WIDER than the threshold — a chunk cap already
            # clamped (by the memory guard, or by earlier reactive
            # shrinks) at or below it fits in HBM and runs clean
            handle = dev
            if handle is None:
                handle = topology.default_topology().device(0)
            if handle.chunk_cap(8192, 1) <= self._plan.oom_above_lanes:
                oom = False
        if oom:
            with self._plan._lock:
                self._plan.ooms_fired += 1
            self._inner.verify()
            raise ResourceExhaustedFault(
                f"RESOURCE_EXHAUSTED: injected HBM allocation failure "
                f"(dispatch #{no}, {n} items){target}"
            )
        if raise_:
            self._inner.verify()  # drop the held items like a real death
            raise FaultInjected(
                f"injected dispatch failure (dispatch #{no}, "
                f"{n} items){target}"
            )
        ok, mask = self._inner.verify()
        if corrupt:
            mask = [not b for b in mask]  # silent wrong verdicts, no raise
            ok = all(mask)
        return ok, mask


def _interruptible_hang(seconds: float) -> None:
    """Simulate a wedged dispatch. If a supervisor watchdog has
    abandoned this thread (mesh.cancel_scope), wake early and die the
    way a cancelled chunk loop does — so tests don't strand sleeping
    threads for an hour."""
    from cometbft_tpu.crypto.tpu import mesh

    ev = mesh.current_cancel_event()
    if ev is None:
        time.sleep(seconds)
        return
    if ev.wait(seconds):
        raise mesh.DispatchCancelled("injected hang abandoned by watchdog")


def install(
    name: str = "faulty",
    inner: cryptobatch.Backend = "cpu",
    plan: Optional[FaultPlan] = None,
) -> FaultPlan:
    """Register a FaultyBackend factory under ``name`` wrapping the
    ``inner`` backend; returns the (shared, live-mutable) plan."""
    plan = plan if plan is not None else FaultPlan.from_env()
    cryptobatch.register_backend(
        name,
        lambda: FaultyBackend(plan, cryptobatch.new_batch_verifier(inner)),
    )
    return plan


# ---------------------------------------------------------------------------
# chaos soak: random fault schedule over simulated blocks
# ---------------------------------------------------------------------------


def run_chaos_soak(
    n_blocks: int = 50,
    batch: int = 48,
    seed: int = 1234,
    inner: cryptobatch.Backend = "cpu",
    dispatch_timeout_ms: int = 500,
    probe_base_ms: int = 20,
    n_submitters: int = 3,
    logger=None,
) -> dict:
    """Drive a supervised VerifyScheduler through ``n_blocks`` simulated
    blocks under a randomized fault schedule (regime re-rolled every few
    blocks among: none / exceptions / hangs / corruption / dead), with
    ``n_submitters`` concurrent threads submitting per block, then clear
    the faults and wait for breaker re-admission.

    Invariants checked here (the caller asserts on the summary):
      * every future completes — ``lost_futures`` == 0;
      * every released verdict equals the CPU ground truth —
        ``wrong_verdicts`` == 0 (sync-audit mode re-checks every device
        batch before release, so corruption cannot escape);
      * after faults stop, the breaker re-admits the backend —
        ``readmitted`` is True and the device saw post-recovery traffic.
    """
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import BackendSpec, CPUBatchVerifier
    from cometbft_tpu.crypto.scheduler import VerifyScheduler
    from cometbft_tpu.crypto.supervisor import HEALTHY, BackendSupervisor

    rng = random.Random(seed)
    name = f"chaos-{seed}-{n_blocks}"
    plan = install(name=name, inner=inner, plan=FaultPlan(seed=seed))
    sup = BackendSupervisor(
        spec=BackendSpec(name),
        dispatch_timeout_ms=dispatch_timeout_ms,
        breaker_threshold=2,
        audit_pct=100,
        audit_sync=True,  # the no-wrong-verdict-ever mode (see supervisor.py)
        probe_base_ms=probe_base_ms,
        probe_max_ms=probe_base_ms * 8,
        logger=logger,
    )
    sched = VerifyScheduler(
        spec=BackendSpec(name), flush_us=1000, supervisor=sup, logger=logger
    )
    sched.start()

    keys = [
        ed.gen_priv_key_from_secret(b"chaos-%d" % i) for i in range(32)
    ]
    regimes = ("none", "exceptions", "hangs", "corruption", "dead",
               "jitter", "oom", "transient")
    wrong = lost = 0
    regime_counts = {r: 0 for r in regimes}

    def make_block(h: int):
        items, truth = [], []
        for i in range(batch):
            k = keys[(h + i) % len(keys)]
            msg = b"chaos block %d sig %d" % (h, i)
            good = rng.random() > 0.1  # ~10% genuinely bad signatures
            sig = k.sign(msg) if good else b"\x11" * 64
            items.append((k.pub_key(), msg, sig))
            truth.append(good)
        return items, truth

    def apply_regime(r: str) -> None:
        plan.clear()
        if r == "exceptions":
            plan.exception_rate = 0.7
        elif r == "hangs":
            plan.hang_rate = 1.0
            plan.hang_s = 30.0
        elif r == "corruption":
            plan.corrupt_rate = 1.0
        elif r == "dead":
            plan.die_after = 0
        elif r == "jitter":
            plan.jitter_ms = 5.0
        elif r == "oom":
            plan.oom_rate = 0.5
        elif r == "transient":
            plan.transient_n = 3

    try:
        for h in range(n_blocks):
            if h % 4 == 0:
                regime = rng.choice(regimes)
                apply_regime(regime)
            regime_counts[regime] += 1
            items, truth = make_block(h)
            # split the block across concurrent submitters, like the
            # node's subsystems racing into one coalesced dispatch
            per = max(1, len(items) // n_submitters)
            slices = [
                (items[i : i + per], truth[i : i + per])
                for i in range(0, len(items), per)
            ]
            futs = [(sched.submit(s), t) for s, t in slices]
            sched.flush()
            for fut, t in futs:
                try:
                    _, mask = fut.result(
                        timeout=dispatch_timeout_ms / 1e3 + 30
                    )
                except Exception:  # noqa: BLE001 - a lost/failed future
                    lost += 1
                    continue
                if mask != t:
                    wrong += 1

        # recovery: faults off, breaker must re-admit via canary probes
        plan.clear()
        deadline = time.monotonic() + 30.0
        readmitted = False
        while time.monotonic() < deadline:
            if sup.state() == HEALTHY:
                readmitted = True
                break
            # traffic while broken is what triggers the lazy probe kick
            ok, _ = sched.submit(
                [(keys[0].pub_key(), b"recovery ping", keys[0].sign(b"recovery ping"))]
            ).result(timeout=30)
            assert ok
            time.sleep(probe_base_ms / 1e3)
        before = plan.dispatches
        post_items, post_truth = make_block(n_blocks + 1)
        _, post_mask = sched.submit(post_items).result(timeout=60)
        if post_mask != post_truth:
            wrong += 1
        device_resumed = plan.dispatches > before
    finally:
        sched.stop()
        sup.stop()

    # sanity: the ground-truth oracle itself agrees with serial verify
    bv = CPUBatchVerifier()
    for pk, m, s in post_items:
        bv.add(pk, m, s)
    _, oracle = bv.verify()
    assert oracle == post_truth

    def total(counter) -> float:
        # labeled counters accumulate in with_labels() children; the
        # parent's own value stays 0 — sum the whole series
        return sum(c.value() for c in counter._series())

    return {
        "blocks": n_blocks,
        "batch": batch,
        "regimes": regime_counts,
        "wrong_verdicts": wrong,
        "lost_futures": lost,
        "trips": total(sup.metrics.trips),
        "watchdog_kills": sup.metrics.watchdog_kills.value(),
        "audit_mismatches": sup.metrics.audit_mismatches.value(),
        "probes": total(sup.metrics.probes),
        "backend_dispatches": plan.dispatches,
        "readmitted": readmitted,
        "device_resumed_after_recovery": device_resumed,
        "final_state": sup.state(),
    }


# ---------------------------------------------------------------------------
# chaos smoke: deterministic walk of every degradation-ladder rung
# ---------------------------------------------------------------------------


def _metric_total(counter) -> float:
    """Sum a (possibly labeled) counter across its whole series."""
    return sum(c.value() for c in counter._series())


def run_chaos_smoke(
    seed: int = 7,
    inner: cryptobatch.Backend = "cpu",
    logger=None,
) -> dict:
    """Walk every rung of the degradation ladder exactly once, fast and
    deterministically (seeded faults, no sleep over 50 ms): transient
    retry, OOM chunk-shrink + hysteretic recovery, hedged verification,
    failed-batch triage with per-request attribution, and the breaker
    trip/probe/re-admit cycle. Ground-truth verdict equality is checked
    at every step. Returns a summary dict; callers (the tier-1 smoke
    test, tools/chaos.py) assert on it."""
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import BackendSpec, CPUBatchVerifier
    from cometbft_tpu.crypto.scheduler import VerifyScheduler
    from cometbft_tpu.crypto.supervisor import (
        BROKEN,
        HEALTHY,
        BackendSupervisor,
    )
    from cometbft_tpu.crypto.tpu import mesh

    name = f"chaos-smoke-{seed}"
    plan = install(name=name, inner=inner, plan=FaultPlan(seed=seed))
    sup = BackendSupervisor(
        spec=BackendSpec(name),
        dispatch_timeout_ms=2000,
        breaker_threshold=3,
        audit_pct=100,
        audit_sync=True,  # no wrong verdict may ever be released
        probe_base_ms=10,
        probe_max_ms=80,
        hedge_pct=200,
        retry_ms=5,
        chunk_recover_n=2,
        logger=logger,
    )
    sched = VerifyScheduler(
        spec=BackendSpec(name), flush_us=1000, supervisor=sup,
        logger=logger,
    )
    sched.start()

    keys = [
        ed.gen_priv_key_from_secret(b"chaos-smoke-%d" % i) for i in range(8)
    ]

    def make_items(count, tag, poison_at=None):
        items, truth = [], []
        for i in range(count):
            k = keys[i % len(keys)]
            msg = b"smoke %s %d" % (tag, i)
            good = i != poison_at
            items.append((k.pub_key(), msg,
                          k.sign(msg) if good else b"\x13" * 64))
            truth.append(good)
        return items, truth

    wrong = 0
    m = sup.metrics
    mesh.reset_chunk_shrink()
    try:
        # rung 1 — transient retry: one UNAVAILABLE flap is absorbed by
        # a single jittered retry; no breaker strike, no CPU fallback
        plan.transient_n = 1
        items, truth = make_items(16, b"transient")
        if sup.verify_items(items, reason="smoke-transient") != truth:
            wrong += 1
        retried = _metric_total(m.retries)
        state_after_transient = sup.state()

        # rung 2 — OOM shrink + hysteretic recovery: RESOURCE_EXHAUSTED
        # halves the chunk cap per retry down to the floor (then the CPU
        # ground truth serves the batch); clean dispatches after repair
        # recover the cap one doubling per chunk_recover_n
        plan.clear()
        plan.oom_rate = 1.0
        items, truth = make_items(16, b"oom")
        if sup.verify_items(items, reason="smoke-oom") != truth:
            wrong += 1
        shrinks = m.chunk_shrinks.value()
        shrink_levels_peak = mesh.chunk_shrink_levels()
        plan.clear()
        items, truth = make_items(16, b"recover")
        for _ in range(2 * sup.chunk_recover_n):
            if sup.verify_items(items, reason="smoke-recover") != truth:
                wrong += 1
        recoveries = m.chunk_recoveries.value()

        # rung 3 — hedged verification: prime the latency model so a
        # 40 ms injected stall overruns predicted p99 × hedge_pct and
        # races the CPU; either side may win, verdicts must agree
        items, truth = make_items(16, b"hedge")
        for _ in range(5):
            sup.latency_model.observe(len(items), 0.002)
        plan.hang_rate = 1.0
        plan.hang_s = 0.04  # 40 ms — inside the smoke's 50 ms sleep cap
        if sup.verify_items(items, reason="smoke-hedge") != truth:
            wrong += 1
        plan.clear()
        plan.hang_rate = 0.0
        hedge_fires = m.hedge_fires.value()
        hedge_wins = _metric_total(m.hedge_wins)

        # rung 4 — failed-batch triage: three coalesced requests, one
        # poisoned; the offender is localized and attributed to its
        # subsystem, the clean requests complete all_ok, no trip
        trips_before_triage = _metric_total(m.trips)
        good_a, truth_a = make_items(8, b"triage-a")
        bad_b, truth_b = make_items(8, b"triage-b", poison_at=3)
        good_c, truth_c = make_items(8, b"triage-c")
        futs = [
            sched.submit(good_a, subsystem="consensus", height=11),
            sched.submit(bad_b, subsystem="blocksync", height=12),
            sched.submit(good_c, subsystem="evidence", height=13),
        ]
        sched.flush()
        res = [f.result(timeout=30) for f in futs]
        for (ok, mask), truth in zip(res, (truth_a, truth_b, truth_c)):
            if mask != truth:
                wrong += 1
        triage_clean_futures_ok = res[0][0] and res[2][0] and not res[1][0]
        triage_runs = m.triage_runs.value()
        triage_passes = m.triage_passes.value()
        offender_by_subsystem = {
            c._labels["subsystem"]: c.value()
            for c in m.triage_offenders._series()
            if "subsystem" in c._labels
        }
        triage_tripped = _metric_total(m.trips) > trips_before_triage

        # rung 5 — breaker: persistent failures strike it open, repair +
        # canary probe re-admits
        plan.die_after = 0
        items, truth = make_items(16, b"dead")
        for _ in range(sup.breaker_threshold):
            if sup.verify_items(items, reason="smoke-dead") != truth:
                wrong += 1
        state_broken = sup.state()
        plan.clear()
        probe_ok = sup.probe_now()
        state_final = sup.state()
    finally:
        sched.stop()
        sup.stop()
        mesh.reset_chunk_shrink()

    # the oracle agrees with itself: pure sanity, mirrors the soak
    bv = CPUBatchVerifier()
    for pk, msg, sig in items:
        bv.add(pk, msg, sig)
    _, oracle = bv.verify()
    assert oracle == truth

    return {
        "wrong_verdicts": wrong,
        "retries": retried,
        "state_after_transient": state_after_transient,
        "chunk_shrinks": shrinks,
        "shrink_levels_peak": shrink_levels_peak,
        "chunk_recoveries": recoveries,
        "hedge_fires": hedge_fires,
        "hedge_wins": hedge_wins,
        "hedge_divergence": m.hedge_divergence.value(),
        "triage_runs": triage_runs,
        "triage_passes": triage_passes,
        "triage_offenders": offender_by_subsystem,
        "triage_clean_futures_ok": triage_clean_futures_ok,
        "triage_tripped_breaker": triage_tripped,
        "triage_divergence": m.triage_divergence.value(),
        "state_broken": state_broken,
        "probe_ok": probe_ok,
        "state_final": state_final,
        "expected": {
            "state_broken": BROKEN,
            "state_final": HEALTHY,
        },
        "backend_dispatches": plan.dispatches,
    }


# ---------------------------------------------------------------------------
# multi-device chaos: kill device k of N, survivors must keep serving
# ---------------------------------------------------------------------------


def run_chaos_multidevice(
    devices: int = 4,
    kill: int = 2,
    seed: int = 7,
    inner: cryptobatch.Backend = "cpu",
    logger=None,
) -> dict:
    """The partial-mesh degradation proof: on an N-fault-domain
    topology, inject hang → oom → corrupt into device ``kill`` ONLY
    (``FaultPlan.device``) and assert after every phase that

      * zero wrong verdicts are ever released (the faulted shard is
        served from the CPU ground truth / triage overturn);
      * the surviving devices keep serving the device path — no
        node-wide CPU fallback (``cpu_routed`` stays 0) and no global
        breaker trip (aggregate state is DEGRADED, never BROKEN);
      * exactly the killed device's breaker leaves HEALTHY (quarantine),
        and its own exponential-backoff canary re-admits it once the
        fault clears.

    Returns a summary dict; tools/chaos.py and the tier-1 smoke test
    assert on it. Deterministic: seeded faults, rate-1.0 regimes."""
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.supervisor import (
        BROKEN,
        DEGRADED,
        HEALTHY,
        BackendSupervisor,
    )
    from cometbft_tpu.crypto.tpu import topology

    if not 0 <= kill < devices:
        raise ValueError(f"kill index {kill} outside 0..{devices - 1}")
    topo = topology.DeviceTopology.virtual(devices)
    name = f"chaos-md-{seed}-{devices}-{kill}"
    plan = install(
        name=name, inner=inner, plan=FaultPlan(seed=seed, device=kill)
    )
    sup = BackendSupervisor(
        spec=BackendSpec(name),
        dispatch_timeout_ms=500,
        breaker_threshold=1,  # first strike quarantines — deterministic
        audit_pct=100,
        audit_sync=True,  # no wrong verdict may ever be released
        # async canary backoff pushed beyond the run: a background probe
        # racing the fault window would fail and re-trip AFTER the
        # explicit re-admission — re-admission here is driven solely by
        # the synchronous per-device probe_now(device=kill) canary
        probe_base_ms=60_000,
        probe_max_ms=120_000,
        hedge_pct=0,  # hedging off: phase outcomes must be attributable
        retry_ms=5,
        chunk_recover_n=1,
        logger=logger,
        topology=topo,
    )
    killed_label = topo.device(kill).label
    m = sup.metrics
    keys = [
        ed.gen_priv_key_from_secret(b"chaos-md-%d" % i) for i in range(8)
    ]
    batch = 64 * devices  # big enough that every healthy domain shards

    def make_items(tag: bytes):
        items, truth = [], []
        for i in range(batch):
            k = keys[i % len(keys)]
            msg = b"md %s %d" % (tag, i)
            items.append((k.pub_key(), msg, k.sign(msg)))
            truth.append(True)
        return items, truth

    def series(counter) -> dict:
        return {
            c._labels["device"]: c.value()
            for c in counter._series() if "device" in c._labels
        }

    wrong = 0
    phases = {}
    try:
        for phase, arm in (
            ("hang", lambda: setattr(plan, "hang_rate", 1.0)),
            ("oom", lambda: setattr(plan, "oom_rate", 1.0)),
            ("corrupt", lambda: setattr(plan, "corrupt_rate", 1.0)),
        ):
            plan.clear()
            arm()
            if phase == "hang":
                plan.hang_s = 30.0
            # 1) faulted batch: device `kill`'s shard fails its way down
            # the ladder and is served from the ground truth; the other
            # shards complete on the device path
            items, truth = make_items(phase.encode())
            if sup.verify_items(items, reason=f"md-{phase}") != truth:
                wrong += 1
            states = sup.device_states()
            quarantined_only_kill = (
                states.get(killed_label) == BROKEN
                and all(
                    s == HEALTHY for d, s in states.items()
                    if d != killed_label
                )
            )
            # 2) survivors keep serving while the fault is still armed:
            # the quarantined domain is excluded from the partition, so
            # the armed fault cannot even fire
            before = dict(plan.per_device)
            items, truth = make_items(phase.encode() + b"-survivors")
            if sup.verify_items(items, reason=f"md-{phase}-surv") != truth:
                wrong += 1
            survivors_grew = all(
                plan.per_device.get(i, 0) > before.get(i, 0)
                for i in range(devices) if i != kill
            )
            state_quarantined = sup.state()
            # 3) repair + per-device canary re-admission
            plan.clear()
            readmit_ok = sup.probe_now(device=kill)
            phases[phase] = {
                "quarantined_only_kill": quarantined_only_kill,
                "survivors_grew": survivors_grew,
                "state_while_quarantined": state_quarantined,
                "readmit_probe_ok": readmit_ok,
                "states_after_readmit": sup.device_states(),
            }
            if phase == "oom":
                # the OOM phase rode the shrink ladder to the floor;
                # model the operator repair (HBM pressure gone) so the
                # corrupt phase shards at full capacity again
                topo.device(kill).reset_chunk_shrink()
    finally:
        final_states = sup.device_states()
        sup.stop()

    quarantine_series = series(m.quarantines)
    summary = {
        "devices": devices,
        "kill": kill,
        "wrong_verdicts": wrong,
        "cpu_routed": m.cpu_routed.value(),
        "quarantines": quarantine_series,
        "readmissions": series(m.readmissions),
        "redistributions": m.redistributions.value(),
        "phases": phases,
        "final_states": final_states,
        "backend_dispatches": plan.dispatches,
        "per_device_dispatches": dict(plan.per_device),
        "expected": {
            "state_while_quarantined": DEGRADED,
            "final_state": HEALTHY,
        },
    }
    # the safety invariants hold unconditionally — assert here so every
    # caller (CLI, tests, bench) gets them for free
    assert wrong == 0, f"wrong verdicts released: {wrong}"
    assert m.cpu_routed.value() == 0, "node-wide CPU fallback engaged"
    assert set(quarantine_series) == {killed_label}, (
        f"devices quarantined: {sorted(quarantine_series)} "
        f"(expected only {killed_label})"
    )
    assert all(s == HEALTHY for s in final_states.values()), final_states
    return summary


# ---------------------------------------------------------------------------
# memory-guard chaos: the proactive shrink must PREVENT the OOM
# ---------------------------------------------------------------------------


def run_chaos_memory_guard(
    seed: int = 11,
    inner: cryptobatch.Backend = "cpu",
    lanes_threshold: int = 256,
    rounds: int = 5,
    logger=None,
) -> dict:
    """The proactive-vs-reactive proof for the memory plane's
    pre-dispatch guard (crypto/tpu/memory.py refresh_guard).

    An allocator-modeled OOM fault (``oom_rate=1.0`` gated by
    ``oom_above_lanes``) fires whenever the device would dispatch wider
    than ``lanes_threshold`` lanes. Two phases over the same fault:

    * **reactive control** (no guard): every dispatch OOMs until the
      supervisor's retry ladder has halved the chunk cap under the
      threshold — each halving cost a real RESOURCE_EXHAUSTED
      (``plan.ooms_fired`` > 0, supervisor ``chunk_shrinks`` > 0);
    * **proactive guard**: a model-only MemoryPlane whose modeled HBM
      limit only fits ``lanes_threshold`` lanes clamps the cap BEFORE
      dispatch — the armed fault never fires (``ooms_fired`` flat,
      ``chunk_shrinks`` flat, zero RESOURCE_EXHAUSTED reaches the
      supervisor) and every verdict still matches the ground truth.

    Deterministic (rate-1.0 fault, seeded keys); asserts the invariants
    inline like the other rungs and returns a summary dict for
    tools/chaos.py and the tier-1 test."""
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.supervisor import HEALTHY, BackendSupervisor
    from cometbft_tpu.crypto.tpu import memory as memlib
    from cometbft_tpu.crypto.tpu import mesh, topology

    topo = topology.default_topology()
    handle = topo.device(0)
    handle.reset_chunk_shrink()
    name = f"chaos-mem-{seed}"
    plan = install(
        name=name, inner=inner,
        plan=FaultPlan(
            seed=seed, oom_rate=1.0, oom_above_lanes=lanes_threshold
        ),
    )
    sup = BackendSupervisor(
        spec=BackendSpec(name),
        dispatch_timeout_ms=2000,
        breaker_threshold=3,
        audit_pct=100,
        audit_sync=True,
        retry_ms=5,
        chunk_recover_n=1000,  # no cap recovery mid-run: phases stay clean
        logger=logger,
        topology=topo,
    )
    m = sup.metrics
    keys = [
        ed.gen_priv_key_from_secret(b"chaos-mem-%d" % i) for i in range(8)
    ]

    def make_items(tag: bytes):
        items, truth = [], []
        for i in range(16):
            k = keys[i % len(keys)]
            msg = b"mem %s %d" % (tag, i)
            items.append((k.pub_key(), msg, k.sign(msg)))
            truth.append(True)
        return items, truth

    # a modeled HBM limit that fits lanes_threshold lanes but not twice
    # that: free = limit × 0.9 lands just above the threshold bucket's
    # projected footprint, so the guard halves exactly down to it
    try:
        depth = mesh.pipeline_depth()
    except ValueError:
        depth = 2
    fit_bytes = int(memlib.SEED_BYTES_PER_LANE * lanes_threshold * depth)
    model_limit = int(fit_bytes / 0.9) + 1

    wrong = 0
    prev_plane = None
    plane_installed = False
    try:
        # phase A — reactive control: the OOM must actually COST
        # dispatches before the cap shrinks under the threshold
        items, truth = make_items(b"reactive")
        if sup.verify_items(items, reason="mem-reactive") != truth:
            wrong += 1
        reactive_ooms = plan.ooms_fired
        reactive_shrinks = m.chunk_shrinks.value()
        reactive_levels = handle.chunk_shrink_levels()

        # phase B — proactive guard: same armed fault, but the memory
        # plane clamps the cap pre-dispatch so it can never fire
        handle.reset_chunk_shrink()
        plane = memlib.MemoryPlane(
            topology=topo,
            poll_ms=1,
            headroom_fraction=0.9,
            model_limit_bytes=model_limit,
            stats=False,
        )
        prev_plane = memlib.set_default_plane(plane)
        plane_installed = True
        guard_cap = plane.refresh_guard(handle, 8192, 64)
        ooms_before = plan.ooms_fired
        shrinks_before = m.chunk_shrinks.value()
        for r in range(rounds):
            items, truth = make_items(b"guarded-%d" % r)
            if sup.verify_items(items, reason="mem-guarded") != truth:
                wrong += 1
        guarded_ooms = plan.ooms_fired - ooms_before
        guarded_shrinks = m.chunk_shrinks.value() - shrinks_before
        guard_shrink_events = sum(
            c.value() for c in plane.metrics.guard_shrinks._series()
        )
        state_final = sup.state()
    finally:
        sup.stop()
        if plane_installed:
            memlib.set_default_plane(prev_plane)
        handle.reset_chunk_shrink()

    summary = {
        "lanes_threshold": lanes_threshold,
        "model_limit_bytes": model_limit,
        "wrong_verdicts": wrong,
        "reactive_ooms": reactive_ooms,
        "reactive_shrinks": reactive_shrinks,
        "reactive_levels": reactive_levels,
        "guard_cap": guard_cap,
        "guarded_ooms": guarded_ooms,
        "guarded_shrinks": guarded_shrinks,
        "guard_shrink_events": guard_shrink_events,
        "state_final": state_final,
        "backend_dispatches": plan.dispatches,
        "expected": {"guarded_ooms": 0, "state_final": HEALTHY},
    }
    assert wrong == 0, f"wrong verdicts released: {wrong}"
    assert reactive_ooms > 0, "control phase never fired the OOM fault"
    assert reactive_shrinks > 0, "control phase never shrank reactively"
    assert guard_cap <= lanes_threshold, (
        f"guard cap {guard_cap} above the allocator threshold "
        f"{lanes_threshold}"
    )
    assert guarded_ooms == 0, (
        f"{guarded_ooms} RESOURCE_EXHAUSTED reached the supervisor "
        "despite the pre-dispatch guard"
    )
    assert guarded_shrinks == 0, "reactive rung engaged under guard"
    assert guard_shrink_events > 0, "guard never recorded its shrink"
    return summary


# ---------------------------------------------------------------------------
# sharded-mesh chaos: kill one domain mid-sharded-flow, mesh re-slices
# ---------------------------------------------------------------------------


def run_chaos_sharded(
    devices: int = 8,
    kill: int = 3,
    seed: int = 7,
    inner: cryptobatch.Backend = "cpu",
    rounds: int = 4,
    logger=None,
) -> dict:
    """The sharded-dispatch degradation proof: megabatches route as ONE
    multi-device program over an N-domain mesh; device ``kill`` is then
    injected with a program-fatal failure (a sharded program containing
    the target dies whole, named — see FaultyBackend._sharded_target_label)
    and the run asserts

      * zero wrong verdicts are ever released (sync-audit mode) and no
        node-wide CPU fallback engages;
      * the failure is attributed to the OFFENDING domain: exactly
        device ``kill`` is quarantined, the topology mirror marks it,
        and the shard plan re-slices to N-1 devices for the retry —
        the faulted megabatch still completes with ground-truth verdicts;
      * sharded throughput on the degraded mesh stays within the
        partial-degradation bound: ≥ 0.6 × (N-1)/N of the full-mesh rate
        (the PR 6 bound, applied to the sharded path);
      * repair + the killed domain's canary re-admit it and the plan
        re-slices back to N devices.

    Requires ≥ ``devices`` visible jax devices (the virtual CPU mesh via
    XLA_FLAGS=--xla_force_host_platform_device_count). Deterministic:
    seeded faults, rate-1.0 kill. Returns a summary dict; tools/chaos.py
    --sharded and the tier-1 suite assert on it."""
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.supervisor import (
        DEGRADED,
        HEALTHY,
        BackendSupervisor,
    )
    from cometbft_tpu.crypto.tpu import mesh, topology

    if not 0 <= kill < devices:
        raise ValueError(f"kill index {kill} outside 0..{devices - 1}")
    topo = topology.DeviceTopology.virtual(devices)
    prev_topo = topology.default_topology()
    # the mesh module's shard_plan resolves the DEFAULT topology (that
    # is what production does: node start installs its detected one)
    topology.set_default_topology(topo)
    name = f"chaos-sh-{seed}-{devices}-{kill}"
    plan = install(
        name=name, inner=inner, plan=FaultPlan(seed=seed, device=kill)
    )
    sup = BackendSupervisor(
        spec=BackendSpec(name),
        dispatch_timeout_ms=2000,
        breaker_threshold=1,
        audit_pct=100,
        audit_sync=True,  # no wrong verdict may ever be released
        probe_base_ms=60_000,
        probe_max_ms=120_000,
        hedge_pct=0,  # hedging off: outcomes must be attributable
        retry_ms=5,
        logger=logger,
        topology=topo,
    )
    if mesh.shard_plan(topo) is None:
        sup.stop()
        topology.set_default_topology(prev_topo)
        raise RuntimeError(
            f"sharded chaos needs a {devices}-way device plane "
            "(XLA_FLAGS=--xla_force_host_platform_device_count)"
        )
    killed_label = topo.device(kill).label
    m = sup.metrics
    keys = [
        ed.gen_priv_key_from_secret(b"chaos-sh-%d" % i) for i in range(8)
    ]
    batch = 64 * devices

    def make_items(tag: bytes, poison_at=None):
        items, truth = [], []
        for i in range(batch):
            k = keys[i % len(keys)]
            msg = b"sh %s %d" % (tag, i)
            good = i != poison_at
            items.append((k.pub_key(), msg,
                          k.sign(msg) if good else b"\x17" * 64))
            truth.append(good)
        return items, truth

    def series(counter) -> dict:
        return {
            c._labels["device"]: c.value()
            for c in counter._series() if "device" in c._labels
        }

    def timed_rounds(tag: bytes) -> float:
        """Sigs/sec over ``rounds`` sharded megabatches (wall clock)."""
        t0 = time.perf_counter()
        for r in range(rounds):
            items, truth = make_items(tag + b"-%d" % r)
            got = sup.verify_items(
                items, reason="sh-" + tag.decode(), route="sharded"
            )
            if got != truth:
                wrong.append(tag)
        return rounds * batch / (time.perf_counter() - t0)

    wrong: List[bytes] = []
    try:
        # phase 1 — full-mesh baseline: clean sharded megabatches (one
        # poisoned lane proves per-lane verdict attribution rides along)
        items, truth = make_items(b"base", poison_at=11)
        if sup.verify_items(items, reason="sh-base", route="sharded") != truth:
            wrong.append(b"base")
        full_rate = timed_rounds(b"full")
        dispatches_full = m.sharded_dispatches.value()

        # phase 2 — kill: the armed fault takes down the whole sharded
        # program, named; the supervisor attributes, quarantines device
        # `kill`, re-slices to N-1, and the SAME megabatch completes
        plan.exception_rate = 1.0
        items, truth = make_items(b"kill", poison_at=5)
        if sup.verify_items(items, reason="sh-kill", route="sharded") != truth:
            wrong.append(b"kill")
        states = sup.device_states()
        quarantined_only_kill = (
            states.get(killed_label) == "broken"
            and all(s == HEALTHY for d, s in states.items()
                    if d != killed_label)
        )
        state_degraded = sup.state()
        reslices = m.sharded_reslices.value()
        plan_after = mesh.shard_plan(topo)
        resliced_n = plan_after.n_shards if plan_after is not None else 0
        topo_mirrored = topo.is_quarantined(kill)

        # phase 3 — degraded throughput: the fault is still armed, but
        # the re-sliced mesh no longer contains the target, so sharded
        # megabatches keep serving on N-1 devices within the bound
        degraded_rate = timed_rounds(b"degraded")
        bound = 0.6 * (devices - 1) / devices * full_rate
        throughput_ok = degraded_rate >= bound

        # phase 4 — repair + re-admission: the killed domain's canary
        # closes its breaker, the mirror clears, the plan re-slices back
        plan.clear()
        readmit_ok = sup.probe_now(device=kill)
        plan_back = mesh.shard_plan(topo)
        restored_n = plan_back.n_shards if plan_back is not None else 0
        items, truth = make_items(b"restored")
        if (
            sup.verify_items(items, reason="sh-restored", route="sharded")
            != truth
        ):
            wrong.append(b"restored")
        final_states = sup.device_states()
    finally:
        sup.stop()
        topology.set_default_topology(prev_topo)

    summary = {
        "devices": devices,
        "kill": kill,
        "batch": batch,
        "wrong_verdicts": len(wrong),
        "cpu_routed": m.cpu_routed.value(),
        "quarantines": series(m.quarantines),
        "sharded_dispatches": m.sharded_dispatches.value(),
        "sharded_dispatches_full_phase": dispatches_full,
        "sharded_reslices": reslices,
        "quarantined_only_kill": quarantined_only_kill,
        "state_while_quarantined": state_degraded,
        "topology_mirrored_quarantine": topo_mirrored,
        "resliced_shards": resliced_n,
        "full_rate_sigs_s": round(full_rate, 1),
        "degraded_rate_sigs_s": round(degraded_rate, 1),
        "throughput_bound_sigs_s": round(bound, 1),
        "throughput_ok": throughput_ok,
        "readmit_probe_ok": readmit_ok,
        "restored_shards": restored_n,
        "final_states": final_states,
        "backend_dispatches": plan.dispatches,
        "expected": {
            "state_while_quarantined": DEGRADED,
            "final_state": HEALTHY,
        },
    }
    # safety invariants hold unconditionally — assert here so every
    # caller (CLI, tests, bench) gets them for free
    assert not wrong, f"wrong verdicts released in phases {wrong}"
    assert m.cpu_routed.value() == 0, "node-wide CPU fallback engaged"
    assert quarantined_only_kill, (
        f"quarantine attribution missed: {states}"
    )
    assert topo_mirrored, "breaker trip never mirrored into the topology"
    assert resliced_n == devices - 1, (
        f"shard plan re-sliced to {resliced_n}, expected {devices - 1}"
    )
    assert reslices >= 1, "sharded re-slice counter never moved"
    assert throughput_ok, (
        f"degraded sharded rate {degraded_rate:.1f} sigs/s below bound "
        f"{bound:.1f} (full-mesh {full_rate:.1f})"
    )
    assert readmit_ok and restored_n == devices, (
        f"re-admission failed: probe={readmit_ok} shards={restored_n}"
    )
    assert all(s == HEALTHY for s in final_states.values()), final_states
    return summary


def _p99_ms(samples_s: List[float]) -> float:
    """p99 of a latency sample list, in milliseconds (0.0 when empty)."""
    if not samples_s:
        return 0.0
    xs = sorted(samples_s)
    idx = min(len(xs) - 1, int(round(0.99 * (len(xs) - 1))))
    return xs[idx] * 1e3


def run_chaos_overload(
    seed: int = 17,
    inner: cryptobatch.Backend = "cpu",
    logger=None,
    flood_s: float = 1.5,
) -> dict:
    """The QoS overload rung: a steady consensus workload rides through a
    10x blocksync+mempool flood without starving, because the admission
    layer sheds/drops the floods and the brownout controller browns the
    low classes out — and the SAME flood with ``CBFT_QOS_CLASSES=off``
    demonstrably starves consensus (the contrast is what proves the
    mechanism is load-bearing, not the workload being easy).

    Phase A (QoS on, default ladder): measure unloaded consensus p99,
    then flood blocksync+mempool for ``flood_s`` while a consensus
    submitter keeps a steady cadence. Assertable outcomes collected in
    the summary: zero consensus sheds/drops/backpressure-timeouts, flood
    sheds >= 1 and drops >= 1, brownout trips >= 1, loaded consensus p99
    within 2x of max(unloaded p99, one dispatch quantum), full brownout
    re-admission once the flood stops (readmissions >= 1, disabled
    empty), and ground-truth verdicts on every non-rejected future.

    Phase B (QoS off, same flood): consensus p99 must come out >= 2x the
    phase-A loaded p99 — FIFO starvation the QoS layer prevented.

    Returns a summary dict; callers (the tier-1 overload test,
    ``tools/chaos.py --overload``) assert on it.
    """
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.scheduler import VerifyScheduler
    from cometbft_tpu.crypto.telemetry import TelemetryHub

    name = f"chaos-overload-{seed}"
    # jitter-dominated dispatch cost: 0-20 ms per flush makes the
    # queueing dynamics (and therefore the latency contrast between the
    # two phases) mostly independent of how fast the host CPU verifies
    install(name=name, inner=inner, plan=FaultPlan(seed=seed, jitter_ms=20))

    keys = [
        ed.gen_priv_key_from_secret(b"chaos-overload-%d" % i)
        for i in range(8)
    ]

    def make_items(count, tag):
        items = []
        for i in range(count):
            k = keys[i % len(keys)]
            msg = b"overload %s %d" % (tag, i)
            items.append((k.pub_key(), msg, k.sign(msg)))
        return items

    CONSENSUS_N = 8
    FLOOD_N = 32
    SLO_TARGET_MS = 30
    # one flood-heavy dispatch quantum (injected jitter + a budget's
    # worth of verification): loaded consensus latency is ~2 quanta (the
    # in-flight flush, then its own), so a bound below 2x this floor
    # would fail on timing noise, not on starvation
    DISPATCH_FLOOR_MS = 40.0

    consensus_items = make_items(CONSENSUS_N, b"consensus")
    flood_items = {
        "blocksync": make_items(FLOOD_N, b"blocksync"),
        "mempool": make_items(FLOOD_N, b"mempool"),
    }

    def run_phase(qos_mode: str) -> dict:
        """One full unloaded->flood->drain cycle under ``qos_mode``."""
        env_save = {
            k: os.environ.get(k)
            for k in ("CBFT_QOS_CLASSES", "CBFT_QOS_SHED_MS")
        }
        os.environ["CBFT_QOS_CLASSES"] = qos_mode
        # tight shed deadline: the rung wants deadline sheds to actually
        # fire within a sub-2s flood, not only post-brownout fast-sheds
        os.environ["CBFT_QOS_SHED_MS"] = "5"
        hub = TelemetryHub(slo_target_ms=SLO_TARGET_MS, window_s=1.5)
        try:
            sched = VerifyScheduler(
                spec=BackendSpec(name),
                flush_us=200,
                lane_budget=64,
                max_queue=128,
                telemetry=hub,
                submit_timeout_ms=250,
                logger=logger,
            )
        finally:
            for k, v in env_save.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        if sched.qos_enabled:
            hub.add_burn_watcher(sched.on_burn)
        sched.start()

        wrong = 0
        rejected = 0
        flood_futs: List[Tuple[str, object]] = []
        stop_flood = threading.Event()
        stop_scrape = threading.Event()

        def scraper():
            # the node's metrics scrape loop: each snapshot recomputes
            # SLO burn and feeds the brownout controller via the watcher
            while not stop_scrape.is_set():
                hub.snapshot()
                time.sleep(0.05)

        def flood(sub):
            while not stop_flood.is_set():
                fut = sched.submit(flood_items[sub], subsystem=sub)
                flood_futs.append((sub, fut))
                time.sleep(0.002)

        scrape_t = threading.Thread(target=scraper, daemon=True)
        scrape_t.start()
        try:
            # -- warmup: the first dispatch pays one-time backend setup
            # (jit/compile on the CPU path) — keep it out of the baseline
            sched.submit(
                consensus_items, subsystem="consensus"
            ).result(timeout=60)

            # -- unloaded baseline ----------------------------------------
            unloaded = []
            for _ in range(30):
                t0 = time.monotonic()
                ok, mask = sched.submit(
                    consensus_items, subsystem="consensus"
                ).result(timeout=30)
                unloaded.append(time.monotonic() - t0)
                if not ok or mask != [True] * CONSENSUS_N:
                    wrong += 1
                time.sleep(0.002)

            # -- flood ----------------------------------------------------
            flood_threads = [
                threading.Thread(target=flood, args=(sub,), daemon=True)
                for sub in ("blocksync", "blocksync", "mempool", "mempool")
            ]
            for t in flood_threads:
                t.start()
            loaded = []
            t_end = time.monotonic() + flood_s
            while time.monotonic() < t_end:
                t0 = time.monotonic()
                ok, mask = sched.submit(
                    consensus_items, subsystem="consensus"
                ).result(timeout=30)
                loaded.append(time.monotonic() - t0)
                if not ok or mask != [True] * CONSENSUS_N:
                    wrong += 1
                time.sleep(0.005)
            stop_flood.set()
            for t in flood_threads:
                t.join(timeout=30)

            # -- drain: every flood future resolves, verdicts ground-truth
            for sub, fut in flood_futs:
                ok, mask = fut.result(timeout=30)
                if getattr(fut, "rejected", False):
                    rejected += 1
                    if ok or any(mask):
                        wrong += 1  # a drop must never claim validity
                elif not ok or mask != [True] * FLOOD_N:
                    wrong += 1

            # -- recovery: flood latencies age out of the SLO window, burn
            # clears, the brownout ladder re-admits bottom-up
            readmitted = True
            if sched.qos_enabled:
                readmitted = False
                deadline = time.monotonic() + 12.0
                while time.monotonic() < deadline:
                    bo = sched.queue_snapshot()["qos"]["brownout"]
                    if not bo["disabled"] and bo["readmissions"] >= 1:
                        readmitted = True
                        break
                    time.sleep(0.2)
            snap = sched.queue_snapshot()
            bp_timeouts = sched.metrics.backpressure_timeouts.value()
        finally:
            stop_flood.set()
            stop_scrape.set()
            scrape_t.join(timeout=10)
            sched.stop()

        out = {
            "backpressure_timeouts": bp_timeouts,
            "qos_mode": qos_mode,
            "unloaded_p99_ms": round(_p99_ms(unloaded), 2),
            "loaded_p99_ms": round(_p99_ms(loaded), 2),
            "consensus_samples": len(loaded),
            "flood_requests": len(flood_futs),
            "wrong_verdicts": wrong,
            "rejected": rejected,
            "readmitted": readmitted,
            "snapshot": snap,
        }
        if snap["qos"]["enabled"]:
            cls = snap["qos"]["classes"]
            out["consensus_sheds"] = cls["consensus"]["sheds"]
            out["consensus_drops"] = cls["consensus"]["drops"]
            out["flood_sheds"] = sum(
                cls[c]["sheds"] for c in ("blocksync", "mempool")
            )
            out["flood_drops"] = sum(
                cls[c]["drops"] for c in ("blocksync", "mempool")
            )
            out["brownout"] = snap["qos"]["brownout"]
        return out

    phase_a = run_phase("default")
    phase_b = run_phase("off")

    latency_bound_ms = 2.0 * max(
        phase_a["unloaded_p99_ms"], DISPATCH_FLOOR_MS
    )
    latency_ok = phase_a["loaded_p99_ms"] <= latency_bound_ms
    starvation_ratio = (
        phase_b["loaded_p99_ms"] / phase_a["loaded_p99_ms"]
        if phase_a["loaded_p99_ms"] > 0
        else float("inf")
    )
    # same bound, both directions: QoS keeps loaded consensus p99 inside
    # it, and the identical flood through a FIFO scheduler blows it
    starved_without_qos = phase_b["loaded_p99_ms"] > latency_bound_ms

    summary = {
        "seed": seed,
        "flood_s": flood_s,
        "wrong_verdicts": phase_a["wrong_verdicts"] + phase_b["wrong_verdicts"],
        "unloaded_p99_ms": phase_a["unloaded_p99_ms"],
        "loaded_p99_ms": phase_a["loaded_p99_ms"],
        "latency_bound_ms": round(latency_bound_ms, 2),
        "latency_ok": latency_ok,
        "consensus_sheds": phase_a["consensus_sheds"],
        "consensus_drops": phase_a["consensus_drops"],
        # in phase A only block-policy classes (consensus/evidence) can
        # hit the backpressure timeout -> inline-CPU path, so this total
        # IS the consensus timeout count
        "consensus_backpressure_timeouts": phase_a["backpressure_timeouts"],
        "flood_sheds": phase_a["flood_sheds"],
        "flood_drops": phase_a["flood_drops"],
        "rejected": phase_a["rejected"],
        "brownout": phase_a["brownout"],
        "readmitted": phase_a["readmitted"],
        "qos_off_p99_ms": phase_b["loaded_p99_ms"],
        "starvation_ratio": round(starvation_ratio, 2),
        "starved_without_qos": starved_without_qos,
        "flush_reasons": phase_a["snapshot"]["flush_reasons"],
        "expected": {
            "wrong_verdicts": 0,
            "consensus_sheds": 0,
            "consensus_drops": 0,
            "consensus_backpressure_timeouts": 0,
            "flood_sheds": ">= 1",
            "flood_drops": ">= 1",
            "brownout_trips": ">= 1",
            "readmitted": True,
            "latency": "loaded p99 <= 2x max(unloaded p99, %.0fms)"
            % DISPATCH_FLOOR_MS,
            "starvation": "qos-off p99 above the same bound",
        },
    }
    return summary


def run_chaos_service(
    seed: int = 17,
    logger=None,
    flood_s: float = 1.5,
) -> dict:
    """The verify-as-a-service rung: ONE daemon (VerifyScheduler +
    VerifyService on a Unix socket), 32 flood clients + 4 consensus
    clients, mixed QoS classes over the network boundary — and the same
    containment/latency invariants the in-process overload rung proves,
    now with real sockets in the loop.

    Three phases:

    1. **Disconnect containment** (deterministic): the device pool is
       frozen (harness holds the dispatch lock), four flood clients park
       requests in flight, then their sockets are severed abruptly. The
       killed clients' futures must resolve via the local-CPU fallback
       with ``reason="disconnected"`` and ground-truth verdicts; a
       survivor's in-flight requests — merged into the SAME coalesced
       flush — must still complete correctly after thaw; the server
       meters the disconnects per tenant and keeps serving.
    2. **Flood**: all 32 flood clients (including the previously-killed
       four, which must reconnect cleanly) push blocksync+mempool load
       at ~2.5x dispatch capacity while consensus clients keep a steady
       cadence. Consensus p99 must hold within 2x of
       max(unloaded p99, one dispatch quantum); the merged queue's QoS
       layer must shed and drop flood (clients see honest rejections,
       NOT wrong verdicts), and the brownout controller must trip.
    3. **Recovery**: flood stops, burn clears, brownout re-admits
       bottom-up; every future ever issued resolves with a ground-truth
       verdict; the service drains to zero pending.

    Returns a summary dict; callers (the tier-1 service-chaos test,
    ``tools/chaos.py --service``) assert on it.
    """
    import json
    import shutil
    import tempfile

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto import service as servicelib
    from cometbft_tpu.crypto.scheduler import VerifyScheduler
    from cometbft_tpu.crypto.telemetry import TelemetryHub
    from cometbft_tpu.libs import trace as tracelib

    CONSENSUS_N = 8
    FLOOD_N = 16
    CONSENSUS_CLIENTS = 4
    FLOOD_CLIENTS = 32
    KILLED = 4
    BAD_LANE = 2  # every flood batch carries one corrupted signature
    SLO_TARGET_MS = 30
    # one flood-heavy dispatch quantum: with 16-lane floods against a
    # 64-lane budget a consensus request can legitimately sit behind two
    # in-flight flushes plus its own (3 x the 5-20 ms injected pool
    # floor), and 36 client threads add real GIL noise on a busy host —
    # a bound below 2x this floor fails on timing, not starvation
    DISPATCH_FLOOR_MS = 60.0

    rng = random.Random(seed)
    keys = [
        ed.gen_priv_key_from_secret(b"chaos-service-%d" % i)
        for i in range(8)
    ]

    def make_items(count, tag, bad=None):
        items = []
        for i in range(count):
            k = keys[i % len(keys)]
            msg = b"service %s %d" % (tag, i)
            sig = k.sign(msg)
            if i == bad:
                sig = bytes(sig[:-1]) + bytes([sig[-1] ^ 0x01])
            items.append((k.pub_key(), msg, sig))
        return items

    consensus_items = make_items(CONSENSUS_N, b"consensus")
    flood_items = {
        "blocksync": make_items(FLOOD_N, b"blocksync", bad=BAD_LANE),
        "mempool": make_items(FLOOD_N, b"mempool", bad=BAD_LANE),
    }
    flood_expected = [i != BAD_LANE for i in range(FLOOD_N)]

    # the "device pool": the shared host row verifier (memoized — every
    # distinct lane truly verified once) behind ONE lock plus a seeded
    # 5-20 ms floor per flush, modeling a single serialized accelerator
    pool_mtx = threading.Lock()
    inner_verifier = servicelib.host_row_verifier()

    def floor_verifier(rows):
        with pool_mtx:
            time.sleep(0.005 + 0.015 * rng.random())
            return inner_verifier(rows)

    env_save = {
        k: os.environ.get(k)
        for k in ("CBFT_QOS_CLASSES", "CBFT_QOS_SHED_MS")
    }
    os.environ["CBFT_QOS_CLASSES"] = "default"
    os.environ["CBFT_QOS_SHED_MS"] = "5"
    hub = TelemetryHub(slo_target_ms=SLO_TARGET_MS, window_s=1.5)
    try:
        sched = VerifyScheduler(
            spec="cpu",
            flush_us=200,
            lane_budget=64,
            max_queue=128,
            telemetry=hub,
            submit_timeout_ms=250,
            row_verifier=floor_verifier,
            logger=logger,
        )
    finally:
        for k, v in env_save.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    hub.add_burn_watcher(sched.on_burn)
    sock_path = "/tmp/cbft-chaos-svc-%d-%d.sock" % (seed, os.getpid())
    service = servicelib.VerifyService(
        sched, "unix://" + sock_path, telemetry=hub, logger=logger,
    )
    # the daemon's incident plumbing, in-harness: a flight recorder
    # whose dump embeds the service view, flushed on the first brownout
    # trip — the chaos rung then proves the dump carries the tenant
    # panel the operator needs
    dump_dir = tempfile.mkdtemp(prefix="cbft-chaos-svc-dump-")
    tracer = tracelib.Tracer(sample=0.0, seed=seed, dump_dir=dump_dir)
    tracer.set_dump_context(lambda: {
        "service": service.snapshot(),
        "timeline": hub.timeline(),
    })
    incident = {"path": None, "fired": False}

    def _on_incident(ev):
        # dump off-thread: the trip fires inside the burn-watcher path
        # and the flood phase is measuring consensus latency
        if ev.get("kind") != "brownout_trip" or incident["fired"]:
            return
        incident["fired"] = True

        def _dump():
            incident["path"] = tracer.dump(
                "brownout_trip", extra={"event": ev}
            )

        threading.Thread(target=_dump, daemon=True).start()

    hub.add_event_listener(_on_incident)
    sched.start()
    service.start()

    wrong = {"baseline": 0, "killed": 0, "survivor": 0,
             "consensus": 0, "drain": 0}
    kill_reasons = {}
    rejected = 0
    disconnect_fallbacks = 0
    flood_futs: List[Tuple[str, object]] = []
    stop_flood = threading.Event()
    stop_scrape = threading.Event()

    def scraper():
        while not stop_scrape.is_set():
            hub.snapshot()
            time.sleep(0.05)

    clients = []
    killed_clients = []
    consensus_clients = []
    try:
        scrape_t = threading.Thread(target=scraper, daemon=True)
        scrape_t.start()

        address = "unix://" + sock_path
        # clients share the hub: their fallback/rejection events land on
        # the SAME timeline as the server's disconnect/brownout events,
        # exactly as a node + daemon pair merged by fleet verify_top
        for i in range(CONSENSUS_CLIENTS):
            consensus_clients.append(servicelib.RemoteVerifier(
                address, tenant="cons%d" % i, timeout_ms=10_000,
                retry_s=0.05, telemetry=hub, logger=logger,
            ))
        for i in range(FLOOD_CLIENTS):
            clients.append(servicelib.RemoteVerifier(
                address, tenant="flood", timeout_ms=5_000,
                retry_s=0.05, telemetry=hub, logger=logger,
            ))
        killed_clients = clients[:KILLED]
        survivor = clients[KILLED]

        def flood_sub(i):
            return "blocksync" if i % 2 == 0 else "mempool"

        # -- warmup: fill the memoized pool (each distinct lane pays its
        # one true verification here, out of the latency baseline)
        consensus_clients[0].submit(
            consensus_items, subsystem="consensus"
        ).result(timeout=60)
        for sub in ("blocksync", "mempool"):
            survivor.submit(
                flood_items[sub], subsystem=sub
            ).result(timeout=60)

        # -- unloaded baseline ------------------------------------------
        unloaded = []
        for n in range(30):
            rv = consensus_clients[n % CONSENSUS_CLIENTS]
            t0 = time.monotonic()
            ok, mask = rv.submit(
                consensus_items, subsystem="consensus"
            ).result(timeout=30)
            unloaded.append(time.monotonic() - t0)
            if not ok or mask != [True] * CONSENSUS_N:
                wrong["baseline"] += 1
            time.sleep(0.002)

        # the warmup/baseline spikes (every distinct lane pays its one
        # true verification there) can trip the brownout controller; let
        # the telemetry window age them out so the phases below start
        # from a healthy admission plane (a browned-out blocksync class
        # would shed the phase-1 requests before the kill)
        settle_deadline = time.monotonic() + 12.0
        while time.monotonic() < settle_deadline:
            bo = sched.queue_snapshot()["qos"]["brownout"]
            if not bo["disabled"]:
                break
            time.sleep(0.1)

        # -- phase 1: deterministic disconnect containment --------------
        # freeze the pool so every request below stays in flight, park
        # requests from the doomed clients AND a survivor in the same
        # merged flush (one lane budget exactly — nothing can queue past
        # the class bound and shed), sever the doomed sockets, thaw
        kill_futs = []
        survivor_futs = []
        with pool_mtx:
            for rv in killed_clients:
                kill_futs.append(rv.submit(
                    flood_items["blocksync"], subsystem="blocksync"
                ))
            for _ in range(2):
                survivor_futs.append(survivor.submit(
                    flood_items["mempool"], subsystem="mempool"
                ))
            time.sleep(0.1)  # frames reach the server, go pending
            kill_t0 = time.time()  # timeline events use the wall clock
            for rv in killed_clients:
                rv.kill_connection()
            time.sleep(0.1)  # server readers observe the dead sockets
        for fut in kill_futs:
            ok, mask = fut.result(timeout=30)
            disconnect_fallbacks += 1
            reason = getattr(fut, "reason", None)
            kill_reasons[str(reason)] = kill_reasons.get(str(reason), 0) + 1
            if reason != "disconnected":
                wrong["killed"] += 1  # containment must be attributed
            elif mask != flood_expected:
                wrong["killed"] += 1
        for fut in survivor_futs:
            ok, mask = fut.result(timeout=30)
            if getattr(fut, "rejected", False):
                rejected += 1
                if ok or any(mask):
                    wrong["survivor"] += 1
            elif mask != flood_expected:
                wrong["survivor"] += 1  # neighbor's death leaked here
        disconnects_metered = sum(
            service.snapshot()["disconnects"].values()
        )
        # the incident timeline must have captured the kill from BOTH
        # sides — the server's disconnect, the client's typed fallback —
        # on one non-decreasing wall clock
        tl = hub.timeline()
        tl_server_disc = [
            ev for ev in tl
            if ev.get("kind") == "disconnect"
            and ev.get("source") == "server"
            and ev.get("tenant") == "flood"
            and ev.get("t", 0.0) >= kill_t0 - 0.001
        ]
        tl_client_fb = [
            ev for ev in tl
            if ev.get("kind") == "client_fallback"
            and ev.get("source") == "client"
            and ev.get("reason") == "disconnected"
            and ev.get("t", 0.0) >= kill_t0 - 0.001
        ]
        tl_ordered = all(
            tl[i].get("t", 0.0) <= tl[i + 1].get("t", 0.0)
            for i in range(len(tl) - 1)
        )
        timeline_ok = (
            len(tl_server_disc) >= 1
            and len(tl_client_fb) >= KILLED
            and tl_ordered
        )

        # -- phase 2: flood ---------------------------------------------
        def flood(idx):
            rv = clients[idx]
            sub = flood_sub(idx)
            while not stop_flood.is_set():
                fut = rv.submit(flood_items[sub], subsystem=sub)
                flood_futs.append((sub, fut))
                time.sleep(0.01)

        flood_threads = [
            threading.Thread(target=flood, args=(i,), daemon=True)
            for i in range(FLOOD_CLIENTS)
        ]
        for t in flood_threads:
            t.start()
        loaded = []
        t_end = time.monotonic() + flood_s
        n = 0
        while time.monotonic() < t_end:
            rv = consensus_clients[n % CONSENSUS_CLIENTS]
            n += 1
            t0 = time.monotonic()
            ok, mask = rv.submit(
                consensus_items, subsystem="consensus"
            ).result(timeout=30)
            loaded.append(time.monotonic() - t0)
            if not ok or mask != [True] * CONSENSUS_N:
                wrong["consensus"] += 1
            time.sleep(0.005)
        stop_flood.set()
        for t in flood_threads:
            t.join(timeout=30)

        # -- drain: every flood future resolves; rejections are honest
        # (never claim validity), completions are ground-truth
        for sub, fut in flood_futs:
            ok, mask = fut.result(timeout=30)
            if getattr(fut, "rejected", False):
                rejected += 1
                if ok or any(mask):
                    wrong["drain"] += 1
            elif getattr(fut, "reason", None) == "disconnected":
                disconnect_fallbacks += 1
                if mask != flood_expected:
                    wrong["drain"] += 1
            elif mask != flood_expected:
                wrong["drain"] += 1

        # -- phase 3: recovery ------------------------------------------
        readmitted = False
        deadline = time.monotonic() + 12.0
        while time.monotonic() < deadline:
            bo = sched.queue_snapshot()["qos"]["brownout"]
            if not bo["disabled"] and bo["readmissions"] >= 1:
                readmitted = True
                break
            time.sleep(0.2)
        snap = sched.queue_snapshot()
        svc_snap = service.snapshot()
        pending_after = service.pending_requests()
        killed_stats = [rv.stats() for rv in killed_clients]
        # the brownout trip must have flushed an incident dump that
        # embeds the service view: the tenant panel and the event ring
        dump_wait = time.monotonic() + 5.0
        while incident["fired"] and incident["path"] is None \
                and time.monotonic() < dump_wait:
            time.sleep(0.05)
        incident_dump_ok = False
        if incident["path"]:
            try:
                with open(incident["path"], "r", encoding="utf-8") as f:
                    dump_doc = json.load(f)
                incident_dump_ok = (
                    dump_doc.get("reason") == "brownout_trip"
                    and bool(
                        dump_doc.get("service", {}).get("tenants_panel")
                    )
                    and isinstance(dump_doc.get("timeline"), list)
                )
            except (OSError, ValueError):
                incident_dump_ok = False
    finally:
        stop_flood.set()
        stop_scrape.set()
        for rv in consensus_clients + clients:
            rv.close()
        service.stop()
        sched.stop()
        try:
            os.unlink(sock_path)
        except OSError:
            pass
        shutil.rmtree(dump_dir, ignore_errors=True)

    cls = snap["qos"]["classes"]
    bpl = svc_snap.get("bytes_per_lane", {})
    latency_bound_ms = 2.0 * max(_p99_ms(unloaded), DISPATCH_FLOOR_MS)
    loaded_p99 = _p99_ms(loaded)
    summary = {
        "seed": seed,
        "flood_s": flood_s,
        "clients": CONSENSUS_CLIENTS + FLOOD_CLIENTS,
        "wrong_verdicts": sum(wrong.values()),
        "wrong_by_phase": wrong,
        "kill_reasons": kill_reasons,
        "unloaded_p99_ms": round(_p99_ms(unloaded), 2),
        "loaded_p99_ms": round(loaded_p99, 2),
        "latency_bound_ms": round(latency_bound_ms, 2),
        "latency_ok": loaded_p99 <= latency_bound_ms,
        "consensus_sheds": cls["consensus"]["sheds"],
        "consensus_drops": cls["consensus"]["drops"],
        "flood_sheds": sum(
            cls[c]["sheds"] for c in ("blocksync", "mempool")
        ),
        "flood_drops": sum(
            cls[c]["drops"] for c in ("blocksync", "mempool")
        ),
        "rejected": rejected,
        "flood_requests": len(flood_futs),
        "disconnect_fallbacks": disconnect_fallbacks,
        "disconnects_metered": disconnects_metered,
        "killed_client_fallbacks": sum(
            s.get("disconnected", 0) for s in killed_stats
        ),
        "brownout": snap["qos"]["brownout"],
        "readmitted": readmitted,
        "pending_after": pending_after,
        "bytes_per_lane": bpl,
        "bytes_per_lane_ok": all(v <= 128.0 for v in bpl.values()),
        "timeline_ok": timeline_ok,
        "timeline_events": len(tl),
        "timeline_kill_disconnects": len(tl_server_disc),
        "timeline_kill_fallbacks": len(tl_client_fb),
        "incident_dump_ok": incident_dump_ok,
        "service": {
            k: svc_snap[k]
            for k in ("frames", "lanes", "errors", "disconnects",
                      "tenants", "inline_dispatches")
        },
        "expected": {
            "wrong_verdicts": 0,
            "consensus_sheds": 0,
            "consensus_drops": 0,
            "flood_sheds": ">= 1",
            "flood_drops": ">= 1",
            "disconnect_fallbacks": ">= %d" % KILLED,
            "disconnects_metered": ">= 1",
            "brownout_trips": ">= 1",
            "timeline_ok": True,
            "incident_dump_ok": True,
            "readmitted": True,
            "pending_after": 0,
            "bytes_per_lane": "<= 128 on every kind",
            "latency": "loaded p99 <= 2x max(unloaded p99, %.0fms)"
            % DISPATCH_FLOOR_MS,
        },
    }
    return summary


def _wire_probe_kernel(x):
    """Trivial parity kernel for the wire chaos rung: True where the
    lane's byte-column sum is even. Module-level so the AOT registry
    gets a stable __qualname__ across runs."""
    import jax.numpy as jnp

    return (x.astype(jnp.uint32).sum(axis=0) & 1) == 0


def run_chaos_wire(
    seed: int = 7,
    chunks: int = 4,
    lanes: int = 128,
    jitter_ms: float = 25.0,
    logger=None,
) -> dict:
    """The attribution proof for the wire ledger (crypto/wire.py): under
    a jittery LINK — every ``jax.device_put`` stretched by a FaultPlan
    jitter draw — the ledger must blame the slowdown on the h2d phase,
    not compute.

    Three runs of the same deterministic payload through
    mesh.dispatch_batch (single-device route, fresh WireLedger each):

    * **warm** — absorbs the kernel compile so neither measured run
      carries it;
    * **clean** — baseline per-phase totals;
    * **jittery** — ``jax.device_put`` monkeypatched to sleep a
      ``FaultPlan(jitter_ms=..., seed=...)`` draw before each real put
      (mesh resolves the attribute at call time, so the patch IS the
      slow link), restored in a finally.

    Asserts: every mask matches the host-computed parity ground truth;
    the jittery run's h2d total grew by at least half the injected
    sleep; the compute total stayed flat (within max(5 ms, 25% of the
    injected sleep) — attribution did NOT leak into the kernel phase).
    Deterministic (seeded RNG payload + seeded jitter draws); returns a
    summary dict for tools/chaos.py and the tier-1 test."""
    import numpy as np

    from cometbft_tpu.crypto import wire as wirelib
    from cometbft_tpu.crypto.tpu import mesh

    n = chunks * lanes
    rng = np.random.RandomState(seed)
    payload = rng.randint(0, 256, size=(4, n)).astype(np.uint8)
    expected = ((payload.astype(np.uint32).sum(axis=0) & 1) == 0)

    def one_run() -> dict:
        """Dispatch the payload under a fresh ledger; → its last
        dispatch reconciliation record (per-phase ms totals)."""
        ledger = wirelib.WireLedger(window=8)
        prev = wirelib.set_default_ledger(ledger)
        try:
            with mesh.route_scope(mesh.ROUTE_SINGLE):
                mask = mesh.dispatch_batch(
                    _wire_probe_kernel, [payload], n, lanes, lanes
                )
        finally:
            wirelib.set_default_ledger(prev)
        if not (np.asarray(mask) == expected).all():
            raise AssertionError("wire chaos rung: wrong verdicts")
        recent = ledger.snapshot()["recent"]
        if not recent:
            raise AssertionError(
                "wire chaos rung: ledger saw no dispatch"
            )
        return recent[-1]

    one_run()  # warm: compile cost must not pollute either measurement
    clean = one_run()

    import jax

    plan = FaultPlan(jitter_ms=jitter_ms, seed=seed)
    injected = {"ms": 0.0}
    real_put = jax.device_put

    def jittery_put(*args, **kwargs):
        jitter_s = plan._decide()[4]
        if jitter_s > 0:
            time.sleep(jitter_s)
            injected["ms"] += jitter_s * 1e3
        return real_put(*args, **kwargs)

    jax.device_put = jittery_put
    try:
        jittery = one_run()
    finally:
        jax.device_put = real_put

    d_h2d = jittery["h2d_ms"] - clean["h2d_ms"]
    d_compute = jittery["compute_ms"] - clean["compute_ms"]
    compute_slack_ms = max(5.0, 0.25 * injected["ms"])
    if injected["ms"] <= 0:
        raise AssertionError("wire chaos rung: no jitter was injected")
    if d_h2d < 0.5 * injected["ms"]:
        raise AssertionError(
            f"wire ledger missed the slow link: h2d grew {d_h2d:.1f}ms "
            f"for {injected['ms']:.1f}ms injected"
        )
    if d_compute > compute_slack_ms:
        raise AssertionError(
            f"wire ledger misattributed the slow link to compute: "
            f"compute grew {d_compute:.1f}ms (slack {compute_slack_ms:.1f}ms)"
        )
    summary = {
        "chunks": chunks,
        "lanes": lanes,
        "injected_jitter_ms": round(injected["ms"], 1),
        "clean_h2d_ms": clean["h2d_ms"],
        "jittery_h2d_ms": jittery["h2d_ms"],
        "h2d_delta_ms": round(d_h2d, 1),
        "clean_compute_ms": clean["compute_ms"],
        "jittery_compute_ms": jittery["compute_ms"],
        "compute_delta_ms": round(d_compute, 1),
        "clean_overlap": clean["overlap"],
        "jittery_overlap": jittery["overlap"],
        "expected": {
            "wrong_verdicts": 0,
            "h2d_delta": ">= 0.5x injected jitter",
            "compute_delta": "<= max(5ms, 0.25x injected jitter)",
        },
        "ok": True,
    }
    if logger is not None:
        logger.info("chaos wire rung passed", **{
            k: v for k, v in summary.items() if k != "expected"
        })
    return summary


def run_chaos_stale_model(
    seed: int = 11,
    batch: int = 16,
    clean_flushes: int = 32,
    jitter_flushes: int = 24,
    recover_flushes: int = 120,
    jitter_ms: float = 300.0,
    logger=None,
) -> dict:
    """The staleness proof for the decision plane (crypto/decisions.py):
    an injected link-jitter regime must trip the anomaly watchdog, fire
    exactly ONE incident dump, and re-arm after clean windows.

    One unsupervised VerifyScheduler over a FaultyBackend (inner CPU)
    feeding a fresh DecisionLedger (the process default for the run;
    ring sampled every finish so the watchdog evaluates deterministically
    often), three regimes over the same live-mutable FaultPlan:

    * **clean** — no injected jitter; the ledger's per-(route, bucket)
      cost EWMA converges on the real dispatch wall, windowed MAPE
      settles low, the watchdog arms (>= MIN_TRIP_OBS observations);
    * **jitter** — ``plan.jitter_ms`` raised mid-run: every dispatch
      stretches by a seeded jitter draw, measured walls leave the
      model's predictions behind, windowed MAPE crosses the trip level
      -> the watchdog fires ``on_anomaly`` ONCE (the flight-recorder
      dump lands in a temp dir) and latches until the model adapts;
    * **recover** — ``plan.clear()``: walls return to baseline, the
      EWMA re-converges, the rolling window drains below HALF the trip
      level, and after REARM_CLEAN consecutive clean samples the
      watchdog is re-armed (it may already have re-armed late in the
      jitter phase once the EWMA caught up — adaptation, not amnesia).

    The scheduler runs with the PRICED live router (ISSUE 16), its cpu
    rung seeded expensive so the argmin engages once the single-chip
    self-EWMA warms: the jitter trip must also ROLL THE ROUTER BACK to
    the threshold ladder (hysteretic guard), and the recovery regime
    must RE-ADMIT it after clean windows — the stale-model proof that a
    lying cost model cannot keep steering live routing.

    Asserts: every verdict correct in all three regimes; zero trips
    during clean; exactly one trip + one anomaly fire + one dump file
    for the whole run; the watchdog is re-armed (not tripped) at the
    end; exactly one priced-router rollback, re-admitted by the end.
    Returns a summary dict for tools/chaos.py and the tier-1 test.
    """
    import glob
    import tempfile

    from cometbft_tpu.crypto import decisions as declib
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.scheduler import VerifyScheduler
    from cometbft_tpu.libs import trace as chaostracelib

    name = f"stale-model-{seed}"
    plan = install(name=name, inner="cpu", plan=FaultPlan(seed=seed))

    dump_dir = tempfile.mkdtemp(prefix="chaos_stale_model_")
    tracer = chaostracelib.Tracer(sample=0.0)
    tracer.set_dump_dir(dump_dir)
    fires: List[Tuple[str, float]] = []

    def on_anomaly(cause: str, value: float) -> None:
        fires.append((cause, value))
        tracer.dump(
            f"decision_{cause}",
            extra={"decision_anomaly": {"cause": cause, "value": value}},
        )

    ledger = declib.DecisionLedger(
        window=16,
        ring_interval_s=0.0,  # watchdog evaluates on every finish
        on_anomaly=on_anomaly,
        # price the host rung expensive: cpu is never walked on this
        # run (so no self-EWMA) and there is no wire profile — without
        # a seed the priced argmin would stay cold and the rollback
        # guard would have nothing to protect
        seed=lambda route, bucket: 1e6 if route == "cpu" else None,
    )
    sched = VerifyScheduler(
        spec=BackendSpec(name), flush_us=200, logger=logger,
        router="priced",
    )
    sched.start()

    keys = [
        ed.gen_priv_key_from_secret(b"stale-%d" % i) for i in range(batch)
    ]
    items = []
    for i, k in enumerate(keys):
        msg = b"stale model flush sig %d" % i
        items.append((k.pub_key(), msg, k.sign(msg)))

    wrong = 0

    def drive(n_flushes: int) -> None:
        nonlocal wrong
        for _ in range(n_flushes):
            ok, mask = sched.submit(items).result(timeout=30)
            if not ok or not all(mask):
                wrong += 1

    # warm BEFORE the ledger installs: the faulty backend's first
    # dispatch pays the TPU-package import, and that one-off wall must
    # not seed the cost model (run_chaos_wire warms the same way)
    drive(4)
    prev = declib.set_default_ledger(ledger)

    try:
        drive(clean_flushes)
        trips_clean = ledger.watchdog_state()["trips"]
        plan.jitter_ms = jitter_ms
        drive(jitter_flushes)
        # probe the trip COUNT, not the latched flag: once the cost
        # EWMA adapts to the jittery regime the window drains and the
        # watchdog may legitimately re-arm before the phase ends
        trips_jitter = ledger.watchdog_state()["trips"]
        plan.clear()
        drive(recover_flushes)
    finally:
        sched.stop()
        declib.set_default_ledger(prev)

    wd = ledger.watchdog_state()
    snap = ledger.snapshot()
    win = snap["windowed"]
    router = sched.queue_snapshot()["router"]
    priced_records = sum(
        1 for r in snap["recent"] if r.get("router") == "priced"
    )
    dumps = sorted(glob.glob(os.path.join(dump_dir, "trace_dump_*.json")))

    if wrong:
        raise AssertionError(
            f"stale-model chaos rung: {wrong} flushes returned wrong "
            "verdicts"
        )
    if trips_clean:
        raise AssertionError(
            f"stale-model chaos rung: watchdog tripped {trips_clean}x "
            "during the clean regime (false positive)"
        )
    if trips_jitter - trips_clean < 1:
        raise AssertionError(
            "stale-model chaos rung: injected jitter regime did not "
            "trip the anomaly watchdog"
        )
    if wd["trips"] != 1 or len(fires) != 1:
        raise AssertionError(
            f"stale-model chaos rung: expected exactly one trip/fire, "
            f"got trips={wd['trips']} fires={len(fires)}"
        )
    if len(dumps) != 1:
        raise AssertionError(
            f"stale-model chaos rung: expected exactly one incident "
            f"dump, found {len(dumps)} in {dump_dir}"
        )
    if wd["tripped"] is not None:
        raise AssertionError(
            "stale-model chaos rung: watchdog did not re-arm after "
            f"{recover_flushes} clean flushes (still tripped: "
            f"{wd['tripped']})"
        )
    if not priced_records:
        raise AssertionError(
            "stale-model chaos rung: the priced router never engaged "
            "(no priced-tagged decision records in the recent ring)"
        )
    if router["rollbacks"] != 1:
        raise AssertionError(
            "stale-model chaos rung: expected exactly one priced-router "
            f"rollback from the jitter trip, got {router['rollbacks']}"
        )
    if router["rolled_back"] or router["readmits"] != 1:
        raise AssertionError(
            "stale-model chaos rung: priced router was not re-admitted "
            f"after recovery (rolled_back={router['rolled_back']}, "
            f"readmits={router['readmits']})"
        )

    summary = {
        "batch": batch,
        "clean_flushes": clean_flushes,
        "jitter_flushes": jitter_flushes,
        "recover_flushes": recover_flushes,
        "injected_jitter_ms": jitter_ms,
        "trip_cause": fires[0][0],
        "trip_value": round(fires[0][1], 3),
        "trips": wd["trips"],
        "anomaly_fires": len(fires),
        "incident_dumps": len(dumps),
        "dump_path": dumps[0],
        "rearmed": wd["tripped"] is None,
        "final_mape": win["mape"],
        "wrong_verdicts": wrong,
        "router_mode": router["mode"],
        "router_live": router["live"],
        "router_rollbacks": router["rollbacks"],
        "router_readmits": router["readmits"],
        "router_rollback_cause": router["rollback_cause"],
        "router_priced_records": priced_records,
        "expected": {
            "wrong_verdicts": 0,
            "trips": 1,
            "anomaly_fires": 1,
            "incident_dumps": 1,
            "rearmed": True,
            "router_rollbacks": 1,
            "router_readmits": 1,
            "router_live": "priced",
        },
        "ok": True,
    }
    if logger is not None:
        logger.info("chaos stale-model rung passed", **{
            k: v for k, v in summary.items() if k != "expected"
        })
    return summary


def run_chaos_adversary(**kwargs) -> dict:
    """Workload-side chaos: the adversarial committee rung
    (crypto/adversary.py) — byzantine vote floods, valset churn,
    equivocation storms, and a mid-storm verifyd restart. Thin
    delegation so the chaos registry stays the one place callers look
    for every rung."""
    from cometbft_tpu.crypto import adversary

    return adversary.run_chaos_adversary(**kwargs)


def run_chaos_ha(
    seed: int = 17,
    logger=None,
    replicas: int = 3,
    load_threads: int = 3,
) -> dict:
    """The HA verify-fleet rung: ``replicas`` verifyd daemons (each its
    own scheduler + serialized "accelerator" floor + authenticated
    VerifyService on a Unix socket) behind ONE HAVerifier, driven
    through the full replica-set failure matrix under committee load:

    1. **Rolling drain-restart** — every replica in turn is silently
       drained (``drain(broadcast=False)``: the NEXT request eats a
       typed ST_DRAINING, deterministically exercising the per-request
       failover path), then broadcast-drained, fully stopped once its
       in-flight work answers, restarted, and probe re-admitted before
       the next replica goes. Invariant: zero wrong verdicts and ZERO
       local-CPU fallbacks — the failover rung absorbs every drained
       connection, and the drain is attributed ``draining``, not
       ``disconnected``.
    2. **Hard kill** — one replica dies abruptly with clients attached;
       in-flight and subsequent requests fail over within a bounded gap
       (disconnect-shaped, so well under the request timeout — never a
       timeout wait), attributed ``disconnected`` on the killed
       endpoint's client.
    3. **Blackhole partition** — one replica is replaced by a listener
       that accepts frames and never answers. The client eats request
       timeouts until the endpoint's breaker opens (quarantine: no
       further picks), then the real daemon returns and the endpoint is
       re-admitted by its OWN health probe — never by live traffic.
    4. **Auth refusal** — a wrong-key HAVerifier is refused typed
       ERR_UNAUTHORIZED on every endpoint: bounded attempts, verdicts
       still ground truth via the CPU rung, and the bad tenant never
       reaches any daemon's scheduler.
    5. **Aggregate throughput** — the same committee load through the
       3-replica fleet vs ONE plain client on one daemon, recorded as
       sigs/sec (the bench `ha` stage's comparison).

    Returns a summary dict; the tier-1 fast test and
    ``tools/chaos.py --ha`` assert on it.
    """
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto import ha as halib
    from cometbft_tpu.crypto import service as servicelib
    from cometbft_tpu.crypto.scheduler import VerifyScheduler
    from cometbft_tpu.crypto.telemetry import TelemetryHub

    N_SIGS = 8
    BAD_LANE = 2
    AUTH_KEY = b"chaos-ha-%d" % seed
    TIMEOUT_MS = 1500
    GAP_BOUND_MS = TIMEOUT_MS / 2.0
    PROBE_BASE_S = 0.05
    PROBE_CAP_S = 0.5

    rng = random.Random(seed)
    keys = [
        ed.gen_priv_key_from_secret(b"chaos-ha-%d" % i) for i in range(8)
    ]
    items = []
    for i in range(N_SIGS):
        k = keys[i % len(keys)]
        msg = b"ha committee %d" % i
        sig = k.sign(msg)
        if i == BAD_LANE:
            sig = bytes(sig[:-1]) + bytes([sig[-1] ^ 0x01])
        items.append((k.pub_key(), msg, sig))
    expected_mask = [i != BAD_LANE for i in range(N_SIGS)]

    base = "/tmp/cbft-chaos-ha-%d-%d" % (seed, os.getpid())

    class _FleetDaemon:
        """One replica: scheduler + service with its OWN serialized
        pool floor (each daemon is its own accelerator) and its own
        hub, like a real verifyd process."""

        def __init__(self, idx: int):
            self.idx = idx
            self.address = "unix://%s-%d.sock" % (base, idx)
            self.hub = TelemetryHub()
            drng = random.Random(seed * 1000 + idx)
            mtx = threading.Lock()
            inner = servicelib.host_row_verifier()

            def floor(rows, _mtx=mtx, _rng=drng, _inner=inner):
                with _mtx:
                    time.sleep(0.004 + 0.008 * _rng.random())
                    return _inner(rows)

            self.sched = VerifyScheduler(
                spec="cpu", flush_us=200, qos="off",
                row_verifier=floor, logger=logger,
            )
            self.service = servicelib.VerifyService(
                self.sched, self.address, telemetry=self.hub,
                auth_key=AUTH_KEY, logger=logger,
            )
            self.running = False

        def start(self):
            self.sched.start()
            self.service.start()
            self.running = True

        def stop(self):
            if not self.running:
                return
            self.running = False
            self.service.stop()
            self.sched.stop()

        def restart(self):
            # a restarted replica is a NEW process: fresh scheduler +
            # service on the same address (stop() already unlinked it)
            self.__init__(self.idx)
            self.start()

    daemons = [_FleetDaemon(i) for i in range(replicas)]
    for d in daemons:
        d.start()
    addresses = [d.address for d in daemons]

    client_hub = TelemetryHub()
    hv = halib.HAVerifier(
        addresses, tenant="committee", timeout_ms=TIMEOUT_MS,
        connect_timeout_s=0.5, retry_s=0.05, retry_cap_s=2.0,
        auth_key=AUTH_KEY, node_id="committee",
        probe_base_s=PROBE_BASE_S, probe_cap_s=PROBE_CAP_S,
        seed=seed, telemetry=client_hub, logger=logger,
    )
    rv_by_addr = dict(hv.endpoints())

    # background committee load: every future tagged with the phase it
    # was submitted in, resolved and classified at the end
    phase = {"name": "baseline"}
    load_records: List[tuple] = []
    load_mtx = threading.Lock()
    stop_load = threading.Event()

    def loader():
        while not stop_load.is_set():
            tag = phase["name"]
            fut = hv.submit(items, subsystem="consensus")
            with load_mtx:
                load_records.append((tag, fut))
            time.sleep(0.01)

    def _submit_ok(timeout=20.0):
        fut = hv.submit(items, subsystem="consensus")
        ok, mask = fut.result(timeout=timeout)
        return fut, ok, mask

    wrong = {"baseline": 0, "rolling": 0, "kill": 0, "blackhole": 0,
             "auth": 0, "throughput": 0, "load": 0}
    cpu_fallbacks_by_phase = {k: 0 for k in wrong}
    failover_reasons: dict = {}
    rolling_failovers = 0
    blackhole_quarantined = False
    quarantine_picks_leaked = 0

    load_pool = [
        threading.Thread(target=loader, daemon=True)
        for _ in range(load_threads)
    ]
    try:
        for t in load_pool:
            t.start()

        # -- baseline: all replicas healthy -----------------------------
        for _ in range(20):
            fut, ok, mask = _submit_ok()
            if mask != expected_mask:
                wrong["baseline"] += 1
            if getattr(fut, "reason", None) not in (None, "failover"):
                cpu_fallbacks_by_phase["baseline"] += 1

        # -- phase 1: rolling drain-restart -----------------------------
        phase["name"] = "rolling"
        rolling_readmits = 0
        for d in daemons:
            ep_rv = rv_by_addr[d.address]
            # silent drain: no FT_DRAINING broadcast, so the NEXT frame
            # the client sends here is answered typed ST_DRAINING and
            # must fail over — the deterministic per-request path
            d.service.drain(broadcast=False)
            # the draining failover may land on this thread OR on a
            # background loader — either way it shows in the fleet-wide
            # counter, which is what the invariant is about
            fo_before = hv.stats().get("failovers", 0)
            for _ in range(80):
                fut, ok, mask = _submit_ok()
                r = getattr(fut, "reason", None)
                if mask != expected_mask:
                    wrong["rolling"] += 1
                if r is not None and r != "failover":
                    cpu_fallbacks_by_phase["rolling"] += 1
                if hv.stats().get("failovers", 0) > fo_before \
                        and ep_rv.server_draining:
                    break
            rolling_failovers += \
                hv.stats().get("failovers", 0) - fo_before
            # broadcast so every attached client routes around, answer
            # the in-flight tail, then the replica goes down for real
            d.service.drain()
            deadline = time.monotonic() + 10.0
            while d.service.pending_requests() > 0 \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            d.stop()
            d.restart()
            # the endpoint re-enters rotation ONLY via its health probe
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                if not ep_rv.server_draining \
                        and hv.endpoint_state(d.address) == halib.HEALTHY:
                    rolling_readmits += 1
                    break
                time.sleep(0.02)

        # -- phase 2: hard kill -----------------------------------------
        phase["name"] = "kill"
        victim = daemons[rng.randrange(replicas)]
        victim_rv = rv_by_addr[victim.address]
        # make sure the victim has live traffic to sever
        for _ in range(10):
            fut, ok, mask = _submit_ok()
            if mask != expected_mask:
                wrong["kill"] += 1
        failovers_before_kill = hv.stats().get("failovers", 0)
        victim.stop()
        for _ in range(40):
            fut, ok, mask = _submit_ok()
            r = getattr(fut, "reason", None)
            if mask != expected_mask:
                wrong["kill"] += 1
            if r is not None and r != "failover":
                cpu_fallbacks_by_phase["kill"] += 1
        # the failover gap (submit -> verdict for requests that lost an
        # endpoint mid-flight) comes from the fleet's own samples — the
        # background load absorbs most of the kill, not this thread.
        # Snapshot BEFORE the blackhole phase, whose probe-quarantine
        # waits would otherwise pollute the p99.
        kill_failovers = hv.stats().get("failovers", 0) \
            - failovers_before_kill
        gap_p99 = hv.gap_p99_ms() or 0.0
        kill_attributed = victim_rv.stats().get("disconnected", 0)
        victim.restart()
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if hv.endpoint_state(victim.address) == halib.HEALTHY \
                    and not victim_rv.server_draining:
                break
            time.sleep(0.02)

        # -- phase 3: blackhole partition -------------------------------
        phase["name"] = "blackhole"
        hole = daemons[(daemons.index(victim) + 1) % replicas]
        hole_rv = rv_by_addr[hole.address]
        hole.stop()
        hole_path = hole.address[len("unix://"):]
        black_sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        black_sock.bind(hole_path)
        black_sock.listen(16)
        black_conns: List[socket.socket] = []
        stop_hole = threading.Event()

        def _blackhole():
            # accept, read, never answer: the partitioned-replica model
            while not stop_hole.is_set():
                try:
                    c, _ = black_sock.accept()
                except OSError:
                    return
                black_conns.append(c)
        hole_t = threading.Thread(target=_blackhole, daemon=True)
        hole_t.start()

        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            fut, ok, mask = _submit_ok(timeout=30.0)
            r = getattr(fut, "reason", None)
            if mask != expected_mask:
                wrong["blackhole"] += 1
            if r is not None and r != "failover":
                cpu_fallbacks_by_phase["blackhole"] += 1
            if hv.endpoint_state(hole.address) == halib.BROKEN:
                blackhole_quarantined = True
                break
        # with auth on, a blackholed endpoint is a no-HELLO connect —
        # "disconnected"-shaped, never a request-timeout wait; the
        # probe's own failures escalate it to BROKEN even when healthy
        # peers keep it out of the live pick rotation
        hole_strikes = hole_rv.stats().get("disconnected", 0) \
            + hole_rv.stats().get("timeout", 0)
        # quarantine: a BROKEN endpoint gets zero picks from live traffic
        picks_before = [
            e for e in hv.snapshot()["endpoints"]
            if e["address"] == hole.address
        ][0]["picks"]
        for _ in range(15):
            fut, ok, mask = _submit_ok()
            if mask != expected_mask:
                wrong["blackhole"] += 1
        picks_after = [
            e for e in hv.snapshot()["endpoints"]
            if e["address"] == hole.address
        ][0]["picks"]
        quarantine_picks_leaked = picks_after - picks_before
        # heal the partition: real daemon back on the same address; the
        # breaker must be re-opened by the PROBE, not by traffic
        stop_hole.set()
        try:
            black_sock.close()
        except OSError:
            pass
        for c in black_conns:
            try:
                c.close()
            except OSError:
                pass
        hole_t.join(timeout=5.0)
        hole.restart()
        probe_readmitted = False
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if hv.endpoint_state(hole.address) == halib.HEALTHY:
                probe_readmitted = True
                break
            time.sleep(0.02)
        readmissions = hv.stats().get("probe_readmissions", 0)

        # -- phase 4: wrong-key client ----------------------------------
        phase["name"] = "auth"
        evil = halib.HAVerifier(
            addresses, tenant="evil", timeout_ms=TIMEOUT_MS,
            connect_timeout_s=0.5, retry_s=0.05, retry_cap_s=2.0,
            auth_key=b"not-the-key", node_id="evil",
            probe_base_s=PROBE_BASE_S, probe_cap_s=PROBE_CAP_S,
            seed=seed + 1, logger=logger,
        )
        evil_unauthorized = 0
        try:
            for _ in range(6):
                fut = evil.submit(items, subsystem="consensus")
                ok, mask = fut.result(timeout=20.0)
                if mask != expected_mask:
                    wrong["auth"] += 1
                if getattr(fut, "reason", None) == "unauthorized":
                    evil_unauthorized += 1
            evil_attempts = sum(
                rv.stats().get("connect_attempts", 0)
                for _, rv in evil.endpoints()
            )
        finally:
            evil.close()
        server_auth_rejects = sum(
            d.service.snapshot().get("auth_rejects", 0) for d in daemons
        )
        evil_served = sum(
            (d.service.snapshot().get("tenants_panel", {})
             .get("evil", {}) or {}).get("requests", 0)
            for d in daemons
        )

        # -- phase 5: aggregate throughput vs single daemon -------------
        phase["name"] = "throughput"
        stop_load.set()
        for t in load_pool:
            t.join(timeout=30.0)

        def _pump(backend, rounds):
            errs = 0
            done = [0]

            def w():
                for _ in range(rounds):
                    f = backend.submit(items, subsystem="consensus")
                    ok, mask = f.result(timeout=30.0)
                    if mask != expected_mask:
                        errs_l[0] += 1
                    done[0] += 1
            errs_l = [0]
            ths = [threading.Thread(target=w, daemon=True)
                   for _ in range(4)]
            t0 = time.monotonic()
            for t in ths:
                t.start()
            for t in ths:
                t.join(timeout=120.0)
            dt = max(time.monotonic() - t0, 1e-6)
            return done[0] * N_SIGS / dt, errs_l[0]

        fleet_sigs, errs = _pump(hv, 20)
        wrong["throughput"] += errs
        single = servicelib.RemoteVerifier(
            daemons[0].address, tenant="single", timeout_ms=TIMEOUT_MS,
            retry_s=0.05, auth_key=AUTH_KEY, node_id="single",
            logger=logger,
        )
        try:
            single_sigs, errs = _pump(single, 20)
            wrong["throughput"] += errs
        finally:
            single.close()

        # -- resolve the background load --------------------------------
        with load_mtx:
            records = list(load_records)
        load_by_phase: dict = {}
        for tag, fut in records:
            ok, mask = fut.result(timeout=30.0)
            r = getattr(fut, "reason", None)
            rec = load_by_phase.setdefault(
                tag, {"n": 0, "failover": 0, "cpu": 0}
            )
            rec["n"] += 1
            if mask != expected_mask:
                wrong["load"] += 1
            if r == "failover":
                rec["failover"] += 1
            elif r is not None:
                rec["cpu"] += 1
                cpu_fallbacks_by_phase[tag] = \
                    cpu_fallbacks_by_phase.get(tag, 0) + 1
        for _, rv in hv.endpoints():
            for reason, n in rv.stats().items():
                if reason in servicelib.FAILOVER_REASONS:
                    failover_reasons[reason] = \
                        failover_reasons.get(reason, 0) + n
        hv_stats = hv.stats()
    finally:
        stop_load.set()
        hv.close()
        for d in daemons:
            d.stop()
        for i in range(replicas):
            try:
                os.unlink("%s-%d.sock" % (base, i))
            except OSError:
                pass

    summary = {
        "seed": seed,
        "replicas": replicas,
        "wrong_verdicts": sum(wrong.values()),
        "wrong_by_phase": wrong,
        "rolling_failovers": rolling_failovers,
        "rolling_readmits": rolling_readmits,
        "rolling_cpu_fallbacks": cpu_fallbacks_by_phase["rolling"],
        "cpu_fallbacks_by_phase": cpu_fallbacks_by_phase,
        "kill_failovers": kill_failovers,
        "kill_attributed_disconnects": kill_attributed,
        "failover_gap_p99_ms": round(gap_p99, 2),
        "failover_gap_bound_ms": GAP_BOUND_MS,
        "blackhole_quarantined": blackhole_quarantined,
        "blackhole_strikes": hole_strikes,
        "quarantine_picks_leaked": quarantine_picks_leaked,
        "probe_readmitted": probe_readmitted,
        "probe_readmissions": readmissions,
        "failover_reasons": failover_reasons,
        "evil_unauthorized": evil_unauthorized,
        "evil_connect_attempts": evil_attempts,
        "server_auth_rejects": server_auth_rejects,
        "evil_requests_served": evil_served,
        "load_by_phase": load_by_phase,
        "fleet_sigs_per_sec": round(fleet_sigs, 1),
        "single_sigs_per_sec": round(single_sigs, 1),
        "fleet_gain": round(fleet_sigs / max(single_sigs, 1e-6), 2),
        "ha_stats": hv_stats,
        "expected": {
            "wrong_verdicts": 0,
            "rolling_failovers": ">= %d" % replicas,
            "rolling_cpu_fallbacks": 0,
            "rolling_readmits": replicas,
            "kill_failovers": ">= 1",
            "kill_attributed_disconnects": ">= 1",
            "failover_gap_p99_ms": "<= %.0f" % GAP_BOUND_MS,
            "blackhole_quarantined": True,
            "quarantine_picks_leaked": 0,
            "probe_readmitted": True,
            "probe_readmissions": ">= 1",
            "failover_reasons": "draining >= %d, disconnected >= 1"
                                % replicas,
            "evil_unauthorized": ">= 1",
            "server_auth_rejects": ">= 1",
            "evil_requests_served": 0,
        },
    }
    return summary
