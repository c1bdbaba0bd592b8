"""Batch signature verification — THE plugin boundary this framework
introduces.

The v0.34 reference has no crypto/batch package: every hot path
(types/validator_set.go:685-823 VerifyCommit*, types/vote_set.go:205 addVote,
light/verifier.go:58-126, blockchain/v0/reactor.go:366) loops over
PubKey.VerifySignature one signature at a time. Here those call sites route
through a BatchVerifier selected by config ``[crypto] backend = "cpu"|"tpu"``.

Semantics contract: verify() returns (all_ok, per_sig_mask) with accept/
reject per signature bit-identical to the serial VerifySignature calls.
"""

from __future__ import annotations

import hashlib
import os
import sys
import threading
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

from cometbft_tpu.crypto import PubKey
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import wire as wirelib
from cometbft_tpu.libs import trace as tracelib


@dataclass(frozen=True)
class BackendSpec:
    """A backend selection PLUS its per-node [crypto] tuning, threaded
    through the same parameter the bare backend name travels (reactors
    and verifiers pass it opaquely; only this module resolves it).
    Replaces the round-5 os.environ.setdefault plumbing, which made
    in-process multi-node setups share the FIRST node's min_batch.

    min_batch/max_chunk of None mean "not configured" — resolution
    falls through to env → calibration → built-in default."""

    name: str
    min_batch: Optional[int] = None
    max_chunk: Optional[int] = None


# what every verify path accepts where a backend used to be a str: a
# bare name, a BackendSpec, the node's VerifyScheduler (duck-typed:
# anything exposing .submit + .spec — crypto/scheduler.py), which
# coalesces concurrent callers into one dispatch, or a
# BackendSupervisor (.verify_items + .spec — crypto/supervisor.py),
# which adds the watchdog / circuit breaker / corruption audit
Backend = Union[str, BackendSpec, None, object]


def unwrap_backend(backend: Backend) -> Union[str, BackendSpec, None]:
    """A scheduler or supervisor travels the same opaque parameter a
    backend name does; every eligibility/floor check resolves against
    its spec."""
    if hasattr(backend, "submit") and hasattr(backend, "spec"):
        return backend.spec
    if hasattr(backend, "verify_items") and hasattr(backend, "spec"):
        return backend.spec
    return backend


def backend_name(backend: Backend) -> str:
    backend = unwrap_backend(backend)
    if isinstance(backend, BackendSpec):
        return backend.name
    return backend or _default_backend


def resolved_device_plane() -> Optional[dict]:
    """What jax gave this process (platform, device kind and count,
    runtime versions, compile cache — crypto/tpu/mesh.device_plane), or
    None while nothing has resolved it. For snapshots (/debug/verify,
    the verifyd panel): never imports the tpu package, never starts a
    jax backend."""
    meshlib = sys.modules.get("cometbft_tpu.crypto.tpu.mesh")
    return meshlib.resolved_plane() if meshlib is not None else None


def ed25519_routing_floor(config_min_batch: Optional[int] = None) -> int:
    """THE resolution of the ed25519 CPU↔device crossover, shared by
    every eligibility check (TPUBatchVerifier partitioning, the resident
    commit path, warmup bucket selection) so they can never diverge:

      CBFT_TPU_MIN_BATCH env (operator A/B override)
      > configured [crypto] min_batch (via BackendSpec)
      > measured crossover recorded at warmup (tpu/calibrate.py)
      > 1024 (the conservative constant from the round-5 on-chip sweep)
    """
    raw = os.environ.get("CBFT_TPU_MIN_BATCH")
    if raw is not None:
        return int(raw)
    if config_min_batch is not None:
        return config_min_batch
    from cometbft_tpu.crypto.tpu import calibrate

    measured = calibrate.ed25519_min_batch()
    if measured is not None:
        return measured
    return 1024


class BatchVerifier:
    """Interface (new; upstream cometbft >= v0.35 has an analogous shape).

    Two ways in, one verdict: ``add`` x n + ``verify`` (a caller that
    meets its lanes one at a time), and ``verify_many(items)`` (a caller
    that already holds the flush: the scheduler's coalesced triples, the
    supervisor's dispatch worker). Who copies a lane's bytes: ``add``
    normalises msg and sig to ``bytes`` and keeps the triple; the bulk
    entry takes the triples AS THEY ARE (``VerifyScheduler.submit`` made
    them ``bytes`` at admission; a lane that is not is normalised as
    ``add`` would) and copies none, so a lane's bytes are copied once,
    where the backend packs its launch."""

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        raise NotImplementedError

    def count(self) -> int:
        raise NotImplementedError

    def verify(self) -> Tuple[bool, List[bool]]:
        """Returns (all_valid, per-entry validity mask) and resets the batch."""
        raise NotImplementedError

    def verify_many(
        self, items: Sequence[Tuple[PubKey, bytes, bytes]]
    ) -> Tuple[bool, List[bool]]:
        """The bulk entry: ``verify()`` of ``items`` (after whatever
        ``add`` already collected). Here it IS ``add`` x n + ``verify``,
        so a backend that overrides those (or reads ``count()`` in its
        ``verify``) sees what it always saw; a backend that can take the
        flush in one pass overrides it (TPUBatchVerifier)."""
        for pk, m, s in items:
            self.add(pk, m, s)
        return self.verify()


def verify_flush(
    bv, items: Sequence[Tuple[PubKey, bytes, bytes]]
) -> Tuple[bool, List[bool]]:
    """Drive ANY verifier over a flush's triples: through its bulk entry
    where it has one, else lane by lane (a duck-typed double with only
    ``add`` / ``verify``)."""
    bulk = getattr(bv, "verify_many", None)
    if bulk is None:
        return BatchVerifier.verify_many(bv, items)
    return bulk(items)


class CPUBatchVerifier(BatchVerifier):
    """CPU fallback — semantics ground truth.

    Ed25519 entries go through ed25519.verify_many, which uses one
    native multi-threaded call on multicore hosts (the `cryptography`
    wheel holds the GIL during verify, so Python threads cannot scale
    this loop — measured; see cometbft_tpu/native/ed25519_batch.c) and a
    cached-handle tight loop otherwise. Other key types verify serially.
    """

    def __init__(self):
        self._items: List[Tuple[PubKey, bytes, bytes]] = []

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key is None:
            raise ValueError("nil pubkey")
        self._items.append((pub_key, bytes(msg), bytes(sig)))

    def count(self) -> int:
        return len(self._items)

    def verify(self) -> Tuple[bool, List[bool]]:
        items, self._items = self._items, []
        if not items:
            return False, []
        mask: List[Optional[bool]] = [None] * len(items)
        ed_idxs = [
            i for i, (pk, _, _) in enumerate(items)
            if isinstance(pk, ed.PubKeyEd25519)
        ]
        if ed_idxs:
            ed_mask = ed.verify_many([items[i] for i in ed_idxs])
            for j, i in enumerate(ed_idxs):
                mask[i] = ed_mask[j]
        for i, (pk, msg, sig) in enumerate(items):
            if mask[i] is None:
                mask[i] = pk.verify_signature(msg, sig)
        final = [bool(m) for m in mask]
        return all(final), final


def curve_floors(
    min_batch: Optional[int] = None,
    secp_min_batch: Optional[int] = None,
    slow_curve_min_batch: Optional[int] = None,
) -> Dict[str, int]:
    """Per-curve CPU↔device routing floors, scaled to the speed of each
    curve's CPU fallback: ed25519 through ed25519_routing_floor (1024 by
    default — the round-5 on-chip crossover, SMALLBATCH_onchip.jsonl),
    secp256k1 256 (CBFT_TPU_SECP_MIN_BATCH; set at 128 from the ed25519
    crossover scaled by the two CPU rates, OpenSSL ECDSA ~3.7k sigs/s on
    the host, BENCH_NOTES.md "Per-curve routing floors", then doubled
    with ed25519's 512 -> 1024 for the ~70 ms dispatch floor of round
    5's slow sessions, commit 75c491a; no chip run has measured a
    secp256k1 crossover), sr25519 4 (pure-Python fallback, ~ms/sig — the
    device wins almost at once).
    THE one table: TPUBatchVerifier partitions by it and the scheduler's
    router reads it (clears_device_floor), so a flush the floor keeps on the
    host is routed and counted as ``cpu``, not as a device dispatch."""
    from cometbft_tpu.crypto import secp256k1 as secp
    from cometbft_tpu.crypto import sr25519 as sr

    if min_batch is None:
        min_batch = ed25519_routing_floor()
    if secp_min_batch is None:
        secp_min_batch = int(
            os.environ.get("CBFT_TPU_SECP_MIN_BATCH", "256")
        )
    if slow_curve_min_batch is None:
        slow_curve_min_batch = int(
            os.environ.get("CBFT_TPU_SLOW_CURVE_MIN_BATCH", "4")
        )
    return {
        ed.KEY_TYPE: min_batch,
        secp.KEY_TYPE: secp_min_batch,
        sr.KEY_TYPE: slow_curve_min_batch,
    }


def clears_device_floor(pub_keys, backend: Backend = None) -> bool:
    """Would the ``tpu`` backend put ANY of these keys' lanes on the
    device — does some curve partition reach its floor? False = the
    whole flush verifies on the host. Stops at the first partition that
    clears, so a large flush costs one floor's worth of type reads."""
    spec = unwrap_backend(backend)
    floors = curve_floors(
        ed25519_routing_floor(spec.min_batch)
        if isinstance(spec, BackendSpec) else None
    )
    counts: Dict[str, int] = {}
    for pk in pub_keys:
        t = pk.type()
        c = counts[t] = counts.get(t, 0) + 1
        if t in floors and c >= floors[t]:
            return True
    return False


class TPUBatchVerifier(BatchVerifier):
    """Partitions the batch by curve (SURVEY.md §7 stage 10): ed25519,
    secp256k1, and sr25519 entries each go to their own batch kernel;
    anything else falls back to serial CPU verification in place. Each
    partition applies its own routing floor (curve_floors): below it the
    device dispatch + host packing dominates and the CPU path is simply
    faster, so small commits (150 validators) verify on the CPU even
    under the "tpu" backend — the hybrid IS the design, the device earns
    its round trip only at scale. ``host_lanes``/``device_lanes`` say
    where the last flush's lanes actually ran, ``single_curve`` whether
    it was one curve (then no partition was built)."""

    def __init__(
        self,
        min_batch: Optional[int] = None,
        slow_curve_min_batch: Optional[int] = None,
        secp_min_batch: Optional[int] = None,
    ):
        # fail fast if a kernel module is unavailable rather than erroring
        # mid-verify after add() calls succeeded (imports are host-only:
        # no backend init — see field.const_fe)
        from cometbft_tpu.crypto.tpu import (  # noqa: F401
            ed25519_batch,
            secp256k1_batch,
            sr25519_batch,
        )

        self._items: List[Tuple[PubKey, bytes, bytes]] = []
        self._floors = curve_floors(
            min_batch, secp_min_batch, slow_curve_min_batch
        )
        self.host_lanes = 0
        self.device_lanes = 0
        self.single_curve = False

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key is None:
            raise ValueError("nil pubkey")
        self._items.append((pub_key, bytes(msg), bytes(sig)))

    def count(self) -> int:
        return len(self._items)

    def verify(self) -> Tuple[bool, List[bool]]:
        items, self._items = self._items, []
        return self.verify_many(items)

    def verify_many(
        self, items: Sequence[Tuple[PubKey, bytes, bytes]]
    ) -> Tuple[bool, List[bool]]:
        """ONE pass from the flush's triples to the kernels' columns,
        under the stage ``sup.columns`` (tags ``lanes``, ``single_curve``)
        on whichever thread drives the verifier: the supervisor's
        dispatch worker in a node. The columns are ``zip(*items)``; which
        curves the flush holds is read from the set of the keys' classes
        (a key's curve is its class's), so a one-curve flush (every
        flush of an ed25519 chain) builds no index list and gathers
        nothing, and a mixed one is partitioned from the same columns.
        Nothing is copied here but a device partition's key bytes: the
        lanes' msg / sig objects go to the kernel entry as they are. A
        ``None`` key raises before anything is dispatched; lengths and
        s < L are the kernel entry's check (``_parse_inputs``)."""
        if self._items:
            items, self._items = self._items + list(items), []
        n = len(items)
        if n == 0:
            return False, []
        floors = self._floors

        def part(curve, idxs, ks, ms, ss):
            # a partition that clears its curve's floor goes to the device
            # and ships its keys as bytes; the rest stays on the host
            on_device = curve in floors and len(ks) >= floors[curve]
            return (curve, idxs, ks, ms, ss,
                    [k.bytes() for k in ks] if on_device else None)

        columns = tracelib.stage("sup.columns", lanes=n)
        with columns as span:
            keys, msgs, sigs = zip(*items)
            kinds = dict(zip(map(type, keys), keys))  # class -> one key
            if type(None) in kinds:
                raise ValueError("nil pubkey")
            if set(map(type, msgs)) | set(map(type, sigs)) != {bytes}:
                # what add() does a lane at a time, for a caller that
                # hands in bytearrays or views
                msgs, sigs = tuple(map(bytes, msgs)), tuple(map(bytes, sigs))
            curves = {k.type() for k in kinds.values()}
            self.single_curve = len(curves) == 1
            span.set_tag("single_curve", int(self.single_curve))
            if self.single_curve:
                parts = [part(curves.pop(), None, keys, msgs, sigs)]
            else:
                by_curve: Dict[str, List[int]] = {c: [] for c in floors}
                for i, k in enumerate(keys):
                    by_curve.setdefault(k.type(), []).append(i)
                parts = [
                    part(
                        curve, idxs,
                        [keys[i] for i in idxs],
                        [msgs[i] for i in idxs],
                        [sigs[i] for i in idxs],
                    )
                    for curve, idxs in by_curve.items() if idxs
                ]
        # the flush record's columns phase, inside its lead
        wirelib.add_phase("columns", columns.seconds)
        mask: List[bool] = [False] * n
        self.device_lanes = 0
        for curve, idxs, ks, ms, ss, pk_bytes in parts:
            if pk_bytes is None:
                sub = _verify_on_host(curve, ks, ms, ss)
            else:
                self.device_lanes += len(ks)
                sub = _verify_on_device(curve, pk_bytes, ms, ss)
            if len(sub) != len(ks):
                raise RuntimeError(
                    f"{curve} partition returned {len(sub)} verdicts for "
                    f"{len(ks)} lanes"
                )
            if idxs is None:
                mask = sub
            else:
                for i, ok in zip(idxs, sub):
                    mask[i] = ok
        self.host_lanes = n - self.device_lanes
        return all(mask), mask


def _verify_on_host(curve: str, keys, msgs, sigs) -> List[bool]:
    """A partition its floor keeps off the device (or of no batch
    curve): ed25519 through the host pool's one native call, any other
    key serially. Booked in the wire ledger's book by curve."""
    ledger = wirelib.default_ledger()
    if ledger is not None:
        ledger.note_host_lanes(curve, len(keys))
    if curve == ed.KEY_TYPE:
        return list(map(bool, ed.verify_many(list(zip(keys, msgs, sigs)))))
    return [
        bool(pk.verify_signature(m, s)) for pk, m, s in zip(keys, msgs, sigs)
    ]


def _verify_on_device(curve: str, pk_bytes, msgs, sigs) -> List[bool]:
    """A partition that cleared its floor, as columns, to its curve's
    kernel entry."""
    from cometbft_tpu.crypto import secp256k1 as secp

    ok = None
    if curve == ed.KEY_TYPE:
        from cometbft_tpu.crypto.tpu import ed25519_batch as kernel
        # steady-state flushes against a resident valset ship an index
        # vector instead of the pubkeys (100 B/lane vs 128 —
        # crypto/tpu/keystore.py); None = no fresh entry covers the
        # flush, fall through to the full wire
        from cometbft_tpu.crypto.tpu import keystore

        ok = keystore.verify_batch_indexed(pk_bytes, msgs, sigs)
    elif curve == secp.KEY_TYPE:
        from cometbft_tpu.crypto.tpu import secp256k1_batch as kernel
    else:
        from cometbft_tpu.crypto.tpu import sr25519_batch as kernel
    if ok is None:
        ok = kernel.verify_batch(pk_bytes, msgs, sigs)
    return list(map(bool, ok))


def resident_commit_eligible(
    n_present: int, backend: Backend = None
) -> bool:
    """Cheap pre-check for the resident commit path, so callers on the
    cpu backend (or below the floor) never pay for the set's curve index
    (CurveIndex) that verify_commit_valset needs."""
    if backend_name(backend) != "tpu":
        return False
    spec = unwrap_backend(backend)
    spec_floor = spec.min_batch if isinstance(spec, BackendSpec) else None
    return n_present >= ed25519_routing_floor(spec_floor)


class CurvePart:
    """The seats of one key type in a validator set: ``seats`` (set
    positions, ascending; None: every seat), their keys and key bytes in
    that order, and ``valset_id``, the sha256 of the key bytes joined:
    the id the resident rows of those keys are stored under."""

    __slots__ = ("curve", "seats", "keys", "key_bytes", "valset_id")

    def __init__(self, curve: str, seats: Optional[List[int]], keys):
        self.curve = curve
        self.seats = seats
        self.keys = keys
        self.key_bytes = [k.bytes() for k in keys]
        self.valset_id = hashlib.sha256(b"".join(self.key_bytes)).digest()


class CurveIndex:
    """A validator set's seats by key type, made once per set
    (ValidatorSet.curve_index) rather than scanned every commit. ``of``
    is the validator list it was made from; ``single`` the one key type
    of a one-curve set (its one part has ``seats`` None), else None."""

    __slots__ = ("of", "parts", "single")

    def __init__(self, validators: list):
        self.of = validators
        by_curve: Dict[str, List[int]] = {}
        for i, v in enumerate(validators):
            by_curve.setdefault(v.pub_key.type(), []).append(i)
        self.single = next(iter(by_curve)) if len(by_curve) == 1 else None
        self.parts = [
            CurvePart(
                curve, None if self.single else seats,
                [validators[i].pub_key for i in seats],
            )
            for curve, seats in by_curve.items()
        ]


# the key types a commit's stream carries on the device
STREAM_CURVES = ("ed25519", "secp256k1")


def verify_commit_valset(
    curves: CurveIndex,
    msgs,
    sigs: List[Optional[bytes]],
    backend: Backend = None,
) -> Optional[List[bool]]:
    """Full-lane commit verification as ONE stream of launches: the
    ed25519 seats against their device-resident key rows
    (ed25519_batch's verify_valset_resident / resident_part), the
    secp256k1 seats on their kernel (secp256k1_batch.stream_part), a
    launch of each in turn (mesh.launch_parts). Returns a per-seat mask,
    or None when the shape is ineligible and the caller should fall back
    to the add()/verify() protocol. ``curves`` is the set's CurveIndex;
    ``msgs`` is one entry per seat, or a callable ``(start, end) ->
    msgs[start:end]`` with ``at(seats)`` for a curve's seats that builds
    a launch's messages when that launch is next (then ``sigs`` says
    which lanes are present).

    Eligibility: the tpu backend is selected, the PRESENT lane count
    clears the ed25519 routing floor (below it the CPU wins the round
    trip regardless — crypto/batch.py min_batch rationale), and every
    key type is one the stream carries. A one-curve ed25519 set takes
    verify_valset_resident whole; in a mixed set a curve whose present
    lanes are under its own floor (curve_floors) is verified on the host
    pool. msgs[i]/sigs[i] None marks an absent lane, reported False and
    skipped by the caller."""
    if backend_name(backend) != "tpu":
        return None
    with tracelib.stage("commit.valset_id"):
        lanes = sigs if callable(msgs) else msgs
        present = sum(1 for m in lanes if m is not None)
        spec = unwrap_backend(backend)
        spec_floor = spec.min_batch if isinstance(spec, BackendSpec) else None
        ed_floor = ed25519_routing_floor(spec_floor)
        if present < ed_floor:
            return None
    from cometbft_tpu.crypto.tpu import ed25519_batch

    if curves.single == ed.KEY_TYPE:
        part = curves.parts[0]
        return ed25519_batch.verify_valset_resident(
            part.valset_id, part.key_bytes, msgs, sigs
        )
    if any(p.curve not in STREAM_CURVES for p in curves.parts):
        return None
    return _verify_commit_mixed(curves, msgs, sigs, curve_floors(ed_floor))


def _verify_commit_mixed(curves: CurveIndex, msgs, sigs, floors):
    """verify_commit_valset of a set whose seats hold more than one key
    type: each curve's seats are a part of one stream, or, under their
    curve's floor, a host-pool partition. → a per-seat mask."""
    from cometbft_tpu.crypto.tpu import ed25519_batch, mesh, secp256k1_batch

    seat_msgs = msgs.at if callable(msgs) else (
        lambda seats: [msgs[i] for i in seats]
    )
    full = [False] * len(sigs)
    on_device, on_host, plan = [], [], None
    for cp in curves.parts:
        part_sigs = [sigs[i] for i in cp.seats]
        present = sum(1 for s in part_sigs if s is not None)
        if present < floors[cp.curve]:
            on_host.append((cp, part_sigs))
            continue

        def part_msgs(start, end, seats=cp.seats):
            return seat_msgs(seats[start:end])

        if cp.curve == ed.KEY_TYPE:
            part, plan = ed25519_batch.resident_part(
                cp.valset_id, cp.key_bytes, part_msgs, part_sigs
            )
            on_device.insert(0, (cp, part))  # the cheaper build leads
        else:
            on_device.append((cp, (cp.key_bytes, part_msgs, part_sigs)))
    if plan is None and on_device:
        plan = mesh.shard_plan()
    nsh = plan.n_shards if plan is not None else 1
    parts = [
        p if isinstance(p, mesh.StreamPart)
        else secp256k1_batch.stream_part(*p, n_shards=nsh)
        for _, p in on_device
    ]
    masks, _ = ed25519_batch.run_commit_parts(parts, plan) if parts else (
        [], None
    )
    for (cp, _), mask in zip(on_device, masks):
        for seat, ok in zip(cp.seats, mask):
            full[seat] = bool(ok)
    for cp, part_sigs in on_host:
        lanes = [k for k, s in enumerate(part_sigs) if s is not None]
        seats = [cp.seats[k] for k in lanes]
        got = _verify_on_host(
            cp.curve, [cp.keys[k] for k in lanes], seat_msgs(seats),
            [part_sigs[k] for k in lanes],
        )
        for seat, ok in zip(seats, got):
            full[seat] = ok
    return full


# ---------------------------------------------------------------------------
# Backend registry + default selection (config [crypto] backend)
# ---------------------------------------------------------------------------

_registry: Dict[str, Callable[[], BatchVerifier]] = {
    "cpu": CPUBatchVerifier,
    "tpu": TPUBatchVerifier,
}
_default_backend = os.environ.get("CMT_CRYPTO_BACKEND", "cpu")
_mtx = threading.Lock()


def register_backend(name: str, factory: Callable[[], BatchVerifier]) -> None:
    with _mtx:
        _registry[name] = factory


def set_default_backend(name: str) -> None:
    global _default_backend
    with _mtx:
        if name not in _registry:
            raise ValueError(f"unknown crypto backend {name!r}")
        _default_backend = name


def default_backend() -> str:
    return _default_backend


class ScheduledBatchVerifier(BatchVerifier):
    """add()/verify() protocol on top of the node-wide VerifyScheduler
    (crypto/scheduler.py): verify() submits the collected items as ONE
    request and blocks on its future, so whatever OTHER subsystems have
    pending rides the same coalesced dispatch — and the TPU/CPU routing
    floor is applied to the coalesced size, not this caller's size.
    Existing call sites get coalescing without code changes the moment
    the node threads its scheduler where the BackendSpec used to go."""

    def __init__(self, scheduler, subsystem: Optional[str] = None):
        self._scheduler = scheduler
        # origin tag: resolves the QoS class and the RED-metering tenant
        # for everything this verifier submits (None = untagged, which
        # maps to the top class — never shed by default)
        self._subsystem = subsystem
        self._items: List[Tuple[PubKey, bytes, bytes]] = []

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key is None:
            raise ValueError("nil pubkey")
        self._items.append((pub_key, bytes(msg), bytes(sig)))

    def count(self) -> int:
        return len(self._items)

    def verify(self) -> Tuple[bool, List[bool]]:
        items, self._items = self._items, []
        if not items:
            return False, []
        return self._scheduler.submit(
            items, subsystem=self._subsystem
        ).result()


def new_batch_verifier(
    backend: Backend = None,
    subsystem: Optional[str] = None,
    force_device: bool = False,
) -> BatchVerifier:
    """``force_device`` lifts the tpu backend's routing floors for this
    verifier: the supervisor's canary and triage passes exist to judge
    the DEVICE, and a handful of lanes left to the floor would be judged
    on the host instead."""
    if hasattr(backend, "submit") and hasattr(backend, "spec"):
        return ScheduledBatchVerifier(backend, subsystem=subsystem)
    if hasattr(backend, "verify_items") and hasattr(backend, "spec"):
        # a bare BackendSupervisor (no scheduler in front): dispatches
        # still get the watchdog / breaker / audit treatment
        from cometbft_tpu.crypto.supervisor import SupervisedBatchVerifier

        return SupervisedBatchVerifier(backend)
    with _mtx:
        name = backend_name(backend)
        factory = _registry.get(name)
    if factory is None:
        raise ValueError(f"unknown crypto backend {name!r}")
    if force_device and factory is TPUBatchVerifier:
        return TPUBatchVerifier(
            min_batch=0, slow_curve_min_batch=0, secp_min_batch=0
        )
    if isinstance(backend, BackendSpec) and factory is TPUBatchVerifier:
        # per-node config reaches the verifier through the spec, not a
        # process-global env default (env still wins inside the floor
        # resolution for operator overrides)
        return TPUBatchVerifier(
            min_batch=ed25519_routing_floor(backend.min_batch)
        )
    return factory()


def supports_batch_verification(pub_key: PubKey) -> bool:
    from cometbft_tpu.crypto import secp256k1 as secp
    from cometbft_tpu.crypto import sr25519 as sr

    return pub_key.type() in (ed.KEY_TYPE, secp.KEY_TYPE, sr.KEY_TYPE)
