"""Generational device key store — the PR 1 resident valset cache
grown into a device-side pubkey TABLE shared by scheduler flushes.

Two consumers, one store:

* `verify_valset_resident` (full-lane commit verification) keeps its
  chunked resident rows — those live in each entry's ``chunks`` exactly
  as the old ``_ResidentValset`` held them, so the dispatch layout and
  the adopt-the-race-winner contract are unchanged.
* The NEW indexed batch path (`verify_batch_indexed`): when every
  pubkey of an ed25519 flush is already resident, steady-state
  consensus traffic ships only msgs+sigs and an int32 index vector —
  100 B/lane (96 B compact R ‖ S ‖ h + 4 B index) instead of re-shipping
  32-byte keys every flush. The kernel gathers pubkey rows from the
  on-device table (`ed25519_batch.verify_kernel_indexed`).

Generations make staleness impossible to verify against: every entry
is stamped with its upload's sequence number and the device-topology
generation it was built under. A valset rotation produces a different
valset_id (miss), an explicit `invalidate` drops entries, and a
topology generation bump — quarantine re-slice, fault-domain change —
makes every older entry undispatchable: `get` and `register` drop it
and rebuild, `verify_batch_indexed` and `entry_for` refuse it
(`stale_drops`). A stale-generation dispatch therefore MISSES; it never
verifies against old keys or an old device slicing.

The STORE generation (`generation()`, the verify service's handshake
token) counts what can make a registration somebody holds wrong: every
entry that LEAVES the store (invalidation, LRU eviction, a topology-
stale drop). An insert does not move it: valset ids are content-
addressed, so new keys beside a client's cannot change what its id
names, and 32 light clients on 8 chains re-registered for ever while
every insert staled every client (PERF.md, PR 30).

Two bounds, by what an entry holds: CACHE_MAX entries with rows on the
device (the 10,000-validator resident sets, ~2.5 MB of HBM each), and
HOST_KEYS_MAX key rows over the service's host-only registrations
(4.8 KB a 150-validator chain), each evicted LRU within its own kind.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from contextlib import contextmanager
from typing import List, Optional, Sequence

import numpy as np

# ~10k vals x 256B x 4 = 10 MB of HBM at most (chunks) plus the
# indexed tables (32 B/key) on top — still < 2 MB per 10k-val entry.
# Counts the entries that hold device rows.
CACHE_MAX = 4
# key rows over all host-only registrations (service.MAX_REGISTER_KEYS a
# frame, so CACHE_MAX of the largest): 2 MB of rows and their indexes,
# or 436 chains of 150 validators
HOST_KEYS_MAX = CACHE_MAX * 16_384


class KeyStoreEntry:
    """One resident valset. ``chunks``/``pk_arr``/``pk_ok`` carry the
    exact _ResidentValset layout (tests and verify_valset_resident
    address them directly); the table/index pair is the indexed path's
    view of the same keys."""

    __slots__ = (
        "valset_id",       # bytes digest the caller keyed this set by
        "generation",      # store generation at upload (monotonic)
        "topo_generation",  # device-topology generation at build
        "chunks",          # list[(start, end, size, a_dev)] — resident rows
        "plan",            # mesh.ShardPlan the rows are sharded over, or None
        "pk_arr",          # np.uint8[n, 32] host copy of the key rows
        "pk_ok",           # np.bool_[n] — False for malformed keys
        "index",           # dict: pubkey bytes -> row in table_dev
        "table_dev",       # device u8[n_pad, 32] gather table
        "n",               # live key count
        "hits",            # uses since upload (0 at eviction = thrash)
        "pins",            # in-flight dispatches holding LRU immunity
    )


def _topo_generation() -> int:
    from cometbft_tpu.crypto.tpu import topology

    return topology.default_topology().generation()


def _key_bytes(pk) -> bytes:
    """Normalize one pubkey to its raw 32 bytes. The scheduler's
    feasibility probe and the supervisor's indexed dispatch hand the
    flush items' PubKey OBJECTS straight through, while batch.py and
    the tests pass raw bytes — the store accepts both (``bytes(obj)``
    on a PubKey raises TypeError, which the callers' advisory
    try/excepts would silently turn into "never indexed")."""
    if isinstance(pk, (bytes, bytearray, memoryview)):
        return bytes(pk)
    b = getattr(pk, "bytes", None)
    if callable(b):
        return b()
    return bytes(pk)


class DeviceKeyStore:
    def __init__(self, max_entries: int = CACHE_MAX):
        self._entries: "OrderedDict[bytes, KeyStoreEntry]" = OrderedDict()
        # verify_commit runs from consensus, blocksync, AND light
        # threads concurrently; the OrderedDict get/move/insert/evict
        # triad is not atomic, so every store touch takes this lock.
        # Slow work (build + H2D upload) runs OUTSIDE it; a lost build
        # race adopts the winner's rows.
        self._mtx = threading.Lock()
        self._max = int(max_entries)
        self._max_host_keys = HOST_KEYS_MAX
        self._gen = 0
        self._stats = {
            "hits": 0,
            "misses": 0,
            "uploads": 0,
            "invalidations": 0,
            "stale_drops": 0,
            "indexed_dispatches": 0,
            "indexed_lanes": 0,
            "evictions": 0,
            # LRU evictions of entries that never served a single use:
            # the churn-thrash signal (valsets rotating faster than
            # flushes drain the cache)
            "keystore_thrash": 0,
        }

    @staticmethod
    def _host_only(e: KeyStoreEntry) -> bool:
        return (getattr(e, "table_dev", None) is None
                and not getattr(e, "chunks", None))

    def _removed_locked(self, n: int = 1) -> None:
        """``n`` entries left the store: whoever holds a registration
        from before must resync (the handshake's generation)."""
        self._gen += n

    def _evict_excess_locked(self) -> None:
        """LRU eviction, each kind within its own bound (device entries
        by count, host-only registrations by key rows), that honors
        pins: an in-flight indexed dispatch pins its entry, so
        per-height valset rotation can never yank the incoming table out
        from under a flush mid-dispatch. If every entry of a kind is
        pinned it overflows temporarily (unpin resumes eviction). An
        evicted entry that never served a hit counts as
        ``keystore_thrash``."""
        device = host_keys = 0
        for e in self._entries.values():
            if self._host_only(e):
                host_keys += getattr(e, "n", 0)
            else:
                device += 1
        while device > self._max or host_keys > self._max_host_keys:
            over_host = host_keys > self._max_host_keys
            victim_id = None
            for vid, e in self._entries.items():  # oldest first
                if getattr(e, "pins", 0) > 0:
                    continue
                if over_host if self._host_only(e) else device > self._max:
                    victim_id = vid
                    break
            if victim_id is None:
                return
            e = self._entries.pop(victim_id)
            if self._host_only(e):
                host_keys -= getattr(e, "n", 0)
            else:
                device -= 1
            self._stats["evictions"] += 1
            if getattr(e, "hits", 0) == 0:
                self._stats["keystore_thrash"] += 1
            self._removed_locked()

    def get(self, valset_id: bytes, pub_keys, build) -> KeyStoreEntry:
        """Resident entry for valset_id, building (slow H2D, outside the
        lock) on miss. An entry built under an older topology generation
        is dropped and rebuilt — its rows were sliced for a mesh that no
        longer exists."""
        topo_gen = _topo_generation()
        with self._mtx:
            e = self._entries.get(valset_id)
            if e is not None:
                if e.topo_generation == topo_gen:
                    self._entries.move_to_end(valset_id)
                    self._stats["hits"] += 1
                    e.hits = getattr(e, "hits", 0) + 1
                    return e
                del self._entries[valset_id]
                self._stats["stale_drops"] += 1
                self._removed_locked()
            self._stats["misses"] += 1
        e = build(pub_keys)  # slow: H2D upload — outside the lock
        e.valset_id = bytes(valset_id)
        e.topo_generation = topo_gen
        with self._mtx:
            won = self._entries.get(valset_id)
            if won is not None and won.topo_generation == topo_gen:
                # lost the race: reuse the winner's rows (one transient
                # duplicate upload at most, never a corrupted LRU)
                self._entries.move_to_end(valset_id)
                return won
            self._stats["uploads"] += 1
            e.generation = self._stats["uploads"]
            e.hits = getattr(e, "hits", 0)
            e.pins = getattr(e, "pins", 0)
            self._entries[valset_id] = e
            self._evict_excess_locked()
        return e

    def pin(self, valset_id: bytes) -> bool:
        """Mark the entry immune to LRU eviction (refcounted) for the
        duration of an in-flight dispatch, and count the use. Pins guard
        against cache PRESSURE only: explicit ``invalidate`` and
        topology-staleness drops still apply — a dispatch that already
        holds the entry object completes against its own table either
        way. Returns False when the entry is already gone."""
        with self._mtx:
            e = self._entries.get(bytes(valset_id))
            if e is None:
                return False
            e.pins = getattr(e, "pins", 0) + 1
            e.hits = getattr(e, "hits", 0) + 1
            return True

    def unpin(self, valset_id: bytes) -> None:
        with self._mtx:
            e = self._entries.get(bytes(valset_id))
            if e is not None:
                e.pins = max(0, getattr(e, "pins", 0) - 1)
            # eviction deferred while everything was pinned resumes here
            self._evict_excess_locked()

    @contextmanager
    def pinned(self, valset_id: bytes):
        """``with store.pinned(vid) as ok:`` — pin for the block when the
        entry exists (ok True), always balanced on exit."""
        ok = self.pin(valset_id)
        try:
            yield ok
        finally:
            if ok:
                self.unpin(valset_id)

    def lookup_fresh(self, topo_gen: Optional[int] = None
                     ) -> List[KeyStoreEntry]:
        """Entries dispatchable under the CURRENT topology generation,
        most recently used first. Stale entries are dropped on sight —
        never returned, never verified against."""
        if topo_gen is None:
            topo_gen = _topo_generation()
        with self._mtx:
            stale = [
                vid for vid, e in self._entries.items()
                if e.topo_generation != topo_gen
            ]
            for vid in stale:
                del self._entries[vid]
                self._stats["stale_drops"] += 1
            self._removed_locked(len(stale))
            return list(reversed(self._entries.values()))

    def invalidate(self, valset_id: Optional[bytes] = None) -> int:
        """Drop one entry (or all, valset_id=None). Bumps the store
        generation: a client that registered before must resync."""
        with self._mtx:
            if valset_id is None:
                dropped = len(self._entries)
                self._entries.clear()
            else:
                dropped = int(
                    self._entries.pop(valset_id, None) is not None
                )
            if dropped:
                self._removed_locked(dropped)
                self._stats["invalidations"] += dropped
        return dropped

    def covers(self, pub_keys: Sequence[bytes]) -> bool:
        """True when ONE fresh resident entry covers every pubkey in
        ``pub_keys`` — the priced router's indexed-feasibility probe.
        Pure host-side dict lookups (no device touch); advisory only:
        verify_batch_indexed re-checks under its own lookup, so a
        concurrent eviction between this answer and the dispatch just
        downgrades to the keyed single-chip wire. Host-only service
        entries (no device table) don't count — they cannot feed the
        on-device gather this probe is pricing."""
        if not pub_keys:
            return False
        entries = self.lookup_fresh()
        for e in entries:
            if e.table_dev is None:
                continue
            index = e.index
            if all(_key_bytes(pk) in index for pk in pub_keys):
                return True
        return False

    def generation(self) -> int:
        """Current store generation — the freshness token of the verify
        service's indexed-frame handshake (stamped on HELLO/RESP frames;
        a client whose cached value diverges must re-register before
        shipping 100 B indexed rows again). It counts the entries that
        have left the store; an insert leaves it alone."""
        with self._mtx:
            return self._gen

    def entry_for(self, valset_id: bytes,
                  generation: Optional[int] = None) -> Optional[KeyStoreEntry]:
        """Frame-accept-time lookup for the verify service: the entry
        for ``valset_id``, but ONLY while the client's cached store
        generation matches the store's and the entry was built under the
        current topology — a stale client is refused (``stale_drops``
        counted) and falls back to full 128 B compact rows rather than
        ever verifying against a key space it has not resynced with."""
        vid = bytes(valset_id)
        topo_gen = _topo_generation()
        with self._mtx:
            e = self._entries.get(vid)
            if e is not None and e.topo_generation != topo_gen:
                del self._entries[vid]
                self._stats["stale_drops"] += 1
                self._removed_locked()
                return None
            if generation is not None and generation != self._gen:
                self._stats["stale_drops"] += 1
                return None
            if e is None:
                return None
            self._entries.move_to_end(vid)
            self._stats["hits"] += 1
            e.hits = getattr(e, "hits", 0) + 1
            return e

    def register(self, valset_id: bytes, pub_keys) -> KeyStoreEntry:
        """Host-side registration for the verify service's generation
        handshake: build (or reuse) an entry carrying only the host key
        rows + index — ``table_dev`` stays None, and the device-dispatch
        probes above skip such entries. The insert leaves the store
        generation alone (the id is the keys' own digest; only an entry
        LEAVING can stale a client), an entry from an older topology is
        dropped and rebuilt. Malformed-length keys get a zeroed row with
        ``pk_ok`` False (refused at verify, like the device build
        does)."""
        vid = bytes(valset_id)
        topo_gen = _topo_generation()
        with self._mtx:
            e = self._entries.get(vid)
            if e is not None and e.topo_generation == topo_gen:
                self._entries.move_to_end(vid)
                self._stats["hits"] += 1
                e.hits = getattr(e, "hits", 0) + 1
                return e
            if e is not None:
                del self._entries[vid]
                self._stats["stale_drops"] += 1
                self._removed_locked()
            self._stats["misses"] += 1
        keys = [_key_bytes(pk) for pk in pub_keys]
        n = len(keys)
        e = KeyStoreEntry()
        e.valset_id = vid
        e.topo_generation = topo_gen
        e.chunks = []
        e.plan = None
        e.pk_arr = np.zeros((n, 32), np.uint8)
        e.pk_ok = np.zeros(n, bool)
        e.index = {}
        e.table_dev = None
        e.n = n
        e.hits = 0
        e.pins = 0
        for i, k in enumerate(keys):
            if len(k) == 32:
                e.pk_arr[i] = np.frombuffer(k, np.uint8)
                e.pk_ok[i] = True
            e.index.setdefault(k, i)
        with self._mtx:
            won = self._entries.get(vid)
            if won is not None:
                self._entries.move_to_end(vid)
                return won
            self._stats["uploads"] += 1
            e.generation = self._stats["uploads"]
            self._entries[vid] = e
            self._evict_excess_locked()
        return e

    def note_indexed(self, lanes: int) -> None:
        with self._mtx:
            self._stats["indexed_dispatches"] += 1
            self._stats["indexed_lanes"] += int(lanes)

    def residency(self) -> dict:
        """Cheap per-flush residency summary for decision-plane inputs:
        entry/key counts, generation, and hit rate — no per-entry rows,
        one short lock hold."""
        with self._mtx:
            hits = self._stats["hits"]
            misses = self._stats["misses"]
            lookups = hits + misses
            return {
                "entries": len(self._entries),
                "keys": sum(e.n for e in self._entries.values()),
                "generation": self._gen,
                "hit_rate": (hits / lookups) if lookups else None,
                "indexed_dispatches": self._stats["indexed_dispatches"],
                "evictions": self._stats["evictions"],
                "thrash": self._stats["keystore_thrash"],
            }

    def snapshot(self) -> dict:
        """Queryable store state for scheduler snapshots / debug RPC."""
        with self._mtx:
            return {
                "generation": self._gen,
                "entries": [
                    {
                        "valset_id": getattr(e, "valset_id", b"").hex()[:16],
                        "generation": getattr(e, "generation", 0),
                        "topo_generation": e.topo_generation,
                        "keys": e.n,
                        "chunks": len(e.chunks),
                        "pins": getattr(e, "pins", 0),
                    }
                    for e in self._entries.values()
                ],
                "stats": dict(self._stats),
            }


_default = DeviceKeyStore()


def default_store() -> DeviceKeyStore:
    return _default


def covers(pub_keys: Sequence[bytes]) -> bool:
    """Module-level convenience over the default store — the
    scheduler's decision-feasibility gathering calls this through the
    sys.modules guard (no import cost for CPU-only nodes)."""
    return _default.covers(pub_keys)


def verify_batch_indexed(
    pub_keys: Sequence[bytes],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
) -> Optional[List[bool]]:
    """Steady-state indexed dispatch: if EVERY pubkey in the flush is
    covered by one fresh resident entry, verify by shipping the compact
    R ‖ S ‖ h rows plus an int32 index vector and gathering the pubkey
    rows from the on-device table — 100 B/lane vs 128 for the full
    compact wire. Returns None (caller falls back to verify_batch) when
    no single entry covers the flush or the mesh is sharded: the table
    gather would need full replication per shard, so the sharded route
    keeps shipping keys."""
    from cometbft_tpu.crypto.tpu import ed25519_batch as ed
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod

    n = len(pub_keys)
    if n == 0:
        return []
    if mesh_mod.n_devices() > 1:
        return None
    entries = _default.lookup_fresh()
    if not entries:
        return None
    entry = None
    for e in entries:
        if e.table_dev is None:
            continue  # host-only service entry: nothing to gather from
        if all(_key_bytes(pk) in e.index for pk in pub_keys):
            entry = e
            break
    if entry is None:
        return None

    idx_full = np.fromiter(
        (entry.index[_key_bytes(pk)] for pk in pub_keys),
        dtype=np.int32, count=n,
    )
    valid = np.ones(n, bool)

    def build(start, end):
        rsh, valid[start:end] = ed._prepare_rsh_compact(
            np.stack([
                np.frombuffer(_key_bytes(pk), np.uint8) for pk in
                pub_keys[start:end]
            ]),
            msgs[start:end], sigs[start:end],
        )
        return [idx_full[start:end], rsh]  # 100 B per padded lane

    cap = mesh_mod.chunk_cap(ed._MAX_CHUNK, ed._MIN_PAD)
    # The table leads every launch and must survive across flushes: only
    # the per-flush staging (idx + rsh) is donated. The wire ledger's
    # "indexed" route is what lets the decision plane PRICE the
    # 100 B/lane path (and the bytes_per_lane gauge prove it). The entry
    # is PINNED for the whole stream: per-height valset rotation would
    # otherwise LRU-evict the incoming table mid-flush (churn thrash)
    # and force the next flush to re-upload what this one was still
    # gathering from.
    with _default.pinned(entry.valset_id):
        out, _ = mesh_mod.launch_stream(
            ed.verify_kernel_indexed,
            [(start, end, size, entry.table_dev) for start, end, size in
             mesh_mod.shard_chunks(n, 1, cap, ed._MIN_PAD)],
            build, n, where=None, prefix="mesh", route="indexed",
            device_label="dev0", donate_from=1,
        )
    _default.note_indexed(n)
    return (out & valid).tolist()
