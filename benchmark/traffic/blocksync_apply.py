"""Traffic: a node that syncs. The blocksync reactor's own pass, with
apply, over a chain of loaded blocks that in-process peers serve.

The generator builds what ``node/node.py`` builds for blocksync (its
steps 1-9: stores, app connections, event bus, mempool, evidence pool,
``BlockExecutor``, ``BlocksyncReactor``, with node.py's classes and
arguments, on the NODE'S OWN scheduler: ``crypto_backend=plane.backend``
to executor, evidence pool and reactor, and no ``verify_window``), puts
a stub switch with in-process peers under the reactor and starts the
reactor's real ``BlockPool``. Each peer answers every ``BlockRequest``
on its own thread by handing ``reactor.receive`` the pre-encoded
``BlockResponse``; all advertise the whole chain at once (a node
``replay_blocks`` behind).

One request is ONE CALL of the reactor's pass (``sync_pass``, what
``_pool_routine`` runs between its timers) over a pool that holds a
full window: ``verify_window`` blocks and the one after. Its latency is
the call. It carries ``verify_window x validators`` signatures: the
distinct commit signatures the blocks' acceptance rests on, whatever
number of times the program checks each. It is ``ok`` only if the
state's height, app hash, last block id and validators hash and the
block store's read-back are the plain reference's for that height
(``benchmark/lib/sync_reference.py``), and neither a fallback counter of
the verify plane nor the reactor's ``_sync_one`` counter moved.

Closed loop, one pass in flight. Between requests the generator waits
until the pool holds a full window again; after the chain's last block
it builds a fresh epoch (genesis state, stores, app, executor, reactor,
pool, peers). Both waits lie inside the measured window and outside
every latency.

Warm-up: the one executable a pass can need (a burst of k x 101 lanes
clears the floor from 11 blocks on and pads to 2,048), then two chains
with one more, byzantine peer that advertises first and so serves the
first heights: (i) one precommit INSIDE the quorum prefix of the commit
for ``forged.prefix_block`` signed by somebody else: the blocks below it
are applied, it is refused by the light check; (ii) one OUTSIDE the
prefix of the commit for ``forged.tail_block``: that block is applied
(the light check does not look there) and the NEXT one, which carries
the commit as its LastCommit, is refused by ``validate_block``. In both
the two heights are asked for again, the byzantine peer is stopped, and
the honest copy then syncs: all as the reference says, or warm-up raises.

Parameters (the traffic file): ``peers``, ``byzantine_peers``,
``forged`` {``prefix_block``, ``prefix_lane``, ``tail_block``,
``tail_lane``}, ``request_timeout_s``.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Dict, List, Optional

# the program's modules load here, with the generator, before the
# harness starts its build thread beside the node's start (chain.py)
from cometbft_tpu.blocksync import BlocksyncReactor
from cometbft_tpu.blocksync.messages import (
    BLOCKSYNC_CHANNEL, BlockRequest, StatusRequest, StatusResponse,
    decode_blocksync_message, encode_blocksync_message,
)
from cometbft_tpu.blocksync.reactor import DEFAULT_VERIFY_WINDOW
from cometbft_tpu.config import MempoolConfig
from cometbft_tpu.evidence.pool import Pool as EvidencePool
from cometbft_tpu.libs.db import MemDB
from cometbft_tpu.mempool.clist_mempool import CListMempool
from cometbft_tpu.mempool.metrics import Metrics as MemMetrics
from cometbft_tpu.node.node import default_client_creator
from cometbft_tpu.proxy import new_app_conns
from cometbft_tpu.state import make_genesis_state
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.state.metrics import Metrics as SMMetrics
from cometbft_tpu.state.store import Store as StateStore
from cometbft_tpu.store import BlockStore
from cometbft_tpu.types.event_bus import EventBus

from benchmark.lib import chain as chainlib
from benchmark.lib import data, loops, sync_reference

SUBSYSTEM = "blocksync"
POLL_S = 0.002
# a byzantine peer's fork reaches past the first window and as far as
# the pool asks one peer at once (pool.MAX_PENDING_REQUESTS_PER_PEER)
FORK_BLOCKS = 20


# --------------------------------------------------------------------------
# the plan: chain, forks and what the reference says of each


def build(config: dict, params: dict, seed: int) -> dict:
    chain = chainlib.build(
        config["chain_id"], int(config["validators"]),
        int(config["replay_blocks"]), int(config["txs_per_block"]),
        int(config["tx_bytes"]), seed,
    )
    vals = chainlib.plain_vals(chain.vals)
    honest = sync_reference.replay(vals, chain.records)
    if honest["refused"] is not None:
        raise AssertionError(
            f"the reference refuses the honest chain: {honest['refused']}"
        )
    forged = params["forged"]
    forks = {}
    for kind in ("prefix", "tail"):
        block, lane = int(forged[kind + "_block"]), int(forged[kind + "_lane"])
        fork = chainlib.fork(chain, block, lane, block + FORK_BLOCKS)
        forks[kind] = {
            "chain": fork,
            "block": block,
            "want": sync_reference.replay(vals, fork.records),
        }
    return {
        "chain_id": config["chain_id"],
        "valset": chain.vals,
        "chain": chain,
        "states": honest["states"],
        "forks": forks,
        "validators": int(config["validators"]),
        "peers": int(params["peers"]),
        "byzantine_peers": int(params["byzantine_peers"]),
        "timeout_s": float(params["request_timeout_s"]),
    }


# --------------------------------------------------------------------------
# in-process peers under a stub switch


class Peer:
    """What the reactor sees of a peer (``id``, ``send``, ``try_send``)
    and the peer's own side: a thread that answers block requests with
    ``serve(height)``, the encoded ``BlockResponse``."""

    def __init__(self, peer_id: str, reactor, top: int,
                 serve: Callable[[int], bytes]):
        self._id = peer_id
        self._reactor = reactor
        self._top = top
        self._serve = serve
        self._inbox: "queue.SimpleQueue" = queue.SimpleQueue()
        self.requests: List[int] = []
        self._thread = threading.Thread(
            target=self._run, name=f"bench-peer-{peer_id}", daemon=True
        )

    def id(self) -> str:
        return self._id

    def send(self, ch_id: int, msg: bytes) -> bool:
        return self.try_send(ch_id, msg)

    def try_send(self, ch_id: int, msg: bytes) -> bool:
        self._inbox.put(msg)
        return True

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._inbox.put(None)

    def advertise(self) -> None:
        """Our StatusResponse, as a peer's add_peer sends it."""
        self._reactor.receive(
            BLOCKSYNC_CHANNEL, self,
            encode_blocksync_message(StatusResponse(self._top, 1)),
        )

    def _run(self) -> None:
        while True:
            raw = self._inbox.get()
            if raw is None:
                return
            msg = decode_blocksync_message(raw)
            if isinstance(msg, BlockRequest):
                self.requests.append(msg.height)
                self._reactor.receive(
                    BLOCKSYNC_CHANNEL, self, self._serve(msg.height)
                )
            elif isinstance(msg, StatusRequest):
                self.advertise()


class Switch:
    """As much of p2p.Switch as the blocksync reactor calls."""

    def __init__(self, reactor):
        self._reactor = reactor
        self.peers: Dict[str, Peer] = {}
        self.all_peers: List[Peer] = []
        self.stopped: List[str] = []

    def add_peer(self, peer: Peer) -> None:
        self.peers[peer.id()] = peer
        self.all_peers.append(peer)
        self._reactor.add_peer(peer)
        peer.start()
        peer.advertise()

    def stop_peer_for_error(self, peer: Peer, err) -> None:
        if self.peers.pop(peer.id(), None) is None:
            return
        self.stopped.append(peer.id())
        self._reactor.remove_peer(peer, err)
        peer.stop()

    def broadcast(self, ch_id: int, msg: bytes) -> None:
        for peer in list(self.peers.values()):
            peer.try_send(ch_id, msg)

    def reactor(self, name: str):
        return None

    def stop(self) -> None:
        for peer in self.all_peers:
            peer.stop()


# --------------------------------------------------------------------------
# the syncing node


class SyncNode:
    """node.py's steps 1-9 for one epoch: a node at genesis about to
    sync ``chain`` from its peers. ``backend`` is the node's verify
    scheduler, ``logger`` the node's logger."""

    def __init__(self, chain, backend, logger=None):
        self.chain = chain
        self.block_store = BlockStore(MemDB())
        self.state_store = StateStore(MemDB())
        self.state = make_genesis_state(chain.doc)
        self.state_store.save(self.state)
        self.proxy_app = new_app_conns(default_client_creator("kvstore"))
        self.proxy_app.start()
        self.event_bus = EventBus()
        self.event_bus.start()
        self.mempool = CListMempool(
            MempoolConfig(), self.proxy_app.mempool(),
            height=self.state.last_block_height, metrics=MemMetrics.nop(),
        )
        self.evidence_pool = EvidencePool(
            MemDB(), self.state_store, self.block_store,
            crypto_backend=backend,
        )
        self.block_executor = BlockExecutor(
            self.state_store,
            self.proxy_app.consensus(),
            mempool=self.mempool,
            evidence_pool=self.evidence_pool,
            event_bus=self.event_bus,
            crypto_backend=backend,
            metrics=SMMetrics.nop(),
            logger=logger,
        )
        # fast_sync=False: the generator's loop stands where
        # _pool_routine stands, so the reactor starts no thread of its
        # own; its pool is started below, as _start_pool starts it
        self.reactor = BlocksyncReactor(
            self.state, self.block_executor, self.block_store,
            fast_sync=False,
            crypto_backend=backend,
            logger=logger,
        )
        self.switch = Switch(self.reactor)
        self.reactor.set_switch(self.switch)
        self.window = int(self.reactor.verify_window)
        # the program's pass; a program without the public name has the
        # same body under its private one
        self._pass = getattr(self.reactor, "sync_pass", None) or (
            lambda st: self.reactor._try_sync_window(st.chain_id, st)
        )

    def add_peer(self, peer_id: str,
                 serve: Optional[Callable[[int], bytes]] = None) -> Peer:
        peer = Peer(peer_id, self.reactor, self.chain.top,
                    serve or self.chain.encoded.__getitem__)
        self.switch.add_peer(peer)
        return peer

    def start(self) -> None:
        self.reactor.start()
        self.reactor.pool.start()

    def stop(self) -> None:
        self.switch.stop()
        for svc in (self.reactor.pool, self.reactor, self.event_bus,
                    self.proxy_app):
            if svc.is_running():
                svc.stop()

    def counters(self) -> dict:
        read = getattr(self.reactor, "sync_counters", None)
        return read() if read is not None else {}

    def full_window(self) -> int:
        """Blocks the next pass applies: the reactor's window, or what
        is left of the chain."""
        return min(self.window,
                   self.chain.top - 1 - self.state.last_block_height)

    def await_window(self, timeout_s: float) -> None:
        """Until the pool holds the next full window and the block
        after it."""
        need = self.full_window() + 1
        deadline = time.monotonic() + timeout_s
        while len(self.reactor.pool.peek_window(self.window)) < need:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"the pool did not fill a window of {need} blocks from "
                    f"height {self.reactor.pool.height} in {timeout_s:g}s: "
                    f"status {self.reactor.pool.get_status()}"
                )
            time.sleep(POLL_S)

    def sync_pass(self) -> int:
        """One pass of the program. → blocks applied."""
        before = self.state.last_block_height
        self.state = self._pass(self.state)
        return self.state.last_block_height - before

    def agrees_with(self, want: dict, applied: int) -> bool:
        """State and block store against the reference's state for this
        height: the last ``applied`` heights' ids read back from the
        store's metas, the newest block read back whole."""
        state, store = self.state, self.block_store
        h = want["height"]
        if (
            state.last_block_height != h
            or bytes(state.app_hash) != want["app_hash"]
            or chainlib.plain_id(state.last_block_id)
            != want["last_block_id"]
            or state.validators.hash() != want["validators_hash"]
            or store.height() != h
        ):
            return False
        if h == 0:
            return True
        for k in range(h - applied + 1, h + 1):
            meta = store.load_block_meta(k)
            if meta is None or (
                chainlib.plain_id(meta.block_id) != self.chain.records[k]["id"]
            ):
                return False
        newest = store.load_block(h)
        return (
            newest is not None
            and newest.hash() == want["last_block_id"][0]
            and store.load_seen_commit(h) is not None
        )


def start_epoch(plane, plan: dict) -> SyncNode:
    node = SyncNode(plan["chain"], plane.backend,
                    getattr(plane.node, "logger", None))
    for k in range(plan["peers"]):
        node.add_peer(f"honest-{k}")
    node.start()
    return node


# --------------------------------------------------------------------------
# warm-up


def sync_forged_chain(plane, plan: dict, kind: str) -> dict:
    """A node whose first peer is byzantine (it advertises first, so the
    pool asks it for the first heights): one pass, then the pool filled
    again from the peers that are left, then one more pass. → what
    happened, for ``check_forged_chain``."""
    case = plan["forks"][kind]
    fork, honest = case["chain"], plan["chain"]
    node = SyncNode(honest, plane.backend, getattr(plane.node, "logger", None))
    try:
        byz = [
            node.add_peer(
                f"byzantine-{k}",
                lambda h: (fork.encoded[h] if h <= fork.top
                           else honest.encoded[h]),
            )
            for k in range(plan["byzantine_peers"])
        ]
        for k in range(plan["peers"]):
            node.add_peer(f"honest-{k}")
        node.start()
        timeout_s = plan["timeout_s"] * 20
        node.await_window(timeout_s)
        before = node.counters()
        applied = node.sync_pass()
        stop_at = case["want"]["states"].get(node.state.last_block_height)
        out = {
            "applied": applied,
            "agrees": stop_at is not None
            and node.agrees_with(stop_at, applied),
            # None: a program that keeps no books
            "refused_count": node.counters()["blocks_refused"]
            - before["blocks_refused"] if before else None,
            "stopped": sorted(node.switch.stopped),
            "byzantine": sorted(p.id() for p in byz),
        }
        node.await_window(timeout_s)
        asked = [h for p in node.switch.all_peers for h in p.requests]
        out["asked"] = {h: asked.count(h)
                        for h in (applied + 1, applied + 2)}
        out["then_applied"] = node.sync_pass()
        target = plan["states"].get(node.state.last_block_height)
        out["then_agrees"] = target is not None and node.agrees_with(
            target, out["then_applied"]
        )
        out["synced_to"] = node.state.last_block_height
        out["then_window"] = min(node.window, honest.top - 1 - applied)
    finally:
        node.stop()
    return out


def check_forged_chain(plan: dict, kind: str, got: dict) -> dict:
    """Raises unless ``got`` is what the reference says of this chain:
    the blocks below the refused one applied and no more, state and
    store the reference's there, one refusal, the byzantine peer
    stopped, both heights asked for again, and the honest copy synced a
    full window further."""
    case = plan["forks"][kind]
    refused, why = case["want"]["refused"]
    expect = {"prefix": case["block"], "tail": case["block"] + 1}[kind]
    if refused != expect:
        raise AssertionError(
            f"{kind}: the reference refuses {refused} ({why}), not {expect}"
        )
    wrong = {
        "applied": got["applied"] != refused - 1,
        "agrees": not got["agrees"],
        "refused_count": got["refused_count"] not in (None, 1),
        "stopped": got["stopped"] != got["byzantine"],
        "asked": any(n < 2 for n in got["asked"].values())
        or sorted(got["asked"]) != [refused, refused + 1],
        "then_applied": got["then_applied"] != got["then_window"],
        "then_agrees": not got["then_agrees"],
    }
    if any(wrong.values()):
        raise AssertionError(
            f"{kind}: the reference refuses block {refused} ({why}); the "
            f"program differs in {[k for k, v in wrong.items() if v]}: {got}"
        )
    return {"refused": refused, "why": why, "synced_to": got["synced_to"]}


def warm(plane, plan: dict) -> dict:
    """The one executable a pass can reach, then both forged chains.
    Raises on anything the reference does not say."""
    chain = plan["chain"]
    window = DEFAULT_VERIFY_WINDOW  # what node.py's reactor runs
    items = [
        lane
        for h in range(1, min(window, chain.top - 1) + 1)
        for lane in data.quorum_prefix_items(
            chain.vals, chain.commits[h], plan["chain_id"]
        )
    ]
    ok, _ = plane.backend.submit(
        items, subsystem=SUBSYSTEM, height=1
    ).result(timeout=plan["timeout_s"] * 20)
    if not ok:
        raise AssertionError("the warm-up burst was refused")
    out = {"burst_lanes": len(items), "window": window}
    for kind in ("prefix", "tail"):
        out[kind] = check_forged_chain(
            plan, kind, sync_forged_chain(plane, plan, kind)
        )
    return out


# --------------------------------------------------------------------------
# the timed window


def _fold(total: dict, part: dict) -> None:
    """Adds one epoch's counters to the run's, nested maps included."""
    for key, val in part.items():
        if isinstance(val, dict):
            _fold(total.setdefault(key, {}), val)
        else:
            total[key] = total.get(key, 0) + val


def drive(plane, plan: dict, seconds: float) -> dict:
    requests, cpu_units = [], []
    sync_books: dict = {}
    epochs = 0
    t0 = time.monotonic()
    node = start_epoch(plane, plan)
    try:
        while time.monotonic() - t0 < seconds:
            node.await_window(plan["timeout_s"])
            want_blocks = node.full_window()
            sigs = want_blocks * plan["validators"]
            marks = plane.fallbacks()
            fell = node.counters().get("sync_one_calls", 0)
            cpu = loops.cpu_seconds()
            t = time.monotonic()
            try:
                with plane.span("bench:sync_pass"):
                    applied = node.sync_pass()
            except Exception as exc:  # noqa: BLE001 - a failed request, counted
                requests.append((time.monotonic() - t, sigs, "error"))
                plane.note(f"pass {len(requests)} raised {exc!r}")
                applied = -1
            else:
                latency = time.monotonic() - t
                cpu = loops.cpu_seconds() - cpu
                want = plan["states"].get(node.state.last_block_height)
                if (applied != want_blocks or want is None
                        or not node.agrees_with(want, applied)):
                    status = "mismatch"
                elif (plane.fallbacks() != marks
                      or node.counters().get("sync_one_calls", 0) != fell):
                    status = "fallback"
                else:
                    status = "ok"
                    cpu_units.append((cpu, sigs))
                requests.append((latency, sigs, status))
            plane.tick()
            if applied != want_blocks or node.full_window() == 0:
                # the chain's end, or a pass that went wrong: a node at
                # genesis again
                _fold(sync_books, node.counters())
                node.stop()
                epochs += 1
                node = start_epoch(plane, plan)
        window_s = time.monotonic() - t0
        _fold(sync_books, node.counters())
    finally:
        node.stop()
    sync_books["epochs_finished"] = epochs
    return {
        "loop": "closed",
        "window_s": window_s,
        "attempted": len(requests),
        "requests": requests,
        "cpu_units": cpu_units,
        "extra_sigs": 0,
        "spans_s": {"sync": sync_books},
        "late_s": [],
    }
