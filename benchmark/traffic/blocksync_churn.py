"""Traffic: a node that syncs a chain whose validator set changes. The
blocksync reactor's own pass, with apply, over a chain on which
``validators_hash`` moves at every height and a seat changes hands every
few heights (``benchmark/lib/churn_chain.py``).

The generator is ``blocksync_apply``'s with three differences. (1) The
application is the persistent kvstore (``node.py``'s
``default_client_creator("persistent_kvstore")``), and it gets InitChain
with the genesis set, as the handshake sends it: it refuses to remove a
validator it was never told of. (2) **One request is 16 heights of
progress**, not one call: ``sync_pass`` is called until the state has
advanced by the reactor's window (``verify_window`` blocks, or what is
left of the chain), with the pool holding those blocks and the one after
when the request starts; its latency is the time for all of them. A
program whose window stops at a validator-set change needs a pass a
block for what one that speculates past it does in one pass, and the two
are compared on this cell. It carries ``blocks x validators``
signatures. (3) It is ``ok`` only if the state's height, app hash, last
block id, validators hash AND the rows of its validator set and next
validator set, and the block store's read-back, are the plain
reference's for that height (``benchmark/lib/churn_reference.py``), and
neither a fallback counter of the verify plane nor the reactor's
``_sync_one`` counter moved. A lane that the speculation missed and the
apply-time walk verified is protocol, not a failure.

Closed loop, one request in flight, one thread where ``_pool_routine``
stands; a fresh epoch (genesis state, stores, application, executor,
reactor, pool, peers) after the chain's last block.

A program whose window stops at every validator-set change sends every
lane of this traffic to the host pool (one block's quorum prefix is
under the routing floor), and which program does is what the cell is
there to show. The chip must still be known alive, and a traced run must
see the device path at least once, whichever program runs. So, as
``steady_votes`` does, the generator runs the supervisor's own canary
(``verify_supervisor.probe_now()``: a known-good batch through the
supervised device path, on a background scope, in no ledger that a
per-layer metric reads) between two requests, on the request thread,
whenever ``health_probe_every_s`` seconds have passed since the last
one. It is not a request and lies in no request's latency; a probe that
fails makes the run incorrect.

Warm-up: the one executable a pass can reach (a burst of the first
window's quorum prefixes, each under its own height's set), then three
chains with one more, byzantine peer that advertises first: (i) a
precommit INSIDE the quorum prefix of the commit for
``forged.prefix_block`` signed by somebody else; (ii) one BEHIND the
prefix of the commit for ``forged.tail_block`` (that block is applied,
the next is refused by ``validate_block``); (iii) the precommit of the
seat that JOINED earlier in the same window, in the commit for
``forged.seat_block``: no lane could be speculated for it, so it is the
apply-time path that has to refuse it. Each is synced until the
reactor refuses a block, compared with the reference, then synced on
from the honest peers, or warm-up raises.

Parameters (the traffic file): ``peers``, ``byzantine_peers``,
``forged`` {``prefix_block``, ``prefix_lane``, ``tail_block``,
``tail_lane``, ``seat_block``}, ``request_timeout_s``,
``health_probe_every_s``.
"""

from __future__ import annotations

import time
from typing import Tuple

from cometbft_tpu.blocksync import BlocksyncReactor
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
from benchmark.lib import churn_chain, churn_reference, data, loops
from benchmark.traffic import blocksync_apply as apply_gen

SUBSYSTEM = apply_gen.SUBSYSTEM
FORK_BLOCKS = apply_gen.FORK_BLOCKS
KINDS = ("prefix", "tail", "seat")


# --------------------------------------------------------------------------
# the plan: chain, forks and what the reference says of each


def build(config: dict, params: dict, seed: int) -> dict:
    chain = churn_chain.build(
        config["chain_id"], int(config["validators"]),
        int(config["replay_blocks"]), int(config["txs_per_block"]),
        int(config["tx_bytes"]), config["schedule"], seed,
    )
    genesis = chainlib.plain_vals(chain.vals)
    honest = churn_reference.replay(genesis, chain.records)
    if honest["refused"] is not None:
        raise AssertionError(
            f"the reference refuses the honest chain: {honest['refused']}"
        )
    forged = params["forged"]
    seat_block = int(forged["seat_block"])
    cases = {
        "prefix": (int(forged["prefix_block"]), int(forged["prefix_lane"])),
        "tail": (int(forged["tail_block"]), int(forged["tail_lane"])),
        "seat": (seat_block, churn_chain.seat_lane(chain, seat_block)),
    }
    forks = {}
    for kind, (block, lane) in cases.items():
        fork = churn_chain.fork(chain, block, lane, block + FORK_BLOCKS)
        forks[kind] = {
            "chain": fork,
            "block": block,
            "lane": lane,
            "want": churn_reference.replay(genesis, fork.records),
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
        "health_probe_every_s": float(params["health_probe_every_s"]),
    }


# --------------------------------------------------------------------------
# the syncing node


class ChurnNode(apply_gen.SyncNode):
    """``blocksync_apply.SyncNode`` on the persistent kvstore, which got
    InitChain with the genesis set: node.py's steps 1-9 for one epoch,
    with node.py's classes and arguments."""

    def __init__(self, chain, backend, logger=None):
        self.chain = chain
        self.block_store = BlockStore(MemDB())
        self.state_store = StateStore(MemDB())
        self.state = make_genesis_state(chain.doc)
        self.state_store.save(self.state)
        self.proxy_app = new_app_conns(
            default_client_creator("persistent_kvstore")
        )
        self.proxy_app.start()
        self.proxy_app.consensus().init_chain_sync(
            churn_chain.init_chain_request(chain.doc)
        )
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
        # _pool_routine stands (blocksync_apply.SyncNode)
        self.reactor = BlocksyncReactor(
            self.state, self.block_executor, self.block_store,
            fast_sync=False,
            crypto_backend=backend,
            logger=logger,
        )
        self.switch = apply_gen.Switch(self.reactor)
        self.reactor.set_switch(self.switch)
        self.window = int(self.reactor.verify_window)
        self._pass = self.reactor.sync_pass

    def sync_heights(self, heights: int) -> Tuple[int, int]:
        """Passes of the program until the state has advanced by
        ``heights`` blocks, or a pass applies none. → (blocks applied,
        passes made)."""
        start, passes = self.state.last_block_height, 0
        while self.state.last_block_height - start < heights:
            passes += 1
            if self.sync_pass() <= 0:
                break
        return self.state.last_block_height - start, passes

    def agrees_with(self, want: dict, applied: int) -> bool:
        """``SyncNode.agrees_with`` and the rows of both validator sets
        the state holds against the reference's for this height."""
        return (
            super().agrees_with(want, applied)
            and chainlib.plain_vals(self.state.validators)["rows"]
            == want["validators"]
            and chainlib.plain_vals(self.state.next_validators)["rows"]
            == want["next_validators"]
        )


def start_epoch(plane, plan: dict) -> ChurnNode:
    node = ChurnNode(plan["chain"], plane.backend,
                     getattr(plane.node, "logger", None))
    for k in range(plan["peers"]):
        node.add_peer(f"honest-{k}")
    node.start()
    return node


# --------------------------------------------------------------------------
# warm-up


def sync_forged_chain(plane, plan: dict, kind: str) -> dict:
    """A node whose first peer is byzantine: passes until the reactor
    refuses a block (or applies none), then the pool filled again from
    the peers that are left, then one request more. → what happened,
    for ``check_forged_chain``."""
    case = plan["forks"][kind]
    fork, honest = case["chain"], plan["chain"]
    node = ChurnNode(honest, plane.backend,
                     getattr(plane.node, "logger", None))
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
        applied = 0
        while True:
            got = node.sync_pass()
            applied += max(got, 0)
            now = node.counters()
            if got <= 0 or now["blocks_refused"] != before["blocks_refused"]:
                break
        stop_at = case["want"]["states"].get(node.state.last_block_height)
        out = {
            "applied": applied,
            "agrees": stop_at is not None
            and node.agrees_with(stop_at, applied),
            "refused_count": now["blocks_refused"] - before["blocks_refused"],
            "apply_time_lanes": now.get("speculation_miss_lanes", 0)
            - before.get("speculation_miss_lanes", 0),
            "stopped": sorted(node.switch.stopped),
            "byzantine": sorted(p.id() for p in byz),
        }
        node.await_window(timeout_s)
        asked = [h for p in node.switch.all_peers for h in p.requests]
        out["asked"] = {h: asked.count(h)
                        for h in (applied + 1, applied + 2)}
        out["then_window"] = node.full_window()
        out["then_applied"], _ = node.sync_heights(out["then_window"])
        target = plan["states"].get(node.state.last_block_height)
        out["then_agrees"] = target is not None and node.agrees_with(
            target, out["then_applied"]
        )
        out["synced_to"] = node.state.last_block_height
    finally:
        node.stop()
    return out


def check_forged_chain(plan: dict, kind: str, got: dict) -> dict:
    """Raises unless ``got`` is what the reference says of this chain:
    the blocks below the refused one applied and no more, state, sets
    and store the reference's there, one refusal, the byzantine peer
    stopped, both heights asked for again, and the honest copy synced a
    full request further."""
    case = plan["forks"][kind]
    refused, why = case["want"]["refused"]
    expect = case["block"] + (1 if kind == "tail" else 0)
    inside = "in the LastCommit" if kind == "tail" else "in the quorum prefix"
    if refused != expect or inside not in why:
        raise AssertionError(
            f"{kind}: the reference refuses {refused} ({why}), not {expect} "
            f"for a wrong signature {inside}"
        )
    wrong = {
        "applied": got["applied"] != refused - 1,
        "agrees": not got["agrees"],
        "refused_count": got["refused_count"] != 1,
        "stopped": got["stopped"] != got["byzantine"],
        "asked": any(n < 2 for n in got["asked"].values())
        or sorted(got["asked"]) != [refused, refused + 1],
        "then_applied": got["then_applied"] < got["then_window"],
        "then_agrees": not got["then_agrees"],
    }
    if any(wrong.values()):
        raise AssertionError(
            f"{kind}: the reference refuses block {refused} ({why}); the "
            f"program differs in {[k for k, v in wrong.items() if v]}: {got}"
        )
    return {"refused": refused, "why": why, "synced_to": got["synced_to"],
            "apply_time_lanes": got["apply_time_lanes"]}


def warm(plane, plan: dict) -> dict:
    """The one executable a pass can reach, then the three forged
    chains. Raises on anything the reference does not say."""
    chain = plan["chain"]
    window = DEFAULT_VERIFY_WINDOW  # what node.py's reactor runs
    items = [
        lane
        for h in range(1, min(window, chain.top - 1) + 1)
        for lane in data.quorum_prefix_items(
            chain.valsets[h], chain.commits[h], plan["chain_id"]
        )
    ]
    ok, _ = plane.backend.submit(
        items, subsystem=SUBSYSTEM, height=1
    ).result(timeout=plan["timeout_s"] * 20)
    if not ok:
        raise AssertionError("the warm-up burst was refused")
    out = {"burst_lanes": len(items), "window": window}
    for kind in KINDS:
        out[kind] = check_forged_chain(
            plan, kind, sync_forged_chain(plane, plan, kind)
        )
    return out


# --------------------------------------------------------------------------
# the timed window


def drive(plane, plan: dict, seconds: float) -> dict:
    requests, cpu_units = [], []
    sync_books: dict = {}
    epochs = 0
    # a stand-in plane with no node behind it (the tests') has no canary
    supervisor = getattr(plane.node, "verify_supervisor", None)
    every = plan["health_probe_every_s"]
    probes = {"ok": 0, "failed": 0}
    probe_due = 0.0
    t0 = time.monotonic()
    node = start_epoch(plane, plan)
    try:
        while time.monotonic() - t0 < seconds:
            node.await_window(plan["timeout_s"])
            if supervisor is not None and time.monotonic() >= probe_due:
                with plane.span("bench:health_probe"):
                    ok = supervisor.probe_now()
                probes["ok" if ok else "failed"] += 1
                probe_due = time.monotonic() + every
            want_blocks = node.full_window()
            marks = plane.fallbacks()
            fell = node.counters().get("sync_one_calls", 0)
            cpu = loops.cpu_seconds()
            t = time.monotonic()
            try:
                with plane.span("bench:sync_request"):
                    applied, passes = node.sync_heights(want_blocks)
            except Exception as exc:  # noqa: BLE001 - a failed request, counted
                requests.append((time.monotonic() - t,
                                 want_blocks * plan["validators"], "error"))
                plane.note(f"request {len(requests)} raised {exc!r}")
                applied = -1
            else:
                latency = time.monotonic() - t
                cpu = loops.cpu_seconds() - cpu
                sigs = max(applied, want_blocks) * plan["validators"]
                want = plan["states"].get(node.state.last_block_height)
                if (applied < want_blocks or want is None
                        or not node.agrees_with(want, applied)):
                    status = "mismatch"
                    plane.note(f"request {len(requests) + 1}: {applied} of "
                               f"{want_blocks} blocks in {passes} passes, at "
                               f"height {node.state.last_block_height}")
                elif (plane.fallbacks() != marks
                      or node.counters().get("sync_one_calls", 0) != fell):
                    status = "fallback"
                else:
                    status = "ok"
                    cpu_units.append((cpu, sigs))
                requests.append((latency, sigs, status))
            plane.tick()
            if applied < want_blocks or node.full_window() == 0:
                # the chain's end, or a request that went wrong: a node
                # at genesis again
                apply_gen._fold(sync_books, node.counters())
                node.stop()
                epochs += 1
                node = start_epoch(plane, plan)
        window_s = time.monotonic() - t0
        apply_gen._fold(sync_books, node.counters())
    finally:
        node.stop()
    sync_books["epochs_finished"] = epochs
    plane.note("the reactor's books over the window: " + repr({
        key: ({k: round(v, 4) for k, v in val.items()}
              if isinstance(val, dict) else val)
        for key, val in sorted(sync_books.items())
    }))
    return {
        "loop": "closed",
        "window_s": window_s,
        "attempted": len(requests),
        "requests": requests,
        "cpu_units": cpu_units,
        "extra_sigs": 0,
        "mismatches": probes["failed"],
        "health_probes": probes["ok"],
        "spans_s": {"sync": sync_books},
        "late_s": [],
    }
