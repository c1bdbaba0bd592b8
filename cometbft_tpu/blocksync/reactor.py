"""Blocksync reactor — serves blocks to peers and fast-syncs from them.

Reference: blockchain/v0/reactor.go — AddPeer sends our StatusResponse
(:150-166), Receive handles the five message kinds (:198-235), and
poolRoutine (:309-420) drives the sync: verify the block at pool height
with the NEXT block's LastCommit (VerifyCommitLight :366), ValidateBlock,
SaveBlock, ApplyBlock, and SwitchToConsensus when caught up (:317-331).

TPU-first: instead of one VerifyCommitLight per loop iteration, the sync
loop takes the pool's contiguous window of fetched blocks and verifies
every commit in it through ONE BatchVerifier call — pipeline-depth ×
quorum-sigs signatures per device round-trip, which is where batch
hardware wins (BASELINE.md config #4). Validator-set changes inside the
window are detected via header.validators_hash: the leading blocks that
carry the state's hash are batched under the state's set.

When the node's VerifyScheduler travels crypto_backend
(crypto/scheduler.py), each window block's commit is submitted as its
own request instead: the scheduler coalesces them (and any concurrent
consensus/light submissions) into one dispatch, and the per-block
futures let block i APPLY while blocks i+1.. are still verifying —
the next commit is in flight during the current apply. On that path
the window SURVIVES a validator-set change: whether a signature is
valid depends on (key, sign-bytes, signature) and on nobody's power,
so the blocks past the first change ride the same dispatch with lanes
chosen by CommitSig.validator_address against the keys the state knows
now (_speculate_window), and the quorum walk (order, powers, > 2/3) is
made at apply time against the set the state then holds
(_tally_speculated), verifying there whatever lane the speculation did
not cover. Without a scheduler (a bare backend name, or the resident
route) the blocks past a change wait for the next pass, as before.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Tuple

from cometbft_tpu.blocksync.messages import (
    BLOCKSYNC_CHANNEL,
    MAX_MSG_SIZE,
    BlockRequest,
    BlockResponse,
    NoBlockResponse,
    StatusRequest,
    StatusResponse,
    decode_blocksync_message,
    encode_blocksync_message,
)
from cometbft_tpu.blocksync.pool import BlockPool
from cometbft_tpu.crypto import batch as cryptobatch
from cometbft_tpu.libs import trace as tracelib
from cometbft_tpu.libs.log import Logger
from cometbft_tpu.p2p.base_reactor import Reactor
from cometbft_tpu.p2p.conn.connection import ChannelDescriptor
from cometbft_tpu.p2p.peer import Peer
from cometbft_tpu.types.block import Block, BlockID
from cometbft_tpu.types.part_set import BLOCK_PART_SIZE_BYTES
from cometbft_tpu.types.validator_set import cs_sig

TRY_SYNC_INTERVAL = 0.01  # reference: trySyncIntervalMS = 10
STATUS_UPDATE_INTERVAL = 10.0  # reference :36
SWITCH_TO_CONSENSUS_INTERVAL = 1.0  # reference :39
DEFAULT_VERIFY_WINDOW = 16  # blocks batch-verified per device call
# A block past a validator-set change is tallied under powers the blocks
# before it may still move, so its by-address lanes go on past the quorum
# of the newest powers known, by this share of that quorum.
SPECULATION_MARGIN = 1 / 8


class BlocksyncReactor(Reactor):
    def __init__(
        self,
        state,  # state.State at store height
        block_exec,  # state.execution.BlockExecutor
        block_store,
        fast_sync: bool,
        verify_window: int = DEFAULT_VERIFY_WINDOW,
        crypto_backend: Optional[str] = None,
        logger: Optional[Logger] = None,
    ):
        super().__init__("BlocksyncReactor", logger)
        if state.last_block_height != block_store.height():
            raise ValueError(
                f"state ({state.last_block_height}) and store "
                f"({block_store.height()}) height mismatch"
            )
        self.initial_state = state
        self.block_exec = block_exec
        self.store = block_store
        self.fast_sync = fast_sync
        self.verify_window = verify_window
        self.crypto_backend = crypto_backend
        start_height = block_store.height() + 1
        if start_height == 1:
            start_height = state.initial_height
        self.pool = BlockPool(
            start_height, self._send_request, self._on_pool_error,
            logger=self.logger,
        )
        self.blocks_synced = 0
        self.sync_error: Optional[Exception] = None
        # monotonic books of the sync loop (sync_counters())
        self._stages = tracelib.StageSeconds()
        self._counts = {
            "passes": 0, "blocks_refused": 0, "sync_one_calls": 0,
            "light_lanes_submitted": 0, "window_blocks": 0,
            "valset_changes_in_window": 0, "speculated_lanes": 0,
            "tally_lanes": 0, "speculation_miss_lanes": 0,
        }
        self._pool_thread: Optional[threading.Thread] = None

    # -- Reactor interface ---------------------------------------------------

    def get_channels(self) -> List[ChannelDescriptor]:
        return [
            ChannelDescriptor(
                id=BLOCKSYNC_CHANNEL,
                priority=5,
                send_queue_capacity=1000,
                recv_message_capacity=MAX_MSG_SIZE,
            )
        ]

    def on_start(self) -> None:
        if self.fast_sync:
            self._start_pool()

    def on_stop(self) -> None:
        if self.pool.is_running():
            self.pool.stop()

    def _start_pool(self) -> None:
        self.pool.start()
        self._pool_thread = threading.Thread(
            target=self._pool_routine, name="blocksync-pool", daemon=True
        )
        self._pool_thread.start()

    def switch_to_fast_sync(self, state) -> None:
        """Called by the statesync reactor after a snapshot restore: resume
        fast sync from the bootstrapped height (blockchain/v0/reactor.go:118)."""
        self.fast_sync = True
        self.initial_state = state
        self.pool.height = state.last_block_height + 1
        self._start_pool()

    def add_peer(self, peer: Peer) -> None:
        # tell the peer our range; it adds us to its pool on receipt
        peer.send(
            BLOCKSYNC_CHANNEL,
            encode_blocksync_message(
                StatusResponse(self.store.height(), self.store.base())
            ),
        )

    def remove_peer(self, peer: Peer, reason) -> None:
        self.pool.remove_peer(peer.id())

    def receive(self, ch_id: int, peer: Peer, msg_bytes: bytes) -> None:
        try:
            msg = decode_blocksync_message(msg_bytes)
        except Exception as exc:
            self.switch.stop_peer_for_error(peer, exc)
            return
        if isinstance(msg, BlockRequest):
            self._respond_to_peer(msg, peer)
        elif isinstance(msg, BlockResponse):
            if msg.block is not None:
                self.pool.add_block(peer.id(), msg.block, len(msg_bytes))
        elif isinstance(msg, StatusRequest):
            peer.send(
                BLOCKSYNC_CHANNEL,
                encode_blocksync_message(
                    StatusResponse(self.store.height(), self.store.base())
                ),
            )
        elif isinstance(msg, StatusResponse):
            self.pool.set_peer_range(peer.id(), msg.base, msg.height)
        elif isinstance(msg, NoBlockResponse):
            self.logger.debug(
                "peer does not have the requested block", height=msg.height
            )

    def _respond_to_peer(self, msg: BlockRequest, peer: Peer) -> None:
        block = self.store.load_block(msg.height)
        if block is not None:
            peer.try_send(
                BLOCKSYNC_CHANNEL,
                encode_blocksync_message(BlockResponse(block)),
            )
        else:
            peer.try_send(
                BLOCKSYNC_CHANNEL,
                encode_blocksync_message(NoBlockResponse(msg.height)),
            )

    # -- pool callbacks -------------------------------------------------------

    def _send_request(self, height: int, peer_id: str) -> None:
        peer = self.switch.peers.get(peer_id) if self.switch else None
        if peer is None:
            return
        peer.try_send(
            BLOCKSYNC_CHANNEL,
            encode_blocksync_message(BlockRequest(height)),
        )

    def _on_pool_error(self, err: Exception, peer_id: str) -> None:
        peer = self.switch.peers.get(peer_id) if self.switch else None
        if peer is not None:
            self.switch.stop_peer_for_error(peer, err)

    def broadcast_status_request(self) -> None:
        if self.switch is not None:
            self.switch.broadcast(
                BLOCKSYNC_CHANNEL, encode_blocksync_message(StatusRequest())
            )

    # -- sync loop -------------------------------------------------------------

    def _pool_routine(self) -> None:
        state = self.initial_state
        last_status = 0.0
        last_switch_check = 0.0
        while self.is_running() and self.pool.is_running():
            now = time.monotonic()
            if now - last_status >= STATUS_UPDATE_INTERVAL:
                self.broadcast_status_request()
                last_status = now
            if now - last_switch_check >= SWITCH_TO_CONSENSUS_INTERVAL:
                last_switch_check = now
                if self.pool.is_caught_up():
                    self.logger.info(
                        "switching to consensus", height=self.pool.height
                    )
                    self.pool.stop()
                    con_r = (
                        self.switch.reactor("CONSENSUS")
                        if self.switch
                        else None
                    )
                    if con_r is not None and hasattr(
                        con_r, "switch_to_consensus"
                    ):
                        con_r.switch_to_consensus(
                            state, self.blocks_synced > 0
                        )
                    return
            try:
                state = self.sync_pass(state)
            except Exception as exc:
                # the reference panics here ("failed to process committed
                # block"); a dead daemon thread would leave a zombie node,
                # so fail visibly: record the error and stop the pool so
                # is_caught_up()/sync_error surface the broken state
                self.sync_error = exc
                self.logger.error(
                    "FATAL: failed to process committed block — "
                    "stopping blocksync", err=str(exc),
                )
                self.pool.stop()
                return
            time.sleep(TRY_SYNC_INTERVAL)

    def sync_pass(self, state):
        """One pass of the sync loop, what ``_pool_routine`` runs between
        its timers: verify and apply the window the pool holds now (up to
        ``verify_window`` blocks and the one after). Returns the new
        state: ``state`` itself where the pool held no two contiguous
        blocks or the first one was refused. Raises what the routine
        treats as fatal (a block that verified and then failed to apply)."""
        self._counts["passes"] += 1
        return self._try_sync_window(state.chain_id, state)

    def sync_counters(self) -> dict:
        """The sync loop's books, monotonic, readable while it runs:
        ``passes`` (``sync_pass`` calls), ``blocks_applied``,
        ``blocks_refused`` (validation failures that re-requested two
        heights), ``sync_one_calls`` (falls to the single-block path),
        ``light_lanes_submitted`` (quorum-prefix lanes handed to the
        scheduler), ``window_blocks`` (blocks whose commits were
        submitted ahead of their apply), ``valset_changes_in_window``
        (times ``validators_hash`` moved from one such block to the
        next), ``speculated_lanes`` (by-address lanes submitted for the
        blocks past a change), ``tally_lanes`` (lanes the apply-time
        quorum walks of those blocks needed), ``speculation_miss_lanes``
        (of them, verified at apply because no speculated lane carried
        the true set's key), and ``seconds`` by stage: this reactor's
        ``sync.*`` and its executor's ``exec.*``."""
        execs = getattr(self.block_exec, "stage_seconds", None)
        seconds = execs.snapshot() if execs is not None else {}
        seconds.update(self._stages.snapshot())
        return dict(self._counts, blocks_applied=self.blocks_synced,
                    seconds=seconds)

    def _try_sync_window(self, chain_id: str, state):
        """Verify + apply the buffered window. Returns the new state.

        Batch path: one BatchVerifier call covers the quorum signatures of
        every window block whose validator set is the current one. Any
        failure falls back to the reference's single-block path so error
        attribution (redo + peer punishment) is identical.
        """
        window = self.pool.peek_window(self.verify_window)
        if not window:
            return state
        val_hash = state.validators.hash()
        # blocks past a validator-set change can't share the batch
        batchable = 0
        for blk in window[:-1]:
            if blk.header.validators_hash != val_hash:
                break
            batchable += 1
        if batchable == 0:
            return self._sync_one(chain_id, state)

        firsts = window[:batchable]
        with self._stages.stage("sync.build", blocks=batchable):
            built = self._build_window(chain_id, state, window, batchable)
        if built is None:
            # malformed commit in the window — single-block path will
            # attribute and redo it
            return self._sync_one(chain_id, state)
        block_ids, part_sets, per_block, lanes_per_block = built
        needed = state.validators.total_voting_power() * 2 // 3

        scheduler = self._window_scheduler(per_block, state)
        if scheduler is not None:
            # the blocks past the first validator-set change ride the
            # same dispatch, their lanes chosen by address
            ahead = window[batchable:-1]
            speculated: List[Tuple[dict, list]] = []
            if ahead:
                with self._stages.stage("sync.build", blocks=len(ahead)):
                    ids, parts, speculated = self._speculate_window(
                        chain_id, state, window, batchable
                    )
                block_ids += ids
                part_sets += parts
            hashes = [val_hash] + [
                blk.header.validators_hash for blk in window[:-1]
            ]
            self._counts["valset_changes_in_window"] += sum(
                a != b for a, b in zip(hashes, hashes[1:])
            )
            with self._stages.stage("sync.submit", blocks=len(window) - 1):
                futs = self._submit_window_commits(
                    scheduler, per_block, lanes_per_block, speculated, state
                )
            return self._apply_window_pipelined(
                chain_id, state, val_hash, window, block_ids, part_sets,
                per_block, speculated, futs, needed, scheduler,
            )

        with self._stages.stage("sync.verdict_wait"):
            mask = self._verify_window_lanes(
                per_block, lanes_per_block, state
            )
        if not all(mask):
            return self._sync_one(chain_id, state)

        # all signatures verified: check quorum per block, then apply
        pos = 0
        for i, entries in enumerate(per_block):
            tallied = 0
            for (idx, val), sig_ok in zip(entries, mask[pos : pos + len(entries)]):
                if sig_ok:
                    tallied += val.voting_power
            pos += len(entries)
            if tallied <= needed:
                return self._sync_one(chain_id, state)

        for i, first in enumerate(firsts):
            # a validator-set change mid-window invalidates the batch
            # assumption from this point on — re-verify individually
            if state.validators.hash() != val_hash:
                return state
            try:
                with self._stages.stage("sync.validate"):
                    self.block_exec.validate_block(state, first)
            except Exception:
                # single-block path re-verifies and attributes the failure
                return self._sync_one(chain_id, state)
            state = self._apply_one(
                state, block_ids[i], first, part_sets[i],
                window[i + 1].last_commit,
            )
        return state

    def _build_window(self, chain_id: str, state, window, batchable: int):
        """Part sets, block ids and quorum-prefix lanes (sign-bytes
        included) of the window's first ``batchable`` blocks, each
        verified by the LastCommit of the block after it. → (block_ids,
        part_sets, per_block, lanes_per_block), or None when a commit in
        the window is malformed."""
        block_ids: List[BlockID] = []
        part_sets: List[object] = []
        per_block: List[List[Tuple[int, object]]] = []
        lanes_per_block: List[Tuple[list, list]] = []
        n_lanes = len(state.validators.validators)
        needed = state.validators.total_voting_power() * 2 // 3
        for i, first in enumerate(window[:batchable]):
            with self._stages.stage("sync.part_set"):
                parts = first.make_part_set(BLOCK_PART_SIZE_BYTES)
            block_id = BlockID(first.hash(), parts.header())
            block_ids.append(block_id)
            part_sets.append(parts)
            second = window[i + 1]
            commit = second.last_commit
            entries = []
            lane_msgs: list = [None] * n_lanes
            lane_sigs: list = [None] * n_lanes
            try:
                self._check_commit_shape(
                    state, block_id, first.header.height, commit
                )
                speculative = 0
                for idx, csig in enumerate(commit.signatures):
                    if not csig.for_block():
                        continue
                    val = state.validators.validators[idx]
                    entries.append((idx, val))
                    lane_sigs[idx] = cs_sig(commit, idx)
                    speculative += val.voting_power
                    if speculative > needed:
                        break
                idxs = [e[0] for e in entries]
                for idx, msg in zip(
                    idxs, commit.vote_sign_bytes_many(chain_id, idxs)
                ):
                    lane_msgs[idx] = msg
            except Exception:
                return None
            per_block.append(entries)
            lanes_per_block.append((lane_msgs, lane_sigs))
        return block_ids, part_sets, per_block, lanes_per_block

    def _window_scheduler(self, per_block, state):
        """The node-wide verification scheduler the window's per-block
        requests go to, or None when it isn't wired (bare backend
        name/spec) or the resident full-lane path is the better route."""
        scheduler = (
            self.crypto_backend
            if hasattr(self.crypto_backend, "submit")
            and hasattr(self.crypto_backend, "spec")
            else None
        )
        if scheduler is None:
            return None
        from cometbft_tpu.crypto import ed25519 as ed

        vals = state.validators.validators
        if all(
            cryptobatch.resident_commit_eligible(
                len(entries), self.crypto_backend
            )
            for entries in per_block
        ) and all(isinstance(v.pub_key, ed.PubKeyEd25519) for v in vals):
            return None  # device-resident fixed executable wins at scale
        return scheduler

    def _speculate_window(
        self, chain_id: str, state, window, batchable: int
    ):
        """The window's blocks past the first validator-set change: their
        block ids and part sets and, a block, the lanes its quorum walk
        is likely to need, chosen before the set that will make the walk
        is known. → (block_ids, part_sets, speculated), ``speculated``
        one ``(lanes, items)`` a block: ``items`` the request's
        ``(pub_key, sign-bytes, signature)`` triples, ``lanes`` {commit
        index: (position in ``items``, pub_key)}.

        The verifying commit's signatures for the block are taken in
        commit order; one whose ``validator_address`` the state knows (in
        ``validators`` or ``next_validators``) gives a lane under that
        address's key, any other (a validator that joins inside the
        window) gives none. The walk stops once the newest powers known
        have carried it ``SPECULATION_MARGIN`` past the quorum of
        ``next_validators``: the true walk, under powers moved since,
        ends near there. A commit that cannot be read gives no lanes;
        the apply-time walk refuses it."""
        known = {}
        for vals in (state.validators, state.next_validators):
            for val in vals.validators:
                known[val.address] = val  # the newest power wins
        quorum = state.next_validators.total_voting_power() * 2 // 3
        reach = quorum + int(quorum * SPECULATION_MARGIN)
        block_ids: List[BlockID] = []
        part_sets: List[object] = []
        out: List[Tuple[dict, list]] = []
        for i in range(batchable, len(window) - 1):
            with self._stages.stage("sync.part_set"):
                parts = window[i].make_part_set(BLOCK_PART_SIZE_BYTES)
            block_ids.append(BlockID(window[i].hash(), parts.header()))
            part_sets.append(parts)
            commit = window[i + 1].last_commit
            lanes: dict = {}
            items: list = []
            with self._stages.stage("sync.speculate"):
                idxs, keys, msgs, power = [], [], [], 0
                try:
                    for idx, csig in enumerate(commit.signatures):
                        if not csig.for_block():
                            continue
                        val = known.get(csig.validator_address)
                        if val is None:
                            continue
                        idxs.append(idx)
                        keys.append(val.pub_key)
                        power += val.voting_power
                        if power > reach:
                            break
                    msgs = commit.vote_sign_bytes_many(chain_id, idxs)
                except Exception:
                    msgs = []
                for pos, (idx, key, msg) in enumerate(zip(idxs, keys, msgs)):
                    lanes[idx] = (pos, key)
                    items.append((key, msg, cs_sig(commit, idx)))
            out.append((lanes, items))
        return block_ids, part_sets, out

    def _submit_window_commits(
        self, scheduler, per_block, lanes_per_block, speculated, state
    ):
        """Submit every window block's lanes as its OWN request to the
        node-wide verification scheduler → one VerifyFuture per block:
        the quorum prefix of a block under the state's set
        (``per_block``), then the by-address lanes of the blocks past a
        validator-set change (``speculated``).

        All requests land inside one flush deadline, so the scheduler
        coalesces the whole window (plus whatever consensus/light have
        pending) into one dispatch — and because each block keeps its
        own verdict slice, a bad commit deep in the window no longer
        throws away its verified predecessors."""
        self._counts["window_blocks"] += len(per_block) + len(speculated)
        self._counts["light_lanes_submitted"] += sum(
            len(entries) for entries in per_block
        )
        self._counts["speculated_lanes"] += sum(
            len(items) for _, items in speculated
        )
        requests = [
            [
                (val.pub_key, lane_msgs[idx], lane_sigs[idx])
                for idx, val in entries
            ]
            for entries, (lane_msgs, lane_sigs) in zip(
                per_block, lanes_per_block
            )
        ] + [items for _, items in speculated]
        return [
            scheduler.submit(
                items,
                subsystem="blocksync",
                # block i of the window commits at this height; trace
                # tag only, never routing
                height=state.last_block_height + 1 + i,
            )
            for i, items in enumerate(requests)
        ]

    def _apply_window_pipelined(
        self, chain_id, state, val_hash, window, block_ids, part_sets,
        per_block, speculated, futs, needed, scheduler,
    ):
        """Apply the window with verification overlapped: every block's
        commit was already submitted (_submit_window_commits), so while
        block i applies, blocks i+1.. are still verifying in the
        scheduler — the next block's commit is in flight during the
        current block's apply. A failed verdict or quorum only costs the
        suffix: the verified prefix stays applied and the reference
        single-block path re-attributes the failure from there.

        The first ``len(per_block)`` blocks were built under the state's
        set and need every lane of their request valid; the blocks after
        them were speculated by address, and their quorum is walked here
        against the set the state holds once the blocks before them are
        applied (_tally_speculated).

        ``futs[i].result()`` takes no timeout, and needs none: a device
        dispatch that dies does not leave its flush unanswered. Under the
        node's supervisor the watchdog abandons it after ``[crypto]
        dispatch_timeout_ms`` and the host pool answers the flush
        (``BackendSupervisor.verify_items`` "never raises for
        device-plane reasons"); without one ``VerifyScheduler._verify``
        catches the failure and verifies on the CPU; a scheduler stopped
        over a wedged worker fails its pending futures, which raises
        here and ends the sync as any fatal error does. What is left is
        a flush worker that died itself, and that hangs every caller of
        the scheduler alike, ``validate_block``'s ``verify_commit`` and
        ``_sync_one`` among them: a timeout here would only move the
        hang to the fallback it fell to."""
        batchable = len(per_block)
        for i, fut in enumerate(futs):
            first, commit = window[i], window[i + 1].last_commit
            # a validator-set change the headers did not announce
            # invalidates the batch assumption from this point on —
            # re-verify individually
            if i < batchable and state.validators.hash() != val_hash:
                return state
            with self._stages.stage("sync.verdict_wait"):
                ok_all, mask = fut.result()
            if i < batchable:
                tallied = sum(val.voting_power for _, val in per_block[i])
                accepted = ok_all and tallied > needed
            else:
                with self._stages.stage("sync.tally"):
                    accepted = self._tally_speculated(
                        chain_id, state, block_ids[i], first, commit,
                        speculated[i - batchable][0], mask, scheduler,
                    )
            if not accepted:
                return self._sync_one(chain_id, state)
            try:
                with self._stages.stage("sync.validate"):
                    self.block_exec.validate_block(state, first)
            except Exception:
                # single-block path re-verifies and attributes the failure
                return self._sync_one(chain_id, state)
            state = self._apply_one(
                state, block_ids[i], first, part_sets[i], commit
            )
        return state

    def _tally_speculated(
        self, chain_id: str, state, block_id: BlockID, first: Block,
        commit, lanes: dict, mask, scheduler,
    ) -> bool:
        """verify_commit_light's walk for a block whose lanes were chosen
        before its validator set was known (_speculate_window), made
        against ``state.validators``, the set the applied chain gives
        this height: the commit's shape, then the signatures for the
        block in the set's order until their power passes 2/3 of its
        total. A lane of the walk that was speculated under the key the
        set holds at that index takes the device's verdict; any other (a
        seat that joined or changed hands inside the window, a
        speculation that stopped short) is verified now, through the
        scheduler. → whether the quorum verified; signatures the walk
        does not reach are not looked at, as upstream does not."""
        try:
            self._check_commit_shape(
                state, block_id, first.header.height, commit
            )
        except ValueError:
            return False
        vals = state.validators.validators
        needed = state.validators.total_voting_power() * 2 // 3
        walk, missed, tallied = [], [], 0
        for idx, csig in enumerate(commit.signatures):
            if not csig.for_block():
                continue
            walk.append(idx)
            lane = lanes.get(idx)
            if lane is None or lane[1] != vals[idx].pub_key:
                missed.append(idx)
            elif not mask[lane[0]]:
                return False
            tallied += vals[idx].voting_power
            if tallied > needed:
                break
        self._counts["tally_lanes"] += len(walk)
        self._counts["speculation_miss_lanes"] += len(missed)
        if tallied <= needed:
            return False
        if not missed:
            return True
        ok_all, _ = scheduler.submit(
            [
                (vals[idx].pub_key, msg, cs_sig(commit, idx))
                for idx, msg in zip(
                    missed, commit.vote_sign_bytes_many(chain_id, missed)
                )
            ],
            subsystem="blocksync",
            height=first.header.height,
        ).result()
        return ok_all

    def _verify_window_lanes(self, per_block, lanes_per_block, state):
        """Verify every window block's quorum prefix → one flat bool per
        entry, in block order (the caller's quorum loop consumes it
        positionally).

        Resident fast path: every batchable block re-verifies the SAME
        validator set, so under the tpu backend its pubkey rows stay on
        device across the window and each block dispatches the resident
        fixed executable (crypto/batch.py verify_commit_valset — 96 B/sig
        on the link instead of 128, one compiled program per chunk
        shape). Any ineligibility (backend, routing floor, non-ed25519
        keys, dead device plane) falls back to ONE BatchVerifier over
        the whole window. Accept/reject is identical either way."""
        from cometbft_tpu.crypto import ed25519 as ed

        vals = state.validators.validators
        if all(
            cryptobatch.resident_commit_eligible(
                len(entries), self.crypto_backend
            )
            for entries in per_block
        ) and all(isinstance(v.pub_key, ed.PubKeyEd25519) for v in vals):
            pub_keys = [v.pub_key.bytes() for v in vals]
            flat: List[bool] = []
            for entries, (lane_msgs, lane_sigs) in zip(
                per_block, lanes_per_block
            ):
                full = cryptobatch.verify_commit_valset(
                    pub_keys, lane_msgs, lane_sigs, self.crypto_backend
                )
                if full is None:
                    break  # shape rejected after all — take the bv path
                flat.extend(bool(full[idx]) for idx, _ in entries)
            else:
                return flat
        bv = cryptobatch.new_batch_verifier(
            self.crypto_backend, subsystem="blocksync"
        )
        for entries, (lane_msgs, lane_sigs) in zip(per_block, lanes_per_block):
            for idx, val in entries:
                bv.add(val.pub_key, lane_msgs[idx], lane_sigs[idx])
        _, mask = bv.verify() if bv.count() else (True, [])
        return mask

    def _sync_one(self, chain_id: str, state):
        """The reference's exact PeekTwoBlocks path (:348-404): verify one
        block, redo + punish on failure."""
        self._counts["sync_one_calls"] += 1
        first, second = self.pool.peek_two_blocks()
        if first is None or second is None:
            return state
        with self._stages.stage("sync.part_set"):
            parts = first.make_part_set(BLOCK_PART_SIZE_BYTES)
        block_id = BlockID(first.hash(), parts.header())
        try:
            with self._stages.stage("sync.verdict_wait"):
                state.validators.verify_commit_light(
                    chain_id,
                    block_id,
                    first.header.height,
                    second.last_commit,
                    backend=self.crypto_backend,
                )
            with self._stages.stage("sync.validate"):
                self.block_exec.validate_block(state, first)
        except Exception as exc:
            self._counts["blocks_refused"] += 1
            self.logger.error("error in validation", err=str(exc))
            for h in (first.header.height, second.header.height):
                peer_id = self.pool.redo_request(h)
                peer = (
                    self.switch.peers.get(peer_id)
                    if self.switch and peer_id
                    else None
                )
                if peer is not None:
                    self.switch.stop_peer_for_error(
                        peer, ValueError(f"blocksync validation error: {exc}")
                    )
            return state
        return self._apply_one(state, block_id, first, parts, second.last_commit)

    def _apply_one(self, state, block_id: BlockID, first: Block, parts, seen_commit):
        self.pool.pop_request()
        with self._stages.stage("sync.save_block"):
            self.store.save_block(first, parts, seen_commit)
        with self._stages.stage("sync.apply"):
            new_state, _ = self.block_exec.apply_block(state, block_id, first)
        self.blocks_synced += 1
        if self.blocks_synced % 100 == 0:
            self.logger.info(
                "blocksync rate", height=self.pool.height,
                max_peer_height=self.pool.max_peer_height(),
            )
        return new_state

    @staticmethod
    def _check_commit_shape(state, block_id: BlockID, height: int, commit) -> None:
        """The non-crypto preconditions of VerifyCommitLight."""
        if commit is None:
            raise ValueError("nil commit")
        if state.validators.size() != len(commit.signatures):
            raise ValueError(
                f"wrong signature count: {state.validators.size()} != "
                f"{len(commit.signatures)}"
            )
        if height != commit.height:
            raise ValueError(f"wrong commit height {commit.height} != {height}")
        if block_id != commit.block_id:
            raise ValueError("commit for a different block ID")
