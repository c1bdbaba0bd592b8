"""The Tendermint consensus state machine.

Reference: consensus/state.go — a single receive routine (:715-804)
serializes peer messages, own messages, and timeouts; every input is
WAL-logged before processing (own votes fsynced); step functions drive
NewRound → Propose → Prevote → (wait) → Precommit → (wait) → Commit with
the lock/unlock rules of the Tendermint algorithm; `add_vote` (:2009) is
the hot path that detects polkas and commits.

Differences from the reference are structural, not semantic: Python
threads + queues instead of goroutines + channels, and vote verification
flows through types.VoteSet → the pluggable batch-verify boundary.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, List, Optional

from cometbft_tpu.config import ConsensusConfig
from cometbft_tpu.crypto import batch as cryptobatch
from cometbft_tpu.consensus.messages import (
    BlockPartMessage,
    EndHeightMessage,
    EventDataRoundStateWAL,
    HasVoteMessage,
    MsgInfo,
    ProposalMessage,
    TimeoutInfo,
    VoteMessage,
    VoteSetMaj23Message,
)
from cometbft_tpu.consensus.round_state import (
    HeightVoteSet,
    RoundState,
    RoundStepType,
)
from cometbft_tpu.consensus.ticker import TimeoutTicker
from cometbft_tpu.consensus.wal import WAL, NilWAL
from cometbft_tpu.libs.log import Logger, new_nop_logger
from cometbft_tpu.libs.service import BaseService
from cometbft_tpu.proto.gogo import Timestamp
from cometbft_tpu.state import State as SMState
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.types.block import BlockID, Commit
from cometbft_tpu.types.event_bus import (
    EventDataCompleteProposal,
    EventDataNewRound,
    EventDataRoundState,
    EventDataVote,
    NopEventBus,
)
from cometbft_tpu.types.part_set import PartSet
from cometbft_tpu.types.proposal import Proposal
from cometbft_tpu.types.validator_set import ValidatorSet
from cometbft_tpu.types.vote import (
    SIGNED_MSG_TYPE_PRECOMMIT,
    SIGNED_MSG_TYPE_PREVOTE,
    Vote,
)
from cometbft_tpu.types.vote_set import ErrVoteConflictingVotes, VoteSet


class ConsensusState(BaseService):
    """One instance per node; owns the round state.

    External inputs arrive via `send_peer_message` / `send_internal` /
    `notify_txs_available`; the reactor subscribes to step/vote broadcasts
    via the callbacks below.
    """

    def __init__(
        self,
        config: ConsensusConfig,
        state: SMState,
        block_exec: BlockExecutor,
        block_store,
        tx_notifier=None,  # object with txs_available() -> bool (mempool)
        evpool=None,
        wal=None,
        event_bus=None,
        crypto_backend: Optional[str] = None,
        metrics=None,  # consensus.metrics.Metrics
        logger: Optional[Logger] = None,
    ):
        super().__init__("ConsensusState")
        from cometbft_tpu.consensus.metrics import Metrics

        self.config = config
        self.crypto_backend = crypto_backend
        self.metrics = metrics if metrics is not None else Metrics.nop()
        self.block_exec = block_exec
        self.block_store = block_store
        self.tx_notifier = tx_notifier
        self.evpool = evpool
        self.logger = logger or new_nop_logger()
        self.event_bus = event_bus if event_bus is not None else NopEventBus()

        self.rs = RoundState()
        self._mtx = threading.RLock()
        self.state: Optional[SMState] = None

        self.priv_validator = None
        self.priv_validator_pub_key = None

        self.peer_msg_queue: "queue.Queue[MsgInfo]" = queue.Queue(maxsize=1000)
        self.internal_msg_queue: "queue.Queue[MsgInfo]" = queue.Queue(maxsize=1000)
        self.n_batch_verify_calls = 0  # observability for the micro-batcher
        self.ticker = TimeoutTicker()
        self.wal = wal if wal is not None else NilWAL()
        self._wal_owned = wal is None

        # reactor hooks (subscribed via set_broadcast_hooks)
        self.on_new_round_step: Optional[Callable[[RoundState], None]] = None
        self.on_has_vote: Optional[Callable[[Vote], None]] = None
        self.on_valid_block: Optional[Callable[[RoundState], None]] = None

        self._receive_thread: Optional[threading.Thread] = None
        self._done_height = threading.Event()
        self.n_steps = 0

        self.update_to_state(state)
        self._reconstruct_last_commit_if_needed(state)

    # -- lifecycle -----------------------------------------------------------

    def set_priv_validator(self, pv) -> None:
        with self._mtx:
            self.priv_validator = pv
            if pv is not None:
                self.priv_validator_pub_key = pv.get_pub_key()

    def set_wal(self, wal) -> None:
        self.wal = wal
        self._wal_owned = False

    def on_start(self) -> None:
        if isinstance(self.wal, NilWAL) and self._wal_owned and self.config.wal_path:
            wal = WAL(self.config.wal_file())
            wal.start()
            self.wal = wal
        self._wal_catchup()
        self._check_double_signing_risk()
        self.ticker.start()
        self._receive_thread = threading.Thread(
            target=self._receive_routine, daemon=True, name="cs-receive"
        )
        self._receive_thread.start()
        self._schedule_round0(self.rs)

    def _wal_catchup(self) -> None:
        """Reference State.OnStart's doWALCatchup loop: we may have lost
        in-flight votes/locks if the process crashed — replay the WAL
        tail before the receive routine starts. Corruption gets ONE
        repair attempt (truncate after the last valid record —
        reference repairWalFile, state.go:2359); any other replay error
        is logged and consensus proceeds (reference behavior — e.g. a
        statesync jump leaves no marker for the new height)."""
        from cometbft_tpu.consensus.replay import catchup_replay
        from cometbft_tpu.consensus.wal import WALDecodeError, repair_wal_tail

        if isinstance(self.wal, NilWAL):
            return
        if getattr(self, "_wal_catchup_done", False):
            return  # an external catchup_replay already ran (tests, tools)
        repaired = False
        while True:
            try:
                catchup_replay(self, self.rs.height)
                return
            except WALDecodeError as exc:
                if repaired:
                    raise
                self.logger.error(
                    "WAL corrupted; repairing tail", err=str(exc)
                )
                if not repair_wal_tail(self.wal):
                    raise
                repaired = True
            except Exception as exc:  # noqa: BLE001 - reference logs all
                self.logger.error(
                    "WAL replay failed; proceeding to consensus",
                    err=str(exc),
                )
                self._wal_catchup_done = True  # attempted; never re-run
                return

    def _check_double_signing_risk(self) -> None:
        """Reference consensus/state.go:2286 checkDoubleSigningRisk
        (called from OnStart): with double_sign_check_height > 0, refuse
        to start if our key already signed a commit within the last N
        heights — the operator likely restored the sign state from an
        old backup, and signing fresh votes from it risks equivocation.
        Off by default, like the reference."""
        n = self.config.double_sign_check_height
        height = self.rs.height
        if (
            n <= 0
            or height <= 0
            or self.priv_validator is None
            or self.priv_validator_pub_key is None
            or self.block_store is None
        ):
            return
        val_addr = self.priv_validator_pub_key.address()
        for i in range(1, min(n, height)):
            commit = self.block_store.load_seen_commit(height - i)
            if commit is None:
                continue
            for sig in commit.signatures:
                if sig.for_block() and sig.validator_address == val_addr:
                    raise RuntimeError(
                        f"found signature from our key at height "
                        f"{height - i} within double_sign_check_height="
                        f"{n}; the sign state may be restored from an "
                        "old backup — refusing to start"
                    )

    def on_stop(self) -> None:
        self.ticker.stop()
        # The WAL must outlive the receive routine (the reference stops
        # the WAL from receiveRoutine's exit path): a finalize in flight
        # still needs write_sync(#ENDHEIGHT) to LAND on disk — stopping
        # the WAL first silently drops the marker while apply_block goes
        # on to persist state, leaving durable state AHEAD of the WAL,
        # and the next start refuses catchup_replay ("WAL has no
        # #ENDHEIGHT h-1"). is_running() is already False here (service
        # stop order), so the routine exits within one iteration.
        t = getattr(self, "_receive_thread", None)
        if t is not None and t is not threading.current_thread():
            # 180 s: must outlast the longest bounded stall a finalize
            # can hit (a supervised device dispatch is abandoned after
            # [crypto] dispatch_timeout_ms, 60 s by default, and the CPU
            # re-verify of a large commit follows it)
            t.join(timeout=180.0)
            if t.is_alive():
                # stopping the WAL now would reintroduce the dropped-
                # #ENDHEIGHT bug; leave it running (its flush thread is
                # a daemon — a late write_sync still lands) and say so
                self.logger.error(
                    "receive routine did not exit before stop timeout; "
                    "leaving WAL running so in-flight writes land"
                )
                return
        if not isinstance(self.wal, NilWAL):
            try:
                self.wal.stop()
            except Exception:
                pass

    # -- accessors -----------------------------------------------------------

    def get_round_state(self) -> RoundState:
        with self._mtx:
            import copy

            rs = copy.copy(self.rs)
            return rs

    def height(self) -> int:
        with self._mtx:
            return self.rs.height

    def is_proposer(self, address: bytes) -> bool:
        with self._mtx:
            return (
                self.rs.validators.proposer is not None
                and self.rs.validators.proposer.address == address
            )

    # -- input plumbing ------------------------------------------------------

    def send_peer_message(self, msg, peer_id: str) -> None:
        self.peer_msg_queue.put(MsgInfo(msg, peer_id))

    def send_internal(self, msg) -> None:
        # Never block: the only consumer is the receive thread, which may be
        # the caller (via _decide_proposal) — a blocking put on a full queue
        # would deadlock the node. Mirror sendInternalMessage's goroutine
        # fallback (reference consensus/state.go:1181-1190).
        mi = MsgInfo(msg, "")
        try:
            self.internal_msg_queue.put_nowait(mi)
        except queue.Full:
            threading.Thread(
                target=self.internal_msg_queue.put, args=(mi,), daemon=True
            ).start()

    def notify_txs_available(self) -> None:
        """Mempool → consensus: txs exist (for CreateEmptyBlocks=false).

        Never block: with the builtin app this fires ON the consensus
        thread itself (commit → mempool update/recheck callbacks), whose
        queue has no other consumer — a blocking put on a full queue
        would deadlock the node (same hazard send_internal documents).

        A full queue DROPS the notification instead of parking a thread
        on it: the signal is level-triggered (the mempool still holds
        txs, so the next height's mempool update re-fires it), and a
        queue already packed with peer messages will wake the consensus
        loop anyway. send_internal keeps its goroutine-mirroring thread
        fallback — votes and proposals are edge-triggered and MUST land."""
        mi = MsgInfo(None, "@txs")
        try:
            self.peer_msg_queue.put_nowait(mi)
        except queue.Full:
            pass

    # -- the serialized event loop ------------------------------------------

    def _receive_routine(self) -> None:
        while self.is_running():
            mi = None
            try:
                mi = self.internal_msg_queue.get_nowait()
                internal = True
            except queue.Empty:
                internal = False
            if mi is None:
                try:
                    ti = self.ticker.tock_chan.get_nowait()
                    # timeouts are replayed after a crash — log the real
                    # TimeoutInfo (state.go:790), not just an event
                    self.wal.write(ti)
                    with self._mtx:
                        self._handle_timeout(ti)
                    continue
                except queue.Empty:
                    pass
                try:
                    mi = self.peer_msg_queue.get(timeout=0.01)
                    internal = False
                except queue.Empty:
                    continue
            if mi.msg is None:  # txs-available poke
                with self._mtx:
                    self._handle_txs_available()
                continue
            if internal:
                # own proposals/votes/parts must hit disk before the network
                self.wal.write_sync(mi)
                with self._mtx:
                    self._handle_msg(mi)
                continue
            # micro-batching (north star, SURVEY §7 "latency vs throughput"):
            # drain whatever else is already queued, batch-verify all the
            # drained vote signatures in ONE BatchVerifier call (pure
            # function, no state), then run the exact serial discipline per
            # message: WAL-write it, process it. Interleaving is preserved —
            # in particular #ENDHEIGHT lands between the message that
            # finalized the commit and the next one, exactly as unbatched
            # (crash replay depends on that ordering).
            batch = self._drain_peer_queue(mi)
            self._batch_preverify_votes(batch)
            for m in batch:
                if m.msg is None:  # txs-available poke drained mid-batch
                    with self._mtx:
                        self._handle_txs_available()
                    continue
                self.wal.write(m)
                with self._mtx:
                    self._handle_msg(m)

    MAX_QUEUE_DRAIN = 1024

    def _drain_peer_queue(self, first: MsgInfo) -> list:
        """first + everything already sitting in the peer queue (bounded).
        Order is preserved exactly — the WAL and the handlers see the same
        sequence a serial loop would have."""
        batch = [first]
        while len(batch) < self.MAX_QUEUE_DRAIN:
            try:
                nxt = self.peer_msg_queue.get_nowait()
            except queue.Empty:
                break
            batch.append(nxt)  # txs pokes (msg=None) stay in order
        return batch

    def _resolve_vote_target(self, vote: Vote):
        """The VoteSet this vote would land in (mirrors _add_vote's routing)
        or None when it can't be known without processing."""
        rs = self.rs
        if (
            vote.height + 1 == rs.height
            and vote.type == SIGNED_MSG_TYPE_PRECOMMIT
        ):
            return rs.last_commit
        if vote.height == rs.height and rs.votes is not None:
            return rs.votes._get_vote_set(vote.round, vote.type)
        return None

    def _batch_preverify_votes(self, batch: list) -> None:
        """One BatchVerifier call covering every drained vote whose target
        set and validator resolve cleanly; verified votes carry a marker
        that lets VoteSet._add_vote skip its serial signature check. Any
        vote that doesn't resolve (or fails) goes through the normal serial
        path unchanged."""
        entries = []  # (vote, chain_id, pub_key)
        with self._mtx:
            for m in batch:
                if not isinstance(m.msg, VoteMessage) or m.msg.vote is None:
                    continue
                vote = m.msg.vote
                if vote.validator_index < 0 or not vote.signature:
                    reason = (
                        "negative_index"
                        if vote.validator_index < 0
                        else "empty_signature"
                    )
                    self.metrics.preverify_dropped.with_labels(
                        reason=reason
                    ).add()
                    self.logger.debug(
                        "vote excluded from batch preverification",
                        reason=reason,
                        height=vote.height,
                        round=vote.round,
                        validator_index=vote.validator_index,
                    )
                    continue
                vs = self._resolve_vote_target(vote)
                if vs is None:
                    continue
                addr, val = vs.val_set.get_by_index(vote.validator_index)
                if val is None or addr != vote.validator_address:
                    continue
                entries.append((vote, vs.chain_id, val.pub_key))
        if len(entries) < 2:
            return  # nothing to batch; serial path handles singletons
        bv = cryptobatch.new_batch_verifier(
            self.crypto_backend, subsystem="consensus"
        )
        for vote, chain_id, pub_key in entries:
            bv.add(pub_key, vote.sign_bytes(chain_id), vote.signature)
        self.n_batch_verify_calls += 1
        _, mask = bv.verify()
        for (vote, chain_id, pub_key), ok in zip(entries, mask):
            if ok:
                vote.sig_batch_verified = (chain_id, pub_key.bytes())

    def _handle_msg(self, mi: MsgInfo) -> None:
        msg, peer_id = mi.msg, mi.peer_id
        try:
            if isinstance(msg, ProposalMessage):
                self._set_proposal(msg.proposal)
            elif isinstance(msg, BlockPartMessage):
                self._add_proposal_block_part(msg, peer_id)
            elif isinstance(msg, VoteMessage):
                self._try_add_vote(msg.vote, peer_id)
            else:
                self.logger.error("unknown msg type", type=str(type(msg)))
        except Exception as e:  # reference logs and moves on
            self.logger.error(
                "failed to process message",
                height=self.rs.height,
                round=self.rs.round,
                err=str(e),
            )

    def _handle_timeout(self, ti: TimeoutInfo) -> None:
        rs = self.rs
        if (
            ti.height != rs.height
            or ti.round < rs.round
            or (ti.round == rs.round and ti.step < rs.step)
        ):
            return
        if ti.step == RoundStepType.NEW_HEIGHT:
            self._enter_new_round(ti.height, 0)
        elif ti.step == RoundStepType.NEW_ROUND:
            self._enter_propose(ti.height, 0)
        elif ti.step == RoundStepType.PROPOSE:
            self.event_bus.publish_event_timeout_propose(
                EventDataRoundState(rs.height, rs.round, rs.step.short())
            )
            self._enter_prevote(ti.height, ti.round)
        elif ti.step == RoundStepType.PREVOTE_WAIT:
            self.event_bus.publish_event_timeout_wait(
                EventDataRoundState(rs.height, rs.round, rs.step.short())
            )
            self._enter_precommit(ti.height, ti.round)
        elif ti.step == RoundStepType.PRECOMMIT_WAIT:
            self.event_bus.publish_event_timeout_wait(
                EventDataRoundState(rs.height, rs.round, rs.step.short())
            )
            self._enter_precommit(ti.height, ti.round)
            self._enter_new_round(ti.height, ti.round + 1)

    def _handle_txs_available(self) -> None:
        """Reference: handleTxsAvailable :947-972."""
        rs = self.rs
        if rs.round != 0:  # only the first round of a height waits on txs (:953)
            return
        if rs.step == RoundStepType.NEW_HEIGHT:
            # still in the commit window from the prior block: preserve the
            # remaining timeout_commit (+1ms), don't truncate it (:964)
            remaining = max(rs.start_time - time.monotonic(), 0.0) + 0.001
            self._schedule_timeout(
                remaining, rs.height, 0, RoundStepType.NEW_ROUND
            )
        elif rs.step == RoundStepType.NEW_ROUND:
            # commit window elapsed; we were only waiting for txs (:967)
            self._enter_propose(rs.height, 0)

    # -- state transitions ---------------------------------------------------

    def update_to_state(self, state: SMState) -> None:
        """Reference: updateToState :1700 — reset round state for the next
        height after a commit (or at boot)."""
        rs = self.rs
        if rs.commit_round > -1 and 0 < rs.height and rs.height != state.last_block_height:
            raise RuntimeError(
                f"updateToState expected state height {rs.height} but got "
                f"{state.last_block_height}"
            )
        if self.state is not None and not self.state.is_empty():
            if self.state.last_block_height > 0 and (
                self.state.last_block_height + 1 != rs.height
            ):
                raise RuntimeError("inconsistent cs.state.LastBlockHeight+1 vs cs.Height")
            if state.last_block_height <= self.state.last_block_height:
                # ignore duplicate/older state
                self._new_step()
                return

        validators = state.validators
        if state.last_block_height == 0:  # genesis
            last_precommits = None
        elif rs.commit_round > -1 and rs.votes is not None:
            precommits = rs.votes.precommits(rs.commit_round)
            if precommits is None or not precommits.has_two_thirds_majority():
                raise RuntimeError("wanted to form a commit, but precommits lack majority")
            last_precommits = precommits
        else:
            last_precommits = self.rs.last_commit

        height = state.last_block_height + 1
        if height == 1:
            height = state.initial_height

        rs.height = height
        rs.round = 0
        rs.step = RoundStepType.NEW_HEIGHT
        self.metrics.height.set(height)
        if rs.commit_time == 0:
            rs.start_time = time.monotonic() + self.config.commit_time()
        else:
            rs.start_time = rs.commit_time + self.config.commit_time()
        rs.validators = validators
        rs.proposal = None
        rs.proposal_block = None
        rs.proposal_block_parts = None
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        rs.valid_round = -1
        rs.valid_block = None
        rs.valid_block_parts = None
        rs.votes = HeightVoteSet(state.chain_id, height, validators)
        rs.commit_round = -1
        rs.last_commit = last_precommits
        rs.last_validators = state.last_validators
        rs.triggered_timeout_precommit = False
        self.state = state
        self._new_step()

    def _reconstruct_last_commit_if_needed(self, state: SMState) -> None:
        """Reference: reconstructLastCommit — rebuild LastCommit votes from
        the block store's seen commit after a restart."""
        if state.last_block_height == 0:
            return
        if self.block_store is None:
            return
        seen = self.block_store.load_seen_commit(state.last_block_height)
        if seen is None:
            return
        from cometbft_tpu.types.block import commit_to_vote_set

        try:
            vote_set = commit_to_vote_set(
                state.chain_id, seen, state.last_validators
            )
        except Exception:
            return
        self.rs.last_commit = vote_set

    def _new_step(self) -> None:
        self.n_steps += 1
        rs = self.rs
        self.metrics.mark_step(rs.step.short())
        self.event_bus.publish_event_new_round_step(
            EventDataRoundState(rs.height, rs.round, rs.step.short())
        )
        if self.on_new_round_step is not None:
            self.on_new_round_step(rs)

    def _schedule_round0(self, rs: RoundState) -> None:
        sleep = max(rs.start_time - time.monotonic(), 0.0)
        self._schedule_timeout(sleep, rs.height, 0, RoundStepType.NEW_HEIGHT)

    def _schedule_timeout(
        self, duration_s: float, height: int, round_: int, step: RoundStepType
    ) -> None:
        self.ticker.schedule_timeout(
            TimeoutInfo(duration_s, height, round_, int(step))
        )

    def _enter_new_round(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.step != RoundStepType.NEW_HEIGHT
        ):
            return
        self.logger.debug("entering new round", height=height, round=round_)

        validators = rs.validators
        if rs.round < round_:
            validators = validators.copy()
            validators.increment_proposer_priority(round_ - rs.round)
        self.metrics.rounds.set(round_)
        rs.round = round_
        rs.step = RoundStepType.NEW_ROUND
        rs.validators = validators
        if round_ != 0:
            rs.proposal = None
            rs.proposal_block = None
            rs.proposal_block_parts = None
        rs.votes.set_round(round_ + 1)
        rs.triggered_timeout_precommit = False

        self.event_bus.publish_event_new_round(
            EventDataNewRound(
                height, round_, rs.step.short(),
                validators.proposer.address if validators.proposer else b"",
            )
        )
        self._new_step()

        # reference config.WaitForTxs(): empty blocks off OR rate-limited
        # by the interval knob (which is otherwise a no-op)
        wait_for_txs = (
            (
                not self.config.create_empty_blocks
                or self.config.create_empty_blocks_interval_ns > 0
            )
            and round_ == 0
            and not self._need_proof_block(height)
        )
        if wait_for_txs:
            if self.config.create_empty_blocks_interval_ns > 0:
                self._schedule_timeout(
                    self.config.create_empty_blocks_interval_ns / 1e9,
                    height, round_, RoundStepType.NEW_ROUND,
                )
            if self.tx_notifier is not None and self.tx_notifier.txs_available():
                self._enter_propose(height, round_)
        else:
            self._enter_propose(height, round_)

    def _need_proof_block(self, height: int) -> bool:
        if self.state is None or height == self.state.initial_height:
            return True
        if self.block_store is None:
            return False
        meta = self.block_store.load_block_meta(height - 1)
        if meta is None:
            return True
        return self.state.app_hash != meta.header.app_hash

    def _enter_propose(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and RoundStepType.PROPOSE <= rs.step
        ):
            return
        rs.round = round_
        rs.step = RoundStepType.PROPOSE
        self._new_step()

        self._schedule_timeout(
            self.config.propose_timeout(round_), height, round_,
            RoundStepType.PROPOSE,
        )

        if self.priv_validator is not None and self.priv_validator_pub_key is not None:
            address = self.priv_validator_pub_key.address()
            if rs.validators.has_address(address) and self.is_proposer(address):
                self._decide_proposal(height, round_)

        if self._is_proposal_complete():
            self._enter_prevote(height, rs.round)

    def _proposal_commit(self, height: int):
        """The last-commit a proposal at `height` must carry, or None
        when it cannot be formed yet (:1131's selection; shared with the
        maverick's equivocating proposal builder in misbehavior.py)."""
        if height == (self.state.initial_height if self.state else 1):
            return Commit(0, 0, BlockID(), [])
        rs = self.rs
        if rs.last_commit is not None and rs.last_commit.has_two_thirds_majority():
            return rs.last_commit.make_commit()
        return None

    def _decide_proposal(self, height: int, round_: int) -> None:
        """Reference: defaultDecideProposal :1131."""
        rs = self.rs
        if rs.valid_block is not None:
            block, block_parts = rs.valid_block, rs.valid_block_parts
        else:
            commit = self._proposal_commit(height)
            if commit is None:
                self.logger.error("propose step; cannot propose without commit")
                return
            proposer_addr = self.priv_validator_pub_key.address()
            block, block_parts = self.block_exec.create_proposal_block(
                height, self.state, commit, proposer_addr
            )

        block_id = BlockID(block.hash(), block_parts.header())
        proposal = Proposal(
            height=height,
            round=round_,
            pol_round=rs.valid_round,
            block_id=block_id,
            timestamp=Timestamp.now(),
        )
        try:
            self.priv_validator.sign_proposal(self.state.chain_id, proposal)
        except Exception as e:
            self.logger.error("propose step; failed signing proposal", err=str(e))
            return

        self.send_internal(ProposalMessage(proposal))
        for i in range(block_parts.total()):
            part = block_parts.get_part(i)
            self.send_internal(BlockPartMessage(height, round_, part))
        self.logger.info("signed proposal", height=height, round=round_)

    def _is_proposal_complete(self) -> bool:
        rs = self.rs
        if rs.proposal is None or rs.proposal_block is None:
            return False
        if rs.proposal.pol_round < 0:
            return True
        prevotes = rs.votes.prevotes(rs.proposal.pol_round)
        return prevotes is not None and prevotes.has_two_thirds_majority()

    def _enter_prevote(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and RoundStepType.PREVOTE <= rs.step
        ):
            return
        rs.round = round_
        rs.step = RoundStepType.PREVOTE
        self._new_step()
        self._do_prevote(height, round_)

    def _do_prevote(self, height: int, round_: int) -> None:
        """Reference: defaultDoPrevote :1259."""
        rs = self.rs
        if rs.locked_block is not None:
            self._sign_add_vote(
                SIGNED_MSG_TYPE_PREVOTE,
                rs.locked_block.hash(),
                rs.locked_block_parts.header(),
            )
            return
        if rs.proposal_block is None:
            self._sign_add_vote(SIGNED_MSG_TYPE_PREVOTE, b"", None)
            return
        try:
            self.block_exec.validate_block(self.state, rs.proposal_block)
        except Exception as e:
            self.logger.error("prevote step: ProposalBlock is invalid", err=str(e))
            self._sign_add_vote(SIGNED_MSG_TYPE_PREVOTE, b"", None)
            return
        self._sign_add_vote(
            SIGNED_MSG_TYPE_PREVOTE,
            rs.proposal_block.hash(),
            rs.proposal_block_parts.header(),
        )

    def _enter_prevote_wait(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and RoundStepType.PREVOTE_WAIT <= rs.step
        ):
            return
        prevotes = rs.votes.prevotes(round_)
        if prevotes is None or not prevotes.has_two_thirds_any():
            return
        rs.round = round_
        rs.step = RoundStepType.PREVOTE_WAIT
        self._new_step()
        self._schedule_timeout(
            self.config.prevote_timeout(round_), height, round_,
            RoundStepType.PREVOTE_WAIT,
        )

    def _enter_precommit(self, height: int, round_: int) -> None:
        """Reference: enterPrecommit :1329 — the lock/unlock decision."""
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and RoundStepType.PRECOMMIT <= rs.step
        ):
            return
        rs.round = round_
        rs.step = RoundStepType.PRECOMMIT
        self._new_step()

        prevotes = rs.votes.prevotes(round_)
        block_id, ok = (prevotes.two_thirds_majority() if prevotes else (None, False))

        if not ok:
            self._sign_add_vote(SIGNED_MSG_TYPE_PRECOMMIT, b"", None)
            return

        self.event_bus.publish_event_polka(
            EventDataRoundState(rs.height, rs.round, rs.step.short())
        )

        if block_id.is_zero():
            # +2/3 prevoted nil: unlock and precommit nil
            if rs.locked_block is not None:
                rs.locked_round = -1
                rs.locked_block = None
                rs.locked_block_parts = None
                self.event_bus.publish_event_unlock(
                    EventDataRoundState(rs.height, rs.round, rs.step.short())
                )
            self._sign_add_vote(SIGNED_MSG_TYPE_PRECOMMIT, b"", None)
            return

        if rs.locked_block is not None and rs.locked_block.hash() == block_id.hash:
            rs.locked_round = round_
            self.event_bus.publish_event_relock(
                EventDataRoundState(rs.height, rs.round, rs.step.short())
            )
            self._sign_add_vote(
                SIGNED_MSG_TYPE_PRECOMMIT, block_id.hash, block_id.part_set_header
            )
            return

        if rs.proposal_block is not None and rs.proposal_block.hash() == block_id.hash:
            try:
                self.block_exec.validate_block(self.state, rs.proposal_block)
            except Exception as e:
                raise RuntimeError(f"precommit step: +2/3 prevoted for an invalid block: {e}")
            rs.locked_round = round_
            rs.locked_block = rs.proposal_block
            rs.locked_block_parts = rs.proposal_block_parts
            self.event_bus.publish_event_lock(
                EventDataRoundState(rs.height, rs.round, rs.step.short())
            )
            self._sign_add_vote(
                SIGNED_MSG_TYPE_PRECOMMIT, block_id.hash, block_id.part_set_header
            )
            return

        # +2/3 prevoted for a block we don't have: unlock, fetch parts, nil
        rs.locked_round = -1
        rs.locked_block = None
        rs.locked_block_parts = None
        if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
            block_id.part_set_header
        ):
            rs.proposal_block = None
            rs.proposal_block_parts = PartSet.from_header(block_id.part_set_header)
        self.event_bus.publish_event_unlock(
            EventDataRoundState(rs.height, rs.round, rs.step.short())
        )
        self._sign_add_vote(SIGNED_MSG_TYPE_PRECOMMIT, b"", None)

    def _enter_precommit_wait(self, height: int, round_: int) -> None:
        rs = self.rs
        if rs.height != height or round_ < rs.round or (
            rs.round == round_ and rs.triggered_timeout_precommit
        ):
            return
        precommits = rs.votes.precommits(round_)
        if precommits is None or not precommits.has_two_thirds_any():
            return
        rs.triggered_timeout_precommit = True
        self._new_step()
        self._schedule_timeout(
            self.config.precommit_timeout(round_), height, round_,
            RoundStepType.PRECOMMIT_WAIT,
        )

    def _enter_commit(self, height: int, commit_round: int) -> None:
        rs = self.rs
        if rs.height != height or RoundStepType.COMMIT <= rs.step:
            return
        rs.step = RoundStepType.COMMIT
        rs.commit_round = commit_round
        rs.commit_time = time.monotonic()
        self._new_step()

        precommits = rs.votes.precommits(commit_round)
        block_id, ok = precommits.two_thirds_majority()
        if not ok:
            raise RuntimeError("RunActionCommit() expects +2/3 precommits")

        if rs.locked_block is not None and rs.locked_block.hash() == block_id.hash:
            rs.proposal_block = rs.locked_block
            rs.proposal_block_parts = rs.locked_block_parts
        if rs.proposal_block is None or rs.proposal_block.hash() != block_id.hash:
            if rs.proposal_block_parts is None or not rs.proposal_block_parts.has_header(
                block_id.part_set_header
            ):
                rs.proposal_block = None
                rs.proposal_block_parts = PartSet.from_header(
                    block_id.part_set_header
                )
                if self.on_valid_block is not None:
                    self.on_valid_block(rs)
        self._try_finalize_commit(height)

    def _try_finalize_commit(self, height: int) -> None:
        rs = self.rs
        if rs.height != height:
            return
        precommits = rs.votes.precommits(rs.commit_round)
        block_id, ok = precommits.two_thirds_majority()
        if not ok or block_id.is_zero():
            return
        if rs.proposal_block is None or rs.proposal_block.hash() != block_id.hash:
            return
        self._finalize_commit(height)

    def _finalize_commit(self, height: int) -> None:
        """Reference: finalizeCommit :1574 — the persistence choreography."""
        from cometbft_tpu.libs import fail

        rs = self.rs
        block, block_parts = rs.proposal_block, rs.proposal_block_parts
        precommits = rs.votes.precommits(rs.commit_round)
        block_id, _ = precommits.two_thirds_majority()

        if not block_parts.has_header(block_id.part_set_header):
            raise RuntimeError("expected ProposalBlockParts header to match commit header")
        if block.hash() != block_id.hash:
            raise RuntimeError("cannot finalize commit; proposal block does not hash to commit hash")
        self.block_exec.validate_block(self.state, block)

        fail.fail()  # before block save
        if self.block_store is not None and self.block_store.height() < block.header.height:
            seen_commit = precommits.make_commit()
            self.block_store.save_block(block, block_parts, seen_commit)
        fail.fail()  # block saved, WAL ENDHEIGHT not yet written

        self.wal.write_sync(EndHeightMessage(height))
        fail.fail()  # ENDHEIGHT written, ApplyBlock not yet run

        state_copy = self.state.copy()
        state_copy, retain_height = self.block_exec.apply_block(
            state_copy, block_id, block
        )
        fail.fail()  # ApplyBlock done

        self._record_metrics(height, block)

        if retain_height > 0 and self.block_store is not None:
            try:
                base = self.block_store.base()
                pruned = self.block_store.prune_blocks(retain_height)
                self.logger.info("pruned blocks", pruned=pruned, retain_height=retain_height)
                # the reference prunes the state artifacts over the same
                # span (consensus/state.go:1717 PruneStates) — without
                # this the per-height validators/params/ABCI-responses
                # grow forever on a pruning chain
                if 0 < base < retain_height:
                    self.block_exec.store().prune_states(base, retain_height)
            except Exception as e:
                self.logger.error("failed to prune blocks", err=str(e))

        self.update_to_state(state_copy)
        self._schedule_round0(self.rs)

    def _record_metrics(self, height: int, block) -> None:
        """Reference: recordMetrics (consensus/state.go:1729-1808)."""
        m = self.metrics
        state = self.state
        m.validators.set(state.validators.size())
        m.validators_power.set(state.validators.total_voting_power())

        if height > state.initial_height and state.last_validators is not None:
            # absent = no signature at all; a nil vote still counts as
            # present (recordMetrics uses commitSig.Absent())
            missing, missing_power = 0, 0
            vals = state.last_validators.validators
            sigs = block.last_commit.signatures
            for i, val in enumerate(vals):
                if i < len(sigs) and sigs[i].is_absent():
                    missing += 1
                    missing_power += val.voting_power
            m.missing_validators.set(missing)
            m.missing_validators_power.set(missing_power)

        byz, byz_power = 0, 0
        for ev in block.evidence:
            addr = getattr(
                getattr(ev, "vote_a", None), "validator_address", None
            )
            if addr is not None:
                _, val = state.validators.get_by_address(addr)
                if val is not None:
                    byz += 1
                    byz_power += val.voting_power
        m.byzantine_validators.set(byz)
        m.byzantine_validators_power.set(byz_power)

        if height > 1 and self.block_store is not None:
            prev = self.block_store.load_block_meta(height - 1)
            if prev is not None:
                dt = (
                    block.header.time.seconds - prev.header.time.seconds
                ) + (block.header.time.nanos - prev.header.time.nanos) / 1e9
                m.block_interval_seconds.observe(dt)

        self._record_prevote_delays(m)

        num_txs = len(block.data.txs)
        m.num_txs.set(num_txs)
        m.total_txs.add(num_txs)
        if self.block_store is not None:
            meta = self.block_store.load_block_meta(height)
            if meta is not None:
                m.block_size_bytes.set(meta.block_size)
        m.committed_height.set(height)

    def _record_prevote_delays(self, m) -> None:
        """Reference: calculatePrevoteMessageDelayMetrics (:2310) — walk
        the commit round's prevotes in timestamp order; the vote that tips
        cumulative power over 2/3 sets the quorum delay, and a 100%-
        prevoted round also sets the full delay."""
        rs = self.rs
        if rs.proposal is None or rs.votes is None or rs.commit_round < 0:
            return
        prevotes = rs.votes.prevotes(rs.commit_round)
        if prevotes is None:
            return
        cast = []
        for v in prevotes.list_votes():
            _, val = rs.validators.get_by_address(v.validator_address)
            if val is not None:
                cast.append((v, val.voting_power))
        if not cast:
            return
        cast.sort(key=lambda e: (e[0].timestamp.seconds, e[0].timestamp.nanos))
        total = rs.validators.total_voting_power()
        prop_ts = rs.proposal.timestamp

        def delay(ts):
            return (ts.seconds - prop_ts.seconds) + (
                ts.nanos - prop_ts.nanos
            ) / 1e9

        cumulative = 0
        quorum_set = False
        for vote, power in cast:
            cumulative += power
            if not quorum_set and cumulative * 3 > total * 2:
                m.quorum_prevote_delay.set(delay(vote.timestamp))
                quorum_set = True
        if cumulative == total:
            m.full_prevote_delay.set(delay(cast[-1][0].timestamp))

    # -- proposals -----------------------------------------------------------

    def _set_proposal(self, proposal: Proposal) -> None:
        """Reference: defaultSetProposal :1817."""
        rs = self.rs
        if rs.proposal is not None:
            return
        if proposal.height != rs.height or proposal.round != rs.round:
            return
        if proposal.pol_round < -1 or (
            proposal.pol_round >= 0 and proposal.pol_round >= proposal.round
        ):
            raise ValueError("error invalid proposal POL round")
        proposer = rs.validators.proposer
        if proposer is None:
            return
        if not proposer.pub_key.verify_signature(
            proposal.sign_bytes(self.state.chain_id), proposal.signature
        ):
            raise ValueError("error invalid proposal signature")
        rs.proposal = proposal
        if rs.proposal_block_parts is None:
            rs.proposal_block_parts = PartSet.from_header(
                proposal.block_id.part_set_header
            )
        self.logger.info("received proposal", proposal_height=proposal.height)

    def _add_proposal_block_part(self, msg: BlockPartMessage, peer_id: str) -> bool:
        """Reference: addProposalBlockPart :1856."""
        rs = self.rs
        if msg.height != rs.height:
            return False
        if rs.proposal_block_parts is None:
            return False
        added = rs.proposal_block_parts.add_part(msg.part)
        self.metrics.block_gossip_parts_received.add(1)
        if not added:
            return False
        self.metrics.block_parts.add(1)
        if rs.proposal_block_parts.is_complete():
            from cometbft_tpu.types.block import Block

            data = rs.proposal_block_parts.get_reader()
            rs.proposal_block = Block.decode(data)
            self.event_bus.publish_event_complete_proposal(
                EventDataCompleteProposal(
                    rs.height, rs.round, rs.step.short(),
                    BlockID(rs.proposal_block.hash(), rs.proposal_block_parts.header()),
                )
            )
            self._handle_complete_proposal(msg.height)
        return True

    def _handle_complete_proposal(self, height: int) -> None:
        """Reference: handleCompleteProposal :1925."""
        rs = self.rs
        prevotes = rs.votes.prevotes(rs.round)
        block_id, has_two_thirds = (
            prevotes.two_thirds_majority() if prevotes else (None, False)
        )
        if has_two_thirds and not block_id.is_zero() and rs.valid_round < rs.round:
            if rs.proposal_block.hash() == block_id.hash:
                rs.valid_round = rs.round
                rs.valid_block = rs.proposal_block
                rs.valid_block_parts = rs.proposal_block_parts

        if rs.step <= RoundStepType.PROPOSE and self._is_proposal_complete():
            self._enter_prevote(height, rs.round)
            if has_two_thirds:
                self._enter_precommit(height, rs.round)
        elif rs.step == RoundStepType.COMMIT:
            self._try_finalize_commit(height)

    # -- votes ---------------------------------------------------------------

    def _try_add_vote(self, vote: Vote, peer_id: str) -> bool:
        try:
            return self._add_vote(vote, peer_id)
        except ErrVoteConflictingVotes as e:
            if self.priv_validator_pub_key is not None and (
                vote.validator_address == self.priv_validator_pub_key.address()
            ):
                self.logger.error(
                    "found conflicting vote from ourselves; did you unsafe_reset a validator?",
                )
                return False
            if self.evpool is not None:
                self.evpool.report_conflicting_votes(e.vote_a, e.vote_b)
            self.logger.debug("found and sent conflicting votes to the evidence pool")
            return False

    def _add_vote(self, vote: Vote, peer_id: str) -> bool:
        """Reference: addVote :2009."""
        rs = self.rs
        # A precommit for the previous height (late precommits)
        if (
            vote.height + 1 == rs.height
            and vote.type == SIGNED_MSG_TYPE_PRECOMMIT
        ):
            if rs.step != RoundStepType.NEW_HEIGHT:
                return False
            if rs.last_commit is None:
                return False
            added, _ = rs.last_commit.add_vote(vote)
            if not added:
                return False
            self.event_bus.publish_event_vote(EventDataVote(vote))
            if self.on_has_vote is not None:
                self.on_has_vote(vote)
            if (
                self.config.skip_timeout_commit
                and rs.last_commit.has_all()
            ):
                self._enter_new_round(rs.height, 0)
            return True

        if vote.height != rs.height:
            return False

        added, err = rs.votes.add_vote(vote, peer_id)
        if not added:
            return False
        self.event_bus.publish_event_vote(EventDataVote(vote))
        if self.on_has_vote is not None:
            self.on_has_vote(vote)

        if vote.type == SIGNED_MSG_TYPE_PREVOTE:
            self._on_prevote_added(vote)
        elif vote.type == SIGNED_MSG_TYPE_PRECOMMIT:
            self._on_precommit_added(vote)
        return True

    def _on_prevote_added(self, vote: Vote) -> None:
        rs = self.rs
        prevotes = rs.votes.prevotes(vote.round)
        block_id, ok = prevotes.two_thirds_majority()
        if ok:
            # unlock on a later polka for a different block (:2074)
            if (
                rs.locked_block is not None
                and rs.locked_round < vote.round
                and vote.round <= rs.round
                and rs.locked_block.hash() != block_id.hash
            ):
                rs.locked_round = -1
                rs.locked_block = None
                rs.locked_block_parts = None
                self.event_bus.publish_event_unlock(
                    EventDataRoundState(rs.height, rs.round, rs.step.short())
                )
            # track the valid block (:2090)
            if not block_id.is_zero() and rs.valid_round < vote.round and (
                vote.round == rs.round
            ):
                if rs.proposal_block is not None and (
                    rs.proposal_block.hash() == block_id.hash
                ):
                    rs.valid_round = vote.round
                    rs.valid_block = rs.proposal_block
                    rs.valid_block_parts = rs.proposal_block_parts
                else:
                    rs.proposal_block = None
                    if rs.proposal_block_parts is None or (
                        not rs.proposal_block_parts.has_header(
                            block_id.part_set_header
                        )
                    ):
                        rs.proposal_block_parts = PartSet.from_header(
                            block_id.part_set_header
                        )
                self.event_bus.publish_event_valid_block(
                    EventDataRoundState(rs.height, rs.round, rs.step.short())
                )
                if self.on_valid_block is not None:
                    self.on_valid_block(rs)

        # transition (:2110)
        if rs.round < vote.round and prevotes.has_two_thirds_any():
            self._enter_new_round(rs.height, vote.round)
        elif rs.round == vote.round and RoundStepType.PREVOTE <= rs.step:
            if ok and (self._is_proposal_complete() or block_id.is_zero()):
                self._enter_precommit(rs.height, vote.round)
            elif prevotes.has_two_thirds_any():
                self._enter_prevote_wait(rs.height, vote.round)
        elif rs.proposal is not None and 0 <= rs.proposal.pol_round and (
            rs.proposal.pol_round == vote.round
        ):
            if self._is_proposal_complete():
                self._enter_prevote(rs.height, rs.round)

    def _on_precommit_added(self, vote: Vote) -> None:
        rs = self.rs
        precommits = rs.votes.precommits(vote.round)
        block_id, ok = precommits.two_thirds_majority()
        if ok:
            self._enter_new_round(rs.height, vote.round)
            self._enter_precommit(rs.height, vote.round)
            if not block_id.is_zero():
                self._enter_commit(rs.height, vote.round)
                if self.config.skip_timeout_commit and precommits.has_all():
                    self._enter_new_round(rs.height, 0)
            else:
                self._enter_precommit_wait(rs.height, vote.round)
        elif rs.round <= vote.round and precommits.has_two_thirds_any():
            self._enter_new_round(rs.height, vote.round)
            self._enter_precommit_wait(rs.height, vote.round)

    def _sign_vote(self, msg_type: int, hash_: bytes, header) -> Optional[Vote]:
        rs = self.rs
        if self.priv_validator_pub_key is None:
            return None
        addr = self.priv_validator_pub_key.address()
        val_idx, _ = rs.validators.get_by_address(addr)
        if val_idx < 0:
            return None
        from cometbft_tpu.types.part_set import PartSetHeader

        vote = Vote(
            type=msg_type,
            height=rs.height,
            round=rs.round,
            block_id=BlockID(hash_, header if header is not None else PartSetHeader()),
            timestamp=self._vote_time(),
            validator_address=addr,
            validator_index=val_idx,
        )
        try:
            self.priv_validator.sign_vote(self.state.chain_id, vote)
            return vote
        except Exception as e:
            self.logger.error("failed signing vote", err=str(e))
            return None

    def _vote_time(self) -> Timestamp:
        """Reference: voteTime :2220-2236 — now, but never before the
        candidate block's time + time_iota. The locked block takes
        precedence over the proposal block (else-if, not fall-through)."""
        now = Timestamp.now()
        min_time = now
        if self.state is not None:
            iota_ns = self.state.consensus_params.block.time_iota_ms * 1_000_000
            if self.rs.locked_block is not None:
                min_time = self.rs.locked_block.header.time.add_ns(iota_ns)
            elif self.rs.proposal_block is not None:
                min_time = self.rs.proposal_block.header.time.add_ns(iota_ns)
        return now if min_time <= now else min_time

    def _sign_add_vote(self, msg_type: int, hash_: bytes, header) -> Optional[Vote]:
        if self.priv_validator is None or self.priv_validator_pub_key is None:
            return None
        if not self.rs.validators.has_address(self.priv_validator_pub_key.address()):
            return None
        vote = self._sign_vote(msg_type, hash_, header)
        if vote is not None:
            self.send_internal(VoteMessage(vote))
            self.metrics.validator_last_signed_height.set(vote.height)
        return vote
