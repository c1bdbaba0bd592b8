"""ValidatorSet — ordered validator set with proposer selection and the
commit-verification hot paths.

Reference: types/validator_set.go. The VerifyCommit/VerifyCommitLight/
VerifyCommitLightTrusting loops (:685-823) are re-expressed through the
batch-verification boundary (cometbft_tpu.crypto.batch): signatures are
collected in order, verified as one batch, then the reference's serial
accept/reject/error sequencing is replayed against the validity mask —
bit-identical outcomes, one TPU round-trip.

Proposer selection (a deterministic weighted round-robin over proposer
priorities) follows validator_set.go IncrementProposerPriority /
RescalePriorities / shiftByAvgProposerPriority exactly, including Go's
truncation-toward-zero integer division.
"""

from __future__ import annotations

import functools
from bisect import bisect_left
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from cometbft_tpu.crypto import batch as cryptobatch
from cometbft_tpu.crypto import merkle
from cometbft_tpu.crypto import wire as wirelib
from cometbft_tpu.libs import protoio
from cometbft_tpu.libs import trace as tracelib
from cometbft_tpu.types.block import BlockID, Commit
from cometbft_tpu.types.validator import MAX_TOTAL_VOTING_POWER, Validator

PRIORITY_WINDOW_SIZE_FACTOR = 2  # validator_set.go PriorityWindowSizeFactor

_INT64_MAX = (1 << 63) - 1
_INT64_MIN = -(1 << 63)


def _books_its_flush(verify):
    """A ``verify_commit*`` entry runs under a flush record of the wire
    ledger (crypto/wire.own_flush): with no scheduler above it (the
    resident path) the call IS the flush, its ``lead`` starts here and
    its ``tail`` ends where it returns or raises, the tally included. A
    commit that reaches no device stream on this thread books nothing."""

    @functools.wraps(verify)
    def wrapper(*args, **kwargs):
        with wirelib.own_flush():
            return verify(*args, **kwargs)

    return wrapper


def _go_div(a: int, b: int) -> int:
    """Go integer division truncates toward zero; Python floors."""
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b >= 0) else -q


def _clip(v: int) -> int:
    return max(_INT64_MIN, min(_INT64_MAX, v))


@dataclass(frozen=True)
class Fraction:
    """Reference: libs/math/fraction.go."""

    numerator: int
    denominator: int


DEFAULT_TRUST_LEVEL = Fraction(1, 3)  # light.DefaultTrustLevel


class ErrInvalidCommitSignatures(ValueError):
    def __init__(self, want: int, got: int):
        super().__init__(
            f"invalid commit -- wrong set size: {want} vs {got}"
        )


class ErrInvalidCommitHeight(ValueError):
    def __init__(self, want: int, got: int):
        super().__init__(f"invalid commit -- wrong height: {want} vs {got}")


class ErrNotEnoughVotingPowerSigned(ValueError):
    def __init__(self, got: int, needed: int):
        super().__init__(
            f"invalid commit -- insufficient voting power: got {got}, needed more than {needed}"
        )
        self.got = got
        self.needed = needed


class ValidatorSet:
    def __init__(self, validators: List[Validator]):
        """Reference: NewValidatorSet — applies the changeset to an empty set
        then increments proposer priority once to pick the first proposer."""
        self.validators: List[Validator] = []
        self.proposer: Optional[Validator] = None
        self._total_voting_power = 0
        if validators:
            self._update_with_change_set(
                [v.copy() for v in validators], allow_deletes=False
            )
            self.increment_proposer_priority(1)

    # -- basic accessors ---------------------------------------------------

    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def size(self) -> int:
        return len(self.validators)

    def total_voting_power(self) -> int:
        if self._total_voting_power == 0:
            self._update_total_voting_power()
        return self._total_voting_power

    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total += v.voting_power
            if total > MAX_TOTAL_VOTING_POWER:
                raise ValueError(
                    f"total voting power exceeds MaxTotalVotingPower {MAX_TOTAL_VOTING_POWER}"
                )
        self._total_voting_power = total

    def has_address(self, address: bytes) -> bool:
        return any(v.address == address for v in self.validators)

    def get_by_address(self, address: bytes) -> Tuple[int, Optional[Validator]]:
        for i, v in enumerate(self.validators):
            if v.address == address:
                return i, v.copy()
        return -1, None

    def get_by_index(self, index: int) -> Tuple[bytes, Optional[Validator]]:
        if index < 0 or index >= len(self.validators):
            return b"", None
        v = self.validators[index]
        return v.address, v.copy()

    def copy(self) -> "ValidatorSet":
        new = ValidatorSet([])
        new.validators = [v.copy() for v in self.validators]
        new.proposer = self.proposer.copy() if self.proposer else None
        new._total_voting_power = self._total_voting_power
        return new

    def hash(self) -> bytes:
        """Merkle root over SimpleValidator encodings
        (validator_set.go:347)."""
        return merkle.hash_from_byte_slices([v.bytes() for v in self.validators])

    # -- proposer selection (validator_set.go:160-345) ---------------------

    def get_proposer(self) -> Optional[Validator]:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        proposer = None
        for v in self.validators:
            proposer = v if proposer is None else proposer.compare_proposer_priority(v)
        return proposer

    def increment_proposer_priority(self, times: int) -> None:
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("cannot call with non-positive times")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def _increment_proposer_priority(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority + v.voting_power)
        mostest = self._find_proposer()
        mostest.proposer_priority = _clip(
            mostest.proposer_priority - self.total_voting_power()
        )
        return mostest

    def rescale_priorities(self, diff_max: int) -> None:
        if diff_max <= 0:
            return
        diff = self._compute_max_min_priority_diff()
        if diff > diff_max:
            ratio = (diff + diff_max - 1) // diff_max
            for v in self.validators:
                v.proposer_priority = _go_div(v.proposer_priority, ratio)

    def _compute_max_min_priority_diff(self) -> int:
        prios = [v.proposer_priority for v in self.validators]
        return max(prios) - min(prios)

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority - avg)

    def _compute_avg_proposer_priority(self) -> int:
        # Go uses big.Int for the sum then big.Int.Div — *Euclidean*
        # division (floor, for a positive divisor), unlike native int64
        # `/` (validator_set.go:181-190). Python `//` floors: exact match.
        total = sum(v.proposer_priority for v in self.validators)
        return total // len(self.validators)

    # -- updates (validator_set.go:365-660) --------------------------------

    def update_with_change_set(self, changes: List[Validator]) -> None:
        self._update_with_change_set(changes, allow_deletes=True)

    def _update_with_change_set(
        self, changes: List[Validator], allow_deletes: bool
    ) -> None:
        if not changes:
            return
        # processChanges: sort by address, reject duplicates, split
        sorted_changes = sorted(changes, key=lambda v: v.address)
        for a, b in zip(sorted_changes, sorted_changes[1:]):
            if a.address == b.address:
                raise ValueError(f"duplicate entry {b} in changes")
        updates, deletes = [], []
        for v in sorted_changes:
            if v.voting_power < 0:
                raise ValueError(f"voting power can't be negative: {v}")
            if v.voting_power > MAX_TOTAL_VOTING_POWER:
                raise ValueError("to prevent clipping/overflow, voting power too large")
            if v.voting_power == 0:
                deletes.append(v)
            else:
                updates.append(v)
        if not allow_deletes and deletes:
            raise ValueError("cannot process validators with voting power 0")
        # verifyRemovals
        removed_voting_power = 0
        for v in deletes:
            _, val = self.get_by_address(v.address)
            if val is None:
                raise ValueError(f"failed to find validator {v.address.hex()} to remove")
            removed_voting_power += val.voting_power
        if len(deletes) > len(self.validators):
            raise ValueError("more deletes than validators")
        # verifyUpdates: check resulting total power
        delta = 0
        by_addr: Dict[bytes, Validator] = {v.address: v for v in self.validators}
        for u in updates:
            prev = by_addr.get(u.address)
            delta += u.voting_power - (prev.voting_power if prev else 0)
        tvp_after_updates_before_removals = self.total_voting_power() + delta if self.validators else delta
        if tvp_after_updates_before_removals - removed_voting_power > MAX_TOTAL_VOTING_POWER:
            raise ValueError(
                "failed to add/update validators: total voting power would exceed limit"
            )
        # computeNewPriorities (validator_set.go computeNewPriorities):
        # new validators start at -1.125 * (total power after updates)
        for u in updates:
            prev = by_addr.get(u.address)
            if prev is None:
                u.proposer_priority = -(
                    tvp_after_updates_before_removals
                    + (tvp_after_updates_before_removals >> 3)
                )
            else:
                u.proposer_priority = prev.proposer_priority
        # applyUpdates + applyRemovals
        delete_addrs = {v.address for v in deletes}
        merged = {v.address: v for v in self.validators}
        for u in updates:
            merged[u.address] = u
        for addr in delete_addrs:
            merged.pop(addr, None)
        self.validators = list(merged.values())
        self._total_voting_power = 0
        self._update_total_voting_power()
        # scale and center, then canonical sort: power desc, address asc
        self.rescale_priorities(
            PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        )
        self._shift_by_avg_proposer_priority()
        self.validators.sort(key=lambda v: (-v.voting_power, v.address))

    # -- commit verification through the batch boundary --------------------

    def _verify_lanes(self, lane_msgs, lane_sigs, entries, backend):
        """Batch-verify the present lanes; returns one bool per entry
        (entry order). Routes through the device-resident full-lane path
        (crypto/batch.py verify_commit_valset — the valset's pubkey rows
        stay on device across heights) when the whole set is ed25519 and
        the backend/shape is eligible; otherwise the add()/verify()
        protocol. Accept/reject is identical either way. ``lane_msgs``
        is one entry per validator, or verify_commit's per-launch source
        (_stream_lane_msgs)."""
        if not entries:
            return []
        from cometbft_tpu.crypto import ed25519 as ed

        if cryptobatch.resident_commit_eligible(len(entries), backend):
            with tracelib.stage("commit.valset_id"):
                pub_keys = (
                    [v.pub_key.bytes() for v in self.validators]
                    if all(
                        isinstance(v.pub_key, ed.PubKeyEd25519)
                        for v in self.validators
                    )
                    else None
                )
            if pub_keys is not None:
                full = cryptobatch.verify_commit_valset(
                    pub_keys, lane_msgs, lane_sigs, backend
                )
                if full is not None:
                    return [bool(full[e[0]]) for e in entries]
        if callable(lane_msgs):  # a streamed commit the device path refused
            lane_msgs = lane_msgs(0, self.size())
        bv = cryptobatch.new_batch_verifier(backend)
        for e in entries:
            idx = e[0]
            bv.add(
                self.validators[idx].pub_key, lane_msgs[idx], lane_sigs[idx]
            )
        _, mask = bv.verify()
        return mask

    @_books_its_flush
    def verify_commit(
        self,
        chain_id: str,
        block_id: BlockID,
        height: int,
        commit: Commit,
        backend: Optional[str] = None,
    ) -> None:
        """Reference: validator_set.go:667 VerifyCommit — checks ALL
        signatures (LastCommitInfo depends on the full mask)."""
        if self.size() != len(commit.signatures):
            raise ErrInvalidCommitSignatures(self.size(), len(commit.signatures))
        if height != commit.height:
            raise ErrInvalidCommitHeight(height, commit.height)
        if block_id != commit.block_id:
            raise ValueError(
                f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
            )
        entries = []  # (idx, val, for_block)
        lane_msgs: list = [None] * self.size()
        lane_sigs: list = [None] * self.size()
        with tracelib.stage("commit.sign_bytes") as span:
            idxs = []
            for idx, cs in enumerate(commit.signatures):
                if cs.is_absent():
                    continue
                val = self.validators[idx]
                idxs.append(idx)
                lane_sigs[idx] = cs.signature
                entries.append((idx, val, cs.for_block()))
            if cryptobatch.resident_commit_eligible(len(entries), backend):
                # the resident path builds a launch's messages when that
                # launch is next, behind the device's work on the one before
                lane_msgs = _stream_lane_msgs(span, commit, chain_id, idxs)
            else:
                _fill_lane_msgs(span, commit, chain_id, idxs, lane_msgs, idxs)
        mask = self._verify_lanes(lane_msgs, lane_sigs, entries, backend)
        with tracelib.stage("commit.tally"):
            tallied = 0
            needed = self.total_voting_power() * 2 // 3
            for (idx, val, for_block), ok in zip(entries, mask):
                if not ok:
                    raise ValueError(
                        f"wrong signature (#{idx}): {commit.signatures[idx].signature.hex().upper()}"
                    )
                if for_block:
                    tallied += val.voting_power
        if tallied <= needed:
            raise ErrNotEnoughVotingPowerSigned(tallied, needed)

    @_books_its_flush
    def verify_commit_light(
        self,
        chain_id: str,
        block_id: BlockID,
        height: int,
        commit: Commit,
        backend: Optional[str] = None,
    ) -> None:
        """Reference: validator_set.go:722 VerifyCommitLight — early exit at
        +2/3. Batch form: verify the minimal in-order prefix of ForBlock
        signatures whose cumulative power crosses quorum, then replay."""
        if self.size() != len(commit.signatures):
            raise ErrInvalidCommitSignatures(self.size(), len(commit.signatures))
        if height != commit.height:
            raise ErrInvalidCommitHeight(height, commit.height)
        if block_id != commit.block_id:
            raise ValueError(
                f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
            )
        needed = self.total_voting_power() * 2 // 3
        # speculative prefix: assume sigs valid, stop once quorum crossed
        entries = []
        speculative = 0
        lane_msgs: list = [None] * self.size()
        lane_sigs: list = [None] * self.size()
        with tracelib.stage("commit.sign_bytes") as span:
            idxs = []
            for idx, cs in enumerate(commit.signatures):
                if not cs.for_block():
                    continue
                val = self.validators[idx]
                entries.append((idx, val))
                idxs.append(idx)
                lane_sigs[idx] = cs_sig(commit, idx)
                speculative += val.voting_power
                if speculative > needed:
                    break
            _fill_lane_msgs(span, commit, chain_id, idxs, lane_msgs, idxs)
        mask = self._verify_lanes(lane_msgs, lane_sigs, entries, backend)
        with tracelib.stage("commit.tally"):
            tallied = 0
            for (idx, val), ok in zip(entries, mask):
                if not ok:
                    raise ValueError(
                        f"wrong signature (#{idx}): {commit.signatures[idx].signature.hex().upper()}"
                    )
                tallied += val.voting_power
                if tallied > needed:
                    return
        raise ErrNotEnoughVotingPowerSigned(tallied, needed)

    @_books_its_flush
    def verify_commit_light_trusting(
        self,
        chain_id: str,
        commit: Commit,
        trust_level: Fraction,
        backend: Optional[str] = None,
    ) -> None:
        """Reference: validator_set.go:775 VerifyCommitLightTrusting —
        by-address lookup against a *different* (trusted) validator set,
        double-vote detection, early exit at trust fraction."""
        if trust_level.denominator == 0:
            raise ValueError("trustLevel has zero Denominator")
        total_mul = self.total_voting_power() * trust_level.numerator
        if total_mul > _INT64_MAX:
            raise ValueError("int64 overflow while calculating voting power needed")
        needed = total_mul // trust_level.denominator
        seen_vals: Dict[int, int] = {}
        # lanes are indexed by TRUSTED-set position (seen_vals guarantees
        # each appears once), so _verify_lanes can route this variant
        # through the resident full-lane path too — the trusted set's
        # pubkey rows are the ones living on device
        entries = []  # (val_idx, commit_idx, val), until speculative quorum
        lane_msgs: list = [None] * self.size()
        lane_sigs: list = [None] * self.size()
        speculative = 0
        double_vote: Optional[Tuple[Validator, int, int]] = None
        with tracelib.stage("commit.sign_bytes") as span:
            for idx, cs in enumerate(commit.signatures):
                if not cs.for_block():
                    continue
                val_idx, val = self.get_by_address(cs.validator_address)
                if val is None:
                    continue
                if val_idx in seen_vals:
                    # double vote: reference errors here *after* verifying
                    # all prior sigs; record and stop collecting
                    double_vote = (val, seen_vals[val_idx], idx)
                    break
                seen_vals[val_idx] = idx
                lane_sigs[val_idx] = cs_sig(commit, idx)
                entries.append((val_idx, idx, val))
                speculative += val.voting_power
                if speculative > needed:
                    break
            _fill_lane_msgs(
                span,
                commit,
                chain_id,
                [e[1] for e in entries],
                lane_msgs,
                [e[0] for e in entries],
            )
        mask = self._verify_lanes(lane_msgs, lane_sigs, entries, backend)
        with tracelib.stage("commit.tally"):
            tallied = 0
            for (val_idx, idx, val), ok in zip(entries, mask):
                if not ok:
                    raise ValueError(
                        f"wrong signature (#{idx}): {commit.signatures[idx].signature.hex().upper()}"
                    )
                tallied += val.voting_power
                if tallied > needed:
                    return
        if double_vote is not None:
            val, first, second = double_vote
            raise ValueError(f"double vote from {val} ({first} and {second})")
        raise ErrNotEnoughVotingPowerSigned(tallied, needed)

    # -- wire (validator.proto: validators=1 rep, proposer=2, total=3) -----

    def encode(self) -> bytes:
        out = b""
        for v in self.validators:
            out += protoio.field_message(1, v.encode())
        if self.proposer is not None:
            out += protoio.field_message(2, self.proposer.encode())
        out += protoio.field_varint(3, self.total_voting_power())
        return out

    @classmethod
    def decode(cls, data: bytes) -> "ValidatorSet":
        r = protoio.WireReader(data)
        vs = cls([])
        while not r.at_end():
            f, wt = r.read_tag()
            if f == 1:
                vs.validators.append(Validator.decode(r.read_bytes()))
            elif f == 2:
                vs.proposer = Validator.decode(r.read_bytes())
            elif f == 3:
                vs._total_voting_power = r.read_varint()
            else:
                r.skip(wt)
        return vs

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValueError("validator set is nil or empty")
        for idx, v in enumerate(self.validators):
            try:
                v.validate_basic()
            except ValueError as e:
                raise ValueError(f"invalid validator #{idx}: {e}") from e
        if self.proposer is not None:
            self.proposer.validate_basic()

    def __iter__(self):
        return iter(self.validators)

    def __str__(self) -> str:
        return (
            f"ValidatorSet{{Proposer: {self.proposer}, "
            f"Validators: {[str(v) for v in self.validators]}}}"
        )


def cs_sig(commit: Commit, idx: int) -> bytes:
    return commit.signatures[idx].signature


def _fill_lane_msgs(
    span, commit: Commit, chain_id: str, idxs: List[int], lane_msgs: list,
    lanes: List[int],
) -> None:
    """lane_msgs[lanes[k]] = the sign-bytes of commit signature idxs[k],
    all built in one pass (Commit.vote_sign_bytes_many); ``span``, the
    ``commit.sign_bytes`` stage's, is tagged with what was built."""
    for lane, msg in zip(lanes, commit.vote_sign_bytes_many(chain_id, idxs)):
        lane_msgs[lane] = msg
    _tag_sign_bytes(span, commit, idxs)


def _stream_lane_msgs(span, commit: Commit, chain_id: str, idxs: List[int]):
    """→ ``msgs(start, end)``: the sign-bytes of lanes [start, end) of a
    full commit (lane = commit signature index; ``idxs``, ascending, the
    present ones), None where the lane is absent; the slices come from
    the commit's one or two templates, built once
    (Commit.sign_bytes_builder). ``span`` is tagged as _fill_lane_msgs
    tags it, with what the slices will add up to."""
    many = commit.sign_bytes_builder(chain_id)

    def msgs(start: int, end: int) -> list:
        present = idxs[bisect_left(idxs, start):bisect_left(idxs, end)]
        built = many(present)
        if len(present) == end - start:
            return built
        out = [None] * (end - start)
        for idx, msg in zip(present, built):
            out[idx - start] = msg
        return out

    _tag_sign_bytes(span, commit, idxs)
    return msgs


def _tag_sign_bytes(span, commit: Commit, idxs: List[int]) -> None:
    if not span.noop:
        all_for_block = all(commit.signatures[i].for_block() for i in idxs)
        span.set_tag("lanes", len(idxs))
        span.set_tag("templates", 1 if all_for_block else 2)
