"""Commit.vote_sign_bytes_many: a commit's sign-bytes from one template
per block id, against the single-vote path (Commit.vote_sign_bytes, which
the golden vectors of test_types.py pin), byte for byte; then the three
verify_commit entry points on the cpu backend over a 150-validator commit
whose timestamps all differ.
"""

import pytest

from cometbft_tpu.libs import protoio
from cometbft_tpu.proto.gogo import GO_ZERO_SECONDS, Timestamp, ZERO_TIME
from cometbft_tpu.types import test_util
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    Commit,
    CommitSig,
)
from cometbft_tpu.types.canonical import CanonicalVoteTemplate
from cometbft_tpu.types.validator_set import Fraction
from cometbft_tpu.types.vote import (
    SIGNED_MSG_TYPE_PRECOMMIT,
    SIGNED_MSG_TYPE_PREVOTE,
)

BLOCK_ID = test_util.make_block_id()

# seconds x nanos below: every zero / non-zero / negative combination a
# Timestamp body can be made of, and the widths a varint changes at
EDGE_SECONDS = [0, 1, 127, 128, 1_700_000_000, 2**35, 2**63 - 1, -1,
                GO_ZERO_SECONDS, -(2**63)]
EDGE_NANOS = [0, 1, 127, 128, 16_384, 999_999_999]
EDGE_TIMESTAMPS = [Timestamp(s, n) for s in EDGE_SECONDS for n in EDGE_NANOS]


def _commit(timestamps, flags=None, height=12_345, round_=0,
            block_id=BLOCK_ID):
    flags = flags or [BLOCK_ID_FLAG_COMMIT] * len(timestamps)
    sigs = [
        CommitSig.absent() if flag == BLOCK_ID_FLAG_ABSENT
        else CommitSig(flag, bytes([i % 256]) * 20, ts, bytes(64))
        for i, (flag, ts) in enumerate(zip(flags, timestamps))
    ]
    return Commit(height=height, round=round_, block_id=block_id,
                  signatures=sigs)


def _assert_same(commit, chain_id, idxs):
    got = commit.vote_sign_bytes_many(chain_id, idxs)
    want = [commit.vote_sign_bytes(chain_id, i) for i in idxs]
    assert got == want
    # bytes == bytearray compares equal; a lane must be hashable bytes
    assert all(type(m) is bytes for m in got)
    return got


def _prefix_len(msg):
    body_len, pos = protoio.decode_uvarint(msg)
    assert body_len == len(msg) - pos
    return pos


def test_all_for_block_one_shared_timestamp():
    commit = _commit([Timestamp(1_700_000_005, 0)] * 200)
    msgs = _assert_same(commit, "test-chain", range(200))
    assert len(set(msgs)) == 1


def test_a_distinct_timestamp_in_every_lane_edges_among_them():
    assert ZERO_TIME in EDGE_TIMESTAMPS and Timestamp(0, 0) in EDGE_TIMESTAMPS
    commit = _commit(EDGE_TIMESTAMPS)
    msgs = _assert_same(commit, "test-chain", range(len(EDGE_TIMESTAMPS)))
    assert len(set(msgs)) == len(EDGE_TIMESTAMPS)


@pytest.mark.parametrize("ts", EDGE_TIMESTAMPS,
                         ids=lambda ts: f"{ts.seconds}s{ts.nanos}n")
@pytest.mark.parametrize("flag", [BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL],
                         ids=["for_block", "nil"])
def test_one_lane_equals_the_single_vote_path(flag, ts):
    _assert_same(_commit([ts], [flag]), "test-chain", [0])


def test_for_block_nil_and_absent_lanes_mixed():
    flags = [
        (BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_ABSENT)[
            (i * 7) % 5 % 3]
        for i in range(60)
    ]
    assert len(set(flags)) == 3
    commit = _commit(EDGE_TIMESTAMPS, flags)
    present = [i for i, f in enumerate(flags) if f != BLOCK_ID_FLAG_ABSENT]
    msgs = _assert_same(commit, "test-chain", present)
    # a nil precommit carries no block id: the zero block id's template
    for i, msg in zip(present, msgs):
        assert (BLOCK_ID.hash in msg) == (flags[i] == BLOCK_ID_FLAG_COMMIT)


def test_only_nil_lanes_asked_for():
    flags = [BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL] * 5
    commit = _commit(EDGE_TIMESTAMPS[:10], flags)
    _assert_same(commit, "test-chain", [1, 3, 9])


@pytest.mark.parametrize("round_", [0, 1, 7, 2**31 - 1])
@pytest.mark.parametrize("height", [1, 12_345, 2**62])
def test_round_zero_and_nonzero(height, round_):
    commit = _commit(EDGE_TIMESTAMPS[:12],
                     [BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL] * 6,
                     height=height, round_=round_)
    _assert_same(commit, "test-chain", range(12))


# chain id length -> bytes of the MarshalDelimited prefix, for-block lane
# at round 0 with a (1_700_000_000, 999_999_999) timestamp: the body is
# 2 + 9 + 74 + 13 + (2 + len) bytes, which passes 127 between 10 and 50
CHAIN_IDS = {0: 1, 10: 1, 50: 2}


@pytest.mark.parametrize("chain_len", sorted(CHAIN_IDS))
def test_chain_id_lengths_and_the_two_byte_length_prefix(chain_len):
    chain_id = "c" * chain_len
    commit = _commit(EDGE_TIMESTAMPS)
    msgs = _assert_same(commit, chain_id, range(len(EDGE_TIMESTAMPS)))
    lane = EDGE_TIMESTAMPS.index(Timestamp(1_700_000_000, 999_999_999))
    assert _prefix_len(msgs[lane]) == CHAIN_IDS[chain_len]
    for msg in msgs:
        _prefix_len(msg)


def test_the_prefix_crosses_127_to_128_within_one_commit():
    """27 characters: the lanes with the shortest timestamp bodies stay
    under 128 bytes and the longest pass it, in ONE call."""
    commit = _commit(EDGE_TIMESTAMPS)
    msgs = _assert_same(commit, "c" * 27, range(len(EDGE_TIMESTAMPS)))
    assert {_prefix_len(m) for m in msgs} == {1, 2}
    assert {127, 128} <= {len(m) - _prefix_len(m) for m in msgs}


def test_a_nil_lane_with_a_long_chain_id_and_round_gets_two_bytes_too():
    commit = _commit([ZERO_TIME], [BLOCK_ID_FLAG_NIL], round_=3)
    (msg,) = _assert_same(commit, "c" * 50, [0])
    assert _prefix_len(msg) == 1  # no block id: 2 + 9 + 9 + 13 + 52
    commit = _commit([ZERO_TIME], [BLOCK_ID_FLAG_COMMIT], round_=3)
    (msg,) = _assert_same(commit, "c" * 50, [0])
    assert _prefix_len(msg) == 2


@pytest.mark.parametrize("idxs", [
    pytest.param(list(range(41)), id="light_prefix"),
    pytest.param([3, 4, 10, 57], id="sparse_commit_order"),
    pytest.param([57, 3, 10, 4, 59, 0], id="valset_order"),
    pytest.param([], id="none"),
    pytest.param([5, 5], id="the_same_lane_twice"),
])
def test_a_strict_subset_in_the_order_asked(idxs):
    commit = _commit(EDGE_TIMESTAMPS)
    _assert_same(commit, "test-chain", idxs)


def test_an_index_past_the_commit_raises_as_the_single_vote_path_does():
    commit = _commit(EDGE_TIMESTAMPS[:4])
    with pytest.raises(IndexError):
        commit.vote_sign_bytes(  # the single-vote path
            "test-chain", 4)
    with pytest.raises(IndexError):
        commit.vote_sign_bytes_many("test-chain", [0, 4])


# -- the golden vectors of tests/test_types.py (types/vote_test.go:60) -------

_GO_ZERO_TS = [0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF,
               0xFF, 0xFF, 0x1]
_H1 = [0x11, 0x1, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0]
_R1 = [0x19, 0x1, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0, 0x0]
GOLDEN = {
    "empty_vote": ((0, 0, 0, ""), [0xD] + _GO_ZERO_TS),
    "precommit": ((SIGNED_MSG_TYPE_PRECOMMIT, 1, 1, ""),
                  [0x21, 0x8, 0x2] + _H1 + _R1 + _GO_ZERO_TS),
    "prevote": ((SIGNED_MSG_TYPE_PREVOTE, 1, 1, ""),
                [0x21, 0x8, 0x1] + _H1 + _R1 + _GO_ZERO_TS),
    "no_type": ((0, 1, 1, ""), [0x1F] + _H1 + _R1 + _GO_ZERO_TS),
    "with_chain_id": ((0, 1, 1, "test_chain_id"),
                      [0x2E] + _H1 + _R1 + _GO_ZERO_TS + [0x32, 0xD]
                      + list(b"test_chain_id")),
}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_the_template_gives_the_golden_vectors(name):
    (msg_type, height, round_, chain_id), want = GOLDEN[name]
    template = CanonicalVoteTemplate(
        msg_type, height, round_, BlockID(), chain_id)
    assert template.sign_bytes(ZERO_TIME) == bytes(want)


@pytest.mark.parametrize("flag", [BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL],
                         ids=["for_block", "nil"])
def test_the_precommit_golden_vector_through_a_one_signature_commit(flag):
    """A commit's votes are precommits, so this is the vector a commit
    can rebuild: height 1, round 1, zero block id, Go's zero time."""
    commit = _commit([ZERO_TIME], [flag], height=1, round_=1,
                     block_id=BlockID())
    (msg,) = _assert_same(commit, "", [0])
    assert msg == bytes(GOLDEN["precommit"][1])
    # and with a chain id: the with_chain_id vector plus the type field
    (msg,) = _assert_same(commit, "test_chain_id", [0])
    want = GOLDEN["with_chain_id"][1]
    assert msg == bytes([want[0] + 2, 0x8, 0x2] + want[1:])


# -- through the entry points, cpu backend ----------------------------------

CHAIN_ID = "sign-bytes-chain"
N_VALS = 150


@pytest.fixture(scope="module")
def signed():
    """150 validators, every precommit at its own (seconds, nanos)."""
    vals, privs = test_util.deterministic_validator_set(N_VALS, 10)
    sigs = []
    for i, pv in enumerate(privs):
        ts = Timestamp(1_700_000_000 + i % 3, (i * 6_666_667) % 10**9)
        vote = test_util.make_vote(
            pv, CHAIN_ID, i, 5, 0, SIGNED_MSG_TYPE_PRECOMMIT, BLOCK_ID, ts)
        sigs.append(vote.to_commit_sig())
    assert len({(s.timestamp.seconds, s.timestamp.nanos)
                for s in sigs}) == N_VALS
    return vals, Commit(height=5, round=0, block_id=BLOCK_ID,
                        signatures=sigs)


def _corrupted(commit, idx):
    sigs = list(commit.signatures)
    cs = sigs[idx]
    sigs[idx] = CommitSig(
        cs.block_id_flag, cs.validator_address, cs.timestamp,
        cs.signature[:-1] + bytes([cs.signature[-1] ^ 1]))
    return Commit(height=commit.height, round=commit.round,
                  block_id=commit.block_id, signatures=sigs)


def _verify(entry, vals, commit):
    if entry == "verify_commit":
        vals.verify_commit(CHAIN_ID, BLOCK_ID, 5, commit, backend="cpu")
    elif entry == "verify_commit_light":
        vals.verify_commit_light(CHAIN_ID, BLOCK_ID, 5, commit,
                                 backend="cpu")
    else:
        vals.verify_commit_light_trusting(
            CHAIN_ID, commit, Fraction(2, 3), backend="cpu")


ENTRIES = ["verify_commit", "verify_commit_light",
           "verify_commit_light_trusting"]


@pytest.mark.parametrize("entry", ENTRIES)
def test_entry_point_accepts_a_good_commit_with_distinct_timestamps(
        signed, entry):
    vals, commit = signed
    _verify(entry, vals, commit)


# lane 100 is the last of the 101-lane quorum prefix the light variants
# verify; lane 149 only the full verify_commit reaches
@pytest.mark.parametrize("entry,bad", [
    (entry, bad) for entry in ENTRIES for bad in (0, 37, 100)
] + [("verify_commit", 149)])
def test_entry_point_names_the_corrupted_lane(signed, entry, bad):
    vals, commit = signed
    with pytest.raises(ValueError,
                       match=rf"wrong signature \(#{bad}\)"):
        _verify(entry, vals, _corrupted(commit, bad))


@pytest.mark.parametrize("entry", ENTRIES[1:])
def test_light_variants_stop_at_the_quorum_prefix(signed, entry):
    """A bad lane past the speculative quorum is never built or checked."""
    vals, commit = signed
    _verify(entry, vals, _corrupted(commit, 101))


def test_verify_commit_with_nil_and_absent_lanes(signed):
    """Mixed flags through verify_commit: a nil precommit is signed over
    the zero block id's bytes and is checked, not tallied."""
    vals, privs = test_util.deterministic_validator_set(N_VALS, 10)
    _, commit = signed
    sigs = list(commit.signatures)
    for i in (3, 77):
        vote = test_util.make_vote(
            privs[i], CHAIN_ID, i, 5, 0, SIGNED_MSG_TYPE_PRECOMMIT,
            BlockID(), Timestamp(1_700_000_009, i))
        sigs[i] = vote.to_commit_sig()
        assert sigs[i].block_id_flag == BLOCK_ID_FLAG_NIL
    sigs[5] = CommitSig.absent()
    mixed = Commit(height=5, round=0, block_id=BLOCK_ID, signatures=sigs)
    vals.verify_commit(CHAIN_ID, BLOCK_ID, 5, mixed, backend="cpu")
    with pytest.raises(ValueError, match=r"wrong signature \(#77\)"):
        vals.verify_commit(CHAIN_ID, BLOCK_ID, 5, _corrupted(mixed, 77),
                           backend="cpu")
