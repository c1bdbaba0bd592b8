"""Canonical sign-bytes encodings.

Reference: types/canonical.go + proto/tendermint/types/canonical.proto.
These byte layouts are consensus-critical: a signature is over
MarshalDelimited(CanonicalVote/CanonicalProposal) — varint length prefix
followed by the proto encoding with sfixed64 height/round
(types/vote.go:93-101). Golden vectors: types/vote_test.go:60.
"""

from __future__ import annotations

from cometbft_tpu.libs import protoio
from cometbft_tpu.proto.gogo import Timestamp
from cometbft_tpu.types.block import BlockID


def canonicalize_block_id(block_id: BlockID) -> bytes | None:
    """CanonicalBlockID proto bytes, or None for a zero block id
    (canonical.go:18 — nil when IsZero)."""
    if block_id.is_zero():
        return None
    psh = protoio.field_varint(
        1, block_id.part_set_header.total
    ) + protoio.field_bytes(2, block_id.part_set_header.hash)
    return protoio.field_bytes(1, block_id.hash) + protoio.field_message(2, psh)


def _canonical_vote_bytes(
    msg_type: int,
    height: int,
    round_: int,
    block_id: BlockID,
    timestamp: Timestamp,
    chain_id: str,
) -> bytes:
    """CanonicalVote: type=1 varint, height=2 sfixed64, round=3 sfixed64,
    block_id=4 (nullable), timestamp=5 (non-null), chain_id=6."""
    out = protoio.field_varint(1, msg_type)
    out += protoio.field_sfixed64(2, height)
    out += protoio.field_sfixed64(3, round_)
    cbid = canonicalize_block_id(block_id)
    if cbid is not None:
        out += protoio.field_message(4, cbid)
    out += protoio.field_message(5, timestamp.encode())
    out += protoio.field_string(6, chain_id)
    return out


def canonical_vote_bytes(chain_id: str, vote) -> bytes:
    """Sign bytes for a Vote: MarshalDelimited(CanonicalVote)
    (types/vote.go:93 VoteSignBytes)."""
    body = _canonical_vote_bytes(
        vote.type, vote.height, vote.round, vote.block_id, vote.timestamp, chain_id
    )
    return protoio.marshal_delimited(body)


_TS_SECONDS_TAG = protoio.tag(1, protoio.WIRE_VARINT)[0]
_TS_NANOS_TAG = protoio.tag(2, protoio.WIRE_VARINT)[0]
# a Timestamp body is at most two fields of one tag byte and a ten-byte
# varint (a negative int64) each
_TS_BODY_MAX = 2 * (1 + protoio.MAX_VARINT_LEN)


def _append_field_varint(buf: bytearray, tag: int, value: int) -> None:
    """protoio.field_varint for a one-byte tag, appended to ``buf``: a
    zero is omitted, a negative is its 64-bit two's complement (ten
    bytes; Go's zero time is second -62135596800). Runs twice a lane of
    a commit, where ``Timestamp.encode`` and its six calls would double
    the lane's cost."""
    if value:
        buf.append(tag)
        if value < 0:
            value += 1 << 64
        while value > 0x7F:
            buf.append(value & 0x7F | 0x80)
            value >>= 7
        buf.append(value)


class CanonicalVoteTemplate:
    """The sign-bytes of many votes that differ only in their timestamp:
    a commit's precommits for one block id.

    What (type, height, round, block id, chain id) fix is encoded once,
    with the same field encoders as ``_canonical_vote_bytes``: fields
    1-4 before the timestamp (field 4 absent for a zero block id) and
    field 6 after it. What the timestamp's body length decides (the
    field-5 tag and length, and the MarshalDelimited prefix of the whole
    message, two bytes from a 128-byte message on) is laid out in full
    for every length a body can have, so a vote costs its timestamp's two
    varints and one concatenation, whatever the other votes carry."""

    __slots__ = ("_heads", "_suffix")

    def __init__(
        self,
        msg_type: int,
        height: int,
        round_: int,
        block_id: BlockID,
        chain_id: str,
    ):
        prefix = protoio.field_varint(1, msg_type)
        prefix += protoio.field_sfixed64(2, height)
        prefix += protoio.field_sfixed64(3, round_)
        cbid = canonicalize_block_id(block_id)
        if cbid is not None:
            prefix += protoio.field_message(4, cbid)
        suffix = protoio.field_string(6, chain_id)
        ts_tag = protoio.tag(5, protoio.WIRE_BYTES)
        heads = []
        for ts_len in range(_TS_BODY_MAX + 1):
            ts_head = ts_tag + protoio.encode_uvarint(ts_len)
            total = len(prefix) + len(ts_head) + ts_len + len(suffix)
            heads.append(protoio.encode_uvarint(total) + prefix + ts_head)
        self._heads = heads
        self._suffix = suffix

    def sign_bytes(self, timestamp: Timestamp) -> bytes:
        """``canonical_vote_bytes`` of the template's vote at ``timestamp``."""
        ts = bytearray()
        _append_field_varint(ts, _TS_SECONDS_TAG, timestamp.seconds)
        _append_field_varint(ts, _TS_NANOS_TAG, timestamp.nanos)
        return self._heads[len(ts)] + ts + self._suffix


def canonical_proposal_bytes(chain_id: str, proposal) -> bytes:
    """Sign bytes for a Proposal: MarshalDelimited(CanonicalProposal)
    (types/proposal.go ProposalSignBytes). Field layout per canonical.proto:
    type=1, height=2 sfixed64, round=3 sfixed64, pol_round=4 int64,
    block_id=5, timestamp=6, chain_id=7."""
    out = protoio.field_varint(1, proposal.type)
    out += protoio.field_sfixed64(2, proposal.height)
    out += protoio.field_sfixed64(3, proposal.round)
    out += protoio.field_varint(4, proposal.pol_round)
    cbid = canonicalize_block_id(proposal.block_id)
    if cbid is not None:
        out += protoio.field_message(5, cbid)
    out += protoio.field_message(6, proposal.timestamp.encode())
    out += protoio.field_string(7, chain_id)
    return protoio.marshal_delimited(out)
