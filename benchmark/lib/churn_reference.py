"""The plain blocksync reference on a chain whose validator set moves:
which blocks of a served chain a syncing node accepts, the state and the
validator sets it is left with.

``replay`` decides what every request of the blocksync-churn cell is
compared with. Like ``sync_reference.py``, whose two commit checks it
calls with the set that holds at each height, it shares no code with
``cometbft_tpu``: blocks and validator sets are dicts and tuples of ints
and bytes, signatures are checked by ``reference.verify_many``, and the
update rule, the set's order and its hash are written out here from
their specifications.

Records:

* a validator set: ``{"hash": bytes, "rows": [(address, power, key)]}``,
  rows in the set's order: power descending, then address ascending
  (types/validator_set.go ValidatorsByVotingPower); ``address`` is the
  first 20 bytes of SHA-256 of the 32-byte ed25519 key; ``hash`` is the
  RFC 6962 Merkle root over each row's SimpleValidator encoding (key and
  power; not the address, not the proposer priority);
* a block: ``sync_reference``'s record with two keys more:
  ``next_validators_hash`` and ``val_txs``, the block's transactions
  that start with ``val:``, as bytes, in block order.

The rules, for block H served with block H + 1 behind it:

1. H continues the accepted chain, and its ``validators_hash`` /
   ``next_validators_hash`` are the hashes of V(H) / V(H + 1), the sets
   THIS replay derived for those heights.
2. The light check of H + 1's LastCommit under V(H)
   (``sync_reference.light_check``): the quorum prefix in V(H)'s order
   and under V(H)'s powers.
3. The full check of H's own LastCommit under V(H - 1)
   (``sync_reference.full_check``).
4. Accepted, H's ``val:`` transactions are executed the way the
   persistent kvstore executes them (abci/example/kvstore/
   persistent_kvstore.go): ``val:<base64 key>!<power>``; power 0 removes
   a validator the application knows and is refused for one it does not;
   any other power adds or updates. The updates that result, in block
   order, are EndBlock's, and act two heights on (state/execution.go
   updateState): V(H + 2) = V(H + 1) with them applied (types/
   validator_set.go UpdateWithChangeSet: no address twice, no removal of
   a non-member, never an empty set), re-sorted. V(1) = V(2) = genesis.

After the last accepted block the node's state is: its height, the
kvstore's app hash (``val:`` transactions add no key), that block's id,
V(H + 1) as ``validators`` (and its hash) and V(H + 2) as
``next_validators``.
"""

from __future__ import annotations

import base64
import binascii
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.lib import reference, sync_reference
from benchmark.lib.sync_reference import VerifyMany

VAL_PREFIX = b"val:"
Row = Tuple[bytes, int, bytes]  # (address, power, key)


def address_of(key: bytes) -> bytes:
    return hashlib.sha256(key).digest()[:20]


def _varint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def simple_validator(key: bytes, power: int) -> bytes:
    """SimpleValidator{pub_key = 1: PublicKey{ed25519 = 1}, voting_power
    = 2} (proto/tendermint/types/validator.proto); a zero is left out."""
    pub = b"\x0a" + _varint(len(key)) + key
    out = b"\x0a" + _varint(len(pub)) + pub
    if power:
        out += b"\x10" + _varint(power)
    return out


def merkle_root(leaves: Sequence[bytes]) -> bytes:
    """RFC 6962: leaf 0x00, inner 0x01, split at the largest power of two
    below the count (crypto/merkle/tree.go)."""
    if not leaves:
        return hashlib.sha256(b"").digest()
    if len(leaves) == 1:
        return hashlib.sha256(b"\x00" + leaves[0]).digest()
    split = 1 << ((len(leaves) - 1).bit_length() - 1)
    return hashlib.sha256(
        b"\x01" + merkle_root(leaves[:split]) + merkle_root(leaves[split:])
    ).digest()


def make_set(rows: Sequence[Row]) -> dict:
    """``rows`` in the set's order, with the set's hash."""
    rows = sorted(rows, key=lambda r: (-r[1], r[0]))
    return {
        "hash": merkle_root([simple_validator(k, p) for _, p, k in rows]),
        "rows": rows,
    }


def parse_val_tx(tx: bytes) -> Optional[Tuple[bytes, int]]:
    """(key, power) of a ``val:<base64 key>!<power>`` transaction, or
    None where the application answers an encoding error."""
    body = tx[len(VAL_PREFIX):]
    if not tx.startswith(VAL_PREFIX) or b"!" not in body:
        return None
    key64, power = body.rsplit(b"!", 1)
    try:
        return base64.b64decode(key64), int(power.decode())
    except (binascii.Error, ValueError, UnicodeDecodeError):
        return None


def end_block_updates(app_keys: set, val_txs: Sequence[bytes]
                      ) -> List[Tuple[bytes, int]]:
    """What the application returns from EndBlock for a block with these
    ``val:`` transactions; ``app_keys``, the keys it knows, moves with
    them."""
    updates = []
    for tx in val_txs:
        parsed = parse_val_tx(tx)
        if parsed is None:
            continue
        key, power = parsed
        if power == 0:
            if key not in app_keys:
                continue  # "Cannot remove non-existent validator"
            app_keys.discard(key)
        else:
            app_keys.add(key)
        updates.append((key, power))
    return updates


def apply_updates(vals: dict, updates: Sequence[Tuple[bytes, int]]) -> dict:
    """UpdateWithChangeSet on a plain set; raises ValueError where
    upstream refuses the change set."""
    if not updates:
        return vals
    by_addr: Dict[bytes, Row] = {r[0]: r for r in vals["rows"]}
    seen = set()
    for key, power in updates:
        addr = address_of(key)
        if addr in seen:
            raise ValueError(f"duplicate entry {addr.hex()} in changes")
        seen.add(addr)
        if power < 0:
            raise ValueError("voting power can't be negative")
        if power == 0:
            if addr not in by_addr:
                raise ValueError(f"failed to find validator {addr.hex()} "
                                 "to remove")
            del by_addr[addr]
        else:
            by_addr[addr] = (addr, power, key)
    if not by_addr:
        raise ValueError("applying the validator changes would result in "
                         "empty set")
    return make_set(list(by_addr.values()))


def check_block(sets: Dict[int, dict], block: dict, nxt: dict, height: int,
                last_id: tuple, verify_many: VerifyMany) -> str:
    """"" or why ``block``, served with ``nxt`` behind it, is refused by
    a node at ``height`` whose last block id is ``last_id`` and whose
    sets by height are ``sets``."""
    h = height + 1
    if block["height"] != h:
        return "not the next height"
    if block["last_block_id"] != last_id:
        return "does not continue the accepted chain"
    if block["validators_hash"] != sets[h]["hash"]:
        return "another validator set"
    if block["next_validators_hash"] != sets[h + 1]["hash"]:
        return "another next validator set"
    why = sync_reference.light_check(sets[h], block, nxt["last_commit"],
                                     verify_many)
    if why:
        return why
    return sync_reference.full_check(sets.get(h - 1, sets[h]), block,
                                     last_id, verify_many)


def replay(genesis: dict, blocks: Sequence[Optional[dict]],
           verify_many: VerifyMany = reference.verify_many) -> Dict:
    """``genesis`` is the genesis set, ``blocks[h]`` the record served
    for height h (index 0 unused), contiguous from 1. → ``{"accepted":
    [bool per served height that has a block behind it], "refused":
    (height, why) or None, "states": {height: state after accepting
    it}, "sets": {height: V(height)}}`` with ``states[0]`` the genesis
    state; a state is ``{"height", "app_hash", "last_block_id",
    "validators_hash", "validators", "next_validators"}``, the last two
    the rows of V(height + 1) and V(height + 2)."""
    genesis = make_set(genesis["rows"])
    sets: Dict[int, dict] = {1: genesis, 2: genesis}
    app_keys = {key for _, _, key in genesis["rows"]}

    def state_at(h: int, app_hash: bytes, last_id: tuple) -> dict:
        return {"height": h, "app_hash": app_hash, "last_block_id": last_id,
                "validators_hash": sets[h + 1]["hash"],
                "validators": sets[h + 1]["rows"],
                "next_validators": sets[h + 2]["rows"]}

    state = state_at(0, b"", sync_reference.ZERO_ID)
    states = {0: state}
    accepted: List[bool] = []
    refused = None
    keys = 0
    for h in range(1, len(blocks) - 1):
        why = check_block(sets, blocks[h], blocks[h + 1], state["height"],
                          state["last_block_id"], verify_many)
        if not why:
            try:
                sets[h + 2] = apply_updates(
                    sets[h + 1],
                    end_block_updates(app_keys, blocks[h]["val_txs"]),
                )
            except ValueError as exc:
                why = f"validator updates refused: {exc}"
        if why:
            refused = (h, why)
            accepted.extend([False] * (len(blocks) - 1 - h))
            break
        accepted.append(True)
        keys += blocks[h]["new_keys"]
        state = state_at(h, sync_reference.app_hash(keys), blocks[h]["id"])
        states[h] = state
    return {"accepted": accepted, "refused": refused, "states": states,
            "sets": sets}
