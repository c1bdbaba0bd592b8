"""The plain blocksync reference: which blocks of a served chain a
syncing node accepts, and the state it is left in.

``replay`` decides what every pass of the blocksync-apply cell is
compared with. It shares no code with ``cometbft_tpu``'s reactor,
executor or validator set: blocks and the validator set arrive as dicts
and tuples of ints and bytes (``chain.plain_block`` / ``plain_vals``
flatten the program's objects, sign-bytes included, as ``data.raw`` does
for lanes), and signatures are checked by ``reference.verify_many``.

Records:

* the validator set: ``{"hash": bytes, "rows": [(address, power, key)]}``
  in the set's own order; it does not change along the chain;
* a block: ``{"height", "id", "last_block_id", "validators_hash",
  "new_keys", "last_commit": {"height", "block_id", "rows": [(flag,
  signature, sign_bytes)]}}``; an id is ``(hash, parts total, parts
  hash)``; one commit row a validator, in the set's order; ``flag`` is 1
  absent, 2 for the block, 3 nil (types.proto BlockIDFlag);
  ``new_keys`` is how many keys the block's transactions add to the
  kvstore.

The rules, for block H served with block H + 1 behind it
(blockchain/v0/reactor.go:348-404, then state/validation.go:15-120):

1. H continues the accepted chain: its height is the next one, its
   ``last_block_id`` is the id of the block accepted before it, its
   ``validators_hash`` is the set's.
2. The light check: H + 1's LastCommit is for height H and for H's id,
   has a row a validator, and its QUORUM PREFIX verifies: the rows for
   the block, in order, up to and including the one that carries the
   tally past 2/3 of the power. A wrong signature behind that row is
   not looked at here.
3. The full check of H's OWN LastCommit (H > 1): it is for height H - 1
   and the id accepted there, has a row a validator, EVERY row that is
   not absent verifies, and the rows for the block carry more than 2/3.
   The first block's LastCommit is empty.

The first block refused ends the run of accepted ones: a node asks for
that height and the next again and stops the peers that sent them.
After the last accepted block the node's state is: its height, the
kvstore's app hash (the count of keys as a zigzag varint in 8 bytes,
abci/example/kvstore), that block's id and the validators hash.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from benchmark.lib import reference

FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL = 1, 2, 3
ZERO_ID = (b"", 0, b"")

VerifyMany = Callable[[Sequence[Tuple[bytes, bytes, bytes]]], List[bool]]


def app_hash(keys: int) -> bytes:
    """Go's binary.PutVarint(keys) in an 8-byte buffer; before the first
    commit the app has no hash."""
    if keys < 0:
        raise ValueError("negative key count")
    zig = keys << 1
    out = bytearray()
    while zig >= 0x80:
        out.append((zig & 0x7F) | 0x80)
        zig >>= 7
    out.append(zig)
    return bytes(out) + bytes(8 - len(out))


def _needed(vals: dict) -> int:
    return sum(power for _, power, _ in vals["rows"]) * 2 // 3


def _lanes(vals: dict, commit: dict, rows: Sequence[int]) -> list:
    return [(vals["rows"][i][2], commit["rows"][i][2], commit["rows"][i][1])
            for i in rows]


def light_check(vals: dict, block: dict, commit: dict,
                verify_many: VerifyMany) -> str:
    """Rule 2: "" or why ``commit`` does not carry ``block``."""
    if len(commit["rows"]) != len(vals["rows"]):
        return "wrong number of commit signatures"
    if commit["height"] != block["height"]:
        return "wrong commit height"
    if commit["block_id"] != block["id"]:
        return "commit for another block id"
    needed, power, prefix = _needed(vals), 0, []
    for i, (flag, _, _) in enumerate(commit["rows"]):
        if flag != FLAG_COMMIT:
            continue
        prefix.append(i)
        power += vals["rows"][i][1]
        if power > needed:
            break
    good = verify_many(_lanes(vals, commit, prefix))
    for i, ok in zip(prefix, good):
        if not ok:
            return f"wrong signature (#{i}) in the quorum prefix"
    if power <= needed:
        return f"not enough voting power signed: {power} of {needed}"
    return ""


def full_check(vals: dict, block: dict, last_id: tuple,
               verify_many: VerifyMany) -> str:
    """Rule 3: "" or why ``block``'s own LastCommit is refused."""
    commit = block["last_commit"]
    if block["height"] == 1:
        return "" if not commit["rows"] else "first block with a LastCommit"
    if len(commit["rows"]) != len(vals["rows"]):
        return "wrong number of commit signatures"
    if commit["height"] != block["height"] - 1:
        return "wrong commit height"
    if commit["block_id"] != last_id:
        return "LastCommit for another block id"
    present = [i for i, (flag, _, _) in enumerate(commit["rows"])
               if flag != FLAG_ABSENT]
    good = verify_many(_lanes(vals, commit, present))
    for i, ok in zip(present, good):
        if not ok:
            return f"wrong signature (#{i}) in the LastCommit"
    power = sum(vals["rows"][i][1] for i in present
                if commit["rows"][i][0] == FLAG_COMMIT)
    if power <= _needed(vals):
        return "not enough voting power signed the LastCommit"
    return ""


def check_block(vals: dict, block: dict, nxt: dict, height: int,
                last_id: tuple, verify_many: VerifyMany) -> str:
    """"" or why ``block``, served with ``nxt`` behind it, is refused by
    a node at ``height`` whose last block id is ``last_id``."""
    if block["height"] != height + 1:
        return "not the next height"
    if block["last_block_id"] != last_id:
        return "does not continue the accepted chain"
    if block["validators_hash"] != vals["hash"]:
        return "another validator set"
    why = light_check(vals, block, nxt["last_commit"], verify_many)
    if why:
        return why
    return full_check(vals, block, last_id, verify_many)


def replay(vals: dict, blocks: Sequence[Optional[dict]],
           verify_many: VerifyMany = reference.verify_many) -> Dict:
    """``blocks[h]`` is the record served for height h (index 0 unused),
    contiguous from 1. → ``{"accepted": [bool per served height that has
    a block behind it], "refused": (height, why) or None, "states":
    {height: state after accepting it}}`` with ``states[0]`` the genesis
    state; a state is ``{"height", "app_hash", "last_block_id",
    "validators_hash"}``."""
    state = {"height": 0, "app_hash": b"", "last_block_id": ZERO_ID,
             "validators_hash": vals["hash"]}
    states = {0: state}
    accepted: List[bool] = []
    refused = None
    keys = 0
    for h in range(1, len(blocks) - 1):
        why = check_block(vals, blocks[h], blocks[h + 1], state["height"],
                          state["last_block_id"], verify_many)
        if why:
            refused = (h, why)
            accepted.extend([False] * (len(blocks) - 1 - h))
            break
        accepted.append(True)
        keys += blocks[h]["new_keys"]
        state = {"height": h, "app_hash": app_hash(keys),
                 "last_block_id": blocks[h]["id"],
                 "validators_hash": vals["hash"]}
        states[h] = state
    return {"accepted": accepted, "refused": refused, "states": states}
