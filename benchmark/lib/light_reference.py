"""The plain light-client reference: VerifyAdjacent and VerifyNonAdjacent
of CometBFT's ``light/verifier.go`` over plain records.

``verify`` decides what every header verification of the light-fleet
cell is compared with. It shares no code with ``cometbft_tpu``: headers,
validator sets and commits arrive as dicts and tuples of ints and bytes
(the generator flattens the program's objects, as ``data.raw`` does for
lanes), and signatures are checked by ``reference.verify_many``.

Records:

* a validator set: ``{"hash": bytes, "rows": [(address, power, key)]}``
  in the set's own order;
* a light block: ``{"chain_id", "height", "time_ns", "hash" (the
  header's), "validators_hash", "next_validators_hash",
  "commit": {"height", "block_hash", "rows": [(flag, address,
  signature, sign_bytes)]}}``, one commit row a validator of the block's
  set, in that set's order. ``flag`` is 1 absent, 2 for the block, 3 nil
  (types.proto BlockIDFlag).

Semantics, in the order ``verifier.go`` has them: adjacency picks the
variant; an expired trusted header; the new header's own checks (chain,
commit for this header, height and time order, not from the future, its
validators hash is the supplied set's); adjacent: the trusted header's
next-validators hash continues; non-adjacent: more than ``trust_level``
of the TRUSTED set's power signed the new commit, looked up by address,
a second vote from one validator refused, one signature after another
until the level is passed; then more than 2/3 of the new set's power in
commit order, again one signature after another until it is passed. A
signature past the point where a tally is decided is never looked at.

``verify`` returns ``(ACCEPT, "")`` or ``(class, detail)``: the class is
which of light/errors.go's kinds the refusal is, the detail says why.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

from benchmark.lib import reference

ACCEPT = "accept"
EXPIRED = "old_header_expired"          # ErrOldHeaderExpired
INVALID_HEADER = "invalid_header"        # ErrInvalidHeader
CANT_BE_TRUSTED = "cant_be_trusted"      # ErrNewValSetCantBeTrusted
TRUSTING_COMMIT = "trusting_commit_error"  # the trusting check's own error

FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL = 1, 2, 3

Verdict = Tuple[str, str]
VerifyMany = Callable[[Sequence[Tuple[bytes, bytes, bytes]]], List[bool]]


def total_power(vals: dict) -> int:
    return sum(power for _, power, _ in vals["rows"])


def _tally(lanes: List[Tuple[int, int, bytes, bytes, bytes]], needed: int,
           verify_many: VerifyMany) -> Tuple[int, Optional[int]]:
    """``lanes`` are (commit row, power, key, sign_bytes, signature) in
    the order a sequential verifier meets them. → (power tallied when
    the walk stopped, the commit row whose signature was wrong or None).
    The walk stops at the first wrong signature or once the tally passes
    ``needed``; lanes behind that point are not verified."""
    reach, power = [], 0
    for lane in lanes:
        reach.append(lane)
        power += lane[1]
        if power > needed:
            break
    good = verify_many([(key, msg, sig) for _, _, key, msg, sig in reach])
    tallied = 0
    for (row, pw, _, _, _), ok in zip(reach, good):
        if not ok:
            return tallied, row
        tallied += pw
    return tallied, None


def commit_light(vals: dict, block: dict, verify_many: VerifyMany) -> str:
    """VerifyCommitLight: "" or why the commit is refused."""
    commit = block["commit"]
    if len(vals["rows"]) != len(commit["rows"]):
        return "wrong number of commit signatures"
    if commit["height"] != block["height"]:
        return "wrong commit height"
    if commit["block_hash"] != block["hash"]:
        return "wrong block id"
    needed = total_power(vals) * 2 // 3
    lanes = [
        (i, vals["rows"][i][1], vals["rows"][i][2], msg, sig)
        for i, (flag, _, sig, msg) in enumerate(commit["rows"])
        if flag == FLAG_COMMIT
    ]
    tallied, wrong = _tally(lanes, needed, verify_many)
    if wrong is not None:
        return f"wrong signature (#{wrong})"
    if tallied <= needed:
        return f"not enough voting power signed: {tallied} of {needed}"
    return ""


def commit_light_trusting(trusted_vals: dict, block: dict,
                          level: Tuple[int, int],
                          verify_many: VerifyMany) -> Verdict:
    """VerifyCommitLightTrusting → (ACCEPT | CANT_BE_TRUSTED |
    TRUSTING_COMMIT, detail)."""
    num, den = level
    if den == 0:
        return TRUSTING_COMMIT, "trust level has a zero denominator"
    needed = total_power(trusted_vals) * num // den
    by_address: Dict[bytes, Tuple[int, int, bytes]] = {
        address: (i, power, key)
        for i, (address, power, key) in enumerate(trusted_vals["rows"])
    }
    lanes, seen, double = [], {}, None
    for row, (flag, address, sig, msg) in enumerate(block["commit"]["rows"]):
        if flag != FLAG_COMMIT or address not in by_address:
            continue
        idx, power, key = by_address[address]
        if idx in seen:
            double = (seen[idx], row)
            break
        seen[idx] = row
        lanes.append((row, power, key, msg, sig))
    tallied, wrong = _tally(lanes, needed, verify_many)
    if wrong is not None:
        return TRUSTING_COMMIT, f"wrong signature (#{wrong})"
    if tallied > needed:
        return ACCEPT, ""
    if double is not None:
        return TRUSTING_COMMIT, f"double vote ({double[0]} and {double[1]})"
    return CANT_BE_TRUSTED, (
        f"not enough voting power signed: {tallied} of {needed}"
    )


def new_header(trusted: dict, untrusted: dict, untrusted_vals: dict,
               now_ns: int, max_clock_drift_ns: int) -> str:
    """verifyNewHeaderAndVals: "" or why the header is refused."""
    if untrusted["chain_id"] != trusted["chain_id"]:
        return "header belongs to another chain"
    commit = untrusted["commit"]
    if commit["height"] != untrusted["height"]:
        return "header and commit height mismatch"
    if commit["block_hash"] != untrusted["hash"]:
        return "commit signs another block"
    if untrusted["height"] <= trusted["height"]:
        return "new header height not greater than the old one's"
    if untrusted["time_ns"] <= trusted["time_ns"]:
        return "new header time not after the old one's"
    if untrusted["time_ns"] >= now_ns + max_clock_drift_ns:
        return "new header has a time from the future"
    if untrusted["validators_hash"] != untrusted_vals["hash"]:
        return "new header validators are not those supplied"
    return ""


def verify(trusted: dict, trusted_vals: dict, untrusted: dict,
           untrusted_vals: dict, trusting_period_ns: int, now_ns: int,
           max_clock_drift_ns: int, trust_level: Tuple[int, int] = (1, 3),
           verify_many: VerifyMany = reference.verify_many) -> Verdict:
    """light.Verify: adjacent or non-adjacent by the two heights."""
    adjacent = untrusted["height"] == trusted["height"] + 1
    if trusted["time_ns"] + trusting_period_ns <= now_ns:
        return EXPIRED, "old header has expired"
    why = new_header(trusted, untrusted, untrusted_vals, now_ns,
                     max_clock_drift_ns)
    if why:
        return INVALID_HEADER, why
    if adjacent:
        if untrusted["validators_hash"] != trusted["next_validators_hash"]:
            return INVALID_HEADER, "old header next validators do not match"
    else:
        cls, why = commit_light_trusting(trusted_vals, untrusted, trust_level,
                                         verify_many)
        if cls != ACCEPT:
            return cls, why
    why = commit_light(untrusted_vals, untrusted, verify_many)
    if why:
        return INVALID_HEADER, why
    return ACCEPT, ""
