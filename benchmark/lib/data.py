"""Seeded data at operator sizes: validator sets, commits, votes.

The benchmark's own copy of chip_smoke.py's generators (PR 21), kept
here so that a later PR can change the program and the smoke but not the
yardstick. Everything comes from ``seed``; the objects are the program's
own types because they are the inputs the served entry points take.
"""

from __future__ import annotations

import hashlib
from typing import List, Sequence, Tuple


def secret(seed: int, *parts) -> bytes:
    return hashlib.sha256(
        ("/".join(str(p) for p in (seed,) + parts)).encode()
    ).digest()


def make_valset(n: int, seed: int, tag: str):
    """n ed25519 validators (equal power) and their signers, in the
    set's canonical order."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types.priv_validator import MockPV
    from cometbft_tpu.types.validator import Validator
    from cometbft_tpu.types.validator_set import ValidatorSet

    privs = [
        MockPV(ed25519.gen_priv_key_from_secret(secret(seed, tag, i)))
        for i in range(n)
    ]
    vals = ValidatorSet([Validator.new(pv.get_pub_key(), 10) for pv in privs])
    by_addr = {pv.get_pub_key().address(): pv for pv in privs}
    return vals, [by_addr[v.address] for v in vals.validators]


def make_block_id(seed: int, height: int):
    from cometbft_tpu.types import test_util

    return test_util.make_block_id(
        secret(seed, "block", height), 1, secret(seed, "parts", height)
    )


def timestamp(height: int):
    from cometbft_tpu.proto.gogo import Timestamp

    return Timestamp(1_700_000_000 + height, 0)


def make_commit(vals, privs, height: int, seed: int, chain_id: str):
    """Every validator's precommit for a seeded block id. → (bid, commit)"""
    from cometbft_tpu.types import test_util

    bid = make_block_id(seed, height)
    commit = test_util.make_commit(
        bid, height, 0, vals, privs, chain_id, now=timestamp(height)
    )
    return bid, commit


def make_votes(vals, privs, height: int, msg_type: int, seed: int,
               chain_id: str) -> list:
    """One signed vote of ``msg_type`` per validator, in valset order."""
    from cometbft_tpu.types import test_util

    bid = make_block_id(seed, height)
    return [
        test_util.make_vote(
            pv, chain_id, i, height, 0, msg_type, bid, timestamp(height)
        )
        for i, pv in enumerate(privs)
    ]


def commit_items(vals, commit, chain_id: str) -> List[tuple]:
    """(pub_key, sign_bytes, signature) for every validator's precommit."""
    return [
        (
            vals.validators[i].pub_key,
            commit.vote_sign_bytes(chain_id, i),
            cs.signature,
        )
        for i, cs in enumerate(commit.signatures)
    ]


def quorum_prefix_items(vals, commit, chain_id: str) -> List[tuple]:
    """verify_commit_light's lanes for one block: the precommits for the
    block, in valset order, up to and including the one that carries the
    tally past 2/3 — what blocksync/reactor.py builds per window block
    and hands to the scheduler as one request."""
    from cometbft_tpu.types.validator_set import cs_sig

    needed = vals.total_voting_power() * 2 // 3
    items, power = [], 0
    for idx, csig in enumerate(commit.signatures):
        if not csig.for_block():
            continue
        val = vals.validators[idx]
        items.append((
            val.pub_key,
            commit.vote_sign_bytes(chain_id, idx),
            cs_sig(commit, idx),
        ))
        power += val.voting_power
        if power > needed:
            break
    return items


def forge(items: List[tuple], lane: int, seed: int) -> List[tuple]:
    """The same request with lane ``lane`` signed by somebody else: a
    precommit "from" that validator which its key did not sign."""
    from cometbft_tpu.crypto import ed25519

    items = list(items)
    pk, msg, _ = items[lane]
    forger = ed25519.gen_priv_key_from_secret(secret(seed, "forger"))
    items[lane] = (pk, msg, forger.sign(msg))
    return items


def corrupt(items: List[tuple],
            swap_keys: bool = True) -> Tuple[List[tuple], List[int]]:
    """Spoil a handful of NON-ADJACENT lanes, one of each kind two
    verifiers must agree on: flipped signature bits, a wrong-length
    signature, s >= L, a pubkey that is no curve point, a changed
    message. ``swap_keys=False`` leaves every lane its validator's own
    key. → (items, spoiled lanes)."""
    from cometbft_tpu.crypto import ed25519

    from benchmark.lib import reference

    n = len(items)
    items = list(items)
    lanes = sorted({(n * k) // 13 + 1 for k in range(12)} | {0, n - 1})
    lanes = [i for j, i in enumerate(lanes) if j == 0 or i - lanes[j - 1] > 1]
    off_curve = reference.first_off_curve_y().to_bytes(32, "little")
    for j, i in enumerate(lanes):
        pk, msg, sig = items[i]
        kind = j % 5
        if kind == 0:
            sig = sig[:7] + bytes([sig[7] ^ 0x10]) + sig[8:]
        elif kind == 1:
            sig = sig[:63]
        elif kind == 2:
            sig = sig[:32] + (reference.L + 5 + j).to_bytes(32, "little")
        elif kind == 3 and swap_keys:
            pk = ed25519.PubKeyEd25519(off_curve)
        elif kind == 3:
            sig = sig[:40] + bytes([sig[40] ^ 0x02]) + sig[41:]
        else:
            msg = msg + b"!"
        items[i] = (pk, msg, sig)
    return items, lanes


def raw(items: Sequence[tuple]) -> List[Tuple[bytes, bytes, bytes]]:
    """(pub_key bytes, message, signature): what the plain reference
    takes, with nothing of the program's types left in it."""
    return [(pk.bytes(), bytes(m), bytes(s)) for pk, m, s in items]
