"""Traffic: full commits of a validator set whose seats hold ed25519 AND
secp256k1 keys, through ``ValidatorSet.verify_commit``.

One request is one commit of the configuration's validator set:
``vals.verify_commit(chain_id, block_id, height, commit,
backend=node.crypto_backend)``, sign-bytes, lane build and mask check
included, as block validation calls it. Closed loop, one caller, back
to back, over the configuration's pool of pre-signed commits at
successive heights (``commit_loop``'s loop).

The set (all from ``--seed``): ``validators`` seats at equal power (10),
``secp256k1_validators`` of them secp256k1 keys, the rest ed25519; the
seats fall where the set's own order (power, then address) puts them,
so the two curves interleave. Every precommit is for the block, signed
over the canonical vote sign-bytes; a secp256k1 signature is made as
v0.34's ``Sign`` makes it: RFC 6979 nonce, SHA-256, low S.

Warm-up verifies three forged commits, each of which the plain
references (``benchmark/lib/reference.py`` for ed25519 lanes,
``secp_reference.py`` for secp256k1 lanes; the commit's verdict is the
conjunction) and the program must refuse: (1) an ed25519 lane with a
flipped bit, (2) a secp256k1 lane with a flipped bit, (3) a secp256k1
lane whose s is replaced by n - s, a valid ECDSA signature that v0.34
refuses (high S). A forged lane is the first seat of its curve at or
after the parameter's index.

The program's book of lanes by curve and by where they ran, and of each
curve's pack seconds (the wire ledger's ``curve_books``), is read at the
window's two edges and carried, as its difference, under
``spans_s["curves"]``; a program without it carries nothing.

Parameters (the traffic file): ``first_height``, ``forged``
{``ed25519_lane``, ``secp256k1_lane``, ``high_s_lane``}.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.lib import data, loops, reference, secp_reference

ED25519, SECP256K1 = "ed25519", "secp256k1"
POWER = 10
# forged kind -> (its parameter, the curve of its lane)
FORGED = {
    "ed25519_flip": ("ed25519_lane", ED25519),
    "secp256k1_flip": ("secp256k1_lane", SECP256K1),
    "secp256k1_high_s": ("high_s_lane", SECP256K1),
}


# --------------------------------------------------------------------------
# the set and its commits


def _secp():
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature,
    )

    return ec, hashes, serialization, decode_dss_signature


def make_mixed_valset(n: int, n_secp: int, seed: int, tag: str):
    """``n`` validators at equal power, ``n_secp`` of them secp256k1.
    → (vals, signers in the set's order: (curve, private key))."""
    from cometbft_tpu.crypto import ed25519, secp256k1
    from cometbft_tpu.types.validator import Validator
    from cometbft_tpu.types.validator_set import ValidatorSet

    ec, _, serialization, _ = _secp()
    signers = []
    for i in range(n - n_secp):
        key = ed25519.gen_priv_key_from_secret(data.secret(seed, tag, i))
        signers.append((ED25519, key, key.pub_key()))
    for i in range(n_secp):
        d = int.from_bytes(data.secret(seed, tag, SECP256K1, i), "big")
        key = ec.derive_private_key(
            d % (secp_reference.N - 1) + 1, ec.SECP256K1()
        )
        pub = key.public_key().public_bytes(
            serialization.Encoding.X962,
            serialization.PublicFormat.CompressedPoint,
        )
        signers.append((SECP256K1, key, secp256k1.PubKeySecp256k1(pub)))
    vals = ValidatorSet([Validator.new(pk, POWER) for _, _, pk in signers])
    by_addr = {pk.address(): (curve, key) for curve, key, pk in signers}
    return vals, [by_addr[v.address] for v in vals.validators]


def _sign(curve: str, key, msg: bytes) -> bytes:
    if curve == ED25519:
        return key.sign(msg)
    ec, hashes, _, decode = _secp()
    r, s = decode(key.sign(
        msg, ec.ECDSA(hashes.SHA256(), deterministic_signing=True)
    ))
    n = secp_reference.N
    if s > n // 2:
        s = n - s
    return r.to_bytes(32, "big") + s.to_bytes(32, "big")


def make_commit(vals, signers, height: int, seed: int, chain_id: str):
    """Every seat's precommit for a seeded block id. → (bid, commit)"""
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT, Commit, CommitSig,
    )

    bid = data.make_block_id(seed, height)
    ts = data.timestamp(height)
    unsigned = Commit(height=height, round=0, block_id=bid, signatures=[
        CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts, b"")
        for v in vals.validators
    ])
    sigs = [
        CommitSig(BLOCK_ID_FLAG_COMMIT, v.address, ts,
                  _sign(curve, key, unsigned.vote_sign_bytes(chain_id, i)))
        for i, (v, (curve, key)) in enumerate(zip(vals.validators, signers))
    ]
    return bid, Commit(height=height, round=0, block_id=bid, signatures=sigs)


class Reference:
    """The commit's verdict by the plain references: every lane by its
    curve's reference, their conjunction. A lane's verdict is a function
    of its bytes and is kept, so a forged commit costs its one new
    lane."""

    def __init__(self):
        self.lanes: Dict[tuple, bool] = {}

    def verdict(self, vals, commit, chain_id: str) -> bool:
        raw = data.raw(data.commit_items(vals, commit, chain_id))
        todo = [r for r in raw if r not in self.lanes]
        for curve, verify in ((ED25519, reference.verify_many),
                              (SECP256K1, secp_reference.verify_many)):
            mine = [r for r in todo if (len(r[0]) == 32) == (curve == ED25519)]
            self.lanes.update(zip(mine, verify(mine)))
        return all(self.lanes[r] for r in raw)


def forged_lane(vals, curve: str, index: int) -> int:
    """The first seat at or after ``index`` (mod the set's size) whose
    key is of ``curve``."""
    n = vals.size()
    for k in range(n):
        seat = (index + k) % n
        if vals.validators[seat].pub_key.type() == curve:
            return seat
    raise ValueError(f"no {curve} seat in the set")


def forge(commit, kind: str, seat: int):
    """``commit`` with the lane of ``seat`` forged as ``kind`` says."""
    import copy

    bad = copy.deepcopy(commit)
    sig = bad.signatures[seat].signature
    if kind == "secp256k1_high_s":
        s = int.from_bytes(sig[32:], "big")
        sig = sig[:32] + (secp_reference.N - s).to_bytes(32, "big")
    else:
        sig = sig[:7] + bytes([sig[7] ^ 0x10]) + sig[8:]
    bad.signatures[seat].signature = sig
    return bad


def build(config: dict, params: dict, seed: int) -> dict:
    chain_id = config["chain_id"]
    vals, signers = make_mixed_valset(
        int(config["validators"]), int(config["secp256k1_validators"]),
        seed, "mixed",
    )
    ref = Reference()
    pool, want = [], []
    for k in range(int(config["pool_commits"])):
        height = int(params["first_height"]) + k
        bid, commit = make_commit(vals, signers, height, seed, chain_id)
        pool.append((bid, height, commit))
        want.append(ref.verdict(vals, commit, chain_id))
    bid, height, commit = pool[0]
    forged = {}
    for kind, (param, curve) in FORGED.items():
        seat = forged_lane(vals, curve, int(params["forged"][param]))
        bad = forge(commit, kind, seat)
        forged[kind] = {
            "entry": (bid, height, bad),
            "seat": seat,
            "want": ref.verdict(vals, bad, chain_id),
        }
    return {
        "chain_id": chain_id,
        "valset": vals,
        "pool": pool,
        "want": want,
        "forged": forged,
        "lanes": len(commit.signatures),
    }


# --------------------------------------------------------------------------
# the loop


def _verify(plane, plan: dict, entry) -> bool:
    """True: accepted. False: refused for a signature or the tally."""
    bid, height, commit = entry
    with plane.span("bench:verify_commit"):
        try:
            plan["valset"].verify_commit(
                plan["chain_id"], bid, height, commit, backend=plane.backend
            )
        except ValueError:
            return False
    return True


def warm(plane, plan: dict) -> dict:
    for kind, case in plan["forged"].items():
        if case["want"]:
            raise AssertionError(f"the reference accepts forged {kind}")
        if _verify(plane, plan, case["entry"]):
            raise AssertionError(f"the forged commit {kind} was not refused")
    for k in range(2):
        k %= len(plan["pool"])
        if _verify(plane, plan, plan["pool"][k]) != plan["want"][k]:
            raise AssertionError("a warm-up commit's verdict is wrong")
    return {"forged_refused": len(plan["forged"])}


def curve_books(plane) -> Optional[dict]:
    """The program's book by curve (wire ledger), or None where the
    program keeps none."""
    ledger = getattr(getattr(plane, "node", None), "wire_ledger", None)
    books = getattr(ledger, "curve_books", None)
    return books() if books is not None else None


def _difference(before: dict, after: dict) -> Dict[str, Dict[str, float]]:
    return {
        curve: {k: v - (before.get(curve) or {}).get(k, 0)
                for k, v in book.items()}
        for curve, book in after.items()
    }


def drive(plane, plan: dict, seconds: float) -> dict:
    pool, want = plan["pool"], plan["want"]

    def serve(i: int) -> bool:
        k = i % len(pool)
        return _verify(plane, plan, pool[k]) == want[k]

    before = curve_books(plane)
    out = loops.closed_loop(plane, seconds, plan["lanes"], serve)
    after = curve_books(plane)
    # the request IS the benchmark's span around verify_commit
    out["spans_s"]["verify_commit"] = [
        lat for lat, _, status in out["requests"] if status != "error"
    ]
    if before is not None and after is not None:
        out["spans_s"]["curves"] = _difference(before, after)
    return out
