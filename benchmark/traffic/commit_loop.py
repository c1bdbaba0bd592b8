"""Traffic: full commits through ``ValidatorSet.verify_commit``.

One request is one commit of the configuration's validator set:
``vals.verify_commit(chain_id, block_id, height, commit,
backend=node.crypto_backend)``, sign-bytes, lane build and mask check
included, as block validation calls it. Closed loop, one caller, back to
back, over the configuration's pool of pre-signed commits at successive
heights. A commit with one corrupted signature is verified once in
warm-up and must be refused.

Parameters (the traffic file): ``first_height``, ``corrupt_lane``.
"""

from __future__ import annotations

import copy

from benchmark.lib import data, loops, reference


def build(config: dict, params: dict, seed: int) -> dict:
    chain_id = config["chain_id"]
    vals, privs = data.make_valset(int(config["validators"]), seed, "mega")
    pool = []
    want = []
    for k in range(int(config["pool_commits"])):
        height = int(params["first_height"]) + k
        bid, commit = data.make_commit(vals, privs, height, seed, chain_id)
        pool.append((bid, height, commit))
        want.append(_reference_verdict(vals, commit, chain_id))
    bid, height, commit = pool[0]
    bad = copy.deepcopy(commit)
    lane = int(params["corrupt_lane"]) % len(bad.signatures)
    sig = bad.signatures[lane].signature
    bad.signatures[lane].signature = (
        sig[:7] + bytes([sig[7] ^ 0x10]) + sig[8:]
    )
    return {
        "chain_id": chain_id,
        "valset": vals,
        "pool": pool,
        "want": want,
        "corrupted": (bid, height, bad),
        "corrupted_want": _reference_verdict(vals, bad, chain_id),
        "lanes": len(commit.signatures),
    }


def _reference_verdict(vals, commit, chain_id: str) -> bool:
    """Full-commit semantics: every signature valid (the commits here
    carry every validator's precommit for the block, so the tally is
    whole whenever the signatures are)."""
    return all(reference.verify_many(
        data.raw(data.commit_items(vals, commit, chain_id))
    ))


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
    if plan["corrupted_want"] or _verify(plane, plan, plan["corrupted"]):
        raise AssertionError("the corrupted commit was not refused")
    for k in range(2):
        k %= len(plan["pool"])
        if _verify(plane, plan, plan["pool"][k]) != plan["want"][k]:
            raise AssertionError("a warm-up commit's verdict is wrong")
    return {"corrupted_refused": 1}


def drive(plane, plan: dict, seconds: float) -> dict:
    pool, want = plan["pool"], plan["want"]

    def serve(i: int) -> bool:
        k = i % len(pool)
        return _verify(plane, plan, pool[k]) == want[k]

    out = loops.closed_loop(plane, seconds, plan["lanes"], serve)
    # the request IS the benchmark's span around verify_commit
    out["spans_s"]["verify_commit"] = [
        lat for lat, _, status in out["requests"] if status != "error"
    ]
    return out
