"""Traffic: blocksync windows through the node-wide scheduler.

One request is one window: ``blocks`` per-block requests, each the
quorum prefix of that block's commit (verify_commit_light's lanes),
submitted to ``node.crypto_backend.submit(subsystem="blocksync")`` in
one burst and consumed in block order — the shapes
``BlocksyncReactor._submit_window_commits`` and
``_apply_window_pipelined`` produce (tests/benchmark/
test_traffic_shapes.py holds the two to each other lane for lane). The
lanes (sign-bytes included) are built with the plan, outside the timed
request; the cell that times sign-bytes is the commit loop's.

Closed loop, one window in flight (the reactor verifies one window while
it applies it). The timed windows are a healthy chain. One more window with one forged precommit is verified in
warm-up: that block alone must be refused.

Parameters (the traffic file): ``blocks`` per window, ``first_height``,
``forged_block`` and ``forged_lane`` of the warm-up window,
``request_timeout_s``.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.lib import data, loops, reference

SUBSYSTEM = "blocksync"


def window_requests(vals, commits, chain_id: str) -> List[tuple]:
    """[(height, items)]: one scheduler request per block."""
    return [
        (commit.height, data.quorum_prefix_items(vals, commit, chain_id))
        for commit in commits
    ]


def build(config: dict, params: dict, seed: int) -> dict:
    chain_id = config["chain_id"]
    blocks = int(params["blocks"])
    n_windows = max(1, int(config["replay_blocks"]) // blocks)
    vals, privs = data.make_valset(int(config["validators"]), seed, "sync")
    windows = []
    for w in range(n_windows):
        commits = [
            data.make_commit(
                vals, privs, int(params["first_height"]) + w * blocks + b,
                seed, chain_id,
            )[1]
            for b in range(blocks)
        ]
        windows.append(window_requests(vals, commits, chain_id))
    fb, fl = int(params["forged_block"]), int(params["forged_lane"])
    forged = list(windows[0])
    forged[fb] = (forged[fb][0], data.forge(forged[fb][1], fl, seed))
    want = [_reference_verdicts(w) for w in windows]
    return {
        "chain_id": chain_id,
        "valset": vals,
        "windows": windows,
        "want": want,
        "forged": forged,
        "forged_want": _reference_verdicts(forged),
        "lanes_per_block": len(windows[0][0][1]),
        "lanes_per_window": sum(len(items) for _, items in windows[0]),
        "timeout_s": float(params["request_timeout_s"]),
    }


def _reference_verdicts(window: List[tuple]) -> List[bool]:
    """Per block: is every lane of its quorum prefix valid?"""
    lanes = iter(reference.verify_many(
        [lane for _, items in window for lane in data.raw(items)]
    ))
    return [all([next(lanes) for _ in items]) for _, items in window]


def _verify_window(plane, window: List[tuple], timeout_s: float) -> List[bool]:
    with plane.span("bench:submit"):
        futs = [
            plane.backend.submit(items, subsystem=SUBSYSTEM, height=height)
            for height, items in window
        ]
    with plane.span("bench:wait_verdict"):
        return [bool(f.result(timeout=timeout_s)[0]) for f in futs]


def reachable_buckets(lanes_per_block: int, blocks: int, floor: int,
                      cap: int) -> Dict[int, int]:
    """{pow2 bucket: blocks in a burst that lands in it}. The deadline
    flush can close a burst anywhere, so a flush holds k blocks' lanes
    for any k; those at or above the routing floor go to the device,
    padded to the next power of two (at most the chunk cap). One k per
    bucket a window can reach is enough to build its executable."""
    out: Dict[int, int] = {}
    for k in range(1, blocks + 1):
        n = k * lanes_per_block
        if n < floor:
            continue
        bucket = 1 << (n - 1).bit_length()
        if bucket > cap:
            break
        out.setdefault(bucket, k)
    return out


def warm(plane, plan: dict) -> dict:
    """Every executable a window can need, the forged window, then two
    full windows. Raises on any verdict the reference does not give."""
    spec = plane.node.crypto_spec
    window = plan["windows"][0]
    buckets = reachable_buckets(
        plan["lanes_per_block"], len(window), int(spec.min_batch),
        int(spec.max_chunk),
    )
    full = 1 << (plan["lanes_per_window"] - 1).bit_length()
    for bucket, k in sorted(buckets.items()):
        if bucket == full:
            continue  # the full windows below build it
        items = [lane for _, its in window[:k] for lane in its]
        ok, _ = plane.backend.submit(
            items, subsystem=SUBSYSTEM, height=window[0][0]
        ).result(timeout=plan["timeout_s"] * 20)
        if not ok:
            raise AssertionError(f"warm-up burst of {k} blocks refused")
    got = _verify_window(plane, plan["forged"], plan["timeout_s"] * 20)
    if got != plan["forged_want"] or got.count(False) != 1:
        raise AssertionError(
            "the forged window's verdicts differ from the reference: "
            f"refused {[i for i, ok in enumerate(got) if not ok]}"
        )
    for i in range(2):
        got = _verify_window(plane, plan["windows"][i % len(plan["windows"])],
                             plan["timeout_s"] * 20)
        if got != plan["want"][i % len(plan["want"])]:
            raise AssertionError("a warm-up window's verdicts are wrong")
    return {"buckets": sorted(buckets), "forged_refused": 1}


def drive(plane, plan: dict, seconds: float) -> dict:
    windows, want = plan["windows"], plan["want"]

    def serve(i: int) -> bool:
        k = i % len(windows)
        return _verify_window(plane, windows[k], plan["timeout_s"]) == want[k]

    return loops.closed_loop(plane, seconds, plan["lanes_per_window"], serve)
