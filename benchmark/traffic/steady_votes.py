"""Traffic: a validator's own load in steady state.

Open loop, one height every ``height_s`` seconds. A producer thread
enqueues, on a seeded schedule, each height's prevotes and then its
precommits (one per validator; each step's votes spread uniformly over
``spread_ms`` from the step's start) and one block-validation event.
ONE consumer thread serves the queue with the discipline of
consensus/state.py ``_receive_routine``: take one message, drain what is
already queued (up to ``ConsensusState.MAX_QUEUE_DRAIN``), verify the
drained votes in one ``crypto/batch.new_batch_verifier(backend,
subsystem="consensus")`` call when there are two or more
(``_batch_preverify_votes``) and a lone vote by the serial
``pub_key.verify_signature``, sign-bytes included; then, for a
block-validation event, one ``ValidatorSet.verify_commit`` of the
height's full commit on the same thread.

One request is one vote, timed from when it was DUE to when its verdict
exists; ``late_s`` records how late the producer enqueued it.

Every vote batch here is under the routing floor, so no lane of the
traffic reaches the device. The chip must still be known alive, and a
traced run must see the device path at least once, so a third thread
runs the supervisor's own canary (``verify_supervisor.probe_now()``: 8
known-good lanes through the supervised device path) every
``health_probe_every_s`` seconds, in every run. It is not a request.

Parameters (the traffic file): ``height_s``, ``prevote_at_ms``,
``precommit_at_ms``, ``commit_at_ms``, ``spread_ms``, ``first_height``,
``drain_grace_s``, ``health_probe_every_s``.
"""

from __future__ import annotations

import dataclasses
import queue
import random
import threading
import time
from typing import List, Tuple

from benchmark.lib import data, loops, reference

SUBSYSTEM = "consensus"
PREVOTE, PRECOMMIT = 1, 2


def build(config: dict, params: dict, seed: int) -> dict:
    chain_id = config["chain_id"]
    vals, privs = data.make_valset(int(config["validators"]), seed, "steady")
    heights = []
    for k in range(int(config["steady_pool_heights"])):
        height = int(params["first_height"]) + k
        bid, commit = data.make_commit(vals, privs, height, seed, chain_id)
        votes = {
            t: data.make_votes(vals, privs, height, t, seed, chain_id)
            for t in (PREVOTE, PRECOMMIT)
        }
        heights.append({"height": height, "bid": bid, "commit": commit,
                        "votes": votes})
    pubs = [v.pub_key for v in vals.validators]
    want_votes = [
        {
            t: reference.verify_many([
                (pubs[v.validator_index].bytes(), v.sign_bytes(chain_id),
                 v.signature) for v in h["votes"][t]
            ]) for t in (PREVOTE, PRECOMMIT)
        } for h in heights
    ]
    want_commit = [
        all(reference.verify_many(
            data.raw(data.commit_items(vals, h["commit"], chain_id))
        )) for h in heights
    ]
    # a prevote "from" validator 3 that its key did not sign, for warm-up
    first = heights[0]["votes"][PREVOTE]
    forged = list(first)
    forged[3] = dataclasses.replace(first[3], signature=data.forge(
        [(pubs[3], first[3].sign_bytes(chain_id), first[3].signature)], 0,
        seed,
    )[0][2])
    return {
        "chain_id": chain_id,
        "valset": vals,
        "pubs": pubs,
        "heights": heights,
        "want_votes": want_votes,
        "want_commit": want_commit,
        "forged_votes": forged,
        "forged_lane": 3,
        "params": dict(params),
        "seed": seed,
    }


def schedule(params: dict, n_validators: int, pool: int, seed: int,
             seconds: float) -> List[Tuple[float, str, int, int, int]]:
    """[(due_s, kind, pool index, vote type, validator)] by due time, a
    pure function of its arguments. ``kind`` is "vote" or "commit"."""
    rng = random.Random(seed)
    height_s = float(params["height_s"])
    spread = float(params["spread_ms"]) / 1e3
    out = []
    k = 0
    while k * height_s < seconds:
        base = k * height_s
        out.append((base + float(params["commit_at_ms"]) / 1e3, "commit",
                    k % pool, 0, 0))
        for vtype, at in ((PREVOTE, params["prevote_at_ms"]),
                          (PRECOMMIT, params["precommit_at_ms"])):
            for i in range(n_validators):
                out.append((base + float(at) / 1e3 + rng.random() * spread,
                            "vote", k % pool, vtype, i))
        k += 1
    out.sort(key=lambda e: (e[0], e[1] != "commit"))
    return out


def max_drain() -> int:
    from cometbft_tpu.consensus.state import ConsensusState

    return int(ConsensusState.MAX_QUEUE_DRAIN)


def verify_votes(plane, plan: dict, votes: list) -> List[bool]:
    """The consumer's verification of one drained batch of votes."""
    from cometbft_tpu.crypto import batch as cryptobatch

    chain_id, pubs = plan["chain_id"], plan["pubs"]
    if len(votes) >= 2:
        with plane.span("bench:vote_batch"):
            bv = cryptobatch.new_batch_verifier(
                plane.backend, subsystem=SUBSYSTEM
            )
            for v in votes:
                bv.add(pubs[v.validator_index], v.sign_bytes(chain_id),
                       v.signature)
            return [bool(ok) for ok in bv.verify()[1]]
    with plane.span("bench:vote_single"):
        return [
            bool(pubs[v.validator_index].verify_signature(
                v.sign_bytes(chain_id), v.signature
            )) for v in votes
        ]


def verify_height_commit(plane, plan: dict, k: int) -> bool:
    h = plan["heights"][k]
    with plane.span("bench:commit150"):
        try:
            plan["valset"].verify_commit(
                plan["chain_id"], h["bid"], h["height"], h["commit"],
                backend=plane.backend,
            )
        except ValueError:
            return False
    return True


def warm(plane, plan: dict) -> dict:
    """Two heights unpaced through the consumer's own calls, and the
    forged prevote, which alone must be refused."""
    got = verify_votes(plane, plan, plan["forged_votes"])
    bad = [i for i, ok in enumerate(got) if not ok]
    if bad != [plan["forged_lane"]]:
        raise AssertionError(f"forged prevote: refused lanes {bad}")
    for k in range(2):
        k %= len(plan["heights"])
        h = plan["heights"][k]
        for t in (PREVOTE, PRECOMMIT):
            if verify_votes(plane, plan, h["votes"][t]) != \
                    plan["want_votes"][k][t]:
                raise AssertionError("a warm-up vote batch is wrong")
            one = verify_votes(plane, plan, h["votes"][t][:1])
            if one != plan["want_votes"][k][t][:1]:
                raise AssertionError("a warm-up single vote is wrong")
        if verify_height_commit(plane, plan, k) != plan["want_commit"][k]:
            raise AssertionError("a warm-up commit is wrong")
    return {"forged_refused": 1}


def drive(plane, plan: dict, seconds: float) -> dict:
    events = schedule(plan["params"], len(plan["pubs"]),
                      len(plan["heights"]), plan["seed"], seconds)
    q: "queue.Queue" = queue.Queue()
    late: List[float] = []
    produced = threading.Event()
    t0 = time.monotonic() + 0.05

    def produce() -> None:
        for ev in events:
            wait = t0 + ev[0] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            q.put(ev)
            if ev[1] == "vote":
                late.append(time.monotonic() - (t0 + ev[0]))
        produced.set()

    probes = {"ok": 0, "failed": 0}
    stop_probing = threading.Event()
    every = float(plan["params"]["health_probe_every_s"])

    def probe() -> None:
        while not stop_probing.wait(every):
            with plane.span("bench:health_probe"):
                ok = plane.node.verify_supervisor.probe_now()
            probes["ok" if ok else "failed"] += 1

    producer = threading.Thread(target=produce, name="bench-producer",
                                daemon=True)
    prober = threading.Thread(target=probe, name="bench-health-probe",
                              daemon=True)
    producer.start()
    if every > 0:
        prober.start()
    limit = max_drain()
    n_vals = len(plan["pubs"])
    requests = []
    commit_spans: List[float] = []
    extra_sigs = 0
    commit_errors = 0
    # one unit of host cost per height: the CPU seconds and the
    # signatures verified between two block validations
    cpu_units: List[Tuple[float, int]] = []
    cpu_mark, sigs_mark = loops.cpu_seconds(), 0
    ok_votes = 0
    attempted = sum(1 for ev in events if ev[1] == "vote")
    give_up = t0 + seconds + float(plan["params"]["drain_grace_s"])
    while time.monotonic() < give_up:
        try:
            first = q.get(timeout=0.01)
        except queue.Empty:
            if produced.is_set() and q.empty():
                break
            continue
        batch = [first]
        while len(batch) < limit:
            try:
                batch.append(q.get_nowait())
            except queue.Empty:
                break
        voted = [ev for ev in batch if ev[1] == "vote"]
        if voted:
            votes = [plan["heights"][k]["votes"][t][i]
                     for _, _, k, t, i in voted]
            marks = plane.fallbacks()
            try:
                got = verify_votes(plane, plan, votes)
            except Exception as exc:  # noqa: BLE001 - failed requests, counted
                plane.note(f"vote batch of {len(votes)} raised {exc!r}")
                got = None
            done = time.monotonic()
            fell_back = plane.fallbacks() != marks
            for j, (due, _, k, t, i) in enumerate(voted):
                if got is None:
                    status = "error"
                elif got[j] != plan["want_votes"][k][t][i]:
                    status = "mismatch"
                elif fell_back:
                    status = "fallback"
                else:
                    status = "ok"
                    ok_votes += 1
                requests.append((done - (t0 + due), 1, status))
        for ev in batch:
            if ev[1] != "commit":
                continue
            cpu_now = loops.cpu_seconds()
            sigs_now = extra_sigs + ok_votes
            if sigs_now > sigs_mark:
                cpu_units.append((cpu_now - cpu_mark, sigs_now - sigs_mark))
            cpu_mark, sigs_mark = cpu_now, sigs_now
            t = time.monotonic()
            if verify_height_commit(plane, plan, ev[2]) == \
                    plan["want_commit"][ev[2]]:
                extra_sigs += n_vals
            else:
                commit_errors += 1
            commit_spans.append(time.monotonic() - t)
        # no plane.tick(): starting or stopping the profiler here would
        # stall the one thread the votes queue behind
    if extra_sigs + ok_votes > sigs_mark:  # the last height
        cpu_units.append((loops.cpu_seconds() - cpu_mark,
                          extra_sigs + ok_votes - sigs_mark))
    producer.join(timeout=5.0)
    stop_probing.set()
    if every > 0:
        prober.join(timeout=30.0)
    window_s = time.monotonic() - t0
    return {
        "loop": "open",
        "window_s": window_s,
        "attempted": attempted,
        "requests": requests,
        "cpu_units": cpu_units,
        "extra_sigs": extra_sigs,
        "mismatches": commit_errors + probes["failed"],
        "health_probes": probes["ok"],
        "spans_s": {"commit150": commit_spans},
        "late_s": late,
    }
